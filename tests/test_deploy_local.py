"""`det deploy local` e2e, including the --tls self-signed bootstrap:
up → verified HTTPS API → down drains over the same TLS channel."""

import json
import os
import ssl
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cli(home, *args, timeout=120):
    env = dict(
        os.environ,
        HOME=str(home),
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    return subprocess.run(
        [sys.executable, "-m", "determined_tpu.cli", *args],
        capture_output=True, text=True, env=env, timeout=timeout)


def test_deploy_local_tls_lifecycle(tmp_path, native_binaries):
    home = tmp_path / "home"
    home.mkdir()
    port = _free_port()
    r = _cli(home, "deploy", "local", "up", "--port", str(port),
             "--agents", "1", "--slots", "1", "--tls")
    try:
        assert r.returncode == 0, r.stdout + r.stderr
        assert "TLS on" in r.stdout, r.stdout
        cert = os.path.join(str(home),
                            ".local/share/determined_tpu/master-cert.pem")
        assert os.path.exists(cert)

        # HTTPS answers when verified against the generated cert...
        ctx = ssl.create_default_context(cafile=cert)
        ctx.check_hostname = False
        with urllib.request.urlopen(f"https://127.0.0.1:{port}/api/v1/master",
                                    timeout=10, context=ctx) as resp:
            assert json.loads(resp.read())["cluster_name"]
        # ...and plaintext is refused.
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/api/v1/master",
                                   timeout=5)
            raise AssertionError("plaintext served on a TLS master")
        except (urllib.error.URLError, ConnectionError, OSError):
            pass

        # The agent (TLS-pinned) registers.
        deadline = time.time() + 30
        while time.time() < deadline:
            req = urllib.request.Request(
                f"https://127.0.0.1:{port}/api/v1/agents",
                headers={"Authorization": "Bearer " + _login(port, ctx)})
            with urllib.request.urlopen(req, timeout=10, context=ctx) as resp:
                agents = json.loads(resp.read())["agents"]
            if any(a["alive"] for a in agents):
                break
            time.sleep(0.5)
        else:
            raise TimeoutError("agent never registered over TLS")
    finally:
        r = _cli(home, "deploy", "local", "down")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "cluster stopped" in r.stdout


def _login(port, ctx):
    from determined_tpu.common.api import salted_hash

    req = urllib.request.Request(
        f"https://127.0.0.1:{port}/api/v1/auth/login",
        data=json.dumps({"username": "determined",
                         "password": salted_hash("determined", "")}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10, context=ctx) as resp:
        return json.loads(resp.read())["token"]
