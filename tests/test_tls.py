"""TLS end-to-end (VERDICT r4 missing #3): master serves HTTPS; agent,
CLI/Session, and spawned trials verify against a pinned self-signed cert;
plaintext and untrusted clients are refused.

Reference: harness/determined/common/api/certs.py (pinned master cert) +
master/agent TLS options.
"""

import os
import ssl
import subprocess
import time
import urllib.error
import urllib.request

import pytest

from tests.test_platform_e2e import Devcluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gen_cert(tmp_path, cn="127.0.0.1"):
    cert = str(tmp_path / f"cert-{cn}.pem")
    key = str(tmp_path / f"key-{cn}.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "5",
         "-subj", f"/CN={cn}", "-addext", f"subjectAltName=IP:{cn}"],
        check=True, capture_output=True)
    return cert, key


@pytest.fixture()
def tls_cluster(tmp_path, native_binaries):
    cert, key = _gen_cert(tmp_path)
    c = Devcluster(str(tmp_path), native_binaries)
    c.master_url = f"https://127.0.0.1:{c.port}"
    c.env["DET_MASTER_CERT_FILE"] = cert
    c.master = subprocess.Popen(
        [os.path.join(c.binaries, "determined-master"),
         "--port", str(c.port), "--host", "127.0.0.1", "--db", c.db_path,
         "--agent-timeout", "15", "--tls-cert", cert, "--tls-key", key],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    ctx = ssl.create_default_context(cafile=cert)
    ctx.check_hostname = False
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            urllib.request.urlopen(c.master_url + "/api/v1/master",
                                   timeout=2, context=ctx)
            break
        except Exception:
            time.sleep(0.3)
    else:
        raise TimeoutError("TLS master did not come up")
    yield c, cert, key
    c.stop()


def _api(cluster, cert, method, path, body=None, token=None):
    """Direct HTTPS call verifying against the pinned cert."""
    import json

    ctx = ssl.create_default_context(cafile=cert)
    ctx.check_hostname = False
    req = urllib.request.Request(
        cluster.master_url + path,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json",
                 **({"Authorization": f"Bearer {token}"} if token else {})},
        method=method)
    with urllib.request.urlopen(req, timeout=30, context=ctx) as r:
        text = r.read().decode()
        return json.loads(text) if text else None


def test_https_end_to_end(tls_cluster, tmp_path):
    """Agent registers over TLS, an experiment runs end to end through the
    CLI (Session verifies via DET_MASTER_CERT_FILE), logs flow."""
    import sys

    cluster, cert, key = tls_cluster
    # Agent dials https and pins the cert.
    cluster.agent = subprocess.Popen(
        [os.path.join(cluster.binaries, "determined-agent"),
         "--master-url", cluster.master_url,
         "--id", "tls-agent", "--slots", "2", "--slot-type", "cpu",
         "--addr", "127.0.0.1",
         "--work-root", os.path.join(cluster.tmpdir, "agent-work"),
         "--token-file", cluster.db_path + ".agent_token",
         "--master-cert-file", cert],
        env=cluster.env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    from determined_tpu.common.api import salted_hash

    deadline = time.time() + 30
    while time.time() < deadline:
        token = _api(cluster, cert, "POST", "/api/v1/auth/login",
                     {"username": "determined",
                      "password": salted_hash("determined", "")})["token"]
        agents = _api(cluster, cert, "GET", "/api/v1/agents",
                      token=token)["agents"]
        if any(a["id"] == "tls-agent" and a["alive"] for a in agents):
            break
        time.sleep(0.3)
    else:
        raise TimeoutError("agent never registered over TLS")

    # Full experiment through the real CLI: Session speaks https with the
    # pinned CA from DET_MASTER_CERT_FILE.
    import yaml

    cfg = {
        "name": "tls-e2e",
        "entrypoint": "python3 train.py",
        "searcher": {"name": "single", "metric": "val_loss",
                     "max_length": {"batches": 4}},
        "hyperparameters": {"lr": 0.5},
        "checkpoint_storage": {
            "type": "shared_fs",
            "host_path": os.path.join(str(tmp_path), "ckpts")},
        "resources": {"slots_per_trial": 1},
    }
    cfg_path = os.path.join(str(tmp_path), "exp.yaml")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(cfg, f)
    env = dict(cluster.env, HOME=cluster.tmpdir)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "determined_tpu.cli",
         "-m", cluster.master_url, "experiment", "create", cfg_path,
         os.path.join(REPO, "tests", "fixtures", "platform"), "--follow"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]


def test_plaintext_refused_when_tls_on(tls_cluster):
    """An http:// client on the TLS port gets a transport failure, never a
    successful API answer."""
    cluster, cert, key = tls_cluster
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(
            f"http://127.0.0.1:{cluster.port}/api/v1/master", timeout=5)


def test_untrusted_cert_rejected(tls_cluster, tmp_path):
    """A client pinning a DIFFERENT CA must refuse the master's cert —
    and the Session must not burn retries on it."""
    cluster, cert, key = tls_cluster
    other_cert, _ = _gen_cert(tmp_path, cn="10.9.9.9")
    ctx = ssl.create_default_context(cafile=other_cert)
    ctx.check_hostname = False
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(cluster.master_url + "/api/v1/master",
                               timeout=5, context=ctx)

    from determined_tpu.common.api import Session

    os.environ["DET_MASTER_CERT_FILE"] = other_cert
    try:
        t0 = time.time()
        with pytest.raises(ssl.SSLCertVerificationError):
            Session(cluster.master_url).get("/api/v1/master")
        assert time.time() - t0 < 10, "verification failure must not retry"
    finally:
        os.environ.pop("DET_MASTER_CERT_FILE", None)
