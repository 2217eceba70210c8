"""NTSC proxy e2e (reference internal/proxy/proxy.go + tcp.go): the master
forwards /proxy/{task_id}/... to the task's registered proxy address."""

import textwrap
import time
import urllib.error
import urllib.request

import pytest

from tests.test_platform_e2e import Devcluster


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    # Teardown kills the task process groups the SIGKILLed agent can no
    # longer reap (VERDICT item 6: the spawned proxy/ws/shell servers used
    # to outlive the suite) — and proves it left nothing behind.
    c.stop()
    assert c.find_orphans() == [], (
        "devcluster teardown leaked task processes")


SERVER = textwrap.dedent("""
    import http.server, threading, sys
    from determined_tpu.exec._util import report_proxy_address

    class H(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass
        def do_GET(self):
            if self.path.startswith("/hello"):
                body = f"hi from task: {self.path}".encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path == "/jump":
                self.send_response(302)
                self.send_header("Location", "/hello-after-jump")
                self.end_headers()
            else:
                self.send_response(404)
                self.end_headers()
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = b"echo:" + self.rfile.read(n)
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    report_proxy_address(f"http://127.0.0.1:{srv.server_address[1]}")
    print("serving", srv.server_address[1])
    sys.stdout.flush()
    srv.serve_forever()
""")


def test_proxy_forwards_to_task(cluster, tmp_path):
    token = cluster.login()
    script = tmp_path / "srv.py"
    script.write_text(SERVER)
    task = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint": f"python3 {script}"}}, token=token)
    tid = task["id"]

    # wait for the proxy address to register
    deadline = time.time() + 30
    addr = None
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid}", token=token)["task"]
        addr = t.get("proxy_address")
        if addr:
            break
        time.sleep(0.3)
    assert addr, "task never registered a proxy address"

    def proxied(method, path, data=None):
        req = urllib.request.Request(
            cluster.master_url + f"/proxy/{tid}{path}",
            data=data, method=method,
            headers={"Authorization": f"Bearer {token}"})
        return urllib.request.urlopen(req, timeout=20)

    # GET with query string
    with proxied("GET", "/hello?x=1") as r:
        assert r.headers.get_content_type() == "text/plain"
        body = r.read().decode()
    assert body.startswith("hi from task: /hello")
    assert "x=1" in body

    # POST body round-trips
    with proxied("POST", "/hello-post") as r:
        pass  # 404 from server is fine — exercise POST on /hello instead
    with proxied("POST", "/hello", data=b"payload-bytes") as r:
        assert r.read() == b"echo:payload-bytes"

    # origin-relative redirects are rewritten into the proxy prefix
    class NoRedirect(urllib.request.HTTPRedirectHandler):
        def redirect_request(self, *a, **k):
            return None

    opener = urllib.request.build_opener(NoRedirect)
    req = urllib.request.Request(
        cluster.master_url + f"/proxy/{tid}/jump",
        headers={"Authorization": f"Bearer {token}"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        opener.open(req, timeout=20)
    assert ei.value.code == 302
    assert ei.value.headers["Location"] == f"/proxy/{tid}/hello-after-jump"

    # unauthenticated proxying rejected; unknown task 502
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            cluster.master_url + f"/proxy/{tid}/hello", timeout=10)
    assert ei.value.code == 401
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            urllib.request.Request(
                cluster.master_url + "/proxy/no-such-task/x",
                headers={"Authorization": f"Bearer {token}"}), timeout=10)
    assert ei.value.code == 404

    # non-owner cannot tunnel into the task (it executes as the owner)
    admin = cluster.login("admin")
    cluster.api("POST", "/api/v1/users",
                {"username": "proxy-bob", "role": "user"}, token=admin)
    bob = cluster.login("proxy-bob")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            urllib.request.Request(
                cluster.master_url + f"/proxy/{tid}/hello",
                headers={"Authorization": f"Bearer {bob}"}), timeout=10)
    assert ei.value.code == 403

    cluster.api("POST", f"/api/v1/commands/{tid}/kill", token=token)


# Minimal RFC6455 server fixture: handshake + unmasked echo of masked
# client text frames. Enough to prove the master splices the upgrade +
# bidirectional frames (reference proxy/ws.go).
WS_SERVER = textwrap.dedent("""
    import base64, hashlib, socket, sys, threading
    from determined_tpu.exec._util import report_proxy_address

    MAGIC = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"

    def handle(conn):
        buf = b""
        while b"\\r\\n\\r\\n" not in buf:
            d = conn.recv(4096)
            if not d:
                return
            buf += d
        head, rest = buf.split(b"\\r\\n\\r\\n", 1)
        key = ""
        for line in head.decode().split("\\r\\n"):
            if line.lower().startswith("sec-websocket-key:"):
                key = line.split(":", 1)[1].strip()
        accept = base64.b64encode(
            hashlib.sha1((key + MAGIC).encode()).digest()).decode()
        conn.sendall((
            "HTTP/1.1 101 Switching Protocols\\r\\n"
            "Upgrade: websocket\\r\\nConnection: Upgrade\\r\\n"
            f"Sec-WebSocket-Accept: {accept}\\r\\n\\r\\n").encode())
        data = rest
        while True:
            while len(data) < 6:
                d = conn.recv(4096)
                if not d:
                    return
                data += d
            ln = data[1] & 0x7F
            need = 6 + ln
            while len(data) < need:
                data += conn.recv(4096)
            mask = data[2:6]
            payload = bytes(b ^ mask[i % 4]
                            for i, b in enumerate(data[6:need]))
            data = data[need:]
            out = bytes([0x81, len(payload)]) + payload
            conn.sendall(out)

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    report_proxy_address(f"http://127.0.0.1:{srv.getsockname()[1]}")
    print("ws serving", srv.getsockname()[1]); sys.stdout.flush()
    while True:
        c, _ = srv.accept()
        threading.Thread(target=handle, args=(c,), daemon=True).start()
""")


def _wait_proxy_addr(cluster, token, kind, tid, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/{kind}/{tid}", token=token)["task"]
        if t.get("proxy_address"):
            return t["proxy_address"]
        time.sleep(0.3)
    raise TimeoutError("task never registered a proxy address")


def test_websocket_proxy_echo(cluster, tmp_path):
    """WS upgrade through /proxy/{task}/: handshake forwarded upstream,
    frames pumped both ways (reference proxy/ws.go)."""
    import base64
    import hashlib
    import socket

    token = cluster.login()
    script = tmp_path / "ws.py"
    script.write_text(WS_SERVER)
    tid = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint": f"python3 {script}"}}, token=token)["id"]
    _wait_proxy_addr(cluster, token, "commands", tid)

    host, port = "127.0.0.1", cluster.port
    s = socket.create_connection((host, port), timeout=20)
    key = base64.b64encode(b"0123456789abcdef").decode()
    s.sendall((
        f"GET /proxy/{tid}/ HTTP/1.1\r\nHost: {host}\r\n"
        f"Authorization: Bearer {token}\r\n"
        "Connection: Upgrade\r\nUpgrade: websocket\r\n"
        f"Sec-WebSocket-Key: {key}\r\nSec-WebSocket-Version: 13\r\n\r\n"
    ).encode())
    buf = b""
    while b"\r\n\r\n" not in buf:
        d = s.recv(4096)
        assert d, f"closed during handshake: {buf!r}"
        buf += d
    head, rest = buf.split(b"\r\n\r\n", 1)
    assert b"101" in head.split(b"\r\n", 1)[0], head
    magic = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"
    want_accept = base64.b64encode(
        hashlib.sha1((key + magic).encode()).digest()).decode()
    assert want_accept.encode() in head, head

    # two masked text frames round-trip through the tunnel
    for msg in (b"hello-ws", b"second-message"):
        mask = b"\x01\x02\x03\x04"
        masked = bytes(b ^ mask[i % 4] for i, b in enumerate(msg))
        s.sendall(bytes([0x81, 0x80 | len(msg)]) + mask + masked)
        want = bytes([0x81, len(msg)]) + msg
        got = rest
        rest = b""
        while len(got) < len(want):
            d = s.recv(4096)
            assert d, "tunnel closed mid-frame"
            got += d
        assert got == want, (got, want)
    s.close()
    cluster.api("POST", f"/api/v1/commands/{tid}/kill", token=token)


def test_shell_round_trip(cluster, tmp_path):
    """`det shell run`: start a shell task, run a command through the
    det-tcp tunnel (reference: ssh over proxy/tcp.go; here exec/shell.py),
    driven through the real CLI as a subprocess."""
    import os
    import subprocess
    import sys

    token = cluster.login()
    tid = cluster.api("POST", "/api/v1/shells", {"config": {}},
                      token=token)["id"]
    _wait_proxy_addr(cluster, token, "shells", tid, timeout=60)

    env = dict(cluster.env, HOME=str(tmp_path))  # isolate the token cache
    r = subprocess.run(
        [sys.executable, "-m", "determined_tpu.cli",
         "-m", cluster.master_url, "shell", "run", tid,
         "echo tunnel-says-$((20+3))"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    assert "tunnel-says-23" in r.stdout, (r.stdout, r.stderr)

    # Direct connection WITHOUT the per-task secret (ADVICE r4 high): the
    # shell binds 0.0.0.0, so anyone with network reach could otherwise run
    # commands as the owner. A connection that doesn't lead with
    # DET_PROXY_SECRET must be dropped with no shell spawned.
    import socket as socketmod

    addr = _wait_proxy_addr(cluster, token, "shells", tid)
    hostport = addr.split("://", 1)[1]
    host, port = hostport.rsplit(":", 1)
    s = socketmod.create_connection((host, int(port)), timeout=10)
    s.sendall(b"wrong-secret\necho direct-pwned-$((40+2))\n")
    s.shutdown(socketmod.SHUT_WR)
    got = b""
    s.settimeout(10)
    try:
        while True:
            d = s.recv(4096)
            if not d:
                break
            got += d
    except OSError:
        pass
    s.close()
    assert b"direct-pwned-42" not in got, got
    cluster.api("POST", f"/api/v1/shells/{tid}/kill", token=token)
