"""Webhook shipping on experiment state change (reference
internal/webhooks/shipper.go): registered URLs get the event POST,
filtered by each webhook's triggers."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from tests.test_platform_e2e import (
    Devcluster,
    _create_experiment,
    _experiment_config,
    _wait_experiment,
)


class Sink:
    def __init__(self):
        self.events = []
        outer = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                outer.events.append(
                    (self.path, json.loads(self.rfile.read(n) or b"{}")))
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.url = f"http://127.0.0.1:{self.srv.server_address[1]}"
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()

    def stop(self):
        self.srv.shutdown()


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def test_webhooks_fire_filtered_by_triggers(cluster, tmp_path):
    sink = Sink()
    try:
        admin = cluster.login("admin")
        # all states; COMPLETED-only; ERROR-only (must stay silent)
        cluster.api("POST", "/api/v1/webhooks",
                    {"url": sink.url + "/all"}, token=admin)
        cluster.api("POST", "/api/v1/webhooks",
                    {"url": sink.url + "/done", "triggers": ["COMPLETED"]},
                    token=admin)
        cluster.api("POST", "/api/v1/webhooks",
                    {"url": sink.url + "/err", "triggers": ["ERROR"]},
                    token=admin)

        eid, token = _create_experiment(cluster, _experiment_config(tmp_path))
        _wait_experiment(cluster, eid, token)

        deadline = time.time() + 20
        while time.time() < deadline and len(sink.events) < 2:
            time.sleep(0.2)
        paths = sorted(p for p, _ in sink.events)
        assert paths == ["/all", "/done"], sink.events
        for _, ev in sink.events:
            assert ev["type"] == "EXPERIMENT_STATE_CHANGE"
            assert ev["experiment_id"] == eid
            assert ev["state"] == "COMPLETED"
    finally:
        sink.stop()
