"""GCP provisioner against a fake TPU API: the full node lifecycle
(VERDICT r4 missing #2 / weak #7).

Reference: rm/agentrm/provisioner/aws/aws_spot.go creates and terminates
cloud instances itself and tolerates spot interruption; scaledecider
terminates idle instances. Here the executor speaks the TPU-VM REST shape
(tpu.googleapis.com v2: nodes create/list/delete) against a fake server,
while REAL agents play the booted VMs: the test starts an agent named
after each created node, so the scheduler path runs for real end to end.
"""

import json
import os
import subprocess
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from tests.test_platform_e2e import (
    Devcluster,
    _create_experiment,
    _experiment_config,
    _wait_experiment,
    _wait_http,
)


class FakeTpuApi:
    """tpu.googleapis.com-shaped fake: nodes create/list/delete."""

    def __init__(self):
        self.nodes = {}   # name -> {"state": ..., "body": ...}
        self.creates = []
        self.deletes = []
        self.fail_creates = False  # 500 every create (failure-storm tests)
        self.failed_creates = []
        self.lock = threading.Lock()
        outer = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _json(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if "/nodes" in self.path and "nodeId=" in self.path:
                    name = self.path.split("nodeId=")[1].split("&")[0]
                    with outer.lock:
                        if outer.fail_creates:
                            outer.failed_creates.append(name)
                            return self._json(
                                500, {"error": "quota exceeded (fake)"})
                        outer.nodes[name] = {"state": "READY", "body": body}
                        outer.creates.append({"name": name, **body})
                    return self._json(200, {"name": name})
                self._json(404, {})

            def do_GET(self):
                if self.path.endswith("/nodes"):
                    with outer.lock:
                        items = [
                            {"name": f"projects/p/locations/z/nodes/{n}",
                             "state": v["state"]}
                            for n, v in outer.nodes.items()
                        ]
                    return self._json(200, {"nodes": items})
                self._json(404, {})

            def do_DELETE(self):
                name = self.path.rsplit("/", 1)[-1]
                with outer.lock:
                    outer.deletes.append(name)
                    outer.nodes.pop(name, None)
                self._json(200, {})

        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.url = f"http://127.0.0.1:{self.srv.server_address[1]}"
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()

    def interrupt(self, name):
        """Spot interruption: the node vanishes out-of-band."""
        with self.lock:
            self.nodes.pop(name, None)

    def node_names(self):
        with self.lock:
            return sorted(self.nodes)

    def stop(self):
        self.srv.shutdown()


def _scrape_metrics(cluster, token):
    """GET /metrics → {series_name_with_labels: float}."""
    import urllib.request

    req = urllib.request.Request(
        cluster.master_url + "/metrics",
        headers={"Authorization": f"Bearer {token}"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name, value = line.rsplit(" ", 1)
        try:
            out[name] = float(value)
        except ValueError:
            pass
    return out


def _wait(cond, timeout=45, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {what}")


@pytest.fixture()
def prov_cluster(tmp_path, native_binaries):
    fake = FakeTpuApi()
    cfg = {
        # Must exceed the agent's 10s heartbeat period or live agents flap
        # dead between heartbeats.
        "agent_timeout_s": 15,
        "provisioner": {
            "type": "gcp",
            "api_base": fake.url + "/v2",
            "project": "p",
            "zone": "z",
            "accelerator_type": "v5litepod-4",
            "slots_per_node": 2,
            "sustain_seconds": 0.5,
            "cooldown_seconds": 1.5,
            "idle_seconds": 2,
            "reconcile_seconds": 0.3,
            "spot": True,
        },
    }
    cfg_path = tmp_path / "master.json"
    cfg_path.write_text(json.dumps(cfg))
    c = Devcluster(str(tmp_path), native_binaries)
    c.master = subprocess.Popen(
        [os.path.join(c.binaries, "determined-master"),
         "--config", str(cfg_path),
         "--port", str(c.port), "--host", "127.0.0.1", "--db", c.db_path],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    _wait_http(c.master_url + "/api/v1/master")
    agents = []

    def boot_vm(name):
        """Play the role of the created TPU-VM: a real agent whose id is
        the node name (real deploys wire this via instance metadata)."""
        p = subprocess.Popen(
            [os.path.join(c.binaries, "determined-agent"),
             "--master-url", c.master_url,
             "--id", name,
             "--slots", "2",
             "--slot-type", "cpu",
             "--addr", "127.0.0.1",
             "--work-root", os.path.join(c.tmpdir, f"agent-{name}"),
             "--token-file", c.db_path + ".agent_token"],
            env=c.env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        agents.append(p)
        return p

    yield c, fake, boot_vm
    for p in agents:
        if p.poll() is None:
            p.kill()
            p.wait()
    c.stop()
    fake.stop()


def test_up_use_idle_down_lifecycle(prov_cluster, tmp_path):
    cluster, fake, boot_vm = prov_cluster
    token = cluster.login()

    # 1. Demand with zero capacity: a 2-slot command queues.
    cluster.api("POST", "/api/v1/commands",
                {"config": {"entrypoint": "echo provisioned-ran-ok",
                            "resources": {"slots": 2}}}, token=token)

    # 2. UP: the provisioner creates a node through the TPU API.
    _wait(lambda: fake.creates[:] or None, what="node create")
    create = fake.creates[0]
    assert create["acceleratorType"] == "v5litepod-4"
    assert create["schedulingConfig"]["preemptible"] is True
    assert create["labels"]["det-pool"] == "default"
    name = create["name"]
    assert name.startswith("det-prov-default-")

    # Fire-once accounting: while the node "boots" (no agent yet), demand
    # persists past the cooldown but launched capacity must be counted —
    # no second node.
    time.sleep(3.5)
    assert len(fake.creates) == 1, fake.creates

    # 3. USE: the VM boots (real agent registers); the task runs on it.
    boot_vm(name)
    tasks = _wait(
        lambda: [t for t in cluster.api("GET", "/api/v1/tasks",
                                        token=token)["tasks"]
                 if t["state"] == "COMPLETED"] or None,
        what="task completed on provisioned node")
    logs = cluster.api("GET", f"/api/v1/tasks/{tasks[0]['id']}/logs",
                       token=token)["logs"]
    assert any("provisioned-ran-ok" in line["log"] for line in logs)

    # 4. DOWN: with the queue empty the node idles past idle_seconds and
    # the provisioner deletes it through the API.
    _wait(lambda: name in fake.deletes or None, what="idle scale-down")
    assert fake.node_names() == []


def test_spot_interruption_fails_over(prov_cluster, tmp_path):
    cluster, fake, boot_vm = prov_cluster

    # Slow trial so the interruption lands mid-run; max_restarts gives the
    # failover budget.
    cfg = _experiment_config(
        tmp_path,
        extra={
            "resources": {"slots_per_trial": 2},
            "max_restarts": 2,
            "environment": {
                "environment_variables": ["TRIAL_STEP_SLEEP=0.6"]},
        },
    )
    eid, token = _create_experiment(cluster, cfg, activate=True)

    _wait(lambda: fake.creates[:] or None, what="node create")
    name0 = fake.creates[0]["name"]
    agent0 = boot_vm(name0)

    def trial_running():
        trials = cluster.api(
            "GET", f"/api/v1/experiments/{eid}/trials", token=token)["trials"]
        # progress proves the trial is actually training on the node
        return any(t.get("total_batches", 0) > 0 and t["state"] == "ACTIVE"
                   for t in trials) or None

    _wait(trial_running, what="trial running on provisioned node")

    # Spot interruption: the node vanishes AND its agent dies.
    fake.interrupt(name0)
    agent0.kill()
    agent0.wait()

    # The master sweeps the dead agent, the trial goes back to pending,
    # and the provisioner launches a replacement node.
    _wait(lambda: len(fake.creates) >= 2 or None, timeout=60,
          what="replacement node create")
    name1 = fake.creates[-1]["name"]
    assert name1 != name0
    boot_vm(name1)

    _wait_experiment(cluster, eid, token, timeout=180)
    trials = cluster.api(
        "GET", f"/api/v1/experiments/{eid}/trials", token=token)["trials"]
    assert trials[0]["restarts"] >= 1

    # Vanished-node postconditions (the ghost must be fully reaped):
    # the dead agent is swept (not alive), its node is gone from the
    # provisioner's tracking, and demand accounting never double-counted
    # the ghost — exactly ONE replacement node was created for the one
    # lost, even though the dead node + requeued trial coexisted for a
    # while.
    agents = {a["id"]: a for a in
              cluster.api("GET", "/api/v1/agents", token=token)["agents"]}
    assert not agents[name0]["alive"], agents[name0]
    assert len(fake.creates) == 2, [c["name"] for c in fake.creates]
    metrics = _scrape_metrics(cluster, token)
    # All demand drained once the trial finished (held demand decays
    # within demand_hysteresis_seconds).
    _wait(lambda: all(
        v == 0 for k, v in _scrape_metrics(cluster, token).items()
        if k.startswith("det_provisioner_demand_slots")) or None,
        timeout=20, what="demand gauges drained")
    assert "det_provisioner_create_failures_total" in metrics


def test_never_joined_node_cleaned_up_and_capacity_refired(
        tmp_path, native_binaries):
    """A created node whose agent never registers must stop suppressing
    scale-up after boot_grace_seconds and be deleted as broken — not
    starve the queue forever."""
    fake = FakeTpuApi()
    cfg = {
        "agent_timeout_s": 15,
        "provisioner": {
            "type": "gcp",
            "api_base": fake.url + "/v2",
            "project": "p", "zone": "z",
            "slots_per_node": 2,
            "sustain_seconds": 0.5,
            "cooldown_seconds": 1,
            "boot_grace_seconds": 3,
            "reconcile_seconds": 0.3,
        },
    }
    cfg_path = tmp_path / "master.json"
    cfg_path.write_text(json.dumps(cfg))
    c = Devcluster(str(tmp_path), native_binaries)
    c.master = subprocess.Popen(
        [os.path.join(c.binaries, "determined-master"),
         "--config", str(cfg_path),
         "--port", str(c.port), "--host", "127.0.0.1", "--db", c.db_path],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    _wait_http(c.master_url + "/api/v1/master")
    try:
        token = c.login()
        c.api("POST", "/api/v1/commands",
              {"config": {"entrypoint": "echo hi",
                          "resources": {"slots": 2}}}, token=token)
        _wait(lambda: fake.creates[:] or None, what="first create")
        name0 = fake.creates[0]["name"]
        # No agent ever boots: past boot grace the node is deleted and a
        # replacement is launched for the still-pending demand.
        _wait(lambda: name0 in fake.deletes or None, timeout=30,
              what="never-joined node deleted")
        _wait(lambda: len(fake.creates) >= 2 or None, timeout=30,
              what="replacement create after cleanup")
    finally:
        c.stop()
        fake.stop()


def _prov_master(tmp_path, native_binaries, fake, prov_extra=None):
    """Master-only cluster against the fake TPU API (no pre-booted
    agents — the test plays the VMs)."""
    cfg = {
        "agent_timeout_s": 15,
        "provisioner": {
            "type": "gcp",
            "api_base": fake.url + "/v2",
            "project": "p", "zone": "z",
            "slots_per_node": 2,
            "sustain_seconds": 0.3,
            "cooldown_seconds": 0.5,
            "idle_seconds": 2,
            "reconcile_seconds": 0.3,
            "demand_hysteresis_seconds": 1,
            **(prov_extra or {}),
        },
    }
    cfg_path = tmp_path / "master.json"
    cfg_path.write_text(json.dumps(cfg))
    c = Devcluster(str(tmp_path), native_binaries)
    c.master = subprocess.Popen(
        [os.path.join(c.binaries, "determined-master"),
         "--config", str(cfg_path),
         "--port", str(c.port), "--host", "127.0.0.1", "--db", c.db_path],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    _wait_http(c.master_url + "/api/v1/master")
    return c


def test_create_failure_storm_backs_off_and_recovers(
        tmp_path, native_binaries):
    """A 100%-node-create-failure storm must NOT busy-loop: attempts space
    out on the capped exponential backoff (base * 2^(n-1)), the failure
    counter climbs, and clearing the storm recovers — the next attempt
    creates a node and the queued work runs on it."""
    fake = FakeTpuApi()
    fake.fail_creates = True
    c = _prov_master(tmp_path, native_binaries, fake, {
        "create_backoff_base_seconds": 0.6,
        "create_backoff_max_seconds": 3,
    })
    agents = []
    try:
        token = c.login()
        c.api("POST", "/api/v1/commands",
              {"config": {"entrypoint": "echo recovered-ok",
                          "resources": {"slots": 2}}}, token=token)
        _wait(lambda: len(fake.failed_creates) >= 2 or None, timeout=20,
              what="two failed create attempts")
        # Bounded retry rate: with backoff 0.6 -> 1.2 -> 2.4 -> 3 (cap)
        # a 3.5s window sees ~3 attempts; a busy-loop at the 0.5s
        # cooldown would see ~7.
        t0 = time.time()
        base = len(fake.failed_creates)
        time.sleep(3.5)
        attempts = len(fake.failed_creates) - base
        assert attempts <= 4, (
            f"{attempts} create attempts in {time.time() - t0:.1f}s — "
            "backoff is not holding")
        metrics = _scrape_metrics(c, token)
        assert metrics.get("det_provisioner_create_failures_total", 0) >= 2
        # Storm clears: the next (backed-off) attempt succeeds, the VM
        # "boots", and the queued command completes on it.
        fake.fail_creates = False
        created = _wait(lambda: fake.creates[:] or None, timeout=30,
                        what="create after storm cleared")
        name = created[0]["name"]
        agents.append(subprocess.Popen(
            [os.path.join(c.binaries, "determined-agent"),
             "--master-url", c.master_url, "--id", name,
             "--slots", "2", "--slot-type", "cpu", "--addr", "127.0.0.1",
             "--work-root", os.path.join(c.tmpdir, f"agent-{name}"),
             "--token-file", c.db_path + ".agent_token"],
            env=c.env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        _wait(lambda: [t for t in c.api("GET", "/api/v1/tasks",
                                        token=token)["tasks"]
                       if t["state"] == "COMPLETED"] or None,
              timeout=60, what="task completed after recovery")
    finally:
        for p in agents:
            if p.poll() is None:
                p.kill()
                p.wait()
        c.stop()
        fake.stop()


def test_create_fault_point_runtime_armed(tmp_path, native_binaries):
    """`provisioner.create.fail` (DET_FAULTS / debug API): armed with a
    count, it eats exactly that many create attempts inside the master —
    the fake API never sees them — then auto-disarms and the pool
    recovers."""
    fake = FakeTpuApi()
    c = _prov_master(tmp_path, native_binaries, fake, {
        "create_backoff_base_seconds": 0.3,
        "create_backoff_max_seconds": 1,
    })
    agents = []
    try:
        admin = c.login("admin")
        c.api("POST", "/api/v1/debug/faults",
              {"point": "provisioner.create.fail", "mode": "error",
               "count": 2}, token=admin)
        token = c.login()
        c.api("POST", "/api/v1/commands",
              {"config": {"entrypoint": "echo fault-cleared",
                          "resources": {"slots": 2}}}, token=token)
        # Both injected failures burn without any API traffic...
        _wait(lambda: _scrape_metrics(c, token).get(
            "det_provisioner_create_failures_total", 0) >= 2 or None,
            timeout=20, what="two injected create failures")
        assert fake.creates == [] and fake.failed_creates == []
        # ...then the point auto-disarms and the third attempt lands.
        created = _wait(lambda: fake.creates[:] or None, timeout=20,
                        what="create after fault exhausted")
        name = created[0]["name"]
        agents.append(subprocess.Popen(
            [os.path.join(c.binaries, "determined-agent"),
             "--master-url", c.master_url, "--id", name,
             "--slots", "2", "--slot-type", "cpu", "--addr", "127.0.0.1",
             "--work-root", os.path.join(c.tmpdir, f"agent-{name}"),
             "--token-file", c.db_path + ".agent_token"],
            env=c.env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        _wait(lambda: [t for t in c.api("GET", "/api/v1/tasks",
                                        token=token)["tasks"]
                       if t["state"] == "COMPLETED"] or None,
              timeout=60, what="task completed after fault cleared")
    finally:
        for p in agents:
            if p.poll() is None:
                p.kill()
                p.wait()
        c.stop()
        fake.stop()


def test_deployment_deficit_drives_provisioning(
        tmp_path, native_binaries):
    """ROADMAP item 3 / the capacity loop: a deployment's replica deficit
    — NOT just queued training slots — summons nodes, labeled under
    demand source "serving"; when the deployment dies, the fleet shrinks
    back to zero nodes."""
    fake = FakeTpuApi()
    c = _prov_master(tmp_path, native_binaries, fake)
    agents = []
    try:
        token = c.login()
        dep = c.api("POST", "/api/v1/deployments", {"config": {
            "name": "prov-dep",
            "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
            "serving": {"model": "gpt2",
                        "replicas": {"min": 2, "max": 2, "target": 2}},
            "resources": {"slots": 1},
            "environment": {"DET_FAKE_HEARTBEAT_S": "0.3"},
        }}, token=token)
        dep_id = dep["id"]
        # The deficit shows up attributed to serving...
        _wait(lambda: _scrape_metrics(c, token).get(
            'det_provisioner_demand_slots{pool="default",source="serving"}',
            0) > 0 or None, timeout=20, what="serving demand gauge")
        # ...and creates a node (2 replicas x 1 slot = 2 slots = 1 node).
        created = _wait(lambda: fake.creates[:] or None, timeout=30,
                        what="node created for replica deficit")
        name = created[0]["name"]
        agents.append(subprocess.Popen(
            [os.path.join(c.binaries, "determined-agent"),
             "--master-url", c.master_url, "--id", name,
             "--slots", "2", "--slot-type", "cpu", "--addr", "127.0.0.1",
             "--work-root", os.path.join(c.tmpdir, f"agent-{name}"),
             "--token-file", c.db_path + ".agent_token"],
            env=c.env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))

        def _ready():
            d = c.api("GET", f"/api/v1/deployments/{dep_id}",
                      token=token)["deployment"]
            up = [r for r in d["replicas"]
                  if r.get("allocation_state") == "RUNNING"
                  and r.get("proxy_address")]
            return d if len(up) == 2 else None

        _wait(_ready, timeout=90, what="both replicas running on the node")
        # Demand drains once the replicas are schedulable (the gauge
        # disappears or reads 0).
        _wait(lambda: _scrape_metrics(c, token).get(
            'det_provisioner_demand_slots{pool="default",source="serving"}',
            0) == 0 or None, timeout=20, what="serving demand drained")
        # Deployment gone -> node idles -> fleet shrinks to zero.
        c.api("POST", f"/api/v1/deployments/{dep_id}/kill", token=token)
        _wait(lambda: name in fake.deletes or None, timeout=45,
              what="idle node deleted after deployment kill")
    finally:
        for p in agents:
            if p.poll() is None:
                p.kill()
                p.wait()
        c.stop()
        fake.stop()


def test_elastic_demand_counts_min_size_and_trial_starts_shrunk(
        tmp_path, native_binaries):
    """A queued elastic trial demands its MIN size, not its preferred
    size: the provisioner summons one min-sized node (not preferred/
    slots_per_node nodes), and the scheduler STARTS the trial shrunk onto
    it (elastic shrink-to-start) instead of stranding it in the queue."""
    from tests.test_platform_e2e import FIXTURES  # noqa: F401

    fake = FakeTpuApi()
    c = _prov_master(tmp_path, native_binaries, fake)
    agents = []
    try:
        cfg = _experiment_config(
            tmp_path,
            extra={
                "resources": {"slots_per_trial": 4,
                              "elastic": {"min_slots": 1, "max_slots": 4}},
            },
        )
        eid, token = _create_experiment(c, cfg, activate=True)
        # Demand is 1 slot (min), under source "elastic" -> ONE node.
        _wait(lambda: fake.creates[:] or None, timeout=30,
              what="node create for elastic-at-min demand")
        time.sleep(1.5)  # past sustain+cooldown: a 4-slot demand would
        assert len(fake.creates) == 1   # have fired a second node
        metrics = _scrape_metrics(c, token)
        assert metrics.get(
            'det_provisioner_demand_slots{pool="default",source="elastic"}',
            0) in (0, 1), metrics  # 1 while queued, 0 once placed
        name = fake.creates[0]["name"]
        agents.append(subprocess.Popen(
            [os.path.join(c.binaries, "determined-agent"),
             "--master-url", c.master_url, "--id", name,
             "--slots", "2", "--slot-type", "cpu", "--addr", "127.0.0.1",
             "--work-root", os.path.join(c.tmpdir, f"agent-{name}"),
             "--token-file", c.db_path + ".agent_token"],
            env=c.env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        # The trial STARTS shrunk (2 slots fit of 4 preferred) and runs
        # to completion on the single summoned node.
        _wait_experiment(c, eid, token, timeout=180)
        trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                       token=token)["trials"]
        assert trials[0]["state"] == "COMPLETED"
        assert len(fake.creates) == 1, [x["name"] for x in fake.creates]
    finally:
        for p in agents:
            if p.poll() is None:
                p.kill()
                p.wait()
        c.stop()
        fake.stop()


def test_master_restart_adopts_provisioned_nodes(tmp_path, native_binaries):
    """Master restart must not orphan provisioned TPU-VMs: the reconcile
    pass adopts listed nodes with our prefix, so idle scale-down still
    happens and new launches can't collide with existing names."""
    fake = FakeTpuApi()
    # Pre-existing node from a "previous master life".
    fake.nodes["det-prov-default-0"] = {"state": "READY", "body": {}}
    cfg = {
        "agent_timeout_s": 15,
        "provisioner": {
            "type": "gcp",
            "api_base": fake.url + "/v2",
            "project": "p", "zone": "z",
            "slots_per_node": 2,
            "sustain_seconds": 0.5,
            "cooldown_seconds": 1,
            "idle_seconds": 1.5,
            "boot_grace_seconds": 4,
            "reconcile_seconds": 0.3,
        },
    }
    cfg_path = tmp_path / "master.json"
    cfg_path.write_text(json.dumps(cfg))
    c = Devcluster(str(tmp_path), native_binaries)
    c.master = subprocess.Popen(
        [os.path.join(c.binaries, "determined-master"),
         "--config", str(cfg_path),
         "--port", str(c.port), "--host", "127.0.0.1", "--db", c.db_path],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    _wait_http(c.master_url + "/api/v1/master")
    agent = None
    try:
        # Boot the agent for the adopted node; it registers, sits idle,
        # and the ADOPTED node gets idle-scale-downed — proof the master
        # took ownership back.
        agent = subprocess.Popen(
            [os.path.join(c.binaries, "determined-agent"),
             "--master-url", c.master_url,
             "--id", "det-prov-default-0",
             "--slots", "2", "--slot-type", "cpu", "--addr", "127.0.0.1",
             "--work-root", os.path.join(c.tmpdir, "aw"),
             "--token-file", c.db_path + ".agent_token"],
            env=c.env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        _wait(lambda: "det-prov-default-0" in fake.deletes or None,
              timeout=30, what="adopted node idle-scale-down")
    finally:
        if agent is not None and agent.poll() is None:
            agent.kill()
            agent.wait()
        c.stop()
        fake.stop()
