"""Split-brain safety: lease-based ownership, fencing epochs, partition chaos.

The master mints a monotonic fencing epoch per allocation run (the trial's
run_id at mint time) and hands it to tasks as DET_ALLOCATION_EPOCH; every
state-mutating harness POST carries it back as X-Allocation-Epoch and a
writer from a superseded run gets a distinct 409 plus a
det_fenced_writes_total{route=...} bump (docs/cluster-ops.md "Leases,
fencing & split-brain"). Liveness is the agent-side ownership lease:
renewed only by register/heartbeat ACKs, so a partitioned agent
self-terminates its tasks at lease expiry — the fence is the backstop for
the zombie that doesn't.

Tier-1-safe tests drive the fence through the api.write.stale_epoch fault
point; the real partition (agent.heartbeat.blackhole mid-trial, master
reassigns, zombie's late COMMIT fenced, survivor trajectory identical to
an unpartitioned control) runs behind -m slow.
"""

import json
import os
import sqlite3
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from threading import Thread

import pytest

from test_platform_e2e import (  # noqa: F401  (fixture re-export)
    FIXTURES,
    Devcluster,
    _create_experiment,
    _experiment_config,
    _wait_experiment,
)

from determined_tpu.common.api import APIError, Session

NEW_POINTS = {
    "agent.heartbeat.blackhole",
    "master.lease.expire",
    "api.write.stale_epoch",
}


@pytest.fixture()
def master_only(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def _arm(cluster, admin_token, **body):
    return cluster.api("POST", "/api/v1/debug/faults", body, token=admin_token)


def _disarm_all(cluster, admin_token):
    return _arm(cluster, admin_token, mode="off")


def _unmanaged_trial(cluster, token):
    eid = cluster.api(
        "POST", "/api/v1/experiments",
        {"unmanaged": True, "config": {"name": "fencing-unmanaged"}},
        token=token)["id"]
    tid = cluster.api(
        "POST", f"/api/v1/experiments/{eid}/trials", {"hparams": {}},
        token=token)["id"]
    return eid, tid


def _scrape(master_url, token):
    req = urllib.request.Request(
        master_url + "/metrics",
        headers={"Authorization": f"Bearer {token}"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.read().decode()


def _metric_value(text, name, label_frag=""):
    """Value of the first sample line for `name` containing `label_frag`;
    None when the series was never emitted (e.g. an empty counter map)."""
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        if line.startswith(name) and label_frag in line:
            return float(line.rsplit(" ", 1)[1])
    return None


# ---------------------------------------------------------------------------
# Fault-point surface (tier-1 safe).
# ---------------------------------------------------------------------------


def test_partition_fault_points_are_registered(master_only):
    c = master_only
    token = c.login()
    listing = c.api("GET", "/api/v1/debug/faults", token=token)
    names = {p["name"] for p in listing["points"]}
    assert NEW_POINTS <= names
    assert listing["armed"] == []


# ---------------------------------------------------------------------------
# The stale-epoch fence (tier-1 safe, driven via api.write.stale_epoch).
# ---------------------------------------------------------------------------


def test_stale_epoch_write_gets_distinct_409_and_counter(master_only):
    c = master_only
    token = c.login()
    admin = c.login("admin")
    _, tid = _unmanaged_trial(c, token)
    plain = Session(c.master_url, token=token, backoff_base=0.02)
    # A session whose every write carries the allocation epoch — what
    # core.init() builds from DET_ALLOCATION_EPOCH.
    epoch0 = Session(c.master_url, token=token, backoff_base=0.02,
                     headers={"X-Allocation-Epoch": "0"})

    def report(sess, step):
        sess.post(f"/api/v1/trials/{tid}/metrics",
                  body={"group": "training", "steps_completed": step,
                        "trial_run_id": 0, "metrics": {"loss": 1.0}})

    # Un-fenced baseline: epoch 0 matches the unmanaged trial's run_id 0.
    report(epoch0, 1)

    # Armed fault forces the stale branch for epoch-carrying writes only.
    _arm(c, admin, point="api.write.stale_epoch", mode="error")
    try:
        report(epoch0, 2)
        raise AssertionError("stale-epoch write should 409")
    except APIError as e:
        assert e.status == 409
        body = json.loads(e.body)
        assert body["fenced"] is True
        assert body["route"] == "metrics"
        assert body["claimed_epoch"] == 0
        assert "current_epoch" in body

    # Epoch-less writers (CLI, unmanaged back-compat) are never fenced,
    # even while the fault is armed: no header, no staleness claim.
    report(plain, 3)

    _disarm_all(c, admin)
    report(epoch0, 4)

    rows = plain.get(f"/api/v1/trials/{tid}/metrics",
                     params={"group": "training"})["metrics"]
    assert {m["total_batches"] for m in rows} == {1, 3, 4}, (
        "exactly the fenced write must be missing")
    assert _metric_value(_scrape(c.master_url, token), "det_fenced_writes_total",
                         'route="metrics"') == 1.0


def test_fenced_commit_never_advances_pointer_and_sweeps_partial(master_only):
    """A zombie's phase-2 COMMIT must neither advance latest_checkpoint nor
    leave its PARTIAL torso behind (docs/checkpointing.md)."""
    c = master_only
    token = c.login()
    admin = c.login("admin")
    _, tid = _unmanaged_trial(c, token)
    sess = Session(c.master_url, token=token, backoff_base=0.02)
    stale = Session(c.master_url, token=token, backoff_base=0.02,
                    headers={"X-Allocation-Epoch": "0"})

    def report(s, uuid, steps, state):
        s.post("/api/v1/checkpoints",
               body={"uuid": uuid, "trial_id": tid, "steps_completed": steps,
                     "metadata": {}, "resources": {}, "state": state})

    report(sess, "ck-good", 2, "PARTIAL")
    report(sess, "ck-good", 2, "COMPLETED")
    report(sess, "ck-zombie", 4, "PARTIAL")

    _arm(c, admin, point="api.write.stale_epoch", mode="error")
    try:
        report(stale, "ck-zombie", 4, "COMPLETED")
        raise AssertionError("zombie COMMIT should 409")
    except APIError as e:
        assert e.status == 409
        assert json.loads(e.body)["route"] == "checkpoints"
    _disarm_all(c, admin)

    trial = sess.get(f"/api/v1/trials/{tid}")["trial"]
    assert trial["latest_checkpoint"] == "ck-good", (
        "a fenced COMMIT must never become the resume pointer")
    # The fenced uuid's PARTIAL row was swept in the same stroke.
    try:
        sess.get("/api/v1/checkpoints/ck-zombie")
        raise AssertionError("fenced checkpoint's PARTIAL should be swept")
    except APIError as e:
        assert e.status == 404
    lineage = sess.get(f"/api/v1/trials/{tid}/checkpoints",
                       params={"state": "COMPLETED"})["checkpoints"]
    assert [ck["uuid"] for ck in lineage] == ["ck-good"]
    assert _metric_value(_scrape(c.master_url, token), "det_fenced_writes_total",
                         'route="checkpoints"') == 1.0


# ---------------------------------------------------------------------------
# Idempotency-replay horizon pinned to the lease TTL (tier-1 safe).
# ---------------------------------------------------------------------------


def test_idempotency_sweep_horizon_tracks_lease_ttl(tmp_path, native_binaries):
    """Replay entries must outlive the longest lease: horizon is
    max(24h, 2 x lease_ttl_s). With lease_ttl_s=90000 a 25h-old key
    survives the sweep (horizon 50h); back at the default lease it is
    swept by the 24h floor."""
    c = Devcluster(str(tmp_path), native_binaries)
    try:
        c.start_master(extra_args=("--lease-ttl", "90000"))
        c.login()  # provision default users before direct db writes
        c.kill_master()
        with sqlite3.connect(c.db_path) as db:
            for key, age in (("k-25h", "-25 hours"), ("k-60h", "-60 hours")):
                db.execute(
                    "INSERT INTO idempotency_keys (key, status, body, "
                    "created_at) VALUES (?, 200, '{}', "
                    "datetime('now', ?))", (key, age))
            db.commit()

        c.start_master(extra_args=("--lease-ttl", "90000"))
        admin = c.login("admin")
        user = c.login()
        try:
            c.api("POST", "/api/v1/master/sweep_idempotency", {}, token=user)
            raise AssertionError("sweep is admin-only")
        except urllib.error.HTTPError as e:
            assert e.code == 403
        out = c.api("POST", "/api/v1/master/sweep_idempotency", {},
                    token=admin)
        assert out["horizon_seconds"] == 180000
        assert out["deleted"] == 1, "only the 60h key is past 2x lease"

        # Default lease (30s): the 24h floor governs and the 25h key goes.
        c.kill_master()
        c.start_master()
        admin = c.login("admin")
        out = c.api("POST", "/api/v1/master/sweep_idempotency", {},
                    token=admin)
        assert out["horizon_seconds"] == 86400
        assert out["deleted"] == 1
        c.kill_master()
        with sqlite3.connect(c.db_path) as db:
            keys = {r[0] for r in db.execute(
                "SELECT key FROM idempotency_keys").fetchall()}
        assert "k-25h" not in keys and "k-60h" not in keys
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# Lease accounting + steady-state zero (tier-1 safe, real agent).
# ---------------------------------------------------------------------------


def test_lease_steady_state_is_zero_and_expiry_counts_once(cluster, tmp_path):
    """An un-partitioned run must see ZERO fenced writes and ZERO lease
    expirations — proof the harness epoch header matches run_id end to end
    — and a forced lapse (master.lease.expire) counts each agent once, not
    once per sweep tick."""
    c = cluster
    eid, token = _create_experiment(cluster, _experiment_config(tmp_path))
    _wait_experiment(cluster, eid, token)

    agents = c.api("GET", "/api/v1/agents", token=token)["agents"]
    assert agents and agents[0]["lease_expired"] is False
    assert agents[0]["lease_remaining_seconds"] > 0

    text = _scrape(c.master_url, token)
    assert _metric_value(text, "det_lease_expirations_total") == 0.0
    for line in text.splitlines():
        if line.startswith("det_fenced_writes_total"):
            assert line.endswith(" 0"), f"steady-state fenced write: {line}"

    # Forced lapse: fires once (count=1); the 200ms sweep must count the
    # agent once per lapse, and the next heartbeat renews the lease.
    admin = c.login("admin")
    _arm(c, admin, point="master.lease.expire", mode="error", count=1)
    deadline = time.time() + 10
    while time.time() < deadline:
        if _metric_value(_scrape(c.master_url, token),
                         "det_lease_expirations_total") == 1.0:
            break
        time.sleep(0.2)
    else:
        raise AssertionError("forced lease lapse never counted")
    time.sleep(1.0)  # several more sweep ticks: still exactly one
    assert _metric_value(_scrape(c.master_url, token),
                         "det_lease_expirations_total") == 1.0


# ---------------------------------------------------------------------------
# Session hardening: connection reset mid-response-body is retryable.
# ---------------------------------------------------------------------------


def test_session_retries_connection_reset_mid_response_body():
    """A peer that dies after the status line, partway through the body,
    surfaces as http.client.IncompleteRead — which urlopen does NOT wrap
    in URLError. The Session must back off and retry instead of crashing
    the caller mid-trial."""
    calls = []
    body = json.dumps({"ok": True}).encode()

    class TruncatingHandler(BaseHTTPRequestHandler):
        def do_GET(self):
            calls.append(1)
            self.send_response(200)
            if len(calls) == 1:
                # Promise more bytes than we send, then cut the socket.
                self.send_header("Content-Length", str(len(body) + 64))
                self.end_headers()
                self.wfile.write(body[: len(body) // 2])
                self.wfile.flush()
                self.connection.close()
            else:
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        def log_message(self, *args):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), TruncatingHandler)
    Thread(target=srv.serve_forever, daemon=True).start()
    try:
        s = Session(f"http://127.0.0.1:{srv.server_address[1]}",
                    max_retries=4, backoff_base=0.01)
        assert s.get("/status") == {"ok": True}
        assert len(calls) == 2, "mid-body reset must be retried exactly once"
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Partition e2e (slow): lease liveness + the full split-brain scenario.
# ---------------------------------------------------------------------------


def _task_pids(work_root):
    try:
        with open(os.path.join(work_root, "running.json")) as f:
            return [e["pid"] for e in json.load(f) if "exit_code" not in e]
    except (OSError, ValueError):
        return []


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def _wait_training_started(c, eid, token, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                       token=token)["trials"]
        if trials:
            tid = trials[0]["id"]
            rows = c.api(
                "GET", f"/api/v1/trials/{tid}/metrics?group=training",
                token=token)["metrics"]
            if rows:
                return trials[0]
        time.sleep(0.3)
    raise TimeoutError("trial never started reporting")


@pytest.mark.slow
def test_partitioned_agent_self_fences_within_lease_ttl(
        tmp_path, native_binaries):
    """Liveness half of split-brain safety: an agent that cannot renew its
    lease kills its own tasks within lease_ttl_s — BEFORE the master's
    reclaim (agent_timeout_s) hands the allocation to someone else."""
    c = Devcluster(str(tmp_path), native_binaries)
    faults_file = os.path.join(str(tmp_path), "agent-faults.txt")
    try:
        c.start_master()
        c.start_agent(extra_env={"DET_AGENT_LEASE_TTL_S": "4",
                                 "DET_AGENT_FAULTS_FILE": faults_file})
        work_root = os.path.join(str(tmp_path), "agent-work")

        config = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": 2000}})
        config["environment"] = {"TRIAL_STEP_SLEEP": "0.1"}
        eid, token = _create_experiment(c, config)
        _wait_training_started(c, eid, token)

        pids = _task_pids(work_root)
        assert pids, "running.json should list the live task"
        assert all(_pid_alive(p) for p in pids)

        # Partition: total heartbeat + long-poll silence, armed mid-run.
        with open(faults_file, "w") as f:
            f.write("agent.heartbeat.blackhole:drop")

        # Lease TTL is 4s (pinned agent-side); allow kill + reap slack but
        # stay well inside the master's 15s reclaim window.
        deadline = time.time() + 12
        while time.time() < deadline:
            if all(not _pid_alive(p) for p in pids):
                break
            time.sleep(0.3)
        else:
            raise AssertionError(
                f"partitioned agent did not self-fence its tasks: {pids}")
        # The agent itself survives — it fenced its tasks, not itself.
        assert c.agent.poll() is None
    finally:
        c.stop()


@pytest.mark.slow
def test_split_brain_partition_fences_zombie_and_preserves_trajectory(
        tmp_path, native_binaries):
    """The acceptance scenario (ISSUE.md): partition a 2-agent devcluster
    mid-trial, master reassigns past the zombie, the zombie's late writes
    (including its checkpoint COMMIT) are fenced with 409s, the partition
    heals, and the surviving trajectory is bit-identical to an
    unpartitioned control run with exactly one COMPLETED lineage."""
    c = Devcluster(str(tmp_path), native_binaries)
    faults_file = os.path.join(str(tmp_path), "agent0-faults.txt")
    try:
        c.start_master()  # --agent-timeout 15 (Devcluster default)
        # Zombie-to-be: lease TTL pinned huge so self-fencing never saves
        # us — this test is about the fence being a sufficient backstop
        # when the liveness half fails.
        c.start_agent(agent_id="agent-0",
                      extra_env={"DET_AGENT_LEASE_TTL_S": "9999",
                                 "DET_AGENT_FAULTS_FILE": faults_file})

        total_batches = 120
        config = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": total_batches}})
        config["environment"] = {"TRIAL_STEP_SLEEP": "0.2"}
        eid, token = _create_experiment(c, config)
        trial = _wait_training_started(c, eid, token)
        tid = trial["id"]
        old_epoch = trial["run_id"]

        # Healthy standby capacity, then the partition.
        c.start_agent(agent_id="agent-1")
        with open(faults_file, "w") as f:
            f.write("agent.heartbeat.blackhole:drop")

        # Master declares agent-0 dead at agent_timeout_s and requeues:
        # run_id bumps, so the new allocation's epoch supersedes the
        # zombie's.
        deadline = time.time() + 60
        while time.time() < deadline:
            t = c.api("GET", f"/api/v1/trials/{tid}", token=token)["trial"]
            if t["run_id"] > old_epoch:
                break
            time.sleep(0.5)
        else:
            raise AssertionError("master never reassigned past the zombie")

        # The zombie's late writes, driven deterministically with its
        # minted epoch (the task process itself also keeps reporting and
        # crashes on its first natural 409 — not blocked by the agent-side
        # blackhole, which silences only the control channel).
        zombie = Session(c.master_url, token=token, backoff_base=0.02,
                         headers={"X-Allocation-Epoch": str(old_epoch)})
        plain = Session(c.master_url, token=token, backoff_base=0.02)
        try:
            zombie.post(f"/api/v1/trials/{tid}/metrics",
                        body={"group": "training", "steps_completed": 999,
                              "trial_run_id": old_epoch,
                              "metrics": {"loss": 123.0}})
            raise AssertionError("zombie metric write should 409")
        except APIError as e:
            assert e.status == 409
            assert json.loads(e.body)["fenced"] is True

        # Its two-phase COMMIT: PARTIAL landed before the fence matters
        # (simulating phase 1 completing pre-partition), phase 2 is fenced
        # and the torso swept.
        plain.post("/api/v1/checkpoints",
                   body={"uuid": "ck-zombie", "trial_id": tid,
                         "steps_completed": 999, "metadata": {},
                         "resources": {}, "state": "PARTIAL"})
        try:
            zombie.post("/api/v1/checkpoints",
                        body={"uuid": "ck-zombie", "trial_id": tid,
                              "steps_completed": 999, "metadata": {},
                              "resources": {}, "state": "COMPLETED"})
            raise AssertionError("zombie COMMIT should 409")
        except APIError as e:
            assert e.status == 409

        # Survivor finishes on agent-1.
        _wait_experiment(c, eid, token, timeout=180)
        survivor = c.api("GET", f"/api/v1/trials/{tid}", token=token)["trial"]
        assert survivor["state"] == "COMPLETED"
        assert survivor["latest_checkpoint"] != "ck-zombie"
        new_epoch = survivor["run_id"]
        try:
            plain.get("/api/v1/checkpoints/ck-zombie")
            raise AssertionError("zombie PARTIAL should be swept")
        except APIError as e:
            assert e.status == 404

        # Exactly one COMPLETED lineage: every COMPLETED checkpoint
        # belongs to the surviving run, none to the zombie's.
        lineage = plain.get(f"/api/v1/trials/{tid}/checkpoints",
                            params={"state": "COMPLETED"})["checkpoints"]
        assert lineage, "survivor must have committed checkpoints"
        assert len({ck["uuid"] for ck in lineage}) == len(lineage)
        assert "ck-zombie" not in {ck["uuid"] for ck in lineage}

        text = _scrape(c.master_url, token)
        assert (_metric_value(text, "det_fenced_writes_total",
                              'route="metrics"') or 0) >= 1
        assert (_metric_value(text, "det_fenced_writes_total",
                              'route="checkpoints"') or 0) >= 1

        # Heal: removing the faults file disarms the blackhole and the
        # zombie agent re-registers.
        os.remove(faults_file)
        deadline = time.time() + 30
        while time.time() < deadline:
            agents = c.api("GET", "/api/v1/agents", token=token)["agents"]
            if any(a["id"] == "agent-0" and a["alive"] for a in agents):
                break
            time.sleep(0.5)
        else:
            raise AssertionError("healed agent never re-registered")

        # Control: the same config on the healed cluster, no partition.
        # The fixture's trajectory is deterministic (loss = 1/steps,
        # val_loss = lr/(1+steps)), so the surviving run's reports must be
        # bit-identical to the control's.
        ceid, _ = _create_experiment(c, config)
        _wait_experiment(c, ceid, token, timeout=180)
        ctrial = c.api("GET", f"/api/v1/experiments/{ceid}/trials",
                       token=token)["trials"][0]

        def rows(trial_id, group, run_id=None):
            out = c.api(
                "GET", f"/api/v1/trials/{trial_id}/metrics?group={group}",
                token=token)["metrics"]
            if run_id is not None:
                out = [m for m in out if m["trial_run_id"] == run_id]
            return [(m["total_batches"], m["metrics"]) for m in out]

        assert rows(tid, "validation", new_epoch) == rows(
            ctrial["id"], "validation", ctrial["run_id"])
        assert rows(tid, "training", new_epoch) == rows(
            ctrial["id"], "training", ctrial["run_id"])
    finally:
        c.stop()
