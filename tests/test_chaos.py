"""Chaos tests: deterministic fault injection + crash-recovery hardening.

The platform's core promise (trial restart-on-failure, agent reattach,
master restore-on-boot) is exercised adversarially here instead of being
trusted incidentally: faults are armed through `DET_FAULTS` / the
admin-gated `POST /api/v1/debug/faults` route (docs/chaos.md), and the
recovery paths are asserted at the DB level — exact metric counts, no
idempotency-key replays applied twice, refcounts that balance.

Tier-1-safe tests run unmarked; the kill-the-master and 30%-5xx
end-to-end runs are behind `-m slow` to hold the tier-1 time budget.
"""

import os
import signal
import sqlite3
import time
import urllib.error
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

from determined_tpu.common import api as api_mod
from determined_tpu.common.api import APIError, Session

KNOWN_POINTS = {
    "api.response.5xx",
    "api.response.drop",
    "db.write.delay",
    "master.allocation.exit.crash",
    "agent.heartbeat.drop",
    "agent.exit_report.drop",
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


def _training_rows(sess, trial_id):
    return sess.get(f"/api/v1/trials/{trial_id}/metrics",
                    params={"group": "training"})["metrics"]


def _assert_no_duplicate_reports(rows):
    """Idempotency at the DB level: no (run, group, batch) applied twice."""
    seen = set()
    for m in rows:
        key = (m["trial_run_id"], m["group_name"], m["total_batches"])
        assert key not in seen, f"duplicated metric report {key}"
        seen.add(key)


# ---------------------------------------------------------------------------
# Fault-point surface (tier-1 safe).
# ---------------------------------------------------------------------------


def test_fault_points_listable_and_admin_gated(master_only):
    c = master_only
    user_token = c.login()
    admin_token = c.login("admin")

    listing = c.api("GET", "/api/v1/debug/faults", token=user_token)
    names = {p["name"] for p in listing["points"]}
    assert KNOWN_POINTS <= names
    assert listing["armed"] == []

    # Arming is admin-only: it is a cluster-wide DoS lever.
    try:
        _arm(c, user_token, point="api.response.5xx", mode="error", count=1)
        raise AssertionError("non-admin arm should 403")
    except urllib.error.HTTPError as e:
        assert e.code == 403

    # Bad mode is rejected with a diagnostic.
    try:
        _arm(c, admin_token, point="api.response.5xx", mode="explode")
        raise AssertionError("bad mode should 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400

    out = _arm(c, admin_token, point="api.response.5xx", mode="error", count=2)
    assert out["armed"][0]["point"] == "api.response.5xx"
    assert out["armed"][0]["remaining"] == 2

    # Exactly two requests fail, then the point auto-disarms.
    for _ in range(2):
        try:
            c.api("GET", "/api/v1/agents", token=user_token)
            raise AssertionError("armed fault should inject a 500")
        except urllib.error.HTTPError as e:
            assert e.code == 500
    assert c.api("GET", "/api/v1/agents", token=user_token)["agents"] == []
    listing = c.api("GET", "/api/v1/debug/faults", token=user_token)
    assert listing["armed"] == [], "count-armed fault must auto-disarm"


def test_unarmed_fault_points_are_noop(master_only):
    c = master_only
    token = c.login()
    admin = c.login("admin")
    _arm(c, admin, point="db.write.delay", mode="delay-50", count=1)
    _disarm_all(c, admin)
    t0 = time.time()
    for _ in range(50):
        c.api("GET", "/api/v1/master")
    assert time.time() - t0 < 10.0
    assert c.api("GET", "/api/v1/debug/faults", token=token)["armed"] == []


def test_db_write_delay_fault(master_only):
    c = master_only
    admin = c.login("admin")
    _arm(c, admin, point="db.write.delay", mode="delay-200", count=1)
    t0 = time.time()
    # login writes a session row → one delayed DB write.
    c.login()
    assert time.time() - t0 >= 0.2


# ---------------------------------------------------------------------------
# Session retry policy: backoff, jitter, Retry-After, idempotent replay.
# ---------------------------------------------------------------------------


class _FlakyHandler(BaseHTTPRequestHandler):
    calls = []
    plan = []  # list of (status, headers) consumed per call; then 200

    def do_GET(self):  # noqa: N802 (stdlib naming)
        _FlakyHandler.calls.append(time.time())
        if _FlakyHandler.plan:
            status, headers = _FlakyHandler.plan.pop(0)
            self.send_response(status)
            for k, v in headers.items():
                self.send_header(k, v)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        body = b'{"ok": true}'
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):
        pass


@pytest.fixture()
def flaky_server():
    _FlakyHandler.calls = []
    _FlakyHandler.plan = []
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _FlakyHandler)
    t = Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", _FlakyHandler
    srv.shutdown()


def test_429_and_retry_after_honored(flaky_server):
    url, handler = flaky_server
    handler.plan = [(429, {"Retry-After": "1"}), (429, {"Retry-After": "1"})]
    t0 = time.time()
    out = Session(url, max_retries=5).get("/anything")
    assert out == {"ok": True}
    assert len(handler.calls) == 3
    # Retry-After floors both sleeps.
    assert time.time() - t0 >= 1.8


def test_500_not_retried_for_non_idempotent_post():
    # POSTs without an idempotency key must NOT retry a bare 500: the
    # master may have applied the mutation.
    calls = []

    class PostHandler(BaseHTTPRequestHandler):
        def do_POST(self):  # noqa: N802
            calls.append(self.headers.get("X-Idempotency-Key"))
            self.send_response(500)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *args):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), PostHandler)
    Thread(target=srv.serve_forever, daemon=True).start()
    try:
        s = Session(f"http://127.0.0.1:{srv.server_address[1]}",
                    max_retries=4)
        with pytest.raises(APIError):
            s.post("/mutate", body={})
        assert len(calls) == 1, "non-idempotent POST must not retry a 500"
        # With idempotent=True the same 500 IS retried, with a stable key.
        with pytest.raises(APIError):
            s.post("/mutate", body={}, idempotent=True)
        keyed = calls[1:]
        assert len(keyed) == 4
        assert keyed[0] is not None and len(set(keyed)) == 1, (
            "idempotency key must be generated once per logical request")
    finally:
        srv.shutdown()


def test_backoff_full_jitter_is_capped(monkeypatch):
    sleeps = []
    monkeypatch.setattr(api_mod.time, "sleep", sleeps.append)
    s = Session("http://127.0.0.1:9", max_retries=5,
                backoff_base=0.1, backoff_cap=0.4)
    with pytest.raises(ConnectionError):
        s.get("/x", timeout=0.2)
    assert len(sleeps) == 4
    for i, d in enumerate(sleeps):
        assert 0.0 <= d <= min(0.4, 0.1 * 2 ** i) + 1e-9


# ---------------------------------------------------------------------------
# Master-side idempotent replay, verified at the DB level (tier-1 safe).
# ---------------------------------------------------------------------------


def _unmanaged_trial(cluster, token):
    eid = cluster.api(
        "POST", "/api/v1/experiments",
        {"unmanaged": True, "config": {"name": "chaos-unmanaged"}},
        token=token)["id"]
    tid = cluster.api(
        "POST", f"/api/v1/experiments/{eid}/trials", {"hparams": {}},
        token=token)["id"]
    return eid, tid


def test_idempotent_metric_report_survives_5xx_and_dropped_response(
        master_only):
    c = master_only
    token = c.login()
    admin = c.login("admin")
    _, tid = _unmanaged_trial(c, token)
    sess = Session(c.master_url, token=token, backoff_base=0.02)

    # Injected 500 BEFORE processing: the retry must deliver exactly once.
    _arm(c, admin, point="api.response.5xx", mode="error", count=1)
    sess.post(f"/api/v1/trials/{tid}/metrics",
              body={"group": "training", "steps_completed": 1,
                    "trial_run_id": 0, "metrics": {"loss": 1.0}},
              idempotent=True)
    rows = _training_rows(sess, tid)
    assert len(rows) == 1

    # Processed-then-dropped response: the retry must be answered from
    # the replay cache, not re-applied — the classic double-count.
    _arm(c, admin, point="api.response.drop", mode="drop", count=1)
    sess.post(f"/api/v1/trials/{tid}/metrics",
              body={"group": "training", "steps_completed": 2,
                    "trial_run_id": 0, "metrics": {"loss": 0.5}},
              idempotent=True)
    rows = _training_rows(sess, tid)
    assert len(rows) == 2, f"dropped-response retry double-applied: {rows}"
    _assert_no_duplicate_reports(rows)

    # The key is recorded server-side.
    c.kill_master()
    with sqlite3.connect(c.db_path) as db:
        n = db.execute("SELECT COUNT(*) FROM idempotency_keys").fetchone()[0]
    assert n >= 2


def test_checkpoint_report_replay_does_not_double_register(master_only):
    c = master_only
    token = c.login()
    admin = c.login("admin")
    _, tid = _unmanaged_trial(c, token)
    sess = Session(c.master_url, token=token, backoff_base=0.02)
    _arm(c, admin, point="api.response.drop", mode="drop", count=1)
    sess.post("/api/v1/checkpoints",
              body={"uuid": "ck-chaos-1", "trial_id": tid,
                    "steps_completed": 4, "metadata": {}, "resources": {}},
              idempotent=True)
    ck = sess.get("/api/v1/checkpoints/ck-chaos-1")["checkpoint"]
    assert ck["trial_id"] == tid
    trial = sess.get(f"/api/v1/trials/{tid}")["trial"]
    assert trial["latest_checkpoint"] == "ck-chaos-1"


def test_partial_checkpoint_never_becomes_resume_pointer(master_only):
    """Two-phase commit at the registry (docs/checkpointing.md): a PARTIAL
    report must not advance latest_checkpoint; the COMPLETED phase-2
    report for the same uuid must; and the lineage endpoint filters by
    state so Trainer fallback only ever sees verified checkpoints."""
    c = master_only
    token = c.login()
    _, tid = _unmanaged_trial(c, token)
    sess = Session(c.master_url, token=token, backoff_base=0.02)

    def report(uuid, steps, state):
        sess.post("/api/v1/checkpoints",
                  body={"uuid": uuid, "trial_id": tid,
                        "steps_completed": steps, "metadata": {},
                        "resources": {}, "state": state},
                  idempotent=True)

    report("ck-good-2", 2, "PARTIAL")
    report("ck-good-2", 2, "COMPLETED")
    report("ck-partial-4", 4, "PARTIAL")  # phase 2 never lands (crash)

    trial = sess.get(f"/api/v1/trials/{tid}")["trial"]
    assert trial["latest_checkpoint"] == "ck-good-2", (
        "a PARTIAL checkpoint must never become the resume pointer")
    assert sess.get("/api/v1/checkpoints/ck-partial-4")["checkpoint"][
        "state"] == "PARTIAL"

    # Lineage endpoint: newest-first, state-filtered.
    lineage = sess.get(f"/api/v1/trials/{tid}/checkpoints",
                       params={"state": "COMPLETED"})["checkpoints"]
    assert [ck["uuid"] for ck in lineage] == ["ck-good-2"]
    everything = sess.get(f"/api/v1/trials/{tid}/checkpoints")["checkpoints"]
    assert [ck["uuid"] for ck in everything] == ["ck-partial-4", "ck-good-2"]

    # Bad state values are rejected, not stored.
    try:
        report("ck-bad", 6, "SHRUG")
        raise AssertionError("invalid state should 400")
    except APIError as e:
        assert e.status == 400


# ---------------------------------------------------------------------------
# Context-blob sweep refcount regression (ADVICE.md #1, tier-1 safe).
# ---------------------------------------------------------------------------


def test_blob_sweep_releases_once_per_ended_task_and_never_live_claims(
        tmp_path, native_binaries):
    """Master restart with two ended tasks sharing one context hash plus a
    live experiment model-def on the same hash: the sweep must release
    exactly the two task claims (not one, not three) and the experiment's
    model definition must survive until the experiment itself is deleted."""
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    try:
        eid, token = _create_experiment(
            c, _experiment_config(tmp_path), activate=False)
        c.kill_master()

        # Manufacture the orphan state the advisory describes: the tasks
        # ended (end_time set) but the inline release never ran — the old
        # master died first. Both share the experiment's context hash.
        with sqlite3.connect(c.db_path) as db:
            (h,) = db.execute(
                "SELECT model_def_hash FROM experiments WHERE id=?",
                (eid,)).fetchone()
            assert h
            db.execute(
                "UPDATE model_defs SET refcount = refcount + 2 WHERE hash=?",
                (h,))
            for tid in ("cmd-orphan-a", "cmd-orphan-b"):
                db.execute(
                    "INSERT INTO tasks (id, type, state, end_time, "
                    "context_hash) VALUES (?, 'COMMAND', 'COMPLETED', "
                    "datetime('now'), ?)", (tid, h))
            db.commit()

        c.start_master()
        admin = c.login("admin")
        out = c.api("POST", "/api/v1/master/cleanup_blobs", {}, token=admin)
        assert out["released"] == 2, (
            "sweep must release one claim per ended-task row")
        # The live experiment's claim survives: model_def still served.
        md = c.api("GET", f"/api/v1/experiments/{eid}/model_def",
                   token=admin)
        assert md["b64_tgz"], "sweep purged a blob with a live claim"
        # Idempotent: a second sweep releases nothing further.
        out = c.api("POST", "/api/v1/master/cleanup_blobs", {}, token=admin)
        assert out["released"] == 0
        md = c.api("GET", f"/api/v1/experiments/{eid}/model_def",
                   token=admin)
        assert md["b64_tgz"]

        # Deleting the experiment drops the LAST claim → blob purged
        # (fails if the sweep leaked or double-released refcounts).
        c.api("POST", f"/api/v1/experiments/{eid}/cancel", {}, token=admin)
        deadline = time.time() + 30
        while time.time() < deadline:
            state = c.api("GET", f"/api/v1/experiments/{eid}",
                          token=admin)["experiment"]["state"]
            if state in ("CANCELED", "COMPLETED", "ERROR"):
                break
            time.sleep(0.2)
        c.api("DELETE", f"/api/v1/experiments/{eid}", token=admin)
        c.kill_master()
        with sqlite3.connect(c.db_path) as db:
            n = db.execute("SELECT COUNT(*) FROM model_defs").fetchone()[0]
        assert n == 0, "refcount accounting leaked the blob"
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# Chaos smoke: experiment completes under injected 5xx (tier-1 safe).
# ---------------------------------------------------------------------------


def test_smoke_experiment_completes_under_injected_5xx(cluster, tmp_path):
    config = _experiment_config(tmp_path)
    eid, token = _create_experiment(cluster, config)
    admin = cluster.login("admin")
    _arm(cluster, admin, point="api.response.5xx", mode="error",
         probability=0.15)
    sess = Session(cluster.master_url, token=token)
    try:
        deadline = time.time() + 120
        state = None
        while time.time() < deadline:
            state = sess.get(f"/api/v1/experiments/{eid}")["experiment"][
                "state"]
            if state in ("COMPLETED", "CANCELED", "ERROR"):
                break
            time.sleep(0.5)
    finally:
        _disarm_all(cluster, admin)
    assert state == "COMPLETED", f"experiment under 15% 5xx ended {state}"
    trial = sess.get(f"/api/v1/experiments/{eid}/trials")["trials"][0]
    rows = _training_rows(sess, trial["id"])
    _assert_no_duplicate_reports(rows)
    batches = sorted(m["total_batches"] for m in rows
                     if m["trial_run_id"] == max(
                         r["trial_run_id"] for r in rows))
    assert batches[-1] == 8, f"final report missing: {batches}"


# ---------------------------------------------------------------------------
# Capstone e2e (slow): SIGKILL the master / kill the agent / 30% 5xx.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_master_sigkill_mid_trial_no_lost_or_duplicated_metrics(
        cluster, tmp_path):
    config = _experiment_config(
        tmp_path,
        searcher={"name": "single", "metric": "val_loss",
                  "max_length": {"batches": 60}},
        extra={"max_restarts": 2},
    )
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
    eid, token = _create_experiment(cluster, config)

    # Wait until the trial is mid-run and reporting.
    sess = Session(cluster.master_url, token=token)
    deadline = time.time() + 60
    trial = None
    while time.time() < deadline:
        trials = sess.get(f"/api/v1/experiments/{eid}/trials")["trials"]
        if trials and _training_rows(sess, trials[0]["id"]):
            trial = trials[0]
            break
        time.sleep(0.3)
    assert trial is not None, "trial never started reporting"

    cluster.kill_master()  # SIGKILL: no snapshot flush, no goodbyes
    time.sleep(1.0)
    cluster.start_master()  # same db: restore-on-boot + re-adoption
    token = cluster.login()
    sess = Session(cluster.master_url, token=token)

    _wait_experiment(cluster, eid, token, timeout=180.0)
    trials = sess.get(f"/api/v1/experiments/{eid}/trials")["trials"]
    assert trials[0]["state"] == "COMPLETED"
    assert trials[0]["total_batches"] >= 60

    rows = _training_rows(sess, trials[0]["id"])
    # Zero duplicated: no (run, batch) applied twice — retried reports
    # during the outage must have been replayed, not re-applied.
    _assert_no_duplicate_reports(rows)
    # Zero lost: the final run reaches 60, and every 4-step report since
    # its resume point is present exactly once.
    final_run = max(m["trial_run_id"] for m in rows)
    final_batches = sorted(m["total_batches"] for m in rows
                           if m["trial_run_id"] == final_run)
    assert final_batches[-1] == 60
    start = final_batches[0]
    assert final_batches == list(range(start, 61, 4)), (
        f"gaps in final run's reports: {final_batches}")


@pytest.mark.slow
def test_agent_and_task_killed_restart_from_checkpoint_within_max_restarts(
        cluster, tmp_path):
    config = _experiment_config(
        tmp_path,
        searcher={"name": "single", "metric": "val_loss",
                  "max_length": {"batches": 200}},
        extra={"max_restarts": 2},
    )
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
    eid, token = _create_experiment(cluster, config)

    import json as _json

    registry = os.path.join(cluster.tmpdir, "agent-work", "running.json")

    def _registry_pids():
        try:
            with open(registry) as f:
                return {e["pid"] for e in _json.load(f)
                        if e.get("pid", -1) > 0}
        except Exception:
            return set()

    # Force a mid-run checkpoint via pause (preempt → checkpoint → exit).
    time.sleep(4.0)
    pre_pause_pids = _registry_pids()
    cluster.api("POST", f"/api/v1/experiments/{eid}/pause", token=token)
    deadline = time.time() + 60
    while time.time() < deadline:
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=token)["trials"]
        if trials and trials[0].get("latest_checkpoint"):
            break
        time.sleep(0.5)
    assert trials[0]["latest_checkpoint"], "pause did not checkpoint"
    cluster.api("POST", f"/api/v1/experiments/{eid}/activate", token=token)

    # Wait for the RESUMED container (a fresh, live pid — not the
    # pre-pause task still draining out of the registry), then kill BOTH
    # the agent and the task process tree — a whole-node death, not a
    # reattachable agent restart.
    def _alive(pid):
        try:
            os.kill(pid, 0)
            return True
        except (ProcessLookupError, PermissionError):
            return False

    deadline = time.time() + 60
    pids = []
    while time.time() < deadline:
        pids = [p for p in _registry_pids()
                if p not in pre_pause_pids and _alive(p)]
        if pids:
            break
        time.sleep(0.3)
    assert pids, "resumed task never appeared in the agent registry"
    time.sleep(2.0)  # let it train past the checkpoint
    cluster.agent.kill()
    cluster.agent.wait()
    for pid in pids:
        try:
            os.killpg(pid, signal.SIGKILL)  # task runs as its own pgroup
        except (ProcessLookupError, PermissionError):
            pass
    time.sleep(1.0)
    cluster.start_agent()  # reattach finds the task dead → exit 137

    _wait_experiment(cluster, eid, token, timeout=240.0)
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                         token=token)["trials"]
    assert trials[0]["state"] == "COMPLETED"
    assert 1 <= trials[0]["restarts"] <= 2, (
        f"expected restart within max_restarts, got {trials[0]['restarts']}")
    logs = cluster.api(
        "GET", f"/api/v1/tasks/trial-{trials[0]['id']}/logs?offset=0",
        token=token)["logs"]
    assert any("resumed from checkpoint" in line["log"] for line in logs), (
        "restart must resume from the latest checkpoint")


@pytest.mark.slow
def test_experiment_completes_exactly_under_30pct_5xx(cluster, tmp_path):
    config = _experiment_config(
        tmp_path,
        searcher={"name": "single", "metric": "val_loss",
                  "max_length": {"batches": 24}},
        extra={"max_restarts": 2},
    )
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.02"}
    eid, token = _create_experiment(cluster, config)
    admin = cluster.login("admin")
    _arm(cluster, admin, point="api.response.5xx", mode="error",
         probability=0.3)
    sess = Session(cluster.master_url, token=token)
    try:
        deadline = time.time() + 240
        state = None
        while time.time() < deadline:
            state = sess.get(f"/api/v1/experiments/{eid}")["experiment"][
                "state"]
            if state in ("COMPLETED", "CANCELED", "ERROR"):
                break
            time.sleep(0.5)
    finally:
        _disarm_all(cluster, admin)
    assert state == "COMPLETED", f"experiment under 30% 5xx ended {state}"

    trial = sess.get(f"/api/v1/experiments/{eid}/trials")["trials"][0]
    rows = _training_rows(sess, trial["id"])
    _assert_no_duplicate_reports(rows)
    final_run = max(m["trial_run_id"] for m in rows)
    final_batches = sorted(m["total_batches"] for m in rows
                           if m["trial_run_id"] == final_run)
    start = final_batches[0]
    assert final_batches == list(range(start, 25, 4)), (
        f"lost or duplicated reports under 30% 5xx: {final_batches}")
    val = sess.get(f"/api/v1/trials/{trial['id']}/metrics",
                   params={"group": "validation"})["metrics"]
    assert [m for m in val if m["trial_run_id"] == final_run], (
        "validation report lost")
