"""Overload-safe multi-tenant master (docs/cluster-ops.md "Overload,
quotas & fair use", docs/chaos.md `db.tx.stall` / `api.overload.force_shed`).

Fast tests (tier-1): pagination abuse is refused with 400 and honest
envelopes, per-token admission control answers 429 + Retry-After, and the
idempotency-key dedupe survives group-commit batching — a retry landing in
the SAME flush window and one landing AFTER the flush both resolve to one
row and a replayed response.

Slow tests (`make chaos`): a stalled/failing DB under a keyed retry storm
turns into bounded 429/503 backpressure with EXACTLY one row per report
(zero lost, zero duplicated), and a forced brownout sheds interactive
reads with the distinct 503 while trial-critical writes pass untouched,
then recovers through the hysteresis hold once the pressure clears.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from test_platform_e2e import (
    Devcluster,
)


@pytest.fixture()
def master_only(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


def _boot(tmp_path, native_binaries, config):
    """A master booted with an overload --config (the deployment shape:
    flags still win, the file sets what flags don't cover)."""
    path = os.path.join(str(tmp_path), "master-overload.json")
    with open(path, "w") as f:
        json.dump(config, f)
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master(extra_args=("--config", path))
    return c


def _raw(cluster, method, path, body=None, token=None, headers=None,
         timeout=30.0):
    """(status, json, headers) — never raises on HTTP errors; these tests
    exist to SEE the 400/429/503s."""
    req = urllib.request.Request(
        cluster.master_url + path, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json",
                 **({"Authorization": f"Bearer {token}"} if token else {}),
                 **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return (resp.status, json.loads(resp.read() or b"{}"),
                    dict(resp.headers))
    except urllib.error.HTTPError as e:
        try:
            out = json.loads(e.read() or b"{}")
        except Exception:  # noqa: BLE001 — error bodies are advisory
            out = {}
        return e.code, out, dict(e.headers)


def _unmanaged_trial(cluster, token, name="overload", n_trials=1):
    eid = cluster.api(
        "POST", "/api/v1/experiments",
        {"unmanaged": True, "config": {"name": name}}, token=token)["id"]
    tids = [cluster.api("POST", f"/api/v1/experiments/{eid}/trials",
                        {"hparams": {}}, token=token)["id"]
            for _ in range(n_trials)]
    return eid, tids


def _metric_rows(cluster, token, tid):
    return cluster.api("GET", f"/api/v1/trials/{tid}/metrics?group=training",
                       token=token)["metrics"]


def _scrape(cluster, token, name, labels=None):
    """Sum of a /metrics series; None if absent. The scrape is
    authenticated like every API route."""
    req = urllib.request.Request(
        cluster.master_url + "/metrics",
        headers={"Authorization": f"Bearer {token}"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        text = resp.read().decode()
    total = None
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, val = line.rpartition(" ")
        if labels is None:
            if head != name and not head.startswith(name + "{"):
                continue
        elif "{" not in head or not all(
                f'{k}="{v}"' in head[head.index("{"):]
                for k, v in labels.items()):
            continue
        total = (total or 0.0) + float(val)
    return total


# ---------------------------------------------------------------------------
# Pagination: 400 on abuse, honest envelopes (covering indexes in
# migration 28 keep these index scans, not table scans).
# ---------------------------------------------------------------------------

def test_pagination_rejects_abuse(master_only):
    token = master_only.login()
    eid, (tid,) = _unmanaged_trial(master_only, token)

    for path in (
            "/api/v1/experiments?limit=0",
            "/api/v1/experiments?limit=1001",
            "/api/v1/experiments?offset=-1",
            f"/api/v1/experiments/{eid}/trials?limit=99999",
            f"/api/v1/experiments/{eid}/checkpoints?limit=0",
            f"/api/v1/trials/{tid}/checkpoints?offset=-5",
            "/api/v1/tasks?limit=0",
            # task-log limit is validated before the task lookup: the
            # abuse cap refuses even for ids that don't exist.
            "/api/v1/tasks/no-such-task/logs?limit=0",
            "/api/v1/tasks/no-such-task/logs?limit=6000",
    ):
        status, body, _ = _raw(master_only, "GET", path, token=token)
        assert status == 400, (path, status, body)
        assert "limit" in body.get("error", "") or \
            "offset" in body.get("error", ""), (path, body)


def test_pagination_envelopes(master_only):
    token = master_only.login()
    eid, tids = _unmanaged_trial(master_only, token, n_trials=25)

    out = master_only.api(
        "GET", f"/api/v1/experiments/{eid}/trials?limit=10", token=token)
    assert len(out["trials"]) == 10
    assert out["pagination"] == {"total": 25, "offset": 0, "limit": 10}

    out = master_only.api(
        "GET", f"/api/v1/experiments/{eid}/trials?limit=10&offset=20",
        token=token)
    assert len(out["trials"]) == 5
    assert out["pagination"]["total"] == 25

    out = master_only.api("GET", "/api/v1/experiments?limit=200",
                          token=token)
    assert out["pagination"]["total"] >= 1

    # Checkpoint lineage pages the same way.
    for i in range(5):
        master_only.api("POST", "/api/v1/checkpoints",
                        {"uuid": f"ovl-ckpt-{i}", "trial_id": tids[0],
                         "steps_completed": i + 1, "metadata": {},
                         "resources": {}, "state": "COMPLETED"},
                        token=token)
    out = master_only.api(
        "GET", f"/api/v1/trials/{tids[0]}/checkpoints?limit=2&offset=4",
        token=token)
    assert len(out["checkpoints"]) == 1
    assert out["pagination"] == {"total": 5, "offset": 4, "limit": 2}

    # The experiment-scoped listing (what `det checkpoint list` hits)
    # pages the same way.
    out = master_only.api(
        "GET", f"/api/v1/experiments/{eid}/checkpoints?limit=2&offset=4",
        token=token)
    assert len(out["checkpoints"]) == 1
    assert out["pagination"] == {"total": 5, "offset": 4, "limit": 2}

    out = master_only.api("GET", "/api/v1/tasks?limit=5", token=token)
    assert "pagination" in out


# ---------------------------------------------------------------------------
# Idempotency under group commit: retry in the SAME batch and AFTER the
# flush both dedupe to one row.
# ---------------------------------------------------------------------------

def test_idempotent_retry_in_same_batch_dedupes(master_only):
    token = master_only.login()
    _, (tid,) = _unmanaged_trial(master_only, token)
    body = {"group": "training", "steps_completed": 1, "trial_run_id": 0,
            "metrics": {"loss": 0.5}}
    key = "same-batch-key-1"

    results, barrier = [], threading.Barrier(2)

    def post():
        barrier.wait()
        results.append(_raw(master_only, "POST",
                            f"/api/v1/trials/{tid}/metrics", body,
                            token=token,
                            headers={"X-Idempotency-Key": key}))

    threads = [threading.Thread(target=post) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # Both callers succeed — one executed, one was held by the in-flight
    # gate and answered from the replay table — and exactly one row landed.
    assert [st for st, _, _ in results] == [200, 200], results
    assert sum(1 for _, _, h in results
               if h.get("x-idempotent-replay")) == 1, results
    assert len(_metric_rows(master_only, token, tid)) == 1


def test_idempotent_retry_after_flush_replays(master_only):
    token = master_only.login()
    _, (tid,) = _unmanaged_trial(master_only, token)
    body = {"group": "training", "steps_completed": 2, "trial_run_id": 0,
            "metrics": {"loss": 0.25}}
    key = "post-flush-key-1"

    st, _, hdrs = _raw(master_only, "POST", f"/api/v1/trials/{tid}/metrics",
                       body, token=token, headers={"X-Idempotency-Key": key})
    assert st == 200 and not hdrs.get("x-idempotent-replay")
    time.sleep(0.1)  # several flush windows past the commit
    st, _, hdrs = _raw(master_only, "POST", f"/api/v1/trials/{tid}/metrics",
                       body, token=token, headers={"X-Idempotency-Key": key})
    assert st == 200 and hdrs.get("x-idempotent-replay") == "true"
    assert len(_metric_rows(master_only, token, tid)) == 1

    # A DIFFERENT key is a different report.
    st, _, _ = _raw(master_only, "POST", f"/api/v1/trials/{tid}/metrics",
                    dict(body, steps_completed=3), token=token,
                    headers={"X-Idempotency-Key": "post-flush-key-2"})
    assert st == 200
    assert len(_metric_rows(master_only, token, tid)) == 2


# ---------------------------------------------------------------------------
# Admission control: per-token buckets, computed Retry-After.
# ---------------------------------------------------------------------------

def test_rate_limit_429_with_retry_after(tmp_path, native_binaries):
    cluster = _boot(tmp_path, native_binaries, {
        "overload": {"rate_limit": {"rps": 3, "burst": 3}}})
    try:
        token = cluster.login()
        statuses, retry_after = [], None
        for _ in range(15):
            st, body, hdrs = _raw(cluster, "GET", "/api/v1/experiments",
                                  token=token)
            statuses.append(st)
            if st == 429:
                assert body.get("rate_limited") is True
                assert body.get("token") == "determined"
                retry_after = hdrs.get("Retry-After")
        assert 429 in statuses, statuses
        assert retry_after is not None and int(retry_after) >= 1

        # The bucket refills: after waiting out the advertised delay the
        # same token is admitted again (the authenticated scrape draws
        # from the same bucket, so it also waits for the refill).
        time.sleep(min(int(retry_after), 5) + 0.2)
        assert _scrape(cluster, token, "det_rate_limited_total",
                       labels={"token": "determined"}) >= 1
        st, _, _ = _raw(cluster, "GET", "/api/v1/experiments", token=token)
        assert st == 200
    finally:
        cluster.stop()


def test_group_commit_disabled_falls_back_to_direct_writes(
        tmp_path, native_binaries):
    cluster = _boot(tmp_path, native_binaries, {
        "overload": {"group_commit": False}})
    try:
        token = cluster.login()
        _, (tid,) = _unmanaged_trial(cluster, token)
        st, _, _ = _raw(cluster, "POST", f"/api/v1/trials/{tid}/metrics",
                        {"group": "training", "steps_completed": 1,
                         "trial_run_id": 0, "metrics": {"loss": 1.0}},
                        token=token, headers={"X-Idempotency-Key": "gc-off"})
        assert st == 200
        assert len(_metric_rows(cluster, token, tid)) == 1
    finally:
        cluster.stop()


# ---------------------------------------------------------------------------
# Chaos (-m slow): stalled/failing DB → backpressure, exactly-once rows;
# forced brownout → sheds reads, never trial-critical writes, recovers.
# ---------------------------------------------------------------------------

def _keyed_storm(cluster, token, tid, n_threads, per_thread, base_step,
                 statuses):
    """Concurrent keyed reports retrying 429/503 per Retry-After, ONE key
    per report across its retries (the harness Session contract)."""
    errors = []
    lock = threading.Lock()

    def worker(wi):
        try:
            for i in range(per_thread):
                step = base_step + wi * per_thread + i
                key = f"storm-{base_step}-{wi}-{i}"
                body = {"group": "training", "steps_completed": step,
                        "trial_run_id": 0, "metrics": {"loss": 1.0}}
                deadline = time.time() + 120
                while True:
                    st, _, hdrs = _raw(
                        cluster, "POST", f"/api/v1/trials/{tid}/metrics",
                        body, token=token,
                        headers={"X-Idempotency-Key": key})
                    with lock:
                        statuses.append(st)
                    if st == 200:
                        break
                    if st not in (429, 503) or time.time() > deadline:
                        raise RuntimeError(f"report got {st}")
                    ra = hdrs.get("Retry-After")
                    time.sleep(min(float(ra) if ra else 0.2, 2.0))
        except Exception as e:  # noqa: BLE001 — re-raised after join
            with lock:
                errors.append(str(e))

    threads = [threading.Thread(target=worker, args=(wi,))
               for wi in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(errors[0])


@pytest.mark.slow
def test_db_stall_backpressure_zero_lost_zero_duplicated(
        tmp_path, native_binaries):
    # Tiny queue cap: a stalled DB must visibly refuse (429), not queue
    # without bound.
    cluster = _boot(tmp_path, native_binaries, {
        "overload": {"group_commit": {"enabled": True, "window_ms": 5,
                                      "queue_cap": 4}}})
    try:
        token = cluster.login()
        admin = cluster.login("admin")
        _, (tid,) = _unmanaged_trial(cluster, token)
        statuses = []

        # Phase 1: every transaction stalls 250ms — flushes back up, the
        # cap turns into 429 + Retry-After, retries keep their keys.
        cluster.api("POST", "/api/v1/debug/faults",
                    {"point": "db.tx.stall", "mode": "delay-250"},
                    token=admin)
        _keyed_storm(cluster, token, tid, 8, 4, 0, statuses)

        # Phase 2: transactions FAIL outright (counted arm: the storm must
        # outlive it) — whole batches fall back to standalone retry, the
        # still-failing ones answer 503, clients retry the same key.
        cluster.api("POST", "/api/v1/debug/faults",
                    {"point": "db.tx.stall", "mode": "error", "count": 12},
                    token=admin)
        _keyed_storm(cluster, token, tid, 8, 2, 1000, statuses)

        cluster.api("POST", "/api/v1/debug/faults", {"mode": "off"},
                    token=admin)

        refused = sum(1 for s in statuses if s in (429, 503))
        assert refused > 0, (
            "a stalled DB was absorbed silently — expected 429/503 "
            f"backpressure (statuses: {sorted(set(statuses))})")

        # Zero lost, zero duplicated: exactly one row per report.
        steps = [r["total_batches"]
                 for r in _metric_rows(cluster, token, tid)]
        assert len(steps) == 48 and len(set(steps)) == 48, (
            f"{len(steps)} rows, {len(set(steps))} unique — expected 48/48")
    finally:
        cluster.stop()


@pytest.mark.slow
def test_forced_brownout_sheds_reads_never_trial_writes(
        tmp_path, native_binaries):
    cluster = _boot(tmp_path, native_binaries, {
        "overload": {"shedding": {"recover_hold_seconds": 0.3}}})
    try:
        token = cluster.login()
        admin = cluster.login("admin")
        _, (tid,) = _unmanaged_trial(cluster, token)

        cluster.api("POST", "/api/v1/debug/faults",
                    {"point": "api.overload.force_shed", "mode": "error"},
                    token=admin)
        # The brownout decision runs on the scheduler tick (200ms).
        deadline = time.time() + 5
        status, body, hdrs = None, {}, {}
        while time.time() < deadline and status != 503:
            status, body, hdrs = _raw(cluster, "GET", "/api/v1/experiments",
                                      token=token)
            time.sleep(0.05)
        assert status == 503, "brownout never engaged"
        assert body.get("shed") is True
        assert body.get("route_family") == "experiments"
        assert int(hdrs.get("Retry-After", "0")) >= 1

        # Trial-critical writes pass untouched while reads shed.
        st, _, _ = _raw(cluster, "POST", f"/api/v1/trials/{tid}/metrics",
                        {"group": "training", "steps_completed": 7,
                         "trial_run_id": 0, "metrics": {"loss": 0.1}},
                        token=token,
                        headers={"X-Idempotency-Key": "brownout-write"})
        assert st == 200
        # ...and so do trial reads (only the interactive list families shed).
        st, _, _ = _raw(cluster, "GET", f"/api/v1/trials/{tid}/metrics",
                        token=token)
        assert st == 200

        assert _scrape(cluster, token, "det_master_shed_total",
                       labels={"route_family": "experiments"}) >= 1
        assert not _scrape(cluster, token, "det_master_shed_total",
                           labels={"route_family": "trials"})

        # Recovery hysteresis: disarm, and the shed clears after the
        # signals hold below the recover thresholds for the hold window.
        cluster.api("POST", "/api/v1/debug/faults", {"mode": "off"},
                    token=admin)
        deadline = time.time() + 10
        while time.time() < deadline:
            status, _, _ = _raw(cluster, "GET", "/api/v1/experiments",
                                token=token)
            if status == 200:
                break
            time.sleep(0.1)
        assert status == 200, "brownout never recovered after disarm"
    finally:
        cluster.stop()
