#!/usr/bin/env python
"""ASHA scheduler throughput: trials/hour through the real master+agent
(BASELINE.md: "ASHA trials/hour — track & report ... adaptive_asha HP
search scheduling concurrent trials across pod sub-slices").

Prints ONE JSON line. Measures platform overhead (scheduling, allocation,
process launch, searcher round-trips, checkpoint/metric reporting) with an
adaptive_asha search of near-instant trials on a devcluster with artificial
slots — the master/agent cost per trial, not model compute. Run with
JAX_PLATFORMS=cpu; BENCH_ASHA_DEBUG=1 prints progress."""

import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def _wait_experiment(cluster, token, eid, timeout=900):
    deadline = time.time() + timeout
    state = None
    while time.time() < deadline:
        e = cluster.api("GET", f"/api/v1/experiments/{eid}",
                        token=token)["experiment"]
        state = e["state"]
        if state in ("COMPLETED", "ERROR", "CANCELED"):
            break
        if os.environ.get("BENCH_ASHA_DEBUG"):
            print(f"  exp {eid}: state={state} progress={e.get('progress')}",
                  file=sys.stderr)
        time.sleep(1.0)
    if state != "COMPLETED":
        raise RuntimeError(f"experiment {eid} finished {state}")


def run_compile_reuse(cluster, token, tmp) -> dict:
    """Compile-bound trials (real jitted GPT-2 step), cache off vs on:
    the persistent XLA compilation cache (agent-injected DET_XLA_CACHE_DIR)
    lets identical-shape rung trials skip compile — the dominant cost of
    short ASHA trials (SURVEY hard part b)."""
    import determined_tpu.cli as cli

    model_def = cli._tar_context(
        os.path.join(REPO, "tests", "fixtures", "platform"))

    def launch(cache_on: bool) -> dict:
        config = {
            "name": f"bench-asha-jit-{'cache' if cache_on else 'nocache'}",
            "entrypoint": "python3 train_jit.py",
            "searcher": {
                "name": "random",
                "metric": "val_loss",
                "smaller_is_better": True,
                "max_length": {"batches": 4},
                "max_trials": 5,
                # Sequential: concurrent compile-heavy CPU trials
                # oversubscribe the host and drown the reuse signal.
                "max_concurrent_trials": 1,
            },
            "hyperparameters": {
                "lr": {"type": "log", "minval": -4, "maxval": -2},
            },
            "checkpoint_storage": {"type": "shared_fs",
                                   "host_path": os.path.join(tmp, "ckpts")},
            "resources": {"slots_per_trial": 1},
            "max_restarts": 0,
        }
        if not cache_on:
            # Empty override disables the agent-injected cache dir.
            config["environment"] = {
                "environment_variables": ["DET_XLA_CACHE_DIR="]}
        t0 = time.time()
        eid = cluster.api(
            "POST", "/api/v1/experiments",
            {"config": config, "model_definition": model_def,
             "activate": True}, token=token)["id"]
        _wait_experiment(cluster, token, eid)
        wall = time.time() - t0
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=token)["trials"]
        walls, compiles = [], []
        for t in trials:
            for m in cluster.api(
                    "GET", f"/api/v1/trials/{t['id']}/metrics",
                    token=token)["metrics"]:
                if m["group_name"] == "validation":
                    mm = m["metrics"]
                    if "trial_wall_s" in mm:
                        walls.append(float(mm["trial_wall_s"]))
                        compiles.append(float(mm.get("compile_s", 0)))
        return {"wall_s": wall, "n_trials": len(trials),
                "trials_per_hour": len(trials) / wall * 3600,
                "trial_walls": sorted(walls),
                "compile_s": sorted(compiles)}

    nocache = launch(cache_on=False)
    cached = launch(cache_on=True)
    # Warm trials = all but the cold compiles of the first wave; the
    # median of the cached run vs the nocache median is the per-trial
    # reuse factor (robust to the cold outliers).
    per_trial = (statistics.median(nocache["trial_walls"]) /
                 statistics.median(cached["trial_walls"])
                 if cached["trial_walls"] and nocache["trial_walls"] else 0)
    return {
        "nocache_trials_per_hour": round(nocache["trials_per_hour"], 1),
        "cached_trials_per_hour": round(cached["trials_per_hour"], 1),
        "wall_speedup": round(cached["trials_per_hour"] /
                              nocache["trials_per_hour"], 2),
        "per_trial_speedup": round(per_trial, 2),
        "nocache_median_trial_s": round(
            statistics.median(nocache["trial_walls"]), 1)
        if nocache["trial_walls"] else None,
        "cached_median_trial_s": round(
            statistics.median(cached["trial_walls"]), 1)
        if cached["trial_walls"] else None,
        "nocache_median_compile_s": round(
            statistics.median(nocache["compile_s"]), 1)
        if nocache["compile_s"] else None,
        "cached_median_compile_s": round(
            statistics.median(cached["compile_s"]), 1)
        if cached["compile_s"] else None,
    }


def run_compile_farm(cluster, token, tmp) -> dict:
    """Compile-farm on/off A/B (docs/compile-farm.md, ROADMAP item 5):
    compile-bound Trainer trials (real jitted GPT-2 step, train_farm
    fixture) in three arms —

      nocache  persistent XLA cache AND farm disabled (every trial pays
               the full trace+compile)
      cache    persistent XLA cache only (the pre-farm baseline: warm
               trials still pay trace + cache deserialize)
      farm     artifact exchange on (default): the first trial uploads
               its serialized executable, successors deserialize it via
               the agent pre-warm and skip trace+lowering+compile

    The headline is cached_median_compile_s: median first-step cost of
    the farm arm's WARM trials (target ~0; acceptance <= 0.5s)."""
    import determined_tpu.cli as cli

    model_def = cli._tar_context(
        os.path.join(REPO, "tests", "fixtures", "compile_farm"))

    def launch(arm: str) -> dict:
        config = {
            "name": f"bench-compile-farm-{arm}",
            "entrypoint": "python3 train_farm.py",
            "searcher": {
                "name": "random",
                "metric": "val_loss",
                "smaller_is_better": True,
                "max_length": {"batches": 4},
                "max_trials": 5,
                # Sequential: concurrent compile-heavy CPU trials
                # oversubscribe the host and drown the reuse signal.
                "max_concurrent_trials": 1,
            },
            # Const hparams: one signature across the arm, the shape an
            # ASHA rung re-runs by the dozen.
            "hyperparameters": {"lr": 0.001, "global_batch_size": 8},
            "checkpoint_storage": {"type": "shared_fs",
                                   "host_path": os.path.join(tmp, "ckpts")},
            "resources": {"slots_per_trial": 1},
            "max_restarts": 0,
        }
        env_vars = []
        if arm == "nocache":
            env_vars.append("DET_XLA_CACHE_DIR=")
        if arm in ("nocache", "cache"):
            config["compile"] = {"enabled": False}
        if env_vars:
            config["environment"] = {"environment_variables": env_vars}
        t0 = time.time()
        eid = cluster.api(
            "POST", "/api/v1/experiments",
            {"config": config, "model_definition": model_def,
             "activate": True}, token=token)["id"]
        _wait_experiment(cluster, token, eid)
        wall = time.time() - t0
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=token)["trials"]
        compiles, hits = [], []
        for t in trials:
            for m in cluster.api(
                    "GET", f"/api/v1/trials/{t['id']}/metrics",
                    token=token)["metrics"]:
                mm = m["metrics"]
                if m["group_name"] == "training" and "compile_ms" in mm:
                    compiles.append(float(mm["compile_ms"]) / 1000.0)
                    hits.append(float(mm.get("compile_cache_hit", 0)))
                    break
        return {"wall_s": wall, "n_trials": len(trials),
                "trials_per_hour": len(trials) / wall * 3600,
                "compile_s": compiles, "cache_hits": hits}

    nocache = launch("nocache")
    cache = launch("cache")
    farm = launch("farm")

    def warm_median(arm):
        # Warm trials = all but the cold first compile of the wave.
        warm = sorted(arm["compile_s"])[:-1] if len(arm["compile_s"]) > 1 \
            else arm["compile_s"]
        return round(statistics.median(warm), 3) if warm else None

    farm_hits = [c for c, h in zip(farm["compile_s"], farm["cache_hits"])
                 if h >= 1.0]
    return {
        "nocache_trials_per_hour": round(nocache["trials_per_hour"], 1),
        "cache_trials_per_hour": round(cache["trials_per_hour"], 1),
        "farm_trials_per_hour": round(farm["trials_per_hour"], 1),
        "farm_vs_cache_speedup": round(
            farm["trials_per_hour"] / cache["trials_per_hour"], 2),
        "farm_vs_nocache_speedup": round(
            farm["trials_per_hour"] / nocache["trials_per_hour"], 2),
        "nocache_median_compile_s": warm_median(nocache),
        "cache_median_compile_s": warm_median(cache),
        # THE headline (ROADMAP item 5: cached_median_compile_s -> ~0).
        "cached_median_compile_s": round(
            statistics.median(farm_hits), 3) if farm_hits else None,
        "farm_cache_hits": int(sum(farm["cache_hits"])),
        "farm_trials": farm["n_trials"],
    }


def _api_raw(cluster, method, path, body=None, token=None, headers=None,
             timeout=60.0):
    """cluster.api with custom headers (X-Idempotency-Key) + wall timing."""
    import urllib.request

    req = urllib.request.Request(
        cluster.master_url + path, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json",
                 **({"Authorization": f"Bearer {token}"} if token else {}),
                 **(headers or {})})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        out = json.loads(resp.read() or b"{}")
    return out, (time.perf_counter() - t0) * 1e3


def run_phase_breakdown(cluster, token, tmp, trial_id) -> dict:
    """Per-phase master-side timings for the r5 ASHA regression hunt
    (ROADMAP item 1): the four suspects measured in isolation against the
    live master, so the next bench run can attribute the drop instead of
    re-guessing. Instrumentation only — the fix is a later PR.

      submit_preflight_ms    POST /api/v1/experiments (the create path
                             runs the native preflight gate)
      ckpt_partial_ms /      the two-phase checkpoint registry writes
      ckpt_commit_ms         (PARTIAL report, then the COMPLETED flip)
      idempotency_replay_ms  the same POST re-sent with the same
                             X-Idempotency-Key — answered from the
                             replay table, no re-execution
      preempt_fanout_ms      pause → preemption long-poll delivery on a
                             live allocation
    """
    import statistics as stats
    import threading
    import uuid

    import determined_tpu.cli as cli

    model_def = cli._tar_context(
        os.path.join(REPO, "tests", "fixtures", "platform"))
    out = {}

    # 1) submit + preflight gate (paused: no scheduling noise).
    config = {
        "name": "bench-phase-submit",
        "entrypoint": "python3 train.py",
        "searcher": {"name": "single", "metric": "val_loss",
                     "max_length": {"batches": 1}},
        "hyperparameters": {"lr": 0.1},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": os.path.join(tmp, "ckpts")},
        "resources": {"slots_per_trial": 1},
    }
    submits = []
    for _ in range(5):
        _, ms = _api_raw(cluster, "POST", "/api/v1/experiments",
                         {"config": config, "model_definition": model_def,
                          "activate": False}, token=token)
        submits.append(ms)
    out["submit_preflight_ms"] = round(stats.median(submits), 2)

    # 2) checkpoint two-phase commit: PARTIAL then COMPLETED, timed apart.
    partials, commits, replays = [], [], []
    for _ in range(5):
        uid = f"bench-phase-{uuid.uuid4().hex[:8]}"
        body = {"uuid": uid, "trial_id": trial_id, "steps_completed": 1,
                "metadata": {}, "resources": {}, "state": "PARTIAL"}
        _, ms = _api_raw(cluster, "POST", "/api/v1/checkpoints", body,
                         token=token)
        partials.append(ms)
        body["state"] = "COMPLETED"
        key = uuid.uuid4().hex
        _, ms = _api_raw(cluster, "POST", "/api/v1/checkpoints", body,
                         token=token, headers={"X-Idempotency-Key": key})
        commits.append(ms)
        # 3) replay lookup: the identical POST again — answered from the
        # idempotency table.
        _, ms = _api_raw(cluster, "POST", "/api/v1/checkpoints", body,
                         token=token, headers={"X-Idempotency-Key": key})
        replays.append(ms)
    out["ckpt_partial_ms"] = round(stats.median(partials), 2)
    out["ckpt_commit_ms"] = round(stats.median(commits), 2)
    out["idempotency_replay_ms"] = round(stats.median(replays), 2)

    # 4) preemption-signal fan-out: pause → long-poll delivery.
    config = dict(config, name="bench-phase-preempt")
    config["searcher"] = {"name": "single", "metric": "val_loss",
                          "max_length": {"batches": 500}}
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
    eid = cluster.api("POST", "/api/v1/experiments",
                      {"config": config, "model_definition": model_def,
                       "activate": True}, token=token)["id"]
    alloc_id = None
    deadline = time.time() + 60
    while time.time() < deadline and alloc_id is None:
        for j in cluster.api("GET", "/api/v1/job-queues",
                             token=token)["jobs"]:
            if j.get("experiment_id") == eid and \
                    j.get("state") == "SCHEDULED":
                a = cluster.api(
                    "GET", f"/api/v1/allocations/{j['allocation_id']}",
                    token=token)["allocation"]
                if a.get("state") == "RUNNING":
                    alloc_id = j["allocation_id"]
        time.sleep(0.2)
    if alloc_id is not None:
        got = {}

        def _poll():
            try:
                got["resp"], got["ms"] = _api_raw(
                    cluster, "GET",
                    f"/api/v1/allocations/{alloc_id}/signals/preemption"
                    "?timeout_seconds=30", token=token, timeout=45)
            except Exception as e:  # noqa: BLE001 — breakdown is advisory
                got["error"] = str(e)

        t = threading.Thread(target=_poll)
        t.start()
        time.sleep(0.3)  # the long-poll must be parked before the pause
        t0 = time.perf_counter()
        cluster.api("POST", f"/api/v1/experiments/{eid}/pause",
                    token=token)
        t.join(timeout=45)
        if got.get("resp", {}).get("preempt"):
            out["preempt_fanout_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
        else:
            out["preempt_fanout_error"] = got.get(
                "error", "no preempt signal delivered")
    else:
        out["preempt_fanout_error"] = "trial never reached RUNNING"
    cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=token)
    return out


def _req_status(cluster, method, path, body=None, token=None, headers=None,
                timeout=60.0):
    """_api_raw that never raises on HTTP errors: (status, json, ms,
    headers) — the overload bench needs to SEE 429/503, not die on them."""
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        cluster.master_url + path, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json",
                 **({"Authorization": f"Bearer {token}"} if token else {}),
                 **(headers or {})})
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return (resp.status, json.loads(resp.read() or b"{}"),
                    (time.perf_counter() - t0) * 1e3, dict(resp.headers))
    except urllib.error.HTTPError as e:
        try:
            out = json.loads(e.read() or b"{}")
        except Exception:  # noqa: BLE001 — error bodies are advisory
            out = {}
        return (e.code, out, (time.perf_counter() - t0) * 1e3,
                dict(e.headers))


def _retrying_post(cluster, path, body, token, key, deadline_s=180.0,
                   statuses=None):
    """POST with a STABLE X-Idempotency-Key, retrying 429/503/5xx per
    Retry-After — the harness Session's contract inlined so the bench can
    count every refusal it absorbed. Returns (final_status, json, ms)."""
    deadline = time.time() + deadline_s
    while True:
        st, out, ms, hdrs = _req_status(
            cluster, "POST", path, body, token=token,
            headers={"X-Idempotency-Key": key})
        if statuses is not None:
            statuses.append(st)
        if st != 429 and st < 500:
            return st, out, ms
        if time.time() > deadline:
            raise RuntimeError(
                f"retry deadline exceeded on {path} (last status {st})")
        ra = hdrs.get("Retry-After")
        time.sleep(min(float(ra) if ra else 0.2, 2.0))


def _prom_value(cluster, token, name, labels=None):
    """Sum of a metric's samples on the authenticated GET /metrics; None
    if absent. `labels` filters to series whose label set contains every
    given key="value" pair (det_master_shed_total{route_family="trials"})."""
    import urllib.request

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
        else:
            if "{" not in head:
                continue
            labelstr = head[head.index("{"):]
            if not all(f'{k}="{v}"' in labelstr for k, v in labels.items()):
                continue
        total = (total or 0.0) + float(val)
    return total


def _mk_trials(cluster, token, n_exp, trials_per_exp, name="bench-load"):
    """Unmanaged experiments + library-created trials: registration-only
    rows, no agent or scheduling — the cheapest way to put 1k+ live trial
    rows behind the API. One thread per experiment."""
    import threading

    tids, errors = [], []
    lock = threading.Lock()

    def one_exp(i):
        try:
            eid = cluster.api(
                "POST", "/api/v1/experiments",
                {"unmanaged": True, "config": {"name": f"{name}-{i}"}},
                token=token)["id"]
            local = []
            for _ in range(trials_per_exp):
                local.append(cluster.api(
                    "POST", f"/api/v1/experiments/{eid}/trials",
                    {"hparams": {}}, token=token)["id"])
            with lock:
                tids.extend(local)
        except Exception as e:  # noqa: BLE001 — re-raised after join
            with lock:
                errors.append(str(e))

    threads = [threading.Thread(target=one_exp, args=(i,))
               for i in range(n_exp)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"trial setup failed: {errors[0]}")
    return tids


def _metric_storm(cluster, token, tids, n_threads, per_thread,
                  statuses=None, base_step=0):
    """Concurrent metric reports round-robined over `tids`, each with a
    unique idempotency key, retrying refusals. Returns per-report wall
    latencies (ms), INCLUDING retry waits — backpressure the client
    absorbs is latency the client sees."""
    import threading
    import uuid

    lat, errors = [], []
    lock = threading.Lock()

    def worker(wi):
        local = []
        try:
            for i in range(per_thread):
                n = wi * per_thread + i
                tid = tids[n % len(tids)]
                body = {"group": "training",
                        "steps_completed": base_step + n,
                        "trial_run_id": 0,
                        "metrics": {"loss": 1.0 / (n + 1)}}
                t0 = time.perf_counter()
                st, _, _ = _retrying_post(
                    cluster, f"/api/v1/trials/{tid}/metrics", body, token,
                    uuid.uuid4().hex, statuses=statuses)
                if st != 200:
                    raise RuntimeError(f"metric report got {st}")
                local.append((time.perf_counter() - t0) * 1e3)
        except Exception as e:  # noqa: BLE001 — re-raised after join
            with lock:
                errors.append(str(e))
            return
        with lock:
            lat.extend(local)

    threads = [threading.Thread(target=worker, args=(wi,))
               for wi in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"metric storm failed: {errors[0]}")
    return lat


def _p99(lat):
    lat = sorted(lat)
    return round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2)


def run_master_load() -> dict:
    """`make bench-master-load` (ISSUE 20 acceptance gates, docs/
    cluster-ops.md "Overload, quotas & fair use"): the master under
    multi-tenant overload, with every gate COUNTED or MEASURED — never
    inferred from timing alone.

      1. group-commit tx ratio   det_master_db_tx_total delta per report,
                                 batching off vs on — gate >= 5x fewer
      2. write p99 under load    1k+ live trials + reader threads polling
                                 the paginated lists — gate p99 <= 250ms
      3. db.tx.stall chaos       stalled AND failing DB under a keyed
                                 retry storm — gate: backpressure seen
                                 (429/503 > 0) and EXACTLY one row per
                                 report (zero lost, zero duplicated)
      4. tenant isolation        adversarial tenant at ~10x fair share
                                 ignoring Retry-After — gates: the good
                                 tenant's p99 stays under the SOLO gate,
                                 the adversary is rate-limited (counter
                                 > 0), trial-critical routes never shed
                                 (det_master_shed_total{route_family=
                                 "trials"} absent/0)
    """
    import statistics as stats

    subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                   check=True, capture_output=True)
    from tests.test_platform_e2e import Devcluster

    debug = os.environ.get("BENCH_ASHA_DEBUG")

    def note(msg):
        if debug:
            print(f"  {msg}", file=sys.stderr)

    def boot(tag, overload_cfg):
        tmp = tempfile.mkdtemp(prefix=f"bench_master_load_{tag}_")
        cfg_path = os.path.join(tmp, "master.json")
        with open(cfg_path, "w") as f:
            json.dump({"overload": overload_cfg}, f)
        cluster = Devcluster(tmp, os.path.join(REPO, "native", "bin"))
        cluster.start_master(extra_args=("--config", cfg_path))
        return cluster, cluster.login()

    out = {}
    gate_ms = 250.0

    # -- 1) group-commit transaction ratio, COUNTED ------------------------
    # Same concurrent workload against batching off vs on; the ratio is
    # transactions PER REPORT from det_master_db_tx_total, so background
    # scheduler ticks are noise on 600 reports, not part of the number.
    n_reports = 600
    cluster, token = boot("off", {"group_commit": False})
    try:
        tids = _mk_trials(cluster, token, 2, 4, name="bench-txoff")
        tx0 = _prom_value(cluster, token, "det_master_db_tx_total") or 0.0
        _metric_storm(cluster, token, tids, 12, n_reports // 12)
        tx_off = (_prom_value(cluster, token, "det_master_db_tx_total") or 0.0) - tx0
    finally:
        cluster.stop()
    note(f"tx off: {tx_off} for {n_reports} reports")

    cluster, token = boot("on", {
        "group_commit": {"enabled": True, "window_ms": 5, "max_batch": 256,
                         "queue_cap": 4096}})
    try:
        # 2) ...and the SAME master then carries 1k+ trials + readers.
        tids = _mk_trials(cluster, token, 8, 150, name="bench-txon")
        tx0 = _prom_value(cluster, token, "det_master_db_tx_total") or 0.0
        lat_on = _metric_storm(cluster, token, tids, 12, n_reports // 12)
        tx_on = (_prom_value(cluster, token, "det_master_db_tx_total") or 0.0) - tx0
        note(f"tx on: {tx_on} for {n_reports} reports")

        per_off = tx_off / n_reports
        per_on = max(tx_on, 1.0) / n_reports
        tx_ratio = per_off / per_on
        out["tx_per_report_off"] = round(per_off, 3)
        out["tx_per_report_on"] = round(per_on, 3)
        out["tx_ratio"] = round(tx_ratio, 1)
        if tx_ratio < 5.0:
            raise RuntimeError(
                f"group-commit tx ratio {tx_ratio:.1f}x below the 5x gate "
                f"(off {tx_off:.0f} vs on {tx_on:.0f} transactions for "
                f"{n_reports} reports each)")

        # -- 2) write p99 with 1k+ trials + concurrent readers -------------
        import threading

        stop = threading.Event()
        read_counts = {"n": 0, "errors": 0}
        rlock = threading.Lock()

        def reader():
            import random
            rng = random.Random(0xDE7)
            while not stop.is_set():
                offset = rng.randrange(0, max(1, len(tids) - 200))
                st1, exps, _, _ = _req_status(
                    cluster, "GET", "/api/v1/experiments?limit=200",
                    token=token)
                eid = (exps.get("experiments") or [{}])[0].get("id", 1)
                st2, _, _, _ = _req_status(
                    cluster, "GET",
                    f"/api/v1/experiments/{eid}/trials"
                    f"?limit=200&offset={offset % 800}",
                    token=token)
                with rlock:
                    read_counts["n"] += 2
                    read_counts["errors"] += (st1 != 200) + (st2 != 200)

        readers = [threading.Thread(target=reader) for _ in range(4)]
        for t in readers:
            t.start()
        try:
            lat_loaded = _metric_storm(cluster, token, tids, 16, 40,
                                       base_step=100000)
        finally:
            stop.set()
            for t in readers:
                t.join()
        out["write_p50_ms"] = round(stats.median(lat_loaded), 2)
        out["write_p99_ms"] = _p99(lat_loaded)
        out["write_p99_unloaded_ms"] = _p99(lat_on)
        out["trials"] = len(tids)
        out["reader_requests"] = read_counts["n"]
        if read_counts["errors"]:
            raise RuntimeError(
                f"{read_counts['errors']} reader requests failed during the "
                f"write storm (of {read_counts['n']})")
        if out["write_p99_ms"] > gate_ms:
            raise RuntimeError(
                f"write p99 {out['write_p99_ms']}ms exceeds the {gate_ms}ms "
                f"gate with {len(tids)} trials + 4 readers")
        batch_n = _prom_value(cluster, token, "det_master_write_batch_events_count")
        batch_sum = _prom_value(cluster, token, "det_master_write_batch_events_sum")
        out["mean_batch_size"] = round(batch_sum / batch_n, 1) if batch_n \
            else None
    finally:
        cluster.stop()

    # -- 3) db.tx.stall: zero lost, zero duplicated ------------------------
    # Tiny queue cap so a stalled DB visibly refuses (429) instead of
    # queueing; then an ERROR storm so whole batches fail and fall back to
    # standalone retry. Every report keeps ONE key across its retries; the
    # row count at the end is the whole proof.
    cluster, token = boot("stall", {
        "group_commit": {"enabled": True, "window_ms": 5, "queue_cap": 4}})
    try:
        admin = cluster.login("admin")
        tids = _mk_trials(cluster, token, 1, 4, name="bench-stall")
        statuses = []
        cluster.api("POST", "/api/v1/debug/faults",
                    {"point": "db.tx.stall", "mode": "delay-300"},
                    token=admin)
        _metric_storm(cluster, token, tids[:1], 8, 5, statuses=statuses)
        depth = _prom_value(cluster, token, "det_master_write_queue_depth")
        cluster.api("POST", "/api/v1/debug/faults",
                    {"point": "db.tx.stall", "mode": "error", "count": 20},
                    token=admin)
        _metric_storm(cluster, token, tids[:1], 8, 5, statuses=statuses,
                      base_step=1000)
        cluster.api("POST", "/api/v1/debug/faults", {"mode": "off"},
                    token=admin)
        rows = cluster.api(
            "GET", f"/api/v1/trials/{tids[0]}/metrics?group=training",
            token=token)["metrics"]
        steps = [r["total_batches"] for r in rows]
        out["stall_reports"] = 80
        out["stall_rows"] = len(rows)
        out["stall_backpressure_responses"] = sum(
            1 for s in statuses if s in (429, 503))
        out["stall_queue_depth_seen"] = depth
        if len(steps) != 80 or len(set(steps)) != 80:
            raise RuntimeError(
                f"db.tx.stall storm: expected exactly 80 unique metric rows, "
                f"got {len(steps)} ({len(set(steps))} unique) — "
                f"lost or duplicated reports")
        if out["stall_backpressure_responses"] == 0:
            raise RuntimeError(
                "db.tx.stall storm refused nothing: the stalled DB was "
                "absorbed silently instead of surfacing 429/503 backpressure")
    finally:
        cluster.stop()

    # -- 4) tenant isolation under an adversarial neighbor -----------------
    cluster, token = boot("tenant", {
        "group_commit": {"enabled": True, "window_ms": 5},
        "rate_limit": {"rps": 50, "burst": 100,
                       "tenant_weights": {"good": 4.0, "noisy": 1.0}}})
    try:
        admin = cluster.login("admin")
        for user in ("good", "noisy"):
            cluster.api("POST", "/api/v1/users",
                        {"username": user, "role": "user"}, token=admin)
        good_tok = cluster.login("good")
        noisy_tok = cluster.login("noisy")
        good_tids = _mk_trials(cluster, good_tok, 1, 8, name="bench-good")
        noisy_tids = _mk_trials(cluster, noisy_tok, 1, 8, name="bench-noisy")

        def good_workload():
            """Paced well-behaved tenant: ~40 writes + 40 reads, 2 threads
            with a think-time sleep — comfortably inside 4x fair share."""
            import threading

            lats, errors = [], []
            lock = threading.Lock()

            def worker(wi):
                import uuid as _uuid
                try:
                    for i in range(20):
                        body = {"group": "training",
                                "steps_completed": wi * 1000 + i,
                                "trial_run_id": 0, "metrics": {"loss": 0.5}}
                        t0 = time.perf_counter()
                        st, _, _ = _retrying_post(
                            cluster,
                            f"/api/v1/trials/{good_tids[wi]}/metrics",
                            body, good_tok, _uuid.uuid4().hex)
                        w = (time.perf_counter() - t0) * 1e3
                        st2, _, r, _ = _req_status(
                            cluster, "GET", "/api/v1/experiments?limit=50",
                            token=good_tok)
                        if st != 200 or st2 != 200:
                            raise RuntimeError(
                                f"good tenant refused: {st}/{st2}")
                        with lock:
                            lats.extend([w, r])
                        time.sleep(0.02)
                except Exception as e:  # noqa: BLE001 — re-raised below
                    with lock:
                        errors.append(str(e))

            threads = [threading.Thread(target=worker, args=(wi,))
                       for wi in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise RuntimeError(f"good-tenant workload: {errors[0]}")
            return lats

        solo = good_workload()

        # The adversary: 12 threads, no pacing, Retry-After ignored —
        # ~10x its fair share in attempted requests.
        import threading

        stop = threading.Event()
        noisy_counts = {"sent": 0, "limited": 0}
        nlock = threading.Lock()

        def flood(wi):
            i = 0
            while not stop.is_set():
                if i % 2 == 0:
                    st, _, _, _ = _req_status(
                        cluster, "GET", "/api/v1/experiments?limit=200",
                        token=noisy_tok, timeout=30)
                else:
                    st, _, _, _ = _req_status(
                        cluster, "POST",
                        f"/api/v1/trials/{noisy_tids[wi % 8]}/metrics",
                        {"group": "training", "steps_completed": i,
                         "trial_run_id": 0, "metrics": {"x": 1.0}},
                        token=noisy_tok, timeout=30)
                with nlock:
                    noisy_counts["sent"] += 1
                    noisy_counts["limited"] += (st == 429)
                i += 1

        flooders = [threading.Thread(target=flood, args=(wi,))
                    for wi in range(12)]
        for t in flooders:
            t.start()
        try:
            time.sleep(1.0)  # let the flood saturate its bucket first
            contended = good_workload()
        finally:
            stop.set()
            for t in flooders:
                t.join()

        out["good_p99_solo_ms"] = _p99(solo)
        out["good_p99_contended_ms"] = _p99(contended)
        out["noisy_requests"] = noisy_counts["sent"]
        out["noisy_rate_limited"] = noisy_counts["limited"]
        limited_metric = _prom_value(cluster, token, "det_rate_limited_total",
                                     labels={"token": "noisy"})
        shed_trials = _prom_value(cluster, token, "det_master_shed_total",
                                  labels={"route_family": "trials"})
        out["rate_limited_total_noisy"] = limited_metric
        out["shed_total_trials_family"] = shed_trials or 0
        if not limited_metric or noisy_counts["limited"] == 0:
            raise RuntimeError(
                "adversarial tenant was never rate-limited "
                f"(sent {noisy_counts['sent']}, counter {limited_metric})")
        if shed_trials:
            raise RuntimeError(
                f"trial-critical routes were shed {shed_trials} times — "
                f"brownout must never touch the trials family")
        if out["good_p99_contended_ms"] > gate_ms:
            raise RuntimeError(
                f"good tenant p99 {out['good_p99_contended_ms']}ms under an "
                f"adversarial neighbor exceeds the {gate_ms}ms solo gate "
                f"(solo: {out['good_p99_solo_ms']}ms)")
    finally:
        cluster.stop()

    return {
        "metric": "master_load_tx_ratio",
        "value": out["tx_ratio"],
        "unit": "hot-path DB transactions per report, batching off/on "
                "(counted via det_master_db_tx_total; gate >= 5x)",
        "vs_baseline": out["tx_ratio"],
        "detail": out,
    }


def run() -> dict:
    subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                   check=True, capture_output=True)
    # Reuse the e2e harness's devcluster (readiness checks, teardown).
    from tests.test_platform_e2e import Devcluster

    import determined_tpu.cli as cli

    from determined_tpu.compile.runtime import DEFAULT_CACHE_DIR

    tmp = tempfile.mkdtemp(prefix="bench_asha_")
    cluster = Devcluster(tmp, os.path.join(REPO, "native", "bin"), slots=8)
    # The agent's own cache dir would sit under the temp work root — a
    # path no later run can hit. Place the cache from outside instead
    # (the agent passes JAX_COMPILATION_CACHE_DIR through to its trials).
    cluster.env.setdefault("JAX_COMPILATION_CACHE_DIR", DEFAULT_CACHE_DIR)
    try:
        cluster.start_master()
        cluster.start_agent()
        token = cluster.login()

        n_trials = 16
        config = {
            "name": "bench-asha",
            "entrypoint": "python3 train.py",
            "searcher": {
                "name": "adaptive_asha",
                "metric": "val_loss",
                "smaller_is_better": True,
                "max_length": {"batches": 8},
                "max_trials": n_trials,
                "max_rungs": 3,
                "divisor": 4,
                "max_concurrent_trials": 8,
            },
            "hyperparameters": {
                "lr": {"type": "log", "minval": -4, "maxval": -1},
            },
            "environment": {"TRIAL_STEP_SLEEP": "0.0"},
            "checkpoint_storage": {"type": "shared_fs",
                                   "host_path": os.path.join(tmp, "ckpts")},
            "resources": {"slots_per_trial": 1},
            "max_restarts": 0,
        }
        model_def = cli._tar_context(
            os.path.join(REPO, "tests", "fixtures", "platform"))
        t0 = time.time()
        eid = cluster.api(
            "POST", "/api/v1/experiments",
            {"config": config, "model_definition": model_def,
             "activate": True}, token=token)["id"]
        _wait_experiment(cluster, token, eid)
        elapsed = time.time() - t0
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=token)["trials"]
        trials_per_hour = len(trials) / elapsed * 3600
        compile_reuse = run_compile_reuse(cluster, token, tmp)
        compile_farm = run_compile_farm(cluster, token, tmp)
        phase_breakdown = run_phase_breakdown(
            cluster, token, tmp, trials[0]["id"] if trials else 1)
        return {
            "metric": "asha_trials_per_hour",
            "value": round(trials_per_hour, 1),
            "unit": "trials/hour (adaptive_asha, 8 artificial slots)",
            "vs_baseline": 1.0,  # no reference number exists (BASELINE.md)
            "detail": {
                "trials": len(trials),
                "wall_seconds": round(elapsed, 1),
                "max_concurrent": 8,
                # Persistent XLA compilation cache (agent-injected
                # DET_XLA_CACHE_DIR): compile-bound trials with cache
                # off vs on.
                "compile_reuse": compile_reuse,
                # Compile farm on/off A/B (docs/compile-farm.md): serialized
                # executables + agent pre-warm vs the persistent cache
                # alone vs nothing.
                "compile_farm": compile_farm,
                # Per-phase master-side timings (ROADMAP item 1: attribute
                # the r5 asha_trials_per_hour regression — suspects are
                # the submit/preflight gate, the checkpoint two-phase
                # commit, the idempotency replay table, and the
                # preemption-signal fan-out).
                "phase_breakdown": phase_breakdown,
            },
        }
    finally:
        cluster.stop()


def main() -> None:
    # `make bench-master-load` (docs/cluster-ops.md "Overload, quotas &
    # fair use"): the overload/multi-tenant gates, standalone — no agent,
    # no ASHA run, four short-lived masters.
    if "--master-load" in sys.argv[1:]:
        print(json.dumps(run_master_load()))
        return
    print(json.dumps(run()))


if __name__ == "__main__":
    sys.exit(main())
