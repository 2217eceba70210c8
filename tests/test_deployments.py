"""Serving deployments (docs/serving.md "Deployments & autoscaling"):
replica-set controller, master-side request router, signal-driven
autoscaler.

Fast tests run the REAL master + agent (devcluster) with a featherweight
fake replica (tests/fixtures/serving/fake_replica.py) that speaks the
replica protocol — proxy registration, serve_stats heartbeats, the
preemption-drain handshake — without building a model, so router and
controller semantics are exercised end-to-end in tier-1 time. The -m slow
e2e at the bottom runs the full lifecycle with REAL engines in `make
chaos`.

The acceptance contracts:
  - the reconciler keeps a deployment at target (spawn on deficit,
    drain-retire on surplus, respawn on death);
  - the router dispatches least-loaded, retries connection refusals once
    on another replica (never an in-flight generation), ejects a failing
    replica via the circuit breaker and re-admits it after respawn;
  - 429/Retry-After when every replica reports a full admission queue;
  - scale-down always drains: zero accepted requests dropped.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from tests.test_platform_e2e import (
    Devcluster,
)

from determined_tpu import expconf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# expconf: serving.replicas validation + defaults.
# ---------------------------------------------------------------------------


def _serving_cfg(replicas):
    return {"name": "d", "serving": {"model": "gpt2", "replicas": replicas},
            "resources": {"slots_per_trial": 1}}


def test_expconf_replicas_valid_and_defaults():
    cfg = expconf.check(_serving_cfg({"min": 1, "max": 4, "target": 2}))
    rep = cfg["serving"]["replicas"]
    assert (rep["min"], rep["target"], rep["max"]) == (1, 2, 4)
    # Defaults fill from min upward.
    cfg = expconf.check(_serving_cfg({"min": 2}))
    rep = cfg["serving"]["replicas"]
    assert (rep["min"], rep["target"], rep["max"]) == (2, 2, 2)
    # Autoscaler knobs pass through.
    cfg = expconf.check(_serving_cfg(
        {"min": 1, "max": 2, "scale_up_after_s": 0.5,
         "scale_down_after_s": 1, "scale_up_threshold": 0.5,
         "scale_down_threshold": 0.2}))
    assert cfg["serving"]["replicas"]["scale_up_after_s"] == 0.5


@pytest.mark.parametrize("bad,needle", [
    ({"min": -1}, "non-negative int"),
    ({"min": 0, "max": 0}, "positive int"),       # max >= 1 always
    ({"min": 3, "max": 2}, "min must be <= max"),
    ({"min": 1, "max": 2, "target": 5}, "within [min, max]"),
    ({"min": 1, "bogus": 2}, "unknown keys"),
    ({"min": 1, "scale_up_after_s": -1}, "non-negative"),
    ({"min": 1, "scale_up_threshold": 3}, "(0, 2]"),
    ({"min": 0, "max": 2, "on_demand_floor": 3}, "on_demand_floor"),
    ({"min": 0, "max": 2, "on_demand_floor": -1}, "on_demand_floor"),
    ({"min": 0, "max": 2, "cold_start_budget_s": 0}, "cold_start_budget_s"),
    ({"min": 0, "max": 2, "cold_start_budget_s": -5},
     "cold_start_budget_s"),
    ("two", "must be a mapping"),
])
def test_expconf_replicas_invalid(bad, needle):
    errors = expconf.validate(_serving_cfg(bad))
    assert any(needle in e for e in errors), (bad, errors)


def test_expconf_scale_to_zero_and_capacity_knobs():
    """min: 0 (scale-to-zero) is legal, defaults stay consistent, and the
    spot/cold-start knobs validate ± (docs/serving.md 'Scale to zero')."""
    cfg = expconf.check(_serving_cfg({"min": 0, "max": 2}))
    rep = cfg["serving"]["replicas"]
    assert (rep["min"], rep["target"], rep["max"]) == (0, 0, 2)
    # min: 0 alone: target defaults to 0, max defaults to 1 (never 0).
    cfg = expconf.check(_serving_cfg({"min": 0}))
    rep = cfg["serving"]["replicas"]
    assert (rep["min"], rep["target"], rep["max"]) == (0, 0, 1)
    # Capacity knobs pass through.
    cfg = expconf.check(_serving_cfg(
        {"min": 0, "max": 3, "on_demand_floor": 1,
         "cold_start_budget_s": 20.5}))
    rep = cfg["serving"]["replicas"]
    assert rep["on_demand_floor"] == 1
    assert rep["cold_start_budget_s"] == 20.5


def test_preflight_dtl207_capacity_knobs_mirror():
    """The Python preflight's DTL207 fires on unsatisfiable capacity
    knobs and stays silent on legal scale-to-zero configs (the native
    master mirror is exercised via the deployment-create gate)."""
    from determined_tpu.analysis.config_rules import check_config

    def codes(cfg):
        return [d.code for d in check_config(cfg)]

    ok = _serving_cfg({"min": 0, "max": 2, "on_demand_floor": 1,
                       "cold_start_budget_s": 30})
    assert "DTL207" not in codes(ok)
    bad_floor = _serving_cfg({"min": 0, "max": 2, "on_demand_floor": 5})
    assert "DTL207" in codes(bad_floor)
    bad_budget = _serving_cfg(
        {"min": 0, "max": 2, "cold_start_budget_s": -1})
    assert "DTL207" in codes(bad_budget)
    bad_min = dict(_serving_cfg({"min": 1}))
    bad_min["serving"]["replicas"]["min"] = -2
    assert "DTL207" in codes(bad_min)


def test_preflight_dtl208_canary_fraction_mirror():
    """DTL208 fires on a canary fraction outside (0, 1) and stays silent
    on real fractions / omitted fraction (the native master mirror is
    exercised via the deployment-create gate in
    test_lifecycle_expconf_and_create_gate)."""
    from determined_tpu.analysis.config_rules import check_config

    def codes(cfg):
        return [d.code for d in check_config(cfg)]

    def cfg_with(**canary):
        c = _serving_cfg({"min": 1})
        c["serving"]["canary"] = {"model": "m", **canary}
        return c

    assert "DTL208" not in codes(cfg_with(fraction=0.05))
    assert "DTL208" not in codes(cfg_with())  # defaulted at create
    for bad in (0, 1, 1.5, -0.1, True, "lots"):
        assert "DTL208" in codes(cfg_with(fraction=bad)), bad
    # Suppressible like every DTL2xx rule.
    from determined_tpu.analysis import filter_suppressed

    diags = filter_suppressed(
        check_config(cfg_with(fraction=0)), ["DTL208"])
    assert [d.code for d in diags] == ["DTL208"] and diags[0].suppressed


def test_expconf_heartbeat_period():
    cfg = _serving_cfg({"min": 1})
    cfg["serving"]["heartbeat_period_s"] = 0.5
    assert not expconf.validate(cfg)
    cfg["serving"]["heartbeat_period_s"] = 0
    assert any("heartbeat_period_s" in e for e in expconf.validate(cfg))


# ---------------------------------------------------------------------------
# Devcluster plumbing.
# ---------------------------------------------------------------------------


def _http(method, url, body=None, token=None, timeout=60.0, headers=None):
    """Raw request returning (status, headers, parsed-json) — unlike
    Devcluster.api it surfaces 4xx/5xx instead of raising."""
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json",
                 **({"Authorization": f"Bearer {token}"} if token else {}),
                 **(headers or {})},
        method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            text = resp.read().decode()
            return resp.status, dict(resp.headers), (
                json.loads(text) if text else None)
    except urllib.error.HTTPError as e:
        text = e.read().decode(errors="replace")
        try:
            parsed = json.loads(text) if text else None
        except ValueError:
            parsed = {"raw": text}
        return e.code, dict(e.headers), parsed


def _dep_config(min_r=1, max_r=4, target=2, heartbeat_s=0.3, **rep_extra):
    replicas = {"min": min_r, "max": max_r, "target": target}
    replicas.update(rep_extra)
    return {
        "name": "fake-dep",
        # Fake replica instead of the real engine: the subsystem under
        # test is the master's controller/router, not the batcher.
        "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
        "serving": {"model": "gpt2", "replicas": replicas},
        "resources": {"slots_per_trial": 0},
        "environment": {"DET_FAKE_HEARTBEAT_S": str(heartbeat_s)},
    }


@pytest.fixture()
def master_only(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


@pytest.fixture()
def fleet(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries, slots=4)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def _wait_ready(c, token, dep_id, n, timeout=90.0):
    """Until `n` replicas are RUNNING with a proxy address and a fresh
    heartbeat; returns the deployment detail."""
    deadline = time.time() + timeout
    detail = None
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        ready = [r for r in detail["replicas"]
                 if r.get("allocation_state") == "RUNNING"
                 and r.get("proxy_address")
                 and 0 <= (r.get("report_age_s") or -1) < 10
                 and not r["retiring"]]
        if len(ready) == n and len(detail["replicas"]) == n:
            return detail
        time.sleep(0.2)
    raise TimeoutError(f"deployment never reached {n} ready replicas: "
                       f"{json.dumps(detail, indent=2)}")


def _replica_addr(detail, task_id):
    for r in detail["replicas"]:
        if r["task_id"] == task_id:
            return r["proxy_address"]
    raise KeyError(task_id)


def _generate(c, token, dep_id, body=None, timeout=60.0, headers=None):
    return _http("POST", f"{c.master_url}/serve/{dep_id}/v1/generate",
                 body or {"max_new_tokens": 4}, token=token,
                 timeout=timeout, headers=headers)


def _trace(c, token, dep_id, rid):
    return _http(
        "GET",
        f"{c.master_url}/api/v1/deployments/{dep_id}/requests/{rid}/trace",
        token=token)


# ---------------------------------------------------------------------------
# Controller: create / reconcile / scale / kill (no agent needed).
# ---------------------------------------------------------------------------


def test_deployment_create_scale_kill(master_only):
    c = master_only
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=2)}, token=token)
    dep_id = resp["id"]
    assert dep_id.startswith("deploy-") and len(resp["replicas"]) == 2

    # Replicas exist as SERVING tasks (PENDING without an agent).
    serving = c.api("GET", "/api/v1/serving", token=token)["serving"]
    ours = [t for t in serving if t["id"] in resp["replicas"]]
    assert len(ours) == 2 and all(t["state"] == "ACTIVE" for t in ours)

    # Scale up: reconciler spawns the deficit.
    c.api("POST", f"/api/v1/deployments/{dep_id}/scale", {"target": 3},
          token=token)
    deadline = time.time() + 10
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if len(detail["replicas"]) == 3:
            break
        time.sleep(0.2)
    assert len(detail["replicas"]) == 3

    # Out-of-range manual scale is a 400, not a clamp-and-shrug.
    status, _, body = _http(
        "POST", f"{c.master_url}/api/v1/deployments/{dep_id}/scale",
        {"target": 9}, token=token)
    assert status == 400 and "within" in body["error"]

    # Scale down: PENDING surplus replicas terminate immediately.
    c.api("POST", f"/api/v1/deployments/{dep_id}/scale", {"target": 1},
          token=token)
    deadline = time.time() + 10
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if len(detail["replicas"]) == 1:
            break
        time.sleep(0.2)
    assert len(detail["replicas"]) == 1

    # Kill: deployment ends, remaining replica task goes terminal.
    c.api("POST", f"/api/v1/deployments/{dep_id}/kill", token=token)
    deps = c.api("GET", "/api/v1/deployments", token=token)["deployments"]
    assert deps[0]["state"] == "KILLED" and deps[0]["end_time"]
    serving = c.api("GET", "/api/v1/serving", token=token)["serving"]
    assert all(t["state"] in ("CANCELED", "COMPLETED", "ERROR")
               for t in serving if t["id"] in resp["replicas"] or
               any(t["id"] == r["task_id"] for r in detail["replicas"]))


def test_deployment_requires_serving_block_and_valid_range(master_only):
    c = master_only
    token = c.login()
    status, _, body = _http(
        "POST", f"{c.master_url}/api/v1/deployments",
        {"config": {"name": "x"}}, token=token)
    assert status == 400 and "serving" in body["error"]
    status, _, body = _http(
        "POST", f"{c.master_url}/api/v1/deployments",
        {"config": {"serving": {"replicas": {"min": 3, "max": 1}}}},
        token=token)
    assert status == 400
    status, _, body = _http(
        "GET", f"{c.master_url}/api/v1/deployments/deploy-nope", token=token)
    assert status == 404 and body["error"] == "no such deployment"


# ---------------------------------------------------------------------------
# Router: dispatch, least-loaded, 429-all-full, failover, breaker.
# ---------------------------------------------------------------------------


def test_router_dispatch_and_least_loaded(fleet):
    c = fleet
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=2)}, token=token)
    dep_id = resp["id"]
    detail = _wait_ready(c, token, dep_id, 2)
    tids = [r["task_id"] for r in detail["replicas"]]

    # Equal load: the rotation spreads requests over both replicas.
    seen = set()
    for _ in range(6):
        status, _, body = _generate(c, token, dep_id)
        assert status == 200, body
        seen.add(body["replica"])
    assert seen == set(tids)

    # Routing by name works too.
    status, _, body = _generate(c, token, "fake-dep")
    assert status == 200

    # Load up replica A: everything flows to B until A clears.
    a, b = tids[0], tids[1]
    addr_a = _replica_addr(detail, a)
    status, _, _ = _http("POST", f"{addr_a}/force_stats",
                         {"queue_depth": 7, "queue_capacity": 8,
                          "active": 4, "slots": 4})
    assert status == 200
    time.sleep(0.2)  # force_stats beats immediately; allow the hop
    for _ in range(4):
        status, _, body = _generate(c, token, dep_id)
        assert status == 200 and body["replica"] == b, body
    _http("POST", f"{addr_a}/force_stats", {})


def test_router_429_when_every_replica_full(fleet):
    c = fleet
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=2)}, token=token)
    dep_id = resp["id"]
    detail = _wait_ready(c, token, dep_id, 2)
    full = {"queue_depth": 8, "queue_capacity": 8, "active": 4, "slots": 4,
            "retry_after_hint_s": 7}
    for r in detail["replicas"]:
        status, _, _ = _http(
            "POST", f"{r['proxy_address']}/force_stats", full)
        assert status == 200
    time.sleep(0.3)
    status, headers, body = _generate(c, token, dep_id)
    assert status == 429, body
    # The Retry-After hint is the smallest replica-computed backoff.
    assert headers.get("Retry-After") == "7", headers
    # One replica clears → requests flow again (to that replica).
    clear = detail["replicas"][0]
    _http("POST", f"{clear['proxy_address']}/force_stats", {})
    time.sleep(0.3)
    status, _, body = _generate(c, token, dep_id)
    assert status == 200 and body["replica"] == clear["task_id"]


def test_router_failover_ejection_and_readmission(fleet):
    """The satellite contract: kill one replica of a 2-replica deployment
    mid-burst — connection-refused requests retry onto the survivor (zero
    accepted requests dropped), the dead replica is ejected, and after the
    master respawns it the router re-admits it."""
    c = fleet
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=2, max_r=2)}, token=token)
    dep_id = resp["id"]
    detail = _wait_ready(c, token, dep_id, 2)
    tids = {r["task_id"] for r in detail["replicas"]}
    victim = detail["replicas"][0]
    survivor_tid = (tids - {victim["task_id"]}).pop()

    results, failures = [], []

    def _burst(n):
        for _ in range(n):
            status, _, body = _generate(
                c, token, dep_id, {"max_new_tokens": 2, "delay_ms": 40})
            (results if status == 200 else failures).append((status, body))

    threads = [threading.Thread(target=_burst, args=(6,)) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    # Kill the victim process mid-burst (its socket dies with it).
    try:
        _http("POST", f"{victim['proxy_address']}/die", {}, timeout=5)
    except Exception:
        pass  # the process may die before finishing the response
    for t in threads:
        t.join(timeout=120)

    # Zero dropped: every request either succeeded (possibly via the
    # retry-once path) or was an explicit router rejection — never a
    # torso. In-flight requests on the victim die WITH their connection
    # (the router must not replay a possibly-generating request), so the
    # caller sees an explicit 502 for those, and only those.
    assert len(results) >= 18, (len(results), failures)
    for status, body in failures:
        assert status == 502, (status, body)
    assert len(failures) <= 6, failures

    # The retry path actually ran: router counters prove the refusals
    # were re-dispatched rather than surfaced.
    raw = urllib.request.urlopen(urllib.request.Request(
        f"{c.master_url}/metrics",
        headers={"Authorization": f"Bearer {token}"}), timeout=10
    ).read().decode()
    retries = [line for line in raw.splitlines()
               if line.startswith("det_serve_router_retries_total")]
    assert retries and int(retries[0].split()[-1]) >= 1, retries

    # Survivor kept serving throughout; victim respawns (restarts >= 1)
    # and is re-admitted by the router after the breaker hold. Poll the
    # restarts bump FIRST: right after the burst the dead replica can
    # still look RUNNING with a fresh-enough heartbeat until the agent's
    # exit report lands, so a bare ready-check can win the race against
    # the requeue (same pattern as test_replica_death_respawns_to_target).
    deadline = time.time() + 120
    task = {}
    while time.time() < deadline:
        task = c.api("GET", f"/api/v1/serving/{victim['task_id']}",
                     token=token)["task"]
        if int(task.get("restarts") or 0) >= 1:
            break
        time.sleep(0.2)
    assert int(task.get("restarts") or 0) >= 1, task
    detail = _wait_ready(c, token, dep_id, 2, timeout=120)
    assert {r["task_id"] for r in detail["replicas"]} == tids
    deadline = time.time() + 60
    seen = set()
    while time.time() < deadline and len(seen) < 2:
        status, _, body = _generate(c, token, dep_id,
                                    {"max_new_tokens": 2, "delay_ms": 1})
        if status == 200:
            seen.add(body["replica"])
    assert seen == tids, f"victim never re-admitted: {seen}"
    assert survivor_tid in seen


def test_scale_down_drains_running_replica_zero_dropped(fleet):
    c = fleet
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=2, max_r=2)}, token=token)
    dep_id = resp["id"]
    _wait_ready(c, token, dep_id, 2)

    results, failures = [], []

    def _burst(n):
        for _ in range(n):
            status, _, body = _generate(
                c, token, dep_id, {"max_new_tokens": 2, "delay_ms": 30})
            (results if status == 200 else failures).append((status, body))

    threads = [threading.Thread(target=_burst, args=(8,)) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    c.api("POST", f"/api/v1/deployments/{dep_id}/scale", {"target": 1},
          token=token)
    for t in threads:
        t.join(timeout=120)
    # The drain is cooperative: every accepted request completed; the
    # router stopped dispatching to the retiring replica the moment its
    # preemption landed, so nothing was refused either.
    assert not failures, failures
    assert len(results) == 24

    deadline = time.time() + 60
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if len(detail["replicas"]) == 1:
            break
        time.sleep(0.2)
    assert len(detail["replicas"]) == 1
    # The retired replica finished COMPLETED — a drain, not a kill — and
    # was NOT respawned.
    serving = c.api("GET", "/api/v1/serving", token=token)["serving"]
    done = [t for t in serving if t["state"] == "COMPLETED"]
    assert len(done) == 1, serving
    assert int(done[0].get("restarts") or 0) == 0


def test_autoscaler_scales_up_on_backpressure_down_when_idle(fleet):
    c = fleet
    token = c.login()
    cfg = _dep_config(min_r=1, max_r=2, target=1, heartbeat_s=0.2,
                      scale_up_after_s=0.5, scale_down_after_s=0.5,
                      scale_up_threshold=0.5, scale_down_threshold=0.2)
    resp = c.api("POST", "/api/v1/deployments", {"config": cfg},
                 token=token)
    dep_id = resp["id"]
    detail = _wait_ready(c, token, dep_id, 1)
    addr = detail["replicas"][0]["proxy_address"]

    # Sustained backpressure: the replica reports a full queue + full
    # batch until the smoothed signal crosses the threshold.
    _http("POST", f"{addr}/force_stats",
          {"queue_depth": 8, "queue_capacity": 8, "active": 4, "slots": 4})
    deadline = time.time() + 45
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if detail["target_replicas"] == 2:
            break
        time.sleep(0.2)
    assert detail["target_replicas"] == 2, detail
    detail = _wait_ready(c, token, dep_id, 2)

    # Quiet down: the signal decays, the idle cooldown passes, target
    # returns to min — via drain (completed, not canceled/killed).
    _http("POST", f"{addr}/force_stats", {})
    deadline = time.time() + 90
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if detail["target_replicas"] == 1 and len(detail["replicas"]) == 1:
            break
        time.sleep(0.3)
    assert detail["target_replicas"] == 1, detail
    assert len(detail["replicas"]) == 1

    # Scale events are published on the stream and counted in /metrics.
    raw = urllib.request.urlopen(urllib.request.Request(
        f"{c.master_url}/metrics",
        headers={"Authorization": f"Bearer {token}"}), timeout=10
    ).read().decode()
    ups = [line for line in raw.splitlines() if line.startswith(
        'det_deployment_scale_events_total{direction="up"}')]
    downs = [line for line in raw.splitlines() if line.startswith(
        'det_deployment_scale_events_total{direction="down"}')]
    assert ups and int(ups[0].split()[-1]) >= 1
    assert downs and int(downs[0].split()[-1]) >= 1
    stream = c.api("GET", "/api/v1/stream?entities=deployments&"
                   "timeout_seconds=0", token=token)
    assert any(e["payload"].get("direction") == "up"
               for e in stream["events"]), stream


def test_scale_to_zero_idle_drain_and_demand_wake_cold_start(fleet):
    """docs/serving.md "Scale to zero": min 0 lets the idle cooldown
    drain the LAST replica (the deployment costs nothing while idle); the
    next request is NOT shed — the router wakes target 0 -> 1, HOLDS the
    request within cold_start_budget_s, and serves it, leaving a
    serve.cold_start span (engine_source=deserialize: the warm-AOT path,
    never a re-trace) on the request's trace."""
    c = fleet
    token = c.login()
    cfg = _dep_config(min_r=0, max_r=1, target=1, heartbeat_s=0.3,
                      scale_down_after_s=1.0, scale_down_threshold=0.5,
                      cold_start_budget_s=45)
    dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                   token=token)["id"]
    _wait_ready(c, token, dep_id, 1)

    # Idle cooldown drains to ZERO replicas.
    deadline = time.time() + 60
    while time.time() < deadline:
        d = c.api("GET", f"/api/v1/deployments/{dep_id}",
                  token=token)["deployment"]
        if int(d["target_replicas"]) == 0 and not d["replicas"]:
            break
        time.sleep(0.3)
    else:
        raise TimeoutError(f"never drained to zero: {d}")

    # The wake: one request, held through the cold start, answered 200.
    t0 = time.time()
    status, headers, body = _generate(c, token, dep_id, timeout=90.0)
    assert status == 200, (status, body)
    rid = headers.get("X-Request-Id")
    assert rid
    # Target is back at 1 and the replica that answered is live.
    d = c.api("GET", f"/api/v1/deployments/{dep_id}",
              token=token)["deployment"]
    assert int(d["target_replicas"]) == 1
    # The trace carries the cold-start phase with warm-AOT provenance.
    status, _, trace = _trace(c, token, dep_id, rid)
    assert status == 200, trace
    by_name = {s["name"]: s for s in trace["spans"]}
    assert "serve.cold_start" in by_name, sorted(by_name)
    cold = by_name["serve.cold_start"]
    assert cold["attrs"]["engine_source"] == "deserialize", cold
    assert 0 <= cold["attrs"]["wait_ms"] <= (time.time() - t0) * 1000 + 1
    assert cold["attrs"]["budget_s"] == 45


def test_cold_deployment_answers_503_with_computed_retry_after(master_only):
    """A deployment with zero READY replicas but NONZERO target (replicas
    still starting — here: no agent exists at all) answers 503 with a
    Retry-After computed from the spawn + warm-AOT budget, never a
    connection error, and never opens breakers against replicas that have
    not started."""
    c = master_only
    token = c.login()
    cfg = _dep_config(min_r=1, max_r=1, target=1,
                      cold_start_budget_s=20)
    dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                   token=token)["id"]
    status, headers, body = _generate(c, token, dep_id)
    assert status == 503, (status, body)
    # No observed cold start yet -> budget/4 = 5s.
    assert headers.get("Retry-After") == "5", headers
    # Repeatable — shedding, not an error path.
    status, headers, _ = _generate(c, token, dep_id)
    assert status == 503 and headers.get("Retry-After") == "5"


def test_breaker_ignores_starting_replica_refusals(fleet):
    """A replica whose proxy address is registered but whose engine is
    still loading refuses connections; those refusals are boot noise and
    must NOT open the circuit breaker — the first real request after the
    engine comes up goes straight through."""
    c = fleet
    token = c.login()
    cfg = _dep_config(min_r=1, max_r=1, target=1, heartbeat_s=0.3)
    cfg["environment"]["DET_FAKE_STARTING_S"] = "4"
    dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                   token=token)["id"]

    # Wait for the proxy address (replica looks routable, engine is not).
    deadline = time.time() + 60
    while time.time() < deadline:
        d = c.api("GET", f"/api/v1/deployments/{dep_id}",
                  token=token)["deployment"]
        reps = [r for r in d["replicas"] if r.get("proxy_address")
                and r.get("allocation_state") == "RUNNING"]
        if reps:
            break
        time.sleep(0.2)
    else:
        raise TimeoutError(f"replica never registered a proxy: {d}")

    # Hammer it during the STARTING window: refusals surface (502) but
    # must not count toward the breaker.
    refusals = 0
    for _ in range(4):
        status, _, _ = _generate(c, token, dep_id, timeout=20.0)
        if status in (502, 503):
            refusals += 1
        time.sleep(0.2)
    assert refusals >= 3, "expected connection refusals while STARTING"
    d = c.api("GET", f"/api/v1/deployments/{dep_id}",
              token=token)["deployment"]
    rep = d["replicas"][0]
    assert rep["consecutive_failures"] == 0, rep
    assert not rep["breaker_open"], rep

    # Engine up (first heartbeat arrives) -> immediate success, no
    # breaker hold to wait out.
    _wait_ready(c, token, dep_id, 1)
    status, _, body = _generate(c, token, dep_id)
    assert status == 200, (status, body)


def test_spot_placement_floor_and_drain_retarget(tmp_path, native_binaries):
    """Spot-aware serving (docs/cluster-ops.md "Capacity loop"): the
    on_demand_floor replica lands on non-preemptible capacity, the
    surplus replica lands on the spot agent first; a PR-5 preemption
    notice on the spot agent drains its replica cooperatively (zero
    dropped) while the reconciler immediately spawns the replacement on
    surviving on-demand capacity."""
    c = Devcluster(str(tmp_path), native_binaries, slots=4)
    c.start_master()
    c.start_agent("agent-od")
    c.start_agent("agent-spot", extra_env={"DET_AGENT_PREEMPTIBLE": "1"})
    try:
        token = c.login()
        agents = {a["id"]: a for a in
                  c.api("GET", "/api/v1/agents", token=token)["agents"]}
        assert agents["agent-spot"]["preemptible"] is True
        assert agents["agent-od"]["preemptible"] is False

        cfg = _dep_config(min_r=2, max_r=2, target=2, heartbeat_s=0.3,
                          on_demand_floor=1)
        cfg["resources"]["slots"] = 1
        dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                       token=token)["id"]
        detail = _wait_ready(c, token, dep_id, 2)
        placed = {r["capacity_class"]: r for r in detail["replicas"]}
        assert set(placed) == {"on_demand", "spot_first"}, detail
        assert placed["on_demand"]["agent"] == "agent-od", detail
        assert placed["spot_first"]["agent"] == "agent-spot", detail
        spot_task = placed["spot_first"]["task_id"]

        # Spot reclamation: termination notice on the spot agent. The
        # replica drains inside the deadline; the replacement respawns on
        # the on-demand agent; requests keep flowing throughout.
        c.api("POST", "/api/v1/agents/agent-spot/preempt_notice",
              {"deadline_seconds": 20, "reason": "spot_preemption"},
              token=c.login("admin"))
        status, _, body = _generate(c, token, dep_id)
        assert status == 200, (status, body)  # zero dropped during drain

        deadline = time.time() + 60
        while time.time() < deadline:
            d = c.api("GET", f"/api/v1/deployments/{dep_id}",
                      token=token)["deployment"]
            live = [r for r in d["replicas"]
                    if not r["retiring"]
                    and r.get("allocation_state") == "RUNNING"
                    and r.get("proxy_address")]
            if (len(live) == 2
                    and all(r["agent"] == "agent-od" for r in live)
                    and spot_task not in [r["task_id"] for r in live]):
                break
            time.sleep(0.3)
        else:
            raise TimeoutError(f"replacement never landed on-demand: {d}")
        # The drained spot replica finished cleanly (drain, not a kill).
        status, _, body = _generate(c, token, dep_id)
        assert status == 200, (status, body)
    finally:
        c.stop()


def test_replica_death_respawns_to_target(fleet):
    """A replica that dies (nonzero exit) respawns via the PR-6 requeue
    machinery under the SAME task id — the deployment holds target."""
    c = fleet
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=1, max_r=1)}, token=token)
    dep_id = resp["id"]
    detail = _wait_ready(c, token, dep_id, 1)
    tid = detail["replicas"][0]["task_id"]
    try:
        _http("POST", f"{detail['replicas'][0]['proxy_address']}/die", {},
              timeout=5)
    except Exception:
        pass
    # First the death lands (restarts bumps), then the respawn comes up.
    deadline = time.time() + 120
    task = {}
    while time.time() < deadline:
        task = c.api("GET", f"/api/v1/serving/{tid}", token=token)["task"]
        if int(task.get("restarts") or 0) >= 1:
            break
        time.sleep(0.2)
    assert int(task.get("restarts") or 0) >= 1, task
    detail = _wait_ready(c, token, dep_id, 1, timeout=120)
    assert detail["replicas"][0]["task_id"] == tid


# ---------------------------------------------------------------------------
# Request-path observability: per-request traces, latency aggregation,
# slow-request ring (ISSUE 12; docs/serving.md "Request latency & SLOs").
# ---------------------------------------------------------------------------


def test_request_trace_end_to_end_with_waterfall(fleet):
    """The acceptance contract: a request served through
    /serve/{deployment} yields a PERSISTED span tree with router-dispatch,
    queue-wait, prefill, and decode phases, and `det serve trace` renders
    it as a waterfall."""
    from determined_tpu.common.trace import render_waterfall

    c = fleet
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=2)}, token=token)
    dep_id = resp["id"]
    _wait_ready(c, token, dep_id, 2)

    # Caller-supplied X-Request-Id is adopted and echoed.
    rid = "trace-me-1"
    status, headers, body = _generate(
        c, token, dep_id, {"max_new_tokens": 4},
        headers={"X-Request-Id": rid})
    assert status == 200, body
    assert headers.get("X-Request-Id") == rid
    assert body["id"] == rid  # the replica served under the same id

    status, _, trace = _trace(c, token, dep_id, rid)
    assert status == 200, trace
    spans = trace["spans"]
    names = {s["name"] for s in spans}
    assert {"serve.request", "serve.router.dispatch", "serve.queue_wait",
            "serve.prefill", "serve.decode"} <= names, names
    # One trace: every span rides the request id; the root IS the id.
    assert all(s["trace_id"] == rid for s in spans)
    root = [s for s in spans if s["name"] == "serve.request"][0]
    assert root["span_id"] == rid
    for s in spans:
        if s["name"] != "serve.request":
            assert s["parent"] == rid, s
    # Phase attrs made it through the store.
    prefill = [s for s in spans if s["name"] == "serve.prefill"][0]
    assert prefill["attrs"]["suffix_len"] >= 1
    dispatch = [s for s in spans if s["name"] == "serve.router.dispatch"][0]
    assert dispatch["attrs"]["status"] == 200
    assert dispatch["attrs"]["retried"] is False
    # Spans are closed and ordered on one timeline.
    assert all(s["end_us"] >= s["start_us"] > 0 for s in spans)
    # The CLI waterfall renders it (same renderer as `det trial trace`).
    out = render_waterfall(spans)
    assert "serve.router.dispatch" in out and "serve.decode" in out
    assert "#" in out  # duration bars drawn

    # Router-minted ids: no header → a fresh rq-* id comes back and its
    # trace is just as queryable (by deployment NAME too).
    status, headers, body = _generate(c, token, dep_id,
                                      {"max_new_tokens": 2})
    assert status == 200
    minted = headers.get("X-Request-Id", "")
    assert minted.startswith("rq-")
    status, _, trace = _trace(c, token, "fake-dep", minted)
    assert status == 200 and trace["deployment_id"] == dep_id

    # Unknown request id → 404 that names the miss, not a routing 404.
    status, _, body = _trace(c, token, dep_id, "rq-never-happened")
    assert status == 404 and "no spans" in body["error"]


def test_request_trace_retried_dispatch_shows_both_attempts(fleet):
    """A connection-refused dispatch that retries onto the survivor leaves
    BOTH attempts in the trace: attempt 0 with the error, attempt 1 with
    the 200 — the 'why was THIS request slow' answer for failover."""
    c = fleet
    token = c.login()
    resp = c.api("POST", "/api/v1/deployments",
                 {"config": _dep_config(target=2, max_r=2)}, token=token)
    dep_id = resp["id"]
    detail = _wait_ready(c, token, dep_id, 2)
    victim = detail["replicas"][0]
    try:
        _http("POST", f"{victim['proxy_address']}/die", {}, timeout=5)
    except Exception:
        pass  # the process may die before finishing the response

    # The router learns of the death only by connecting: issue requests
    # until one draws the dead replica first (tie rotation alternates, so
    # this converges in a couple of tries).
    retried_trace = None
    for i in range(12):
        rid = f"retry-{i}"
        status, _, body = _generate(
            c, token, dep_id, {"max_new_tokens": 2, "delay_ms": 1},
            headers={"X-Request-Id": rid})
        if status != 200:
            continue  # in-flight edge cases surface as explicit errors
        status, _, trace = _trace(c, token, dep_id, rid)
        if status != 200:
            continue
        dispatches = [s for s in trace["spans"]
                      if s["name"] == "serve.router.dispatch"]
        if len(dispatches) == 2:
            retried_trace = (rid, trace, dispatches)
            break
    assert retried_trace is not None, "no request drew the dead replica"
    rid, trace, dispatches = retried_trace
    dispatches.sort(key=lambda s: s["attrs"]["attempt"])
    first, second = dispatches
    assert first["attrs"]["attempt"] == 0 and "error" in first["attrs"]
    assert first["attrs"]["replica"] == victim["task_id"]
    assert second["attrs"]["attempt"] == 1
    assert second["attrs"]["retried"] is True
    assert second["attrs"]["status"] == 200
    # The replica-side phases exist alongside both dispatch attempts.
    names = {s["name"] for s in trace["spans"]}
    assert {"serve.request", "serve.prefill", "serve.decode"} <= names


def test_deployment_latency_aggregation_and_slow_ring(fleet):
    """Replica heartbeats carry TTFT/TPOT/e2e/queue-wait histograms; the
    master aggregates fresh ones into per-deployment p50/p99 on the
    detail API, exposes det_serve_request_seconds{deployment=...} on
    /metrics, and records SLO breaches in the slow-request ring."""
    c = fleet
    token = c.login()
    cfg = _dep_config(target=2, heartbeat_s=0.2)
    # Every fake generation takes ~30 ms — a 1 ms SLO makes each one a
    # breach, so the ring fills deterministically.
    cfg["serving"]["slo_ms"] = 1
    resp = c.api("POST", "/api/v1/deployments", {"config": cfg},
                 token=token)
    dep_id = resp["id"]
    _wait_ready(c, token, dep_id, 2)

    rids = []
    for i in range(6):
        status, headers, _ = _generate(c, token, dep_id,
                                       {"max_new_tokens": 4})
        assert status == 200
        rids.append(headers["X-Request-Id"])

    # Aggregation rides the heartbeat: poll until all 6 requests landed.
    deadline = time.time() + 30
    lat = {}
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        lat = detail.get("latency") or {}
        if (lat.get("e2e") or {}).get("count", 0) >= 6:
            break
        time.sleep(0.2)
    assert lat["e2e"]["count"] >= 6, detail
    for key in ("ttft", "tpot", "e2e", "queue_wait"):
        h = lat[key]
        assert h["count"] >= 1 and h["p99_ms"] >= h["p50_ms"] >= 0, (key, h)
    # TTFT ≈ 25% of the ~30 ms service time; e2e covers all of it.
    assert lat["e2e"]["p50_ms"] > lat["ttft"]["p50_ms"] > 0
    # Per-replica summaries ride the detail too.
    assert any((r.get("latency") or {}).get("e2e", {}).get("count", 0) > 0
               for r in detail["replicas"])

    # The list API (what `det serve status` prints) carries the same
    # aggregation.
    deps = c.api("GET", "/api/v1/deployments", token=token)["deployments"]
    mine = [d for d in deps if d["id"] == dep_id][0]
    assert mine["latency"]["e2e"]["count"] >= 6

    # Slow-request ring: every request breached the 1 ms SLO; entries are
    # traceable ids, newest first.
    assert detail["slo_ms"] == 1
    ring = detail["slow_requests"]
    assert ring, detail
    assert all(s["ms"] > 1 and s["request_id"] for s in ring)
    assert {s["request_id"] for s in ring} <= set(rids)

    # CLI smoke: `det serve status` renders the p50/p99 latency columns
    # and `det serve trace` renders a slow request's waterfall.
    import argparse
    import contextlib
    import io

    from determined_tpu.cli import cmd_serve
    from determined_tpu.common.api import Session

    sess = Session(c.master_url, token)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cmd_serve(sess, argparse.Namespace(
            target="status", extra=[], local=False, json=False))
    out = buf.getvalue()
    assert "ttft_ms" in out and "tpot_ms" in out and "e2e_ms" in out
    assert dep_id in out
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cmd_serve(sess, argparse.Namespace(
            target="trace", extra=[dep_id, ring[0]["request_id"]],
            local=False, json=False))
    out = buf.getvalue()
    assert "serve.router.dispatch" in out and "serve.decode" in out

    # Master /metrics: per-deployment latency histogram + counters.
    raw = urllib.request.urlopen(urllib.request.Request(
        f"{c.master_url}/metrics",
        headers={"Authorization": f"Bearer {token}"}), timeout=10
    ).read().decode()
    count_lines = [line for line in raw.splitlines() if line.startswith(
        f'det_serve_request_seconds_count{{deployment="{dep_id}"}}')]
    assert count_lines and int(count_lines[0].split()[-1]) >= 6, count_lines
    spans_total = [line for line in raw.splitlines()
                   if line.startswith("det_request_spans_ingested_total")]
    assert spans_total and int(spans_total[0].split()[-1]) >= 6
    breaches = [line for line in raw.splitlines()
                if line.startswith("det_serve_slo_breaches_total")]
    assert breaches and int(breaches[0].split()[-1]) >= 6


# ---------------------------------------------------------------------------
# Model lifecycle: registry-driven rolling swaps, canary routing, version
# surfacing (docs/serving.md "Model lifecycle").
# ---------------------------------------------------------------------------


def _register_versions(c, token, model, uuids):
    """Trial-less COMPLETED checkpoint rows + registry versions 1..N for
    them; returns nothing (versions are 1-based in uuid order)."""
    _http("POST", f"{c.master_url}/api/v1/models",
          {"name": model, "metadata": {}, "labels": []}, token=token)
    for uuid in uuids:
        c.api("POST", "/api/v1/checkpoints",
              {"uuid": uuid, "state": "COMPLETED"}, token=token)
        c.api("POST", f"/api/v1/models/{model}/versions",
              {"checkpoint_uuid": uuid}, token=token)


def _live_versions(detail):
    return sorted((r["model_version"], r.get("canary", False))
                  for r in detail["replicas"] if not r["retiring"])


def test_register_version_requires_committed_checkpoint(master_only):
    """Registry versions are immutable promises: only COMPLETED
    checkpoints register; unknown/PARTIAL refuse; numbering is
    sequential; the version detail carries the checkpoint; registration
    publishes a `models` stream event."""
    c = master_only
    token = c.login()
    c.api("POST", "/api/v1/models",
          {"name": "m", "metadata": {}, "labels": []}, token=token)
    # Unknown checkpoint: 404.
    status, _, body = _http(
        "POST", f"{c.master_url}/api/v1/models/m/versions",
        {"checkpoint_uuid": "nope"}, token=token)
    assert status == 404, body
    # PARTIAL checkpoint: 400 (torsos never become versions).
    c.api("POST", "/api/v1/checkpoints",
          {"uuid": "ck-partial", "state": "PARTIAL"}, token=token)
    status, _, body = _http(
        "POST", f"{c.master_url}/api/v1/models/m/versions",
        {"checkpoint_uuid": "ck-partial"}, token=token)
    assert status == 400 and "PARTIAL" in body["error"], body
    # COMPLETED registers, versions count up, detail resolves.
    _register_versions(c, token, "m", ["ck-1", "ck-2"])
    vers = c.api("GET", "/api/v1/models/m/versions",
                 token=token)["model_versions"]
    assert [v["version"] for v in vers] == [1, 2]
    one = c.api("GET", "/api/v1/models/m/versions/2",
                token=token)["model_version"]
    assert one["checkpoint_uuid"] == "ck-2"
    stream = c.api("GET", "/api/v1/stream?entities=models&timeout_seconds=0",
                   token=token)
    assert any(e["payload"].get("version") == 2
               and e["payload"].get("model") == "m"
               for e in stream["events"]), stream


def test_rolling_update_swap_and_rollback(fleet):
    """`det serve update` semantics: the deployment rolls to the new
    version one replica at a time — spawn-at-new BEFORE drain-at-old
    (live never exceeds target+1, dispatch never fails) — and rolling
    back is the same call with the prior version. The completed swap
    leaves a serve.swap span reachable through the stream's swap_id."""
    c = fleet
    token = c.login()
    cfg = _dep_config(min_r=1, max_r=4, target=2, heartbeat_s=0.3)
    dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                   token=token)["id"]
    detail = _wait_ready(c, token, dep_id, 2)
    # Initial version label derives from the pinned checkpoint.
    assert detail["model_version"] == "checkpoint:latest"
    v0_tasks = {r["task_id"] for r in detail["replicas"]}

    _register_versions(c, token, "m", ["ck-v1", "ck-v2"])
    resp = c.api("POST", f"/api/v1/deployments/{dep_id}/update",
                 {"model": "m", "version": 2}, token=token)
    assert resp["rolling"] and resp["model_version"] == "m:2"
    assert resp["checkpoint"] == "ck-v2"

    # Roll to completion: every generation keeps succeeding, live
    # non-retiring never exceeds target+1 (the one-at-a-time surge).
    deadline = time.time() + 120
    while time.time() < deadline:
        status, _, out = _generate(c, token, dep_id)
        assert status == 200, out
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        live = [r for r in detail["replicas"] if not r["retiring"]]
        assert len(live) <= 3, _live_versions(detail)
        if (len(detail["replicas"]) == 2
                and all(r["model_version"] == "m:2"
                        for r in detail["replicas"])
                and "swap" not in detail):
            break
        time.sleep(0.3)
    assert detail["model_version"] == "m:2"
    assert all(r["model_version"] == "m:2" for r in detail["replicas"]), \
        _live_versions(detail)
    # Blue-green for real: the v2 set is a fresh replica set.
    assert not v0_tasks & {r["task_id"] for r in detail["replicas"]}
    # A generation now reports the new version (fake echoes
    # DET_MODEL_VERSION, exactly like the real replica's heartbeat).
    status, _, out = _generate(c, token, dep_id)
    assert status == 200 and out["model_version"] == "m:2", out

    # serve.swap span: the stream's swap_complete event names the span's
    # request-id scope; the trace endpoint serves it back.
    stream = c.api(
        "GET", "/api/v1/stream?entities=deployments&timeout_seconds=0",
        token=token)
    done = [e["payload"] for e in stream["events"]
            if e["payload"].get("swap_complete")]
    assert done and done[-1]["model_version"] == "m:2", stream
    status, _, tr = _trace(c, token, dep_id, done[-1]["swap_id"])
    assert status == 200
    swap_spans = [s for s in tr["spans"] if s["name"] == "serve.swap"]
    assert swap_spans, tr
    attrs = swap_spans[0]["attrs"]
    assert attrs["to"] == "m:2" and attrs["replicas_swapped"] == 2, attrs

    # Rollback = update back to the prior version (still registered).
    resp = c.api("POST", f"/api/v1/deployments/{dep_id}/update",
                 {"model": "m", "version": 1}, token=token)
    assert resp["model_version"] == "m:1"
    deadline = time.time() + 120
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if (len(detail["replicas"]) == 2
                and all(r["model_version"] == "m:1"
                        for r in detail["replicas"])):
            break
        time.sleep(0.3)
    assert all(r["model_version"] == "m:1" for r in detail["replicas"]), \
        _live_versions(detail)
    # No-op update answers rolling=false.
    resp = c.api("POST", f"/api/v1/deployments/{dep_id}/update",
                 {"model": "m", "version": 1}, token=token)
    assert resp["rolling"] is False
    # Unknown version/model: 400 with a useful message.
    status, _, body = _http(
        "POST", f"{c.master_url}/api/v1/deployments/{dep_id}/update",
        {"model": "m", "version": 9}, token=token)
    assert status == 400 and "no version 9" in body["error"], body
    status, _, body = _http(
        "POST", f"{c.master_url}/api/v1/deployments/{dep_id}/update",
        {"model": "ghost"}, token=token)
    assert status == 400 and "no such model" in body["error"], body


def test_canary_split_observed_fraction_and_promote(fleet):
    """Canary routing: a 0.25 split sends EXACTLY every 4th traced
    generation to the canary replica (deterministic debt accounting),
    per-version latency aggregates separately, and promote folds the
    canary version into the deployment via the rolling-swap path."""
    c = fleet
    token = c.login()
    cfg = _dep_config(min_r=1, max_r=2, target=1, heartbeat_s=0.3)
    dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                   token=token)["id"]
    _wait_ready(c, token, dep_id, 1)
    _register_versions(c, token, "m", ["ck-v1", "ck-v2"])

    # Fraction gate: the API refuses anything outside (0, 1) — the
    # DTL208 contract at the verb.
    for bad in (0, 1.0, -0.25, 2):
        status, _, body = _http(
            "POST", f"{c.master_url}/api/v1/deployments/{dep_id}/canary",
            {"model": "m", "version": 2, "fraction": bad}, token=token)
        assert status == 400 and "(0, 1)" in body["error"], (bad, body)
    # Promote/abort without a canary: 400.
    for verb in ({"promote": True}, {"abort": True}):
        status, _, body = _http(
            "POST", f"{c.master_url}/api/v1/deployments/{dep_id}/canary",
            verb, token=token)
        assert status == 400, (verb, body)

    resp = c.api("POST", f"/api/v1/deployments/{dep_id}/canary",
                 {"model": "m", "version": 2, "fraction": 0.25},
                 token=token)
    assert resp["canary"] == "m:2" and resp["fraction"] == 0.25

    # Wait for the canary replica to become routable beside stable.
    deadline = time.time() + 90
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        ready_canary = [
            r for r in detail["replicas"]
            if r.get("canary") and r.get("allocation_state") == "RUNNING"
            and r.get("proxy_address")
            and 0 <= (r.get("report_age_s") or -1) < 10]
        if ready_canary:
            break
        time.sleep(0.2)
    assert ready_canary, detail
    assert detail["canary"]["version"] == "m:2"

    # 40 traced generations: the debt accumulator routes exactly 10 to
    # the canary (both groups stayed routable throughout).
    by_version = {}
    for _ in range(40):
        status, _, out = _generate(c, token, dep_id)
        assert status == 200, out
        v = out.get("model_version") or "stable"
        by_version[v] = by_version.get(v, 0) + 1
    assert by_version.get("m:2") == 10, by_version

    detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                   token=token)["deployment"]
    canary = detail["canary"]
    assert canary["routed"] == 10 and canary["routed_stable"] == 30, canary
    assert abs(canary["observed_fraction"] - 0.25) < 1e-9
    # Canary-vs-stable latency side by side (after the next heartbeat
    # ships the histograms).
    deadline = time.time() + 15
    byv = {}
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        byv = detail.get("latency_by_version") or {}
        if len(byv) >= 2 and all(
                (v.get("e2e") or {}).get("count") for v in byv.values()):
            break
        time.sleep(0.3)
    assert "m:2" in byv and len(byv) == 2, byv
    # The split shows up on master /metrics.
    raw = urllib.request.urlopen(urllib.request.Request(
        f"{c.master_url}/metrics",
        headers={"Authorization": f"Bearer {token}"}), timeout=10
    ).read().decode()
    assert (f'det_serve_canary_requests_total{{deployment="{dep_id}"'
            ',group="canary"} 10') in raw, raw

    # Promote: the canary replica becomes the stable set; the old stable
    # replica drains; deployment lands on m:2 with target replicas.
    canary_task = ready_canary[0]["task_id"]
    resp = c.api("POST", f"/api/v1/deployments/{dep_id}/canary",
                 {"promote": True}, token=token)
    assert resp["promoted"] == "m:2"
    assert resp["canary_stats"]["routed"] == 10
    deadline = time.time() + 120
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if (len(detail["replicas"]) == 1
                and detail["replicas"][0]["model_version"] == "m:2"
                and not detail["replicas"][0]["retiring"]):
            break
        time.sleep(0.3)
    assert detail["model_version"] == "m:2"
    assert detail.get("canary") is None
    # The promoted replica IS the canary task (already at m:2 — no
    # needless respawn), demoted to a regular replica.
    assert detail["replicas"][0]["task_id"] == canary_task
    assert detail["replicas"][0]["canary"] is False


def test_canary_abort_drains_canary_only(fleet):
    """Abort drains the canary replicas and leaves stable untouched —
    the cheap exit when the canary's p99 looks wrong."""
    c = fleet
    token = c.login()
    cfg = _dep_config(min_r=1, max_r=2, target=1, heartbeat_s=0.3)
    dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                   token=token)["id"]
    detail = _wait_ready(c, token, dep_id, 1)
    stable_task = detail["replicas"][0]["task_id"]
    _register_versions(c, token, "m", ["ck-v1"])
    c.api("POST", f"/api/v1/deployments/{dep_id}/canary",
          {"model": "m", "version": 1, "fraction": 0.5}, token=token)
    deadline = time.time() + 90
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if any(r.get("canary") for r in detail["replicas"]):
            break
        time.sleep(0.2)
    assert any(r.get("canary") for r in detail["replicas"]), detail

    resp = c.api("POST", f"/api/v1/deployments/{dep_id}/canary",
                 {"abort": True}, token=token)
    assert resp["aborted"] == "m:1"
    deadline = time.time() + 120
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if (len(detail["replicas"]) == 1
                and not detail["replicas"][0].get("canary")):
            break
        time.sleep(0.3)
    assert detail["replicas"][0]["task_id"] == stable_task, detail
    assert detail.get("canary") is None
    assert detail["model_version"] == "checkpoint:latest"
    # Post-abort traffic is 100% stable (the initial checkpoint label).
    status, _, out = _generate(c, token, dep_id)
    assert status == 200, out
    assert out.get("model_version") == "checkpoint:latest", out


def test_lifecycle_expconf_and_create_gate(master_only):
    """Config-declared lifecycle blocks: serving.canary arms the split at
    deployment create (resolved through the registry), and the DTL208
    fraction gate refuses a bad fraction at POST /deployments when the
    preflight gate is armed."""
    c = master_only
    token = c.login()
    _register_versions(c, token, "m", ["ck-v1", "ck-v2"])
    cfg = _dep_config(min_r=1, max_r=2, target=1)
    cfg["serving"]["canary"] = {"model": "m", "version": 2,
                                "fraction": 0.1}
    cfg = expconf.check(cfg)  # client-side validation passes + defaults
    assert cfg["serving"]["canary"]["replicas"] == 1
    dep_id = c.api("POST", "/api/v1/deployments", {"config": cfg},
                   token=token)["id"]
    detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                   token=token)["deployment"]
    assert detail["canary"]["version"] == "m:2"
    assert detail["canary"]["fraction"] == 0.1
    # One canary replica spawns beside the stable target within a couple
    # of reconcile ticks (the crash-loop spawn throttle spaces it from
    # the stable spawn; no agent in this cluster, so they stay PENDING —
    # fine for the check).
    deadline = time.time() + 15
    while time.time() < deadline:
        detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                       token=token)["deployment"]
        if sum(1 for r in detail["replicas"] if r["canary"]) == 1:
            break
        time.sleep(0.3)
    assert sum(1 for r in detail["replicas"] if r["canary"]) == 1, detail

    # Master-side DTL208 gate (same gate:error semantics as experiments).
    bad = _dep_config(min_r=1, max_r=2, target=1)
    bad["serving"]["canary"] = {"model": "m", "fraction": 1.5}
    bad["preflight"] = {"gate": "error"}
    status, _, body = _http("POST", f"{c.master_url}/api/v1/deployments",
                            {"config": bad}, token=token)
    assert status == 400, body
    assert any(d.get("code") == "DTL208"
               for d in body.get("preflight", [])), body

    # serving.model_version pins a registered version at create.
    pinned = _dep_config(min_r=1, max_r=2, target=1)
    pinned["serving"]["model_version"] = "m:1"
    resp = c.api("POST", "/api/v1/deployments", {"config": pinned},
                 token=token)
    assert resp["model_version"] == "m:1"
    detail = c.api("GET", f"/api/v1/deployments/{resp['id']}",
                   token=token)["deployment"]
    assert detail["model_version"] == "m:1"
    assert all(r["model_version"] == "m:1" for r in detail["replicas"])
    # Unknown registry label at create: 400, not a broken deployment.
    pinned["serving"]["model_version"] = "ghost:7"
    status, _, body = _http("POST", f"{c.master_url}/api/v1/deployments",
                            {"config": pinned}, token=token)
    assert status == 400 and "no such model" in body["error"], body


# ---------------------------------------------------------------------------
# Full lifecycle with REAL replicas (make chaos).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_deployment_lifecycle_real_replicas_e2e(tmp_path, native_binaries):
    """Scale-up under real load, scale-down via drain, zero dropped — with
    real engines serving a real checkpoint through the router."""
    import jax
    import jax.numpy as jnp

    from determined_tpu import core
    from determined_tpu.models import gpt2

    cfg = gpt2.Config(
        vocab_size=256, n_positions=64, d_model=32, n_layer=2, n_head=2,
        dtype=jnp.float32, remat=False, attention_impl="dot")
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    ctx = core.init(max_length=2,
                    checkpoint_dir=os.path.join(str(tmp_path), "ckpts"))
    ctx.checkpoint.save_state(
        {"step": jnp.asarray(2, jnp.int32), "params": params,
         "opt_state": {"count": jnp.zeros((), jnp.int32)}}, 2)
    ctx.checkpoint.wait()
    ctx.close()

    config = {
        "name": "real-dep",
        "serving": {
            "checkpoint": "trial0-step2",
            "model": "gpt2",
            "model_config": {"model_size": "tiny", "seq_len": 64,
                             "dtype": "float32", "vocab_size": 256,
                             "n_positions": 64, "d_model": 32,
                             "n_layer": 2, "n_head": 2},
            "max_batch_size": 4,
            "max_seq_len": 32,
            "prefill_buckets": [8],
            "queue_depth": 32,
            "heartbeat_period_s": 0.3,
            "replicas": {"min": 1, "max": 2, "target": 1,
                         "scale_up_after_s": 1.0,
                         "scale_down_after_s": 2.0,
                         "scale_up_threshold": 0.5,
                         "scale_down_threshold": 0.05},
        },
        "resources": {"slots_per_trial": 1},
        "checkpoint_storage": {
            "type": "shared_fs",
            "host_path": os.path.join(str(tmp_path), "ckpts"),
        },
    }

    c = Devcluster(str(tmp_path), native_binaries, slots=1)
    c.start_master()
    c.start_agent("fleet-a")
    c.start_agent("fleet-b")
    try:
        token = c.login()
        dep_id = c.api("POST", "/api/v1/deployments", {"config": config},
                       token=token)["id"]
        _wait_ready(c, token, dep_id, 1, timeout=240)

        stop_load = threading.Event()
        results, failures = [], []

        def _loader():
            while not stop_load.is_set():
                status, _, body = _generate(
                    c, token, dep_id,
                    {"tokens": [5, 9, 17, 3], "max_new_tokens": 16,
                     "timeout_s": 120}, timeout=150)
                if status == 200:
                    results.append(body)
                elif status in (429, 503):
                    time.sleep(0.2)  # explicit backpressure, not a drop
                else:
                    failures.append((status, body))

        threads = [threading.Thread(target=_loader) for _ in range(8)]
        for t in threads:
            t.start()

        # Sustained backpressure on the single replica → autoscale to 2.
        deadline = time.time() + 240
        scaled = False
        while time.time() < deadline:
            detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                           token=token)["deployment"]
            if detail["target_replicas"] == 2:
                scaled = True
                break
            time.sleep(0.5)
        assert scaled, f"never scaled up: {json.dumps(detail, indent=2)}"
        _wait_ready(c, token, dep_id, 2, timeout=240)

        # Load off → idle cooldown → drain back to 1 with zero dropped.
        stop_load.set()
        for t in threads:
            t.join(timeout=180)
        assert not failures, failures[:5]
        assert results, "no request completed under load"
        assert all(len(r["tokens"]) == 16 for r in results)

        deadline = time.time() + 240
        while time.time() < deadline:
            detail = c.api("GET", f"/api/v1/deployments/{dep_id}",
                           token=token)["deployment"]
            if (detail["target_replicas"] == 1
                    and len(detail["replicas"]) == 1):
                break
            time.sleep(0.5)
        assert detail["target_replicas"] == 1, detail
        # The drained replica completed cleanly (zero-dropped drain).
        serving = c.api("GET", "/api/v1/serving", token=token)["serving"]
        assert any(t["state"] == "COMPLETED" for t in serving), serving

        c.api("POST", f"/api/v1/deployments/{dep_id}/kill", token=token)
    finally:
        c.stop()
