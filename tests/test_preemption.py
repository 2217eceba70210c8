"""Spot-capacity survival: termination notices, deadline-budgeted
emergency checkpoints, and agent drain → reschedule
(docs/cluster-ops.md "Preemption & drain lifecycle",
docs/checkpointing.md "Emergency checkpoints").

Fast tier-1 tests cover the deadline parsing + backoff/join fixes in the
preemption watcher, the emergency-save budget math, the Trainer's
emergency/skip paths in local mode (bit-identical restore), and the
master's DRAINING lifecycle (notice route, scheduler exclusion, admin
enable/disable) through the native master harness. The `-m slow` e2e
drives a real 2-agent devcluster through a mid-trial spot notice:
emergency COMPLETED checkpoint inside the deadline, DRAINING agent takes
no new work, trial resumes on the survivor.
"""

import json
import os
import sqlite3
import sys
import threading
import time

import jax
import numpy as np
import pytest

from test_platform_e2e import (  # noqa: F401  (fixture re-export)
    FIXTURES,
    Devcluster,
    _create_experiment,
    _experiment_config,
    _wait_experiment,
)

from determined_tpu import core
from determined_tpu.core._preempt import PreemptContext, _PreemptionWatcher
from determined_tpu.train import Trainer
from determined_tpu.train.health import PreemptionConfig
from determined_tpu.train.trial import TrialContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "fixtures", "selfheal"))

from trial_def import LinearTrial  # noqa: E402


# ---------------------------------------------------------------------------
# Watcher: deadline/reason parsing, falsy-response backoff, bounded join.
# ---------------------------------------------------------------------------


class _ScriptedSession:
    """Fake Session: yields `responses` in order, repeating the last one
    (callables are invoked; exceptions are raised)."""

    def __init__(self, responses):
        self._responses = list(responses)
        self.calls = 0
        self.posts = []

    def get(self, path, params=None, timeout=None):
        self.calls += 1
        r = self._responses[min(self.calls - 1, len(self._responses) - 1)]
        if callable(r):
            r = r()
        if isinstance(r, Exception):
            raise r
        return r

    def post(self, path, body=None, **kwargs):
        self.posts.append(path)
        return {}


def _wait_for(cond, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def test_watcher_parses_deadline_and_reason():
    sess = _ScriptedSession([
        {"preempt": False},
        {"preempt": True, "deadline_seconds": 12.5,
         "reason": "spot_preemption"},
    ])
    ctx = PreemptContext(sess, allocation_id="a1")
    try:
        assert _wait_for(lambda: ctx.should_preempt(auto_ack=False))
        remaining = ctx.preemption_deadline()
        assert remaining is not None and 10.0 < remaining <= 12.5
        assert ctx.preemption_reason() == "spot_preemption"
        # the deadline counts DOWN between calls
        time.sleep(0.05)
        assert ctx.preemption_deadline() < remaining
    finally:
        ctx.close()


def test_watcher_without_deadline_is_unbounded():
    sess = _ScriptedSession([{"preempt": True}])
    ctx = PreemptContext(sess, allocation_id="a1")
    try:
        assert _wait_for(lambda: ctx.should_preempt(auto_ack=False))
        assert ctx.preemption_deadline() is None
    finally:
        ctx.close()


def test_watcher_garbage_deadline_treated_as_unbounded():
    sess = _ScriptedSession([
        {"preempt": True, "deadline_seconds": "soon"}])
    ctx = PreemptContext(sess, allocation_id="a1")
    try:
        assert _wait_for(lambda: ctx.should_preempt(auto_ack=False))
        assert ctx.preemption_deadline() is None
    finally:
        ctx.close()


def test_watcher_backs_off_on_falsy_responses():
    """Satellite: a successful-but-falsy response (master restarting
    behind a proxy, 404 body → None) must not hot-loop the poll."""
    sess = _ScriptedSession([None])
    w = _PreemptionWatcher(sess, "a1", backoff_base=0.05, backoff_cap=0.2)
    w.start()
    time.sleep(0.8)
    w.close()
    # Backoff schedule 0.05, 0.1, 0.2, 0.2... → a handful of calls in
    # 0.8s. A zero-delay hot loop would make thousands.
    assert 2 <= sess.calls <= 20, sess.calls
    assert not w.is_alive()


def test_watcher_backs_off_on_exceptions_capped():
    sess = _ScriptedSession([ConnectionError("down")])
    w = _PreemptionWatcher(sess, "a1", backoff_base=0.05, backoff_cap=0.2)
    w.start()
    time.sleep(0.8)
    w.close()
    assert 2 <= sess.calls <= 20, sess.calls
    assert not w.is_alive()


def test_watcher_long_poll_false_repolls_without_backoff():
    """A well-formed {"preempt": false} is the long-poll timing out — the
    re-poll must be immediate (that IS the protocol), not backed off."""
    sess = _ScriptedSession([{"preempt": False}] * 30 + [{"preempt": True}])
    w = _PreemptionWatcher(sess, "a1", backoff_base=0.5)
    t0 = time.monotonic()
    w.start()
    assert _wait_for(lambda: w.preempted, timeout=2.0)
    assert time.monotonic() - t0 < 1.0, "long-poll returns were backed off"
    assert sess.calls == 31
    w.close()


def test_watcher_close_joins_thread_no_orphans():
    """Satellite: close() joins (bounded) so the threading.enumerate()
    orphan assertions hold for the watcher too."""
    sess = _ScriptedSession([{"preempt": False}])
    ctx = PreemptContext(sess, allocation_id="a1")
    assert any(t.name == "preemption-watcher" for t in threading.enumerate())
    ctx.close()
    assert not any(
        t.name == "preemption-watcher" and t.is_alive()
        for t in threading.enumerate())


def test_force_deadline_local_mode():
    ctx = PreemptContext(None)
    assert ctx.preemption_deadline() is None
    ctx.force(deadline=30.0)
    assert ctx.should_preempt()
    d = ctx.preemption_deadline()
    assert d is not None and 29.0 < d <= 30.0


# ---------------------------------------------------------------------------
# Budget math (PreemptionConfig).
# ---------------------------------------------------------------------------


def test_budget_no_deadline_always_saves():
    assert PreemptionConfig().should_attempt_save(None, None)
    assert PreemptionConfig().should_attempt_save(None, 1e9)


def test_budget_no_estimate_is_optimistic():
    # No observed save cost yet: attempt — a blown budget leaves only a
    # PARTIAL torso that lineage fallback skips, never a corrupt restore.
    assert PreemptionConfig().should_attempt_save(30.0, None)


def test_budget_estimate_fits():
    cfg = PreemptionConfig(budget_safety_factor=1.5, budget_margin_sec=2.0)
    # 10s estimate * 1.5 = 15s <= 30 - 2 → attempt
    assert cfg.should_attempt_save(30.0, 10_000.0)


def test_budget_estimate_does_not_fit():
    cfg = PreemptionConfig(budget_safety_factor=1.5, budget_margin_sec=2.0)
    # 10s estimate * 1.5 = 15s > 15 - 2 → skip
    assert not cfg.should_attempt_save(15.0, 10_000.0)


def test_budget_margin_reserved():
    cfg = PreemptionConfig(budget_safety_factor=1.0, budget_margin_sec=5.0)
    assert not cfg.should_attempt_save(5.0, 1.0)  # margin eats the window
    assert not cfg.should_attempt_save(4.0, None)


def test_budget_disabled_never_saves():
    cfg = PreemptionConfig(emergency_checkpoint=False)
    assert not cfg.should_attempt_save(1e9, 1.0)
    assert not cfg.should_attempt_save(None, None)


def test_preemption_config_resolution_precedence():
    class T:
        preemption = {"budget_margin_sec": 7.0}

    cfg = PreemptionConfig.resolve(
        T(), {"preemption": {"budget_margin_sec": 1.0}})
    assert cfg.budget_margin_sec == 7.0  # trial attribute wins
    cfg = PreemptionConfig.resolve(
        None, {"preemption": {"emergency_checkpoint": False}})
    assert not cfg.emergency_checkpoint
    assert PreemptionConfig.resolve(None, None) == PreemptionConfig()
    # bare bool == emergency_checkpoint switch
    assert not PreemptionConfig.from_block(False).emergency_checkpoint
    # floors applied
    assert PreemptionConfig.from_block(
        {"budget_safety_factor": 0.1}).budget_safety_factor == 1.0


# ---------------------------------------------------------------------------
# Trainer: emergency checkpoint / budget-exhausted skip (local mode).
# ---------------------------------------------------------------------------


def _local_core(tmp_path, max_length):
    return core.init(
        max_length=max_length,
        checkpoint_dir=str(tmp_path / "ckpts"),
        async_checkpointing=False,
    )


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _grace_report(ctx):
    rows = [m for m in ctx.train.local_training_metrics
            if "preemption_grace_used_ms" in m["metrics"]]
    assert rows, "preemption_grace_used_ms never reported"
    return rows[-1]["metrics"]


class _ForcingTrial(LinearTrial):
    """LinearTrial whose data stream raises the (forced) preemption with a
    deadline mid-run — the deterministic stand-in for the notice landing
    between two steps."""

    def __init__(self, tctx, on_batch, action):
        super().__init__(tctx)
        self._on_batch = on_batch
        self._action = action

    def build_training_data(self):
        rng = np.random.default_rng(7)
        for i in range(256):
            if i == self._on_batch:
                self._action()
            yield {"x": rng.normal(size=(8, 4)).astype(np.float32)}


def test_trainer_emergency_checkpoint_commits_within_deadline(tmp_path):
    """Deadline preemption with room in the budget: the Trainer saves
    out-of-band, the COMMIT lands before exit (not in the epilogue), the
    grace metric is reported, and a fresh process restores the emergency
    checkpoint bit-identically."""
    ctx = _local_core(tmp_path, max_length=64)
    trial = _ForcingTrial(
        TrialContext(), on_batch=5,
        action=lambda: ctx.preempt.force(deadline=60.0))
    trainer = Trainer(trial, core_context=ctx)
    state = trainer.fit(report_period=1, preempt_period=1)
    step = int(jax.device_get(state.step))
    assert step == 6, "should have stopped at the first poll past batch 5"

    ck = tmp_path / "ckpts" / f"trial0-step{step}"
    assert (ck / "COMMIT").exists() and (ck / "manifest.json").exists(), (
        "emergency checkpoint must be fully committed, not a torso")
    metrics = _grace_report(ctx)
    assert metrics["preemption_emergency_checkpoint"] == 1.0
    assert metrics["preemption_grace_used_ms"] >= 0.0
    ctx.close()

    # bit-identical resume in a fresh context
    ctx2 = _local_core(tmp_path, max_length=64)
    trainer2 = Trainer(LinearTrial(TrialContext()), core_context=ctx2)
    trainer2._build(seed=0)
    restored = trainer2._restore(f"trial0-step{step}")
    assert restored == f"trial0-step{step}"
    expected = ctx2.checkpoint.restore_state(f"trial0-step{step}",
                                             trainer2.state)
    assert _tree_equal(trainer2.state, expected)
    ctx2.close()


def test_trainer_budget_exhausted_skips_save_and_restores_previous(tmp_path):
    """Acceptance: with a deadline shorter than the estimated save time,
    the trainer skips the emergency save, exits cleanly, and restore
    lands on the previous COMPLETED checkpoint — never a PARTIAL torso."""
    ctx = _local_core(tmp_path, max_length=64)

    def blow_budget():
        # pretend the last durable save took an hour, then give 5s grace
        ctx.checkpoint.last_save_ms = 3_600_000.0
        ctx.preempt.force(deadline=5.0)

    # on_batch=4 → the poll trips at step 5, NOT a checkpoint_period
    # boundary: the newest COMPLETED checkpoint is the periodic step-4 one.
    trial = _ForcingTrial(TrialContext(), on_batch=4, action=blow_budget)
    trainer = Trainer(trial, core_context=ctx)
    state = trainer.fit(report_period=1, preempt_period=1,
                        checkpoint_period=2)
    step = int(jax.device_get(state.step))
    assert step == 5

    # The skipped save must not have touched storage at all: no torso.
    assert not (tmp_path / "ckpts" / f"trial0-step{step}").exists()
    metrics = _grace_report(ctx)
    assert metrics["preemption_emergency_checkpoint"] == 0.0
    # The periodic step-4 checkpoint is the newest COMPLETED one.
    assert ctx.checkpoint.lineage()[0] == "trial0-step4"
    ctx.close()

    # A managed restart would point at step 4; even a stale pointer to
    # the never-written step-6 id walks back to step 4, bit-identically.
    ctx2 = _local_core(tmp_path, max_length=64)
    trainer2 = Trainer(LinearTrial(TrialContext()), core_context=ctx2)
    trainer2._build(seed=0)
    assert trainer2._restore(f"trial0-step{step}") == "trial0-step4"
    expected = ctx2.checkpoint.restore_state("trial0-step4", trainer2.state)
    assert _tree_equal(trainer2.state, expected)
    ctx2.close()


def test_trainer_unbounded_preemption_keeps_old_behavior(tmp_path):
    """No deadline → the pre-existing path: checkpoint at the boundary,
    commit in the epilogue, no grace metric."""
    ctx = _local_core(tmp_path, max_length=64)
    trial = _ForcingTrial(TrialContext(), on_batch=5,
                          action=lambda: ctx.preempt.force())
    trainer = Trainer(trial, core_context=ctx)
    state = trainer.fit(report_period=1, preempt_period=1)
    step = int(jax.device_get(state.step))
    assert (tmp_path / "ckpts" / f"trial0-step{step}" / "COMMIT").exists()
    assert not any("preemption_grace_used_ms" in m["metrics"]
                   for m in ctx.train.local_training_metrics)
    ctx.close()


def test_validation_polls_preemption(tmp_path):
    """Satellite: a long `_validate` pass must poll should_preempt() every
    preempt_period batches and cut the pass short."""
    ctx = _local_core(tmp_path, max_length=8)

    seen = []

    class ValTrial(LinearTrial):
        def evaluate(self, params, batch):
            import jax.numpy as jnp

            return {"loss": jnp.mean((params["w"] - batch["x"]) ** 2)}

        def build_validation_data(self):
            rng = np.random.default_rng(3)
            for i in range(1000):
                if i == 7:
                    ctx.preempt.force(deadline=60.0)
                seen.append(i)
                yield {"x": rng.normal(size=(8, 4)).astype(np.float32)}

    trainer = Trainer(ValTrial(TrialContext()), core_context=ctx)
    trainer.fit(report_period=1, preempt_period=2)
    # The pass was cut short at the first poll after batch 7, nowhere
    # near the 1000 batches the iterator offers.
    assert len(seen) < 20, f"validation never polled preemption: {len(seen)}"
    # ... but the partial averages were still reported.
    assert any("validation_loss" in m["metrics"]
               for m in ctx.train.local_validation_metrics)
    ctx.close()


def test_last_save_ms_observed(tmp_path):
    ctx = _local_core(tmp_path, max_length=4)
    assert ctx.checkpoint.last_save_ms is None
    trainer = Trainer(LinearTrial(TrialContext()), core_context=ctx)
    trainer.fit(report_period=1)
    assert ctx.checkpoint.last_save_ms is not None
    assert ctx.checkpoint.last_save_ms > 0.0
    ctx.close()


# ---------------------------------------------------------------------------
# Master harness: DRAINING lifecycle + scheduler exclusion (tier-1 safe).
# ---------------------------------------------------------------------------


@pytest.fixture()
def master_only(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


def _register_fake_agent(c, admin, agent_id, slots=2):
    out = c.api("POST", "/api/v1/agents/register",
                {"id": agent_id, "resource_pool": "default",
                 "addr": "127.0.0.1",
                 "slots": [{"id": i, "type": "cpu"} for i in range(slots)]},
                token=admin)
    assert out["agent_id"] == agent_id


def _agent(c, token, agent_id):
    agents = c.api("GET", "/api/v1/agents", token=token)["agents"]
    return next(a for a in agents if a["id"] == agent_id)


def _trial_allocation(c, token, eid, timeout=10.0):
    """(allocation_id, state) of the experiment's single trial's job."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        jobs = [j for j in c.api("GET", "/api/v1/job-queues",
                                 token=token)["jobs"]
                if j.get("experiment_id") == eid]
        if jobs:
            return jobs[0]["allocation_id"], jobs[0]["state"]
        time.sleep(0.2)
    raise TimeoutError("trial allocation never appeared")


def _wait_alloc_state(c, token, eid, want, timeout=15.0):
    deadline = time.time() + timeout
    state = None
    while time.time() < deadline:
        _, state = _trial_allocation(c, token, eid)
        if state == want:
            return
        time.sleep(0.2)
    raise AssertionError(f"allocation stuck in {state}, wanted {want}")


def test_preempt_notice_marks_draining_and_pushes_deadline(master_only):
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "fake-1")
    assert _agent(c, admin, "fake-1")["state"] == "ENABLED"

    # An allocation lands on the only agent...
    eid, token = _create_experiment(c, _experiment_config(c.tmpdir))
    _wait_alloc_state(c, token, eid, "SCHEDULED")
    aid, _ = _trial_allocation(c, token, eid)

    # ...then the notice arrives.
    r = c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
              {"deadline_seconds": 25, "reason": "spot_preemption"},
              token=admin)
    assert r["state"] == "DRAINING"
    a = _agent(c, admin, "fake-1")
    assert a["state"] == "DRAINING"
    assert a["drain_reason"] == "spot_preemption"
    assert 20 < a["drain_deadline_seconds"] <= 25

    # The allocation's preemption signal carries the remaining deadline.
    sig = c.api("GET",
                f"/api/v1/allocations/{aid}/signals/preemption"
                "?timeout_seconds=0", token=token)
    assert sig["preempt"] is True
    assert sig["reason"] == "spot_preemption"
    assert 0 < sig["deadline_seconds"] <= 25

    # Repeated notices may only TIGHTEN the deadline.
    c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
          {"deadline_seconds": 10, "reason": "spot_preemption"}, token=admin)
    assert _agent(c, admin, "fake-1")["drain_deadline_seconds"] <= 10
    c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
          {"deadline_seconds": 300, "reason": "host_maintenance"},
          token=admin)
    assert _agent(c, admin, "fake-1")["drain_deadline_seconds"] <= 10

    # Notices persisted for spot-churn audits (migration 18).
    c.kill_master()
    with sqlite3.connect(c.db_path) as db:
        rows = db.execute(
            "SELECT agent_id, reason, deadline_seconds FROM agent_notices "
            "ORDER BY id").fetchall()
    assert rows[0] == ("fake-1", "spot_preemption", 25.0)
    assert len(rows) == 3


def test_draining_agent_excluded_from_placement(master_only):
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "fake-1")
    c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
          {"deadline_seconds": 3600, "reason": "spot_preemption"},
          token=admin)

    eid, token = _create_experiment(c, _experiment_config(c.tmpdir))
    _, state = _trial_allocation(c, token, eid)
    time.sleep(1.5)  # give the scheduler every chance to misplace it
    _, state = _trial_allocation(c, token, eid)
    assert state == "QUEUED", "scheduler placed work on a DRAINING agent"

    # Fresh capacity arrives → the queue drains onto IT.
    _register_fake_agent(c, admin, "fake-2")
    _wait_alloc_state(c, token, eid, "SCHEDULED")
    aid, _ = _trial_allocation(c, token, eid)
    alloc = c.api("GET", f"/api/v1/allocations/{aid}", token=token)[
        "allocation"]
    assert [r["agent_id"] for r in alloc["resources"]] == ["fake-2"]


def test_admin_enable_clears_draining_and_restores_placement(master_only):
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "fake-1")
    c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
          {"deadline_seconds": 3600, "reason": "host_maintenance"},
          token=admin)
    eid, token = _create_experiment(c, _experiment_config(c.tmpdir))
    time.sleep(1.0)
    _, state = _trial_allocation(c, token, eid)
    assert state == "QUEUED"

    # Operator override: the maintenance completed without a termination.
    c.api("POST", "/api/v1/agents/fake-1/enable", {}, token=admin)
    a = _agent(c, admin, "fake-1")
    assert a["state"] == "ENABLED" and a["drain_reason"] == ""
    _wait_alloc_state(c, token, eid, "SCHEDULED")


def test_fresh_register_clears_draining(master_only):
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "fake-1")
    c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
          {"deadline_seconds": 30, "reason": "spot_preemption"}, token=admin)
    assert _agent(c, admin, "fake-1")["state"] == "DRAINING"
    # The replacement machine boots with the same id and registers fresh.
    _register_fake_agent(c, admin, "fake-1")
    assert _agent(c, admin, "fake-1")["state"] == "ENABLED"


def test_preempt_notice_validation_and_auth(master_only):
    import urllib.error

    c = master_only
    admin = c.login("admin")
    user = c.login()
    _register_fake_agent(c, admin, "fake-1")

    try:
        c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
              {"deadline_seconds": 30}, token=user)
        raise AssertionError("non-agent/non-admin notice should 403")
    except urllib.error.HTTPError as e:
        assert e.code == 403
    try:
        c.api("POST", "/api/v1/agents/fake-1/preempt_notice",
              {"deadline_seconds": -5}, token=admin)
        raise AssertionError("negative deadline should 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400
    try:
        c.api("POST", "/api/v1/agents/no-such/preempt_notice",
              {"deadline_seconds": 30}, token=admin)
        raise AssertionError("unknown agent should 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


# ---------------------------------------------------------------------------
# Satellite: the pre-existing admin drain endpoints, previously untested.
# ---------------------------------------------------------------------------


def test_admin_disable_excludes_enable_restores(master_only):
    """POST /api/v1/agents/{id}/enable|disable: disabled slots take no new
    allocations; re-enable restores placement."""
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "fake-1")

    c.api("POST", "/api/v1/agents/fake-1/disable", {}, token=admin)
    a = _agent(c, admin, "fake-1")
    assert a["state"] == "DISABLED"
    assert all(not s["enabled"] for s in a["slots"])

    eid, token = _create_experiment(c, _experiment_config(c.tmpdir))
    time.sleep(1.5)
    _, state = _trial_allocation(c, token, eid)
    assert state == "QUEUED", "disabled slots accepted an allocation"

    c.api("POST", "/api/v1/agents/fake-1/enable", {}, token=admin)
    a = _agent(c, admin, "fake-1")
    assert a["state"] == "ENABLED"
    assert all(s["enabled"] for s in a["slots"])
    _wait_alloc_state(c, token, eid, "SCHEDULED")


def test_admin_drain_endpoints_are_admin_only(master_only):
    import urllib.error

    c = master_only
    admin = c.login("admin")
    user = c.login()
    _register_fake_agent(c, admin, "fake-1")
    for action in ("disable", "enable"):
        try:
            c.api("POST", f"/api/v1/agents/fake-1/{action}", {}, token=user)
            raise AssertionError(f"non-admin {action} should 403")
        except urllib.error.HTTPError as e:
            assert e.code == 403
    # unknown agent → 404 (routed, validated)
    try:
        c.api("POST", "/api/v1/agents/no-such/disable", {}, token=admin)
        raise AssertionError("unknown agent should 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


# ---------------------------------------------------------------------------
# Capstone e2e (slow): spot notice mid-trial on a 2-agent devcluster.
# ---------------------------------------------------------------------------


def _task_log_text(c, token, trial_id):
    logs = c.api("GET", f"/api/v1/tasks/trial-{trial_id}/logs?offset=0",
                 token=token)["logs"]
    return "\n".join(line["log"] for line in logs)


@pytest.mark.slow
def test_spot_notice_emergency_checkpoint_and_reschedule_e2e(
        tmp_path, native_binaries):
    """Acceptance: a 30s-deadline termination notice mid-trial on a
    2-agent devcluster → the trial commits a COMPLETED (manifest+COMMIT)
    emergency checkpoint within the deadline, the agent goes DRAINING and
    takes no new allocations, and the trial resumes ON THE SURVIVOR from
    exactly the emergency checkpoint (restarts >= 1, no lineage rollback
    past it)."""
    c = Devcluster(str(tmp_path), native_binaries, slots=1)
    c.start_master()
    notice_files = {}
    for agent_id in ("spot-a", "spot-b"):
        nf = os.path.join(str(tmp_path), f"notice-{agent_id}.json")
        notice_files[agent_id] = nf
        c.start_agent(agent_id, extra_env={"DET_AGENT_NOTICE_FILE": nf})
    try:
        config = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": 400}},
            extra={"max_restarts": 2,
                   "entrypoint": "python3 spot_train.py"},
        )
        config["environment"] = {"SPOT_STEP_SLEEP": "0.1"}
        eid, token = _create_experiment(c, config)
        sess_token = token

        # Wait until the trial is mid-run (reporting steps), then find
        # which agent runs it.
        deadline = time.time() + 120
        trial, victim = None, None
        while time.time() < deadline:
            trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                           token=token)["trials"]
            if trials:
                rows = c.api(
                    "GET",
                    f"/api/v1/trials/{trials[0]['id']}/metrics?group=training",
                    token=token)["metrics"]
                if len(rows) >= 5:  # several steps in: genuinely mid-trial
                    trial = trials[0]
                    jobs = [j for j in c.api("GET", "/api/v1/job-queues",
                                             token=token)["jobs"]
                            if j.get("experiment_id") == eid]
                    alloc = c.api(
                        "GET", f"/api/v1/allocations/{jobs[0]['allocation_id']}",
                        token=token)["allocation"]
                    victim = alloc["resources"][0]["agent_id"]
                    break
            time.sleep(0.5)
        assert trial is not None and victim in ("spot-a", "spot-b"), (
            "trial never started reporting")
        survivor = "spot-b" if victim == "spot-a" else "spot-a"

        # Checkpoints registered BEFORE the notice (periodic ones).
        def _completed_uuids():
            return {ck["uuid"] for ck in c.api(
                "GET",
                f"/api/v1/trials/{trial['id']}/checkpoints?state=COMPLETED",
                token=token)["checkpoints"]}

        pre_notice = _completed_uuids()

        # The notice: node `victim` disappears in 30 seconds.
        t_notice = time.time()
        with open(notice_files[victim], "w") as f:
            json.dump({"deadline_seconds": 30,
                       "reason": "spot_preemption"}, f)

        # The agent relays it; the master marks it DRAINING.
        deadline = time.time() + 20
        while time.time() < deadline:
            a = _agent(c, c.login("admin"), victim)
            if a["state"] == "DRAINING":
                break
            time.sleep(0.3)
        assert a["state"] == "DRAINING" and a["drain_reason"] == \
            "spot_preemption"

        # The emergency checkpoint must turn up COMPLETED in the registry
        # within the 30s deadline, fully committed on shared storage.
        # Verified MID-RUN: experiment-completion GC sweeps non-best
        # checkpoints later, so the disk evidence must be captured now.
        ck_root = os.path.join(str(tmp_path), "checkpoints")
        committed_mid_run = set()
        deadline = t_notice + 35.0
        settle_until = None  # keep collecting a bit past the first hit:
        # a periodic save can race the emergency one into the diff
        while time.time() < deadline:
            for uuid in _completed_uuids() - pre_notice:
                if uuid in committed_mid_run:
                    continue
                assert os.path.exists(
                    os.path.join(ck_root, uuid, "COMMIT")), uuid
                assert os.path.exists(
                    os.path.join(ck_root, uuid, "manifest.json")), uuid
                committed_mid_run.add(uuid)
            if committed_mid_run and settle_until is None:
                settle_until = time.time() + 8.0
            if settle_until is not None and time.time() > settle_until:
                break
            time.sleep(0.3)
        assert committed_mid_run, (
            "no COMPLETED emergency checkpoint within the 30s deadline")

        # The trial must be rescheduled onto the survivor and run to
        # completion there.
        _wait_experiment(c, eid, token, timeout=240.0)

        trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                       token=token)["trials"]
        assert trials[0]["state"] == "COMPLETED"
        assert trials[0]["restarts"] >= 1, (
            "the spot move must be recorded as a restart")

        text = _task_log_text(c, sess_token, trials[0]["id"])
        assert "emergency checkpoint committed" in text, text[-2000:]
        # The resumed run restored exactly the emergency checkpoint (no
        # lineage rollback past it): the step named in the emergency log
        # line is the step named in the restore log line.
        import re

        m = re.search(
            r"deadline preemption \(spot_preemption\) at step (\d+): "
            r"emergency checkpoint committed, grace used (\d+)ms", text)
        assert m, f"no emergency-checkpoint log line:\n{text[-2000:]}"
        em_step, grace_ms = int(m.group(1)), int(m.group(2))
        assert grace_ms < 30_000, "emergency save blew the 30s deadline"
        assert re.search(
            rf"restored from checkpoint trial\d+-step{em_step} at step "
            rf"{em_step}", text), (
            f"resume did not land on the emergency checkpoint:\n"
            f"{text[-2000:]}")

        # The checkpoint we saw committed mid-run IS the emergency one the
        # logs name (registry + disk + logs all agree on the step).
        assert any(u.endswith(f"-step{em_step}") for u in committed_mid_run), (
            f"emergency step {em_step} not among mid-run COMPLETED "
            f"checkpoints {committed_mid_run}")

        # The resumed run landed on the survivor, and the grace metric
        # flowed through the metrics path.
        jobs = [j for j in c.api("GET", "/api/v1/job-queues",
                                 token=token)["jobs"]
                if j.get("experiment_id") == eid]
        if jobs:  # terminal allocations may have left the queue view
            alloc = c.api("GET",
                          f"/api/v1/allocations/{jobs[-1]['allocation_id']}",
                          token=token)["allocation"]
            assert all(r["agent_id"] == survivor
                       for r in alloc["resources"])
        rows = c.api(
            "GET", f"/api/v1/trials/{trials[0]['id']}/metrics?group=training",
            token=token)["metrics"]
        assert any("preemption_grace_used_ms" in r["metrics"] for r in rows)
    finally:
        c.stop()


@pytest.mark.slow
def test_agent_preempt_notice_fault_point_e2e(tmp_path, native_binaries):
    """The `agent.preempt.notice` DET_FAULTS point: armed in the agent's
    environment, it fires once a task is running (mid-trial by
    construction), drains the agent with the DET_AGENT_PREEMPT_DEADLINE_S
    deadline, and the re-enabled agent finishes the trial."""
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent(extra_env={
        "DET_FAULTS": "agent.preempt.notice:error:1",
        "DET_AGENT_PREEMPT_DEADLINE_S": "60",
    })
    try:
        config = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": 120}},
            extra={"max_restarts": 2},
        )
        config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
        eid, token = _create_experiment(c, config)
        admin = c.login("admin")

        deadline = time.time() + 60
        a = None
        while time.time() < deadline:
            a = _agent(c, admin, "agent-0")
            if a["state"] == "DRAINING":
                break
            time.sleep(0.3)
        assert a and a["state"] == "DRAINING", (
            "fault point never drained the agent")
        assert a["drain_reason"] == "spot_preemption"
        assert 0 < a["drain_deadline_seconds"] <= 60

        # The sole agent is draining: the preempted trial re-queues but
        # cannot place. The operator re-enables (maintenance survived) →
        # placement restored, trial completes.
        time.sleep(3.0)
        c.api("POST", "/api/v1/agents/agent-0/enable", {}, token=admin)
        _wait_experiment(c, eid, token, timeout=240.0)
        trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                       token=token)["trials"]
        assert trials[0]["state"] == "COMPLETED"
        assert trials[0]["restarts"] >= 1
        assert "resumed from checkpoint" in _task_log_text(
            c, token, trials[0]["id"])

        c.kill_master()
        with sqlite3.connect(c.db_path) as db:
            rows = db.execute(
                "SELECT reason, deadline_seconds FROM agent_notices"
            ).fetchall()
        assert ("spot_preemption", 60.0) in rows
    finally:
        c.stop()
