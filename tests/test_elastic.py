"""Elastic re-meshing: trials resize across capacity loss instead of
requeueing (docs/elasticity.md).

Fast tier-1 tests cover the expconf `resources.elastic` block, the
resize-offer parsing/deadline on the preemption signal, the DTL204
every-size feasibility rule, the DevicePrefetcher detach (data-order
preservation), the Trainer's in-process reshard pipeline — including the
acceptance bit-identity contract: a 4-slot run resized to 2 matches an
uninterrupted 2-slot run restored from the same checkpoint — and the
master's full resize lifecycle (offer on drain, same-allocation
re-placement with restarts untouched, size history, grow-back, and the
`master.resize.offer.drop` fault proving requeue remains the fallback)
through the native master harness. The `-m slow` e2e drives a real
heterogeneous devcluster through a notice-file drain: shrink 2->1 slots
without a requeue, then grow back on re-enable.
"""

import json
import os
import sqlite3
import sys
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
from test_preemption import (  # noqa: F401
    _ScriptedSession,
    _register_fake_agent,
    _agent,
    _trial_allocation,
    _wait_alloc_state,
    _wait_for,
)

from determined_tpu import core, expconf
from determined_tpu.analysis import config_rules
from determined_tpu.core._preempt import PreemptContext
from determined_tpu.data import DevicePrefetcher
from determined_tpu.parallel.mesh import MeshConfig
from determined_tpu.train import Trainer
from determined_tpu.train.trial import TrialContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "fixtures", "selfheal"))

from trial_def import LinearTrial  # noqa: E402


# ---------------------------------------------------------------------------
# expconf: the resources.elastic block.
# ---------------------------------------------------------------------------


def _base_config(**resources):
    return {
        "entrypoint": "python3 train.py",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 8}},
        "resources": {"slots_per_trial": 4, **resources},
    }


def test_expconf_elastic_valid_and_defaults():
    cfg = _base_config(elastic={"min_slots": 2})
    assert expconf.validate(cfg) == []
    out = expconf.apply_defaults(cfg)
    assert out["resources"]["elastic"] == {"min_slots": 2, "max_slots": 4}


def test_expconf_elastic_rejects_bad_blocks():
    assert any("must be a mapping" in e for e in expconf.validate(
        _base_config(elastic=3)))
    assert any("unknown keys" in e for e in expconf.validate(
        _base_config(elastic={"minimum": 1})))
    assert any("positive int" in e for e in expconf.validate(
        _base_config(elastic={"min_slots": 0})))
    assert any("min_slots > max_slots" in e for e in expconf.validate(
        _base_config(elastic={"min_slots": 4, "max_slots": 2})))
    assert any("below" in e for e in expconf.validate(
        _base_config(elastic={"min_slots": 8, "max_slots": 16})))
    assert any("exceeds" in e for e in expconf.validate(
        _base_config(elastic={"min_slots": 1, "max_slots": 2})))


# ---------------------------------------------------------------------------
# DTL204: elastic configs must be runnable at EVERY size in [min, max].
# ---------------------------------------------------------------------------


def _dtl204_codes(cfg):
    return [d for d in config_rules.check_config(cfg) if d.code == "DTL204"]


def test_dtl204_flags_indivisible_batch_sizes():
    cfg = {
        "resources": {"slots_per_trial": 4,
                      "elastic": {"min_slots": 1, "max_slots": 4}},
        "hyperparameters": {"global_batch_size": 32, "mesh": {"data": -1}},
    }
    diags = _dtl204_codes(cfg)
    # 32 divides 1, 2, 4 but not 3.
    assert len(diags) == 1 and "elastic size 3" in diags[0].message


def test_dtl204_flags_unresolvable_mesh_sizes():
    cfg = {
        "resources": {"slots_per_trial": 4,
                      "elastic": {"min_slots": 2, "max_slots": 4}},
        "hyperparameters": {"global_batch_size": 32,
                            "mesh": {"tensor": 2, "data": -1}},
    }
    diags = _dtl204_codes(cfg)
    # tensor=2 cannot divide 3 slots.
    assert len(diags) == 1 and "does not resolve" in diags[0].message


def test_dtl204_clean_for_divisor_ranges():
    cfg = {
        "resources": {"slots_per_trial": 4,
                      "elastic": {"min_slots": 2, "max_slots": 4}},
        "hyperparameters": {"global_batch_size": 32, "mesh": {"data": -1}},
    }
    assert _dtl204_codes(cfg) == [] or all(
        "elastic size 3" in d.message for d in _dtl204_codes(cfg))
    # non-elastic configs never fire DTL204
    cfg2 = {
        "resources": {"slots_per_trial": 3},
        "hyperparameters": {"global_batch_size": 32, "mesh": {"data": -1}},
    }
    assert _dtl204_codes(cfg2) == []


def test_dtl204_suppressible():
    from determined_tpu.analysis import _preflight

    cfg = {
        "entrypoint": "python3 x.py",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 1}},
        "resources": {"slots_per_trial": 4,
                      "elastic": {"min_slots": 1, "max_slots": 4}},
        "hyperparameters": {"global_batch_size": 32, "mesh": {"data": -1}},
        "preflight": {"suppress": ["DTL204"]},
    }
    report = _preflight.preflight(cfg)
    d204 = [d for d in report.diagnostics if d.code == "DTL204"]
    assert d204 and all(d.suppressed for d in d204)


def test_dtl204_hbm_leg_per_candidate_mesh():
    """The abstract-trace engine runs per candidate size: a model that fits
    at the preferred size but blows the per-device budget at min_slots is
    flagged as DTL204 naming that size."""
    from determined_tpu.analysis._preflight import _elastic_hbm_diags

    class BigTrial(LinearTrial):
        def init_params(self, rng):
            import jax

            # ~4 MiB of params, fsdp-sharded: per-device share doubles
            # every halving of the mesh.
            return {"w": jax.random.normal(rng, (1024, 1024))}

        def param_logical_axes(self):
            return {"w": ("fsdp_dim", None)}

        def sharding_rules(self):
            from determined_tpu.parallel.sharding import LogicalRules

            return LogicalRules(rules=[("fsdp_dim", "fsdp"),
                                       ("batch", ("data", "fsdp"))])

        def mesh_config(self):
            return MeshConfig(data=1, fsdp=-1)

        def build_training_data(self):
            yield {"x": np.zeros((8, 1024), np.float32)}

        def loss(self, params, batch, rng):
            import jax.numpy as jnp

            return jnp.mean((batch["x"] @ params["w"]) ** 2)

    cfg = {
        "resources": {"slots_per_trial": 8,
                      "elastic": {"min_slots": 1, "max_slots": 8}},
        "hyperparameters": {},
    }
    # Budget chosen between the 8-way share and the 1-way share: fine at
    # the preferred 8, over budget at small sizes.
    trial = BigTrial(TrialContext(n_devices=8))
    diags = _elastic_hbm_diags(trial, cfg, preferred=8,
                               hbm_budget=6 * 2**20, source_file=None)
    assert diags, "undersized candidate meshes must flag DTL204"
    assert all(d.code == "DTL204" for d in diags)
    assert any("elastic size 1" in d.message for d in diags)
    # No budget armed -> no HBM leg (same contract as DTL004).
    assert _elastic_hbm_diags(trial, cfg, 8, None, None) == []


# ---------------------------------------------------------------------------
# Resize-offer parsing on the preemption signal.
# ---------------------------------------------------------------------------


def test_watcher_parses_resize_offer():
    sess = _ScriptedSession([
        {"preempt": False},
        {"preempt": True, "resize": True, "target_slots": 2,
         "deadline_seconds": 25.0, "reason": "spot_preemption"},
    ])
    ctx = PreemptContext(sess, allocation_id="a1")
    try:
        assert _wait_for(lambda: ctx.should_preempt(auto_ack=False))
        assert ctx.resize_target() == 2
        remaining = ctx.preemption_deadline()
        assert remaining is not None and 20.0 < remaining <= 25.0
        assert ctx.preemption_reason() == "spot_preemption"
    finally:
        ctx.close()


def test_watcher_garbage_resize_target_is_plain_preemption():
    sess = _ScriptedSession([
        {"preempt": True, "resize": True, "target_slots": "lots"}])
    ctx = PreemptContext(sess, allocation_id="a1")
    try:
        assert _wait_for(lambda: ctx.should_preempt(auto_ack=False))
        assert ctx.resize_target() is None
    finally:
        ctx.close()


def test_force_resize_and_reset():
    ctx = PreemptContext(None)
    assert ctx.resize_target() is None
    ctx.force_resize(2, deadline=30.0)
    assert ctx.should_preempt()
    assert ctx.resize_target() == 2
    d = ctx.preemption_deadline()
    assert d is not None and 29.0 < d <= 30.0
    ctx.reset()
    assert not ctx.should_preempt()
    assert ctx.resize_target() is None
    assert ctx.preemption_deadline() is None


def test_mesh_resolvable():
    assert MeshConfig().resolvable(3)
    assert MeshConfig(tensor=2).resolvable(4)
    assert not MeshConfig(tensor=2).resolvable(3)
    assert not MeshConfig(data=4).resolvable(2)


# ---------------------------------------------------------------------------
# DevicePrefetcher.detach — the data-order contract under a resize.
# ---------------------------------------------------------------------------


def test_prefetcher_detach_preserves_order():
    pf = DevicePrefetcher(iter(range(64)), depth=4)
    consumed = [next(pf) for _ in range(10)]
    assert consumed == list(range(10))
    # Let the producer fill the queue before detaching.
    time.sleep(0.2)
    staged, rest = pf.detach()
    remaining = staged + list(rest)
    assert consumed + remaining == list(range(64)), (
        "detach dropped or reordered batches")


def test_prefetcher_detach_then_rewrap():
    import itertools

    pf = DevicePrefetcher(iter(range(20)), depth=2)
    head = [next(pf) for _ in range(5)]
    staged, rest = pf.detach()
    pf2 = DevicePrefetcher(itertools.chain(staged, rest), depth=2)
    tail = list(pf2)
    assert head + tail == list(range(20))


# ---------------------------------------------------------------------------
# Trainer: the in-process reshard pipeline.
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


class IndexedTrial(LinearTrial):
    """LinearTrial over an index-addressed batch stream, so a comparison
    run can start mid-stream and consume bit-identical batches."""

    def __init__(self, tctx, start=0, n=64, on_batch=None, action=None):
        super().__init__(tctx)
        self._start, self._n = start, n
        self._on_batch, self._action = on_batch, action

    @staticmethod
    def batch(i):
        rng = np.random.default_rng(1000 + i)
        return {"x": rng.normal(size=(8, 4)).astype(np.float32)}

    def build_training_data(self):
        for i in range(self._start, self._n):
            if self._on_batch is not None and i == self._on_batch:
                self._action()
            yield self.batch(i)


def _losses(ctx, lo=None):
    out = []
    for m in ctx.train.local_training_metrics:
        if "loss" in m["metrics"] and (
                lo is None or m["steps_completed"] > lo):
            out.append((m["steps_completed"], float(m["metrics"]["loss"])))
    return out


def test_resize_bit_identity_vs_uninterrupted_target_run(tmp_path):
    """Acceptance: train on 4 slots, resize to 2 mid-run; the post-resize
    loss trajectory and final state are BIT-identical (f32, fixed seed) to
    an uninterrupted 2-slot run restored from the same checkpoint and fed
    the same batches."""
    devices = jax.devices()
    ctx = _local_core(tmp_path, max_length=12)
    trial = IndexedTrial(
        TrialContext(), on_batch=5,
        action=lambda: ctx.preempt.force_resize(2, deadline=60.0))
    trainer = Trainer(trial, core_context=ctx, devices=devices[:4])
    state = trainer.fit(report_period=1, preempt_period=1, seed=0)
    assert trainer.mesh.size == 2, "mesh did not resize"
    step = int(jax.device_get(state.step))
    assert step == 12
    # The resize happened at step 6 (first poll past batch 5): the
    # emergency checkpoint is trial0-step6, COMPLETED on disk.
    ck = tmp_path / "ckpts" / "trial0-step6"
    assert (ck / "COMMIT").exists() and (ck / "manifest.json").exists()
    resized_losses = _losses(ctx, lo=6)
    rows = [m["metrics"] for m in ctx.train.local_training_metrics
            if "resize_downtime_ms" in m["metrics"]]
    assert rows and rows[0]["resize_from_slots"] == 4.0
    assert rows[0]["resize_target_slots"] == 2.0
    ctx.close()

    # Uninterrupted 2-slot run from the same checkpoint, same batches.
    ctx2 = _local_core(tmp_path, max_length=12)
    trainer2 = Trainer(IndexedTrial(TrialContext(), start=6),
                       core_context=ctx2, devices=devices[:2])
    state2 = trainer2.fit(report_period=1, seed=0,
                          resume_from="trial0-step6")
    assert int(jax.device_get(state2.step)) == 12
    baseline_losses = _losses(ctx2, lo=6)
    assert resized_losses == baseline_losses, (
        "post-resize loss trajectory diverged from the uninterrupted "
        "2-slot run")
    assert _tree_equal(state, state2), (
        "post-resize state is not bit-identical to the uninterrupted run")
    ctx2.close()


def test_resize_grow_in_process(tmp_path):
    """Shrink is not the only direction: a grow offer re-meshes 2 -> 4."""
    devices = jax.devices()
    ctx = _local_core(tmp_path, max_length=10)
    trial = IndexedTrial(
        TrialContext(), on_batch=4,
        action=lambda: ctx.preempt.force_resize(4))
    trainer = Trainer(trial, core_context=ctx, devices=devices[:2])
    trainer._devices = list(devices[:4])  # capacity returns mid-run
    state = trainer.fit(report_period=1, preempt_period=1)
    assert trainer.mesh.size == 4
    assert int(jax.device_get(state.step)) == 10
    ctx.close()


def test_resize_budget_exhausted_falls_back_to_lineage(tmp_path):
    """A resize whose deadline cannot cover a fresh save reshard-restores
    the newest COMPLETED checkpoint instead (steps rewind, nothing is
    corrupted) and still finishes."""
    devices = jax.devices()
    ctx = _local_core(tmp_path, max_length=12)

    def blow_budget():
        ctx.checkpoint.last_save_ms = 3_600_000.0
        ctx.preempt.force_resize(2, deadline=5.0)

    # on_batch=4 -> the poll trips at step 5, NOT a checkpoint_period
    # boundary: the newest COMPLETED checkpoint is the periodic step-4 one.
    trial = IndexedTrial(TrialContext(), n=128, on_batch=4,
                         action=blow_budget)
    trainer = Trainer(trial, core_context=ctx, devices=devices[:4])
    state = trainer.fit(report_period=1, preempt_period=1,
                        checkpoint_period=2)
    assert trainer.mesh.size == 2
    assert int(jax.device_get(state.step)) == 12
    # No step-5 emergency checkpoint was written; the reshard restored the
    # periodic step-4 one and the run rewound one step.
    assert not (tmp_path / "ckpts" / "trial0-step5").exists()
    assert (tmp_path / "ckpts" / "trial0-step4" / "COMMIT").exists()
    ctx.close()


def test_resize_with_prefetch_preserves_stream(tmp_path):
    """The detach()+rewrap pipeline: a resized run with prefetch ON is
    bit-identical to the same run with prefetch OFF (any dropped or
    reordered staged batch would diverge the SGD trajectory)."""
    devices = jax.devices()
    states = []
    for prefetch in (False, {"depth": 3}):
        ctx = _local_core(tmp_path, max_length=12)
        # Pin the resize to the very first poll so both runs reshard at
        # the same step regardless of producer lookahead.
        ctx.preempt.force_resize(2, deadline=60.0)
        trial = IndexedTrial(TrialContext())
        trial.prefetch = prefetch
        trainer = Trainer(trial, core_context=ctx, devices=devices[:4])
        states.append(trainer.fit(report_period=1, preempt_period=1))
        assert trainer.mesh.size == 2
        ctx.close()
    assert _tree_equal(states[0], states[1]), (
        "prefetch detach/rewrap changed the consumed batch stream")


# ---------------------------------------------------------------------------
# Master harness: the full resize lifecycle (tier-1 safe, fake agents).
# ---------------------------------------------------------------------------


@pytest.fixture()
def master_only(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


def _elastic_config(tmp_path, min_slots=1, max_slots=2, slots=2, extra=None):
    config = _experiment_config(tmp_path)
    config["resources"] = {
        "slots_per_trial": slots,
        "elastic": {"min_slots": min_slots, "max_slots": max_slots},
    }
    config.update(extra or {})
    return config


def _report_exit(c, admin, agent_id, aid, exit_code=0):
    c.api("POST", f"/api/v1/agents/{agent_id}/allocations/{aid}/state",
          {"state": "EXITED", "exit_code": exit_code}, token=admin)


def _signal(c, token, aid):
    return c.api(
        "GET",
        f"/api/v1/allocations/{aid}/signals/preemption?timeout_seconds=0",
        token=token)


def _alloc(c, token, aid):
    return c.api("GET", f"/api/v1/allocations/{aid}", token=token)[
        "allocation"]


def _trial(c, token, eid):
    return c.api("GET", f"/api/v1/experiments/{eid}/trials",
                 token=token)["trials"][0]


def test_master_resize_offer_shrink_and_grow_lifecycle(master_only):
    """Drain a 2-slot agent under an elastic 2-slot trial with a 1-slot
    survivor: the master offers a shrink to 1, the clean exit becomes a
    same-allocation re-placement (restarts unchanged, size history 2->1),
    and once the drained agent is re-enabled the trial gets a grow offer
    back to 2."""
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "big", slots=2)
    _register_fake_agent(c, admin, "small", slots=1)

    eid, token = _create_experiment(c, _elastic_config(c.tmpdir))
    _wait_alloc_state(c, token, eid, "SCHEDULED")
    aid, _ = _trial_allocation(c, token, eid)
    alloc = _alloc(c, token, aid)
    assert alloc["slots"] == 2
    assert {r["agent_id"] for r in alloc["resources"]} == {"big"}

    # The notice arrives: the signal carries a RESIZE offer, not a bare
    # preemption.
    c.api("POST", "/api/v1/agents/big/preempt_notice",
          {"deadline_seconds": 60, "reason": "spot_preemption"}, token=admin)
    sig = _signal(c, token, aid)
    assert sig["preempt"] is True
    assert sig.get("resize") is True
    assert sig.get("target_slots") == 1
    assert 0 < sig["deadline_seconds"] <= 60

    # Harness contract: budgeted checkpoint, clean exit.
    _report_exit(c, admin, "big", aid)

    # Same allocation, new size, surviving agent — no trial requeue.
    deadline = time.time() + 15
    while time.time() < deadline:
        alloc = _alloc(c, token, aid)
        if alloc["slots"] == 1 and alloc["resources"] and \
                alloc["resources"][0]["agent_id"] == "small":
            break
        time.sleep(0.2)
    assert alloc["slots"] == 1, f"allocation never shrank: {alloc}"
    assert [r["agent_id"] for r in alloc["resources"]] == ["small"]
    t = _trial(c, token, eid)
    assert t.get("restarts", 0) == 0, "elastic resize must not burn restarts"
    assert t.get("current_slots") == 1

    hist = c.api("GET", f"/api/v1/allocations/{aid}/size_history",
                 token=token)["size_history"]
    assert [(h["from_slots"], h["to_slots"]) for h in hist] == [(2, 1)]
    assert hist[0]["reason"] == "spot_preemption"

    # Container comes up on the survivor; capacity returns; cooldown
    # passes -> the scheduler offers a grow back toward the preferred 2.
    c.api("POST", f"/api/v1/agents/small/allocations/{aid}/state",
          {"state": "RUNNING"}, token=admin)
    c.api("POST", "/api/v1/agents/big/enable", {}, token=admin)
    deadline = time.time() + 20  # 5s grow cooldown + scheduler ticks
    sig = {}
    while time.time() < deadline:
        sig = _signal(c, token, aid)
        if sig.get("resize"):
            break
        time.sleep(0.5)
    assert sig.get("resize") is True and sig.get("target_slots") == 2, sig
    # reason distinguishes opportunistic grows from drains
    assert "scale-up" in sig.get("reason", "")

    # Accept it: clean exit -> re-placed at 2 slots on the big agent.
    _report_exit(c, admin, "small", aid)
    deadline = time.time() + 15
    while time.time() < deadline:
        alloc = _alloc(c, token, aid)
        if alloc["slots"] == 2 and alloc["resources"]:
            break
        time.sleep(0.2)
    assert alloc["slots"] == 2
    assert {r["agent_id"] for r in alloc["resources"]} == {"big"}
    hist = c.api("GET", f"/api/v1/allocations/{aid}/size_history",
                 token=token)["size_history"]
    assert [(h["from_slots"], h["to_slots"]) for h in hist] == \
        [(2, 1), (1, 2)]
    assert _trial(c, token, eid).get("restarts", 0) == 0

    # Persisted for post-mortems (migration 20).
    c.kill_master()
    with sqlite3.connect(c.db_path) as db:
        rows = db.execute(
            "SELECT from_slots, to_slots FROM allocation_size_history "
            "ORDER BY id").fetchall()
    assert rows == [(2, 1), (1, 2)]


def test_master_non_elastic_keeps_requeue_behavior(master_only):
    """Control: without resources.elastic the PR-5 pipeline is untouched —
    plain deadline preemption, clean exit requeues the trial with
    restarts += 1 under a NEW allocation."""
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "big", slots=2)
    _register_fake_agent(c, admin, "small", slots=1)

    config = _experiment_config(c.tmpdir)
    config["resources"] = {"slots_per_trial": 1}
    eid, token = _create_experiment(c, config)
    _wait_alloc_state(c, token, eid, "SCHEDULED")
    aid, _ = _trial_allocation(c, token, eid)

    victim = _alloc(c, token, aid)["resources"][0]["agent_id"]
    c.api("POST", f"/api/v1/agents/{victim}/preempt_notice",
          {"deadline_seconds": 60, "reason": "spot_preemption"}, token=admin)
    sig = _signal(c, token, aid)
    assert sig["preempt"] is True and "resize" not in sig

    _report_exit(c, admin, victim, aid)
    deadline = time.time() + 15
    new_aid = aid
    while time.time() < deadline:
        new_aid, state = _trial_allocation(c, token, eid)
        if new_aid != aid and state == "SCHEDULED":
            break
        time.sleep(0.2)
    assert new_aid != aid, "non-elastic trial should requeue a NEW allocation"
    assert _trial(c, token, eid).get("restarts", 0) == 1


def test_master_resize_offer_drop_falls_back_to_requeue(master_only):
    """The `master.resize.offer.drop` fault point eats the offer: the
    drain degrades to the PR-5 path (plain preemption, trial requeue,
    restarts += 1) — proving requeue remains the fallback."""
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "big", slots=2)
    _register_fake_agent(c, admin, "small", slots=1)
    c.api("POST", "/api/v1/debug/faults",
          {"point": "master.resize.offer.drop", "mode": "error"},
          token=admin)

    eid, token = _create_experiment(c, _elastic_config(c.tmpdir))
    _wait_alloc_state(c, token, eid, "SCHEDULED")
    aid, _ = _trial_allocation(c, token, eid)

    c.api("POST", "/api/v1/agents/big/preempt_notice",
          {"deadline_seconds": 60, "reason": "spot_preemption"}, token=admin)
    sig = _signal(c, token, aid)
    assert sig["preempt"] is True and "resize" not in sig, sig

    _report_exit(c, admin, "big", aid)
    deadline = time.time() + 15
    new_aid = aid
    while time.time() < deadline:
        new_aid, _ = _trial_allocation(c, token, eid)
        if new_aid != aid:
            break
        time.sleep(0.2)
    assert new_aid != aid, "dropped offer must fall back to a requeue"
    assert _trial(c, token, eid).get("restarts", 0) == 1
    # No size transition was recorded.
    hist = c.api("GET", f"/api/v1/allocations/{aid}/size_history",
                 token=token)["size_history"]
    assert hist == []


def test_master_unclean_exit_with_offer_requeues(master_only):
    """A nonzero exit while a resize offer is outstanding must NOT become
    a size transition — the trial takes the ordinary failure/restart
    path."""
    c = master_only
    admin = c.login("admin")
    _register_fake_agent(c, admin, "big", slots=2)
    _register_fake_agent(c, admin, "small", slots=1)

    eid, token = _create_experiment(c, _elastic_config(c.tmpdir))
    _wait_alloc_state(c, token, eid, "SCHEDULED")
    aid, _ = _trial_allocation(c, token, eid)
    c.api("POST", "/api/v1/agents/big/preempt_notice",
          {"deadline_seconds": 60, "reason": "spot_preemption"}, token=admin)
    assert _signal(c, token, aid).get("resize") is True

    _report_exit(c, admin, "big", aid, exit_code=137)
    deadline = time.time() + 15
    new_aid = aid
    while time.time() < deadline:
        new_aid, _ = _trial_allocation(c, token, eid)
        if new_aid != aid:
            break
        time.sleep(0.2)
    assert new_aid != aid
    assert _trial(c, token, eid).get("restarts", 0) == 1
    assert c.api("GET", f"/api/v1/allocations/{aid}/size_history",
                 token=token)["size_history"] == []


# ---------------------------------------------------------------------------
# Capstone e2e (slow): heterogeneous devcluster, notice-file drain.
# ---------------------------------------------------------------------------


def _task_log_text(c, token, trial_id):
    logs = c.api("GET", f"/api/v1/tasks/trial-{trial_id}/logs?offset=0",
                 token=token)["logs"]
    return "\n".join(line["log"] for line in logs)


@pytest.mark.slow
def test_elastic_shrink_grow_e2e(tmp_path, native_binaries):
    """Acceptance: an elastic trial on a draining 2-slot agent shrinks to
    the 1-slot survivor and resumes WITHOUT a requeue (same allocation,
    restarts unchanged, size history records 2->1), then grows back to 2
    when the drained agent is re-enabled."""
    c = Devcluster(str(tmp_path), native_binaries, slots=2)
    c.start_master()
    nf = os.path.join(str(tmp_path), "notice-big.json")
    # XLA_FLAGS cleared so exec/launch sizes the virtual CPU "chips" to the
    # granted slot count — the re-placed run really re-resolves its mesh.
    c.start_agent("big", slots=2, extra_env={
        "DET_AGENT_NOTICE_FILE": nf, "XLA_FLAGS": ""})
    c.start_agent("small", slots=1, extra_env={"XLA_FLAGS": ""})
    try:
        config = _elastic_config(
            tmp_path,
            extra={
                "entrypoint": "python3 elastic_train.py",
                "searcher": {"name": "single", "metric": "val_loss",
                             "max_length": {"batches": 600}},
                "max_restarts": 2,
                "environment": {"ELASTIC_STEP_SLEEP": "0.1"},
            })
        eid, token = _create_experiment(c, config)
        admin = c.login("admin")

        # Mid-run on the big agent.
        deadline = time.time() + 120
        aid = None
        while time.time() < deadline:
            try:
                aid, state = _trial_allocation(c, token, eid)
            except TimeoutError:
                continue
            if state == "SCHEDULED":
                trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                               token=token)["trials"]
                if trials and len(c.api(
                        "GET",
                        f"/api/v1/trials/{trials[0]['id']}/metrics"
                        "?group=training", token=token)["metrics"]) >= 5:
                    break
            time.sleep(0.5)
        alloc = _alloc(c, token, aid)
        assert alloc["slots"] == 2
        assert {r["agent_id"] for r in alloc["resources"]} == {"big"}
        trial_id = _trial(c, token, eid)["id"]

        # The notice: the big agent disappears in 45s.
        with open(nf, "w") as f:
            json.dump({"deadline_seconds": 45,
                       "reason": "spot_preemption"}, f)

        # Shrink: same allocation id lands on the survivor at 1 slot.
        deadline = time.time() + 60
        while time.time() < deadline:
            alloc = _alloc(c, token, aid)
            if alloc["slots"] == 1 and alloc["resources"] and \
                    alloc["resources"][0]["agent_id"] == "small":
                break
            time.sleep(0.5)
        assert alloc["slots"] == 1, f"never shrank: {alloc}"
        assert [r["agent_id"] for r in alloc["resources"]] == ["small"]
        hist = c.api("GET", f"/api/v1/allocations/{aid}/size_history",
                     token=token)["size_history"]
        assert [(h["from_slots"], h["to_slots"]) for h in hist] == [(2, 1)]
        assert _trial(c, token, eid).get("restarts", 0) == 0, (
            "elastic shrink must not consume a restart")

        # The harness took the resize path: budgeted emergency checkpoint,
        # then the re-placed run restored it.
        deadline = time.time() + 60
        text = ""
        while time.time() < deadline:
            text = _task_log_text(c, token, trial_id)
            if "resize preemption" in text and \
                    "restored from checkpoint" in text:
                break
            time.sleep(0.5)
        assert "resize preemption" in text, text[-2000:]
        assert "emergency checkpoint committed" in text, text[-2000:]
        assert "restored from checkpoint" in text, text[-2000:]

        # Capacity returns: the drained node dies (the agent exits once
        # idle+drained); its spot replacement boots with the same id and
        # registers FRESH, which clears the drain. The grow offer then
        # moves the trial back to 2 slots.
        os.unlink(nf)
        if c.agent.poll() is None:  # "big" was the first agent started
            c.agent.kill()
            c.agent.wait()
        c.start_agent("big", slots=2, extra_env={"XLA_FLAGS": ""})
        assert _agent(c, admin, "big")["state"] == "ENABLED"
        deadline = time.time() + 90
        while time.time() < deadline:
            alloc = _alloc(c, token, aid)
            hist = c.api("GET",
                         f"/api/v1/allocations/{aid}/size_history",
                         token=token)["size_history"]
            if len(hist) >= 2 and alloc["slots"] == 2:
                break
            time.sleep(1.0)
        assert alloc["slots"] == 2, f"never grew back: {alloc} {hist}"
        assert [(h["from_slots"], h["to_slots"]) for h in hist][:2] == \
            [(2, 1), (1, 2)]
        assert "scale-up" in hist[1]["reason"]
        assert _trial(c, token, eid).get("restarts", 0) == 0

        # And the trial still finishes.
        _wait_experiment(c, eid, token, timeout=300.0)
        t = _trial(c, token, eid)
        assert t["state"] == "COMPLETED"
        assert t.get("restarts", 0) == 0
    finally:
        c.stop()
