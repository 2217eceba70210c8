"""`det serve` — continuous batching, KV accounting, drain (docs/serving.md).

Fast tier-1 tests pin the batcher core contracts: admission/backpressure,
join-at-step-boundary + retire-without-drain ordering, KV block
reuse/free accounting, decode-vs-full-forward equivalence (the KV cache
produces bit-identical greedy generations), the ISSUE-6 acceptance burst
(>= 32 concurrent requests, batch occupancy > 1), drain semantics
(stop-admitting → finish in-flight, zero dropped), integrity-verified
checkpoint loading with lineage fallback, and the HTTP front-end's
status-code contract. The `-m slow` e2e drives a real devcluster:
submit → serve through the master proxy → spot-notice drain → replica
reschedule onto the survivor.
"""

import dataclasses
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu import core
from determined_tpu.common import faultpoint
from determined_tpu.models import gpt2
from determined_tpu.serve import (
    AdmissionQueue,
    BlockManager,
    ContinuousBatcher,
    Draining,
    KVBlockError,
    QueueFull,
    Request,
    ServingEngine,
    load_checkpoint_params,
)
from determined_tpu.serve.engine import _tree_bytes
from determined_tpu.serve.scheduler import FAULT_POINT_DROP

# Tiny f32 config: CPU-fast, and float32 keeps the cached-decode vs
# full-forward argmax comparison exact (bf16 rounding could flip ties).
TINY = gpt2.Config(
    vocab_size=128, n_positions=64, d_model=32, n_layer=2, n_head=2,
    dtype=jnp.float32, remat=False, attention_impl="dot",
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faultpoint.disarm_all()
    yield
    faultpoint.disarm_all()


# The decode kernel cuts a pool row into 128-lane groups, so its legs run
# at the smallest width that has one: two heads of 64.
KERNEL_TINY = dataclasses.replace(TINY, d_model=128)


@pytest.fixture(scope="module")
def tiny_params():
    return gpt2.init(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def kernel_tiny_params():
    return gpt2.init(jax.random.PRNGKey(0), KERNEL_TINY)


def make_engine(params, slots=4, max_seq=32, buckets=(8, 16, 32)):
    return ServingEngine(params, TINY, slots=slots, max_seq_len=max_seq,
                         prefill_buckets=list(buckets))


def reference_greedy(params, prompt, n, cfg=TINY):
    """Full-forward greedy generation — the ground truth the KV-cached
    path must reproduce exactly."""
    ctx = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        logits = gpt2.apply(params, jnp.asarray([ctx], jnp.int32), cfg)
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        ctx.append(tok)
    return out


def make_batcher(engine, queue_size=64, num_blocks=None, block_size=8):
    blocks = BlockManager(
        num_blocks=num_blocks if num_blocks is not None
        else engine.slots * (engine.max_seq_len // block_size),
        block_size=block_size)
    return ContinuousBatcher(
        engine, queue=AdmissionQueue(queue_size), block_manager=blocks,
        idle_wait_s=0.005)


# ---------------------------------------------------------------------------
# KV block manager: allocation, reuse/free accounting, failure modes.
# ---------------------------------------------------------------------------


def test_block_manager_allocate_free_roundtrip():
    bm = BlockManager(num_blocks=8, block_size=4)
    assert bm.blocks_for_tokens(1) == 1
    assert bm.blocks_for_tokens(4) == 1
    assert bm.blocks_for_tokens(5) == 2
    blocks = bm.allocate("a", 10)  # 3 blocks
    assert len(blocks) == 3 and bm.free_blocks == 5 and bm.used_blocks == 3
    assert bm.free("a") == 3
    assert bm.free_blocks == 8
    assert bm.stats()["total_allocated"] == 3
    assert bm.stats()["total_freed"] == 3


def test_block_manager_exhaustion_is_backpressure_not_error():
    bm = BlockManager(num_blocks=4, block_size=4)
    assert bm.allocate("a", 16) is not None  # all 4 blocks
    assert not bm.can_allocate(1)
    assert bm.allocate("b", 1) is None       # exhausted: None, no raise
    bm.free("a")
    assert bm.allocate("b", 1) is not None   # freed capacity admits it


def test_block_manager_reuse_accounting():
    bm = BlockManager(num_blocks=4, block_size=4)
    bm.allocate("a", 8)
    bm.free("a")
    bm.allocate("b", 8)  # reuses a's two blocks
    assert bm.stats()["total_reused"] == 2


def test_block_manager_extend():
    bm = BlockManager(num_blocks=3, block_size=4)
    bm.allocate("a", 4)
    assert bm.extend("a", 8) is True    # +1 block
    assert bm.extend("a", 8) is True    # already covered: no-op
    assert bm.extend("a", 100) is False  # pool can't cover
    assert bm.free("a") == 2


def test_block_manager_misuse_raises():
    bm = BlockManager(num_blocks=4, block_size=4)
    bm.allocate("a", 4)
    with pytest.raises(KVBlockError):
        bm.allocate("a", 4)  # double allocate
    bm.free("a")
    with pytest.raises(KVBlockError):
        bm.free("a")         # double free
    with pytest.raises(KVBlockError):
        bm.extend("ghost", 4)


# ---------------------------------------------------------------------------
# Admission queue: bounded backpressure, drain, chaos.
# ---------------------------------------------------------------------------


def _req(n_prompt=4, max_new=4, **kw):
    return Request(np.arange(1, 1 + n_prompt, dtype=np.int32),
                   max_new_tokens=max_new, **kw)


def test_queue_backpressure():
    q = AdmissionQueue(maxsize=2)
    q.submit(_req())
    q.submit(_req())
    with pytest.raises(QueueFull):
        q.submit(_req())
    assert q.rejected_full == 1 and q.depth() == 2
    q.pop()
    q.submit(_req())  # capacity freed → admits again


def test_queue_drain_stops_admissions():
    q = AdmissionQueue(maxsize=4)
    q.submit(_req())
    q.drain()
    with pytest.raises(Draining):
        q.submit(_req())
    assert q.rejected_draining == 1
    assert q.depth() == 1  # accepted work stays queued
    q.undrain()
    q.submit(_req())


def test_queue_fault_point_drop_and_error():
    q = AdmissionQueue(maxsize=4)
    faultpoint.arm(FAULT_POINT_DROP, "drop", count=1)
    with pytest.raises(QueueFull, match="shed"):
        q.submit(_req())
    assert q.dropped == 1
    faultpoint.arm(FAULT_POINT_DROP, "error", count=1)
    with pytest.raises(faultpoint.FaultInjected):
        q.submit(_req())
    q.submit(_req())  # disarmed again: admits


# ---------------------------------------------------------------------------
# Engine: KV-cached decode == full forward; buckets.
# ---------------------------------------------------------------------------


def test_cached_decode_matches_full_forward(tiny_params):
    eng = make_engine(tiny_params, slots=2)
    eng.compile()
    prompt = np.array([5, 9, 17, 3], np.int32)
    first = eng.prefill_request(0, prompt)
    out = [first]
    tokens = np.zeros(2, np.int32)
    positions = np.zeros(2, np.int32)
    temps = np.zeros(2, np.float32)
    pos, last = len(prompt), first
    for _ in range(7):
        tokens[0], positions[0] = last, pos
        last = int(eng.decode(tokens, positions, temps)[0])
        out.append(last)
        pos += 1
    assert out == reference_greedy(tiny_params, prompt, 8)


# Weights resident in the serving dtype (PERF.md PR 29): bfloat16 compute
# over the float32 tree of `tiny_params`.
TINY_BF16 = dataclasses.replace(TINY, dtype=jnp.bfloat16)

_NARROWED = ("qkv", "attn_out", "mlp_up", "mlp_down")


def _small_engine(params, cfg, attention_impl="auto"):
    return ServingEngine(params, cfg, slots=2, max_seq_len=16,
                         prefill_buckets=[8], kv_block_size=8,
                         attention_impl=attention_impl)


def test_engine_narrows_weights_to_the_serving_dtype_once(tiny_params):
    """A float32 checkpoint served in bfloat16 is cast when the engine is
    built, not in every call: each leaf the step functions read only
    through `.astype(cfg.dtype)` rests in bfloat16, the layer norms (which
    multiply in float32) keep the checkpoint's dtype, and the counters say
    what was saved."""
    eng = _small_engine(tiny_params, TINY_BF16)
    blocks = eng.params["blocks"]
    for name in _NARROWED:
        assert blocks[name]["kernel"].dtype == jnp.bfloat16, name
        assert blocks[name]["bias"].dtype == jnp.bfloat16, name
    assert eng.params["wte"].dtype == jnp.bfloat16
    assert eng.params["wpe"].dtype == jnp.bfloat16
    for ln in (blocks["ln1"], blocks["ln2"], eng.params["ln_f"]):
        assert ln["scale"].dtype == ln["bias"].dtype == jnp.float32
    stats = eng.stats()
    saved = _tree_bytes(tiny_params) - _tree_bytes(eng.params)
    narrowed = sum(_tree_bytes(tiny_params["blocks"][n]) for n in _NARROWED)
    narrowed += _tree_bytes([tiny_params["wte"], tiny_params["wpe"]])
    assert saved == narrowed // 2 > 0
    assert stats["weights_narrowed_bytes"] == saved
    assert stats["weights_hbm_bytes"] == _tree_bytes(eng.params)
    assert eng.compile_stats["weights_narrowed_bytes"] == saved
    # bf16(w) is the value every call cast to anyway.
    np.testing.assert_array_equal(
        np.asarray(eng.params["wte"]),
        np.asarray(tiny_params["wte"].astype(jnp.bfloat16)))
    # Nothing was donated: the tree handed in serves a second engine.
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(tiny_params))
    again = _small_engine(tiny_params, TINY_BF16)
    prompt = np.array([5, 9, 17, 3], np.int32)
    assert again.prefill_request(0, prompt) == eng.prefill_request(0, prompt)


@pytest.mark.parametrize("tree_dtype,cfg", [
    (jnp.bfloat16, TINY_BF16), (jnp.float32, TINY),
], ids=["bf16_tree", "f32_tree_f32_serving"])
def test_engine_leaves_a_tree_no_wider_than_the_serving_dtype(
        tiny_params, tree_dtype, cfg):
    tree = jax.tree_util.tree_map(lambda x: x.astype(tree_dtype), tiny_params)
    eng = _small_engine(tree, cfg)
    assert eng.stats()["weights_narrowed_bytes"] == 0
    assert eng.stats()["weights_hbm_bytes"] == _tree_bytes(tree)
    before, after = (jax.tree_util.tree_leaves_with_path(t)
                     for t in (tree, eng.params))
    assert [p for p, _ in before] == [p for p, _ in after]
    for (path, x), (_, y) in zip(before, after):
        assert x.dtype == y.dtype, path
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_resident_weights_serve_bit_for_bit_what_the_f32_tree_does(
        tiny_params):
    """The engine's calls on its narrowed tree give the very logits (and
    so the tokens) of the step functions called with the float32 tree,
    whose `.astype(cfg.dtype)` rounds the same values inside the call."""
    from determined_tpu.serve import model as smodel

    cfg = TINY_BF16
    eng = _small_engine(tiny_params, cfg, attention_impl="reference")
    eng.compile()
    prompt = np.array([5, 9, 17, 3], np.int32)
    padded = np.zeros((8,), np.int32)
    padded[:4] = prompt
    length = np.int32(4)
    table = jnp.asarray([0, 1], jnp.int32)
    tables = jnp.asarray([[0, 1], [2, 2]], jnp.int32)
    cache = smodel.init_paged_cache(cfg, eng.num_blocks + 1, 8)
    pf = jax.jit(lambda p, c, t: smodel.paged_prefill(
        p, c, t, length, np.int32(0), table, cfg))
    dec = jax.jit(lambda p, c, t, pos: smodel.paged_decode_step(
        p, c, t, pos, tables, cfg))

    cache, want = pf(tiny_params, cache, padded)
    eng._cache, tok, got = eng._compiled_prefill[8](
        eng.params, eng._cache, padded, length, np.int32(0), table,
        np.float32(0), eng._rng, np.int32(1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(tok) == int(np.argmax(np.asarray(want)))
    tokens = np.zeros((2,), np.int32)
    positions = np.zeros((2,), np.int32)
    greedy = [int(np.argmax(np.asarray(want)))]
    for step in range(4):
        tokens[0], positions[0] = greedy[-1], 4 + step
        cache, want = dec(tiny_params, cache, tokens, positions)
        eng._cache, got = eng._compiled_decode(
            eng.params, eng._cache, tokens, positions, tables)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        greedy.append(int(np.argmax(np.asarray(want)[0])))

    # And through the engine's own entry points: the generated tokens.
    served = [eng.prefill_request(0, prompt)]
    for step in range(4):
        tokens[0], positions[0] = served[-1], 4 + step
        served.append(int(eng.decode(
            tokens, positions, np.zeros((2,), np.float32))[0]))
    assert served == greedy


def check_first_tokens(engine, calls, reference, seed=0):
    """The first token's contract, for any family (also
    tests/test_falcon_h1.py): the prefill call samples its own. Each of
    `calls` (keywords of `prefill_request`) runs greedy and then at a
    temperature; `reference[i]` is call i's logits by a plain forward.
    Greedy returns their argmax; a temperature returns
    `jax.random.categorical` of the call's logits / T under the engine's
    key folded with that call's step; four bytes a prefill cross to the
    host, and the engine keeps no `[slots, vocab]` rows there."""
    kept = []
    enqueue = engine._enqueue_prefill

    def keep_logits(*args):
        first, logits = enqueue(*args)
        kept.append(np.asarray(logits))
        return first, logits

    engine._enqueue_prefill = keep_logits
    bytes_before = engine.stats()["first_token_host_bytes"]
    sampled, greedy = [], []
    for temperature in (0.0, 0.7):
        for call, want in zip(calls, reference):
            step = engine._step_counter + 1
            token = engine.prefill_request(temperature=temperature, **call)
            np.testing.assert_allclose(kept[-1], want, atol=2e-4)
            if not temperature:
                assert token == int(np.argmax(want))
                greedy.append(token)
                continue
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            assert token == int(jax.random.categorical(
                key, kept[-1][None] / np.float32(temperature), axis=-1)[0])
            sampled.append(token)
    assert sampled != greedy             # the temperature was an operand
    assert (engine.stats()["first_token_host_bytes"] - bytes_before
            == 4 * len(kept) == 8 * len(calls))
    rows = engine.slots * engine.cfg.vocab_size
    assert not [leaf for leaf in jax.tree_util.tree_leaves(vars(engine))
                if isinstance(leaf, np.ndarray) and leaf.size >= rows]


@pytest.mark.parametrize("arm", ["base", "adapter_and_cached_prefix"])
def test_first_token_is_sampled_in_the_prefill_call(tiny_params,
                                                    finetuned_params, arm):
    prompts = [np.array([5, 9, 17, 3], np.int32),
               np.arange(1, 21, dtype=np.int32),
               np.array([7] * 9, np.int32)]
    if arm == "base":
        eng = make_engine(tiny_params, slots=4)
        calls = [dict(slot=i, tokens=p) for i, p in enumerate(prompts)]
        trees = [tiny_params] * 3
    else:
        # Lane 0 fills two blocks with the fine-tune's keys; lane 1 takes
        # them as its cached prefix and prefills its four novel tokens.
        eng = ServingEngine(tiny_params, TINY, slots=4, max_seq_len=32,
                            prefill_buckets=[8, 16, 32], kv_block_size=8,
                            adapters={"ft": finetuned_params}, seed=3)
        shared = np.arange(1, 21, dtype=np.int32)
        eng.prefill_request(0, shared, block_table=[0, 1, 2, 3], adapter=1)
        prompts[1] = np.concatenate([shared[:16], [40, 41, 42, 43]]
                                    ).astype(np.int32)
        calls = [dict(slot=1, tokens=prompts[1], block_table=[0, 1, 4, 5],
                      cached_len=16, adapter=1),
                 dict(slot=2, tokens=prompts[0], adapter=1,
                      block_table=[6, 7, 8, 9]),
                 dict(slot=3, tokens=prompts[2], adapter=0,
                      block_table=[10, 11, 12, 13])]
        prompts = [prompts[1], prompts[0], prompts[2]]
        trees = [finetuned_params, finetuned_params, tiny_params]
    reference = [
        np.asarray(gpt2.apply(tree, jnp.asarray(p)[None], TINY))[0, -1]
        for tree, p in zip(trees, prompts)]
    check_first_tokens(eng, calls, reference,
                       seed=0 if arm == "base" else 3)


def test_engine_warm_aot_deserializes_on_second_boot(tiny_params, tmp_path):
    """The scale-to-zero cold-start contract (docs/serving.md "Scale to
    zero"): the FIRST engine for a serving signature traces and saves its
    executables into the node-local AOT dir; the SECOND engine with the
    same signature deserializes every piece (aot_source "deserialize",
    never a re-trace) and generates identically."""
    from determined_tpu.compile.runtime import FarmClient

    sig = "serve-warmaot-test"
    aot_dir = str(tmp_path / "aot")

    import os as _os

    def boot():
        """One replica boot: engine + farm + batcher (the batcher syncs
        block geometry, then compiles through the farm — exactly the
        serve task's startup order)."""
        eng = make_engine(tiny_params, slots=2, max_seq=16,
                          buckets=(8, 16))
        eng.farm = FarmClient(session=None, signature=sig,
                              aot_dir=aot_dir)
        b = make_batcher(eng, block_size=8)
        b.start()
        return eng, b

    cold, b1 = boot()
    try:
        assert cold.aot_source == "trace"
        assert cold.compile_stats["aot_misses"] > 0
        # Artifacts landed locally (decode, prefill buckets, sampler,
        # CoW block copy).
        saved = _os.listdir(_os.path.join(aot_dir, sig))
        assert any(n.startswith("aot-decode") for n in saved), saved

        req = b1.submit(Request(np.asarray([5, 9, 17], np.int32),
                                max_new_tokens=4))
        req.result(timeout=60)
        want = reference_greedy(tiny_params, [5, 9, 17], 4)
        assert list(req.out_tokens) == want
    finally:
        b1.stop()

    warm, b2 = boot()
    try:
        assert warm.aot_source == "deserialize", warm.compile_stats
        assert warm.compile_stats["aot_misses"] == 0
        assert warm.compile_stats["decode_source"] == "deserialize"
        # Warm executables behave identically.
        req = b2.submit(Request(np.asarray([5, 9, 17], np.int32),
                                max_new_tokens=4))
        req.result(timeout=60)
        assert list(req.out_tokens) == want
    finally:
        b2.stop()


def test_serving_signature_stable_and_shape_sensitive():
    """Same serving config -> same signature (replicas share artifacts);
    any shape-affecting knob change -> a different signature (a respawn
    can never load a stale executable)."""
    from determined_tpu.serve.task import serving_signature

    base = {"model": "gpt2", "model_config": {"model_size": "tiny"},
            "max_batch_size": 4, "max_seq_len": 64, "kv_block_size": 16}
    assert serving_signature(dict(base)) == serving_signature(dict(base))
    changed = dict(base, max_seq_len=128)
    assert serving_signature(changed) != serving_signature(base)
    # Non-shape knobs (ports, sampling) don't fragment the cache.
    assert serving_signature(dict(base, port=9999)) == \
        serving_signature(base)


def test_serving_signature_keys_the_resident_dtypes(tiny_params):
    """The executables take the resident tree's leaves as arguments: an
    artifact compiled for float32-resident weights must not be loaded by
    an engine that narrowed them, so the two address different stores."""
    from determined_tpu.serve.task import serving_signature

    serving = {"model": "gpt2", "model_config": {"model_size": "tiny"},
               "max_batch_size": 2, "max_seq_len": 16, "kv_block_size": 8}
    f32 = _small_engine(tiny_params, TINY)
    bf16 = _small_engine(tiny_params, TINY_BF16)
    assert f32.params["wte"].dtype != bf16.params["wte"].dtype
    sigs = [serving_signature(serving, e.params) for e in (f32, bf16)]
    assert sigs[0] != sigs[1]
    assert sigs[1] == serving_signature(
        serving, _small_engine(tiny_params, TINY_BF16).params)
    assert serving_signature(serving) not in sigs


def test_bucket_selection(tiny_params):
    eng = make_engine(tiny_params, buckets=(8, 16, 32))
    assert eng.bucket_for(1) == 8
    assert eng.bucket_for(8) == 8
    assert eng.bucket_for(9) == 16
    assert eng.bucket_for(32) == 32
    assert eng.bucket_for(33) is None


def test_engine_compiles_every_bucket_aot(tiny_params):
    eng = make_engine(tiny_params, buckets=(8, 16))
    stats = eng.compile()
    assert set(eng._compiled_prefill) == {8, 16}
    assert stats["decode_s"] > 0 and "total_s" in stats


# ---------------------------------------------------------------------------
# Continuous batcher: the ISSUE-6 acceptance contracts.
# ---------------------------------------------------------------------------


def test_burst_completes_with_occupancy_above_one(tiny_params):
    """Acceptance: a burst of >= 32 concurrent requests completes with
    batch occupancy > 1, and every result is the exact full-forward
    greedy generation (continuous batching changes scheduling, never
    content)."""
    eng = make_engine(tiny_params, slots=4)
    b = make_batcher(eng).start()
    try:
        rng = np.random.default_rng(0)
        reqs = [
            b.submit(Request(
                rng.integers(1, 100, size=int(rng.integers(2, 7))),
                max_new_tokens=int(rng.integers(3, 10))))
            for _ in range(32)
        ]
        results = [r.result(timeout=120) for r in reqs]
        stats = b.stats()
        assert stats["completed"] == 32
        assert stats["mean_occupancy"] > 1.0, stats
        assert stats["max_occupancy"] > 1
        # Spot-check content against the reference (first + last).
        for req, res in [(reqs[0], results[0]), (reqs[-1], results[-1])]:
            assert res["tokens"] == reference_greedy(
                tiny_params, req.tokens, req.max_new_tokens)
    finally:
        b.stop()


def test_join_at_boundary_retire_without_drain(tiny_params):
    """With 2 slots and 3 requests, the 3rd joins at the step boundary
    where the 1st retires, while the 2nd keeps decoding — the batch
    NEVER drains to refill."""
    from determined_tpu.common import trace

    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng).start()
    try:
        r1 = b.submit(_req(n_prompt=3, max_new=2))
        r2 = b.submit(_req(n_prompt=3, max_new=12))
        r3 = b.submit(_req(n_prompt=3, max_new=2))
        for r in (r1, r2, r3):
            r.result(timeout=60)
    finally:
        b.stop()   # joins the batcher thread: its last phase has closed
    # The step count at which each request joined and left, from the
    # batcher's phase records (their iteration is that count).
    kinds = {"serve.loop.admit": "admit", "serve.step.retire": "retire"}
    ev = {(kinds[rec["name"]], rid): rec["iteration"]
          for rec in trace.phase_log(since=r1.submitted_at)
          if rec["name"] in kinds for rid in rec["counts"]["ids"]}
    # r1 and r2 joined before r3 (only 2 slots).
    assert ev[("admit", r3.id)] >= ev[("retire", r1.id)]
    # retire-without-drain: r2 was still mid-decode when r3 joined —
    # its retirement happened strictly after r3's admission.
    assert ev[("retire", r2.id)] > ev[("admit", r3.id)]


def test_kv_blocks_gate_admission(tiny_params):
    """Block exhaustion keeps requests queued (occupancy 1) until a
    retire frees capacity — backpressure, not failure."""
    eng = make_engine(tiny_params, slots=4)
    # Pool covers exactly one worst-case sequence at a time.
    b = make_batcher(eng, num_blocks=1, block_size=16)
    b.start()
    try:
        reqs = [b.submit(_req(n_prompt=4, max_new=6)) for _ in range(3)]
        for r in reqs:
            r.result(timeout=60)
        stats = b.stats()
        assert stats["completed"] == 3
        assert stats["max_occupancy"] == 1, (
            "block pool for one sequence must serialize the batch")
    finally:
        b.stop()


def test_kv_accounting_balances_after_load(tiny_params):
    eng = make_engine(tiny_params, slots=4)
    b = make_batcher(eng).start()
    try:
        reqs = [b.submit(_req(n_prompt=5, max_new=5)) for _ in range(12)]
        for r in reqs:
            r.result(timeout=60)
        kv = b.stats()["kv_blocks"]
        assert kv["used_blocks"] == 0
        assert kv["free_blocks"] == kv["num_blocks"]
        assert kv["total_freed"] == kv["total_allocated"] > 0
        assert kv["total_reused"] > 0  # retired blocks cycled back in
    finally:
        b.stop()


def test_drain_finishes_accepted_work_zero_dropped(tiny_params):
    """Drain contract: stop admitting (Draining), but every accepted
    request — queued or in-flight — completes successfully."""
    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng).start()
    try:
        reqs = [b.submit(_req(n_prompt=4, max_new=10)) for _ in range(6)]
        assert b.drain(timeout=None) in (True, False)  # signal only
        with pytest.raises(Draining):
            b.submit(_req())
        assert b.drain(timeout=60) is True
        results = [r.result(timeout=5) for r in reqs]  # none dropped
        assert all(len(res["tokens"]) == 10 for res in results)
        stats = b.stats()
        assert stats["completed"] == 6 and stats["failed"] == 0
        assert stats["rejected_draining"] == 1
    finally:
        b.stop()


def test_submit_validates_against_engine_limits(tiny_params):
    eng = make_engine(tiny_params, slots=2, max_seq=32, buckets=(8, 16))
    b = make_batcher(eng)
    with pytest.raises(ValueError, match="prefill bucket"):
        b.submit(_req(n_prompt=20))  # no bucket covers 20
    with pytest.raises(ValueError, match="max_seq_len"):
        b.submit(_req(n_prompt=16, max_new=20))  # 36 > 32 budget


def test_batcher_stop_fails_outstanding(tiny_params):
    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng).start()
    r = b.submit(_req(n_prompt=4, max_new=28))
    b.stop()
    with pytest.raises((RuntimeError, TimeoutError)):
        r.result(timeout=5)


# ---------------------------------------------------------------------------
# Paged KV: attention-impl equivalence (ISSUE-11 acceptance) — greedy decode
# through the paged path (Pallas kernel in interpret mode AND the jnp
# reference gather) must match full-forward gpt2.apply exactly, in f32.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_attention_impl_greedy_equivalence(tiny_params, kernel_tiny_params,
                                           impl):
    from jax.experimental.pallas import tpu as pltpu

    cfg, params = ((KERNEL_TINY, kernel_tiny_params) if impl == "pallas"
                   else (TINY, tiny_params))
    eng = ServingEngine(params, cfg, slots=2, max_seq_len=32,
                        prefill_buckets=[8, 16, 32], attention_impl=impl)
    # The kernel leg compiles on the CPU only because this test asks for
    # the interpreter; the engine itself never does.
    with pltpu.force_tpu_interpret_mode():
        eng.compile()
    prompt = np.array([5, 9, 17, 3], np.int32)
    first = eng.prefill_request(0, prompt)
    out = [first]
    tokens = np.zeros(2, np.int32)
    positions = np.zeros(2, np.int32)
    temps = np.zeros(2, np.float32)
    pos, last = len(prompt), first
    for _ in range(7):
        tokens[0], positions[0] = last, pos
        last = int(eng.decode(tokens, positions, temps)[0])
        out.append(last)
        pos += 1
    assert out == reference_greedy(params, prompt, 8, cfg)


def test_resolve_attention_impl_refuses_the_deleted_dense_layout():
    """The slot-dense cache is gone: its spelling is refused like any
    unknown one, with the three paths that exist named."""
    from determined_tpu.serve.engine import resolve_attention_impl

    with pytest.raises(ValueError, match="auto, pallas, reference"):
        resolve_attention_impl("dense", TINY)


def test_paged_reference_logits_match_full_forward(tiny_params):
    """The jnp gather path computes what the training forward does: the
    paged prefill's logits and the first decode step's, in float32, are
    `gpt2.apply`'s at the same positions. They differ only in how the sums
    are ordered (a softmax over a whole masked lane of 32 against a causal
    one of five): read on the CPU (PR 30) the largest difference was
    5.96e-8 at the prefill and 4.47e-8 at the decode step, on logits of
    magnitude up to 0.6 — a few float32 ulps — and is held here to 1e-6."""
    from determined_tpu.serve import model as smodel

    prompt = np.array([5, 9, 17, 3], np.int32)
    # bs=8 -> 4 blocks tile max_seq 32 exactly; the fifth is the trash.
    pcache = smodel.init_paged_cache(TINY, 5, 8)
    table = jnp.asarray([0, 1, 2, 3], jnp.int32)
    pcache, plog = smodel.paged_prefill(
        tiny_params, pcache, jnp.asarray(prompt), jnp.int32(4),
        jnp.int32(0), table, TINY)
    tok = jnp.argmax(plog).astype(jnp.int32)
    pcache, pstep = smodel.paged_decode_step(
        tiny_params, pcache, tok[None], jnp.asarray([4], jnp.int32),
        table[None], TINY, attention_impl="reference")
    ctx = jnp.asarray([list(prompt) + [int(tok)]], jnp.int32)
    full = gpt2.apply(tiny_params, ctx, TINY)[0].astype(jnp.float32)
    assert plog.dtype == pstep.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(plog), np.asarray(full[3]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pstep[0]), np.asarray(full[4]),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("nh,dh", [(2, 64), (8, 32), (2, 128), (20, 64)],
                         ids=["2x64", "8x32", "2x128", "served-20x64"])
def test_paged_attention_pallas_matches_reference(nh, dh):
    """Unit-level: the Pallas kernel (interpret mode on CPU) and the jnp
    gather agree numerically on a random paged pool of several layers,
    including partially filled blocks and an inactive (trash-table) slot,
    whether a 128-lane group holds four heads, two or one."""
    import jax.numpy as jnp

    from determined_tpu.ops.paged_attention import (
        paged_attention_pallas, paged_attention_reference)

    rng = np.random.default_rng(7)
    layers, slots, mb, bs = 3, 3, 4, 8
    pool = (layers, slots * mb + 1, bs, nh * dh)
    q = jnp.asarray(rng.normal(size=(slots, nh, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    tbl = np.arange(slots * mb).reshape(slots, mb).astype(np.int32)
    tbl[2] = slots * mb  # inactive slot: all-trash table
    tbl = jnp.asarray(tbl)
    pos = jnp.asarray([5, 17, 0], jnp.int32)
    for layer in (0, 2):
        ref = paged_attention_reference(q, kp, vp, jnp.int32(layer), tbl, pos)
        out = paged_attention_pallas(q, kp, vp, jnp.int32(layer), tbl, pos,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out[:2]), np.asarray(ref[:2]),
                                   atol=1e-5)
    # Another layer's K/V give another answer: the layer index is read.
    assert not np.allclose(
        np.asarray(paged_attention_reference(q, kp, vp, jnp.int32(1), tbl,
                                             pos)[:2]), np.asarray(ref[:2]))


def _span_case(name):
    """(tables, positions, idle lanes) of one span-edge case: 2 heads of
    64, blocks of 8 tokens, so a span is 16 blocks = 128 tokens; 40 table
    entries a lane (2.5 spans: the table is padded to 48), 5 lanes."""
    slots, mb, bs = 5, 40, 8
    trash = slots * mb
    tbl = np.random.default_rng(11).permutation(trash).reshape(
        slots, mb).astype(np.int32)
    pos = np.array([0, 127, 128, 129, mb * bs - 1], np.int32)
    idle = []
    if name == "idle-between-live":
        idle = [1, 3]
    elif name == "idle-first-and-last":
        idle = [0, 4]
    elif name == "foreign-blocks-past-position":
        # Dead entries hold ANOTHER lane's live blocks, not the trash.
        for lane in range(4):
            dead = pos[lane] // bs + 1
            tbl[lane, dead:] = tbl[4, dead:]
    elif name == "trash-past-position":
        for lane in range(4):
            tbl[lane, pos[lane] // bs + 1:] = trash
    elif name == "one-lane-one-token":
        idle = [1, 2, 3, 4]
    else:
        assert name == "span-edges"
    for lane in idle:
        tbl[lane], pos[lane] = trash, 0
    return tbl, pos, idle


@pytest.mark.parametrize("name", [
    "span-edges", "idle-between-live", "idle-first-and-last",
    "foreign-blocks-past-position", "trash-past-position",
    "one-lane-one-token"])
def test_paged_attention_pallas_span_edges(name):
    """The kernel walks a lane's table a 128-token span at a time, up to
    the lane's position: positions on both sides of a span's edge and at
    the table's end, a table that is no whole number of spans, lanes of
    different lengths in one call, idle lanes between, before and after
    live ones (their rows are zeros, their neighbours' rows the
    reference's), dead table entries that name another lane's live blocks
    (nothing past a position reaches a result), a layer that is not the
    pool's first.
    Run by the TPU interpreter, whose uninitialised memory is NaN: a block
    that was not fetched must not leak into a sum either."""
    from jax.experimental.pallas import tpu as pltpu

    from determined_tpu.ops.paged_attention import (
        paged_attention_pallas, paged_attention_reference, span_tokens)

    nh, dh, bs = 2, 64, 8
    tbl, pos, idle = _span_case(name)
    slots, mb = tbl.shape
    assert span_tokens(bs, mb) == 128 and mb * bs % 128
    rng = np.random.default_rng(7)
    pool = (3, slots * mb + 1, bs, nh * dh)
    q = jnp.asarray(rng.normal(size=(slots, nh, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    args = (jnp.int32(1), jnp.asarray(tbl), jnp.asarray(pos))
    ref = np.asarray(paged_attention_reference(q, kp, vp, *args))
    out = np.asarray(paged_attention_pallas(
        q, kp, vp, *args, interpret=pltpu.InterpretParams()))
    live = [lane for lane in range(slots) if lane not in idle]
    np.testing.assert_allclose(out[live], ref[live], atol=1e-5)
    assert not out[idle].any()


def test_engine_counts_the_decode_kernels_spans(tiny_params):
    """`decode_spans_live` is the sum over decode calls and live lanes of
    ceil((position + 1) / span): a lane crossing a span's edge counts two
    from then on, an idle slot nothing; the kernel's loop ends at the
    position, so what is launched is what is live."""
    cfg = dataclasses.replace(TINY, n_positions=512)
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, slots=3, max_seq_len=512,
                        prefill_buckets=[8, 128], kv_block_size=16)
    rng = np.random.default_rng(0)
    position = {0: 126, 2: 5}      # slot 1 stays idle
    last = {slot: eng.prefill_request(
        slot, rng.integers(0, cfg.vocab_size, n).astype(np.int32))
        for slot, n in position.items()}
    assert eng.stats()["decode_spans_live"] == 0
    want = 0
    for _ in range(4):             # slot 0 writes at 126, 127, 128, 129
        tokens, positions = np.zeros(3, np.int32), np.zeros(3, np.int32)
        for slot in position:
            tokens[slot], positions[slot] = last[slot], position[slot]
            want += -(-(position[slot] + 1) // 128)
        out = eng.decode(tokens, positions, np.zeros(3, np.float32))
        for slot in position:
            last[slot], position[slot] = int(out[slot]), position[slot] + 1
    stats = eng.stats()
    assert want == 1 + 1 + 2 + 2 + 4
    assert stats["decode_spans_live"] == want
    assert stats["decode_spans_live"] <= stats["decode_spans_grid"]
    eng.release_slot(0)
    eng.decode(np.zeros(3, np.int32), np.array([0, 0, 9], np.int32),
               np.zeros(3, np.float32))
    assert eng.stats()["decode_spans_live"] == want + 1


def test_kernel_geometry_auto_falls_back_and_explicit_pallas_raises(
        tiny_params, monkeypatch):
    """TINY's pool row is 2 x 16 = 32 lanes, which no 128-lane slice
    tiles: `auto` serves it through the reference even on a TPU, and an
    explicit `pallas` says why it cannot."""
    from determined_tpu.ops.paged_attention import kernel_refusal
    from determined_tpu.parallel import mesh
    from determined_tpu.serve.engine import resolve_attention_impl

    assert kernel_refusal(20, 20, 64) is None
    assert kernel_refusal(2, 2, 128) is None
    assert kernel_refusal(20, 4, 128) is None   # query heads share K/V heads
    assert "multiple of 128" in kernel_refusal(
        TINY.n_head, TINY.n_head, TINY.head_dim)
    assert "head dim 96" in kernel_refusal(4, 4, 96)
    assert "whole 128-lane groups" in kernel_refusal(8, 2, 64)
    assert "do not divide" in kernel_refusal(20, 3, 128)
    monkeypatch.setattr(mesh, "on_tpu", lambda *a, **k: True)
    assert resolve_attention_impl("auto", KERNEL_TINY) == "pallas"
    assert resolve_attention_impl("auto", TINY) == "reference"
    with pytest.raises(ValueError, match="32 lanes"):
        ServingEngine(tiny_params, TINY, slots=2, max_seq_len=32,
                      prefill_buckets=[8], attention_impl="pallas")
    monkeypatch.setattr(mesh, "on_tpu", lambda *a, **k: False)
    assert resolve_attention_impl("auto", KERNEL_TINY) == "reference"


def test_paged_decode_across_admissions_matches_full_forward(tiny_params):
    """The suite's oracle over the batcher's whole life: greedy requests
    admitted and retired in waves through two slots give, from the
    carried, in-place pool, the tokens the full forward generates."""
    prompts = [np.arange(1, 1 + n, dtype=np.int32) * 3 % 120 + 1
               for n in (3, 9, 5, 12, 4, 7)]
    lengths = [6, 3, 8, 4, 5, 2]
    eng = ServingEngine(tiny_params, TINY, slots=2, max_seq_len=32,
                        prefill_buckets=[8, 16], attention_impl="reference",
                        kv_block_size=8)
    b = make_batcher(eng).start()
    try:
        reqs = [b.submit(Request(p, max_new_tokens=n))
                for p, n in zip(prompts, lengths)]
        served = [r.result(timeout=60)["tokens"] for r in reqs]
    finally:
        b.stop()
    assert served == [reference_greedy(tiny_params, p, n)
                      for p, n in zip(prompts, lengths)]


# ---------------------------------------------------------------------------
# BlockManager sharing semantics: refcounts, prefix reuse, CoW, eviction.
# ---------------------------------------------------------------------------


def test_prefix_blocks_shared_and_survive_one_sharer(tiny_params):
    bm = BlockManager(num_blocks=16, block_size=4)
    prompt = list(range(1, 9))  # 2 full blocks
    ta, ca, cowa = bm.admit("a", prompt, 12)
    assert ca == 0 and cowa == [] and len(ta) == 3
    tb, cb, cowb = bm.admit("b", prompt + [99], 12)  # same 8-token prefix
    assert cb == 8  # both full blocks reused
    assert tb[:2] == ta[:2] and cowb == []
    assert bm.ref_count(ta[0]) == 2
    # a retires: the shared blocks survive for b.
    bm.free("a")
    assert bm.ref_count(ta[0]) == 1
    # b retires: prompt blocks park in the prefix cache, still reusable.
    bm.free("b")
    assert bm.ref_count(ta[0]) == 0
    assert bm.cached_blocks >= 2
    tc, cc, cowc = bm.admit("c", prompt + [7], 12)
    assert cc == 8 and tc[:2] == ta[:2]
    bm.free("c")


def test_full_prompt_hit_copies_on_write_while_shared(tiny_params):
    bm = BlockManager(num_blocks=16, block_size=4)
    prompt = list(range(1, 9))  # exactly 2 full blocks
    ta, _, _ = bm.admit("a", prompt, 10)
    # b's prompt IS the cached prefix: the last token must be recomputed,
    # which writes into a's still-referenced final block -> private copy.
    tb, cb, cowb = bm.admit("b", prompt, 10)
    assert cb == 7  # len(prompt) - 1: one novel query for the logits
    assert cowb == [(ta[1], tb[1])]
    assert tb[0] == ta[0] and tb[1] != ta[1]
    assert bm.ref_count(ta[0]) == 2 and bm.ref_count(ta[1]) == 1
    bm.free("a")
    bm.free("b")
    # With no live sharer the parked copy is exclusively pinned: no CoW.
    tc, cc, cowc = bm.admit("c", prompt, 10)
    assert cc == 7 and cowc == []
    bm.free("c")
    assert bm.stats()["cow_copies"] == 1


def test_block_accounting_exact_under_interleaved_admit_retire():
    bm = BlockManager(num_blocks=12, block_size=4)
    prompt = list(range(1, 9))  # 2 full blocks

    def invariant():
        s = bm.stats()
        assert s["free_blocks"] + s["used_blocks"] == s["num_blocks"]
        return s

    ta, _, _ = bm.admit("a", prompt, 16)           # 4 blocks, 0 shared
    assert invariant()["used_blocks"] == 4
    tb, cb, _ = bm.admit("b", prompt + [9], 16)    # shares 2, charges 2
    assert cb == 8
    assert invariant()["used_blocks"] == 6     # 4 + 2 novel
    bm.free("a")
    # b still references the 2 shared blocks; only a's 2 private freed.
    assert invariant()["used_blocks"] == 4
    tc, cc, _ = bm.admit("c", [1, 2, 3], 4)    # 1 block, no full-block hit
    assert cc == 0
    assert invariant()["used_blocks"] == 5
    bm.free("b")
    bm.free("c")
    s = invariant()
    assert s["used_blocks"] == 0
    assert s["free_blocks"] == s["num_blocks"]
    assert s["total_freed"] == s["total_allocated"] > 0


def test_prefix_cache_eviction_under_pressure():
    bm = BlockManager(num_blocks=4, block_size=4)
    bm.admit("a", list(range(1, 9)), 8)   # 2 hashed blocks
    bm.free("a")                          # -> cached (evictable)
    assert bm.cached_blocks == 2
    # A non-matching allocation needs the space: cached LRU is evicted.
    tb = bm.allocate("b", 16)             # all 4 blocks
    assert tb is not None and bm.cached_blocks == 0
    assert bm.stats()["cached_evictions"] == 2
    bm.free("b")
    # The evicted prefix no longer matches.
    _, cached_len, _ = bm.admit("c", list(range(1, 9)), 8)
    assert cached_len == 0


def test_admit_misuse_raises():
    bm = BlockManager(num_blocks=8, block_size=4)
    bm.admit("a", [1, 2, 3], 4)
    with pytest.raises(KVBlockError):
        bm.admit("a", [1, 2, 3], 4)       # double admit
    with pytest.raises(KVBlockError):
        bm.admit("x", [], 4)              # empty prompt
    with pytest.raises(KVBlockError):
        bm.admit("y", [1, 2, 3], 2)       # budget below prompt
    bm.free("a")
    with pytest.raises(KVBlockError):
        bm.free("a")                      # double free


def test_prefix_cache_disabled_never_shares():
    bm = BlockManager(num_blocks=8, block_size=4, prefix_cache=False)
    ta, ca, _ = bm.admit("a", list(range(1, 9)), 8)
    tb, cb, _ = bm.admit("b", list(range(1, 9)), 8)
    assert ca == cb == 0
    assert not set(ta) & set(tb)
    bm.free("a")
    bm.free("b")
    assert bm.cached_blocks == 0


# ---------------------------------------------------------------------------
# Engine-level prefix caching: shared prompts admit at suffix-only cost
# and still generate exactly the reference tokens.
# ---------------------------------------------------------------------------


def test_shared_prefix_admits_at_suffix_cost(tiny_params):
    """Two requests sharing a 75% prefix: after the first, the second is
    charged only its novel suffix's prompt blocks (~25%) — and both
    generate exactly the full-forward reference tokens."""
    eng = make_engine(tiny_params, slots=4, max_seq=32, buckets=(8, 16, 32))
    b = make_batcher(eng, block_size=8)  # 32/8 = 4 blocks per sequence
    b.start()
    try:
        shared = list(np.arange(1, 25))          # 24 tokens = 3 full blocks
        p1 = np.asarray(shared + [30, 31], np.int32)      # 26-token prompt
        p2 = np.asarray(shared + [40, 41], np.int32)      # same 24 prefix
        r1 = b.submit(Request(p1, max_new_tokens=4))
        r1.result(timeout=60)
        alloc_after_r1 = b.blocks.total_allocated
        r2 = b.submit(Request(p2, max_new_tokens=4))
        r2.result(timeout=60)
        charged = b.blocks.total_allocated - alloc_after_r1
        # r2's budget is 30 tokens = 4 blocks; 3 were served from cache.
        assert charged == 1, b.blocks.stats()
        kv = b.blocks.stats()
        assert kv["prefix_hit_tokens"] == 24
        assert kv["prefix_hits"] == 1
        assert 0 < kv["prefix_cache_hit_rate"] < 1
        # Prefix reuse changes cost, never content.
        assert r1.out_tokens == reference_greedy(tiny_params, p1, 4)
        assert r2.out_tokens == reference_greedy(tiny_params, p2, 4)
    finally:
        b.stop()


def test_identical_prompt_full_hit_still_exact(tiny_params):
    """A 100% prompt hit (the CoW path end to end, device copy included)
    still produces the exact reference generation."""
    eng = make_engine(tiny_params, slots=2, max_seq=32, buckets=(8, 16, 32))
    b = make_batcher(eng, block_size=8)
    try:
        prompt = np.asarray(np.arange(1, 17), np.int32)  # 2 full blocks
        # Submit BOTH before starting: they admit at the same boundary,
        # so r2's full-prompt hit lands while r1 still references its
        # final block — the deterministic CoW case.
        r1 = b.submit(Request(prompt, max_new_tokens=5))
        r2 = b.submit(Request(prompt, max_new_tokens=5))
        b.start()
        r1.result(timeout=60)
        r2.result(timeout=60)
        ref = reference_greedy(tiny_params, prompt, 5)
        assert r1.out_tokens == ref and r2.out_tokens == ref
        assert b.blocks.stats()["cow_copies"] == 1
        assert eng.block_copies == 1
    finally:
        b.stop()


def test_heartbeat_and_stats_carry_paging_fields(tiny_params):
    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng)
    hb = b.heartbeat_stats()
    for key in ("kv_blocks_used", "kv_blocks_free", "kv_blocks_total",
                "prefix_cache_hit_rate"):
        assert key in hb, hb
    from determined_tpu.serve.http import prometheus_exposition

    text = prometheus_exposition(b.stats())
    assert "det_serve_kv_blocks_used" in text
    assert "det_serve_prefix_cache_hit_rate" in text
    est = eng.stats()
    assert est["kv_layout"] == "paged"
    assert est["cache_hbm_bytes"] > 0


# ---------------------------------------------------------------------------
# Checkpoint loading: COMPLETED-verified, lineage fallback.
# ---------------------------------------------------------------------------


def _save_checkpoint(tmp_path, params, steps, extra_poison=None):
    ctx = core.init(max_length=steps,
                    checkpoint_dir=str(tmp_path / "ckpts"))
    state = {"step": jnp.asarray(steps, jnp.int32), "params": params,
             "opt_state": {"count": jnp.zeros((), jnp.int32)}}
    sid = ctx.checkpoint.save_state(state, steps)
    ctx.checkpoint.wait()
    ctx.close()
    return ctx, sid


def test_load_checkpoint_params_roundtrip(tmp_path, tiny_params):
    ctx, sid = _save_checkpoint(tmp_path, tiny_params, 2)
    loaded = load_checkpoint_params(ctx.checkpoint, sid)
    flat_a = jax.tree_util.tree_leaves(tiny_params)
    flat_b = jax.tree_util.tree_leaves(loaded)
    assert len(flat_a) == len(flat_b)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(flat_a, flat_b))


def test_checkpoint_trained_on_a_mesh_serves_on_one_device(
        tmp_path, tiny_params, devices):
    """A checkpoint written under a multi-chip (fsdp) layout must load
    into a one-device replica: restored to the host, placed on the
    engine's device — not re-created with the trainer's shardings (which
    a smaller host cannot even load)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices[:4]), ("fsdp",))
    sharded = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, P("fsdp") if x.ndim and x.shape[0] % 4 == 0 else P())),
        tiny_params)
    ctx, sid = _save_checkpoint(tmp_path, sharded, 2)
    loaded = load_checkpoint_params(ctx.checkpoint, sid)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(loaded))
    # ...and whatever layout params arrive in, the engine holds them on
    # its one device (its executables are compiled for exactly that).
    eng = ServingEngine(sharded, TINY, slots=2, max_seq_len=32,
                        prefill_buckets=[8], attention_impl="reference")
    assert all(x.sharding.device_set == {devices[0]}
               for x in jax.tree_util.tree_leaves(eng.params))


def test_load_checkpoint_latest_resolves_lineage(tmp_path, tiny_params):
    _save_checkpoint(tmp_path, tiny_params, 2)
    ctx, _ = _save_checkpoint(tmp_path, tiny_params, 4)
    loaded = load_checkpoint_params(ctx.checkpoint, "latest")
    assert loaded is not None


def test_load_checkpoint_corrupt_falls_back_through_lineage(
        tmp_path, tiny_params):
    """A torn latest checkpoint must never be served: verification fails
    and the previous COMPLETED checkpoint loads instead."""
    _save_checkpoint(tmp_path, tiny_params, 2)
    ctx, sid4 = _save_checkpoint(tmp_path, tiny_params, 4)
    path4 = ctx.checkpoint._storage.path_for(sid4)
    victim = None
    for root, _, files in os.walk(os.path.join(path4, "state")):
        for f in files:
            victim = os.path.join(root, f)
    with open(victim, "r+b") as f:
        f.truncate(max(0, os.path.getsize(victim) // 2))
    loaded = load_checkpoint_params(ctx.checkpoint, sid4)
    assert loaded is not None  # fell back to trial0-step2


def test_load_checkpoint_nothing_completed_raises(tmp_path):
    ctx = core.init(max_length=2, checkpoint_dir=str(tmp_path / "ckpts"))
    with pytest.raises(FileNotFoundError):
        load_checkpoint_params(ctx.checkpoint, "latest")
    ctx.close()


# ---------------------------------------------------------------------------
# HTTP front-end: the status-code contract load balancers act on.
# ---------------------------------------------------------------------------


def _http(method, url, body=None, timeout=30.0):
    req = urllib.request.Request(
        url, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture()
def http_replica(tiny_params):
    from determined_tpu.serve.http import ServingServer

    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng).start()
    server = ServingServer(b, host="127.0.0.1", port=0).start()
    yield f"http://127.0.0.1:{server.port}", b
    server.stop()
    b.stop()


def test_http_generate_stats_health(http_replica, tiny_params):
    url, _ = http_replica
    status, body = _http("POST", url + "/v1/generate",
                         {"tokens": [5, 9, 17, 3], "max_new_tokens": 6})
    assert status == 200
    assert body["tokens"] == reference_greedy(
        tiny_params, [5, 9, 17, 3], 6)
    assert body["latency_ms"] >= body["queue_ms"] >= 0
    status, stats = _http("GET", url + "/v1/stats")
    assert status == 200 and stats["completed"] >= 1
    assert stats["engine"]["prefill_buckets"]
    status, health = _http("GET", url + "/healthz")
    assert (status, health["status"]) == (200, "ok")


def test_http_error_codes(http_replica):
    url, batcher = http_replica
    status, body = _http("POST", url + "/v1/generate", {"tokens": []})
    assert status == 400
    status, body = _http("POST", url + "/v1/generate",
                         {"tokens": list(range(1, 30))})  # no bucket
    assert status == 400
    batcher.queue.drain()
    status, body = _http("POST", url + "/v1/generate",
                         {"tokens": [1, 2], "max_new_tokens": 2})
    assert status == 503
    status, health = _http("GET", url + "/healthz")
    assert health["status"] == "draining"
    batcher.queue.undrain()
    status, _ = _http("POST", url + "/v1/generate",
                      {"tokens": [1, 2], "max_new_tokens": 2})
    assert status == 200


def test_429_carries_computed_retry_after(tiny_params):
    """A QueueFull 429 carries a Retry-After computed from queue depth ×
    the smoothed service time — a hint the harness Session (and the
    deployment router, which propagates the header) can act on."""
    import urllib.error
    import urllib.request

    from determined_tpu.serve.http import ServingServer

    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng, queue_size=1)
    # No start(): with the batcher thread parked, the queue fills and the
    # second submit 429s deterministically.
    eng.compile()
    server = ServingServer(b, host="127.0.0.1", port=0).start()
    try:
        url = f"http://127.0.0.1:{server.port}"
        body = {"tokens": [1, 2], "max_new_tokens": 2, "timeout_s": 0.1}
        req = urllib.request.Request(
            url + "/v1/generate", method="POST",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=10)  # fills the queue(504)
        except urllib.error.HTTPError:
            pass
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("expected 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            assert int(e.headers["Retry-After"]) >= 1
    finally:
        server.stop()
        b.stop()


def test_retry_after_hint_scales_with_backlog(tiny_params):
    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng, queue_size=64)
    assert b.retry_after_hint() == 1  # no history, empty queue
    # Synthetic history: 4s per request over 2 slots; 8 queued → ~16s.
    b._service_s_ewma = 4.0
    for _ in range(8):
        b.queue.submit(_req())
    assert b.retry_after_hint() == 16
    assert b.heartbeat_stats()["retry_after_hint_s"] == 16
    hb = b.heartbeat_stats()
    assert hb["queue_depth"] == 8 and hb["queue_capacity"] == 64
    assert hb["slots"] == 2 and hb["draining"] is False
    # The hint is clamped: a pathological backlog still answers <= 60.
    b._service_s_ewma = 1000.0
    assert b.retry_after_hint() == 60


# ---------------------------------------------------------------------------
# Request-path observability: latency histograms, phase stamps, request
# tracer + the serving.trace.drop contract (ISSUE 12; docs/serving.md
# "Request latency & SLOs", docs/observability.md "Request spans").
# ---------------------------------------------------------------------------


def test_latency_hist_percentiles():
    from determined_tpu.serve.scheduler import LatencyHist

    h = LatencyHist(buckets=(0.01, 0.1, 1.0))
    assert h.percentile(0.5) == 0.0  # empty
    for _ in range(99):
        h.observe(0.05)
    h.observe(0.5)
    assert h.count == 100 and 0.01 < h.percentile(0.5) <= 0.1
    assert 0.1 < h.percentile(0.995) <= 1.0
    # Over the top bucket: the estimate clamps to the last boundary.
    h2 = LatencyHist(buckets=(0.01,))
    h2.observe(5.0)
    assert h2.percentile(0.99) == 0.01
    wire = h.to_wire()
    assert wire["count"] == 100 and len(wire["le"]) == len(wire["counts"])
    # Cumulative counts are monotonic (Prometheus le semantics).
    assert wire["counts"] == sorted(wire["counts"])
    s = h.summary()
    assert s["p99_ms"] >= s["p50_ms"] > 0


def test_request_phase_stamps_and_histograms(tiny_params):
    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng).start()
    try:
        reqs = [b.submit(_req(max_new=4, request_id=f"phase-{i}"))
                for i in range(4)]
        results = [r.result(timeout=120) for r in reqs]
        for r, res in zip(reqs, results):
            # submit ≤ admit ≤ prefill end = first token ≤ finish, all on
            # the wall-clock span timeline.
            assert (r.submitted_us <= r.admitted_us <= r.prefill_end_us
                    == r.first_token_us <= r.finished_us)
            assert r.decode_steps == 3  # 4 new tokens = prefill + 3 steps
            assert res["ttft_ms"] >= 0 and res["tpot_ms"] >= 0
            assert res["latency_ms"] >= res["ttft_ms"] >= res["queue_ms"]
        # One observation per retired request in every histogram.
        hb = b.heartbeat_stats()["latency"]
        for key in ("ttft", "tpot", "e2e", "queue_wait"):
            assert hb[key]["count"] == 4, (key, hb[key])
        lat = b.stats()["latency"]
        assert lat["e2e"]["p50_ms"] >= lat["ttft"]["p50_ms"] > 0
    finally:
        b.stop()


def test_request_tracer_span_tree(tiny_params):
    from determined_tpu.serve.tracing import RequestTracer

    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng)
    tracer = RequestTracer(None, "", sample=1.0)
    b.tracer = tracer
    b.start()
    try:
        b.submit(_req(n_prompt=4, max_new=4,
                      request_id="tree-1")).result(timeout=120)
        tracer.flush()
        spans = [s for s in tracer.local_spans
                 if s["trace_id"] == "tree-1"]
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"serve.request", "serve.queue_wait",
                                "serve.prefill", "serve.decode"}
        root = by_name["serve.request"]
        assert root["span_id"] == "tree-1" and root["parent"] == ""
        assert root["attrs"] == {"prompt_tokens": 4, "new_tokens": 4}
        for name in ("serve.queue_wait", "serve.prefill", "serve.decode"):
            assert by_name[name]["parent"] == "tree-1"
        pf = by_name["serve.prefill"]["attrs"]
        assert pf["suffix_len"] == 4 and pf["prefix_cache_hit"] is False
        assert pf["bucket"] >= 4 and pf["blocks"] >= 1
        dec = by_name["serve.decode"]["attrs"]
        assert dec["tokens"] == 4 and dec["steps"] == 3
        assert dec["occupancy_at_admit"] >= 1
        # Phases nest inside the root on the timeline.
        for name in ("serve.queue_wait", "serve.prefill", "serve.decode"):
            s = by_name[name]
            assert root["start_us"] <= s["start_us"] <= s["end_us"] \
                <= root["end_us"]
    finally:
        b.stop()


def test_request_tracer_sampling_error_and_slo():
    """sample=0 suppresses healthy traces, but errors and SLO breaches
    are ALWAYS traced — the 'why was THIS request slow' contract."""
    from determined_tpu.serve.scheduler import now_us
    from determined_tpu.serve.tracing import RequestTracer

    def fake_request(rid, error=None, e2e_ms=5.0):
        r = _req(request_id=rid)
        r.admitted_us = r.submitted_us + 100
        r.prefill_start_us = r.admitted_us
        r.prefill_end_us = r.first_token_us = r.admitted_us + 200
        r.out_tokens = [1, 2]
        r.error = error
        r.finished_us = r.submitted_us + int(e2e_ms * 1000)
        return r

    tracer = RequestTracer(None, "", sample=0.0, slo_ms=100.0)
    assert tracer.record(fake_request("healthy")) is False
    assert tracer.sampled_out == 1
    assert tracer.record(fake_request("failed", error="boom")) is True
    assert tracer.record(fake_request("slow", e2e_ms=500.0)) is True
    assert tracer.slo_breaches == 1
    tracer.flush()
    traced = {s["trace_id"] for s in tracer.local_spans}
    assert traced == {"failed", "slow"}
    err_root = [s for s in tracer.local_spans
                if s["trace_id"] == "failed"
                and s["name"] == "serve.request"][0]
    assert err_root["attrs"]["error"] == "boom"
    # Fractional sampling stays within the fraction's ballpark.
    tracer2 = RequestTracer(None, "", sample=0.5)
    hits = sum(tracer2.record(fake_request(f"r{i}")) for i in range(200))
    assert 50 <= hits <= 150


def test_serving_trace_drop_generations_survive_span_sink_loss(tiny_params):
    """The chaos satellite (docs/chaos.md): with `serving.trace.drop`
    armed — and separately with a dead sink session — span batches drop
    and NOT ONE generation blocks or fails (same contract as PR 8's
    trace.span.drop)."""
    from determined_tpu.serve.tracing import FAULT_TRACE_DROP, RequestTracer

    class DeadSink:
        posts = 0

        def post(self, *a, **kw):
            DeadSink.posts += 1
            raise ConnectionError("span sink is gone")

    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng)
    tracer = RequestTracer(DeadSink(), "alloc-x", sample=1.0)
    b.tracer = tracer
    b.start()
    try:
        # Leg 1: the fault point eats the batch before it reaches any
        # sink — flush returns 0, nothing raises.
        faultpoint.arm(FAULT_TRACE_DROP, "drop", count=1)
        r = b.submit(_req(max_new=3, request_id="drop-1"))
        assert r.result(timeout=120)["tokens"]
        assert tracer.pending() > 0
        assert tracer.flush() == 0
        assert tracer.dropped == 1 and DeadSink.posts == 0

        # Leg 2: disarmed, the sink itself is dead — the POST raises
        # inside flush, the batch drops, generations keep completing.
        reqs = [b.submit(_req(max_new=3, request_id=f"drop-{i}"))
                for i in range(2, 6)]
        results = [r.result(timeout=120) for r in reqs]
        assert all(res["tokens"] for res in results)
        assert tracer.flush() == 0 and DeadSink.posts == 1
        assert tracer.dropped == 2
        # Zero failed requests — the acceptance gate.
        assert b.failed == 0 and b.stats()["completed"] == 5
    finally:
        b.stop()


def test_http_request_id_and_latency_exposition(http_replica):
    """The replica front-end adopts X-Request-Id, echoes it, and /metrics
    carries the four SLO histograms in exposition form."""
    url, batcher = http_replica
    req = urllib.request.Request(
        url + "/v1/generate", method="POST",
        data=json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 3}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "http-rid-1"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        assert resp.status == 200
        assert resp.headers["X-Request-Id"] == "http-rid-1"
        assert json.loads(resp.read())["id"] == "http-rid-1"
    with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
        text = resp.read().decode()
    for name in ("det_serve_ttft_seconds", "det_serve_tpot_seconds",
                 "det_serve_e2e_seconds", "det_serve_queue_wait_seconds"):
        assert f"# TYPE {name} histogram" in text
        count = [line for line in text.splitlines()
                 if line.startswith(f"{name}_count")]
        assert count and int(count[0].split()[-1]) >= 1, (name, text)
    # /v1/stats carries the summarized form next to the raw counters.
    status, stats = _http("GET", url + "/v1/stats")
    assert status == 200
    assert stats["latency"]["e2e"]["count"] >= 1
    assert stats["latency"]["e2e"]["p99_ms"] >= stats["latency"]["e2e"]["p50_ms"]


# ---------------------------------------------------------------------------
# Devcluster e2e (slow): submit → serve → drain → replica reschedule.
# ---------------------------------------------------------------------------


def _serving_config(tmp_path, sid="trial0-step2"):
    return {
        "name": "serve-e2e",
        "serving": {
            "checkpoint": sid,
            "model": "gpt2",
            "model_config": {"model_size": "tiny", "seq_len": 64,
                             "dtype": "float32",
                             "vocab_size": TINY.vocab_size,
                             "n_positions": 64,
                             "d_model": TINY.d_model,
                             "n_layer": TINY.n_layer,
                             "n_head": TINY.n_head},
            "max_batch_size": 4,
            "max_seq_len": 32,
            "prefill_buckets": [8, 16],
            "queue_depth": 32,
        },
        "resources": {"slots_per_trial": 1},
        "checkpoint_storage": {
            "type": "shared_fs",
            "host_path": os.path.join(str(tmp_path), "ckpts"),
        },
    }


# ---------------------------------------------------------------------------
# Model lifecycle (docs/serving.md "Model lifecycle"): multi-adapter
# replicas, swap bit-identity, registered-version restore.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def finetuned_params(tiny_params):
    """A head-tuned fine-tune of tiny_params: SAME transformer body,
    retrained (here: perturbed) tied embedding/LM-head table — the
    adapter contract (engine stacks exactly the wte per adapter)."""
    ft = dict(tiny_params)
    ft["wte"] = tiny_params["wte"] + 0.5 * jax.random.normal(
        jax.random.PRNGKey(7), tiny_params["wte"].shape)
    return ft


@pytest.fixture(scope="module")
def finetuned_params_b(tiny_params):
    ft = dict(tiny_params)
    ft["wte"] = tiny_params["wte"] + 0.5 * jax.random.normal(
        jax.random.PRNGKey(11), tiny_params["wte"].shape)
    return ft


class TestAdapters:
    """Multi-adapter replicas: N fine-tunes resident beside one base
    executable, routed per request by `model:` name."""

    def test_adapter_routed_equals_direct_serve(self, tiny_params,
                                                finetuned_params):
        """The acceptance contract: a request routed to adapter `ft`
        produces the SAME generations as a dedicated deployment of the
        fine-tuned checkpoint — many fine-tunes share a fleet without
        changing a single token of anyone's output."""
        eng = ServingEngine(tiny_params, TINY, slots=4, max_seq_len=32,
                            prefill_buckets=[8, 16, 32],
                            adapters={"ft": finetuned_params})
        b = make_batcher(eng)
        b.start()
        prompt = [5, 9, 17, 3]
        try:
            base_out = b.submit(Request(prompt, max_new_tokens=8)
                                ).result(60)["tokens"]
            ft_out = b.submit(Request(prompt, max_new_tokens=8,
                                      model="ft")).result(60)["tokens"]
        finally:
            b.stop()
        assert base_out != ft_out, "fine-tune must change generations"

        # Direct serve of the full fine-tuned checkpoint.
        eng2 = ServingEngine(finetuned_params, TINY, slots=4,
                             max_seq_len=32, prefill_buckets=[8, 16, 32])
        b2 = make_batcher(eng2)
        b2.start()
        try:
            direct = b2.submit(Request(prompt, max_new_tokens=8)
                               ).result(60)["tokens"]
        finally:
            b2.stop()
        assert ft_out == direct

        # And base routing on the adapter engine is bit-equal to a
        # no-adapter engine (index 0 IS the base table).
        eng3 = ServingEngine(tiny_params, TINY, slots=4, max_seq_len=32,
                             prefill_buckets=[8, 16, 32])
        b3 = make_batcher(eng3)
        b3.start()
        try:
            plain = b3.submit(Request(prompt, max_new_tokens=8)
                              ).result(60)["tokens"]
        finally:
            b3.stop()
        assert plain == base_out

    def test_mixed_batch_per_slot_routing(self, tiny_params,
                                          finetuned_params,
                                          finetuned_params_b):
        """Different adapters decode in the SAME continuous batch, each
        lane using its own table — per-slot routing, zero recompiles."""
        eng = ServingEngine(tiny_params, TINY, slots=4, max_seq_len=32,
                            prefill_buckets=[8, 16, 32],
                            adapters={"ft-a": finetuned_params,
                                      "ft-b": finetuned_params_b})
        b = make_batcher(eng)
        b.start()
        prompt = [5, 9, 17, 3]
        try:
            reqs = [
                b.submit(Request(prompt, max_new_tokens=12, model=m))
                for m in (None, "ft-a", "ft-b", None)
            ]
            outs = [r.result(60)["tokens"] for r in reqs]
            # Concurrency really happened (they shared decode steps).
            assert b.max_occupancy >= 2
        finally:
            b.stop()
        assert outs[0] == outs[3]            # same model, same tokens
        # Each matches its solo run (fresh batcher, same engine — the
        # compiled executables and adapter stack are the same objects).
        b2 = make_batcher(eng)
        b2.start()
        try:
            solo = {
                m: b2.submit(Request(prompt, max_new_tokens=12, model=m)
                             ).result(60)["tokens"]
                for m in (None, "ft-a", "ft-b")
            }
        finally:
            b2.stop()
        # The mixed batch reproduced each lane's solo generations: no
        # lane leaked another lane's table (and the fine-tune really
        # moved the base's output).
        assert outs[0] == solo[None]
        assert outs[1] == solo["ft-a"]
        assert outs[2] == solo["ft-b"]
        assert solo["ft-a"] != solo[None]

    def test_unknown_adapter_rejected(self, tiny_params,
                                      finetuned_params):
        eng = ServingEngine(tiny_params, TINY, slots=2, max_seq_len=32,
                            prefill_buckets=[8],
                            adapters={"ft": finetuned_params})
        b = make_batcher(eng)
        with pytest.raises(ValueError, match="unknown adapter"):
            b.submit(Request([1, 2, 3], model="ghost"))
        # No adapters resident at all: any model name is refused.
        eng2 = ServingEngine(tiny_params, TINY, slots=2, max_seq_len=32,
                             prefill_buckets=[8])
        b2 = make_batcher(eng2)
        with pytest.raises(ValueError, match="unknown adapter"):
            b2.submit(Request([1, 2, 3], model="ft"))

    def test_adapter_shape_mismatch_refused(self, tiny_params):
        bad = dict(tiny_params)
        bad["wte"] = jnp.zeros((8, 8), jnp.float32)
        with pytest.raises(ValueError, match="geometry"):
            ServingEngine(tiny_params, TINY, slots=2, max_seq_len=32,
                          adapters={"bad": bad})

    def test_adapter_stats_and_counters(self, tiny_params,
                                        finetuned_params):
        eng = ServingEngine(tiny_params, TINY, slots=2, max_seq_len=32,
                            prefill_buckets=[8],
                            adapters={"ft": finetuned_params})
        b = make_batcher(eng)
        b.start()
        try:
            b.submit(Request([1, 2, 3], max_new_tokens=2)).result(60)
            b.submit(Request([1, 2, 3], max_new_tokens=2,
                             model="ft")).result(60)
            b.submit(Request([1, 2, 3], max_new_tokens=2,
                             model="ft")).result(60)
        finally:
            b.stop()
        stats = b.stats()
        assert stats["adapter_requests"] == {"base": 1, "ft": 2}
        assert eng.stats()["adapters"] == ["ft"]


class TestLifecycleBitIdentity:
    """Swap bit-identity + registered-version restore (acceptance
    criteria of the model-lifecycle PR)."""

    def test_post_swap_replica_matches_fresh_deployment(
            self, tmp_path, tiny_params, finetuned_params):
        """A rolling swap replaces replicas rather than hot-editing
        weights: the replica the reconciler spawns for version B is
        config-identical to a fresh deployment of B — assert the
        generations are bit-identical, with BOTH loads going through the
        manifest+COMMIT verification path."""
        _save_checkpoint(tmp_path, tiny_params, 2)          # version A
        ctx, sid_b = _save_checkpoint(tmp_path, finetuned_params, 4)

        def replica_generations(storage_id):
            params = load_checkpoint_params(ctx.checkpoint, storage_id)
            eng = ServingEngine(params, TINY, slots=2, max_seq_len=32,
                                prefill_buckets=[8, 16])
            b = make_batcher(eng)
            b.start()
            try:
                return b.submit(Request([5, 9, 17, 3], max_new_tokens=8)
                                ).result(60)["tokens"]
            finally:
                b.stop()

        # "Post-swap replica": what spawn_deployment_replica_locked
        # launches after `det serve update` rewrote serving.checkpoint.
        post_swap = replica_generations(sid_b)
        # "Fresh deployment of that version": same checkpoint, new boot.
        fresh = replica_generations(sid_b)
        assert post_swap == fresh

    def test_registered_version_restore_verifies_integrity(
            self, tmp_path, tiny_params):
        """Registered-version restore reuses the PR-6 manifest+COMMIT
        path: a corrupted registered checkpoint REFUSES to serve (falls
        back through the lineage) instead of loading a torso."""
        _save_checkpoint(tmp_path, tiny_params, 2)
        ctx, sid = _save_checkpoint(tmp_path, tiny_params, 4)
        # Corrupt the registered version's payload.
        path = ctx.checkpoint._storage.path_for(sid)
        victim = None
        for root, _, files in os.walk(os.path.join(path, "state")):
            for f in files:
                victim = os.path.join(root, f)
        with open(victim, "r+b") as f:
            f.truncate(max(0, os.path.getsize(victim) // 2))
        # The resolution a deployment performs for "model:N" is exactly
        # load_checkpoint_params on the version's storage id.
        loaded = load_checkpoint_params(ctx.checkpoint, sid)
        assert loaded is not None  # lineage fallback, never the torso


@pytest.mark.slow
def test_serve_drain_reschedule_e2e(tmp_path, native_binaries):
    """Acceptance: a serve replica under load receives a spot notice —
    it stops admitting, finishes every in-flight sequence inside the
    grace window (zero dropped), exits cleanly, and the master
    reschedules it onto the surviving agent (restarts >= 1, fresh proxy
    address, serving again)."""
    from tests.test_platform_e2e import Devcluster

    # A checkpoint to serve. The tiny model must match the serve config;
    # TINY here uses n_positions=64 to cover seq_len.
    cfg = gpt2.Config(
        vocab_size=TINY.vocab_size, n_positions=64, d_model=32,
        n_layer=2, n_head=2, dtype=jnp.float32, remat=False,
        attention_impl="dot")
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    ctx = core.init(max_length=2,
                    checkpoint_dir=os.path.join(str(tmp_path), "ckpts"))
    ctx.checkpoint.save_state(
        {"step": jnp.asarray(2, jnp.int32), "params": params,
         "opt_state": {"count": jnp.zeros((), jnp.int32)}}, 2)
    ctx.checkpoint.wait()
    ctx.close()

    c = Devcluster(str(tmp_path), native_binaries, slots=1)
    c.start_master()
    notice_files = {}
    for agent_id in ("serve-a", "serve-b"):
        nf = os.path.join(str(tmp_path), f"notice-{agent_id}.json")
        notice_files[agent_id] = nf
        c.start_agent(agent_id, extra_env={"DET_AGENT_NOTICE_FILE": nf})
    try:
        token = c.login()
        resp = c.api("POST", "/api/v1/serving",
                     {"config": _serving_config(tmp_path)}, token=token)
        tid = resp["id"]

        def _task():
            return c.api("GET", f"/api/v1/serving/{tid}",
                         token=token)["task"]

        # Wait for the replica to come up and register its address.
        deadline = time.time() + 180
        task = None
        while time.time() < deadline:
            task = _task()
            if task.get("proxy_address"):
                break
            time.sleep(0.5)
        assert task and task.get("proxy_address"), task

        def generate(max_new=8, timeout=60):
            return c.api(
                "POST", f"/proxy/{tid}/v1/generate",
                {"tokens": [5, 9, 17, 3], "max_new_tokens": max_new,
                 "timeout_s": timeout},
                token=token)

        first = generate(max_new=4)
        assert len(first["tokens"]) == 4

        # Which agent hosts the replica? (serving allocation ids embed
        # the task id: alloc-{task_id}[-rN])
        jobs = c.api("GET", "/api/v1/job-queues", token=token)["jobs"]
        alloc_id = next(j["allocation_id"] for j in jobs
                        if tid in str(j.get("allocation_id", "")))
        alloc = c.api("GET", f"/api/v1/allocations/{alloc_id}",
                      token=token)["allocation"]
        victim = alloc["resources"][0]["agent_id"]
        survivor = "serve-b" if victim == "serve-a" else "serve-a"

        # Load in flight while the notice lands: every accepted request
        # must complete (zero dropped responses).
        results, errors = [], []

        def _loader():
            for _ in range(4):
                try:
                    results.append(generate(max_new=16, timeout=90))
                except Exception as e:  # 503s after drain are expected
                    errors.append(str(e))

        threads = [threading.Thread(target=_loader) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        with open(notice_files[victim], "w") as f:
            json.dump({"deadline_seconds": 30,
                       "reason": "spot_preemption"}, f)
        for t in threads:
            t.join(timeout=180)

        # Every response that came back is complete; HTTP-level
        # rejections (503 while draining) are allowed, dropped/truncated
        # responses are not.
        assert results, "no request completed during the drain window"
        assert all(len(r["tokens"]) == 16 for r in results), results

        # The replica reschedules onto the survivor with restarts >= 1
        # and serves again from its new address.
        deadline = time.time() + 180
        moved = None
        while time.time() < deadline:
            task = _task()
            if int(task.get("restarts") or 0) >= 1 and \
                    task.get("allocation_state") == "RUNNING" and \
                    task.get("proxy_address"):
                jobs = c.api("GET", "/api/v1/job-queues",
                             token=token)["jobs"]
                for j in jobs:
                    a = c.api("GET",
                              f"/api/v1/allocations/{j['allocation_id']}",
                              token=token)["allocation"]
                    if a.get("task_id") == tid and a["state"] == "RUNNING":
                        moved = a["resources"][0]["agent_id"]
                if moved:
                    break
            time.sleep(0.5)
        assert moved == survivor, (
            f"replica did not reschedule onto {survivor}: task={task}")
        again = generate(max_new=4)
        assert len(again["tokens"]) == 4
        c.api("POST", f"/api/v1/serving/{tid}/kill", {}, token=token)
    finally:
        c.stop()
