"""Falcon-H1 (a Mamba-2 mixer beside grouped-query attention in every
block) at a tiny size on the CPU: the benchmark's plain reference against
Hugging Face's implementation, and the served path (`build_model` →
`ServingEngine` → `ContinuousBatcher` → `BlockManager`) against the
reference — logits, not tokens."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import cells
from benchmarks.run import ROOT

MANIFEST = cells.load_manifest(ROOT)
with open(os.path.join(ROOT, "benchmarks/configs/falcon-h1-34b.json")) as f:
    PUBLISHED = json.load(f)
TINY = cells.merged(PUBLISHED, PUBLISHED["tiny"])
ADAPTER = cells.load_model(ROOT, MANIFEST, PUBLISHED)

# every multiplier of the source at a value other than 1
MULTIPLIERS = {
    "embedding_multiplier": 1.7, "attention_in_multiplier": 0.8,
    "attention_out_multiplier": 0.6, "key_multiplier": 0.45,
    "ssm_in_multiplier": 1.3, "ssm_out_multiplier": 0.7,
    "ssm_multipliers": [0.9, 1.2, 0.75, 1.4, 0.6],
    "mlp_multipliers": [0.8, 0.55], "lm_head_multiplier": 0.35,
}


def tiny_config(**over):
    return dict(TINY, **MULTIPLIERS, **over)


def float_params(config, seed=0, scale=1.0):
    """The adapter's draw, upcast, its matrices widened by `scale` so that
    every path carries signal at the tiny size."""
    dims = ADAPTER.dims(config)
    params = ADAPTER.init_params(jax.random.PRNGKey(seed), dims)
    wide = {"qkv", "o", "in_proj", "out_proj", "gate", "up", "down"}
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    params["blocks"] = {k: v * (scale if k in wide else 1.0)
                        for k, v in params["blocks"].items()}
    params["embed"] = params["embed"] * scale
    params["lm_head"] = params["lm_head"] * scale
    return params, dims


def test_reference_agrees_with_hugging_face():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.models.falcon_h1 import (FalconH1Config,
                                               FalconH1ForCausalLM)

    del transformers
    config = tiny_config()
    params, dims = float_params(config, seed=3, scale=8.0)
    own = {"model_type", "source", "reduced", "published", "deployment",
           "assumed", "serve", "tiny"}
    hf_config = FalconH1Config(
        **{k: v for k, v in config.items() if k not in own},
        attn_implementation="eager")
    model = FalconH1ForCausalLM(hf_config).eval().float()

    def put(tensor, value):
        with torch.no_grad():
            tensor.copy_(torch.from_numpy(np.array(value, np.float32)))

    hq = dims["n_head"] * dims["head_dim"]
    hkv = dims["kv_heads"] * dims["head_dim"]
    put(model.model.embed_tokens.weight, params["embed"])
    put(model.lm_head.weight, params["lm_head"])
    put(model.model.final_layernorm.weight, params["final_norm"])
    for i, layer in enumerate(model.model.layers):
        lp = jax.tree.map(lambda x: x[i], params["blocks"])
        put(layer.input_layernorm.weight, lp["input_norm"])
        put(layer.pre_ff_layernorm.weight, lp["pre_ff_norm"])
        put(layer.self_attn.q_proj.weight, lp["qkv"][:, :hq].T)
        put(layer.self_attn.k_proj.weight, lp["qkv"][:, hq:hq + hkv].T)
        put(layer.self_attn.v_proj.weight, lp["qkv"][:, hq + hkv:].T)
        put(layer.self_attn.o_proj.weight, lp["o"].T)
        put(layer.mamba.in_proj.weight, lp["in_proj"].T)
        put(layer.mamba.conv1d.weight, lp["conv_w"].T[:, None, :])
        put(layer.mamba.conv1d.bias, lp["conv_b"])
        put(layer.mamba.A_log, lp["A_log"])
        put(layer.mamba.dt_bias, lp["dt_bias"])
        put(layer.mamba.D, lp["D"])
        put(layer.mamba.norm.weight, lp["mixer_norm"])
        put(layer.mamba.out_proj.weight, lp["out_proj"].T)
        put(layer.feed_forward.gate_proj.weight, lp["gate"].T)
        put(layer.feed_forward.up_proj.weight, lp["up"].T)
        put(layer.feed_forward.down_proj.weight, lp["down"].T)

    # 21 tokens: two whole chunks of 8 and a part of a third
    tokens = np.random.default_rng(5).integers(
        0, dims["vocab_size"], (2, 21)).astype(np.int32)
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens).long(),
                       logits_to_keep=0).logits.numpy()
    gather = np.broadcast_to(np.arange(21, dtype=np.int32), (2, 21))
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(ADAPTER.logits(params, tokens, gather, dims))
    assert np.std(theirs) > 0.05        # the comparison carries signal
    np.testing.assert_allclose(ours, theirs, atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------- the kernels


def _recurrence_inputs(seed, t, heads=4, groups=2, p=16, n=16):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    x = jnp.asarray(rng.normal(size=(t, heads, p)), f32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (t, heads)), f32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), f32)
    b = jnp.asarray(rng.normal(size=(t, groups, n)), f32)
    c = jnp.asarray(rng.normal(size=(t, groups, n)), f32)
    return x, dt, a, b, c


def _sequential_scan(x, dt, a, b, c):
    """The recurrence a step at a time → (y [T, H, P], state [H, N, P])."""
    heads, groups = x.shape[1], b.shape[1]
    bh, ch = (jnp.repeat(m, heads // groups, axis=1) for m in (b, c))

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        return state, jnp.sum(state * c_t[:, :, None], axis=1)

    state, y = jax.lax.scan(
        step, jnp.zeros((heads, b.shape[2], x.shape[2]), jnp.float32),
        (x, dt, bh, ch))
    return y, state


@pytest.mark.parametrize("t,real", [(16, 16), (24, 19), (21, 21), (8, 3)],
                         ids=["whole-chunks", "padded-behind", "ragged",
                              "shorter-than-a-chunk"])
def test_chunked_scan_agrees_with_the_sequential_scan(t, real):
    """Chunks of 8: matmuls within a chunk and a carried state between
    them give what the recurrence gives a step at a time; positions with
    dt = 0 behind the real ones leave the state where the last real one
    left it."""
    from determined_tpu.ops.ssm_state import ssd_chunked_scan

    x, dt, a, b, c = _recurrence_inputs(t, t)
    dt = jnp.where(jnp.arange(t)[:, None] < real, dt, 0.0)
    y, state = ssd_chunked_scan(x, dt, a, b, c, chunk=8)
    want_y, want_state = _sequential_scan(*(m[:real] for m in (x, dt)), a,
                                          b[:real], c[:real])
    np.testing.assert_allclose(y[:real], want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("live", [
    [True, True, True, True, True], [False, True, False, True, False],
    [True, False, False, False, False], [False, False, False, False, True],
    [False] * 5], ids=["all", "alternating", "first", "last", "nobody"])
def test_state_kernel_agrees_with_its_reference(live):
    """The Pallas state update (run by the TPU interpreter, whose
    uninitialised memory is NaN) against the jnp twin: the same new state
    and the same y on live lanes, and an idle lane's state bit for bit
    what it was, in a layer that is not the pool's first."""
    from jax.experimental.pallas import tpu as pltpu

    from determined_tpu.ops.ssm_state import (ssm_state_pallas,
                                              ssm_state_reference)

    slots, heads, groups, p, n = 5, 8, 2, 128, 16
    x, dt, a, b, c = _recurrence_inputs(3, slots, heads, groups, p, n)
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.normal(size=(3, slots, heads, n, p)), jnp.float32)
    live = jnp.asarray(live)
    args = (x, dt, b, c, pool, jnp.int32(1), live, a)
    want_pool, want_y = ssm_state_reference(*args)
    got_pool, got_y = ssm_state_pallas(
        *args, interpret=pltpu.InterpretParams())
    np.testing.assert_allclose(got_y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_pool, want_pool, atol=1e-6, rtol=1e-6)
    idle = np.flatnonzero(~np.asarray(live))
    assert np.array_equal(np.asarray(got_pool)[:, idle],
                          np.asarray(pool)[:, idle])
    assert np.array_equal(np.asarray(got_pool)[[0, 2]],
                          np.asarray(pool)[[0, 2]])
    assert not np.asarray(got_y)[idle].any()
    if bool(live.any()):
        assert not np.allclose(got_pool[1], pool[1])


@pytest.mark.parametrize("hq,hkv,dh", [(20, 4, 128), (20, 20, 64)],
                         ids=["grouped-20-over-4x128", "own-20x64"])
def test_paged_kernel_takes_shared_kv_heads(hq, hkv, dh):
    """The paged decode kernel (interpreted) against its reference with
    query heads sharing K/V heads — the pool's row is Hkv*Dh lanes — and,
    unchanged, with a K/V head for each query head; the grouped reference
    itself against attention written out head by head."""
    from determined_tpu.ops.paged_attention import (
        paged_attention_pallas, paged_attention_reference)

    rng = np.random.default_rng(7)
    layers, slots, mb, bs = 2, 3, 12, 16
    pool = (layers, slots * mb + 1, bs, hkv * dh)
    q = jnp.asarray(rng.normal(size=(slots, hq, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    tbl = np.arange(slots * mb).reshape(slots, mb).astype(np.int32)
    tbl[1] = slots * mb                    # an idle lane between live ones
    pos = np.array([5, 0, 150], np.int32)
    args = (jnp.int32(1), jnp.asarray(tbl), jnp.asarray(pos))
    ref = np.asarray(paged_attention_reference(q, kp, vp, *args))
    out = np.asarray(paged_attention_pallas(q, kp, vp, *args,
                                            interpret=True))
    np.testing.assert_allclose(out[[0, 2]], ref[[0, 2]], atol=1e-5)
    assert not out[1].any()
    for lane in (0, 2):
        keys = np.asarray(kp)[1, tbl[lane]].reshape(mb * bs, hkv, dh)
        values = np.asarray(vp)[1, tbl[lane]].reshape(mb * bs, hkv, dh)
        keys, values = keys[:pos[lane] + 1], values[:pos[lane] + 1]
        for head in range(hq):
            kv = head // (hq // hkv)
            logit = keys[:, kv] @ np.asarray(q)[lane, head] / np.sqrt(dh)
            prob = np.exp(logit - logit.max())
            np.testing.assert_allclose(
                ref[lane, head], prob @ values[:, kv] / prob.sum(),
                atol=1e-5)


# ------------------------------------------- through the engine and batcher


def _replica(config, params, prefix_cache=False, **engine_kwargs):
    """`benchmarks/loops.py make_replica`'s objects at the tiny size."""
    from determined_tpu.serve.engine import ServingEngine
    from determined_tpu.serve.kv_cache import BlockManager
    from determined_tpu.serve.scheduler import (AdmissionQueue,
                                                ContinuousBatcher)
    from determined_tpu.serve.task import build_model

    serve = config["serve"]
    cfg = build_model(ADAPTER.serving(config, serve))
    engine = ServingEngine(
        params, cfg, slots=serve["max_batch_size"],
        max_seq_len=serve["max_seq_len"],
        prefill_buckets=serve["prefill_buckets"],
        attention_impl=serve["attention_impl"],
        kv_block_size=serve["kv_block_size"],
        kv_num_blocks=serve["kv_num_blocks"], **engine_kwargs)
    blocks = BlockManager(num_blocks=engine.num_blocks,
                          block_size=engine.block_size,
                          prefix_cache=prefix_cache)
    return engine, ContinuousBatcher(
        engine, queue=AdmissionQueue(maxsize=16), block_manager=blocks)


class _Logits:
    """Stands where the engine samples and keeps the logits it sampled
    from: a prefill's (the call's third output, which the serving path
    leaves on the device) with its lane, its prompt and the token the
    call sampled, a decode call's with its positions."""

    def __init__(self, engine):
        self.events, self.positions = [], None
        prefill, decode, sample = (engine._enqueue_prefill, engine.decode,
                                   engine._compiled_sample)

        def enqueue_prefill(ph, slot, tokens, *args):
            first, logits = prefill(ph, slot, tokens, *args)
            self.events.append(("prefill", slot, tuple(tokens.tolist()),
                                np.asarray(logits), int(first)))
            return first, logits

        def decode_call(tokens, positions, temperatures):
            self.positions = np.array(positions)
            return decode(tokens, positions, temperatures)

        def compiled_sample(logits, temps, rng):
            self.events.append(
                ("decode", self.positions, np.asarray(logits)))
            return sample(logits, temps, rng)

        engine._enqueue_prefill = enqueue_prefill
        engine.decode = decode_call
        engine._compiled_sample = compiled_sample


def test_prefill_then_decode_through_the_batcher_gives_the_references_logits():
    """Six requests over four lanes, float32 throughout and every
    multiplier off 1: each prefill's logits and each decode step's, lane
    by lane, are the reference's one full forward over prompt + reply.
    Prompts shorter than their bucket, of a whole bucket, across a chunk's
    edge (chunks of 8) and in the second bucket; lanes are released and
    admitted again, so a new sequence starts from a zero state in a lane
    that holds an old one's."""
    from determined_tpu.serve.scheduler import Request

    config = tiny_config()
    config["serve"] = dict(config["serve"], dtype="float32")
    params, dims = float_params(config, seed=1, scale=6.0)
    engine, batcher = _replica(config, params)
    batcher.start()
    seen = _Logits(engine)
    rng = np.random.default_rng(4)
    shapes = [(5, 9), (11, 4), (16, 12), (19, 6), (8, 7), (3, 10)]
    requests = [Request(rng.integers(0, dims["vocab_size"], p, np.int32),
                        max_new_tokens=n, temperature=0.0)
                for p, n in shapes]
    try:
        for req in requests:
            batcher.submit(req)
        for req in requests:
            req.result(timeout=120)
    finally:
        batcher.stop()
    width = max(p + n for p, n in shapes)
    tokens = np.zeros((len(requests), width), np.int32)
    for r, req in enumerate(requests):
        seq = np.concatenate([req.tokens, req.out_tokens])
        tokens[r, :len(seq)] = seq
    gather = np.broadcast_to(np.arange(width, dtype=np.int32), tokens.shape)
    ref = np.asarray(ADAPTER.logits(params, tokens, gather, dims))
    assert np.std(ref) > 0.05
    row = {tuple(req.tokens.tolist()): r for r, req in enumerate(requests)}
    owner, prefills, compared = {}, {}, 0
    for event in seen.events:
        if event[0] == "prefill":
            _, slot, prompt, logits, first = event
            owner[slot] = row[prompt]
            prefills[slot] = prefills.get(slot, 0) + 1
            np.testing.assert_allclose(
                logits, ref[row[prompt], len(prompt) - 1], atol=2e-4)
            assert first == int(np.argmax(logits))
            assert first == requests[row[prompt]].out_tokens[0]
            compared += 1
        else:
            _, positions, logits = event
            for slot in np.flatnonzero(positions > 0):
                np.testing.assert_allclose(
                    logits[slot], ref[owner[slot], positions[slot]],
                    atol=2e-4)
                compared += 1
    assert compared == sum(n for _, n in shapes)
    assert max(prefills.values()) > 1       # a lane was admitted twice
    stats = engine.stats()
    assert stats["state_lanes_live"] == sum(n - 1 for _, n in shapes)
    assert stats["state_lanes_grid"] == stats["state_lanes_live"]
    assert stats["first_token_host_bytes"] == 4 * len(shapes)


def test_first_token_is_sampled_in_the_prefill_call():
    """The engine's one first-token path in this family: greedy is the
    argmax of the reference's logits, a temperature draws from them under
    the engine's key for that step, four bytes a prefill come back."""
    from tests.test_serving import check_first_tokens

    config = tiny_config()
    config["serve"] = dict(config["serve"], dtype="float32")
    params, dims = float_params(config, seed=5, scale=6.0)
    engine, _ = _replica(config, params, seed=11)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, dims["vocab_size"], n, np.int32)
               for n in (5, 16, 19)]
    calls = [dict(slot=slot, tokens=p,
                  block_table=[slot * 4 + i for i in range(4)])
             for slot, p in enumerate(prompts)]
    reference = [
        np.asarray(ADAPTER.logits(
            params, p[None], np.arange(len(p), dtype=np.int32)[None],
            dims))[0, -1] for p in prompts]
    check_first_tokens(engine, calls, reference, seed=11)


def test_idle_lanes_keep_their_state_and_counters_count_the_state_pool(
        monkeypatch):
    """A lane that is not decoding — never admitted, or released with an
    old sequence's state still in it — is left bit for bit as it was by
    other lanes' decode steps; `engine.stats()` counts the state pool's
    bytes and the lanes whose state a decode call moved."""
    config = tiny_config()
    params, dims = float_params(config, seed=2, scale=6.0)
    engine, _ = _replica(config, jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), params))
    engine.compile()
    slots, layers = 4, dims["n_layer"]
    state = layers * slots * (
        dims["ssm_heads"] * dims["ssm_head_dim"] * dims["ssm_state"] * 4
        + 3 * (dims["ssm_heads"] * dims["ssm_head_dim"]
               + 2 * dims["ssm_groups"] * dims["ssm_state"]) * 2)
    stats = engine.stats()
    assert stats["state_hbm_bytes"] == state
    kv = 2 * layers * 17 * 16 * dims["kv_heads"] * dims["head_dim"] * 2
    assert stats["cache_hbm_bytes"] == state + kv
    assert stats["state_lanes_live"] == stats["state_lanes_grid"] == 0
    rng = np.random.default_rng(0)
    for slot, length in ((1, 7), (2, 12)):
        engine.prefill_request(
            slot, rng.integers(0, 512, length).astype(np.int32),
            block_table=[slot * 4 + i for i in range(4)])
    engine.release_slot(2)              # its state stays in the pool
    before = {k: np.asarray(v) for k, v in engine._cache.items()}
    assert before["ssm"][:, 2].any() and not before["ssm"][:, [0, 3]].any()
    token, position = 5, 7
    for _ in range(3):
        tokens, positions = np.zeros(4, np.int32), np.zeros(4, np.int32)
        tokens[1], positions[1] = token, position
        token = int(engine.decode(tokens, positions,
                                  np.zeros(4, np.float32))[1])
        position += 1
    after = {k: np.asarray(v) for k, v in engine._cache.items()}
    for pool in ("ssm", "conv"):
        assert np.array_equal(after[pool][:, [0, 2, 3]],
                              before[pool][:, [0, 2, 3]]), pool
        assert not np.array_equal(after[pool][:, 1], before[pool][:, 1])
    stats = engine.stats()
    assert stats["state_lanes_live"] == stats["state_lanes_grid"] == 3
    assert stats["decode_spans_live"] == 3


def test_a_family_with_recurrent_state_refuses_what_it_cannot_share():
    """Prefix sharing, a cached prefix, copy-on-write and adapters do not
    apply to a recurrent state: each is refused, with its reason."""
    from determined_tpu.serve.task import build_model, build_replica

    config = tiny_config()
    params, _ = float_params(config)
    serving = dict(ADAPTER.serving(config, config["serve"]),
                   max_batch_size=2, max_seq_len=64, kv_block_size=16)
    with pytest.raises(ValueError, match="prefix_cache.*recurrent state"):
        build_replica({"serving": dict(serving, prefix_cache=True)})
    with pytest.raises(ValueError, match="adapters.*recurrent state"):
        _replica(config, params, adapters={"tuned": params})
    engine, _ = _replica(config, params)
    with pytest.raises(ValueError, match="cached_len 16.*recurrent state"):
        engine.prefill_request(0, np.arange(20, dtype=np.int32),
                               cached_len=16)
    with pytest.raises(ValueError, match="copy_block.*recurrent state"):
        engine.copy_block(0, 1)
    with pytest.raises(ValueError, match="falcon_h1, glm4_moe_lite, gpt2"):
        build_model({"model": "mamba"})
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        build_model({"model": "falcon_h1", "model_config": dict(
            serving["model_config"], mamba_norm_before_gate=True)})
    # nothing clips the context to a position table the family lacks
    engine, _ = _replica(cells.merged(config, {"serve": {
        "max_seq_len": 4096, "kv_num_blocks": 8}}), params)
    assert engine.max_seq_len == 4096


def test_the_adapters_draw_is_the_tree_the_program_serves():
    """Serving hands the engine the adapter's leaves as drawn: every leaf
    bfloat16, nothing for the engine to narrow, the same draw from the
    same key, decays neither 0 nor 1, and the configuration's dtypes and
    work counts as the cell states them."""
    from determined_tpu.serve import falcon_h1 as family
    from determined_tpu.serve.task import build_model

    dims = ADAPTER.dims(TINY)
    cfg = build_model(ADAPTER.serving(TINY, TINY["serve"]))
    assert cfg.family == "falcon_h1"
    ours = ADAPTER.init_params(jax.random.PRNGKey(7), dims)
    again = ADAPTER.init_params(jax.random.PRNGKey(7), dims)
    resident = family.resident_params(ours, cfg)
    for a, b, c in zip(jax.tree.leaves(ours), jax.tree.leaves(again),
                       jax.tree.leaves(resident)):
        assert a.dtype == jnp.bfloat16 and bool(jnp.all(a == b))
        assert c is a
    assert ours["blocks"]["in_proj"].shape == (
        cfg.num_hidden_layers, cfg.hidden_size, sum(cfg.in_proj_sections))
    decay = np.exp(-np.exp(np.asarray(ours["blocks"]["A_log"], np.float32))
                   * np.log1p(np.exp(np.asarray(
                       ours["blocks"]["dt_bias"], np.float32))))
    assert 0.1 < decay.min() and decay.max() < 0.9999
    assert cfg.state_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16
    work = ADAPTER.work(ADAPTER.dims(PUBLISHED))
    assert work["params_per_token"] == 6 * 430_100_480 + 261120 * 5120
    assert (work["q_heads"], work["kv_heads"], work["head_dim"]) == (
        20, 4, 128)
    assert (work["ssm_layers"], work["ssm_heads"], work["ssm_head_dim"],
            work["ssm_state"], work["ssm_groups"],
            work["ssm_state_itemsize"]) == (6, 32, 128, 256, 2, 4)
