"""GLM-4-MoE-Lite (latent attention over a pool under the block table, a
dropless expert layer with a shared expert) at a tiny size on the CPU: the
benchmark's plain reference against Hugging Face's DeepseekV3 (the same
equations under another `model_type`), the served path (`build_model` →
`ServingEngine` → `ContinuousBatcher` → `BlockManager`) against the
reference — logits, not tokens —, and both kernels interpreted."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import cells
from benchmarks.run import ROOT
from tests.test_falcon_h1 import _Logits

MANIFEST = cells.load_manifest(ROOT)
with open(os.path.join(ROOT, "benchmarks/configs/glm-4.7-flash.json")) as f:
    PUBLISHED = json.load(f)
TINY = cells.merged(PUBLISHED, PUBLISHED["tiny"])
ADAPTER = cells.load_model(ROOT, MANIFEST, PUBLISHED)
WIDE = {"q_a", "q_b", "kv_a", "kv_b", "o", "gate", "up", "down", "w13",
        "w2", "shared_gate", "shared_up", "shared_down"}


def float_params(config, seed=0, scale=1.0, bias=0.2):
    """The adapter's draw, upcast, its matrices widened by `scale` and the
    router's bias by `bias / 0.02`, so that every path — the bias's part
    in the choice too — carries signal at the tiny size."""
    dims = ADAPTER.dims(dict(config, reference={}))   # no row levelled
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32),
        ADAPTER.init_params(jax.random.PRNGKey(seed), dims))
    for kind in ("dense", "moe"):
        params[kind] = {k: v * (scale if k in WIDE else 1.0)
                        for k, v in params[kind].items()}
    params["moe"]["router"] = params["moe"]["router"] * 20.0
    params["moe"]["router_bias"] = params["moe"]["router_bias"] * bias / 0.02
    params["embed"] = params["embed"] * scale
    params["lm_head"] = params["lm_head"] * scale
    return params, dims


def _float32(config, **serve):
    return dict(config, serve=dict(config["serve"], dtype="float32",
                                   **serve))


# ------------------------------------------------- (f) against Hugging Face


def test_reference_agrees_with_hugging_faces_deepseek_v3():
    """The reference against `DeepseekV3ForCausalLM` built from the same
    keys with the weights copied across; `rope_interleave=False` is the
    rotate_half pairing the reference and the program use."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.models.deepseek_v3 import (DeepseekV3Config,
                                                 DeepseekV3ForCausalLM)

    config = dict(TINY)
    params, dims = float_params(config, seed=3, scale=8.0)
    own = {"model_type", "source", "reduced", "published", "deployment",
           "assumed", "reference", "serve", "tiny", "partial_rotary_factor"}
    model = DeepseekV3ForCausalLM(DeepseekV3Config(
        **{k: v for k, v in config.items() if k not in own},
        rope_interleave=False, attn_implementation="eager")).eval().float()

    def put(tensor, value, transpose=True):
        value = np.array(value, np.float32)
        with torch.no_grad():
            tensor.copy_(torch.from_numpy(
                value.T if transpose and value.ndim == 2 else value))

    put(model.model.embed_tokens.weight, params["embed"], transpose=False)
    put(model.lm_head.weight, params["lm_head"], transpose=False)
    put(model.model.norm.weight, params["final_norm"])
    f = dims["expert_ff"]
    for i, layer in enumerate(model.model.layers):
        kind, j = ("dense", i) if i < dims["dense_layers"] \
            else ("moe", i - dims["dense_layers"])
        lp = jax.tree.map(lambda x: x[j], params[kind])
        attn = layer.self_attn
        put(layer.input_layernorm.weight, lp["input_norm"])
        put(layer.post_attention_layernorm.weight, lp["post_norm"])
        put(attn.q_a_proj.weight, lp["q_a"])
        put(attn.q_a_layernorm.weight, lp["q_a_norm"])
        put(attn.q_b_proj.weight, lp["q_b"])
        put(attn.kv_a_proj_with_mqa.weight, lp["kv_a"])
        put(attn.kv_a_layernorm.weight, lp["kv_a_norm"])
        put(attn.kv_b_proj.weight, lp["kv_b"])
        put(attn.o_proj.weight, lp["o"])
        if kind == "dense":
            for name in ("gate", "up", "down"):
                put(getattr(layer.mlp, name + "_proj").weight, lp[name])
            continue
        put(layer.mlp.gate.weight, lp["router"])
        put(layer.mlp.gate.e_score_correction_bias, lp["router_bias"])
        for e, expert in enumerate(layer.mlp.experts):
            put(expert.gate_proj.weight, lp["w13"][e, :, :f])
            put(expert.up_proj.weight, lp["w13"][e, :, f:])
            put(expert.down_proj.weight, lp["w2"][e])
        for name in ("gate", "up", "down"):
            put(getattr(layer.mlp.shared_experts, name + "_proj").weight,
                lp["shared_" + name])
    tokens = np.random.default_rng(0).integers(
        0, dims["vocab_size"], (2, 24), dtype=np.int32)
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens.astype(np.int64))
                       ).logits.numpy()
    gather = np.broadcast_to(np.arange(24, dtype=np.int32), tokens.shape)
    ours = np.asarray(ADAPTER.logits(params, tokens, gather, dims))
    assert np.std(theirs) > 0.05
    np.testing.assert_allclose(ours, theirs, atol=2e-4)


def test_a_near_tie_in_the_routing_levels_the_references_row():
    """`reference.routing_band` of the configuration's file: the float32
    pass answers a level row (gap 0 for every token) at the positions
    whose choice of experts, in some layer, is decided by less than the
    band, every other row is the plain pass's to the bit, and a control's
    pass is never levelled. The margin is the last chosen score + bias
    less the first one left out."""
    params, plain = float_params(TINY, seed=3, scale=8.0)
    tokens = np.random.default_rng(1).integers(
        0, plain["vocab_size"], (2, 24), dtype=np.int32)
    gather = np.broadcast_to(np.arange(24, dtype=np.int32), tokens.shape)
    margin = np.asarray(ADAPTER.hidden_and_margin(params, tokens, plain)[1])
    band = float(np.median(margin))
    banded = ADAPTER.dims(dict(TINY, reference={"routing_band": band}))
    assert banded["band"] == band and plain["band"] == 0.0
    assert ADAPTER.dims(PUBLISHED)["band"] \
        == PUBLISHED["reference"]["routing_band"] > 0
    full = np.asarray(ADAPTER.logits(params, tokens, gather, plain))
    cut = np.asarray(ADAPTER.logits(params, tokens, gather, banded))
    near = margin < band
    assert 0 < near.sum() < near.size
    assert not cut[near].any() and full[near].any()
    np.testing.assert_array_equal(cut[~near], full[~near])
    np.testing.assert_array_equal(
        np.asarray(ADAPTER.logits(params, tokens, gather, banded, "int8")),
        np.asarray(ADAPTER.logits(params, tokens, gather, plain, "int8")))
    assert "reference" not in ADAPTER.serving(
        PUBLISHED, PUBLISHED["serve"])["model_config"]

    x, layer, dims = _skewed_layer()
    mask, margin = ADAPTER.routing_mask(x, layer, dims, None)
    biased = np.sort(np.asarray(
        jax.nn.sigmoid(x @ layer["router"]) + layer["router_bias"]), -1)
    np.testing.assert_allclose(margin, biased[:, -2] - biased[:, -3],
                               atol=1e-6)
    assert (np.count_nonzero(np.asarray(mask), -1) == 2).all()


# ----------------------------------------- (c), (d), (e) the expert layer


def _skewed_layer(seed=0, t=100, d=64, f=32, e=8, k=2):
    """A layer whose routing is skewed: expert 3's bias far above the
    others', three experts' far below — one gets most tokens, several
    none."""
    rng = np.random.default_rng(seed)
    bias = np.array([0.0, -9, 0.1, 5.0, -9, 0.05, -9, 0.0], np.float32)
    params = {
        "router": jnp.asarray(rng.normal(size=(d, e)) * 0.3, jnp.float32),
        "router_bias": jnp.asarray(bias),
        "w13": jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.2, jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(e, f, d)) * 0.2, jnp.float32),
        "shared_gate": jnp.asarray(rng.normal(size=(d, f)) * 0.2, jnp.float32),
        "shared_up": jnp.asarray(rng.normal(size=(d, f)) * 0.2, jnp.float32),
        "shared_down": jnp.asarray(rng.normal(size=(f, d)) * 0.2,
                                   jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    dims = {"experts": e, "top_k": k, "scaling": 1.8, "norm_topk": True,
            "expert_ff": f, "held_first": 0, "held": e}
    return x, params, dims


def _shared(x, params):
    return ADAPTER._swiglu(x, params["shared_gate"], params["shared_up"],
                           params["shared_down"], None)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_dropless_layer_is_the_references_masked_sum(impl):
    """Under a routing skewed so that one expert gets most tokens and
    several get none, every assignment is computed: the layer (the jnp
    twin, and the Pallas kernel interpreted: 200 rows in two tiles of 128
    with a padded tail) is the reference's sum over every expert of every
    token masked by the routing weights. Nothing is dropped — the Switch
    layer's capacity of ceil(100 / 8 * 1.25) = 16 would drop most of the
    busy expert's rows."""
    from jax.experimental.pallas import tpu as pltpu

    from determined_tpu.ops import moe

    x, params, dims = _skewed_layer()
    want = ADAPTER.expert_layer(x, params, dims)[0] - _shared(x, params)
    valid = jnp.arange(x.shape[0]) < 90
    # One jitted call: an eager op dispatched while the interpreter's
    # callbacks of the kernel before it are in flight can deadlock.
    with pltpu.force_tpu_interpret_mode():
        got, load = jax.jit(functools.partial(
            moe.dropless_moe, top_k=2, routed_scaling_factor=1.8,
            impl=impl))(x, params, valid=valid)
    np.testing.assert_allclose(got, want, atol=2e-5)
    load = np.asarray(load)
    assert load.sum() == 2 * 90 and load[3] > 80
    assert (load[[1, 4, 6]] == 0).all()


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(impl):
    """The share test: the parts that `experts_held` = (0,2), (2,2),
    (4,2), (6,2) give — each routes over all 8 experts and computes its
    own two —, with the shared expert counted once, add up to the uncut
    reference's whole layer."""
    from jax.experimental.pallas import tpu as pltpu

    from determined_tpu.ops import moe

    x, params, dims = _skewed_layer(seed=1)
    total = _shared(x, params)
    for first in (0, 2, 4, 6):
        share = dict(params, w13=params["w13"][first:first + 2],
                     w2=params["w2"][first:first + 2])
        with pltpu.force_tpu_interpret_mode():     # jitted: see above
            part, load = jax.jit(functools.partial(
                moe.dropless_moe, top_k=2, routed_scaling_factor=1.8,
                experts_held=(first, 2), impl=impl))(x, share)
        assert int(load.sum()) == 2 * x.shape[0]   # routed over all 8
        held = dict(dims, held_first=first, held=2)
        np.testing.assert_allclose(       # the reference given the share
            part, ADAPTER.expert_layer(x, share, held)[0] - _shared(x, share),
            atol=2e-5)
        total = total + part
    np.testing.assert_allclose(
        total, ADAPTER.expert_layer(x, params, dims)[0], atol=5e-5)


# --------------------------------------------- (b), (e) the latent kernel


def _latent_case(seed, slots=4, heads=4, rank=128, rope=8, bs=16, mb=20):
    from determined_tpu.ops import mla_attention as mla

    rng = np.random.default_rng(seed)
    row = mla.latent_row(rank, rope)
    blocks = slots * mb
    pool = np.zeros((2, blocks + 1, bs, row), np.float32)
    pool[..., :rank + rope] = rng.normal(size=(2, blocks + 1, bs,
                                               rank + rope))
    tables = rng.permutation(blocks).reshape(slots, mb).astype(np.int32)
    q_lat = rng.normal(size=(slots, heads, rank)).astype(np.float32)
    q_rope = rng.normal(size=(slots, heads, rope)).astype(np.float32)
    return (mla, jnp.asarray(pool), tables, jnp.asarray(q_lat),
            jnp.asarray(q_rope), row)


@pytest.mark.parametrize("positions,mb", [
    ((0, 15, 127, 128), 20), ((300, 129, 255, 17), 20), ((5, 0, 0, 319), 20),
    ((511, 512, 1023, 1535), 100), ((1599, 0, 40, 1024), 100)],
    ids=["span-edges", "long", "idle-lanes", "512-span-edges",
         "short-beside-long"])
def test_latent_kernel_agrees_with_its_reference(positions, mb):
    """The Pallas kernel under the TPU interpreter (uninitialised memory
    is NaN there, semaphores simulated) against the jnp twin: positions
    at block and span edges, lanes of several spans, idle lanes (table all
    trash) between live ones, which write zeros. A table of 20 blocks is
    one span of 320 tokens (the span is capped at the table); one of 100
    is three spans of 512 tokens and 4 blocks, padded to whole spans with
    the trash block, with a lane shorter than a span beside long ones.
    One jitted call: an eager op dispatched while the interpreter's
    callbacks are in flight can deadlock."""
    from jax.experimental.pallas import tpu as pltpu

    mla, pool, tables, q_lat, q_rope, row = _latent_case(1, mb=mb)
    assert mla.latent_span_tokens(16, mb) == min(mb * 16, 512)
    trash = pool.shape[1] - 1
    positions = np.array(positions, np.int32)
    idle = (positions == 0) & (np.arange(4) > 0)
    tables = np.where(idle[:, None], trash, tables).astype(np.int32)
    q = mla.absorbed_query(q_lat, q_rope, row)
    args = (q, pool, jnp.int32(1), jnp.asarray(tables),
            jnp.asarray(positions))
    want = np.asarray(mla.mla_attention_reference(*args, 128, 0.25))
    kernel = jax.jit(functools.partial(
        mla.mla_attention_pallas, rank=128, scale=0.25,
        interpret=pltpu.InterpretParams()))
    got = np.asarray(kernel(*args))
    assert not got[idle].any()
    np.testing.assert_allclose(got[~idle], want[~idle], atol=2e-5)


def test_the_latent_and_kv_kernels_keep_their_own_spans():
    """The latent kernel folds 512 tokens a span (one pool of 1,280 B a
    token), capped at the lane's table; the K/V kernel keeps its 128
    (two pools): each read from its own shapes."""
    from determined_tpu.ops.mla_attention import latent_span_tokens
    from determined_tpu.ops.paged_attention import span_tokens

    assert latent_span_tokens(16, 256) == 512
    assert latent_span_tokens(16, 20) == 320
    assert latent_span_tokens(32, 256) == 512
    assert span_tokens(16, 256) == 128


def test_absorbed_decode_is_plain_attention_on_the_same_latents():
    """`q_lat = W_uk^T q_nope` against the latents, then `W_uv`, is plain
    attention with every head's keys `[W_uk c | k_rope]` and values `W_uv
    c` expanded from the same latents."""
    mla, pool, tables, _, q_rope, row = _latent_case(2, rank=32)
    rng = np.random.default_rng(3)
    slots, heads, rank, rope, nope, vd = 4, 4, 32, 8, 16, 16
    q_nope = jnp.asarray(rng.normal(size=(slots, heads, nope)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(rank, heads, nope)) * 0.3,
                       jnp.float32)
    w_uv = jnp.asarray(rng.normal(size=(rank, heads, vd)) * 0.3, jnp.float32)
    positions = np.array([40, 7, 300, 129], np.int32)
    scale = (nope + rope) ** -0.5
    o_lat = mla.mla_attention_reference(
        mla.absorbed_query(jnp.einsum("shn,chn->shc", q_nope, w_uk), q_rope,
                           row),
        pool, jnp.int32(0), jnp.asarray(tables), jnp.asarray(positions),
        rank, scale)
    got = jnp.einsum("shc,chv->shv", o_lat, w_uv)
    lane = np.asarray(pool[0])[tables].reshape(slots, -1, row)
    c, k_rope = lane[..., :rank], lane[..., rank:rank + rope]
    k = np.concatenate([
        np.einsum("smc,chn->smhn", c, w_uk),
        np.broadcast_to(k_rope[:, :, None], (*k_rope.shape[:2], heads,
                                             rope))], -1)
    v = np.einsum("smc,chv->smhv", c, w_uv)
    q = np.concatenate([q_nope, q_rope], -1)
    scores = np.einsum("shd,smhd->shm", q, k) * scale
    scores = np.where(np.arange(lane.shape[1])[None, None]
                      <= positions[:, None, None], scores, -np.inf)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(scores), axis=-1))
    np.testing.assert_allclose(got, np.einsum("shm,smhv->shv", probs, v),
                               atol=2e-5)


# ------------------------------------- (a) through the engine and batcher


def _replica(config, params, prefix_cache=True, **engine_kwargs):
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


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_sessions_through_the_batcher_give_the_references_logits(impl):
    """Two rounds of requests over four lanes, float32 throughout: in the
    first every prompt is prefilled whole; in the second three prompts
    open with a first-round prompt's 32-token document (a prefix hit:
    `cached_len` 32, the novel tokens attend to cached latents and to
    themselves) and one IS the two-block document of a request that is
    still decoding (a full hit: its last token is recomputed into a block
    still shared — a copy-on-write). Each prefill's logits and each of the >= 24 decode
    steps', lane by lane, are the reference's one forward pass over prompt
    + reply; with `pallas` both kernels run interpreted in the compiled
    calls. The counters count what happened."""
    from jax.experimental.pallas import tpu as pltpu

    from determined_tpu.serve.scheduler import Request

    config = _float32(TINY, attention_impl=impl, kv_num_blocks=24)
    if impl == "pallas":     # widths the kernels take: whole 128-lane rows
        config.update(hidden_size=128, moe_intermediate_size=128,
                      kv_lora_rank=128)
    params, dims = float_params(config, seed=1, scale=6.0)
    with pltpu.force_tpu_interpret_mode():
        engine, batcher = _replica(config, params)
        batcher.start()
    seen = _Logits(engine)
    rng = np.random.default_rng(4)

    def ids(n):
        return rng.integers(0, dims["vocab_size"], n, np.int32)

    docs = [ids(32) for _ in range(3)]
    first = [np.concatenate([docs[0], ids(5)]),
             np.concatenate([docs[1], ids(9)]),
             docs[2],
             ids(11)]
    second = [np.concatenate([docs[0], ids(7)]),
              np.concatenate([docs[1], ids(3)]),
              docs[0].copy(),                      # the full hit
              np.concatenate([docs[0], ids(12)])]
    replies = [26, 5, 7, 4, 6, 24, 5, 8]
    requests = []
    try:
        for prompts, lo in ((first, 0), (second, 4)):
            batch = [Request(p, max_new_tokens=n, temperature=0.0)
                     for p, n in zip(prompts, replies[lo:lo + 4])]
            requests += batch
            for req in batch:
                batcher.submit(req)
            if lo == 0:       # the long reply holds docs[0] shared
                for req in batch[1:]:
                    req.result(timeout=300)
        for req in requests:
            req.result(timeout=300)
    finally:
        batcher.stop()
    assert [r.cached_len for r in requests[4:]] == [32, 32, 31, 32]
    width = max(len(r.tokens) + len(r.out_tokens) for r in requests)
    tokens = np.zeros((len(requests), width), np.int32)
    for r, req in enumerate(requests):
        seq = np.concatenate([req.tokens, req.out_tokens])
        tokens[r, :len(seq)] = seq
    gather = np.broadcast_to(np.arange(width, dtype=np.int32), tokens.shape)
    ref = np.asarray(ADAPTER.logits(params, tokens, gather, dims))
    assert np.std(ref) > 0.05
    rows = {}
    for r, req in enumerate(requests):
        rows.setdefault(tuple(req.tokens.tolist()), []).append(r)
    owner, compared = {}, 0
    for event in seen.events:
        if event[0] == "prefill":
            _, slot, prompt, logits, first_token = event
            owner[slot] = rows[prompt].pop(0)
            np.testing.assert_allclose(
                logits, ref[owner[slot], len(prompt) - 1], atol=3e-4)
            assert first_token == requests[owner[slot]].out_tokens[0]
            compared += 1
        else:
            _, positions, logits = event
            for slot in np.flatnonzero(positions > 0):
                np.testing.assert_allclose(
                    logits[slot], ref[owner[slot], positions[slot]],
                    atol=3e-4)
                compared += 1
    assert compared == sum(replies)
    stats = engine.stats()
    assert stats["block_copies"] == 1
    assert stats["prefix_hit_tokens"] == 32 + 32 + 31 + 32
    novel = sum(len(r.tokens) for r in requests) - stats["prefix_hit_tokens"]
    assert stats["prefix_novel_tokens"] == novel
    moe_layers, k = dims["n_layer"] - dims["dense_layers"], dims["top_k"]
    decoded = sum(replies) - len(replies)
    assert stats["moe_assignments"] == (novel + decoded) * moe_layers * k
    # the experts' load, kept on the device and fetched here alone
    assert stats["moe_expert_tokens_mean"] * dims["experts"] * moe_layers \
        == pytest.approx(stats["moe_assignments"])
    assert stats["moe_expert_tokens_max"] >= stats["moe_expert_tokens_mean"]
    row = 128 if impl == "reference" else 256    # rank + 8, in whole 128s
    assert stats["latent_hbm_bytes"] == dims["n_layer"] * 25 * 16 * row * 4
    assert stats["cache_hbm_bytes"] == stats["latent_hbm_bytes"] \
        + moe_layers * dims["experts"] * 4
    assert stats["state_hbm_bytes"] == 0
    assert stats["first_token_host_bytes"] == 4 * len(requests)


def test_first_token_is_sampled_in_the_prefill_call():
    """The engine's one first-token path in this family too."""
    from tests.test_serving import check_first_tokens

    config = _float32(TINY)
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


def test_engine_counts_the_latent_kernels_spans():
    """`decode_spans_live` counts the latent kernel's own 512-token spans:
    a lane writing at 510..513 counts 1, 1, 2, 2 (it crosses position
    512), a short lane 1 a step, an idle slot nothing."""
    config = _float32(TINY)
    config["serve"].update(max_batch_size=3, max_seq_len=1024,
                           kv_num_blocks=192, prefill_buckets=[16, 512])
    params, dims = float_params(config, seed=2)
    engine, _ = _replica(config, params)
    assert engine.family.decode_span_tokens(16, 64) == 512
    rng = np.random.default_rng(8)
    position = {0: 510, 2: 5}       # slot 1 stays idle
    last = {slot: engine.prefill_request(
        slot, rng.integers(0, dims["vocab_size"], n, np.int32))
        for slot, n in position.items()}
    assert engine.stats()["decode_spans_live"] == 0
    for _ in range(4):
        tokens, positions = np.zeros(3, np.int32), np.zeros(3, np.int32)
        for slot in position:
            tokens[slot], positions[slot] = last[slot], position[slot]
        out = engine.decode(tokens, positions, np.zeros(3, np.float32))
        for slot in position:
            last[slot], position[slot] = int(out[slot]), position[slot] + 1
    stats = engine.stats()
    assert stats["decode_spans_live"] == (1 + 1 + 2 + 2) + 4
    assert stats["decode_spans_grid"] == stats["decode_spans_live"]


def test_the_family_allows_sharing_and_refuses_adapters():
    """Prefix cache and copy-on-write are allowed (a latent block is a
    function of the tokens before it); adapters are refused with the
    reason; a switch of the published config set the other way raises."""
    from determined_tpu.serve.task import build_model

    params, _ = float_params(TINY)
    serving = ADAPTER.serving(TINY, TINY["serve"])
    engine, batcher = _replica(TINY, params)
    assert batcher.blocks.prefix_cache and engine.family.copy_block
    assert engine.max_seq_len == 64          # no position table clips it
    with pytest.raises(ValueError, match="adapters.*head is untied"):
        _replica(TINY, params, adapters={"tuned": params})
    with pytest.raises(ValueError, match="falcon_h1, glm4_moe_lite, gpt2"):
        build_model({"model": "mamba"})
    for key, other in (("topk_method", "greedy"), ("n_group", 2),
                       ("num_key_value_heads", 2)):
        with pytest.raises(ValueError, match=key):
            build_model(dict(serving, model_config=dict(
                serving["model_config"], **{key: other})))


def test_the_adapters_draw_is_the_tree_the_program_serves():
    """Serving hands the engine the adapter's leaves as drawn: every leaf
    bfloat16, nothing for the engine to narrow, the same draw from the
    same key, a share's experts the whole model's; and the published
    configuration's work counts and bytes as PERF.md states them."""
    from determined_tpu.serve import glm4_moe_lite as family
    from determined_tpu.serve.task import build_model

    dims = ADAPTER.dims(TINY)
    cfg = build_model(ADAPTER.serving(TINY, TINY["serve"]))
    assert cfg.family == "glm4_moe_lite" and cfg.held == (0, 8)
    ours = ADAPTER.init_params(jax.random.PRNGKey(7), dims)
    again = ADAPTER.init_params(jax.random.PRNGKey(7), dims)
    resident = family.resident_params(ours, cfg)
    for a, b, c in zip(jax.tree.leaves(ours), jax.tree.leaves(again),
                       jax.tree.leaves(resident)):
        assert a.dtype == jnp.bfloat16 and bool(jnp.all(a == b))
        assert c is a
    assert float(jnp.abs(ours["moe"]["router_bias"]).max()) > 0
    share = ADAPTER.init_params(jax.random.PRNGKey(7), ADAPTER.dims(
        dict(TINY, experts_held=[2, 4])))
    assert share["moe"]["w13"].shape[1] == 4
    assert bool(jnp.all(share["moe"]["w13"] == ours["moe"]["w13"][:, 2:6]))
    published = ADAPTER.dims(PUBLISHED)
    work = ADAPTER.work(published)
    attention = 21_757_952               # PERF.md §4: 21.76 M a layer
    assert ADAPTER.attention_params(published) == attention
    assert work["params_per_token"] == (
        8 * attention + 3 * 2048 * 10240
        + 7 * (2048 * 64 + 5 * 3 * 2048 * 1536) + 154880 * 2048)
    assert (work["mla_layers"], work["mla_heads"], work["mla_rank"],
            work["mla_rope"]) == (8, 20, 512, 64)
    assert (work["moe_layers"], work["moe_experts"], work["moe_top_k"],
            work["moe_d_model"], work["moe_width"]) == (7, 64, 4, 2048, 1536)
    full = build_model(ADAPTER.serving(PUBLISHED, PUBLISHED["serve"]))
    assert family.latent_bytes(full, 14337, 16) == 8 * 14337 * 16 * 640 * 2
    assert family.kernel_refusal(full) is None
    resident_bytes = sum(
        np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(
            jax.eval_shape(lambda k: ADAPTER.init_params(k, published),
                           jax.random.PRNGKey(0))))
    assert 10.30e9 < resident_bytes < 10.36e9


@pytest.mark.parametrize("reader,kernel,seconds", [
    ("mla_attn_roofline", "mla_decode_attention_bf16_64_20_512_", 0.004),
    ("moe_experts_roofline", "moe_grouped_matmul_bf16_256_3072_", 0.03)])
def test_a_kernels_roofline_reads_its_custom_call_by_name(reader, kernel,
                                                          seconds):
    """The two readers over a hand-made traced window at the published
    sizes — one decode call of 64 lanes at 2,750 tokens each and one
    prefill of 100 novel tokens: the share is the floor of the counted
    work over the named kernel's seconds (another kernel's are not
    counted), and a trace without the kernel reads nothing."""
    import importlib

    from benchmarks import kernel_work, mla_work, moe_work

    read = importlib.import_module(f"benchmarks.metrics.{reader}").read
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"cell": {"model": ADAPTER, "config": PUBLISHED}, "peak": peak,
           "traced": {"t_open": 0.0, "t_close": 3.0},
           "calls": [("decode", 1.0, 1.02, 64, 64 * 2750, 64),
                     ("prefill", 1.03, 1.04, 100, 0, 1),
                     ("decode", 4.0, 4.02, 64, 64 * 2750, 64)],
           "trace": {"kernels": {kernel: seconds, "another_call": 1.0}}}
    if reader == "mla_attn_roofline":
        floor = 8 * kernel_work.floor_seconds(mla_work.mla_decode_work(
            64, 64 * 2750, 20, 512, 64), peak)
        # the bytes bound it: 176,000 tokens x 576 numbers x 2 B a layer
        assert floor == pytest.approx(8 * 176000 * 1152 / 819e9, rel=0.02)
    else:
        floor = 7 * sum(kernel_work.floor_seconds(moe_work.moe_experts_work(
            n, 4, 64, 2048, 1536), peak) for n in (64, 100))
        # two calls, each reading all 64 experts' 9.437 M numbers once
        assert floor == pytest.approx(
            7 * 2 * 64 * 9437184 * 2 / 819e9, rel=0.02)
    assert read(run) == pytest.approx(100.0 * floor / seconds)
    assert 0 < read(run) < 100
    assert read(dict(run, trace={"kernels": {"another_call": 1.0}})) is None
    assert read(dict(run, trace=None)) is None
