"""GLM-4-MoE-Lite served: latent attention (MLA) over **one pool addressed
through the block table that is neither K nor V**, and a dropless sparse
expert layer with a shared expert.

The block (keys are the published config's; `models/glm4_moe_lite.Config`;
DeepSeek-V3's equations, arXiv:2412.19437 §2.1), `h` the residual stream:

    u = RMSNorm(h; input_norm)
    q = q_b(RMSNorm(q_a(u); q_a_norm))            [H, nope + rope] a token
    [c | k_rope] = kv_a(u);  c <- RMSNorm(c; kv_a_norm)   [rank], [rope]
    q_rope, k_rope: rotary positions (rotate_half, theta = rope_theta),
       k_rope one key for every head
    per head: k = [W_uk c | k_rope], v = W_uv c   (kv_b = [W_uk | W_uv])
    causal softmax at (nope + rope)^-1/2, o
    h <- h + attention
    v = RMSNorm(h; post_norm)
    the first `first_k_dense_replace` layers: h <- h + down(up(v) * SiLU(gate(v)))
    the others: scores = sigmoid(router(v)) over all routed experts, the
       `num_experts_per_tok` largest of scores + router_bias chosen,
       their scores normalised and times `routed_scaling_factor`;
       h <- h + sum_chosen w_e SwiGLU_e(v) + SwiGLU_shared(v)
    logits = lm_head(RMSNorm(h; final_norm)), the head untied.

The parameters (a checkpoint's tree; the published one is bfloat16), the
layers of a kind stacked along a leading axis for `lax.scan`:

    embed, lm_head [V, d]; final_norm [d];
    dense (the leading layers) and moe (the rest), each with the
       attention's input_norm, post_norm [n, d]; q_a [n, d, q_lora],
       q_a_norm [n, q_lora], q_b [n, q_lora, H (nope + rope)]; kv_a [n, d,
       rank + rope], kv_a_norm [n, rank], kv_b [n, rank, H (nope + v)];
       o [n, H v, d];
    dense: gate, up [n, d, F], down [n, F, d];
    moe: router [n, d, E], router_bias [n, E]; w13 [n, held, d, 2f] (an
       expert's gate and up side by side), w2 [n, held, f, d] — `held`
       the experts this chip holds (`experts_held`, all E here);
       shared_gate, shared_up [n, d, fs], shared_down [n, fs, d].

The cache (`init_cache`) is a pytree of

    latent    [L, pool_blocks, block_size, row]   paged, by block table:
              a token's `[c | k_rope | 0]` (`ops/mla_attention.py
              latent_row`: 512 + 64 numbers in a row of 640 lanes), 1,280 B
              a token a layer where per-head K and V would be 20,480 B;
    moe_load  [moe layers, E] int32   assignments each expert has drawn,
              added to inside the steps and fetched only when
              `engine.stats()` is asked (`cache_counters`).

A latent block is a function of the tokens before it, as a K/V block is:
**prefix sharing and copy-on-write apply** (`copy_block`), and prefill
takes `prefix_len` — the novel tokens attend to the cached latents and to
themselves.

**Two forms of one attention.** Prefill is the plain form: it writes the
novel tokens' rows, gathers the lane's latents, expands every head's K and
V from them (`kv_b`), and attends a block of queries at a time (no
`[H, T, T]` matrix). The decode step is the absorbed form: `W_uk` is
folded into the query and `W_uv` applied after, so the kernel
(`ops/mla_attention.py`) reads each cached latent once for all heads.
Both agree with the plain reference (tests/test_glm4_moe_lite.py).

Not loaded: the multi-token-prediction module (`num_nextn_predict_layers`);
plain decoding leaves it out. Adapters are refused (an adapter swaps a tied
embedding table; this head is untied).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from determined_tpu.models.glm4_moe_lite import Config
from determined_tpu.ops import mla_attention, moe
from determined_tpu.ops.norm_rope import rms_norm, rotary
from determined_tpu.serve.model import narrowed, sample  # noqa: F401

# What the engine asks a family (serve/engine.py `family_of`).
RECURRENT_STATE = False      # latent blocks are shared as K/V blocks are
config_from = Config.from_published
decode_span_tokens = mla_attention.latent_span_tokens


def position_limit(cfg: Config) -> Optional[int]:
    """Rotary positions need no table: nothing clips `max_seq_len`."""
    return None


def adapter_refusal(cfg: Config) -> Optional[str]:
    return ("this family has no adapter arm (an adapter swaps the tied "
            "embedding table under one shared cache; this family's head "
            "is untied)")


def kernel_refusal(cfg: Config) -> Optional[str]:
    """Why `pallas` cannot serve this geometry (either kernel), or None."""
    return mla_attention.kernel_refusal(
        cfg.kv_lora_rank, cfg.qk_rope_head_dim) \
        or moe.kernel_refusal(cfg.hidden_size, cfg.moe_intermediate_size)


def assignments_per_token(cfg: Config) -> int:
    """Expert assignments a token's forward makes, over all layers."""
    return cfg.moe_layers * cfg.num_experts_per_tok


_NORMS = ("input_norm", "post_norm", "q_a_norm", "kv_a_norm", "router_bias")


def resident_params(params: Dict[str, Any], cfg: Config) -> Dict[str, Any]:
    """The tree the steps are called with: every matrix narrowed to
    `cfg.dtype` where a checkpoint has it wider (the published one is
    bfloat16 throughout: nothing to do); norms and the router's bias are
    read in float32 and stay."""
    def narrow(x):
        return narrowed(x, cfg.dtype)

    out = dict(params, embed=narrow(params["embed"]),
               lm_head=narrow(params["lm_head"]))
    for kind in ("dense", "moe"):
        out[kind] = {name: leaf if name in _NORMS else narrow(leaf)
                     for name, leaf in params[kind].items()}
    return out


# ---------------------------------------------------------------- cache


def _cache_shapes(cfg: Config, pool_blocks: int, block_size: int):
    row = mla_attention.latent_row(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    return {
        "latent": ((cfg.num_hidden_layers, pool_blocks, block_size, row),
                   cfg.dtype),
        "moe_load": ((cfg.moe_layers, cfg.n_routed_experts), jnp.int32),
    }


def init_cache(cfg: Config, pool_blocks: int, block_size: int,
               slots: int) -> Dict[str, jax.Array]:
    """Zeroed pools; `pool_blocks` includes the trailing trash block."""
    del slots                # nothing here is indexed by lane
    return {name: jnp.zeros(shape, dtype) for name, (shape, dtype) in
            _cache_shapes(cfg, pool_blocks, block_size).items()}


def _bytes(shape_dtype) -> int:
    shape, dtype = shape_dtype
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def latent_bytes(cfg: Config, pool_blocks: int, block_size: int) -> int:
    """HBM of the latent pool as allocated, the row's padding with it."""
    return _bytes(_cache_shapes(cfg, pool_blocks, block_size)["latent"])


def cache_bytes(cfg: Config, pool_blocks: int, block_size: int,
                slots: int) -> int:
    del slots
    return sum(_bytes(sd) for sd in
               _cache_shapes(cfg, pool_blocks, block_size).values())


def state_bytes(cfg: Config, slots: int) -> int:
    return 0


def cache_counters(cfg: Config, cache, pool_blocks: int,
                   block_size: int) -> Optional[Dict[str, Any]]:
    """What `engine.stats()` adds for this family; the experts' load is
    fetched here and nowhere else. None where the batcher donated the
    cache to a call under the reader (it asks again later)."""
    try:
        load = np.asarray(cache["moe_load"])
    except RuntimeError:
        return None
    return {"latent_hbm_bytes": latent_bytes(cfg, pool_blocks, block_size),
            "moe_expert_tokens_max": int(load.max(initial=0)),
            "moe_expert_tokens_mean": float(load.mean()) if load.size else 0.0}


def copy_block(cache: Dict[str, jax.Array], dst: jax.Array,
               src: jax.Array) -> Dict[str, jax.Array]:
    """Copy-on-write: pool block `src` into `dst` across every layer."""
    return dict(cache, latent=cache["latent"].at[:, dst].set(
        cache["latent"][:, src]))


# ----------------------------------------------------------------- block


def _matmul(x, w, cfg: Config):
    return jnp.einsum("td,de->te", x, w.astype(cfg.dtype))


def _query_and_latent(u, lp, positions, cfg: Config):
    """u [T, d] at `positions` → q_nope [T, H, nope], q_rope [T, H, rope]
    (rotated), and the tokens' pool rows [T, row] = [c | k_rope | 0]."""
    t = u.shape[0]
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    q = _matmul(rms_norm(_matmul(u, lp["q_a"], cfg), lp["q_a_norm"],
                          cfg.rms_norm_eps), lp["q_b"], cfg)
    q = q.reshape(t, heads, cfg.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    ckv = _matmul(u, lp["kv_a"], cfg)
    c = rms_norm(ckv[:, :rank], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_rope = rotary(ckv[:, None, rank:], positions, cfg.rope_theta)[:, 0]
    row = mla_attention.latent_row(rank, cfg.qk_rope_head_dim)
    rows = jnp.pad(jnp.concatenate([c, k_rope], axis=-1),
                   ((0, 0), (0, row - cfg.latent_dim)))
    return q_nope, rotary(q_rope, positions, cfg.rope_theta), rows


def _up_projections(lp, cfg: Config):
    """kv_b [rank, H (nope + v)] → W_uk [rank, H, nope], W_uv [rank, H, v]."""
    w = lp["kv_b"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _swiglu(v, gate, up, down, cfg: Config):
    act = jax.nn.silu(_matmul(v, gate, cfg).astype(jnp.float32)) \
        * _matmul(v, up, cfg).astype(jnp.float32)
    return _matmul(act.astype(cfg.dtype), down, cfg)


def _dense_mlp(h, lp, cfg: Config):
    v = rms_norm(h, lp["post_norm"], cfg.rms_norm_eps)
    return _swiglu(v, lp["gate"], lp["up"], lp["down"], cfg)


def _expert_mlp(h, lp, experts, layer, valid, cfg: Config, impl: str):
    """→ (the layer's result [T, d] in cfg.dtype, assignments per expert
    [E] int32 of the `valid` tokens): the routed experts held here —
    `experts` is every expert layer's `w13` and `w2`, whole, and `layer`
    this one's index among them — and the shared expert once."""
    v = rms_norm(h, lp["post_norm"], cfg.rms_norm_eps)
    routed, load = moe.dropless_moe(
        v, dict(lp, **experts), layer=layer,
        top_k=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, experts_held=cfg.held,
        valid=valid, impl=impl)
    shared = _swiglu(v, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"], cfg)
    return (routed + shared.astype(jnp.float32)).astype(cfg.dtype), load


def _logits(params, h, cfg: Config):
    """h [T, d] → logits [T, V] float32."""
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("td,vd->tv", h, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def _scan_layers(attend, params, h, cache, valid, cfg: Config, impl: str):
    """Both kinds of layer in order, every pool in the carry:
    `attend(u, lp, pool, layer) -> (pool', attention's result [T, d])`.
    The routed experts' stacks are not scanned over: the grouped matmul
    takes them whole with the layer's index, as the attention kernel
    takes the pool (a scan's slice of them would be a copy of a layer's
    experts in every call)."""
    dense_n = cfg.first_k_dense_replace
    experts = {name: params["moe"][name] for name in ("w13", "w2")}
    scanned = {name: leaf for name, leaf in params["moe"].items()
               if name not in experts}

    def block(carry, layer_in, mlp):
        h, pool = carry
        lp, layer = layer_in
        u = rms_norm(h, lp["input_norm"], cfg.rms_norm_eps)
        pool, attn = attend(u, lp, pool, layer)
        h = h + attn.astype(cfg.dtype)
        return mlp(h, pool, lp, layer)

    def dense(h, pool, lp, layer):
        return (h + _dense_mlp(h, lp, cfg), pool), None

    def sparse(h, pool, lp, layer):
        out, load = _expert_mlp(h, lp, experts, layer - dense_n, valid, cfg,
                                impl)
        pool = dict(pool, moe_load=pool["moe_load"].at[
            layer - dense_n].add(load))
        return (h + out, pool), None

    carry, _ = jax.lax.scan(
        lambda c, x: block(c, x, dense), (h, cache),
        (params["dense"], jnp.arange(dense_n)))
    carry, _ = jax.lax.scan(
        lambda c, x: block(c, x, sparse), carry,
        (scanned, jnp.arange(dense_n, cfg.num_hidden_layers)))
    return carry


# ------------------------------------------------------------------ steps


def _query_block(s: int) -> int:
    """Queries attended at a time: the largest divisor of the bucket under
    512 (a `[H, 512, context]` float32 tile, never `[H, T, T]`)."""
    return next(b for b in range(min(512, s), 0, -1) if s % b == 0)


def prefill(
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    tokens: jax.Array,       # [bucket] int32: the NOVEL SUFFIX, right-padded
    suffix_len: jax.Array,   # scalar int32: its real length (<= bucket)
    prefix_len: jax.Array,   # scalar int32: tokens whose latents are cached
    block_table: jax.Array,  # [max_blocks] int32: the sequence's table
    cfg: Config,
    rules=None,
    attention_impl: str = "reference",
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Prefill the suffix of a prompt whose first `prefix_len` tokens'
    latents already sit in `block_table`'s blocks → (cache', the last real
    position's logits [V] float32). The plain form of the attention."""
    del rules
    s = tokens.shape[0]
    mb = block_table.shape[0]
    bs = cache["latent"].shape[2]
    trash = cache["latent"].shape[1] - 1
    dt_ = cfg.dtype
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    pos = prefix_len + jnp.arange(s)
    real = jnp.arange(s) < suffix_len
    dest_blk = jnp.where(real, block_table[jnp.minimum(pos // bs, mb - 1)],
                         trash)
    dest_off = pos % bs
    qb = _query_block(s)
    keys_at = jnp.arange(mb * bs)
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)

    def attend(u, lp, pool, layer):
        q_nope, q_rope, rows = _query_and_latent(u, lp, pos, cfg)
        pool = dict(pool, latent=pool["latent"].at[
            layer, dest_blk, dest_off].set(rows.astype(pool["latent"].dtype)))
        # the lane's latents, cached prefix and the suffix just written
        lane = pool["latent"][layer, block_table].reshape(mb * bs, -1)
        w_uk, w_uv = _up_projections(lp, cfg)
        k_nope = jnp.einsum("mc,chn->mhn", lane[:, :rank], w_uk)
        v = jnp.einsum("mc,chv->mhv", lane[:, :rank], w_uv)
        k_rope = lane[:, rank:cfg.latent_dim]

        def block(args):
            qn, qr, at = args          # [qb, H, nope], [qb, H, rope], [qb]
            scores = (jnp.einsum("qhn,mhn->hqm", qn, k_nope,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("qhr,mr->hqm", qr, k_rope,
                                   preferred_element_type=jnp.float32))
            scores = jnp.where(keys_at[None, None] <= at[None, :, None],
                               scores * scale, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt_)
            return jnp.einsum("hqm,mhv->qhv", probs, v)

        split = (s // qb, qb)
        ctx = jax.lax.map(block, (
            q_nope.reshape(*split, heads, -1),
            q_rope.reshape(*split, heads, -1), pos.reshape(split)))
        return pool, _matmul(ctx.reshape(s, heads * cfg.v_head_dim),
                             lp["o"], cfg)

    h = params["embed"][tokens].astype(dt_)
    h, cache = _scan_layers(attend, params, h, cache, real, cfg,
                            attention_impl)
    last = jax.lax.dynamic_slice_in_dim(
        h, jnp.maximum(suffix_len - 1, 0), 1, axis=0)
    return cache, _logits(params, last, cfg)[0]


def decode_step(
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    tokens: jax.Array,        # [slots] int32: last emitted token per slot
    positions: jax.Array,     # [slots] int32: index this step writes at
    block_tables: jax.Array,  # [slots, max_blocks] int32
    cfg: Config,
    rules=None,
    attention_impl: str = "reference",
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """One decode step for every slot → (cache', logits [slots, V]): the
    absorbed form of the attention. `attention_impl` names the path of
    both kernels."""
    del rules
    slots = tokens.shape[0]
    bs = cache["latent"].shape[2]
    mb = block_tables.shape[1]
    trash = cache["latent"].shape[1] - 1
    rank = cfg.kv_lora_rank
    live = block_tables[:, 0] != trash
    wblk = jnp.take_along_axis(
        block_tables, jnp.minimum(positions // bs, mb - 1)[:, None],
        axis=1)[:, 0]
    woff = positions % bs
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)

    def attend(u, lp, pool, layer):
        q_nope, q_rope, rows = _query_and_latent(u, lp, positions, cfg)
        latent = pool["latent"].at[layer, wblk, woff].set(
            rows.astype(pool["latent"].dtype))
        w_uk, w_uv = _up_projections(lp, cfg)
        q_lat = jnp.einsum("shn,chn->shc", q_nope, w_uk)
        o_lat = mla_attention.mla_paged_decode(
            mla_attention.absorbed_query(q_lat, q_rope, rows.shape[-1]),
            latent, layer, block_tables, positions, rank, scale,
            impl=attention_impl)
        ctx = jnp.einsum("shc,chv->shv", o_lat, w_uv)
        return dict(pool, latent=latent), _matmul(
            ctx.reshape(slots, -1), lp["o"], cfg)

    h = params["embed"][tokens].astype(cfg.dtype)
    h, cache = _scan_layers(attend, params, h, cache, live, cfg,
                            attention_impl)
    return cache, _logits(params, h, cfg)
