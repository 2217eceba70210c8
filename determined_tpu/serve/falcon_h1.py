"""Falcon-H1 served: prefill and the decode step of a parallel-hybrid block,
with **two kinds of state side by side** in one cache.

The block (keys are the published config's; `models/falcon_h1.Config`),
with `h` the residual stream:

    h0 = embed[token] * embedding_multiplier
    u  = RMSNorm(h; input_norm)
    attention on u * attention_in_multiplier: q, k, v (grouped: H query
       heads over Hkv K/V heads), k * key_multiplier, rotary positions
       (rotate_half, theta = rope_theta, no table), causal softmax at
       1/sqrt(Dh), o, * attention_out_multiplier
    mixer on u * ssm_in_multiplier: in_proj -> [z | x | B | C | dt], each
       section times its ssm_multipliers entry; depthwise causal
       convolution (width mamba_d_conv, bias) over [x|B|C], SiLU;
       dt = softplus(dt + dt_bias), A = -exp(A_log); the recurrence
       S_t = exp(dt A) S_{t-1} + dt x (x) B, y = S C + D x
       (ops/ssm_state.py); y * SiLU(z), RMSNorm over each group,
       out_proj, * ssm_out_multiplier
    h <- h + mixer + attention
    h <- h + down(up(v) * SiLU(gate(v) * mlp_multipliers[0]))
             * mlp_multipliers[1],           v = RMSNorm(h; pre_ff_norm)
    logits = lm_head(RMSNorm(h; final_norm)) * lm_head_multiplier

The parameters (a checkpoint's tree; the published one is bfloat16
throughout), the layers stacked along a leading axis L for `lax.scan`:

    embed, lm_head [V, d]; final_norm [d];
    blocks: input_norm, pre_ff_norm [L, d]; qkv [L, d, (H + 2 Hkv) Dh],
       o [L, H Dh, d]; in_proj [L, d, z+x+B+C+dt], conv_w [L, width,
       x+B+C], conv_b [L, x+B+C], A_log, dt_bias, D [L, heads],
       mixer_norm [L, d_ssm], out_proj [L, d_ssm, d]; gate, up [L, d, f],
       down [L, f, d]

The cache is a pytree of four pools (`init_cache`):

    k, v   [L, pool_blocks, block_size, Hkv*Dh]   paged, by block table,
           as serve/model.py's (the last block is the trash block);
    conv   [L, slots, mamba_d_conv - 1, channels]  the convolution's
           tail: the last inputs of [x|B|C] before the convolution;
    ssm    [L, slots, heads, state, head_dim]      the recurrent state,
           `state_dtype` (float32), 4.19 MB a lane and layer at 32 x 256
           x 128 — as much as 2,048 tokens of this model's K/V, constant
           in the context, read and written every step.

`conv` and `ssm` are indexed by slot, not through the block table: a
recurrent state is the whole prefix folded into one tensor and cannot be
rebuilt from shared prefix blocks, so prefix sharing, copy-on-write and a
`cached_len > 0` do not apply to this family (`RECURRENT_STATE`; the
engine and `build_replica` refuse them, and there is no `copy_block`).

Both steps `lax.scan` the layers with **every pool in the carry**, written
in place (PERF.md, PR 26). Prefill runs one sequence from a zero state
(the chunked scan) and leaves the lane's pools as they are after the last
REAL token: padded positions get dt = 0 and stay out of the tail. The
head is multiplied for the last real position only. In the decode step a
lane is live where its table's first entry is not the trash block; idle
lanes ride along and change nothing.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from determined_tpu.models.falcon_h1 import Config
from determined_tpu.ops import paged_attention, ssm_state
from determined_tpu.ops.norm_rope import rms_norm, rotary
from determined_tpu.serve.model import (  # noqa: F401
    _write_rows, narrowed, sample)

# What the engine asks a family (serve/engine.py `family_of`).
RECURRENT_STATE = True
copy_block = None        # no block of this cache can be shared
decode_span_tokens = paged_attention.span_tokens   # the grouped K/V kernel's
# serving.model_config is spelt as the published config.json is, with
# `dtype` and `state_dtype` (the recurrent state at rest) beside.
config_from = Config.from_published


def position_limit(cfg: Config) -> Optional[int]:
    """Rotary positions need no table: nothing clips `max_seq_len`."""
    return None


def adapter_refusal(cfg: Config) -> Optional[str]:
    return ("a family with recurrent state has no adapter arm (an adapter "
            "swaps the tied embedding table under one shared cache; this "
            "family's head is untied and its state is the lane's own)")


def assignments_per_token(cfg: Config) -> int:
    return 0                 # no routed experts


def cache_counters(cfg: Config, cache, pool_blocks: int,
                   block_size: int) -> Dict[str, Any]:
    return {}                # the cache holds no counter


def kernel_refusal(cfg: Config) -> Optional[str]:
    """Why `pallas` cannot serve this geometry (either kernel), or None."""
    return paged_attention.kernel_refusal(
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) \
        or ssm_state.kernel_refusal(
            cfg.mamba_n_heads, cfg.mamba_n_groups, cfg.mamba_d_head,
            cfg.mamba_d_state)


_MATRICES = ("qkv", "o", "in_proj", "conv_w", "conv_b", "out_proj", "gate",
             "up", "down")


def resident_params(params: Dict[str, Any], cfg: Config) -> Dict[str, Any]:
    """The tree the steps are called with: the matrices, the embedding and
    the head narrowed to `cfg.dtype` where a checkpoint has them wider
    (the published one is bfloat16 throughout: nothing to do); norms and
    the recurrence's own parameters are read in float32 and stay."""
    def narrow(x):
        return narrowed(x, cfg.dtype)

    out = dict(params, embed=narrow(params["embed"]),
               lm_head=narrow(params["lm_head"]))
    out["blocks"] = {name: narrow(leaf) if name in _MATRICES else leaf
                     for name, leaf in params["blocks"].items()}
    return out


# ---------------------------------------------------------------- cache


def _cache_shapes(cfg: Config, pool_blocks: int, block_size: int,
                  slots: int):
    layers = cfg.num_hidden_layers
    kv = (layers, pool_blocks, block_size,
          cfg.num_key_value_heads * cfg.head_dim)
    return {
        "k": (kv, cfg.dtype), "v": (kv, cfg.dtype),
        "conv": ((layers, slots, cfg.mamba_d_conv - 1, cfg.conv_channels),
                 cfg.dtype),
        "ssm": ((layers, slots, cfg.mamba_n_heads, cfg.mamba_d_state,
                 cfg.mamba_d_head), cfg.state_dtype),
    }


def init_cache(cfg: Config, pool_blocks: int, block_size: int,
               slots: int) -> Dict[str, jax.Array]:
    """Zeroed pools; `pool_blocks` includes the trailing trash block."""
    return {name: jnp.zeros(shape, dtype) for name, (shape, dtype) in
            _cache_shapes(cfg, pool_blocks, block_size, slots).items()}


def state_bytes(cfg: Config, slots: int) -> int:
    """HBM of what a lane owns whatever its context: conv tail and state."""
    shapes = _cache_shapes(cfg, 1, 1, slots)
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
               for shape, dtype in (shapes["conv"], shapes["ssm"]))


def cache_bytes(cfg: Config, pool_blocks: int, block_size: int,
                slots: int) -> int:
    """HBM footprint of the whole cache: paged K/V and the state pool."""
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
               for shape, dtype in _cache_shapes(
                   cfg, pool_blocks, block_size, slots).values())


# ----------------------------------------------------------------- block


def _qkv(u, lp, positions, cfg: Config):
    """u [T, d] → q [T, H, Dh], k, v [T, Hkv, Dh], rotated."""
    t = u.shape[0]
    hq, hkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    dt = cfg.dtype
    qkv = jnp.einsum("td,de->te", u * cfg.attention_in_multiplier,
                     lp["qkv"].astype(dt))
    q, k, v = jnp.split(qkv, [hq * dh, (hq + hkv) * dh], axis=-1)
    q = rotary(q.reshape(t, hq, dh), positions, cfg.rope_theta)
    k = rotary(k.reshape(t, hkv, dh) * cfg.key_multiplier, positions,
                cfg.rope_theta)
    return q, k, v.reshape(t, hkv, dh)


def _mixer_in(u, lp, cfg: Config):
    """u [T, d] → z [T, d_ssm], [x|B|C] before the convolution [T,
    channels], dt before its bias [T, heads] (float32)."""
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                           zip(cfg.in_proj_sections, cfg.ssm_multipliers)])
    proj = jnp.einsum("td,de->te", u * cfg.ssm_in_multiplier,
                      lp["in_proj"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32) * mup
    z, xbc, dt = jnp.split(
        proj, [cfg.d_ssm, cfg.d_ssm + cfg.conv_channels], axis=-1)
    return z.astype(cfg.dtype), xbc.astype(cfg.dtype), dt


def _split_xbc(xbc, cfg: Config):
    """[T, channels] after the convolution → x [T, H, P], B, C [T, G, N]."""
    t = xbc.shape[0]
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    x, b, c = jnp.split(xbc, [cfg.d_ssm, cfg.d_ssm + gn], axis=-1)
    shape = (t, cfg.mamba_n_groups, cfg.mamba_d_state)
    return (x.reshape(t, cfg.mamba_n_heads, cfg.mamba_d_head),
            b.reshape(shape), c.reshape(shape))


def _mixer_out(y, x, z, lp, cfg: Config):
    """The recurrence's y [T, H, P] float32 → the mixer's result [T, d]:
    the skip, the gate, the grouped norm, out_proj."""
    t = y.shape[0]
    y = y + lp["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(t, cfg.d_ssm) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(t, cfg.mamba_n_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = y.reshape(t, cfg.d_ssm) * lp["mixer_norm"].astype(jnp.float32)
    return jnp.einsum("te,ed->td", y.astype(cfg.dtype),
                      lp["out_proj"].astype(cfg.dtype)) \
        * cfg.ssm_out_multiplier


def _step_sizes(lp, dt, cfg: Config):
    """dt = softplus(dt + dt_bias) and A = -exp(A_log), float32."""
    return (jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32)),
            -jnp.exp(lp["A_log"].astype(jnp.float32)))


def _mlp(h, lp, cfg: Config):
    dt = cfg.dtype
    v = rms_norm(h, lp["pre_ff_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(jnp.einsum("td,df->tf", v, lp["gate"].astype(dt))
                       * cfg.mlp_multipliers[0])
    up = jnp.einsum("td,df->tf", v, lp["up"].astype(dt))
    return jnp.einsum("tf,fd->td", up * gate, lp["down"].astype(dt)) \
        * cfg.mlp_multipliers[1]


def _logits(params, h, cfg: Config):
    """h [T, d] → logits [T, V] float32."""
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("td,vd->tv", h, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32) \
        * cfg.lm_head_multiplier


def _embed(params, tokens, cfg: Config):
    return (params["embed"][tokens].astype(cfg.dtype)
            * cfg.embedding_multiplier).astype(cfg.dtype)


# ------------------------------------------------------------------ steps


def prefill(
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    tokens: jax.Array,       # [bucket] int32: the prompt, right-padded
    suffix_len: jax.Array,   # scalar int32: its real length (<= bucket)
    prefix_len: jax.Array,   # scalar int32: always 0 (nothing is shared)
    block_table: jax.Array,  # [max_blocks] int32: the sequence's table
    cfg: Config,
    rules=None,
    slot: jax.Array = None,  # scalar int32: the lane whose state this is
    attention_impl: str = "reference",   # no kernel in this prefill
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Prefill one prompt from a zero state into lane `slot` → (cache',
    the last real position's logits [V] float32)."""
    del prefix_len, rules, attention_impl
    s = tokens.shape[0]
    mb = block_table.shape[0]
    bs = cache["k"].shape[2]
    trash = cache["k"].shape[1] - 1
    dt_ = cfg.dtype
    hq, hkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    pos = jnp.arange(s)
    real = pos < suffix_len
    dest_blk = jnp.where(real, block_table[jnp.minimum(pos // bs, mb - 1)],
                         trash)
    dest_off = pos % bs
    causal = pos[None, :] <= pos[:, None]
    width = cfg.mamba_d_conv
    # the tail the lane keeps: the last width-1 real inputs, zeros where
    # the prompt is shorter
    tail_at = suffix_len - (width - 1) + jnp.arange(width - 1)
    h = _embed(params, tokens, cfg)

    def body(carry, layer_in):
        h, pool = carry
        lp, layer = layer_in
        u = rms_norm(h, lp["input_norm"], cfg.rms_norm_eps)
        # attention over the prompt's own keys; their rows go to the pool
        q, k, v = _qkv(u, lp, pos, cfg)
        pool = dict(pool, **_write_rows(pool, layer, dest_blk, dest_off,
                                        k, v))
        scores = jnp.einsum("qgrd,kgd->grqk",
                            q.reshape(s, hkv, hq // hkv, dh), k,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(causal, scores / math.sqrt(dh),
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt_)
        attn = jnp.einsum("grqk,kgd->qgrd", probs, v).reshape(s, hq * dh)
        attn = jnp.einsum("te,ed->td", attn, lp["o"].astype(dt_)) \
            * cfg.attention_out_multiplier
        # mixer: convolution, then the chunked scan from a zero state
        z, xbc, dt = _mixer_in(u, lp, cfg)
        tail = jnp.where((tail_at >= 0)[:, None],
                         xbc[jnp.maximum(tail_at, 0)], 0)
        padded = jnp.pad(xbc, ((width - 1, 0), (0, 0))).astype(jnp.float32)
        conv = sum(padded[j:j + s] * lp["conv_w"][j].astype(jnp.float32)
                   for j in range(width)) \
            + lp["conv_b"].astype(jnp.float32)
        x, b, c = _split_xbc(jax.nn.silu(conv).astype(dt_), cfg)
        dt, a = _step_sizes(lp, dt, cfg)
        y, state = ssm_state.ssd_chunked_scan(
            x, jnp.where(real[:, None], dt, 0.0), a, b, c,
            cfg.mamba_chunk_size)
        pool["conv"] = pool["conv"].at[layer, slot].set(
            tail.astype(pool["conv"].dtype))
        pool["ssm"] = pool["ssm"].at[layer, slot].set(
            state.astype(pool["ssm"].dtype))
        h = h + _mixer_out(y, x, z, lp, cfg).astype(dt_) + attn.astype(dt_)
        h = h + _mlp(h, lp, cfg).astype(dt_)
        return (h, pool), None

    (h, cache), _ = jax.lax.scan(
        body, (h, cache),
        (params["blocks"], jnp.arange(cfg.num_hidden_layers)))
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
    """One decode step for every slot → (cache', logits [slots, V]).
    `attention_impl` names the path of both kernels."""
    del rules
    slots = tokens.shape[0]
    bs = cache["k"].shape[2]
    mb = block_tables.shape[1]
    trash = cache["k"].shape[1] - 1
    dt_ = cfg.dtype
    live = block_tables[:, 0] != trash
    wblk = jnp.take_along_axis(
        block_tables, jnp.minimum(positions // bs, mb - 1)[:, None],
        axis=1)[:, 0]
    woff = positions % bs
    h = _embed(params, tokens, cfg)

    def body(carry, layer_in):
        h, pool = carry
        lp, layer = layer_in
        u = rms_norm(h, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(u, lp, positions, cfg)
        pool = dict(pool, **_write_rows(pool, layer, wblk, woff, k, v))
        attn = paged_attention.paged_decode_attention(
            q, pool["k"], pool["v"], layer, block_tables, positions,
            impl=attention_impl).reshape(slots, -1)
        attn = jnp.einsum("te,ed->td", attn, lp["o"].astype(dt_)) \
            * cfg.attention_out_multiplier
        # mixer: the tail and this token are the convolution's window
        z, xbc, dt = _mixer_in(u, lp, cfg)
        tail = pool["conv"][layer]                  # [slots, width-1, C]
        window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], 1)
        conv = jnp.einsum("swc,wc->sc", window.astype(jnp.float32),
                          lp["conv_w"].astype(jnp.float32)) \
            + lp["conv_b"].astype(jnp.float32)
        pool["conv"] = pool["conv"].at[layer].set(
            jnp.where(live[:, None, None], window[:, 1:], tail))
        x, b, c = _split_xbc(jax.nn.silu(conv).astype(dt_), cfg)
        dt, a = _step_sizes(lp, dt, cfg)
        pool["ssm"], y = ssm_state.ssm_decode_step(
            x, dt, b, c, pool["ssm"], layer, live, a, impl=attention_impl)
        h = h + _mixer_out(y, x, z, lp, cfg).astype(dt_) + attn.astype(dt_)
        h = h + _mlp(h, lp, cfg).astype(dt_)
        return (h, pool), None

    (h, cache), _ = jax.lax.scan(
        body, (h, cache),
        (params["blocks"], jnp.arange(cfg.num_hidden_layers)))
    return cache, _logits(params, h, cfg)
