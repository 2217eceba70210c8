"""KV-cached GPT-2 inference steps (prefill + single-token decode).

The training forward pass (models/gpt2.apply) recomputes attention over the
whole context every call — O(S²) per generated token. Serving splits it the
standard way:

  - **prefill**: one full causal pass over the (bucket-padded) prompt,
    writing every position's K/V into the sequence's cache lane and
    returning the next-token logits. Compiled per prompt bucket, so a small
    set of AOT executables covers every prompt length.
  - **decode**: one token per active slot per call — each slot attends over
    its cached K/V only. One compiled executable regardless of batch
    composition; the continuous batcher joins/retires sequences purely by
    editing host-side slot state.

The cache is a block pool `[L, num_blocks + 1, block_size, H*Dh]`
addressed through per-sequence block tables (`init_paged_cache` /
`paged_prefill` / `paged_decode_step`): admission bounds real HBM and
prompt prefixes can be shared (docs/serving.md "Paged KV & prefix
caching"). Both steps `lax.scan` the layers' stacked params with the pool
as the scan's CARRY, one buffer for the whole call, written in place by
token-sized scatters and read through the tables; it is never a per-layer
input or a stacked output of the scan, which made the compiler keep a
second pool and copy it back (PERF.md, PR 26).

Positions beyond a sequence's current length hold stale bytes; the
decode mask (`index <= position`) never admits a stale index before the
step that overwrites it, and padded/inactive writes land in a dedicated
trash block.

Works for GPT-2's dense blocks and for its Switch-style MoE variant
(`ops/moe.moe_block`: capacity with drops, routed per token, so a 1-token
decode step reuses it unchanged). A published sparse model is another
family, served through the dropless layer (`serve/glm4_moe_lite.py`). All
functions are shape-static and jit/AOT-friendly; tier-1 exercises them on
the CPU backend.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from determined_tpu.models.gpt2 import Config, _embed_tokens, _layer_norm
from determined_tpu.ops.paged_attention import span_tokens
from determined_tpu.parallel.sharding import LogicalRules, shard_logical


# Block leaves the step functions read only through `.astype(cfg.dtype)`
# (kernel and bias alike). Layer-norm leaves are not among them:
# `_layer_norm` multiplies them in float32. A `moe` subtree is read by
# ops/moe.py, which has its own casts.
_COMPUTE_DTYPE_BLOCK_LEAVES = ("qkv", "attn_out", "mlp_up", "mlp_down")


def narrowed(x: jax.Array, dtype: Any) -> jax.Array:
    """A floating leaf wider than `dtype` cast to it; any other leaf, and
    one already that narrow, as it came (every family's
    `resident_params`)."""
    dt = jnp.dtype(dtype)
    wider = (jnp.issubdtype(x.dtype, jnp.floating)
             and jnp.dtype(x.dtype).itemsize > dt.itemsize)
    return x.astype(dt) if wider else x


def resident_params(params: Dict[str, Any], cfg: Config) -> Dict[str, Any]:
    """The tree the step functions are to be called with, call after call.

    Every leaf they read only through `.astype(cfg.dtype)` — the block
    matrices and their biases, `wte`, `wpe` — is cast to `cfg.dtype` where
    it is wider; every other leaf, and a leaf already that narrow, comes
    back as it came. `bf16(w)` is what each call multiplied by anyway: a
    float32 tree left as it is has the cast of every `[L, ...]` stack
    hoisted out of the layer scan and run again in every call (PERF.md,
    PR 29). The `.astype` in the step functions is a no-op on this tree
    and keeps direct callers with a float32 tree working."""
    narrow = functools.partial(narrowed, dtype=cfg.dtype)
    out = dict(params)
    for name in ("wte", "wpe"):
        out[name] = narrow(params[name])
    out["blocks"] = {
        name: (jax.tree_util.tree_map(narrow, leaf)
               if name in _COMPUTE_DTYPE_BLOCK_LEAVES else leaf)
        for name, leaf in params["blocks"].items()}
    return out


def _qkv(x, lp, cfg: Config):
    """x: [B, S, D] → q, k, v: [B, S, H, Dh]."""
    b, s, _ = x.shape
    dt = cfg.dtype
    qkv = jnp.einsum("bsd,de->bse", x, lp["qkv"]["kernel"].astype(dt)) + lp[
        "qkv"]["bias"].astype(dt)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (b, s, cfg.n_head, cfg.head_dim)
    return q.reshape(shape), k.reshape(shape), v.reshape(shape)


def _mlp(y, lp, cfg: Config, rules: Optional[LogicalRules]):
    """The block's FFN — dense or token-routed MoE, matching _block."""
    dt = cfg.dtype
    if cfg.num_experts > 1:
        from determined_tpu.ops.moe import moe_block

        down, _ = moe_block(
            y, lp["moe"], cfg.num_experts, top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor, rules=rules,
        )
        return down
    up = jnp.einsum("bsd,df->bsf", y, lp["mlp_up"]["kernel"].astype(dt)) + lp[
        "mlp_up"]["bias"].astype(dt)
    up = shard_logical(up, ("batch", "seq", "mlp"), rules)
    up = jax.nn.gelu(up, approximate=True)
    return (
        jnp.einsum("bsf,fd->bsd", up, lp["mlp_down"]["kernel"].astype(dt))
        + lp["mlp_down"]["bias"].astype(dt)
    )


def _finish(params, x, cfg: Config, rules: Optional[LogicalRules]):
    """Final layernorm + LM head → logits [..., vocab]."""
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"],
                    cfg.layer_norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(cfg.dtype))
    return shard_logical(logits, ("batch", "seq", "vocab"), rules)


# ------------------------------------------------------------- adapters
#
# Multi-adapter serving (docs/serving.md "Model lifecycle"): many
# fine-tunes share ONE base executable and ONE KV pool. An adapter is the
# (tied) embedding/LM-head table of a head-tuned checkpoint; the stack
# `[A+1, vocab, d_model]` (index 0 = the base table) rides every compiled
# call like params do, and a per-slot adapter index selects each lane's
# table at the only two places the table is read — token embedding and the
# final logits projection. The transformer body (and therefore the cached
# K/V) stays the base's for every adapter, which is exactly what lets one
# executable and one block pool serve thousands of fine-tunes: selection
# is a gather + a batched matmul, never a recompile.


def _embed_adapter(adapters: jax.Array, idx: jax.Array,
                   tokens: jax.Array, dtype) -> jax.Array:
    """Per-lane token embedding from the adapter stack.

    adapters: [A+1, V, D]. idx scalar (prefill: one lane, tokens [S] →
    [S, D]) or [slots] (decode: one token per lane, tokens [slots] →
    [slots, D]). Same gather `wte[tokens]` as _embed_tokens, with wte
    selected per lane (serving runs unsharded — the one-hot Megatron
    path is a training concern)."""
    sel = jnp.take(adapters, idx, axis=0).astype(dtype)
    if idx.ndim == 0:
        return sel[tokens]  # [S, D]
    return jnp.take_along_axis(
        sel, tokens[:, None, None], axis=1)[:, 0, :]  # [slots, D]


def _finish_adapter(params, x, adapters: jax.Array, idx: jax.Array,
                    cfg: Config, rules: Optional[LogicalRules]):
    """_finish with the LM head selected per lane from the adapter stack.

    x: [B, S, D]; idx: [] (prefill, B==1) or [slots] (decode, S==1)."""
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"],
                    cfg.layer_norm_eps)
    sel = jnp.take(adapters, idx, axis=0).astype(cfg.dtype)
    if idx.ndim == 0:
        logits = jnp.einsum("bsd,vd->bsv", x, sel)
    else:
        logits = jnp.einsum("sqd,svd->sqv", x, sel)
    return shard_logical(logits, ("batch", "seq", "vocab"), rules)


# ---------------------------------------------------------------- paged
#
# vLLM-style paged layout (docs/serving.md "Paged KV & prefix caching"):
# the cache is a block pool `[L, pool_blocks, block_size, H*Dh]` and each
# sequence owns an ordered block table mapping logical block i → a pool
# block. The LAST pool block is the trash block: padded/inactive writes
# land there so they can never corrupt an owned block, and inactive slots
# point their whole table at it. Prefix caching falls out of the layout —
# a shared prompt's blocks appear in many tables at once (refcounted by
# the host BlockManager), and prefill only computes the novel suffix.


def init_paged_cache(
    cfg: Config, pool_blocks: int, block_size: int, dtype: Any = None
) -> Dict[str, jax.Array]:
    """Zeroed paged KV pool: {"k","v"}: [L, pool_blocks, bs, H*Dh].

    A token's heads lie side by side in one row: with `bs` a multiple of
    16 (bf16) and `H*Dh` of 128 the row-major form is dense under the
    TPU's (8, 128) tiling, so the scatter that writes a token and the
    kernel that reads a block use the buffer as it rests. `pool_blocks`
    INCLUDES the trailing trash block (callers size it as
    `num_blocks + 1`)."""
    dt = dtype or cfg.dtype
    shape = (cfg.n_layer, pool_blocks, block_size, cfg.n_head * cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_cache_bytes(cfg: Config, pool_blocks: int, block_size: int,
                      dtype: Any = None) -> int:
    """HBM footprint of the paged pool (both K and V)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    per = cfg.n_layer * pool_blocks * block_size * cfg.n_head * cfg.head_dim
    return 2 * per * dt.itemsize


def _write_rows(pool, layer, blk, off, k, v):
    """Write tokens' K/V (`[T, H, Dh]`) at `[layer, blk[t], off[t], :]`:
    one scatter of `[T, H*Dh]` rows each into the carried pool, which the
    compiler performs in place."""
    rows = (k.shape[0], -1)
    return {
        "k": pool["k"].at[layer, blk, off].set(
            k.reshape(rows).astype(pool["k"].dtype)),
        "v": pool["v"].at[layer, blk, off].set(
            v.reshape(rows).astype(pool["v"].dtype)),
    }


def paged_prefill(
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    tokens: jax.Array,       # [bucket] int32: the NOVEL SUFFIX, right-padded
    suffix_len: jax.Array,   # scalar int32: real suffix length (<= bucket)
    prefix_len: jax.Array,   # scalar int32: tokens already cached (KV reuse)
    block_table: jax.Array,  # [max_blocks] int32: the sequence's table
    cfg: Config,
    rules: Optional[LogicalRules] = None,
    adapters: Optional[jax.Array] = None,   # [A+1, V, D] stack
    slot_adapter: Optional[jax.Array] = None,  # scalar int32 stack index
    attention_impl: str = "reference",   # no kernel in this prefill
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Prefill the suffix `tokens[prefix_len:]` of a prompt whose first
    `prefix_len` tokens' K/V already sit in `block_table`'s blocks.

    Returns (cache', last-position logits [vocab]). The suffix K/V are
    scattered into the pool per the table, then the suffix queries attend
    over the gathered lane (cached prefix + just-written suffix). With
    `prefix_len == 0` this is a full prefill — same executable.
    """
    del attention_impl
    s = tokens.shape[0]
    mb = block_table.shape[0]
    bs = cache["k"].shape[2]
    trash = cache["k"].shape[1] - 1
    dt = cfg.dtype
    if adapters is None:
        x = _embed_tokens(params, tokens[None], cfg, rules, dt)
    else:
        x = _embed_adapter(adapters, slot_adapter, tokens, dt)[None]
    # Absolute positions prefix_len + i (clip keeps padded lanes in-table;
    # their queries are garbage the `last` index never selects).
    pos_ids = jnp.minimum(prefix_len + jnp.arange(s),
                          params["wpe"].shape[0] - 1)
    x = x + jnp.take(params["wpe"].astype(dt), pos_ids, axis=0)[None]
    x = shard_logical(x, ("batch", "seq", "embed"), rules)
    # Scatter destinations: real suffix positions land in their table
    # block; padded positions land in the trash block.
    dest_blk = jnp.where(jnp.arange(s) < suffix_len,
                         block_table[jnp.minimum(pos_ids // bs, mb - 1)],
                         trash)
    dest_off = pos_ids % bs
    # Causal mask over the gathered lane: key j visible to suffix query i
    # iff j <= prefix_len + i (prefix + suffix written so far + self).
    mask = jnp.arange(mb * bs)[None, :] <= (prefix_len + jnp.arange(s))[:, None]
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def body(carry, layer_in):
        xx, pool = carry
        lp, layer = layer_in
        y = _layer_norm(xx, lp["ln1"]["scale"], lp["ln1"]["bias"],
                        cfg.layer_norm_eps)
        q, k, v = _qkv(y, lp, cfg)  # [1, S, H, Dh]
        pool = _write_rows(pool, layer, dest_blk, dest_off, k[0], v[0])
        lane = (mb * bs, cfg.n_head, cfg.head_dim)
        k_lane = pool["k"][layer, block_table].reshape(lane)
        v_lane = pool["v"][layer, block_table].reshape(lane)
        logits = jnp.einsum("bshd,mhd->bhsm", q, k_lane).astype(jnp.float32)
        logits = jnp.where(mask[None, None], logits * scale,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        attn = jnp.einsum("bhsm,mhd->bshd", probs, v_lane)
        attn = attn.reshape(xx.shape)
        attn = (jnp.einsum("bsd,de->bse", attn,
                           lp["attn_out"]["kernel"].astype(dt))
                + lp["attn_out"]["bias"].astype(dt))
        xx = xx + attn
        y = _layer_norm(xx, lp["ln2"]["scale"], lp["ln2"]["bias"],
                        cfg.layer_norm_eps)
        xx = xx + _mlp(y, lp, cfg, rules)
        xx = shard_logical(xx, ("batch", "seq", "embed"), rules)
        return (xx, pool), None

    (x, cache), _ = jax.lax.scan(
        body, (x, cache), (params["blocks"], jnp.arange(cfg.n_layer)))
    if adapters is None:
        logits = _finish(params, x, cfg, rules)  # [1, S, V]
    else:
        logits = _finish_adapter(params, x, adapters, slot_adapter, cfg,
                                 rules)
    last = jax.lax.dynamic_index_in_dim(
        logits[0], jnp.maximum(suffix_len - 1, 0), axis=0, keepdims=False)
    return cache, last.astype(jnp.float32)


def paged_decode_step(
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    tokens: jax.Array,        # [slots] int32: last emitted token per slot
    positions: jax.Array,     # [slots] int32: index this step writes at
    block_tables: jax.Array,  # [slots, max_blocks] int32
    cfg: Config,
    rules: Optional[LogicalRules] = None,
    attention_impl: str = "reference",
    adapters: Optional[jax.Array] = None,      # [A+1, V, D] stack
    slot_adapters: Optional[jax.Array] = None,  # [slots] int32 stack index
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """One paged decode step for every slot → (cache', logits [slots, V]).

    Each slot's new K/V is a block write (table lookup of
    `position // block_size`), then a block-table-gathered attention
    (ops/paged_attention). Inactive slots ride along: they write the
    trash block and attend garbage the batcher discards, so the
    executable never depends on which lanes are live and joining or
    retiring a sequence costs zero recompiles.
    """
    from determined_tpu.ops.paged_attention import paged_decode_attention

    slots = tokens.shape[0]
    bs = cache["k"].shape[2]
    mb = block_tables.shape[1]
    dt = cfg.dtype
    if adapters is None:
        x = _embed_tokens(params, tokens[:, None], cfg, rules, dt)
    else:
        x = _embed_adapter(adapters, slot_adapters, tokens, dt)[:, None]
    pos_emb = jnp.take(params["wpe"].astype(dt), positions, axis=0)
    x = x + pos_emb[:, None]
    x = shard_logical(x, ("batch", "seq", "embed"), rules)
    wblk = jnp.take_along_axis(
        block_tables, jnp.minimum(positions // bs, mb - 1)[:, None],
        axis=1)[:, 0]  # [slots]
    woff = positions % bs

    def body(carry, layer_in):
        xx, pool = carry  # [slots, 1, D], the whole pool
        lp, layer = layer_in
        y = _layer_norm(xx, lp["ln1"]["scale"], lp["ln1"]["bias"],
                        cfg.layer_norm_eps)
        q, k, v = _qkv(y, lp, cfg)  # [slots, 1, H, Dh]
        pool = _write_rows(pool, layer, wblk, woff, k[:, 0], v[:, 0])
        attn = paged_decode_attention(
            q[:, 0], pool["k"], pool["v"], layer, block_tables, positions,
            impl=attention_impl)  # [slots, H, Dh]
        attn = attn.reshape(slots, 1, -1)
        attn = (jnp.einsum("bsd,de->bse", attn,
                           lp["attn_out"]["kernel"].astype(dt))
                + lp["attn_out"]["bias"].astype(dt))
        xx = xx + attn
        y = _layer_norm(xx, lp["ln2"]["scale"], lp["ln2"]["bias"],
                        cfg.layer_norm_eps)
        xx = xx + _mlp(y, lp, cfg, rules)
        return (xx, pool), None

    (x, cache), _ = jax.lax.scan(
        body, (x, cache), (params["blocks"], jnp.arange(cfg.n_layer)))
    if adapters is None:
        logits = _finish(params, x, cfg, rules)  # [slots, 1, V]
    else:
        logits = _finish_adapter(params, x, adapters, slot_adapters, cfg,
                                 rules)
    return cache, logits[:, 0].astype(jnp.float32)


def copy_paged_block(
    cache: Dict[str, jax.Array], dst: jax.Array, src: jax.Array
) -> Dict[str, jax.Array]:
    """Copy-on-write: duplicate pool block `src` into `dst` across every
    layer (both K and V). Used when a sequence must write into a block
    whose content is shared with other sequences (prefix caching)."""
    return {
        "k": cache["k"].at[:, dst].set(cache["k"][:, src]),
        "v": cache["v"].at[:, dst].set(cache["v"][:, src]),
    }


# ---------------------------------------------------------------- family
#
# What the engine asks a serving family (serve/engine.py `family_of`): its
# cache, its two steps and its block copy, under names every family shares.

RECURRENT_STATE = False      # K/V is the only thing a sequence leaves


def adapter_refusal(cfg: Config) -> Optional[str]:
    """Adapters swap this family's tied embedding table: served."""
    return None


def assignments_per_token(cfg: Config) -> int:
    """The Switch variant's routing is not counted: no published sparse
    model is served through this family."""
    return 0


def cache_counters(cfg: Config, cache, pool_blocks: int,
                   block_size: int) -> Dict[str, Any]:
    """This family's cache holds K and V and no counter."""
    return {}


def config_from(mc: Dict[str, Any]) -> Config:
    """serving.model_config → the Config: a named size with every
    architecture dim overridable. The config must reproduce the trained
    checkpoint's exact shapes or the engine's first trace fails loudly at
    startup (the intended failure mode for a mismatch)."""
    base = {
        "tiny": Config.tiny,
        "small": Config.small,
        "medium": Config.medium,
        "large": Config.large,
    }[mc.get("model_size", "small")]()
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    seq_len = int(mc.get("seq_len", base.n_positions))
    return Config(
        vocab_size=int(mc.get("vocab_size", base.vocab_size)),
        n_positions=max(int(mc.get("n_positions", base.n_positions)),
                        seq_len),
        d_model=int(mc.get("d_model", base.d_model)),
        n_layer=int(mc.get("n_layer", base.n_layer)),
        n_head=int(mc.get("n_head", base.n_head)),
        dtype=dtypes[mc.get("dtype", "bfloat16")],
        attention_impl="dot",  # decode attends over the KV cache directly
        num_experts=int(mc.get("num_experts", 1)),
        moe_top_k=int(mc.get("moe_top_k", 2)),
    )


prefill = paged_prefill
decode_step = paged_decode_step
copy_block = copy_paged_block
decode_span_tokens = span_tokens


def position_limit(cfg: Config) -> int:
    """The learned position table bounds the context."""
    return cfg.n_positions


def kernel_refusal(cfg: Config) -> Optional[str]:
    from determined_tpu.ops.paged_attention import kernel_refusal as refusal

    return refusal(cfg.n_head, cfg.n_head, cfg.head_dim)


def init_cache(cfg: Config, pool_blocks: int, block_size: int,
               slots: int) -> Dict[str, jax.Array]:
    del slots                # nothing here is indexed by lane
    return init_paged_cache(cfg, pool_blocks, block_size)


def cache_bytes(cfg: Config, pool_blocks: int, block_size: int,
                slots: int) -> int:
    del slots
    return paged_cache_bytes(cfg, pool_blocks, block_size)


def state_bytes(cfg: Config, slots: int) -> int:
    return 0


def sample(
    logits: jax.Array,        # [slots, vocab] fp32
    temperature: jax.Array,   # [slots] fp32; 0 = greedy
    rng: jax.Array,
) -> jax.Array:
    """Next token per slot: greedy at temperature 0, else categorical."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    drawn = jax.random.categorical(rng, logits / temp, axis=-1).astype(
        jnp.int32)
    return jnp.where(temperature > 0, drawn, greedy)
