"""Pallas TPU paged decode-attention (vLLM-style block-table gather).

The serving decode hot path (ROADMAP item 2; docs/serving.md "Paged KV &
prefix caching"): each decode step, every active slot attends its single
query token over K/V that live in a **block pool** — `[num_blocks,
block_size, H, Dh]` per layer — addressed through a per-slot **block
table** (`[slots, max_blocks]` int32, logical block i of the sequence →
pool block `table[s, i]`). The dense layout's `slots × max_seq` lane
reservation disappears: HBM holds exactly the blocks sequences actually
own, and admission can pack many more sequences into the same budget.

Two interchangeable implementations (selected by
`serving.attention_impl`, asserted token-identical by tests/test_serving):

  - `paged_attention_reference` — pure-jnp gather (`pool[table]`) +
    the exact masked-softmax arithmetic of the dense decode step. With
    `block_size` dividing `max_seq` the gathered lane has the same
    shape and element order as the dense lane, so greedy decode is
    bit-identical to the dense path. Fast on CPU; the fallback anywhere
    Pallas is unavailable.

  - `paged_attention_pallas` — the TPU kernel. Grid `(slots,
    max_blocks)`; the block table and positions ride
    `PrefetchScalarGridSpec` scalar prefetch so each program's K/V
    BlockSpec `index_map` dereferences `table[s, b]` — the gather IS the
    pipeline's block fetch, no materialized `[slots, max_seq]` lane ever
    exists. The inner loop is an online softmax: fp32 running max `m`,
    normalizer `l`, and accumulator `acc` live in VMEM scratch across
    the `b` iterations of one slot; the output block is written at the
    final block index. Both inner products are matmuls batched over a
    LEADING heads dim with 3-D operands (`[H, 1, Dh] x [H, bs, Dh]`) —
    the form Mosaic lowers; a 2-D `[H, Dh]` lhs with a batch dim and no
    non-contracting dim is refused by its dot-dimension parser. Compiles
    through Mosaic unless the caller passes `interpret=True` (tests).

Inactive slots point every table entry at a reserved trash block and sit
at position 0 — they compute garbage the batcher discards, exactly like
the dense path's stale lanes, so the executable never depends on which
slots are live.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from determined_tpu.ops._pallas_common import (
    HAVE_PALLAS,
    NEG_INF,
    finish_softmax_scratch,
    init_softmax_scratch,
    online_softmax_update,
    softmax_scratch,
)

if HAVE_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Reference implementation: gather + dense masked softmax.
# ---------------------------------------------------------------------------


def paged_attention_reference(
    q: jax.Array,             # [slots, H, Dh]
    k_pool: jax.Array,        # [num_pool_blocks, block_size, H, Dh]
    v_pool: jax.Array,        # [num_pool_blocks, block_size, H, Dh]
    block_tables: jax.Array,  # [slots, max_blocks] int32 pool indices
    positions: jax.Array,     # [slots] int32: index written this step
) -> jax.Array:
    """Pure-jnp paged decode attention → [slots, H, Dh] in q.dtype.

    Gathers each slot's lane (`pool[table]` → `[max_blocks × block_size,
    H, Dh]`) and then runs the *identical* arithmetic of the dense decode
    step (serve/model.decode_step): fp32 logits, `index <= position`
    mask, fp32 softmax, probs cast back to the compute dtype. Identical
    shapes + identical op order ⇒ bit-identical greedy decode vs dense.
    """
    slots, mb = block_tables.shape
    bs = k_pool.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    k_lane = k_pool[block_tables].reshape(slots, mb * bs, *k_pool.shape[2:])
    v_lane = v_pool[block_tables].reshape(slots, mb * bs, *v_pool.shape[2:])
    mask = jnp.arange(mb * bs)[None] <= positions[:, None]  # [slots, S]
    logits = jnp.einsum("bhd,bmhd->bhm", q, k_lane).astype(jnp.float32)
    logits = jnp.where(mask[:, None], logits * scale,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhm,bmhd->bhd", probs, v_lane)


# ---------------------------------------------------------------------------
# Pallas kernel: scalar-prefetched block-table gather + online softmax.
# ---------------------------------------------------------------------------


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block_size, scale):
    s, b = pl.program_id(0), pl.program_id(1)
    mb = pl.num_programs(1)

    @pl.when(b == 0)
    def _init():
        init_softmax_scratch(acc_ref, m_ref, l_ref)

    pos = pos_ref[s]

    # Blocks past the slot's write position hold nothing visible; their
    # programs still run (the TPU grid is static) but touch no state.
    @pl.when(b * block_size <= pos)
    def _accumulate():
        q = q_ref[0]                                           # [H, 1, Dh]
        k = jnp.swapaxes(k_ref[0], 0, 1)                       # [H, bs, Dh]
        v = jnp.swapaxes(v_ref[0], 0, 1)
        st = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale        # [H, 1, bs]
        idx = b * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_size), 2)
        st = jnp.where(idx <= pos, st, NEG_INF)
        online_softmax_update(st, v, acc_ref, m_ref, l_ref,
                              (((2,), (1,)), ((0,), (0,))))    # [H, 1, Dh]

    @pl.when(b == mb - 1)
    def _finish():
        finish_softmax_scratch(o_ref, acc_ref, l_ref, idx=0)


def paged_attention_pallas(
    q: jax.Array,             # [slots, H, Dh]
    k_pool: jax.Array,        # [num_pool_blocks, block_size, H, Dh]
    v_pool: jax.Array,        # [num_pool_blocks, block_size, H, Dh]
    block_tables: jax.Array,  # [slots, max_blocks] int32
    positions: jax.Array,     # [slots] int32
    interpret: bool = False,
) -> jax.Array:
    """Pallas paged decode attention → [slots, H, Dh] in q.dtype."""
    if not HAVE_PALLAS:
        raise RuntimeError(
            "pallas unavailable in this jax build; use "
            "serving.attention_impl: reference")
    slots, nh, dh = q.shape
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    # The query rides as [slots, H, 1, Dh]: the unit dim is the matmuls'
    # non-contracting lhs dim, added out here because Mosaic cannot
    # reshape a packed bf16 [H, Dh] tile to [H, 1, Dh] in-kernel.
    q_block = pl.BlockSpec((1, nh, 1, dh), lambda s, b, tbl, pos: (s, 0, 0, 0))
    kv_block = pl.BlockSpec((1, bs, nh, dh),
                            lambda s, b, tbl, pos: (tbl[s, b], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, positions
        grid=(slots, mb),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        scratch_shapes=softmax_scratch((nh, 1), dh),  # fp32 acc/m/l in VMEM
    )
    kernel = functools.partial(
        _paged_kernel, block_size=bs, scale=1.0 / (dh ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, nh, 1, dh), q.dtype),
        cost_estimate=pl.CostEstimate(
            # Worst case: every table entry live. 2 matmuls over the lane.
            flops=int(4 * slots * mb * bs * nh * dh),
            bytes_accessed=int(
                2 * slots * mb * bs * nh * dh * k_pool.dtype.itemsize),
            transcendentals=int(slots * mb * bs * nh),
        ),
        interpret=interpret,
    )(block_tables, positions, q[:, :, None, :], k_pool, v_pool)
    return out[:, :, 0, :]


def paged_decode_attention(q, k_pool, v_pool, block_tables, positions,
                           impl: str = "reference"):
    """Dispatch by `serving.attention_impl` ("pallas" | "reference")."""
    if impl == "pallas":
        return paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                      positions)
    if impl == "reference":
        return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                         positions)
    raise ValueError(f"unknown paged attention impl {impl!r}")
