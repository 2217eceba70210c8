"""Pallas TPU paged decode-attention (vLLM-style block-table gather).

The serving decode hot path (ROADMAP item 2; docs/serving.md "Paged KV &
prefix caching"): each decode step, every active slot attends its single
query token over K/V that live in a **block pool** — `[L, num_blocks,
block_size, H*Dh]`, every layer in one buffer and a token's heads side by
side in one row — addressed through a per-slot **block table** (`[slots,
max_blocks]` int32, logical block i of the sequence → pool block
`table[s, i]`). No `slots × max_seq` lane is reserved: HBM holds exactly
the blocks sequences actually own, and admission packs sequences into
that budget.

Both implementations take the WHOLE pool and a layer index: the serving
step carries the pool through its loop over layers and writes it in
place, and an operand that was one layer of it would be a slice, hence a
copy. The row-major form of `[.., block_size, H*Dh]` is dense under the
TPU's (8, 128) tiling (block_size a multiple of 16 for bf16, H*Dh of
128), so the kernel reads the buffer as it rests.

Two interchangeable implementations (selected by
`serving.attention_impl`, asserted token-identical by tests/test_serving):

  - `paged_attention_reference` — pure-jnp gather (`pool[layer, table]`:
    the lanes' blocks, never a layer) + a masked softmax over the
    gathered lane; in float32 its logits are the full forward's
    (`gpt2.apply`) to a few ulps (tests/test_serving). Fast on CPU; the
    fallback anywhere Pallas is unavailable.

  - `paged_attention_pallas` — the TPU kernel. Grid `(slots,
    max_blocks)`; the block table, positions and the layer ride
    `PrefetchScalarGridSpec` scalar prefetch so each program's K/V
    BlockSpec `index_map` dereferences `(layer, table[s, b])` — the
    gather IS the pipeline's block fetch, no materialized `[slots,
    max_seq]` lane ever exists. The inner loop is an online softmax:
    fp32 running max `m`, normalizer `l`, and accumulator `acc` live in
    VMEM scratch across the `b` iterations of one slot; the output block
    is written at the final block index. A block arrives as `[bs, H*Dh]`
    rows and heads are taken as static lane-aligned slices of it, G
    groups of W = max(128, Dh) lanes: two heads of 64 share a group, with
    q laid block-diagonally (`[G, per, W]`, made outside the kernel) so
    one product gives each head its own logits; nothing is transposed.
    Both inner products are matmuls batched over the LEADING group dim
    with 3-D operands (`[G, per, W] x [G, bs, W]`) — the form Mosaic
    lowers; a 2-D lhs with a batch dim and no non-contracting dim is
    refused by its dot-dimension parser. A geometry that cannot be cut
    into such groups (`kernel_refusal`) raises with the reason. Compiles
    through Mosaic unless the caller passes `interpret=True` (tests).

Inactive slots point every table entry at a reserved trash block and sit
at position 0 — they compute garbage the batcher discards, so the
executable never depends on which slots are live.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

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
# Reference implementation: gather + masked softmax over the lane.
# ---------------------------------------------------------------------------


def paged_attention_reference(
    q: jax.Array,             # [slots, H, Dh]
    k_pool: jax.Array,        # [L, pool_blocks, block_size, H*Dh]
    v_pool: jax.Array,        # [L, pool_blocks, block_size, H*Dh]
    layer: jax.Array,         # scalar int32: the layer attended
    block_tables: jax.Array,  # [slots, max_blocks] int32 pool indices
    positions: jax.Array,     # [slots] int32: index written this step
) -> jax.Array:
    """Pure-jnp paged decode attention → [slots, H, Dh] in q.dtype.

    Gathers each slot's lane (`pool[layer, table]` → `[max_blocks ×
    block_size, H, Dh]`: the lanes' blocks, never a layer of the pool) and
    attends over it: fp32 logits, `index <= position` mask, fp32 softmax,
    probs cast back to the compute dtype.
    """
    slots, mb = block_tables.shape
    _, nh, dh = q.shape
    bs = k_pool.shape[2]
    scale = 1.0 / (dh ** 0.5)
    k_lane = k_pool[layer, block_tables].reshape(slots, mb * bs, nh, dh)
    v_lane = v_pool[layer, block_tables].reshape(slots, mb * bs, nh, dh)
    mask = jnp.arange(mb * bs)[None] <= positions[:, None]  # [slots, S]
    logits = jnp.einsum("bhd,bmhd->bhm", q, k_lane).astype(jnp.float32)
    logits = jnp.where(mask[:, None], logits * scale,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhm,bmhd->bhd", probs, v_lane)


# ---------------------------------------------------------------------------
# Pallas kernel: scalar-prefetched block-table gather + online softmax.
# ---------------------------------------------------------------------------

LANES = 128


def kernel_refusal(n_head: int, head_dim: int) -> Optional[str]:
    """Why the kernel cannot take this head geometry, or None if it can.

    The kernel cuts a pool row (`H*Dh` lanes) into static lane-aligned
    slices, so whole heads have to tile whole 128-lane groups."""
    if not HAVE_PALLAS:
        return "pallas is not in this jax build"
    if (n_head * head_dim) % LANES:
        return (f"{n_head} heads of {head_dim} make a pool row of "
                f"{n_head * head_dim} lanes, not a multiple of {LANES}")
    if head_dim % LANES and LANES % head_dim:
        return f"head dim {head_dim} neither divides nor is divided by {LANES}"
    return None


def _lane_groups(n_head: int, head_dim: int) -> Tuple[int, int]:
    """(groups, heads per group): a group is one slice of the pool row,
    `max(128, Dh)` lanes — two heads of 64, one head of 128 or 256."""
    why_not = kernel_refusal(n_head, head_dim)
    if why_not:
        raise ValueError(f"the paged decode kernel cannot run: {why_not}")
    per = max(1, LANES // head_dim)
    return n_head // per, per


def _paged_kernel(tbl_ref, pos_ref, lay_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block_size, scale):
    del lay_ref  # read by the K/V index maps only
    s, b = pl.program_id(0), pl.program_id(1)
    mb = pl.num_programs(1)
    groups, _, width = q_ref.shape[1:]

    @pl.when(b == 0)
    def _init():
        init_softmax_scratch(acc_ref, m_ref, l_ref)

    pos = pos_ref[s]

    def by_group(ref):
        """The block's `[bs, H*Dh]` rows as `[G, bs, W]`: static
        lane-aligned slices, no transpose."""
        return jnp.stack([ref[0, 0, :, g * width:(g + 1) * width]
                          for g in range(groups)])

    # Blocks past the slot's write position hold nothing visible; their
    # programs still run (the TPU grid is static) but touch no state.
    @pl.when(b * block_size <= pos)
    def _accumulate():
        q = q_ref[0]                                           # [G, per, W]
        st = jax.lax.dot_general(
            q, by_group(k_ref), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale        # [G, per, bs]
        idx = b * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_size), 2)
        st = jnp.where(idx <= pos, st, NEG_INF)
        online_softmax_update(st, by_group(v_ref), acc_ref, m_ref, l_ref,
                              (((2,), (1,)), ((0,), (0,))))    # [G, per, W]

    @pl.when(b == mb - 1)
    def _finish():
        finish_softmax_scratch(o_ref, acc_ref, l_ref, idx=0)


def paged_attention_pallas(
    q: jax.Array,             # [slots, H, Dh]
    k_pool: jax.Array,        # [L, pool_blocks, block_size, H*Dh]
    v_pool: jax.Array,        # [L, pool_blocks, block_size, H*Dh]
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [slots, max_blocks] int32
    positions: jax.Array,     # [slots] int32
    interpret: bool = False,
) -> jax.Array:
    """Pallas paged decode attention → [slots, H, Dh] in q.dtype."""
    slots, nh, dh = q.shape
    groups, per = _lane_groups(nh, dh)
    width = per * dh
    bs = k_pool.shape[2]
    mb = block_tables.shape[1]
    # Heads sharing a 128-lane group ride block-diagonally: row j of group
    # g is head g*per+j in its own Dh lanes and zeros in its neighbours',
    # so one [per, W] x [W, bs] product gives every head's own logits. The
    # rows are also the matmuls' non-contracting lhs dim, which Mosaic
    # needs and cannot make in-kernel from a packed bf16 [H, Dh] tile.
    eye = jnp.eye(per, dtype=q.dtype)[:, :, None]
    q_diag = (q.reshape(slots, groups, per, 1, dh) * eye).reshape(
        slots, groups, per, width)
    q_block = pl.BlockSpec((1, groups, per, width),
                           lambda s, b, tbl, pos, lay: (s, 0, 0, 0))
    kv_block = pl.BlockSpec(
        (1, 1, bs, nh * dh),
        lambda s, b, tbl, pos, lay: (lay[0], tbl[s, b], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_tables, positions, layer
        grid=(slots, mb),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        scratch_shapes=softmax_scratch((groups, per), width),  # fp32, VMEM
    )
    kernel = functools.partial(
        _paged_kernel, block_size=bs, scale=1.0 / (dh ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, groups, per, width), q.dtype),
        cost_estimate=pl.CostEstimate(
            # Worst case: every table entry live. 2 matmuls over the lane.
            flops=int(4 * slots * mb * bs * nh * dh),
            bytes_accessed=int(
                2 * slots * mb * bs * nh * dh * k_pool.dtype.itemsize),
            transcendentals=int(slots * mb * bs * nh),
        ),
        interpret=interpret,
    )(block_tables, positions, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q_diag, k_pool, v_pool)
    # Row j of a group holds head j's output in its own Dh lanes (the
    # others are that row's probabilities over a neighbour's values).
    out = out.reshape(slots, groups, per, per, dh)
    return jnp.stack([out[:, :, j, j] for j in range(per)],
                     axis=2).reshape(slots, nh, dh)


def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, positions,
                           impl: str = "reference"):
    """Dispatch by `serving.attention_impl` ("pallas" | "reference")."""
    if impl == "pallas":
        return paged_attention_pallas(q, k_pool, v_pool, layer, block_tables,
                                      positions)
    if impl == "reference":
        return paged_attention_reference(q, k_pool, v_pool, layer,
                                         block_tables, positions)
    raise ValueError(f"unknown paged attention impl {impl!r}")
