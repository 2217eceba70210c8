"""Pallas TPU paged decode-attention (vLLM-style block-table gather).

The serving decode hot path (ROADMAP item 2; docs/serving.md "Paged KV &
prefix caching"): each decode step, every active slot attends its single
query token over K/V that live in a **block pool** — `[L, num_blocks,
block_size, H*Dh]`, every layer in one buffer and a token's heads side by
side in one row — addressed through a per-slot **block table** (`[slots,
max_blocks]` int32, logical block i of the sequence → pool block
`table[s, i]`). `H` there is the number of K/V heads: a model whose query
heads share K/V heads (grouped-query attention) has a row of `Hkv*Dh`
lanes, and both implementations read the number off the pool's row. No
`slots × max_seq` lane is reserved: HBM holds exactly
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

  - `paged_attention_pallas` — the TPU kernel. It does work in
    proportion to the live context. The grid is over lanes, `(slots,)`:
    one program a lane, and no program per table entry. The block table,
    positions and the layer ride `PrefetchScalarGridSpec` scalar
    prefetch; the pools stay where they rest (`pl.ANY`) and the program
    copies what it needs by hand. It walks the lane's table a **span**
    at a time — `span_tokens`: `max(1, 128 // block_size)` consecutive
    logical blocks, 8 blocks = 128 tokens at `block_size` 16, capped at
    the table's length, read from the shapes and set by nothing else —
    in a loop that ends at the lane's own position: `position // 128 +
    1` folds, so a span past the position is neither fetched nor
    multiplied, and of the last span only the blocks up to the position
    are copied (`pool[layer, table[s, b]]` → rows `[j*bs, (j+1)*bs)` of
    a `[span*bs, H*Dh]` VMEM tile: a block is rows, so a span's blocks
    stack with no transpose). The tile is double-buffered: span i+1 is in
    flight while span i is folded, and behind a lane's last fold the
    NEXT live lane's first span is started (two SMEM words carry "which
    buffer, whose span" from program to program), so no program opens on
    a cold fetch. **An idle lane** — its table is the trash block
    throughout and its position 0, which the kernel sees as `table[s, 0]
    == pool_blocks - 1` — fetches nothing, multiplies nothing and writes
    a row of zeros. Each fold is one online-softmax step over the span's
    128 keys: fp32 running max `m`, normalizer `l` and accumulator `acc`
    in VMEM scratch, the mask `index <= position`. Heads are static
    lane-aligned slices of the tile, G groups of W = max(128, Dh) lanes:
    two heads of 64 share a group, with q laid block-diagonally (`[G,
    per, W]`, made outside the kernel) so one product gives each head its
    own logits; nothing is transposed. With shared K/V heads (`Hkv < H`,
head dim a multiple of 128) a group is a K/V head and its rows are the
query heads that share it (`[Hkv, H/Hkv, Dh]`), no block diagonal; with
`Hkv == H` the traced program is what it was. Both inner products are matmuls
    batched over the LEADING group dim with 3-D operands (`[G, per, W] x
    [G, span*bs, W]`) — the form Mosaic lowers; a 2-D lhs with a batch
    dim and no non-contracting dim is refused by its dot-dimension
    parser. A geometry that cannot be cut into such groups
    (`kernel_refusal`) raises with the reason. Compiles through Mosaic
    unless the caller passes `interpret=True` (tests).

`live_spans` is the same arithmetic on the host: the engine sums it into
`decode_spans_live` / `decode_spans_grid` (`engine.stats()`, `/v1/stats`),
spans that held a visible key and spans launched, per layer. The loop
ends at the position, so the two are equal by construction; a grid over
`(slots, table)` would launch the second whatever the first.

Inactive slots point every table entry at a reserved trash block and sit
at position 0: the reference attends garbage there that the batcher
discards, the kernel writes zeros — either way the executable never
depends on which slots are live.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
    k_pool: jax.Array,        # [L, pool_blocks, block_size, Hkv*Dh]
    v_pool: jax.Array,        # [L, pool_blocks, block_size, Hkv*Dh]
    layer: jax.Array,         # scalar int32: the layer attended
    block_tables: jax.Array,  # [slots, max_blocks] int32 pool indices
    positions: jax.Array,     # [slots] int32: index written this step
) -> jax.Array:
    """Pure-jnp paged decode attention → [slots, H, Dh] in q.dtype.

    Gathers each slot's lane (`pool[layer, table]` → `[max_blocks ×
    block_size, Hkv, Dh]`: the lanes' blocks, never a layer of the pool)
    and attends over it: fp32 logits, `index <= position` mask, fp32
    softmax, probs cast back to the compute dtype. The pool's row says how
    many K/V heads there are; with fewer than query heads, query head i
    reads K/V head `i // (H / Hkv)`.
    """
    slots, mb = block_tables.shape
    _, nh, dh = q.shape
    bs = k_pool.shape[2]
    scale = 1.0 / (dh ** 0.5)
    hkv = k_pool.shape[-1] // dh
    # Two bodies on purpose: with `Hkv == H` the grouped einsum is this
    # one with a row axis of 1, but it lowers to other HLO, and ISSUE 32
    # holds a K/V head per query head to the traced program it had (the
    # engine calls' lowered text is compared side against side on the CPU,
    # where this reference is the path).
    if hkv != nh:
        return _grouped_reference(q, k_pool, v_pool, layer, block_tables,
                                  positions, hkv)
    k_lane = k_pool[layer, block_tables].reshape(slots, mb * bs, nh, dh)
    v_lane = v_pool[layer, block_tables].reshape(slots, mb * bs, nh, dh)
    mask = jnp.arange(mb * bs)[None] <= positions[:, None]  # [slots, S]
    logits = jnp.einsum("bhd,bmhd->bhm", q, k_lane).astype(jnp.float32)
    logits = jnp.where(mask[:, None], logits * scale,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhm,bmhd->bhd", probs, v_lane)


def _grouped_reference(q, k_pool, v_pool, layer, block_tables, positions,
                       hkv: int):
    """The reference with the query heads that share a K/V head as rows
    of that head."""
    slots, mb = block_tables.shape
    _, nh, dh = q.shape
    bs = k_pool.shape[2]
    k_lane = k_pool[layer, block_tables].reshape(slots, mb * bs, hkv, dh)
    v_lane = v_pool[layer, block_tables].reshape(slots, mb * bs, hkv, dh)
    mask = jnp.arange(mb * bs)[None] <= positions[:, None]  # [slots, S]
    qg = q.reshape(slots, hkv, nh // hkv, dh)
    logits = jnp.einsum("bgrd,bmgd->bgrm", qg, k_lane).astype(jnp.float32)
    logits = jnp.where(mask[:, None, None], logits / (dh ** 0.5),
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrm,bmgd->bgrd", probs, v_lane).reshape(
        slots, nh, dh)


# ---------------------------------------------------------------------------
# Pallas kernel: scalar-prefetched block-table gather + online softmax.
# ---------------------------------------------------------------------------

LANES = 128


def kernel_refusal(q_heads: int, kv_heads: int,
                   head_dim: int) -> Optional[str]:
    """Why the kernel cannot take this head geometry, or None if it can.

    The kernel cuts a pool row (`Hkv*Dh` lanes) into static lane-aligned
    slices, so whole heads have to tile whole 128-lane groups; query heads
    that share a K/V head are the rows of that head's slice, which then
    has to be a whole number of 128-lane groups by itself."""
    if not HAVE_PALLAS:
        return "pallas is not in this jax build"
    if q_heads % kv_heads:
        return f"{q_heads} query heads do not divide over {kv_heads} K/V heads"
    if (kv_heads * head_dim) % LANES:
        return (f"{kv_heads} heads of {head_dim} make a pool row of "
                f"{kv_heads * head_dim} lanes, not a multiple of {LANES}")
    if head_dim % LANES and LANES % head_dim:
        return f"head dim {head_dim} neither divides nor is divided by {LANES}"
    if kv_heads != q_heads and head_dim % LANES:
        return (f"shared K/V heads of {head_dim} are not whole {LANES}-lane "
                "groups")
    return None


def _lane_groups(q_heads: int, kv_heads: int,
                 head_dim: int) -> Tuple[int, int, int]:
    """(groups, rows per group, lanes per group) of the query tile.

    A K/V head of its own for each query head: a group is one slice of
    the pool row, `max(128, Dh)` lanes — two heads of 64 (laid block-
    diagonally, a row each), one head of 128 or 256. Shared K/V heads: a
    group is a K/V head and its rows are the query heads that share it,
    no block diagonal."""
    why_not = kernel_refusal(q_heads, kv_heads, head_dim)
    if why_not:
        raise ValueError(f"the paged decode kernel cannot run: {why_not}")
    if kv_heads != q_heads:
        return kv_heads, q_heads // kv_heads, head_dim
    per = max(1, LANES // head_dim)
    return q_heads // per, per, per * head_dim


def span_tokens(block_size: int, max_blocks: int) -> int:
    """Tokens one fold of the kernel's online softmax covers: a span of
    `max(1, 128 // block_size)` consecutive logical blocks of a lane,
    capped at the lane's whole table. Read from the shapes alone."""
    return min(max(1, LANES // block_size), max_blocks) * block_size


def live_spans(positions, live, tile: int) -> int:
    """Spans a decode call folds in one layer: `position // tile + 1`
    for each live lane, none for an idle one, where `tile` is the tokens
    of the family's decode kernel's span (host arithmetic on what the
    engine already holds; `engine.stats()` sums it)."""
    return int(np.sum((np.asarray(positions) // tile + 1)[np.asarray(live)]))


def walk_live_spans(tbl_ref, pos_ref, layer, pools, sems, flight, *,
                    block_size, span, fold, first_program=None):
    """The walk every paged decode kernel makes over its lane's table (the
    grid is over lanes): a span of `span` blocks at a time up to the
    lane's position, the span's live blocks copied by hand from each pool
    of `pools` — `(pool in HBM, [2, span*block_size, row] VMEM buffer)`
    pairs, copied alike under `sems[buffer, pool]` — and `fold(i, buf)`
    called with span i resident in buffer `buf`. Span i+1 is in flight
    while span i is folded, and behind a lane's last fold the NEXT live
    lane's first span is started (`flight`, two SMEM words, carries "which
    buffer, whose span" from program to program). An idle lane — its
    table the trash block throughout — copies and folds nothing.
    `first_program()` runs once, in the first program, before any copy.
    → the lane's number of spans."""
    s, slots = pl.program_id(0), pl.num_programs(0)
    tile = span * block_size
    trash = pools[0][0].shape[1] - 1

    def spans_of(lane):
        """Spans that hold a visible key: none for an idle lane, whose
        table is the trash block throughout."""
        return jnp.where(tbl_ref[lane, 0] == trash, 0,
                         pos_ref[lane] // tile + 1)

    def copy_blocks(lane, i, buf, act):
        """Start or wait for (`act`) the copies of the blocks of span i
        of `lane` into buffer `buf`, the blocks up to the lane's position
        and no others: a block is `block_size` rows of a pool, so the
        span's blocks stack as rows of one tile."""
        def block(j, _):
            at = tbl_ref[lane, i * span + j]
            rows = pl.ds(pl.multiple_of(j * block_size, block_size),
                         block_size)
            for which, (hbm, vmem) in enumerate(pools):
                act(pltpu.make_async_copy(
                    hbm.at[layer, at], vmem.at[buf, rows],
                    sems.at[buf, which]))

        jax.lax.fori_loop(
            0, jnp.minimum(span, (pos_ref[lane] - i * tile) // block_size
                           + 1), block, None)

    def start(lane, i, buf):
        copy_blocks(lane, i, buf, lambda copy: copy.start())

    def wait(lane, i, buf):
        copy_blocks(lane, i, buf, lambda copy: copy.wait())

    @pl.when(s == 0)
    def _first_program():
        if first_program is not None:
            first_program()
        flight[0] = 0    # the buffer the next first span lands in
        flight[1] = -1   # the lane whose first span is already in flight

    n = spans_of(s)
    buf0 = flight[0]

    @pl.when(jnp.logical_and(n > 0, flight[1] != s))
    def _own_first_span():
        start(s, 0, buf0)

    def step(i, _):
        buf = (buf0 + i) % 2

        @pl.when(i + 1 < n)
        def _next_span():
            start(s, i + 1, 1 - buf)

        @pl.when(i + 1 == n)
        def _next_lane():
            # The next live lane's first span rides behind this lane's
            # last fold, so that no program opens on a cold fetch.
            nxt = jax.lax.while_loop(
                lambda j: jnp.logical_and(
                    j < slots, spans_of(jnp.minimum(j, slots - 1)) == 0),
                lambda j: j + 1, s + 1)
            flight[1] = nxt

            @pl.when(nxt < slots)
            def _():
                start(jnp.minimum(nxt, slots - 1), 0, 1 - buf)

        wait(s, i, buf)
        fold(i, buf)

    jax.lax.fori_loop(0, n, step, None)
    flight[0] = (buf0 + n) % 2
    return n


def _paged_kernel(tbl_ref, pos_ref, lay_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sems, flight, acc_ref, m_ref, l_ref, *,
                  block_size, span, scale):
    groups, _, width = q_ref.shape[1:]
    tile = span * block_size
    pos = pos_ref[pl.program_id(0)]

    def zero_values():
        # A block past a span's last live one is not fetched, and its
        # rows of the tile keep what an earlier span left. A stale key
        # gives a logit the mask replaces; a stale value is multiplied by
        # a masked probability, exactly 0, and must not be the NaN of
        # uninitialised memory: zeros first.
        vbuf[...] = jnp.zeros_like(vbuf)

    def by_group(ref, buf):
        """The tile's `[span*bs, H*Dh]` rows as `[G, span*bs, W]`: static
        lane-aligned slices, no transpose."""
        return jnp.stack([ref[buf, :, g * width:(g + 1) * width]
                          for g in range(groups)])

    def fold(i, buf):
        st = jax.lax.dot_general(
            q_ref[0], by_group(kbuf, buf), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale      # [G, per, tile]
        idx = i * tile + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, tile), 2)
        st = jnp.where(idx <= pos, st, NEG_INF)
        online_softmax_update(st, by_group(vbuf, buf), acc_ref, m_ref,
                              l_ref, (((2,), (1,)), ((0,), (0,))))

    init_softmax_scratch(acc_ref, m_ref, l_ref)
    n = walk_live_spans(
        tbl_ref, pos_ref, lay_ref[0], ((k_hbm, kbuf), (v_hbm, vbuf)), sems,
        flight, block_size=block_size, span=span, fold=fold,
        first_program=zero_values)

    @pl.when(n > 0)
    def _finish():
        finish_softmax_scratch(o_ref, acc_ref, l_ref, idx=0)

    @pl.when(n == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


def paged_attention_pallas(
    q: jax.Array,             # [slots, H, Dh]
    k_pool: jax.Array,        # [L, pool_blocks, block_size, Hkv*Dh]
    v_pool: jax.Array,        # [L, pool_blocks, block_size, Hkv*Dh]
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [slots, max_blocks] int32
    positions: jax.Array,     # [slots] int32
    interpret: bool = False,
) -> jax.Array:
    """Pallas paged decode attention → [slots, H, Dh] in q.dtype."""
    slots, nh, dh = q.shape
    row = k_pool.shape[-1]
    hkv = row // dh
    groups, per, width = _lane_groups(nh, hkv, dh)
    bs = k_pool.shape[2]
    mb = block_tables.shape[1]
    tile = span_tokens(bs, mb)
    span = tile // bs
    # The table is read a span at a time: pad it to whole spans with the
    # trash block (never fetched: it lies past every position).
    block_tables = jnp.pad(block_tables, ((0, 0), (0, -mb % span)),
                           constant_values=k_pool.shape[1] - 1)
    # Heads sharing a 128-lane group ride block-diagonally: row j of group
    # g is head g*per+j in its own Dh lanes and zeros in its neighbours',
    # so one [per, W] x [W, tile] product gives every head's own logits.
    # The rows are also the matmuls' non-contracting lhs dim, which Mosaic
    # needs and cannot make in-kernel from a packed bf16 [H, Dh] tile.
    if hkv == nh:
        eye = jnp.eye(per, dtype=q.dtype)[:, :, None]
        q_diag = (q.reshape(slots, groups, per, 1, dh) * eye).reshape(
            slots, groups, per, width)
    else:   # the rows of a K/V head are the query heads that share it
        q_diag = q.reshape(slots, groups, per, width)
    q_block = pl.BlockSpec((1, groups, per, width),
                           lambda s, tbl, pos, lay: (s, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)  # copied by hand, by table
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_tables, positions, layer
        grid=(slots,),
        in_specs=[q_block, pool, pool],
        out_specs=q_block,
        scratch_shapes=[
            pltpu.VMEM((2, tile, row), k_pool.dtype),      # K, two spans
            pltpu.VMEM((2, tile, row), v_pool.dtype),      # V, two spans
            pltpu.SemaphoreType.DMA((2, 2)),               # [buffer, K|V]
            pltpu.SMEM((2,), jnp.int32),                   # across lanes
        ] + softmax_scratch((groups, per), width),         # fp32, VMEM
    )
    kernel = functools.partial(
        _paged_kernel, block_size=bs, span=span, scale=1.0 / (dh ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, groups, per, width), q.dtype),
        cost_estimate=pl.CostEstimate(
            # Worst case: every table entry live. 2 matmuls over the lane.
            flops=int(4 * slots * mb * bs * nh * dh),
            bytes_accessed=int(
                2 * slots * mb * bs * row * k_pool.dtype.itemsize),
            transcendentals=int(slots * mb * bs * nh),
        ),
        interpret=interpret,
    )(block_tables, positions, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q_diag, k_pool, v_pool)
    if hkv != nh:
        return out.reshape(slots, nh, dh)
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
