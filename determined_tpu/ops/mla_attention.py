"""Latent (MLA) paged decode attention: one query token a lane against its
**latent** blocks through the block table, in the absorbed form.

Multi-head latent attention keeps, for every token and layer, one vector
`c_kv` of `kv_lora_rank` numbers (from which every head's key and value
are `W_uk c_kv` and `W_uv c_kv`) and one rotary key `k_rope` that all
heads share. The pool holds exactly that, a row a token:

    latent [L, pool_blocks, block_size, row]     row = [c_kv | k_rope | 0]

`row` is `latent_row(kv_lora_rank, rope)`: the two parts side by side,
padded with zeros to whole 128-lane groups (512 + 64 → 640), so that
every slice the kernel takes is lane-aligned and a padded layout the
compiler would make anyway is the one `cache_bytes` reports.

**Absorbed**: a head's score against a cached token is
`q_nope·(W_uk c_kv) + q_rope·k_rope = (W_uk^T q_nope)·c_kv + q_rope·k_rope`,
so with `q_lat = W_uk^T q_nope` (made outside, one small matmul) the
query is `[q_lat | q_rope | 0]`, `[H, row]`, and the H heads are the
**rows of one matmul** against the one shared latent tile — no head has
keys of its own to read. The values are the latent again: `o_lat = p ·
c_kv` (`[H, kv_lora_rank]`), and `W_uv`, then `W_o`, are applied outside.
Scores are scaled by `scale` (the model's `(nope + rope)^-1/2`), softmax
in float32.

Two implementations under `serving.attention_impl`:

  - `mla_attention_reference` — a jnp gather of each lane's blocks and a
    masked softmax over them;
  - `mla_attention_pallas` — the TPU kernel: the span walk of
    `ops/paged_attention.py` (`walk_live_spans`: a grid over lanes, a
    lane's live spans only, double-buffered hand copies, the next live
    lane's first span behind a lane's last fold, idle lanes cost nothing)
    over ONE pool, in spans of its own, `latent_span_tokens` (512 tokens:
    the K/V kernel's 128 were chosen for two pools, and with one pool of
    1,280 B a token their copies are too short to hide a fold's fixed
    cost, PERF.md §6, PR 36), and per span one `[H, row] x [row, 512]`
    product, an online-softmax fold, one `[H, 512] x [512, kv_lora_rank]`
    product. Per byte of cache it reads, the kernel does `2 H (row +
    rank) / (2 row)` ≈ 38 operations at 20 heads — far under the chip's
    197e12 / 819e9 = 240, but the 20 heads fill 20 of an MXU pass's 128
    rows, so the matmuls run at about a sixth of peak and the two bounds
    lie close; PERF.md says which side a traced run found.
"""

from __future__ import annotations

import functools
from typing import Optional

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
from determined_tpu.ops.paged_attention import LANES, walk_live_spans

if HAVE_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu


def latent_row(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of a pool row: latent and rotary key, in whole 128s."""
    return -(-(kv_lora_rank + rope_dim) // LANES) * LANES


def latent_span_tokens(block_size: int, max_blocks: int) -> int:
    """Tokens one fold of the latent kernel covers: a span of
    `max(1, 512 // block_size)` consecutive logical blocks of a lane,
    capped at the lane's whole table. Read from the shapes alone, as the
    K/V kernel's `span_tokens` is."""
    return min(max(1, 512 // block_size), max_blocks) * block_size


def kernel_refusal(kv_lora_rank: int, rope_dim: int) -> Optional[str]:
    """Why the kernel cannot take this latent geometry, or None."""
    if not HAVE_PALLAS:
        return "pallas is not in this jax build"
    if kv_lora_rank % LANES:
        return (f"a latent of {kv_lora_rank} is not whole {LANES}-lane "
                "groups: the values' slice of a row would not be aligned")
    return None


def absorbed_query(q_lat: jax.Array, q_rope: jax.Array,
                   row: int) -> jax.Array:
    """[slots, H, rank], [slots, H, rope] → the query row [slots, H, row]
    laid as the pool's: latent part, rotary part, zeros."""
    pad = row - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.pad(jnp.concatenate([q_lat, q_rope], axis=-1),
                   ((0, 0), (0, 0), (0, pad)))


def mla_attention_reference(
    q: jax.Array,             # [slots, H, row]: `absorbed_query`
    pool: jax.Array,          # [L, pool_blocks, block_size, row]
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [slots, max_blocks] int32
    positions: jax.Array,     # [slots] int32: index written this step
    rank: int,
    scale: float,
) -> jax.Array:
    """→ o_lat [slots, H, rank] in q.dtype: probabilities over the lane's
    cached latents times those latents."""
    slots, mb = block_tables.shape
    bs, row = pool.shape[2:]
    lane = pool[layer, block_tables].reshape(slots, mb * bs, row)
    logits = jnp.einsum("shr,smr->shm", q, lane,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(mb * bs)[None] <= positions[:, None]
    logits = jnp.where(mask[:, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("shm,smc->shc", probs, lane[..., :rank])


def _mla_kernel(tbl_ref, pos_ref, lay_ref, q_ref, pool_hbm, o_ref, buf_ref,
                sems, flight, acc_ref, m_ref, l_ref, *, block_size, span,
                rank, scale):
    tile = span * block_size
    pos = pos_ref[pl.program_id(0)]

    def zero_tiles():
        # Rows past a span's last live block keep what an earlier span
        # left: as keys the mask replaces their logits, as values they
        # meet a probability of exactly 0 — which must not be the NaN of
        # uninitialised memory.
        buf_ref[...] = jnp.zeros_like(buf_ref)

    def fold(i, buf):
        latents = buf_ref[buf]                               # [tile, row]
        st = jax.lax.dot_general(
            q_ref[0], latents, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, tile]
        idx = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        st = jnp.where(idx <= pos, st, NEG_INF)
        online_softmax_update(st, latents[:, :rank], acc_ref, m_ref, l_ref)

    init_softmax_scratch(acc_ref, m_ref, l_ref)
    n = walk_live_spans(
        tbl_ref, pos_ref, lay_ref[0], ((pool_hbm, buf_ref),), sems, flight,
        block_size=block_size, span=span, fold=fold,
        first_program=zero_tiles)

    @pl.when(n > 0)
    def _finish():
        finish_softmax_scratch(o_ref, acc_ref, l_ref, idx=0)

    @pl.when(n == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


def mla_attention_pallas(q, pool, layer, block_tables, positions, rank: int,
                         scale: float, interpret=False):
    """`mla_attention_reference` as a Pallas TPU kernel."""
    slots, heads, row = q.shape
    why_not = None if interpret else kernel_refusal(rank, row - rank)
    if why_not:
        raise ValueError(f"the latent decode kernel cannot run: {why_not}")
    bs = pool.shape[2]
    mb = block_tables.shape[1]
    tile = latent_span_tokens(bs, mb)
    span = tile // bs
    # The table is read a span at a time: pad it to whole spans with the
    # trash block (never fetched: it lies past every position).
    block_tables = jnp.pad(block_tables, ((0, 0), (0, -mb % span)),
                           constant_values=pool.shape[1] - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_tables, positions, layer
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, heads, row), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # copied by hand, by table
        ],
        out_specs=pl.BlockSpec((1, heads, rank), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, row), pool.dtype),        # two spans
            pltpu.SemaphoreType.DMA((2, 1)),               # [buffer, pool]
            pltpu.SMEM((2,), jnp.int32),                   # across lanes
        ] + softmax_scratch(heads, rank),                  # fp32, VMEM
    )
    kernel = functools.partial(_mla_kernel, block_size=bs, span=span,
                               rank=rank, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, rank), q.dtype),
        cost_estimate=pl.CostEstimate(
            # Worst case: every table entry live.
            flops=int(2 * slots * mb * bs * heads * (row + rank)),
            bytes_accessed=int(slots * mb * bs * row * pool.dtype.itemsize),
            transcendentals=int(slots * mb * bs * heads),
        ),
        interpret=interpret,
    )(block_tables, positions, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, pool)


@functools.partial(jax.jit, static_argnames=("rank", "scale"))
def mla_decode_attention(q, pool, layer, block_tables, positions, rank,
                         scale):
    """The kernel under a name of its own: a device trace calls a custom
    call after the innermost function traced around it."""
    return mla_attention_pallas(q, pool, layer, block_tables, positions,
                                rank, scale)


def mla_paged_decode(q, pool, layer, block_tables, positions, rank: int,
                     scale: float, impl: str = "reference"):
    """Dispatch by `serving.attention_impl` ("pallas" | "reference")."""
    if impl == "pallas":
        return mla_decode_attention(q, pool, layer, block_tables, positions,
                                    rank, scale)
    if impl == "reference":
        return mla_attention_reference(q, pool, layer, block_tables,
                                       positions, rank, scale)
    raise ValueError(f"unknown latent attention impl {impl!r}")
