"""Shared plumbing for the platform's Pallas TPU attention kernels.

Both attention kernels — the serving decode kernel
(`ops/paged_attention.py`, PR 11) and the training flash kernel
(`ops/flash_attention.py`) — are online-softmax accumulators walking a
grid of K/V tiles: fp32 running max `m`, normalizer `l`, and output
accumulator `acc` live in VMEM scratch across the innermost grid
dimension, initialized at the first tile and normalized out at the last.
This module is the single home for that machinery so the two kernels
cannot drift (the decode kernel once carried its own private copies):

  - availability (`HAVE_PALLAS`). There is no interpret-mode policy
    here: the kernels compile through Mosaic unless the caller asks
    otherwise — a test passes `interpret=True` or traces under
    `pltpu.force_tpu_interpret_mode()`; nothing sniffs the backend, so a
    kernel can never be selected and then quietly interpreted;
  - grid sizing (`pick_blocks`): MXU/VMEM-friendly tile edges that
    divide the sequence;
  - VMEM scratch shapes for the online-softmax state
    (`softmax_scratch`);
  - the accumulate step itself (`online_softmax_update`): one masked
    logits tile folded into (acc, m, l) — written once, used by decode
    and by the training forward kernel.

Keep this module import-safe without pallas: the serving reference path
and CPU-only deploys must not pay a hard pallas dependency.
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp

try:  # pallas is optional at import time
    from jax.experimental import pallas as pl  # noqa: F401
    from jax.experimental.pallas import tpu as pltpu

    HAVE_PALLAS = True
except ImportError:  # pragma: no cover - pallas not in this build
    HAVE_PALLAS = False

# Masked logits value. Not -inf: exp(-inf - -inf) is NaN when an entire
# row is masked (the first causal tile's padding rows); a large-negative
# finite value keeps exp() at exactly 0.0 without poisoning m.
NEG_INF = -1e30


def pick_blocks(s: int, max_block: int = 512) -> Tuple[int, int]:
    """(block_q, block_k) tile edges tuned for v5e VMEM; both divide s.

    512 keeps the fp32 logits tile (512x512x4B = 1 MiB) plus the q/k/v/o
    tiles comfortably inside the ~16 MiB VMEM budget with room for the
    pipeline's double buffering; shorter sequences halve down until the
    edge divides s.
    """
    block_q = min(max_block, s)
    block_k = min(max_block, s)
    while s % block_q:
        block_q //= 2
    while s % block_k:
        block_k //= 2
    return block_q, block_k


def softmax_scratch(rows: Union[int, Tuple[int, ...]], d: int):
    """VMEM scratch for one online-softmax accumulator: [acc, m, l].

    `rows` is the per-program row shape (query rows for the training
    kernel; `(groups, heads per group)` for the decode kernel, whose
    matmuls are batched over lane groups); `d` the output feature depth.
    All three are fp32 regardless of the i/o dtype — the running
    statistics are the one place bf16 is never acceptable (exp/sum
    cancellation), which is also why they live in dedicated scratch
    rather than riding the (possibly bf16) output block.
    """
    if not HAVE_PALLAS:  # pragma: no cover - guarded by callers
        raise RuntimeError("pallas unavailable in this jax build")
    lead = (rows,) if isinstance(rows, int) else tuple(rows)
    return [
        pltpu.VMEM(lead + (d,), jnp.float32),  # acc
        pltpu.VMEM(lead + (1,), jnp.float32),  # running max m
        pltpu.VMEM(lead + (1,), jnp.float32),  # running normalizer l
    ]


def init_softmax_scratch(acc_ref, m_ref, l_ref) -> None:
    """Reset (acc, m, l) at the first tile of a program's accumulation."""
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def online_softmax_update(st, v, acc_ref, m_ref, l_ref,
                          dimension_numbers=(((1,), (0,)), ((), ()))):
    """Fold one masked logits tile into the VMEM (acc, m, l) state.

    st: fp32 logits tile [..., rows, cols] with masked entries at NEG_INF;
    v:  the matching value tile, contracted with the tile's probabilities
        per `dimension_numbers` (default: plain [cols, d] matmul; the
        decode kernel batches over a leading heads dim).

    The p·v matmul runs in the value dtype (bf16 inputs hit the MXU's
    bf16 path) but accumulates into fp32 (`preferred_element_type`) —
    the split the online-softmax statistics demand: m/l/acc stay exact
    while the O(s²·d) multiply rides the fast path.
    """
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(st, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(st - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers,
        preferred_element_type=jnp.float32)


def finish_softmax_scratch(o_ref, acc_ref, l_ref, idx=...) -> None:
    """Normalize the accumulator out to the output block's dtype.

    `idx` addresses the output block when it carries a leading unit dim
    (the decode kernel's (1, G, per, W) slot block passes idx=0)."""
    o_ref[idx] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
