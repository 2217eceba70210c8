"""Operations and bytes the attention kernels' calls need, from their
shapes alone — what the algorithm requires, not what an implementation
happens to do. A roofline share is this floor over the measured time, so
recomputation and padding count against the kernel, never for it.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional


def flash_attention_work(batch: int, seq: int, heads: int, head_dim: int,
                         itemsize: int = 2, causal: bool = True,
                         backward: bool = True,
                         kv_heads: Optional[int] = None) -> Dict[str, float]:
    """Causal self-attention of `heads` query heads over [batch, seq,
    kv_heads, head_dim] keys and values (a K/V head for each query head
    unless `kv_heads` says fewer).

    Forward is two matmuls (QK^T, PV); backward four more (dV, dP, dQ,
    dK): each 2*B*H*S*S*Dh FLOPs, halved under the causal mask. The
    recomputation of QK^T that a flash backward makes is not required by
    the mathematics and is not counted. Bytes: forward reads Q, K, V and
    writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV — half
    of either list is as wide as the queries, half as the keys."""
    per_matmul = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        per_matmul /= 2.0
    elems = batch * seq * (heads + (kv_heads or heads)) * head_dim
    n_matmul, n_arrays = (6, 6) if backward else (2, 2)
    return {"flops": n_matmul * per_matmul,
            "bytes": float(n_arrays * elems * itemsize)}


def paged_decode_work(context_lengths: Iterable[int], heads: int,
                      head_dim: int, itemsize: int = 2,
                      kv_heads: Optional[int] = None) -> Dict[str, float]:
    """One decode call of one layer: each live slot's single query attends
    over its own context. Per slot of context n: K and V of n tokens are
    read once (2*n*Hkv*Dh elements; Hkv = H unless `kv_heads` says fewer),
    q read and the output written (2*H*Dh), and the two matmuls cost
    4*n*H*Dh FLOPs. Bandwidth-bound by far."""
    kv_heads = kv_heads or heads
    flops = bytes_ = 0.0
    for n in context_lengths:
        flops += 4.0 * n * heads * head_dim
        bytes_ += (2.0 * n * kv_heads + 2.0 * heads) * head_dim * itemsize
    return {"flops": flops, "bytes": bytes_}


def floor_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def train_flops_per_token(n_params: int, attn_layers: int, attn_width: int,
                          seq_len: int) -> float:
    """Forward + backward FLOPs a token requires: 6 per parameter its
    forward multiplies plus the attention term 12*L*w*s over the layers
    that attend, w the query heads times their size (the PaLM appendix-B
    count, unmasked; recomputation not counted)."""
    return 6.0 * n_params + 12.0 * attn_layers * attn_width * seq_len
