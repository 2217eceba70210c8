"""Ring attention — causal self-attention with the sequence sharded over the
`context` mesh axis.

First-class context parallelism (absent in the reference, SURVEY.md §2.4/§5):
each device holds S/n of the sequence; K/V blocks rotate around the ICI ring
via `ppermute` while every device accumulates flash-style (running max m,
normaliser l, weighted output o) against its local Q block. Communication
overlaps with the block matmuls and total memory is O(S/n) per device —
sequence length scales linearly with ring size.

Layout contract: q,k,v are [B, S, H, D] sharded P(batch, "context", heads, -)
outside; inside shard_map each device sees [B, S/n, H, D].
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _block_attend(q, k, v, mask, sm_scale):
    """One q-block × kv-block flash partial: returns (m, l, o) in fp32.

    q: [B,Sq,H,D], k/v: [B,Sk,H,D], mask: [Sq,Sk] bool or None.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # [B,H,Sq]
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    m_safe = jnp.where(m == NEG_INF, 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_safe, l, o


def _combine(m1, l1, o1, m2, l2, o2):
    """Merge two flash partials with the standard rescaling identity."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    # a*: [B,H,Sq] → broadcast onto o: [B,Sq,H,D]
    o = o1 * a1.transpose(0, 2, 1)[..., None] + o2 * a2.transpose(0, 2, 1)[..., None]
    return m, l, o


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Runs inside shard_map; q,k,v are the device-local blocks."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s_q, h, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)

    causal_mask = jnp.tril(jnp.ones((s_q, s_q), jnp.bool_)) if causal else None

    m0 = jnp.full((b, h, s_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_q), jnp.float32)
    o0 = jnp.zeros((b, s_q, h, d), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(step, carry):
        m, l, o, k_cur, v_cur = carry
        kv_idx = (my_idx - step) % axis_size
        # Block-level causality: kv block strictly before ours → unmasked;
        # our own block → triangular; after ours → skipped entirely.
        def attend(mask):
            bm, bl, bo = _block_attend(q, k_cur, v_cur, mask, sm_scale)
            return _combine(m, l, o, bm, bl, bo)

        if causal:
            m2, l2, o2 = jax.lax.cond(
                kv_idx < my_idx,
                lambda: attend(None),
                lambda: jax.lax.cond(
                    kv_idx == my_idx,
                    lambda: attend(causal_mask),
                    lambda: (m, l, o),
                ),
            )
        else:
            m2, l2, o2 = attend(None)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return m2, l2, o2, k_next, v_next

    m, l, o, _, _ = jax.lax.fori_loop(0, axis_size, body, (m0, l0, o0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (can't happen causal)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [B, S, H, D], S sharded over `axis_name`
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "context",
    causal: bool = True,
    mesh=None,
) -> jax.Array:
    """Causal ring attention over the ambient mesh's `axis_name` ring.

    Falls back to single-block fused attention when the axis has size 1
    (including CPU test meshes with context=1).
    """
    if mesh is None:
        # Works both inside jit (abstract mesh from the ambient set_mesh) and
        # outside (set_mesh also installs the abstract mesh).
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            from determined_tpu.ops.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=causal)
    if mesh.shape.get(axis_name, 1) == 1:
        from determined_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal)

    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    head_axis = "tensor" if "tensor" in mesh.axis_names else None
    spec = P(batch_axes or None, axis_name, head_axis, None)
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
