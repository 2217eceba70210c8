"""Ulysses-style sequence parallelism for attention (DeepSpeed-Ulysses,
arXiv:2309.14509 — re-derived for JAX shard_map; the reference has no
sequence parallelism at all, SURVEY.md §2.4/§5).

Attention needs every key/value for each query, so a sequence-sharded
layout cannot compute it locally. Ulysses swaps the sharded dimension with
two all-to-alls instead of gathering:

    [B, S/cp, H, Dh]  --all_to_all-->  [B, S, H/cp, Dh]   (shard heads)
        attention over the FULL sequence on H/cp local heads
    [B, S, H/cp, Dh]  --all_to_all-->  [B, S/cp, H, Dh]   (shard seq again)

Communication is 2 all-to-alls of the activation size — O(S·H·Dh/cp) per
chip — versus an all-gather of the whole K/V for the naive approach, and
unlike ring attention it composes with any inner attention kernel (the
full-sequence attention below can itself be the pallas flash kernel).

Used under `shard_map` over the mesh's `context` axis; wired into GPT-2
via `Config.attention_impl = "ulysses"`.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def _inner_attention(q, k, v, causal: bool):
    """[B, S, H, Dh] full-sequence attention (XLA path)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), jnp.bool_))
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ulysses_attention(
    q: jax.Array,  # [B, S, H, Dh], sequence sharded over `seq_axis`
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    seq_axis: str = "context",
    batch_axes=("data", "fsdp"),
    head_axis: str = "tensor",
    inner: str = "flash",  # full-seq kernel inside the shard: flash | dot
) -> jax.Array:
    """Attention with the sequence dim sharded over `seq_axis` via two
    all-to-alls (head-sharding inside). Falls back to plain attention when
    the ambient mesh has no (or a size-1) `seq_axis`."""
    mesh = jax.sharding.get_abstract_mesh()
    cp = (mesh.shape.get(seq_axis, 1) or 1) if mesh is not None else 1
    if cp <= 1:
        return _inner_attention(q, k, v, causal)

    # Inside the shard_map below the head dim is already sharded over
    # `head_axis`, so the all_to_all (split_axis=2) splits the LOCAL head
    # count — that, not the global count, must divide the context size.
    n_head = q.shape[2]
    tp = mesh.shape.get(head_axis, 1) or 1
    if n_head % tp != 0:
        raise ValueError(
            f"ulysses attention needs n_head ({n_head}) divisible by the "
            f"{head_axis} axis size ({tp})"
        )
    local_heads = n_head // tp
    if local_heads % cp != 0:
        raise ValueError(
            f"ulysses attention needs per-shard head count {local_heads} "
            f"(n_head {n_head} / {head_axis} size {tp}) divisible by the "
            f"{seq_axis} axis size ({cp})"
        )

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, seq_axis, head_axis, None)

    # This is Ulysses' composability advantage over ring attention: after
    # the all-to-all the shard holds the FULL sequence for a head subset,
    # so any single-device attention kernel drops in — including the
    # pallas flash kernel (which falls back to the XLA path off-TPU).
    if inner == "flash":
        from determined_tpu.ops.flash_attention import flash_attention

        def attend(qq, kk, vv):
            return flash_attention(qq, kk, vv, causal=causal)
    else:
        def attend(qq, kk, vv):
            return _inner_attention(qq, kk, vv, causal)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def sharded(ql, kl, vl):
        # local [b, S/cp, h, Dh] → [b, S, h/cp, Dh]: exchange seq chunks
        # for head chunks across the context group.
        def spread(x):
            return jax.lax.all_to_all(
                x, seq_axis, split_axis=2, concat_axis=1, tiled=True)

        def gather_back(x):
            return jax.lax.all_to_all(
                x, seq_axis, split_axis=1, concat_axis=2, tiled=True)

        out = attend(spread(ql), spread(kl), spread(vl))
        return gather_back(out)

    return sharded(q, k, v)
