"""RMS norm and rotary embedding as the served families share them
(`serve/falcon_h1.py`, `serve/glm4_moe_lite.py`): float32 inside, the
input's dtype out."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rotary(x, positions, theta: float):
    """x [T, heads, Dh] at `positions` [T], the rotate_half convention."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None]
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., dh // 2:], x32[..., :dh // 2]], -1)
    return (x32 * cos + turned * sin).astype(x.dtype)
