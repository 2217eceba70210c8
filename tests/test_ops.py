"""Attention kernel tests: fused flash vs reference, ring vs single-device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.ops.flash_attention import _xla_attention, flash_attention
from determined_tpu.ops.ring_attention import ring_attention
from determined_tpu.parallel import MeshConfig, create_mesh


def _qkv(key, b=2, s=32, h=4, d=8, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, s, h, d), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


class TestFlashAttention:
    def test_matches_reference_causal(self):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        out = flash_attention(q, k, v, causal=True)
        ref = _xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_causality(self):
        q, k, v = _qkv(jax.random.PRNGKey(1))
        out1 = flash_attention(q, k, v, causal=True)
        k2 = k.at[:, -1].set(99.0)
        v2 = v.at[:, -1].set(99.0)
        out2 = flash_attention(q, k2, v2, causal=True)
        np.testing.assert_allclose(
            np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-5
        )


class TestPallasFlashAttention:
    """Numerical equivalence of the pallas kernel vs _xla_attention.

    Runs the TPU kernel in interpreter mode on the CPU test mesh (asked
    for here — nothing in the library interprets on its own); the Mosaic
    lowering of the same code is tests/test_chip_lowering.py, its
    numbers on the chip are chip_smoke.py.
    """

    def _run(self, fn, *args):
        from jax.experimental.pallas import tpu as pltpu

        with pltpu.force_tpu_interpret_mode():
            return fn(*args)

    def test_fwd_matches_reference(self):
        from determined_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv(jax.random.PRNGKey(0), b=1, s=256, h=2, d=64)
        out = self._run(pallas_flash_attention, q, k, v)
        ref = _xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_bwd_matches_reference(self):
        from determined_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv(jax.random.PRNGKey(3), b=1, s=256, h=2, d=64)

        def loss_p(q, k, v):
            return jnp.sum(pallas_flash_attention(q, k, v, True) ** 2)

        def loss_x(q, k, v):
            return jnp.sum(_xla_attention(q, k, v, True) ** 2)

        gp = self._run(jax.grad(loss_p, argnums=(0, 1, 2)), q, k, v)
        gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3)

    def test_multiblock_causality(self):
        """Blocks beyond the causal frontier must not leak (s > block sizes)."""
        from determined_tpu.ops import pallas_attention as pa

        q, k, v = _qkv(jax.random.PRNGKey(4), b=1, s=512, h=1, d=64)
        out1 = self._run(pa.pallas_flash_attention, q, k, v)
        k2 = k.at[:, 300:].add(50.0)
        v2 = v.at[:, 300:].add(50.0)
        out2 = self._run(pa.pallas_flash_attention, q, k2, v2)
        np.testing.assert_allclose(np.asarray(out1[:, :300]),
                                   np.asarray(out2[:, :300]), atol=1e-4)


class TestPallasReferenceEquivalence:
    """PR 18 gates: pallas ≡ reference forward AND backward (interpret mode
    on CPU — the same kernel code Mosaic compiles on TPU) across the shape
    families the trainer produces: single-block, multi-block causal,
    non-causal (padded batches run full attention over the padded length),
    and the MoE/large-head geometry (d=128, non-pow2 sequence)."""

    SHAPES = [
        pytest.param(2, 128, 2, 64, True, id="single-block-causal"),
        pytest.param(1, 256, 2, 64, True, id="multi-block-causal"),
        pytest.param(1, 256, 2, 64, False, id="non-causal-padded"),
        pytest.param(1, 384, 1, 128, True, id="moe-head128-nonpow2-seq"),
    ]

    def _run(self, fn, *args):
        from jax.experimental.pallas import tpu as pltpu

        with pltpu.force_tpu_interpret_mode():
            return fn(*args)

    @pytest.mark.parametrize("b,s,h,d,causal", SHAPES)
    @pytest.mark.parametrize("bf16", [False, True],
                             ids=["f32", "bf16"])
    def test_fwd_and_bwd_match_reference(self, b, s, h, d, causal, bf16):
        from determined_tpu.ops.flash_attention import (
            pallas_flash_attention, reference_attention)

        q, k, v = _qkv(jax.random.PRNGKey(7), b=b, s=s, h=h, d=d)

        out = self._run(pallas_flash_attention, q, k, v, causal, bf16)
        ref = reference_attention(q, k, v, causal=causal, bf16=bf16)
        # bf16 probability matmuls lose mantissa; fp32 stats keep the
        # error bounded to bf16 resolution.
        fwd_tol = dict(atol=1e-2, rtol=1e-2) if bf16 else \
            dict(atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **fwd_tol)

        def loss_p(q, k, v):
            return jnp.sum(
                pallas_flash_attention(q, k, v, causal, bf16) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(
                reference_attention(q, k, v, causal=causal,
                                    bf16=bf16) ** 2)

        gp = self._run(jax.grad(loss_p, argnums=(0, 1, 2)), q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        bwd_tol = dict(atol=5e-2, rtol=5e-2) if bf16 else \
            dict(atol=2e-3, rtol=2e-3)
        for a, r in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       **bwd_tol)

    @pytest.mark.parametrize("causal", [True, False])
    def test_reference_grad_matches_naive_dense(self, causal):
        """The reference path is exactly dense-attention arithmetic: its
        jax.grad must equal jax.grad of an inline naive implementation."""
        from determined_tpu.ops.flash_attention import reference_attention

        q, k, v = _qkv(jax.random.PRNGKey(11), b=2, s=48, h=2, d=16)

        def naive(q, k, v):
            scale = 1.0 / np.sqrt(q.shape[-1])
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            if causal:
                s = q.shape[1]
                mask = jnp.tril(jnp.ones((s, s), jnp.bool_))
                logits = jnp.where(mask, logits,
                                   jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

        def l_ref(q, k, v):
            return jnp.sum(
                reference_attention(q, k, v, causal=causal) ** 2)

        def l_naive(q, k, v):
            return jnp.sum(naive(q, k, v) ** 2)

        gr = jax.grad(l_ref, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(l_naive, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_multi_device_placement_matches_unsharded(self, devices,
                                                      monkeypatch):
        """On a multi-device mesh the dispatcher places the kernel call in
        a shard_map over the batch (data, fsdp) and heads (tensor) axes:
        values and gradients must equal the unsharded computation. The
        per-shard function is stood in by the reference (the interpreter
        simulates inter-device semaphores and crawls on an 8-device
        mesh); the kernel itself under that shard_map is lowered by
        tests/test_chip_lowering.py and run by chip_smoke.py."""
        import importlib

        # (determined_tpu.ops re-exports the function under this name)
        fa = importlib.import_module("determined_tpu.ops.flash_attention")
        monkeypatch.setattr(
            fa, "pallas_flash_attention",
            lambda q, k, v, causal, bf16: fa.reference_attention(
                q, k, v, causal=causal, bf16=bf16))
        mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices)
        q, k, v = _qkv(jax.random.PRNGKey(5), b=4, s=128, h=2, d=64)

        def loss(attend):
            return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

        placed = jax.jit(jax.value_and_grad(loss(
            lambda q, k, v: fa.flash_attention(q, k, v, impl="pallas")),
            argnums=(0, 1, 2)))
        with jax.sharding.set_mesh(mesh):
            assert fa._kernel_placement(q, None) == jax.sharding.PartitionSpec(
                ("data", "fsdp"), None, ("tensor",), None)
            out, grads = placed(q, k, v)
        ref, ref_grads = jax.value_and_grad(
            loss(fa.reference_attention), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5)
        for a, r in zip(grads, ref_grads):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=1e-5, rtol=1e-5)

    def test_explicit_pallas_unsupported_shape_raises(self):
        from determined_tpu.ops.flash_attention import flash_attention

        # d=8 can't tile on the MXU: an explicit pallas must say so, not
        # hand back the reference path under the kernel's name.
        q, k, v = _qkv(jax.random.PRNGKey(12), b=1, s=32, h=2, d=8)
        with pytest.raises(ValueError, match="not a multiple of 128"):
            flash_attention(q, k, v, causal=True, impl="pallas")


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_single_device(self, devices, causal):
        mesh = create_mesh(MeshConfig(data=2, context=4), devices)
        q, k, v = _qkv(jax.random.PRNGKey(0), b=4, s=32)
        ref = _xla_attention(q, k, v, causal=causal)
        with jax.sharding.set_mesh(mesh):
            out = jax.jit(
                lambda q, k, v: ring_attention(q, k, v, causal=causal, mesh=mesh)
            )(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_context_axis_size_one_falls_back(self, devices):
        mesh = create_mesh(MeshConfig(data=8), devices)
        q, k, v = _qkv(jax.random.PRNGKey(2))
        with jax.sharding.set_mesh(mesh):
            out = ring_attention(q, k, v, mesh=mesh)
        ref = _xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_gpt2_with_ring_attention(self, devices):
        """End-to-end: GPT-2 tiny configured with attention_impl='ring'."""
        from determined_tpu.models import gpt2

        cfg_ring = gpt2.Config(
            vocab_size=128, n_positions=64, d_model=32, n_layer=1, n_head=2,
            attention_impl="ring", remat=False, dtype=jnp.float32,
        )
        cfg_dot = gpt2.Config(
            vocab_size=128, n_positions=64, d_model=32, n_layer=1, n_head=2,
            attention_impl="dot", remat=False, dtype=jnp.float32,
        )
        params = gpt2.init(jax.random.PRNGKey(0), cfg_dot)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
        ref = gpt2.apply(params, tokens, cfg_dot)
        mesh = create_mesh(MeshConfig(data=2, context=4), devices)
        with jax.sharding.set_mesh(mesh):
            out = jax.jit(lambda p, t: gpt2.apply(p, t, cfg_ring))(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
