"""Flash attention for TRAINING: Pallas fwd+bwd kernel, reference path,
and the `optimizations.attention_impl` dispatcher.

Three interchangeable implementations, selected by the experiment
config's `optimizations.attention_impl` block (threaded through
`gpt2.Config.attention_impl`; docs/training-perf.md):

  - `pallas` — the TPU kernel below. Tiled causal attention with online
    softmax: the S×S logits matrix never round-trips through HBM — each
    [block_q, block_k] tile lives in VMEM, the fp32 running max `m`,
    normalizer `l`, and accumulator `acc` sit in VMEM *scratch* across
    the k-tile grid dimension (`ops/_pallas_common.py`, the exact
    machinery of the serving decode kernel `ops/paged_attention.py`),
    and only the [S, D] output plus a per-row logsumexp (for the
    backward) are written back. Causal block skipping: tiles strictly
    above the diagonal are `pl.when`-predicated out AND their K/V
    BlockSpec index clamps to the causal frontier, so a skipped tile
    costs neither FLOPs nor a fresh DMA (consecutive programs with the
    same block index skip the re-fetch). Backward is the standard
    two-kernel flash split — dq grids over q tiles, dk/dv over k tiles —
    with p = exp(s - L) recomputed from the saved logsumexp and
    delta = rowsum(dO ∘ O) precomputed in XLA. Mosaic kernels cannot be
    partitioned by GSPMD, so on a multi-device mesh the dispatcher wraps
    the call in `jax.shard_map` over the mesh axes the rules give the
    batch and heads dims. The kernels run interpreted only when a test
    asks (`interpret=True` / `pltpu.force_tpu_interpret_mode()`).

  - `reference` — pure-jnp with exactly the dense-attention arithmetic
    (fp32 logits, causal mask, fp32 softmax). Differentiable by plain
    `jax.grad`; tests/test_ops.py asserts the pallas backward against
    it. What `auto` resolves to off-TPU, and (announced in the log) for
    shapes the kernel cannot tile.

  - `dense` — the legacy XLA path, byte-for-byte the pre-flash
    `_xla_attention` (kept as the A/B baseline for `make bench-train`).

The bf16 option (`bf16=True` / `optimizations.attention_bf16`): the
probability tile is cast to bfloat16 for the P·V (and dS·K / dS^T·Q)
matmuls so they ride the MXU's bf16 path; the QK^T products and the
online-softmax statistics m/l/acc always accumulate in fp32 — the one
place bf16 is never acceptable (exp/sum cancellation). The bf16 numerics
gate lives in tests/test_train_perf.py (loss-trajectory parity vs f32).

Layout: kernels operate on [BH, S, D] (batch×heads flattened); the
public wrappers accept the model's [B, S, H, D] and transpose.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from determined_tpu.ops._pallas_common import (
    HAVE_PALLAS,
    NEG_INF,
    finish_softmax_scratch,
    init_softmax_scratch,
    online_softmax_update,
    pick_blocks,
    softmax_scratch,
)
from determined_tpu.parallel.mesh import ambient_mesh, on_tpu
from determined_tpu.parallel.sharding import LogicalRules

logger = logging.getLogger("determined_tpu.ops")

if HAVE_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

TRAIN_ATTENTION_IMPLS = ("auto", "pallas", "reference", "dense")


def resolve_attention_impl(setting: Optional[str] = None,
                           devices: Optional[Iterable[Any]] = None) -> str:
    """`optimizations.attention_impl` → the concrete implementation.

    auto (the default) picks pallas when compiling for a TPU (`on_tpu`:
    `devices` if given, else the ambient mesh) and reference elsewhere;
    the legacy model-config spellings stay accepted ("flash" == auto,
    "dot" == dense) so pre-PR-18 configs keep their exact behavior.
    """
    s = setting or "auto"
    if s in ("auto", "flash"):
        return "pallas" if on_tpu(devices) else "reference"
    if s == "dot":
        return "dense"
    if s not in ("pallas", "reference", "dense"):
        raise ValueError(
            f"attention_impl must be one of {TRAIN_ATTENTION_IMPLS} "
            f"(or legacy flash/dot), got {setting!r}")
    return s


# --------------------------------------------------------------------------
# reference / dense paths
# --------------------------------------------------------------------------


def reference_attention(q, k, v, causal: bool = True,
                        bf16: bool = False) -> jax.Array:
    """Pure-jnp attention with exactly the dense arithmetic.

    q,k,v: [B, S, H, D] → [B, S, H, D]. fp32 logits and softmax; with
    bf16=True the probabilities are cast to bfloat16 for the P·V matmul
    (the kernel's bf16 option, mirrored so pallas-vs-reference stays an
    apples-to-apples equivalence check in both modes).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), k=s_k - s_q)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = probs.astype(jnp.bfloat16 if bf16 else q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)


def _xla_attention(q, k, v, causal: bool) -> jax.Array:
    """The legacy dense path (attention_impl: dense), unchanged — the
    `make bench-train` A/B baseline and the pre-PR-18 default."""
    return reference_attention(q, k, v, causal=causal, bf16=False)


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, bf16):
    qi, ki = pl.program_id(1), pl.program_id(2)
    num_k = pl.num_programs(2)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        init_softmax_scratch(acc_ref, m_ref, l_ref)

    # Causal frontier: tiles strictly above the diagonal contribute
    # nothing. Their programs still run (the TPU grid is static) but the
    # body is predicated out and the BlockSpec index_map clamps their K/V
    # fetch to the frontier tile — no FLOPs, no fresh DMA.
    visible = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0]                       # [block_q, d]
        k_blk = k_ref[0]                   # [block_k, d]
        st = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                          # [block_q, block_k] fp32
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            st = jnp.where(rows >= cols, st, NEG_INF)
        # bf16 option: P·V in bf16 on the MXU; fp32 otherwise. The m/l
        # statistics inside the update are fp32 either way.
        v_blk = v_ref[0] if bf16 else v_ref[0].astype(jnp.float32)
        online_softmax_update(st, v_blk, acc_ref, m_ref, l_ref)

    @pl.when(ki == num_k - 1)
    def _finish():
        finish_softmax_scratch(o_ref, acc_ref, l_ref, idx=0)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])  # [block_q, 1]


def _causal_k_index(block_q: int, block_k: int):
    """K/V index_map for q-major grids: clamp the k tile to the causal
    frontier so skipped programs re-request the tile they already hold
    (pallas skips the DMA when consecutive block indices repeat)."""

    def index_map(b, i, j):
        return (b, jnp.minimum(j, (i * block_q + block_q - 1) // block_k), 0)

    return index_map


def _causal_q_index(block_q: int, block_k: int):
    """Q-side index_map for k-major grids (the dk/dv kernel): clamp the q
    tile up to the first visible row block."""

    def index_map(b, j, i):
        return (b, jnp.maximum(i, (j * block_k) // block_q), 0)

    return index_map


def _like(x, shape, dtype) -> jax.ShapeDtypeStruct:
    """An output aval that varies over the same manual mesh axes as `x`.
    Under `jax.shard_map` a pallas_call's outputs are otherwise typed
    invariant, and the custom vjp's cotangents then fail to match their
    (device-varying) primals."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(x).vma)


def _flash_fwd(q, k, v, causal: bool, bf16: bool, interpret):
    """q,k,v: [BH, S, D] → (o [BH,S,D], lse [BH,S,1] fp32)."""
    bh, s, d = q.shape
    block_q, block_k = pick_blocks(s)
    scale = 1.0 / math.sqrt(d)
    grid = (bh, s // block_q, s // block_k)
    kv_index = (_causal_k_index(block_q, block_k) if causal
                else (lambda b, i, j: (b, j, 0)))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bf16=bf16)
    flops_per_bh = 4 * s * s * d * (0.5 if causal else 1.0)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # trailing unit dim: TPU block tiling needs the last dim to match
            # the array (per-row stats can't be a bare [bh, s] block)
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _like(q, (bh, s, d), q.dtype),
            _like(q, (bh, s, 1), jnp.float32),
        ],
        scratch_shapes=softmax_scratch(block_q, d),  # fp32 acc/m/l in VMEM
        cost_estimate=pl.CostEstimate(
            flops=int(flops_per_bh * bh),
            bytes_accessed=int(3 * bh * s * d * q.dtype.itemsize),
            transcendentals=int(bh * s * s * (0.5 if causal else 1.0)),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *, scale, causal, bf16):
    qi, ki = pl.program_id(1), pl.program_id(2)
    num_k = pl.num_programs(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    visible = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]       # [block_q, 1]
        delta = delta_ref[0]   # [block_q, 1]
        st = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(st - lse)  # ≤ 1; lse is the exact logsumexp
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * scale).astype(
            jnp.bfloat16 if bf16 else jnp.float32)
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds, k_blk.astype(ds.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc_ref, dv_acc_ref, *, scale, causal, bf16):
    ki, qi = pl.program_id(1), pl.program_id(2)
    num_q = pl.num_programs(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # Mirror image of the forward frontier: q tiles strictly above the
    # diagonal see nothing of this k tile.
    visible = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(visible)
    def _accumulate():
        k_blk = k_ref[0]       # [block_k, d]
        v_blk = v_ref[0]
        q_blk = q_ref[0]       # [block_q, d]
        do = do_ref[0]
        lse = lse_ref[0]       # [block_q, 1]
        delta = delta_ref[0]
        st = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale              # [block_q, block_k]
        p = jnp.exp(st - lse)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        pt = p.astype(jnp.bfloat16 if bf16 else jnp.float32)
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            pt, do.astype(pt.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_k, d]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        ds = (p * (dp - delta) * scale).astype(pt.dtype)
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds, q_blk.astype(ds.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_k, d]

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, bf16: bool, interpret):
    bh, s, d = q.shape
    block_q, block_k = pick_blocks(s)
    scale = 1.0 / math.sqrt(d)
    # delta_i = sum_d dO_id * O_id — cheap elementwise reduce; let XLA fuse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [bh, s, 1]
    q_major = lambda b, i, j: (b, i, 0)  # noqa: E731 — index_map shorthand
    kv_index = (_causal_k_index(block_q, block_k) if causal
                else (lambda b, i, j: (b, j, 0)))
    bwd_flops = 10 * s * s * d * (0.5 if causal else 1.0)  # 5 matmuls

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bf16=bf16),
        grid=(bh, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_major),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_q, d), q_major),
            pl.BlockSpec((1, block_q, 1), q_major),
            pl.BlockSpec((1, block_q, 1), q_major),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_major),
        out_shape=_like(q, (bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=int(bwd_flops * bh * 0.4),
            bytes_accessed=int(4 * bh * s * d * q.dtype.itemsize),
            transcendentals=int(bh * s * s * (0.5 if causal else 1.0)),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    k_major = lambda b, j, i: (b, j, 0)  # noqa: E731
    q_index = (_causal_q_index(block_q, block_k) if causal
               else (lambda b, j, i: (b, i, 0)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bf16=bf16),
        grid=(bh, s // block_k, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), k_major),
            pl.BlockSpec((1, block_k, d), k_major),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 1), q_index),
            pl.BlockSpec((1, block_q, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), k_major),
            pl.BlockSpec((1, block_k, d), k_major),
        ],
        out_shape=[
            _like(k, (bh, s, d), k.dtype),
            _like(v, (bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(bwd_flops * bh * 0.6),
            bytes_accessed=int(4 * bh * s * d * q.dtype.itemsize),
            transcendentals=int(bh * s * s * (0.5 if causal else 1.0)),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public op with custom vjp
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, bf16, interpret):
    o, _ = _flash_fwd(q, k, v, causal, bf16, interpret)
    return o


def _flash_vjp_fwd(q, k, v, causal, bf16, interpret):
    o, lse = _flash_fwd(q, k, v, causal, bf16, interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, bf16, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, do, causal, bf16, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def pallas_flash_attention(q, k, v, causal: bool = True, bf16: bool = False,
                           interpret: bool = False) -> jax.Array:
    """q,k,v: [B, S, H, D] → [B, S, H, D]. Fused training attention
    (differentiable; the custom vjp runs the two-kernel flash backward).
    One device's worth of work: under GSPMD call it through
    `flash_attention`, which adds the shard_map."""
    if not HAVE_PALLAS:
        raise RuntimeError(
            "pallas unavailable in this jax build; use "
            "optimizations.attention_impl: reference")
    b, s, h, d = q.shape
    to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)  # noqa: E731
    o = _flash(to3(q), to3(k), to3(v), causal, bf16, interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


class _Unsupported(Exception):
    """Why the TPU kernel cannot serve this call (shape or placement)."""


def _live_axes(entry, mesh) -> tuple:
    """The mesh axes of one PartitionSpec entry that actually split the
    dim on this mesh (larger than 1)."""
    axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
    return tuple(a for a in axes if mesh.shape.get(a, 1) > 1)


def _kernel_placement(q, rules: Optional[LogicalRules]):
    """Where the Mosaic call goes: None = call it bare (one device, or a
    body every mesh axis of which is already manual — ulysses), else the
    PartitionSpec of q/k/v/o for a `jax.shard_map` over the ambient mesh.
    Raises `_Unsupported` with the reason when the kernel cannot run.

    XLA cannot partition a Mosaic custom call (lowering raises "wrap the
    call in a shard_map"), so on a multi-device mesh the kernel runs
    manual over every mesh axis: batch and heads split over the axes the
    logical rules give them (data/fsdp, tensor), each device attends over
    its own [B/n, S, H/t, D] block, no collective inside; any other axis
    sees a replicated block.
    """
    _, s, _, d = q.shape
    if not HAVE_PALLAS:
        raise _Unsupported("pallas is not in this jax build")
    if s % 128:
        raise _Unsupported(f"sequence {s} is not a multiple of 128")
    if d not in (64, 128, 256):
        raise _Unsupported(f"head dim {d} is not one of 64, 128, 256")
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return None
    bound = set(mesh.manual_axes)
    if len(bound) == len(mesh.axis_names):
        return None
    if bound:
        # The pipeline stage body: manual over `pipeline`, GSPMD inside.
        # Nesting the kernel's shard_map there lowers forward, but its
        # transpose fails XLA's Shardy verifier (residual shardings).
        raise _Unsupported(
            f"the call sits inside a shard_map that binds only "
            f"{sorted(bound)} of the mesh axes (pipeline parallelism)")
    batch, seq, heads, _ = (rules or LogicalRules()).spec(
        ("batch", "seq", "heads", "kv"))
    seq_axes = _live_axes(seq, mesh)
    if seq_axes:
        raise _Unsupported(
            f"the sharding rules split the sequence over mesh axes "
            f"{seq_axes}, and the kernel needs whole sequences (context "
            "parallelism is attention_impl: ring or ulysses)")
    return PartitionSpec(_live_axes(batch, mesh) or None, None,
                         _live_axes(heads, mesh) or None, None)


def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    impl: Optional[str] = None,
    bf16: bool = False,
    rules: Optional[LogicalRules] = None,
) -> jax.Array:
    """Causal self-attention, dispatched by `optimizations.attention_impl`.

    impl: auto | pallas | reference | dense (None == auto; legacy
    flash/dot accepted). Where the kernel cannot serve the call (an
    untileable shape, a pipeline stage body, a sharded sequence) an
    explicit `pallas` raises; `auto` takes the reference path and says
    so in the log. `rules` name the mesh axes of the batch and heads dims
    for the multi-device placement (`_kernel_placement`).
    """
    resolved = resolve_attention_impl(impl)
    if resolved == "pallas":
        kernel = functools.partial(pallas_flash_attention, causal=causal,
                                   bf16=bf16)
        try:
            spec = _kernel_placement(q, rules)
        except _Unsupported as why_not:
            if impl == "pallas":
                raise ValueError(
                    f"attention_impl: pallas cannot serve q{tuple(q.shape)}: "
                    f"{why_not}; use auto (which takes the reference path "
                    "for such calls) or reference") from None
            logger.warning(
                "attention_impl auto: reference path for q%s on a TPU (%s)",
                tuple(q.shape), why_not)
        else:
            if spec is None:
                return kernel(q, k, v)
            return jax.shard_map(kernel, in_specs=(spec, spec, spec),
                                 out_specs=spec)(q, k, v)
    if resolved == "dense":
        return _xla_attention(q, k, v, causal)
    return reference_attention(q, k, v, causal=causal, bf16=bf16)
