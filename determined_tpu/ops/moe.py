"""Mixture-of-Experts layers: the Switch-style block GPT-2's MoE variant
trains, and the dropless expert layer a served family routes through.

**`moe_block` (capacity, with drops)** is what `models/gpt2.py` builds when
`num_experts > 1`, and what its tests and `serve/model.py` use: GShard/
Switch dispatch re-derived for GSPMD, with expert parallelism over the
mesh's `expert` axis.

  - top-k router with capacity factor; overflow tokens are dropped (their
    combine weight is zero, so the residual path carries them — standard
    Switch behaviour);
  - dispatch/combine are dense one-hot einsums: `xe = d[t,e,c] · x[t,d]`
    gives per-expert token buffers [E, C, D] which GSPMD shards over the
    `expert` mesh axis (the einsum boundary becomes the all-to-all); the
    expert FFN itself is a batched matmul with weights sharded [E→expert];
  - an auxiliary load-balancing loss (mean fraction × mean router prob ×
    E²) keeps the router from collapsing onto one expert.

It is quadratic in tokens (`[T, E, C]` with C ~ T/E) and drops
assignments: right for a small trained variant, wrong for serving a
published sparse model. For that:

**`dropless_moe` (no capacity, no drop, no `[T, E, C]` tensor)** computes
every assignment: sigmoid scores over all routed experts, the choice by
score + bias (`noaux_tc`: the bias takes part in the choice only), the
chosen scores normalised and scaled; the assignments sorted by expert, the
experts' sizes counted, and a **grouped matmul** (`moe_grouped_matmul`:
rows of group g times matrix g) run over gate-and-up, then SiLU·mul, then
down; the rows unsorted and combined in float32. The layer is told which
experts it holds (`experts_held = (first, count)`): it routes over all of
them and computes its own experts' part of the result, which is what a
chip of an expert-parallel deployment computes before the exchange
(ROADMAP "Reach"); with every expert held that part is the whole layer.
`grouped_matmul_pallas` is the TPU kernel (rows in tiles of 128, each tile
visited once for every group it holds rows of, the metadata scalar-
prefetched; the same scheme as jax's `megablox.gmm`, with the whole
contraction in one block and the metadata made once for both matmuls);
`grouped_matmul_reference` is `lax.ragged_dot`, for the CPU.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from determined_tpu.ops._pallas_common import HAVE_PALLAS
from determined_tpu.parallel.sharding import LogicalRules, shard_logical

if HAVE_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu


def init_moe(
    rng: jax.Array,
    d_model: int,
    d_ff: int,
    num_experts: int,
    param_dtype=jnp.float32,
    std: float = 0.02,
    layers: Optional[int] = None,
) -> Dict[str, Any]:
    """Parameters for one MoE FFN (or a stacked [layers, ...] pytree)."""
    lead = () if layers is None else (layers,)
    k_router, k_up, k_down = jax.random.split(rng, 3)

    def normal(k, shape, s):
        return (jax.random.normal(k, lead + shape) * s).astype(param_dtype)

    return {
        "router": {"kernel": normal(k_router, (d_model, num_experts), std)},
        "up": {
            "kernel": normal(k_up, (num_experts, d_model, d_ff), std),
            "bias": jnp.zeros(lead + (num_experts, d_ff), param_dtype),
        },
        "down": {
            "kernel": normal(
                k_down, (num_experts, d_ff, d_model), std / math.sqrt(2)
            ),
            "bias": jnp.zeros(lead + (num_experts, d_model), param_dtype),
        },
    }


def moe_logical_axes(layers: bool = False) -> Dict[str, Any]:
    """Logical axis names for init_moe params (expert dim → `expert` mesh
    axis via the default rules)."""
    L = ("layers",) if layers else ()
    return {
        "router": {"kernel": L + ("embed", None)},
        "up": {"kernel": L + ("expert", "embed", "mlp"),
               "bias": L + ("expert", "mlp")},
        "down": {"kernel": L + ("expert", "mlp", "embed"),
                 "bias": L + ("expert", "embed")},
    }


def moe_block(
    x: jax.Array,  # [B, S, D]
    params: Dict[str, Any],
    num_experts: int,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    rules: Optional[LogicalRules] = None,
) -> Tuple[jax.Array, jax.Array]:
    """→ (y [B, S, D], aux_load_balance_loss scalar f32)."""
    b, s, d = x.shape
    t = b * s
    e = num_experts
    k = min(top_k, e)
    dt = x.dtype
    xt = x.reshape(t, d)

    # Router in f32 (small matmul; numerics matter for the softmax).
    logits = (xt.astype(jnp.float32)
              @ params["router"]["kernel"].astype(jnp.float32))  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # Top-k expert choice per token.
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    capacity = max(1, int(math.ceil(t / e * capacity_factor)))

    # GShard-style position assignment: for each of the k choices in
    # priority order, a token takes the next free slot in its expert's
    # buffer; tokens past capacity are dropped (combine weight 0).
    dispatch = jnp.zeros((t, e, capacity), dtype=dt)
    combine = jnp.zeros((t, e, capacity), dtype=dt)
    used = jnp.zeros((e,), jnp.int32)  # slots consumed per expert so far
    for choice in range(k):
        sel = jax.nn.one_hot(gate_idx[:, choice], e, dtype=jnp.int32)  # [T,E]
        pos = jnp.cumsum(sel, axis=0) - 1 + used[None, :]  # [T, E]
        within = (pos < capacity) & (sel > 0)
        pos_c = jnp.clip(pos, 0, capacity - 1)
        oh = jax.nn.one_hot(pos_c, capacity, dtype=dt) * within[..., None]
        dispatch = dispatch + oh
        combine = combine + oh * gate_vals[:, choice, None, None].astype(dt)
        used = used + jnp.sum(sel, axis=0)

    # Per-expert token buffers; the [E, ...] dims shard over `expert`, so
    # XLA places each expert's buffer (and its FFN) on its own sub-mesh and
    # inserts the all-to-all at the einsum boundary.
    xe = jnp.einsum("tec,td->ecd", dispatch, xt)
    xe = shard_logical(xe, ("expert", None, "embed"), rules)
    h = jnp.einsum("ecd,edf->ecf", xe, params["up"]["kernel"].astype(dt))
    h = h + params["up"]["bias"].astype(dt)[:, None, :]
    h = shard_logical(h, ("expert", None, "mlp"), rules)
    h = jax.nn.gelu(h, approximate=True)
    ye = jnp.einsum("ecf,efd->ecd", h, params["down"]["kernel"].astype(dt))
    ye = ye + params["down"]["bias"].astype(dt)[:, None, :]
    ye = shard_logical(ye, ("expert", None, "embed"), rules)
    y = jnp.einsum("tec,ecd->td", combine, ye)

    # Load-balance aux (Switch Transformer eq. 4): E · Σ_e f_e · p_e where
    # f_e = fraction of tokens routed (first choice) to e, p_e = mean
    # router prob for e. Minimised at uniform routing.
    first = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32)
    f = jnp.mean(first, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)

    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# The dropless layer: routing, the grouped matmul, the combine.
# ---------------------------------------------------------------------------

ROW_TILE = 128     # rows of a tile of the grouped matmul (an MXU edge)


def route_noaux_tc(x, router, bias, top_k: int, scaling: float,
                   normalise: bool):
    """x [T, d] → (experts [T, k] int32, weights [T, k] float32): sigmoid
    scores over every routed expert, the k largest of score + bias chosen,
    their scores (without the bias) normalised to sum 1 where `normalise`,
    times `scaling`. The router is a small matmul read in float32 at the
    highest precision: a choice flips on the last bits of a score."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return experts.astype(jnp.int32), weights * scaling


def group_metadata(sizes: jax.Array, rows: int, tile: int):
    """What the kernel's index maps read, from the groups' sizes [G] (rows
    sorted by group, group g's rows at `offsets[g]:offsets[g+1]`): a
    **visit** is one (group, row tile) pair that shares rows, in order of
    group — at most `rows/tile + G - 1` of them, padded to that static
    count with repeats of the last, which the kernel skips. → (offsets
    [G+1], group of visit [V], tile of visit [V], visits [1])."""
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // tile
    last = jnp.maximum(ends - 1, 0) // tile
    per_group = jnp.where(sizes > 0, last - first + 1, 0)
    visit_ends = jnp.cumsum(per_group)
    total = visit_ends[-1]
    at = jnp.minimum(jnp.arange(rows // tile + groups - 1),
                     jnp.maximum(total - 1, 0))
    group = jnp.minimum(jnp.searchsorted(visit_ends, at, side="right"),
                        groups - 1).astype(jnp.int32)
    tile_of = first[group] + at - (visit_ends[group] - per_group[group])
    return (offsets.astype(jnp.int32), group, tile_of.astype(jnp.int32),
            jnp.reshape(total, (1,)).astype(jnp.int32))


def _column_tile(n: int) -> int:
    return next((c for c in (512, 256, 128) if n % c == 0), n)


def _gmm_kernel(offsets, group_of, tile_of, visits, layer, lhs_ref, rhs_ref,
                out_ref):
    del layer                       # read by the index map
    v = pl.program_id(1)

    @pl.when(v < visits[0])
    def _visit():
        g = group_of[v]
        acc = jnp.dot(lhs_ref[...], rhs_ref[0, 0],
                      preferred_element_type=jnp.float32)
        rows = tile_of[v] * acc.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = jnp.logical_and(rows >= offsets[g], rows < offsets[g + 1])
        # The tile stays in VMEM over its consecutive visits: rows of
        # other groups keep what their own visit wrote (or, before it,
        # whatever the buffer held — a select, so nothing propagates).
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


def grouped_matmul_pallas(lhs, rhs, metadata, layer=None, out_dtype=None,
                          interpret: bool = False):
    """lhs [M, K] (rows sorted by group) x rhs [G, K, N] → [M, N]: a row
    of group g times `rhs[g]`, accumulated in float32. With `layer` (a
    scalar) `rhs` is every layer's stack `[L, G, K, N]` and the groups are
    `rhs[layer]`'s: the stack stays where it rests and the index map picks
    the layer — an operand that was one layer of it would be a slice, hence
    a copy of every expert, in every call. `metadata` is
    `group_metadata(sizes, M, min(ROW_TILE, M))`. The grid is (column
    tiles, visits): within a column tile the visits go in order of group,
    so a group's `[K, tile]` block of `rhs` is fetched once however many
    row tiles it spans, and an output tile is written back once, after
    the last group with rows in it. Rows beyond the groups' total belong
    to no visit and are left unwritten."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    if layer is None:
        rhs, layer = rhs[None], 0
    tile = min(ROW_TILE, m)
    if m % tile:
        raise ValueError(f"{m} rows are not whole tiles of {tile}")
    cols = _column_tile(n)
    offsets, group_of, tile_of, visits = metadata
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // cols, group_of.shape[0]),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda j, v, o, g, t, nv, lay: (t[v], 0)),
                pl.BlockSpec((1, 1, k, cols),
                             lambda j, v, o, g, t, nv, lay:
                             (lay[0], g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (tile, cols), lambda j, v, o, g, t, nv, lay: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * m * k * n), transcendentals=0,
            bytes_accessed=int(rhs[0].size * rhs.dtype.itemsize
                               + m * (k + n) * lhs.dtype.itemsize)),
        interpret=interpret,
    )(offsets, group_of, tile_of, visits,
      jnp.reshape(layer, (1,)).astype(jnp.int32), lhs, rhs)


def grouped_matmul_reference(lhs, rhs, sizes, layer=None, out_dtype=None):
    """The same product as `lax.ragged_dot` (rows beyond the groups'
    total come out zero): the CPU's twin. On a TPU `auto` resolves to the
    kernel; XLA:TPU's own lowering of `ragged_dot` read wrong there in
    float32 at `highest` precision for a decode step's 4 rows (PERF.md §7,
    PR 35: logits off by 1.77 where a dense gather of the rows' matrices
    reads 0.0; in bfloat16 it agreed)."""
    return jax.lax.ragged_dot(
        lhs, rhs if layer is None else rhs[layer], sizes,
        preferred_element_type=jnp.float32).astype(out_dtype or lhs.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def moe_grouped_matmul(lhs, rhs, metadata, layer=None, out_dtype=None):
    """The kernel under a name of its own: a device trace calls a custom
    call after the innermost function traced around it."""
    return grouped_matmul_pallas(lhs, rhs, metadata, layer, out_dtype)


def kernel_refusal(d_model: int, d_ff: int) -> Optional[str]:
    """Why the grouped matmul kernel cannot take these widths, or None."""
    if not HAVE_PALLAS:
        return "pallas is not in this jax build"
    if d_model % 128 or d_ff % 128:
        return (f"expert matrices of {d_model} x {d_ff} are not whole "
                "128-lane tiles")
    return None


def dropless_moe(
    x: jax.Array,            # [T, d]
    params: Dict[str, Any],  # router [d, E], router_bias [E],
                             # w13 [held, d, 2f] (gate | up), w2 [held, f, d]
    *,
    layer: Optional[jax.Array] = None,   # w13, w2 are [L, ...]: this layer's
    top_k: int,
    routed_scaling_factor: float = 1.0,
    norm_topk_prob: bool = True,
    experts_held: Optional[Tuple[int, int]] = None,
    valid: Optional[jax.Array] = None,   # [T] bool: tokens that count
    impl: str = "reference",
) -> Tuple[jax.Array, jax.Array]:
    """→ (y [T, d] float32, assignments per expert [E] int32, of the
    `valid` tokens: padding and idle lanes are computed and not counted).

    Every token is routed over all E experts of the router and every
    assignment to an expert held here — `experts_held = (first, count)`,
    all of them where None — is computed: no capacity, no drop. `y` is the
    held experts' part of the layer (the whole of it when all are held);
    what a shared expert adds is the caller's, once. Inside a scan over
    layers the experts' matrices come as the whole stacks with `layer`:
    sliced by the scan they would be copied, every expert, every call."""
    t, d = x.shape
    e = params["router"].shape[-1]
    first, count = experts_held or (0, e)
    w13, w2 = params["w13"].astype(x.dtype), params["w2"].astype(x.dtype)
    if w13.shape[-3] != count or w13.ndim != (3 if layer is None else 4):
        raise ValueError(
            f"experts_held {(first, count)}, layer {layer}: but the "
            f"experts' matrices are {w13.shape}")
    experts, weights = route_noaux_tc(
        x, params["router"], params["router_bias"], top_k,
        routed_scaling_factor, norm_topk_prob)
    flat = experts.reshape(-1)                               # [T*k]
    m = flat.shape[0]
    local = flat - first
    key = jnp.where(jnp.logical_and(local >= 0, local < count), local, count)
    order = jnp.argsort(key, stable=True)      # held first, by expert
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    rows = x[order // top_k]                                 # [m, d]
    if impl == "pallas":
        tile = min(ROW_TILE, m)
        pad = -m % tile
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        meta = group_metadata(sizes, m + pad, tile)

        def gmm(lhs, rhs, out_dtype=None):
            return moe_grouped_matmul(lhs, rhs, meta, layer,
                                      out_dtype=out_dtype)
    elif impl == "reference":
        pad = 0

        def gmm(lhs, rhs, out_dtype=None):
            return grouped_matmul_reference(lhs, rhs, sizes, layer,
                                            out_dtype)
    else:
        raise ValueError(f"unknown expert layer impl {impl!r}")
    gate, up = jnp.split(gmm(rows, w13).astype(jnp.float32), 2, axis=-1)
    out = gmm((jax.nn.silu(gate) * up).astype(x.dtype), w2, jnp.float32)
    if count < e or pad:
        # rows of experts held elsewhere were visited by nobody
        out = jnp.where((jnp.arange(m + pad) < jnp.sum(sizes))[:, None],
                        out, 0.0)
    back = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    y = jnp.sum(out[back].reshape(t, top_k, d) * weights[..., None], axis=1)
    counted = jnp.ones((t,), jnp.int32) if valid is None \
        else valid.astype(jnp.int32)
    return y, jnp.zeros((e,), jnp.int32).at[flat].add(
        jnp.repeat(counted, top_k))
