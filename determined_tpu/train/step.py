"""Jitted train/eval step factories.

The hot loop. One `jit` per trial covering forward+backward+optimizer update;
batch sharded over (data, fsdp) on entry; all cross-device communication is
GSPMD-inserted XLA collectives (psum for grads over data axes,
reduce-scatter/all-gather for fsdp params) riding ICI — the TPU-native
replacement for DDP allreduce / ZeRO (reference:
harness/determined/pytorch/_pytorch_context.py:297 wrap_model → DDP).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from determined_tpu.parallel.sharding import LogicalRules
from determined_tpu.train.state import TrainState

# loss_fn(params, batch, rng) -> scalar loss OR (loss, aux_metrics)
LossFn = Callable[..., Any]


def _call_loss(loss_fn: LossFn, params, batch, rng) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    out = loss_fn(params, batch, rng)
    if isinstance(out, tuple):
        loss, aux = out
    else:
        loss, aux = out, {}
    return loss, aux


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    rules: Optional[LogicalRules] = None,
    donate_state: bool = True,
    stateful: bool = False,
    input_sharding: Any = None,
):
    """Build `step(state, batch, rng) -> (state, metrics)`, jitted.

    Stateless (default): loss_fn(params, batch, rng) -> loss | (loss, metrics).
    Stateful (BatchNorm etc.): loss_fn(params, extra, batch, rng) ->
    (loss, metrics, new_extra); new_extra lands in state.extra.

    `input_sharding` (a `NamedSharding` pytree prefix or per-leaf tree —
    `step_input_shardings`) is declared as the batch argument's
    in_shardings: with the DevicePrefetcher placing batches with the same
    shardings, XLA's compiled argument layout equals the arrival layout
    and no resharding copy precedes the first layer (the pre-partitioned
    input contract; asserted on compiled HLO in tests). State and rng
    shardings stay inferred from the arguments.

    metrics always include `loss` and `grad_norm` (fp32 scalars, replicated).
    """
    rules = rules or LogicalRules()

    def step(state: TrainState, batch: Any, rng: jax.Array):
        batch = _constrain_batch(batch, mesh, rules)

        def lfn(params):
            if stateful:
                loss, aux, new_extra = loss_fn(params, state.extra, batch, rng)
            else:
                loss, aux = _call_loss(loss_fn, params, batch, rng)
                new_extra = None
            return loss.astype(jnp.float32), (aux, new_extra)

        (loss, (aux, new_extra)), grads = jax.value_and_grad(lfn, has_aux=True)(
            state.params
        )
        gnorm = optax.global_norm(grads)
        new_state = state.apply_gradients(grads, tx, new_extra)
        # Divergence sentinel (train/health.py): grad_norm is already a
        # reduction over every gradient leaf (NaN/Inf anywhere propagates
        # into it), so one fused logical-and over (loss, grad_norm) covers
        # the whole step. Rides the regular metrics fetch — no extra host
        # sync, no extra collective.
        all_finite = jnp.logical_and(jnp.isfinite(loss), jnp.isfinite(gnorm))
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "all_finite": all_finite.astype(jnp.float32), **aux}
        return new_state, metrics

    kwargs: Dict[str, Any] = {}
    if input_sharding is not None:
        kwargs["in_shardings"] = (None, input_sharding, None)
    return jax.jit(step, donate_argnums=(0,) if donate_state else (),
                   **kwargs)


def make_multi_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    steps_per_call: int,
    mesh: Optional[Mesh] = None,
    rules: Optional[LogicalRules] = None,
    donate_state: bool = True,
    input_sharding: Any = None,
):
    """Build `multi_step(state, batches, rng) -> (state, metrics)` running
    `steps_per_call` optimizer steps inside ONE jitted call via `lax.scan`.

    TPU-first rationale: a per-step host→device dispatch costs real latency
    and forces a host sync point. Scanning N steps per dispatch amortizes that to ~0
    and lets XLA overlap the next step's grads with the optimizer update —
    the same structure production LLM trainers use. Batches: every leaf has
    a leading [steps_per_call, ...] axis (stack loader batches). Returned
    metrics are the per-window mean of each scalar.
    """
    rules = rules or LogicalRules()

    def one_step(state: TrainState, batch: Any, rng: jax.Array):
        def lfn(params):
            loss, aux = _call_loss(loss_fn, params, batch, rng)
            return loss.astype(jnp.float32), aux

        (loss, aux), grads = jax.value_and_grad(lfn, has_aux=True)(state.params)
        gnorm = optax.global_norm(grads)
        new_state = state.apply_gradients(grads, tx, None)
        all_finite = jnp.logical_and(jnp.isfinite(loss), jnp.isfinite(gnorm))
        return new_state, {"loss": loss, "grad_norm": gnorm,
                           "all_finite": all_finite.astype(jnp.float32),
                           **aux}

    def multi_step(state: TrainState, batches: Any, rng: jax.Array):
        batches = _constrain_batch(batches, mesh, rules, leading_dims=2)

        def body(carry, inp):
            state, rng = carry
            rng, step_rng = jax.random.split(rng)
            state, metrics = one_step(state, inp, step_rng)
            return (state, rng), metrics

        (state, _), metrics = jax.lax.scan(
            body, (state, rng), batches, length=steps_per_call
        )
        return state, jax.tree_util.tree_map(lambda m: m.mean(axis=0), metrics)

    kwargs: Dict[str, Any] = {}
    if input_sharding is not None:
        kwargs["in_shardings"] = (None, input_sharding, None)
    return jax.jit(multi_step, donate_argnums=(0,) if donate_state else (),
                   **kwargs)


def _constrain_batch(batch: Any, mesh: Optional[Mesh], rules: LogicalRules,
                     leading_dims: int = 1) -> Any:
    """Pin batch leaves to the (data, fsdp) layout along the batch dim.

    leading_dims=2 means leaves carry a [steps, batch, ...] stack (multi-step
    window): the steps axis stays unsharded, batch sharding applies to dim 1.
    """
    if mesh is None:
        return batch
    batch_axes = rules.mesh_axes("batch")
    spec = (PartitionSpec(None, batch_axes) if leading_dims == 2
            else PartitionSpec(batch_axes))

    def constrain(x):
        # Branches on pytree STRUCTURE (rank), fixed per trial — not a
        # per-shape recompile hazard.
        if getattr(x, "ndim", 0) < leading_dims:  # det: noqa[DTL104]
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(constrain, batch)


def batch_sharding(mesh: Mesh, rules: Optional[LogicalRules] = None) -> NamedSharding:
    """The sharding data loaders should device_put batches with."""
    rules = rules or LogicalRules()
    return NamedSharding(mesh, PartitionSpec(rules.mesh_axes("batch")))


def step_input_shardings(
    mesh: Mesh,
    rules: Optional[LogicalRules] = None,
    batch: Any = None,
    leading_dims: int = 1,
) -> Any:
    """The jitted step's exact batch-argument `NamedSharding`s.

    One source of truth for both sides of the pre-partitioned input
    contract (`optimizations.prepartition_inputs`): the DevicePrefetcher
    device_puts batches with these shardings and make_train_step /
    make_multi_step declare the same value as `input_sharding`, so the
    compiled step finds its inputs already laid out and inserts no
    resharding copy before the first layer.

    Without `batch` the single batch-dim sharding is returned — jit and
    device_put both accept it as a pytree prefix covering every leaf.
    With an example `batch`, a matching per-leaf tree is returned
    (sub-`leading_dims`-rank leaves replicate — same rank guard as
    `_constrain_batch`). leading_dims=2 is the multi-step window layout
    ([steps, batch, ...]: steps axis unsharded).
    """
    rules = rules or LogicalRules()
    batch_axes = rules.mesh_axes("batch")
    spec = (PartitionSpec(None, batch_axes) if leading_dims == 2
            else PartitionSpec(batch_axes))
    sharded = NamedSharding(mesh, spec)
    if batch is None:
        return sharded

    def leaf(x):
        if getattr(x, "ndim", 0) < leading_dims:  # det: noqa[DTL104]
            return NamedSharding(mesh, PartitionSpec())
        return sharded

    return jax.tree_util.tree_map(leaf, batch)


def make_eval_step(
    eval_fn: Callable[..., Dict[str, jax.Array]],
    mesh: Optional[Mesh] = None,
    rules: Optional[LogicalRules] = None,
    stateful: bool = False,
    input_sharding: Any = None,
):
    """Build `eval_step(state, batch) -> metrics` (per-batch sums/means).

    Stateless: eval_fn(params, batch); stateful: eval_fn(params, extra, batch).
    """
    rules = rules or LogicalRules()

    def step(state: TrainState, batch: Any):
        batch = _constrain_batch(batch, mesh, rules)
        if stateful:
            return eval_fn(state.params, state.extra, batch)
        return eval_fn(state.params, batch)

    kwargs: Dict[str, Any] = {}
    if input_sharding is not None:
        kwargs["in_shardings"] = (None, input_sharding)
    return jax.jit(step, **kwargs)
