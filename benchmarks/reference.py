"""What every model's plain reference shares, and nothing of any model.

An architecture's own float32 forward, loss and weights live in its adapter
(`benchmarks/models/<model_type>.py`; the contract is `models/__init__.py`)
and come in here as the argument `model`. This file holds the rest of the
yardstick `correct` is decided against — the matmul both forwards go
through and its two controls, AdamW and its schedule, the norms and
sketches a training cell compares, the three followed steps, the replay of
served tokens — and imports nothing of the program. Every function of an
adapter is traced under `jax.default_matmul_precision("highest")`: on a TPU
a float32 matmul is otherwise computed in bfloat16 passes.

`quant="int8"` (or `"fp8"`, float8 e4m3) is the control, not a feature:
the same mathematics with both operands of every matmul rounded to 8 bits
(symmetric, weights per tensor, activations per row), the precision below
the bfloat16 the configurations state. A `correct` that cannot tell it from
the float32 reference decides nothing.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ------------------------------------------------------------------- matmul


def _fake_int8(x: jax.Array, axis: Optional[int]) -> jax.Array:
    """Round to a symmetric 8-bit grid (one scale per tensor, or per row
    along `axis`); the gradient passes straight through."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x: jax.Array, axis: Optional[int]) -> jax.Array:
    """Round to float8 e4m3 (scaled so the largest magnitude sits at its
    top, 448); the gradient passes straight through."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


ROUND = {"int8": _fake_int8, "fp8": _fake_fp8}


def matmul(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """x [..., k] @ w [k, n] in float32 — or on an 8-bit grid."""
    if quant is not None:
        x, w = ROUND[quant](x, -1), ROUND[quant](w, None)
    return jnp.matmul(x, w)


@functools.lru_cache(maxsize=None)
def at_highest(fn, *static: str):
    """An adapter's function, jitted (the named arguments static) and
    traced under `highest` matmul precision. Cached by the function, so
    that every seed of one process shares a compilation."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return jax.jit(traced, static_argnames=static)


def draw_params(model, key: jax.Array, dims) -> Params:
    """The adapter's float32 weights, on the device, in one jitted call."""
    return at_highest(model.init_params, "dims")(key, dims=dims)


# ----------------------------------------------------------------- training


def learning_rate(opt: Dict[str, float], count) -> jax.Array:
    """Linear warm-up from 0 to the peak over `warmup_steps`, then cosine
    decay to 0 at `decay_steps`; `count` is the number of updates made."""
    peak, warm, total = (opt["learning_rate"], opt["warmup_steps"],
                         opt["decay_steps"])
    count = jnp.asarray(count, jnp.float32)
    frac = jnp.clip((count - warm) / max(total - warm, 1), 0.0, 1.0)
    return jnp.where(count < warm, peak * count / max(warm, 1),
                     peak * 0.5 * (1.0 + jnp.cos(jnp.pi * frac)))


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2, 3))
def adamw_step(params, grads, mu, nu, count, *, opt: Tuple):
    """One AdamW update after clipping the gradient to `clip_norm` by its
    global norm (Loshchilov & Hutter; decay applied to every leaf).
    Returns (params, clipped gradient, mu, nu)."""
    o = dict(opt)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    factor = jnp.where(gnorm < o["clip_norm"], 1.0, o["clip_norm"] / gnorm)
    grads = jax.tree.map(lambda g: g * factor, grads)
    t = count + 1
    mu = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: o["b2"] * n + (1 - o["b2"]) * g * g,
                      nu, grads)
    lr = learning_rate(o, count)

    def upd(p, m, n):
        m_hat = m / (1 - o["b1"] ** t)
        n_hat = n / (1 - o["b2"] ** t)
        return p - lr * (m_hat / (jnp.sqrt(n_hat) + o["eps"])
                         + o["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), grads, mu, nu


def _paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [("/".join(str(getattr(k, "key", k)) for k in path), x)
            for path, x in flat]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for _, x in _paths(tree)]


def leaf_norms(tree) -> Dict[str, float]:
    """{path: L2 norm} of every leaf, on the host."""
    return {path: float(n) for (path, _), n in
            zip(_paths(tree), jax.device_get(_norms(tree)))}


@jax.jit
def _columns(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.reshape(-1, x.shape[-1])), axis=0))
            for _, x in _paths(tree)]


def column_norms(tree) -> Dict[str, np.ndarray]:
    """{path: the L2 norm of each column} (a column: one index of the
    last axis, over all the others)."""
    return {path: np.asarray(c) for (path, _), c in
            zip(_paths(tree), jax.device_get(_columns(tree)))}


@jax.jit
def _sketch(tree, key):
    out = []
    for i, (_, x) in enumerate(_paths(tree)):
        sign = jax.random.rademacher(jax.random.fold_in(key, i), x.shape,
                                     dtype=jnp.float32)
        out.append(jnp.sum((x.astype(jnp.float32) * sign).reshape(
            -1, x.shape[-1]), axis=0))
    return out


def sketch(tree, seed: int) -> Dict[str, np.ndarray]:
    """A seeded sign sketch: every leaf's elements times fixed random
    signs, summed into one number per column. It is linear, so the sketch
    of a difference is the difference of sketches, and the squared norm
    of a sketch estimates the leaf's (to about sqrt(2/columns), with the
    same signs on both sides of a comparison). It lets the program's
    gradient and parameters be held against the reference's as vectors —
    direction and all — from a few thousand numbers a leaf, with no
    second copy of the parameters kept on the chip."""
    vectors = jax.device_get(_sketch(tree, jax.random.PRNGKey(seed)))
    return {path: np.asarray(v) for (path, _), v in
            zip(_paths(tree), vectors)}


def spread(tree, devices):
    """Lay every leaf out over `devices` along its last axis that divides
    by their number (the compiler partitions the arithmetic to match): a
    model whose float32 state does not fit one chip is followed on all of
    the cell's chips. One device, or none given: nothing to do."""
    if not devices or len(devices) < 2:
        return tree
    mesh = jax.sharding.Mesh(np.asarray(devices), ("x",))

    def place(x):
        axes = [None] * x.ndim
        for axis in reversed(range(x.ndim)):
            if x.shape[axis] % len(devices) == 0:
                axes[axis] = "x"
                break
        return jax.device_put(x, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*axes)))

    return jax.tree.map(place, tree)


def train_three_steps(model, key: jax.Array, dims, opt: Dict[str, float],
                      batches, sketch_seed: int, quant: Optional[str] = None,
                      rows: int = 2, devices=None):
    """Follow the first three optimizer steps of the architecture `model`
    (its adapter) from its initial weights.

    Returns the three losses; of the first gradient as the optimizer gets
    it (clipped) the leaf norms, the column norms and the sketch; and the
    sketches of the parameters after step 1 and after step 3 (the warm-up
    schedule's first learning rate is 0, so step 1 moves nothing and the
    change between the two is steps 2 and 3's)."""
    params = spread(draw_params(model, key, dims), devices)
    loss_and_grads = at_highest(model.loss_and_grads, "dims", "quant", "rows")
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    out: Dict[str, Any] = {"losses": []}
    for i, tokens in enumerate(batches[:3]):
        loss, grads = loss_and_grads(params, jnp.asarray(tokens), dims=dims,
                                     quant=quant, rows=rows)
        out["losses"].append(float(loss))
        params, clipped, mu, nu = adamw_step(
            params, grads, mu, nu, i, opt=tuple(sorted(opt.items())))
        if i == 0:
            out.update(grad1=leaf_norms(clipped),
                       grad1_columns=column_norms(clipped),
                       grad1_sketch=sketch(clipped, sketch_seed),
                       params1_sketch=sketch(params, sketch_seed))
        del grads, clipped
    out["params3_sketch"] = sketch(params, sketch_seed)
    return out


# ------------------------------------------------------------------ serving


def replay_gaps(model, params: Params, dims, requests, *, width: int,
                max_new: int, rows: int = 4, control: Optional[str] = None):
    """For every served token of every request, how far its float32
    reference logit (the adapter `model`'s) lies below the reference's
    best at that position.

    `requests` is a list of (prompt ids, served ids). One full forward
    pass over prompt + served tokens gives, at position len(prompt)+i-1,
    the distribution token i was drawn from: prefill answers for i = 0,
    decoding through the cache for the rest. With `control` set, the gap
    read is that of the token the lower precision puts first at the same
    position, in place of the served one. Returns one gap per token, in
    request order."""
    logits = at_highest(model.logits, "dims", "quant")
    gaps = []
    for start in range(0, len(requests), rows):
        chunk = requests[start:start + rows]
        toks = np.zeros((rows, width), np.int32)
        gather = np.zeros((rows, max_new), np.int32)
        for r, (prompt, served) in enumerate(chunk):
            seq = np.concatenate([prompt, served])[:width]
            toks[r, :len(seq)] = seq
            n = len(served)
            gather[r, :n] = len(prompt) - 1 + np.arange(n)
        ref = logits(params, toks, gather, dims=dims)
        best = jnp.max(ref, axis=-1)
        if control is None:
            chosen = np.zeros((rows, max_new), np.int32)
            for r, (_, served) in enumerate(chunk):
                chosen[r, :len(served)] = served
            chosen = jnp.asarray(chosen)
        else:
            low = logits(params, toks, gather, dims=dims, quant=control)
            chosen = jnp.argmax(low, axis=-1)
        gap = np.asarray(best - jnp.take_along_axis(
            ref, chosen[:, :, None], axis=-1)[:, :, 0])
        for r, (_, served) in enumerate(chunk):
            gaps.extend(float(g) for g in gap[r, :len(served)])
    return gaps
