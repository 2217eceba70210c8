"""The plain reference: GPT-2 in float32 `jax.numpy`, nothing else.

No kernel, no cache, no batching, no sharding rule, and no import of the
program: this file is the yardstick `correct` is decided against, so it
may not move when the program does. It follows the published model
(Radford et al. 2019; `openai-community/gpt2*` `config.json`): learned
token and position embeddings, pre-LayerNorm blocks (eps 1e-5) of causal
multi-head attention (q, k, v are the thirds of one projection, heads
contiguous) and a 4x MLP with the tanh GELU, a final LayerNorm, and the
output head tied to the token embedding. Every matrix multiplication runs
under `jax.default_matmul_precision("highest")`: on a TPU a float32
matmul is otherwise computed in bfloat16 passes.

`quant="int8"` (or `"fp8"`, float8 e4m3) is the control, not a feature:
the same mathematics with both operands of every matmul rounded to 8 bits
(symmetric, weights per tensor, activations per row), the precision below
the bfloat16 the configurations state. A `correct` that cannot tell it from
the float32 reference decides nothing.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ------------------------------------------------------------------ weights


def init_params(key: jax.Array, dims: Dict[str, int]) -> Params:
    """GPT-2's initialisation from one key: N(0, 0.02), residual
    projections scaled by 1/sqrt(2L), biases 0, LayerNorm scale 1; the
    blocks stacked along a leading layer axis. The key is split 8 ways
    (token table, position table, blocks) and the blocks' key 4 ways
    (qkv, attention out, MLP up, MLP down), which is also how the
    program's trainer draws its weights — so the training reference can
    start from the same numbers without being handed them."""
    d, v, p, n_layer = (dims["d_model"], dims["vocab_size"],
                        dims["n_positions"], dims["n_layer"])
    f = dims.get("d_ff") or 4 * d
    std, res_std = 0.02, 0.02 / math.sqrt(2 * n_layer)
    keys = jax.random.split(key, 8)
    ks = jax.random.split(keys[2], 4)

    def normal(k, shape, s):
        return (jax.random.normal(k, shape) * s).astype(jnp.float32)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    zeros = functools.partial(jnp.zeros, dtype=jnp.float32)
    return {
        "wte": normal(keys[0], (v, d), std),
        "wpe": normal(keys[1], (p, d), std),
        "blocks": {
            "ln1": {"scale": ones((n_layer, d)), "bias": zeros((n_layer, d))},
            "qkv": {"kernel": normal(ks[0], (n_layer, d, 3 * d), std),
                    "bias": zeros((n_layer, 3 * d))},
            "attn_out": {"kernel": normal(ks[1], (n_layer, d, d), res_std),
                         "bias": zeros((n_layer, d))},
            "ln2": {"scale": ones((n_layer, d)), "bias": zeros((n_layer, d))},
            "mlp_up": {"kernel": normal(ks[2], (n_layer, d, f), std),
                       "bias": zeros((n_layer, f))},
            "mlp_down": {"kernel": normal(ks[3], (n_layer, f, d), res_std),
                         "bias": zeros((n_layer, d))},
        },
        "ln_f": {"scale": ones((d,)), "bias": zeros((d,))},
    }


def param_count(dims: Dict[str, int]) -> int:
    d, v, p, n_layer = (dims["d_model"], dims["vocab_size"],
                        dims["n_positions"], dims["n_layer"])
    f = dims.get("d_ff") or 4 * d
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    return v * d + p * d + n_layer * per_layer + 2 * d


# ------------------------------------------------------------------ forward


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


_ROUND = {"int8": _fake_int8, "fp8": _fake_fp8}


def _matmul(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """x [..., k] @ w [k, n] in float32 — or on an 8-bit grid."""
    if quant is not None:
        x, w = _ROUND[quant](x, -1), _ROUND[quant](w, None)
    return jnp.matmul(x, w)


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lp, n_head: int, quant: Optional[str]):
    b, s, d = x.shape
    dh = d // n_head
    y = _layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
    qkv = _matmul(y, lp["qkv"]["kernel"], quant) + lp["qkv"]["bias"]
    q, k, v = (t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    if quant is not None:
        q, k, v = (_ROUND[quant](t, -1) for t in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if quant is not None:
        probs = _ROUND[quant](probs, -1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + _matmul(ctx, lp["attn_out"]["kernel"], quant) \
        + lp["attn_out"]["bias"]
    y = _layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
    up = _gelu(_matmul(y, lp["mlp_up"]["kernel"], quant)
               + lp["mlp_up"]["bias"])
    return x + _matmul(up, lp["mlp_down"]["kernel"], quant) \
        + lp["mlp_down"]["bias"]


def hidden(params: Params, tokens: jax.Array, n_head: int,
           quant: Optional[str] = None) -> jax.Array:
    """tokens [B, S] → the final LayerNorm's output [B, S, d]. The layers
    run under `lax.scan` with each block rematerialised in the backward
    pass: the same numbers as a plain loop, in a fraction of the memory."""
    s = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:s][None]
    block = jax.checkpoint(
        lambda xx, lp: _block(xx, lp, n_head, quant))
    x, _ = jax.lax.scan(lambda xx, lp: (block(xx, lp), None), x,
                        params["blocks"])
    return _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])


def head(params: Params, h: jax.Array, quant: Optional[str] = None):
    """The tied output head: hidden [..., d] → logits [..., V]."""
    return _matmul(h, params["wte"].T, quant)


# ----------------------------------------------------------------- training


def _nll_sum(params, tokens, n_head, quant):
    """Sum over every position of the next-token negative log-likelihood
    of rows [B, S+1]."""
    logits = head(params, hidden(params, tokens[:, :-1], n_head, quant),
                  quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.sum(tgt)


@functools.partial(jax.jit, static_argnames=("n_head", "quant", "rows"))
def loss_and_grads(params: Params, tokens: jax.Array, *, n_head: int,
                   quant: Optional[str] = None, rows: int = 2):
    """Mean loss of the batch [B, S+1] and its gradient, `rows` rows at a
    time so that the float32 activations fit beside the state."""
    b = tokens.shape[0]
    rows = math.gcd(b, rows)
    blocks = tokens.reshape(b // rows, rows, tokens.shape[1])
    vg = jax.value_and_grad(_nll_sum)

    def body(acc, block):
        loss, grads = vg(params, block, n_head, quant)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], grads)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    with jax.default_matmul_precision("highest"):
        (loss, grads), _ = jax.lax.scan(body, zero, blocks)
    n = b * (tokens.shape[1] - 1)
    return loss / n, jax.tree.map(lambda g: g / n, grads)


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


def train_three_steps(key: jax.Array, dims: Dict[str, int],
                      opt: Dict[str, float], batches, sketch_seed: int,
                      quant: Optional[str] = None, rows: int = 2,
                      devices=None):
    """Follow the first three optimizer steps from the initial weights.

    Returns the three losses; of the first gradient as the optimizer gets
    it (clipped) the leaf norms, the column norms and the sketch; and the
    sketches of the parameters after step 1 and after step 3 (the warm-up
    schedule's first learning rate is 0, so step 1 moves nothing and the
    change between the two is steps 2 and 3's)."""
    params = spread(jax.jit(init_params, static_argnames=("dims",))(
        key, dims=_freeze(dims)), devices)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    out: Dict[str, Any] = {"losses": []}
    for i, tokens in enumerate(batches[:3]):
        loss, grads = loss_and_grads(params, jnp.asarray(tokens),
                                     n_head=dims["n_head"], quant=quant,
                                     rows=rows)
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


class _freeze(dict):
    """A dict that can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


# ------------------------------------------------------------------ serving


@functools.partial(jax.jit, static_argnames=("n_head", "quant"))
def _replay_logits(params, rows, gather, *, n_head, quant=None):
    """rows [R, T] tokens (prompt then served tokens, right-padded) →
    logits [R, G, V] at the positions `gather` [R, G]."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, rows, n_head, quant)
        h = jnp.take_along_axis(h, gather[:, :, None], axis=1)
        return head(params, h, quant)


def replay_gaps(params: Params, dims: Dict[str, int], requests, *,
                width: int, max_new: int, rows: int = 4,
                control: Optional[str] = None):
    """For every served token of every request, how far its float32
    reference logit lies below the reference's best at that position.

    `requests` is a list of (prompt ids, served ids). One full forward
    pass over prompt + served tokens gives, at position len(prompt)+i-1,
    the distribution token i was drawn from: prefill answers for i = 0,
    decoding through the cache for the rest. With `control` set, the gap
    read is that of the token the lower precision puts first at the same
    position, in place of the served one. Returns one gap per token, in
    request order."""
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
        ref = _replay_logits(params, toks, gather, n_head=dims["n_head"])
        best = jnp.max(ref, axis=-1)
        if control is None:
            chosen = np.zeros((rows, max_new), np.int32)
            for r, (_, served) in enumerate(chunk):
                chosen[r, :len(served)] = served
            chosen = jnp.asarray(chosen)
        else:
            low = _replay_logits(params, toks, gather,
                                 n_head=dims["n_head"], quant=control)
            chosen = jnp.argmax(low, axis=-1)
        gap = np.asarray(best - jnp.take_along_axis(
            ref, chosen[:, :, None], axis=-1)[:, :, 0])
        for r, (_, served) in enumerate(chunk):
            gaps.extend(float(g) for g in gap[r, :len(served)])
    return gaps
