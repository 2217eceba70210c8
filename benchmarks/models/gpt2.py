"""GPT-2's adapter (the contract: `benchmarks/models/__init__.py`), with
its plain reference: GPT-2 in float32 `jax.numpy`, nothing else.

No kernel, no cache, no batching, no sharding rule, and no import of the
program: this file is the yardstick `correct` is decided against, so it
may not move when the program does. It follows the published model
(Radford et al. 2019; `openai-community/gpt2*` `config.json`): learned
token and position embeddings, pre-LayerNorm blocks (eps 1e-5) of causal
multi-head attention (q, k, v are the thirds of one projection, heads
contiguous) and a 4x MLP with the tanh GELU, a final LayerNorm, and the
output head tied to the token embedding.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.models import Dims
from benchmarks.reference import ROUND, matmul

Params = Dict[str, Any]


def dims(config: Dict[str, Any]) -> Dims:
    """The reference's sizes, from the configuration file's published
    keys."""
    return Dims(vocab_size=config["vocab_size"],
                n_positions=config["n_positions"],
                d_model=config["n_embd"], n_layer=config["n_layer"],
                n_head=config["n_head"],
                d_ff=config.get("n_inner") or 4 * config["n_embd"])


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


def work(dims: Dict[str, int]) -> Dict[str, int]:
    """Dense: a token's forward multiplies every parameter, every layer
    attends, and a query head has a K/V head of its own."""
    return {"params_per_token": param_count(dims),
            "attn_layers": dims["n_layer"], "q_heads": dims["n_head"],
            "kv_heads": dims["n_head"],
            "head_dim": dims["d_model"] // dims["n_head"]}


# ------------------------------------------------------------------ forward


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
    qkv = matmul(y, lp["qkv"]["kernel"], quant) + lp["qkv"]["bias"]
    q, k, v = (t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    if quant is not None:
        q, k, v = (ROUND[quant](t, -1) for t in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if quant is not None:
        probs = ROUND[quant](probs, -1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + matmul(ctx, lp["attn_out"]["kernel"], quant) \
        + lp["attn_out"]["bias"]
    y = _layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
    up = _gelu(matmul(y, lp["mlp_up"]["kernel"], quant)
               + lp["mlp_up"]["bias"])
    return x + matmul(up, lp["mlp_down"]["kernel"], quant) \
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
    return matmul(h, params["wte"].T, quant)


def logits(params: Params, tokens: jax.Array, gather: jax.Array,
           dims: Dict[str, int], quant: Optional[str] = None) -> jax.Array:
    """One whole pass over tokens [R, T]; the longest request of a mix is
    under the table's 1,024 positions, whose [T, T] scores fit a few rows
    at a time, so nothing is blocked here."""
    h = hidden(params, tokens, dims["n_head"], quant)
    h = jnp.take_along_axis(h, gather[:, :, None], axis=1)
    return head(params, h, quant)


# ----------------------------------------------------------------- training


def _nll_sum(params, tokens, n_head, quant):
    """Sum over every position of the next-token negative log-likelihood
    of rows [B, S+1]."""
    logits = head(params, hidden(params, tokens[:, :-1], n_head, quant),
                  quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.sum(tgt)


def loss_and_grads(params: Params, tokens: jax.Array, dims: Dict[str, int],
                   quant: Optional[str] = None, rows: int = 2):
    """Mean loss of the batch [B, S+1] and its gradient, `rows` rows at a
    time so that the float32 activations fit beside the state."""
    b = tokens.shape[0]
    rows = math.gcd(b, rows)
    blocks = tokens.reshape(b // rows, rows, tokens.shape[1])
    vg = jax.value_and_grad(_nll_sum)

    def body(acc, block):
        loss, grads = vg(params, block, dims["n_head"], quant)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], grads)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, blocks)
    n = b * (tokens.shape[1] - 1)
    return loss / n, jax.tree.map(lambda g: g / n, grads)


# ------------------------------------------------------------- the program


def serving(config: Dict[str, Any], serve: Dict[str, Any]) -> Dict[str, Any]:
    return {"model": "gpt2",
            "model_config": {"model_size": config["model_size"],
                             "seq_len": int(serve["max_seq_len"]),
                             "dtype": serve["dtype"]}}


def hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"model_size": config["model_size"]}
