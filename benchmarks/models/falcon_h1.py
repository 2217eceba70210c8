"""Falcon-H1's adapter (the contract: `benchmarks/models/__init__.py`), with
its plain reference: the parallel-hybrid block in float32 `jax.numpy`.

No kernel, no cache, no chunked scan, no batching, and no import of the
program. It follows the published model (`tiiuae/Falcon-H1-*` `config.json`
and the forward of `transformers` `models/falcon_h1/modeling_falcon_h1.py`,
against which `tests/test_falcon_h1.py` holds it at a tiny size). With `h`
the residual stream and every name a key of the published config:

  h0 = embed[token] * embedding_multiplier
  per layer, u = RMSNorm(h; input_layernorm, rms_norm_eps):
    attention on u * attention_in_multiplier: q, k, v without bias
      (num_attention_heads / num_key_value_heads heads of head_dim),
      k <- k * key_multiplier, rotary over the whole head (rope_theta,
      the rotate_half convention), causal softmax at 1/sqrt(head_dim),
      o_proj, result * attention_out_multiplier;
    mixer on u * ssm_in_multiplier: in_proj to [z | x | B | C | dt]
      (mamba_d_ssm, mamba_d_ssm, groups*state, groups*state, heads
      columns), each section times its ssm_multipliers entry; a depthwise
      causal convolution of width mamba_d_conv with bias over [x|B|C],
      then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); per head i
      of group g = i // (heads / groups), over time t,
        S_t = exp(dt_t A_i) S_{t-1} + dt_t x_t (x) B_t[g],
        y_t = S_t C_t[g] + D_i x_t          (S in R^{head_dim x state});
      y <- y * SiLU(z), RMSNorm over each of the groups with the norm's
      weight (mamba_rms_norm true, mamba_norm_before_gate false),
      out_proj, result * ssm_out_multiplier;
    h <- h + mixer + attention; v = RMSNorm(h; pre_ff_layernorm);
    h <- h + down(up(v) * SiLU(gate(v) * mlp_multipliers[0]))
             * mlp_multipliers[1]
  logits = lm_head(RMSNorm(h; final_layernorm)) * lm_head_multiplier,
  the head untied.

Departures from the published forward, all of form and none of value: the
recurrence is a sequential `lax.scan` over time (the published code's
chunked form computes the same sums); biases the config switches off
(`attention_bias`, `mlp_bias`, `mamba_proj_bias`, `projectors_bias`) have
no leaf; q, k and v are the column blocks of one leaf, as are the
sections of `in_proj`; the convolution's weight is laid `[width,
channels]`. Weights are random from the seed (the configuration's
`assumed`): N(0, 0.02) matrices, norms 1, and the recurrence's own
parameters in the ranges Mamba-2 initialises them in.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.models import Dims
from benchmarks.reference import ROUND, matmul

Params = Dict[str, Any]

# The head is multiplied a block of the vocabulary at a time, so that no
# float32 copy of it is ever whole beside the bfloat16 leaves.
VOCAB_BLOCKS = 30
DTYPE = jnp.bfloat16      # the checkpoint is published in bfloat16


def dims(config: Dict[str, Any]) -> Dims:
    """The reference's sizes and multipliers, from the published keys."""
    for off in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                "projectors_bias", "mamba_norm_before_gate",
                "tie_word_embeddings"):
        if config[off]:
            raise ValueError(f"{off} is set: this reference has no such leaf")
    if not (config["mamba_rms_norm"] and config["mamba_conv_bias"]):
        raise ValueError("the mixer's norm and convolution bias are assumed")
    if config.get("rope_scaling") or config["hidden_act"] != "silu":
        raise ValueError("plain rotary positions and SiLU are assumed")
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    d_ssm = config["mamba_d_ssm"] or config["mamba_expand"] \
        * config["hidden_size"]
    if heads * p != d_ssm or heads % config["mamba_n_groups"]:
        raise ValueError("mixer heads do not tile its width or its groups")
    return Dims(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], ssm_heads=heads, ssm_head_dim=p,
        ssm_state=config["mamba_d_state"], ssm_groups=config["mamba_n_groups"],
        conv_width=config["mamba_d_conv"], eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        embedding_multiplier=config["embedding_multiplier"],
        attention_in_multiplier=config["attention_in_multiplier"],
        attention_out_multiplier=config["attention_out_multiplier"],
        key_multiplier=config["key_multiplier"],
        ssm_in_multiplier=config["ssm_in_multiplier"],
        ssm_out_multiplier=config["ssm_out_multiplier"],
        ssm_multipliers=tuple(config["ssm_multipliers"]),
        mlp_multipliers=tuple(config["mlp_multipliers"]),
        lm_head_multiplier=config["lm_head_multiplier"],
        state_itemsize=jnp.dtype(
            config.get("serve", {}).get("state_dtype", "float32")).itemsize)


def sections(dims: Dict[str, Any]):
    """Widths of `in_proj`'s column sections [z | x | B | C | dt]."""
    d_ssm = dims["ssm_heads"] * dims["ssm_head_dim"]
    gn = dims["ssm_groups"] * dims["ssm_state"]
    return d_ssm, d_ssm, gn, gn, dims["ssm_heads"]


# ------------------------------------------------------------------ weights


def init_params(key: jax.Array, dims: Dict[str, Any]) -> Params:
    """Weights from one key, every leaf bfloat16, in the tree the program
    serves (`determined_tpu/serve/falcon_h1.py` "The parameters"; the
    program loads checkpoints and draws none itself, so the draw is this
    adapter's): the key 3 ways (embedding, head, blocks), the blocks' key
    once a layer, a layer's 11 ways. A stacked leaf is drawn layer by layer under
    `lax.map`, each slice cast as it is drawn, so that no float32 copy of
    a stack is ever live. Matrices N(0, 0.02); norms 1; the convolution
    U(-1/2, 1/2) as a width-4 depthwise Conv1d's default; `A_log` =
    log U(1, 16), `dt_bias` the inverse softplus of exp U(log 1e-3,
    log 1e-1), `D` 1 (Mamba-2's `__init__`): decays neither 0 nor 1."""
    d, v, f = dims["d_model"], dims["vocab_size"], dims["d_ff"]
    hq, hkv, dh = dims["n_head"], dims["kv_heads"], dims["head_dim"]
    z, x, b, c, heads = sections(dims)
    conv = x + b + c
    k_embed, k_head, k_blocks = jax.random.split(key, 3)

    def normal(k, shape):
        return (0.02 * jax.random.normal(k, shape)).astype(DTYPE)

    def layer(k):
        ks = jax.random.split(k, 11)
        dt = jnp.exp(jax.random.uniform(
            ks[8], (heads,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        ones = jnp.ones
        return {
            "input_norm": ones((d,), DTYPE),
            "qkv": normal(ks[0], (d, (hq + 2 * hkv) * dh)),
            "o": normal(ks[1], (hq * dh, d)),
            "in_proj": normal(ks[2], (d, z + conv + heads)),
            "conv_w": jax.random.uniform(
                ks[3], (dims["conv_width"], conv), minval=-0.5,
                maxval=0.5).astype(DTYPE),
            "conv_b": jax.random.uniform(
                ks[4], (conv,), minval=-0.5, maxval=0.5).astype(DTYPE),
            "A_log": jnp.log(jax.random.uniform(
                ks[7], (heads,), minval=1.0, maxval=16.0)).astype(DTYPE),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(DTYPE),
            "D": ones((heads,), DTYPE),
            "mixer_norm": ones((x,), DTYPE),
            "out_proj": normal(ks[5], (x, d)),
            "pre_ff_norm": ones((d,), DTYPE),
            "gate": normal(ks[6], (d, f)),
            "up": normal(ks[9], (d, f)),
            "down": normal(ks[10], (f, d)),
        }

    return {
        "embed": normal(k_embed, (v, d)),
        "lm_head": normal(k_head, (v, d)),
        "final_norm": jnp.ones((d,), DTYPE),
        "blocks": jax.lax.map(layer, jax.random.split(
            k_blocks, dims["n_layer"])),
    }


def layer_matrix_params(dims: Dict[str, Any]) -> int:
    """Parameters of one layer's matrices: what a token's forward
    multiplies there (the convolution's four taps among them)."""
    d, f = dims["d_model"], dims["d_ff"]
    hq, hkv, dh = dims["n_head"], dims["kv_heads"], dims["head_dim"]
    z, x, b, c, heads = sections(dims)
    return (d * (hq + 2 * hkv) * dh + hq * dh * d
            + d * (z + x + b + c + heads) + x * d
            + dims["conv_width"] * (x + b + c) + 3 * d * f)


def work(dims: Dict[str, Any]) -> Dict[str, int]:
    """A token's forward multiplies every layer's matrices and the head;
    the embedding is gathered, not multiplied. Every layer attends (its
    query heads share the K/V heads) and every layer keeps a state."""
    return {"params_per_token": dims["n_layer"] * layer_matrix_params(dims)
            + dims["vocab_size"] * dims["d_model"],
            "attn_layers": dims["n_layer"], "q_heads": dims["n_head"],
            "kv_heads": dims["kv_heads"], "head_dim": dims["head_dim"],
            "ssm_layers": dims["n_layer"], "ssm_heads": dims["ssm_heads"],
            "ssm_head_dim": dims["ssm_head_dim"],
            "ssm_state": dims["ssm_state"], "ssm_groups": dims["ssm_groups"],
            "ssm_state_itemsize": dims["state_itemsize"]}


# ------------------------------------------------------------------ forward


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def _rotary(x, theta: float):
    """x [B, H, T, Dh] at positions 0..T-1, the rotate_half convention:
    the head's two halves are the pairs' two members."""
    t, dh = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(u, lp, dims, quant):
    b, t, _ = u.shape
    hq, hkv, dh = dims["n_head"], dims["kv_heads"], dims["head_dim"]
    qkv = matmul(u * dims["attention_in_multiplier"], lp["qkv"], quant)
    q, k, v = jnp.split(qkv, [hq * dh, (hq + hkv) * dh], axis=-1)
    q = q.reshape(b, t, hq, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, hkv, dh).transpose(0, 2, 1, 3) * dims["key_multiplier"]
    v = v.reshape(b, t, hkv, dh).transpose(0, 2, 1, 3)
    q, k = _rotary(q, dims["rope_theta"]), _rotary(k, dims["rope_theta"])
    if quant is not None:
        q, k, v = (ROUND[quant](a, -1) for a in (q, k, v))
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if quant is not None:
        probs = ROUND[quant](probs, -1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, hq * dh)
    return matmul(ctx, lp["o"], quant) * dims["attention_out_multiplier"]


def _mixer(u, lp, dims, quant):
    b, t, _ = u.shape
    heads, p = dims["ssm_heads"], dims["ssm_head_dim"]
    n, groups = dims["ssm_state"], dims["ssm_groups"]
    widths = sections(dims)
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                           zip(widths, dims["ssm_multipliers"])])
    proj = matmul(u * dims["ssm_in_multiplier"], lp["in_proj"], quant) * mup
    z, xbc, dt = jnp.split(proj, [widths[0], sum(widths[:4])], axis=-1)
    # depthwise causal convolution: tap j reads the input j - (width-1)
    # steps back, zeros before the sequence's start
    width = dims["conv_width"]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + t] * lp["conv_w"][j] for j in range(width)) \
        + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    if quant is not None:
        xbc = ROUND[quant](xbc, -1)
    x, bm, cm = jnp.split(xbc, [widths[1], widths[1] + widths[2]], axis=-1)
    x = x.reshape(b, t, heads, p)
    per = heads // groups
    bm = jnp.repeat(bm.reshape(b, t, groups, n), per, axis=2)
    cm = jnp.repeat(cm.reshape(b, t, groups, n), per, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"])               # [b, t, heads]
    decay = jnp.exp(dt * -jnp.exp(lp["A_log"]))

    def step(state, at):                 # state [b, heads, p, n]
        decay_t, dt_t, x_t, b_t, c_t = at
        state = state * decay_t[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    over_time = [a.swapaxes(0, 1) for a in (decay, dt, x, bm, cm)]
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, p, n), jnp.float32),
                        over_time)
    y = y.swapaxes(0, 1) + lp["D"][:, None] * x            # [b, t, heads, p]
    y = y.reshape(b, t, heads * p) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(b, t, groups, -1),
                  lp["mixer_norm"].reshape(groups, -1), dims["eps"])
    return matmul(y.reshape(b, t, heads * p), lp["out_proj"], quant) \
        * dims["ssm_out_multiplier"]


def _block(h, lp, dims, quant):
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)   # this layer only
    u = _rms_norm(h, lp["input_norm"], dims["eps"])
    h = h + _mixer(u, lp, dims, quant) + _attention(u, lp, dims, quant)
    v = _rms_norm(h, lp["pre_ff_norm"], dims["eps"])
    gate = jax.nn.silu(matmul(v, lp["gate"], quant)
                       * dims["mlp_multipliers"][0])
    return h + matmul(matmul(v, lp["up"], quant) * gate, lp["down"], quant) \
        * dims["mlp_multipliers"][1]


def hidden(params: Params, tokens: jax.Array, dims: Dict[str, Any],
           quant: Optional[str] = None) -> jax.Array:
    """tokens [B, T] -> the final norm's output [B, T, d]; the layers under
    `lax.scan`, each upcast as it is reached."""
    h = params["embed"][tokens].astype(jnp.float32) \
        * dims["embedding_multiplier"]
    h, _ = jax.lax.scan(lambda hh, lp: (_block(hh, lp, dims, quant), None),
                        h, params["blocks"])
    return _rms_norm(h, params["final_norm"].astype(jnp.float32),
                     dims["eps"])


def head(params: Params, h: jax.Array, dims: Dict[str, Any],
         quant: Optional[str] = None) -> jax.Array:
    """The untied head, hidden [..., d] -> logits [..., V], a block of the
    vocabulary at a time."""
    table = params["lm_head"]
    v, d = table.shape
    blocks = math.gcd(v, VOCAB_BLOCKS)
    out = jax.lax.map(lambda w: matmul(h, w.T, quant),
                      table.reshape(blocks, v // blocks, d))
    return jnp.moveaxis(out, 0, -2).reshape(*h.shape[:-1], v) \
        * dims["lm_head_multiplier"]


def logits(params: Params, tokens: jax.Array, gather: jax.Array,
           dims: Dict[str, Any], quant: Optional[str] = None) -> jax.Array:
    """One whole pass over tokens [R, T]; the head is multiplied for the
    gathered positions [R, G] only."""
    h = hidden(params, tokens, dims, quant)
    h = jnp.take_along_axis(h, gather[:, :, None], axis=1)
    return head(params, h, dims, quant)


# ------------------------------------------------------------- the program


def serving(config: Dict[str, Any], serve: Dict[str, Any]) -> Dict[str, Any]:
    """Every published key the family's `Config` reads travels as it is
    spelt; what the replica is not handed otherwise (`state_dtype`) rides
    with them."""
    own = ("source", "reduced", "published", "deployment", "assumed",
           "serve", "tiny")
    return {"model": "falcon_h1",
            "model_config": {
                **{k: v for k, v in config.items() if k not in own},
                "dtype": serve["dtype"],
                "state_dtype": serve["state_dtype"]}}
