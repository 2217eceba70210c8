"""GLM-4-MoE-Lite's adapter (the contract: `benchmarks/models/__init__.py`),
with its plain reference: latent attention in its **plain** form and every
expert over every token, in float32 `jax.numpy`.

No kernel, no cache, no absorption, no sorting of tokens, and no import of
the program. The equations are DeepSeek-V3's (arXiv:2412.19437 §2.1), which
this family's published keys spell term for term (`transformers`'
`models/deepseek_v3/modeling_deepseek_v3.py` computes the same under
another `model_type`; `tests/test_glm4_moe_lite.py` holds this file against
it at a tiny size). With `h` the residual stream:

  per layer, u = RMSNorm(h; input_layernorm, rms_norm_eps):
    q = q_b_proj(RMSNorm(q_a_proj(u)))  -> H heads of qk_nope_head_dim +
        qk_rope_head_dim;
    [c | k_rope] = kv_a_proj_with_mqa(u), c <- RMSNorm(c) (kv_lora_rank),
        k_rope one key of qk_rope_head_dim for all heads;
    [k_nope | v] = kv_b_proj(c) -> H heads of qk_nope_head_dim + v_head_dim;
    rotary positions on q_rope and k_rope (rope_theta; **the rotate_half
        pairing**: element i of the first half with element i of the
        second — `rope_interleave: false` in transformers' spelling; with
        weights drawn from a seed the interleaved pairing is the same
        model up to a fixed permutation of q_b's and kv_a's columns);
    causal softmax over q.k at (qk_nope_head_dim + qk_rope_head_dim)^-1/2,
        times v, o_proj;  h <- h + attention;
    v = RMSNorm(h; post_attention_layernorm);
    layers before first_k_dense_replace: h <- h + down(up(v) SiLU(gate(v)))
        at intermediate_size;
    the others: scores = sigmoid(router(v)) over n_routed_experts; the
        num_experts_per_tok largest of scores + e_score_correction_bias
        are chosen (topk_method noaux_tc; n_group = topk_group = 1: no
        group limit); their scores — without the bias — divided by their
        sum + 1e-20 (norm_topk_prob) and times routed_scaling_factor;
        h <- h + sum over ALL held experts of mask_e SwiGLU_e(v)
               + SwiGLU_shared(v)          (moe_intermediate_size each,
        the shared one n_shared_experts times as wide, ungated);
  logits = lm_head(RMSNorm(h; norm)), the head untied.

Departures from the published forward, of form and not of value: q_b's and
kv_b's heads are column blocks of one leaf; an expert's gate and up are
the two halves of one leaf `w13`; the multi-token-prediction module
(`num_nextn_predict_layers`) is not loaded — plain decoding leaves it out.
`experts_held` (absent: all) is the chip's share of each layer's routed
experts: the router keeps its width and the held experts' part of the sum
is what goes on, in the program and here alike.

**What this reference answers where the choice of experts is a near-tie.**
The choice is the one step of the forward that is not continuous: where a
token's `num_experts_per_tok`-th and next score + bias lie closer than the
stated precision (bfloat16 leaves and activations) moves them, the float32
choice and the bfloat16 one differ, both are right, and the layer's output
differs by a whole expert's. One forward pass cannot answer with both, and
the harness reads a maximum over the sample (`check.compare_serving`), which
one such position sets. So the configuration states a `reference.routing_band`
and `logits` answers a **level row** (every token as good as the best: gap
0) at every gathered position whose own choice, in any expert layer, is
decided by less than the band: such a position is not held, every other one
is held to rounding alone. The band is in the units the choice is made in
(sigmoid score + bias) and its readings are in the cell's limits file. A
control's pass (`quant` set) is never levelled: its first choice is read
against this reference at the positions that are held. With the band 0 or
absent (the CPU tests against Hugging Face and the program) nothing is.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.models import Dims
from benchmarks.reference import ROUND, matmul

Params = Dict[str, Any]

VOCAB_BLOCKS = 20         # the head is multiplied a block of rows at a time
QUERY_BLOCK = 256         # queries attended at a time: no [T, T] matrix
DTYPE = jnp.bfloat16      # the checkpoint is published in bfloat16


def dims(config: Dict[str, Any]) -> Dims:
    """The reference's sizes, from the published keys."""
    fixed = {"attention_bias": False, "hidden_act": "silu",
             "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
             "rope_scaling": None, "tie_word_embeddings": False,
             "partial_rotary_factor": 1}
    for key, want in fixed.items():
        if config[key] != want:
            raise ValueError(f"{key}={config[key]!r}: this reference "
                             f"computes {want!r} only")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("every head reads the one latent")
    experts = config["n_routed_experts"]
    first, count = config.get("experts_held") or (0, experts)
    return Dims(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        n_head=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], rope_theta=float(config["rope_theta"]),
        eps=config["rms_norm_eps"], d_ff=config["intermediate_size"],
        expert_ff=config["moe_intermediate_size"], experts=experts,
        shared=config["n_shared_experts"],
        top_k=config["num_experts_per_tok"],
        scaling=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]),
        held_first=int(first), held=int(count),
        band=float(config.get("reference", {}).get("routing_band", 0.0)))


# ------------------------------------------------------------------ weights


def init_params(key: jax.Array, dims: Dict[str, Any]) -> Params:
    """Weights from one key, every leaf bfloat16, in the tree the program
    serves (`determined_tpu/serve/glm4_moe_lite.py` "The parameters"; the
    program loads checkpoints and draws none itself, so the draw is this
    adapter's): the key 4 ways (embedding, head, dense layers, expert
    layers), a kind's key once a layer, a layer's 12 ways. A stacked leaf
    is drawn layer by layer under `lax.map`, each slice cast as it is
    drawn. Matrices N(0, 0.02), norms 1, the router's bias N(0, 0.02) —
    small beside sigmoid scores that spread over ~0.3..0.7, and not 0, so
    that the choice by score + bias differs from the choice by score."""
    d, v = dims["d_model"], dims["vocab_size"]
    heads, rank, rope = dims["n_head"], dims["rank"], dims["rope"]
    f, fs = dims["expert_ff"], dims["expert_ff"] * dims["shared"]
    k_embed, k_head, k_dense, k_moe = jax.random.split(key, 4)

    def normal(k, shape):
        return (0.02 * jax.random.normal(k, shape)).astype(DTYPE)

    def attention(ks):
        ones = jnp.ones
        return {
            "input_norm": ones((d,), DTYPE),
            "q_a": normal(ks[0], (d, dims["q_rank"])),
            "q_a_norm": ones((dims["q_rank"],), DTYPE),
            "q_b": normal(ks[1], (dims["q_rank"],
                                  heads * (dims["nope"] + rope))),
            "kv_a": normal(ks[2], (d, rank + rope)),
            "kv_a_norm": ones((rank,), DTYPE),
            "kv_b": normal(ks[3], (rank,
                                   heads * (dims["nope"] + dims["v_dim"]))),
            "o": normal(ks[4], (heads * dims["v_dim"], d)),
            "post_norm": ones((d,), DTYPE),
        }

    def dense(k):
        ks = jax.random.split(k, 12)
        return dict(attention(ks), gate=normal(ks[5], (d, dims["d_ff"])),
                    up=normal(ks[6], (d, dims["d_ff"])),
                    down=normal(ks[7], (dims["d_ff"], d)))

    def sparse(k):
        ks = jax.random.split(k, 12)
        held = slice(dims["held_first"], dims["held_first"] + dims["held"])
        return dict(
            attention(ks),
            router=normal(ks[5], (d, dims["experts"])),
            router_bias=normal(ks[6], (dims["experts"],)),
            # every expert is drawn and the held ones kept, so that a
            # share's experts are the whole model's
            w13=normal(ks[7], (dims["experts"], d, 2 * f))[held],
            w2=normal(ks[8], (dims["experts"], f, d))[held],
            shared_gate=normal(ks[9], (d, fs)),
            shared_up=normal(ks[10], (d, fs)),
            shared_down=normal(ks[11], (fs, d)))

    return {
        "embed": normal(k_embed, (v, d)),
        "lm_head": normal(k_head, (v, d)),
        "final_norm": jnp.ones((d,), DTYPE),
        "dense": jax.lax.map(dense, jax.random.split(
            k_dense, dims["dense_layers"])),
        "moe": jax.lax.map(sparse, jax.random.split(
            k_moe, dims["n_layer"] - dims["dense_layers"])),
    }


def attention_params(dims: Dict[str, Any]) -> int:
    d, heads = dims["d_model"], dims["n_head"]
    return (d * dims["q_rank"]
            + dims["q_rank"] * heads * (dims["nope"] + dims["rope"])
            + d * (dims["rank"] + dims["rope"])
            + dims["rank"] * heads * (dims["nope"] + dims["v_dim"])
            + heads * dims["v_dim"] * d)


def work(dims: Dict[str, Any]) -> Dict[str, int]:
    """A token's forward multiplies every layer's attention matrices, a
    dense layer's SwiGLU, of an expert layer the router, the experts it is
    routed to (`top_k`, not all) and the shared expert, and the head; the
    embedding is gathered. Every layer attends, over one latent a token
    (`kv_heads` 1 of `head_dim` rank + rope numbers)."""
    d = dims["d_model"]
    sparse_layers = dims["n_layer"] - dims["dense_layers"]
    expert = 3 * d * dims["expert_ff"]
    return {"params_per_token":
            dims["n_layer"] * attention_params(dims)
            + dims["dense_layers"] * 3 * d * dims["d_ff"]
            + sparse_layers * (d * dims["experts"]
                               + (dims["top_k"] + dims["shared"]) * expert)
            + dims["vocab_size"] * d,
            "attn_layers": dims["n_layer"], "q_heads": dims["n_head"],
            "kv_heads": 1, "head_dim": dims["rank"] + dims["rope"],
            "mla_layers": dims["n_layer"], "mla_heads": dims["n_head"],
            "mla_rank": dims["rank"], "mla_rope": dims["rope"],
            "moe_layers": sparse_layers, "moe_experts": dims["held"],
            "moe_top_k": dims["top_k"], "moe_d_model": d,
            "moe_width": dims["expert_ff"]}


# ------------------------------------------------------------------ forward


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def _rotary(x, theta: float):
    """x [..., T, Dh] at positions 0..T-1, the rotate_half pairing."""
    t, dh = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(u, lp, dims, quant):
    """Plain MLA: every head's keys and values expanded from the latent."""
    b, t, _ = u.shape
    heads, rank = dims["n_head"], dims["rank"]
    nope, rope, vd = dims["nope"], dims["rope"], dims["v_dim"]
    q = matmul(_rms_norm(matmul(u, lp["q_a"], quant), lp["q_a_norm"],
                         dims["eps"]), lp["q_b"], quant)
    q = q.reshape(b, t, heads, nope + rope).transpose(0, 2, 1, 3)
    ckv = matmul(u, lp["kv_a"], quant)
    c = _rms_norm(ckv[..., :rank], lp["kv_a_norm"], dims["eps"])
    kv = matmul(c, lp["kv_b"], quant)
    kv = kv.reshape(b, t, heads, nope + vd).transpose(0, 2, 1, 3)
    k_rope = _rotary(ckv[:, None, :, rank:], dims["rope_theta"])
    q = jnp.concatenate(
        [q[..., :nope], _rotary(q[..., nope:], dims["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, heads, t, rope))], -1)
    v = kv[..., nope:]
    if quant is not None:
        q, k, v = (ROUND[quant](a, -1) for a in (q, k, v))
    qb = math.gcd(t, QUERY_BLOCK)
    keys_at = jnp.arange(t)

    def block(args):
        qi, at = args                     # [b, H, qb, Dh], [qb]
        scores = jnp.einsum("bhqd,bhkd->bhqk", qi, k) \
            / math.sqrt(nope + rope)
        scores = jnp.where(keys_at[None, :] <= at[:, None], scores,
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        if quant is not None:
            probs = ROUND[quant](probs, -1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    ctx = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(b, heads, t // qb, qb, nope + rope), 2, 0),
        keys_at.reshape(t // qb, qb)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, heads, t, vd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, heads * vd)
    return matmul(ctx, lp["o"], quant)


def _swiglu(v, gate, up, down, quant):
    return matmul(matmul(v, up, quant)
                  * jax.nn.silu(matmul(v, gate, quant)), down, quant)


def routing_mask(v, lp, dims, quant):
    """v [..., d] → ([..., E] float32: each token's weight on every routed
    expert, 0 on those it did not choose; [...] float32: the margin its
    choice was made by — the last chosen score + bias less the first one
    left out)."""
    scores = jax.nn.sigmoid(matmul(v, lp["router"], quant))
    best, chosen = jax.lax.top_k(scores + lp["router_bias"],
                                 dims["top_k"] + 1)
    margin = best[..., -2] - best[..., -1]
    chosen = chosen[..., :-1]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if dims["norm_topk"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    one_hot = jax.nn.one_hot(chosen, dims["experts"], dtype=jnp.float32)
    return jnp.einsum("...ke,...k->...e", one_hot,
                      weights * dims["scaling"]), margin


def expert_layer(v, lp, dims, quant=None):
    """→ (the routed experts held here, each over EVERY token and masked
    by the routing weights, and the shared expert once; each token's
    routing margin)."""
    mask, margin = routing_mask(v, lp, dims, quant)
    held = mask[..., dims["held_first"]:dims["held_first"] + dims["held"]]
    f = dims["expert_ff"]

    def one(total, expert):
        w13, w2, weight = expert
        out = _swiglu(v, w13[:, :f], w13[:, f:], w2, quant)
        return total + out * weight[..., None], None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(v),
        (lp["w13"], lp["w2"], jnp.moveaxis(held, -1, 0)))
    return routed + _swiglu(v, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], quant), margin


def _block(carry, lp, dims, quant, sparse: bool):
    h, margin = carry
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)   # this layer only
    u = _rms_norm(h, lp["input_norm"], dims["eps"])
    h = h + _attention(u, lp, dims, quant)
    v = _rms_norm(h, lp["post_norm"], dims["eps"])
    if sparse:
        out, here = expert_layer(v, lp, dims, quant)
        return h + out, jnp.minimum(margin, here)
    return h + _swiglu(v, lp["gate"], lp["up"], lp["down"], quant), margin


def hidden_and_margin(params: Params, tokens: jax.Array,
                      dims: Dict[str, Any], quant: Optional[str] = None):
    """tokens [B, T] -> (the final norm's output [B, T, d], each position's
    narrowest routing margin over the expert layers [B, T]); the layers of
    a kind under `lax.scan`, each upcast as it is reached."""
    carry = (params["embed"][tokens].astype(jnp.float32),
             jnp.full(tokens.shape, jnp.inf, jnp.float32))
    for kind, sparse in (("dense", False), ("moe", True)):
        carry, _ = jax.lax.scan(
            lambda c, lp, sparse=sparse: (
                _block(c, lp, dims, quant, sparse), None),
            carry, params[kind])
    h, margin = carry
    return _rms_norm(h, params["final_norm"].astype(jnp.float32),
                     dims["eps"]), margin


def head(params: Params, h: jax.Array, quant: Optional[str] = None):
    """The untied head, hidden [..., d] -> logits [..., V], a block of the
    vocabulary at a time."""
    table = params["lm_head"]
    v, d = table.shape
    blocks = math.gcd(v, VOCAB_BLOCKS)
    out = jax.lax.map(lambda w: matmul(h, w.T, quant),
                      table.reshape(blocks, v // blocks, d))
    return jnp.moveaxis(out, 0, -2).reshape(*h.shape[:-1], v)


def logits(params: Params, tokens: jax.Array, gather: jax.Array,
           dims: Dict[str, Any], quant: Optional[str] = None) -> jax.Array:
    """One whole pass over tokens [R, T]; the head is multiplied for the
    gathered positions [R, G] only. The float32 pass answers a level row
    where a position's routing is decided by less than the stated band
    (the module's docstring)."""
    h, margin = hidden_and_margin(params, tokens, dims, quant)
    h = jnp.take_along_axis(h, gather[:, :, None], axis=1)
    out = head(params, h, quant)
    if quant is not None or not dims["band"]:
        return out
    near_tie = jnp.take_along_axis(margin, gather, axis=1) < dims["band"]
    return jnp.where(near_tie[:, :, None], 0.0, out)


# ------------------------------------------------------------- the program


def serving(config: Dict[str, Any], serve: Dict[str, Any]) -> Dict[str, Any]:
    """Every published key the family's `Config` reads travels as it is
    spelt (`experts_held` with them where the file states a share); what
    the replica is not handed otherwise (`dtype`) rides with them."""
    own = ("source", "reduced", "published", "deployment", "assumed",
           "reference", "serve", "tiny")
    return {"model": "glm4_moe_lite",
            "model_config": {
                **{k: v for k, v in config.items() if k not in own},
                "dtype": serve["dtype"]}}
