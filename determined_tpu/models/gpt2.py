"""GPT-2 in plain JAX, TPU-first.

The flagship workload (BASELINE.md north star: GPT-2 pretraining ≥40% MFU).
Equivalent capability to the reference's HF-Trainer GPT-2 path
(reference: examples/hf_trainer_api/hf_language_modeling/run_clm.py,
harness/determined/transformers/_hf_callback.py) but re-designed for the MXU:

  - bfloat16 activations, fp32 params/optimizer (mixed precision by default)
  - transformer blocks stacked along a leading "layers" dim and iterated with
    `lax.scan` → one compiled block regardless of depth
  - logical-axis sharding annotations (batch/embed/heads/mlp/vocab) so the
    same model runs DP, FSDP, TP or any combination by swapping rules
  - optional `jax.checkpoint` rematerialisation of each block
  - attention pluggable via `optimizations.attention_impl`
    (auto | pallas | reference | dense — ops/flash_attention.py; plus the
    context-parallel "ring"/"ulysses" strategies) with an optional
    bf16-probabilities mode (`attention_bf16`)
  - optional comm/compute overlap (`overlap_allgather`): the layers scan
    carries the current layer's fsdp-gathered params while the next
    layer's all-gather is issued a step ahead (docs/training-perf.md)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from determined_tpu.parallel.mesh import ambient_mesh, on_tpu
from determined_tpu.parallel.sharding import LogicalRules, shard_logical


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    d_model: int = 768
    n_layer: int = 12
    n_head: int = 12
    d_ff: int = 0  # 0 → 4*d_model
    dropout: float = 0.0  # pretraining default; rng-free when 0
    dtype: Any = jnp.bfloat16  # activation dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # jax.checkpoint policy name: None = full remat; "dots" saves matmul
    # outputs and recomputes only elementwise/softmax (less recompute, more
    # HBM); see jax.checkpoint_policies.
    remat_policy: Optional[str] = "dots"
    # `optimizations.attention_impl`: "auto" = pallas flash kernel on TPU,
    # jnp reference elsewhere; "pallas"/"reference" force one side;
    # "dense" = legacy XLA path (A/B baseline). Legacy spellings accepted:
    # "flash" == auto, "dot" == dense. "ring"/"ulysses" = context-parallel.
    attention_impl: str = "flash"
    # `optimizations.attention_bf16`: cast attention probabilities to bf16
    # for the P·V / dS·K matmuls (MXU bf16 path); the online-softmax
    # statistics stay fp32 regardless. Numerics gate: tests/test_models.py.
    attention_bf16: bool = False
    # `optimizations.overlap_allgather`: restructure the layers scan so each
    # layer's fsdp param all-gather is issued one layer ahead of its use
    # (carry holds the gathered slice; gather overlaps the previous layer's
    # compute). No-op unless the rules map params onto a >1 "fsdp" axis.
    overlap_allgather: bool = False
    layer_norm_eps: float = 1e-5
    # Unroll factor for the layers scan. 0 = full unroll: removes the
    # per-layer stacked-param dynamic-slice and scan-carry stacking overhead
    # (~10% step time on v5e) at the cost of longer compiles; 1 = rolled
    # (fast compile — the right default for tests and short ASHA trials).
    scan_unroll: int = 1
    # Mixture-of-Experts: >1 replaces every block's MLP with a top-k routed
    # MoE FFN whose experts shard over the mesh `expert` axis (ops/moe.py).
    # The reference has no MoE at all (SURVEY §2.4).
    num_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01

    family = "gpt2"  # its serving steps: serve/model.py

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @staticmethod
    def small() -> "Config":
        return Config()  # gpt2-124M

    @staticmethod
    def medium() -> "Config":
        return Config(d_model=1024, n_layer=24, n_head=16)

    @staticmethod
    def large() -> "Config":
        return Config(d_model=1280, n_layer=36, n_head=20)

    @staticmethod
    def tiny() -> "Config":
        """Test-sized config (CPU-mesh unit tests, dryrun_multichip)."""
        return Config(
            vocab_size=512, n_positions=128, d_model=64, n_layer=2, n_head=4
        )


def flops_per_token(cfg: Config, seq_len: int) -> float:
    """Approx fwd+bwd FLOPs per token (6N + attention term) for MFU math."""
    n_params = param_count(cfg)
    attn = 12 * cfg.n_layer * cfg.d_model * seq_len  # 2*2*3 * L * d * s
    return 6.0 * n_params + attn


def param_count(cfg: Config) -> int:
    d, f, v, p, L = cfg.d_model, cfg.ff_dim, cfg.vocab_size, cfg.n_positions, cfg.n_layer
    per_layer = (
        3 * d * d + 3 * d  # qkv
        + d * d + d  # attn out
        + d * f + f  # mlp up
        + f * d + d  # mlp down
        + 4 * d  # 2 layernorms
    )
    return v * d + p * d + L * per_layer + 2 * d  # + final ln


# ---------------------------------------------------------------- init


def _normal(rng, shape, std, dtype):
    return (jax.random.normal(rng, shape) * std).astype(dtype)


def init(rng: jax.Array, cfg: Config) -> Dict[str, Any]:
    d, f, v, p, L = cfg.d_model, cfg.ff_dim, cfg.vocab_size, cfg.n_positions, cfg.n_layer
    pd = cfg.param_dtype
    keys = jax.random.split(rng, 8)
    # GPT-2 init: N(0, 0.02); residual projections scaled by 1/sqrt(2L).
    std, res_std = 0.02, 0.02 / math.sqrt(2 * L)

    def layer_params(k):
        ks = jax.random.split(k, 4)
        out = {
            "ln1": {"scale": jnp.ones((L, d), pd), "bias": jnp.zeros((L, d), pd)},
            "qkv": {
                "kernel": _normal(ks[0], (L, d, 3 * d), std, pd),
                "bias": jnp.zeros((L, 3 * d), pd),
            },
            "attn_out": {
                "kernel": _normal(ks[1], (L, d, d), res_std, pd),
                "bias": jnp.zeros((L, d), pd),
            },
            "ln2": {"scale": jnp.ones((L, d), pd), "bias": jnp.zeros((L, d), pd)},
        }
        if cfg.num_experts > 1:
            from determined_tpu.ops.moe import init_moe

            out["moe"] = init_moe(
                ks[2], d, f, cfg.num_experts, param_dtype=pd, std=std,
                layers=L,
            )
        else:
            out["mlp_up"] = {
                "kernel": _normal(ks[2], (L, d, f), std, pd),
                "bias": jnp.zeros((L, f), pd),
            }
            out["mlp_down"] = {
                "kernel": _normal(ks[3], (L, f, d), res_std, pd),
                "bias": jnp.zeros((L, d), pd),
            }
        return out

    return {
        "wte": _normal(keys[0], (v, d), std, pd),
        "wpe": _normal(keys[1], (p, d), std, pd),
        "blocks": layer_params(keys[2]),
        "ln_f": {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)},
    }


def param_logical_axes(cfg: Config) -> Dict[str, Any]:
    """Logical axis names per param dim; the leading "layers" dim of the
    stacked blocks shards over the `pipeline` mesh axis (replicated when
    pipeline=1)."""
    L = "layers"
    blocks: Dict[str, Any] = {
        "ln1": {"scale": (L, "embed"), "bias": (L, "embed")},
        "qkv": {"kernel": (L, "embed", "heads"), "bias": (L, "heads")},
        "attn_out": {"kernel": (L, "heads", "embed"), "bias": (L, "embed")},
        "ln2": {"scale": (L, "embed"), "bias": (L, "embed")},
    }
    if cfg.num_experts > 1:
        blocks["moe"] = {
            "router": {"kernel": (L, "embed", None)},
            "up": {"kernel": (L, "expert", "embed", "mlp"),
                   "bias": (L, "expert", "mlp")},
            "down": {"kernel": (L, "expert", "mlp", "embed"),
                     "bias": (L, "expert", "embed")},
        }
    else:
        blocks["mlp_up"] = {"kernel": (L, "embed", "mlp"), "bias": (L, "mlp")}
        blocks["mlp_down"] = {"kernel": (L, "mlp", "embed"),
                              "bias": (L, "embed")}
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": blocks,
        "ln_f": {"scale": ("embed",), "bias": ("embed",)},
    }


# ---------------------------------------------------------------- forward


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _attention(q, k, v, cfg: Config, rules: Optional[LogicalRules]):
    """q,k,v: [B, S, H, Dh]. Causal self-attention."""
    if cfg.attention_impl == "ring":
        from determined_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name="context")
    if cfg.attention_impl == "ulysses":
        from determined_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, causal=True)
    from determined_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True, impl=cfg.attention_impl,
                           bf16=cfg.attention_bf16, rules=rules)


def _fsdp_stripped_entry(entry):
    """One PartitionSpec entry with the fsdp mesh axis removed."""
    if entry is None:
        return None
    if isinstance(entry, (tuple, list)):
        kept = tuple(a for a in entry if a != "fsdp")
        # len() of a Python axis-name tuple, not a traced shape.
        return kept[0] if len(kept) == 1 else (kept or None)  # det: noqa[DTL104]
    return None if entry == "fsdp" else entry


def _gather_block_params(lp, cfg: Config, rules: LogicalRules):
    """Constrain one layer's param slice to its fsdp-UNsharded layout.

    Each leaf keeps every mesh axis its logical spec resolves to except
    "fsdp" — i.e. tensor-parallel shards stay sharded, only the fsdp
    split is gathered. Placing this constraint where the slice enters the
    scan carry is what lets the partitioner issue layer N+1's all-gather
    while layer N's matmuls run (`overlap_allgather`)."""
    axes = param_logical_axes(cfg)["blocks"]

    def one(p, leaf_axes):
        spec = rules.spec(tuple(leaf_axes)[1:])  # drop stacked layers dim
        stripped = jax.sharding.PartitionSpec(
            *[_fsdp_stripped_entry(e) for e in spec])
        try:
            return jax.lax.with_sharding_constraint(p, stripped)
        except (ValueError, RuntimeError):  # no mesh context (eager use)
            return p

    return jax.tree.map(one, lp, axes)


def _scan_overlap(block, x, blocks, cfg: Config, rules: LogicalRules,
                  unroll: int):
    """Layers scan with the fsdp all-gather issued one layer ahead.

    The carry holds the CURRENT layer's already-gathered params; xs are
    the block stack rolled by −1 so iteration i delivers layer i+1's
    shards. The body constrains the incoming slice to the fsdp-stripped
    spec BEFORE running the current block, so the gather collective for
    the next layer overlaps this layer's compute instead of serializing
    in front of it. Arithmetic is identical to the plain scan (asserted
    in tests/test_models.py); the final iteration's rolled-around gather
    of layer 0 is dead and DCE'd or wasted-but-harmless.
    """
    first = jax.tree.map(lambda p: p[0], blocks)
    rest = jax.tree.map(lambda p: jnp.roll(p, -1, axis=0), blocks)
    gathered0 = _gather_block_params(first, cfg, rules)

    def body(carry, lp_next):
        xx, lp = carry
        lp_next = _gather_block_params(lp_next, cfg, rules)
        xx, aux = block(xx, lp)
        return (xx, lp_next), aux

    (x, _), auxs = jax.lax.scan(body, (x, gathered0), rest, unroll=unroll)
    return x, auxs


def _block(x, lp, cfg: Config, rules: Optional[LogicalRules]):
    """One transformer block. x: [B, S, D]; lp: this layer's param slice."""
    b, s, d = x.shape
    h, dh = cfg.n_head, cfg.head_dim
    dt = cfg.dtype

    y = _layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"], cfg.layer_norm_eps)
    qkv = jnp.einsum("bsd,de->bse", y, lp["qkv"]["kernel"].astype(dt)) + lp["qkv"][
        "bias"
    ].astype(dt)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, h, dh)
    v = v.reshape(b, s, h, dh)
    q = shard_logical(q, ("batch", "seq", "heads", "kv"), rules)
    k = shard_logical(k, ("batch", "seq", "heads", "kv"), rules)
    attn = _attention(q, k, v, cfg, rules).reshape(b, s, d)
    attn = (
        jnp.einsum("bsd,de->bse", attn, lp["attn_out"]["kernel"].astype(dt))
        + lp["attn_out"]["bias"].astype(dt)
    )
    x = x + attn
    x = shard_logical(x, ("batch", "seq", "embed"), rules)

    y = _layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"], cfg.layer_norm_eps)
    if cfg.num_experts > 1:
        from determined_tpu.ops.moe import moe_block

        down, aux = moe_block(
            y, lp["moe"], cfg.num_experts, top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor, rules=rules,
        )
    else:
        up = jnp.einsum("bsd,df->bsf", y, lp["mlp_up"]["kernel"].astype(dt)) + lp[
            "mlp_up"
        ]["bias"].astype(dt)
        up = shard_logical(up, ("batch", "seq", "mlp"), rules)
        up = jax.nn.gelu(up, approximate=True)
        down = (
            jnp.einsum("bsf,fd->bsd", up, lp["mlp_down"]["kernel"].astype(dt))
            + lp["mlp_down"]["bias"].astype(dt)
        )
        aux = jnp.zeros((), jnp.float32)
    x = x + down
    return shard_logical(x, ("batch", "seq", "embed"), rules), aux


def _remat(block, cfg: Config):
    """Wrap a block fn in jax.checkpoint per cfg.remat_policy."""
    policies = {
        None: None,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "everything": jax.checkpoint_policies.everything_saveable,
    }
    policy = policies[cfg.remat_policy]
    return jax.checkpoint(block, policy=policy) if policy else jax.checkpoint(block)


def _nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean NLL without materialising a full fp32 log-softmax over the
    vocab: nll = logsumexp(logits) - logits[target]. XLA fuses the f32
    upcast into the reduction, so the [B,S,V] array stays bf16 in HBM."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt.astype(jnp.float32))


def _shift(batch: Dict[str, jax.Array]):
    tokens = batch["tokens"]
    if "targets" in batch:
        return tokens, batch["targets"]
    return tokens[:, :-1], tokens[:, 1:]


def _embed_tokens(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: Config,
    rules: Optional[LogicalRules],
    dtype,
) -> jax.Array:
    """Token embedding honoring vocab sharding (shared by apply() and
    apply_pipelined(), so pipeline+vocab-sharded configs don't regress).

    Megatron parallel embedding: with the table ACTUALLY vocab-sharded
    (rules map "vocab" to a >1 mesh axis), a gather forces SPMD into
    involuntary full rematerialization (all-gather the table AND
    replicate the output — the warnings VERDICT r4 weak #2 flags). A
    one-hot matmul instead contracts over vocab locally per shard + one
    psum, native on the MXU. Rules that keep wte replicated keep the
    near-free gather.
    """
    wte = params["wte"].astype(dtype)
    mesh = ambient_mesh()
    vocab_axes = (rules or LogicalRules()).mesh_axes("vocab")
    if isinstance(vocab_axes, str):
        vocab_axes = (vocab_axes,)
    vocab_sharded = mesh is not None and any(
        (mesh.shape.get(a, 1) or 1) > 1 for a in (vocab_axes or ()))
    if vocab_sharded:
        return jax.nn.one_hot(tokens, cfg.vocab_size, dtype=dtype) @ wte
    return wte[tokens]


def apply(
    params: Dict[str, Any],
    tokens: jax.Array,  # [B, S] int32
    cfg: Config,
    rules: Optional[LogicalRules] = None,
    return_aux: bool = False,
):
    """Forward pass → logits [B, S, vocab] (bf16); with return_aux also the
    mean MoE load-balance loss (0 for dense configs)."""
    b, s = tokens.shape
    dt = cfg.dtype
    x = _embed_tokens(params, tokens, cfg, rules, dt)
    x = x + params["wpe"].astype(dt)[:s][None]
    x = shard_logical(x, ("batch", "seq", "embed"), rules)

    block = partial(_block, cfg=cfg, rules=rules)
    if cfg.remat:
        block = _remat(block, cfg)

    def scan_body(carry, lp):
        x, aux = block(carry, lp)
        return x, aux

    unroll = cfg.scan_unroll if cfg.scan_unroll > 0 else cfg.n_layer
    if cfg.overlap_allgather and rules is not None:
        x, auxs = _scan_overlap(block, x, params["blocks"], cfg, rules,
                                unroll)
    else:
        x, auxs = jax.lax.scan(scan_body, x, params["blocks"], unroll=unroll)
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], cfg.layer_norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(dt))
    logits = shard_logical(logits, ("batch", "seq", "vocab"), rules)
    if return_aux:
        return logits, jnp.mean(auxs)
    return logits


def apply_pipelined(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: Config,
    mesh,
    rules: Optional[LogicalRules] = None,
    num_microbatches: Optional[int] = None,
) -> jax.Array:
    """Forward pass with the transformer blocks run as pipeline stages over
    the mesh's `pipeline` axis (GPipe schedule; parallel/pipeline.py).
    Embedding and the LM head run outside the pipeline on every stage."""
    from determined_tpu.parallel.pipeline import (
        pipeline_apply, pipeline_microbatches_default)

    b, s = tokens.shape
    # Activation dtype: cfg.dtype (bf16) when the mesh is TPU chips —
    # embedding, pipeline body, and head all match the non-pipelined
    # apply(). XLA's CPU SPMD partitioner crashes on low-precision
    # activation gradients around a partial-manual shard_map ("Invalid
    # binary instruction opcode copy"), so everything runs f32 there
    # (weights still cast in _block).
    compute = cfg.dtype if on_tpu(mesh.devices.flat) else jnp.float32
    x = (_embed_tokens(params, tokens, cfg, rules, compute)
         + params["wpe"].astype(compute)[:s][None])
    x = shard_logical(x, ("batch", "seq", "embed"), rules)

    if cfg.num_experts > 1:
        raise NotImplementedError(
            "MoE blocks are not supported under pipeline parallelism yet — "
            "drop the pipeline axis or use a dense config"
        )

    def block(xx, lp):
        return _block(xx.astype(compute), lp, cfg, rules)[0].astype(compute)

    if cfg.remat:
        block = _remat(block, cfg)
    m = num_microbatches or pipeline_microbatches_default(mesh, b, rules)
    x = pipeline_apply(block, params["blocks"], x, mesh=mesh,
                       num_microbatches=m, rules=rules,
                       compute_dtype=compute)
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"],
                    cfg.layer_norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(compute))
    return shard_logical(logits, ("batch", "seq", "vocab"), rules)


def loss_fn_pipelined(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: Config,
    mesh,
    rules: Optional[LogicalRules] = None,
    num_microbatches: Optional[int] = None,
) -> jax.Array:
    tokens = batch["tokens"]
    if "targets" in batch:
        inputs, targets = tokens, batch["targets"]
    else:
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = apply_pipelined(params, inputs, cfg, mesh, rules,
                             num_microbatches)
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt.astype(jnp.float32))


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],  # {"tokens": [B, S+1]} or {"tokens","targets"}
    cfg: Config,
    rules: Optional[LogicalRules] = None,
) -> jax.Array:
    tokens = batch["tokens"]
    if "targets" in batch:
        inputs, targets = tokens, batch["targets"]
    else:
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = apply(params, inputs, cfg, rules, return_aux=True)
    # NLL without materialising a full fp32 log-softmax over the vocab:
    # nll = logsumexp(logits) - logits[target]. XLA fuses the f32 upcast into
    # the reduction, so the [B,S,V] array stays bf16 in HBM.
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - tgt.astype(jnp.float32))
    if cfg.num_experts > 1:
        nll = nll + cfg.moe_aux_coef * aux
    return nll
