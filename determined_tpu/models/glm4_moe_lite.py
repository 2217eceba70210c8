"""GLM-4-MoE-Lite (`model_type: glm4_moe_lite`): a decoder whose attention
keeps one **latent** a token (multi-head latent attention, MLA) and whose
feed-forward layers, after `first_k_dense_replace` dense ones, are a
**dropless** sparse expert layer with a shared expert — DeepSeek-V3's
equations (arXiv:2412.19437 §2.1) under this family's published keys.

This module is the family's `Config`, spelt with the keys of the published
`config.json`. The family is served from a checkpoint
(`serve/glm4_moe_lite.py`: the parameter tree, the layer equations,
prefill, the decode step, the latent pool under the block table); it has
no training step and draws no weights of its own — MLA and a dropless
expert layer under `Trainer.fit` are ROADMAP "Reach".
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 10240       # the leading dense layers' SwiGLU
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1536    # one expert's SwiGLU
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    # The chip's share of a layer's experts: (first, count). The router
    # keeps its width; the layer computes its own experts' part.
    experts_held: Any = None
    dtype: Any = jnp.bfloat16            # activations and matmul operands

    family = "glm4_moe_lite"             # serve/glm4_moe_lite.py

    # Switches of the published config this implementation has one side of.
    _FIXED = {"attention_bias": False, "hidden_act": "silu",
              "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
              "rope_scaling": None, "tie_word_embeddings": False,
              "partial_rotary_factor": 1}

    @classmethod
    def from_published(cls, mc: Mapping[str, Any]) -> "Config":
        """From a mapping spelt as the published `config.json` is (keys
        that say nothing of the shape are passed over;
        `num_nextn_predict_layers` among them: the multi-token-prediction
        module is not loaded, the next-token distribution does not contain
        it), plus `dtype` by name and `experts_held`. A switch set the
        other way than this implementation computes raises."""
        for key, want in cls._FIXED.items():
            if key in mc and mc[key] != want:
                raise ValueError(
                    f"glm4_moe_lite: {key}={mc[key]!r} is not implemented "
                    f"(only {want!r})")
        dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in mc.items() if k in fields}
        for key in ("rope_theta", "routed_scaling_factor"):
            if key in kwargs:
                kwargs[key] = float(kwargs[key])
        if isinstance(kwargs.get("dtype"), str):
            kwargs["dtype"] = dtypes[kwargs["dtype"]]
        if kwargs.get("experts_held") is not None:
            kwargs["experts_held"] = tuple(
                int(n) for n in kwargs["experts_held"])
        cfg = cls(**kwargs)
        heads = mc.get("num_key_value_heads", cfg.num_attention_heads)
        if heads != cfg.num_attention_heads:
            raise ValueError(
                "glm4_moe_lite: every head reads the one latent; "
                f"num_key_value_heads={heads} names no other layout")
        first, count = cfg.held
        if not (0 <= first and count > 0
                and first + count <= cfg.n_routed_experts
                and cfg.num_experts_per_tok <= cfg.n_routed_experts
                and 0 <= cfg.first_k_dense_replace <= cfg.num_hidden_layers):
            raise ValueError(
                "glm4_moe_lite: experts_held must lie inside the routed "
                "experts, and the dense layers inside the depth")
        return cfg

    # The sizes under the names the serving engine's generic parts read.
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def held(self):
        """(first, count) of the routed experts this chip holds."""
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token leaves in the cache a layer: the compressed
        latent and the one rotary key every head shares."""
        return self.kv_lora_rank + self.qk_rope_head_dim
