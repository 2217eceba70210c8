"""Falcon-H1: a parallel-hybrid decoder — in every block a Mamba-2 mixer
beside grouped-query attention on the same normed input, then SwiGLU.

This module is the family's `Config`, spelt with the keys of the
published `config.json` (`tiiuae/Falcon-H1-*`). The family is served from
a checkpoint (`serve/falcon_h1.py`: the parameter tree with its layers
stacked for `lax.scan`, the layer equations, prefill, the decode step, the
recurrent-state pool beside the paged K/V pool); it has no training step
and so draws no weights of its own yet — a chunked scan with a backward
pass is ROADMAP "Reach".
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    dtype: Any = jnp.bfloat16        # activations and matmul operands
    state_dtype: Any = jnp.float32   # the SSM state at rest

    family = "falcon_h1"             # its serving steps: serve/falcon_h1.py

    # Switches of the published config this implementation has one side of.
    _FIXED = {"attention_bias": False, "mlp_bias": False,
              "mamba_proj_bias": False, "projectors_bias": False,
              "mamba_norm_before_gate": False, "tie_word_embeddings": False,
              "mamba_rms_norm": True, "mamba_conv_bias": True,
              "hidden_act": "silu", "rope_scaling": None}

    @classmethod
    def from_published(cls, mc: Mapping[str, Any]) -> "Config":
        """From a mapping spelt as the published `config.json` is (keys
        that say nothing of the shape are passed over), plus `dtype` and
        `state_dtype` by name. A switch set the other way than this
        implementation computes raises: a silent other model would be
        worse."""
        for key, want in cls._FIXED.items():
            if key in mc and mc[key] != want:
                raise ValueError(
                    f"falcon_h1: {key}={mc[key]!r} is not implemented "
                    f"(only {want!r})")
        dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in mc.items() if k in fields}
        for key in ("ssm_multipliers", "mlp_multipliers"):
            if key in kwargs:
                kwargs[key] = tuple(float(m) for m in kwargs[key])
        if "rope_theta" in kwargs:     # published as an integer, 1e11
            kwargs["rope_theta"] = float(kwargs["rope_theta"])
        for key in ("dtype", "state_dtype"):
            if isinstance(kwargs.get(key), str):
                kwargs[key] = dtypes[kwargs[key]]
        cfg = cls(**kwargs)
        d_ssm = mc.get("mamba_d_ssm") or cfg.d_ssm
        if d_ssm != cfg.d_ssm or cfg.mamba_n_heads % cfg.mamba_n_groups \
                or cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(
                "falcon_h1: mixer heads must tile mamba_d_ssm and its "
                "groups, and query heads their K/V heads")
        return cfg

    # The sizes under the names the serving engine's generic parts read.
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def d_ssm(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        """[x | B | C]: what the depthwise convolution runs over."""
        return self.d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_sections(self) -> Tuple[int, ...]:
        """Column sections of `in_proj`: [z | x | B | C | dt]."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.mamba_n_heads)
