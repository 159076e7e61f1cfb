"""GPT-NeoX family, TPU-native (reference analogue:
``examples/training/tp_dp_gpt_neox_hf_pretrain`` — the 20B pretrain example
wired through §2.1 sharded layers).

NeoX specifics reproduced: PARALLEL residual (x + attn(ln1(x)) + mlp(ln2(x))),
partial rotary (``rotary_pct`` of each head dim), LayerNorm with bias, biased
linears throughout."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.modules.attention import ParallelMLP, ParallelSelfAttention
from neuronx_distributed_tpu.modules.layer_norm import LayerNorm
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
)
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 6144
    intermediate_size: int = 24576
    num_layers: int = 44
    num_heads: int = 64
    max_seq_len: int = 2048
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    remat: bool = False


def gpt_neox_20b(**over) -> GPTNeoXConfig:
    return GPTNeoXConfig(**over)


def tiny_gpt_neox(**over) -> GPTNeoXConfig:
    return GPTNeoXConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=256, num_layers=2,
        num_heads=8, max_seq_len=64, dtype=jnp.float32,
    ), **over})


class GPTNeoXLayer(nn.Module):
    config: GPTNeoXConfig
    mode: str = "train"

    @nn.compact
    def __call__(self, x, positions=None, segment_ids=None, padding_mask=None):
        cfg = self.config
        norm = dict(eps=cfg.layer_norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        common = dict(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                      sequence_parallel_enabled=cfg.sequence_parallel)
        attn_in = LayerNorm(cfg.hidden_size, name="input_norm", **norm)(x)
        attn = ParallelSelfAttention(
            hidden_size=cfg.hidden_size, num_heads=cfg.num_heads, causal=True,
            use_bias=True, rotary_pct=cfg.rotary_pct, rope_theta=cfg.rope_theta,
            max_seq_len=cfg.max_seq_len, mode=self.mode, name="attn", **common,
        )(attn_in, positions, padding_mask, segment_ids)
        if cfg.use_parallel_residual:
            # x + attn(ln1(x)) + mlp(ln2(x)) — NeoX's parallel formulation
            mlp_in = LayerNorm(cfg.hidden_size, name="post_attn_norm", **norm)(x)
            mlp = ParallelMLP(
                hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
                activation="gelu", use_bias=True, name="mlp", **common,
            )(mlp_in)
            return x + attn + mlp
        x = x + attn
        mlp_in = LayerNorm(cfg.hidden_size, name="post_attn_norm", **norm)(x)
        return x + ParallelMLP(
            hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
            activation="gelu", use_bias=True, name="mlp", **common,
        )(mlp_in)


class GPTNeoXForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``."""

    config: GPTNeoXConfig
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 padding_mask=None):
        cfg = self.config
        x = ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        layer_cls = nn.remat(GPTNeoXLayer) if cfg.remat else GPTNeoXLayer
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, self.mode, name=f"layers_{i}")(
                x, positions, segment_ids, padding_mask
            )
        x = LayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="final_norm")(x)
        if self.mode == "prefill":
            x = x[:, -1:]
        return ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="lm_head",
        )(x)

    def loss(self, params, input_ids, labels):
        return parallel_cross_entropy(self.apply(params, input_ids), labels).mean()
