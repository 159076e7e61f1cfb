"""DBRX family, TPU-native (reference analogue: ``examples/training/dbrx`` —
fine-grained MoE decoder on the §2.5 MoE stack).

DBRX specifics: GQA attention with fused-QKV geometry, fine-grained MoE
(16 experts, top-4), LayerNorm (not RMSNorm), SwiGLU experts. Router aux
losses aggregate exactly like Mixtral's."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaAttention, rope_frequencies
from neuronx_distributed_tpu.modules.layer_norm import LayerNorm
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
)
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy


@dataclasses.dataclass(frozen=True)
class DbrxConfig:
    vocab_size: int = 100352
    hidden_size: int = 6144
    intermediate_size: int = 10752  # per-expert ffn
    num_layers: int = 40
    num_heads: int = 48
    num_kv_heads: int = 8
    max_seq_len: int = 32768
    rope_theta: float = 5e5
    num_experts: int = 16
    top_k: int = 4
    capacity_factor: Optional[float] = None
    router_aux_loss_coef: float = 0.05
    router_z_loss_coef: float = 0.0
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    remat: bool = False
    # weight-only serving quantization (same contract as Mixtral/Llama)
    quantization: Optional[Any] = None

    @property
    def head_dim_(self) -> int:
        return self.hidden_size // self.num_heads

    def as_llama(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size, num_layers=self.num_layers,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            dtype=self.dtype, param_dtype=self.param_dtype,
            sequence_parallel=self.sequence_parallel, remat=self.remat,
            scan_layers=False, quantization=self.quantization,
        )


def dbrx_base(**over) -> DbrxConfig:
    return DbrxConfig(**over)


def tiny_dbrx(**over) -> DbrxConfig:
    return DbrxConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=8, num_kv_heads=4, max_seq_len=64, num_experts=8, top_k=2,
        dtype=jnp.float32,
    ), **over})


class DbrxBlock(nn.Module):
    config: DbrxConfig
    attention_impl: str = "auto"
    deterministic: bool = True
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, segment_ids=None,
                 padding_mask=None):
        cfg = self.config
        # bias-free LayerNorm — DBRX's norms carry no bias (HF modeling_dbrx),
        # and a native-only bias would be silently dropped on HF export
        norm = dict(eps=cfg.layer_norm_eps, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype)
        h = LayerNorm(cfg.hidden_size, name="norm_1", **norm)(x)
        x = x + LlamaAttention(
            cfg.as_llama(), self.attention_impl, self.mode, name="attn"
        )(h, freqs, positions, None, segment_ids, padding_mask)
        h = LayerNorm(cfg.hidden_size, name="norm_2", **norm)(x)
        moe_out, aux = MoE(
            num_experts=cfg.num_experts,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            quantization_config=cfg.quantization,
            name="moe",
        )(h, deterministic=self.deterministic,
          row_mask=padding_mask if self.mode == "prefill" else None)
        x = x + moe_out
        return x, jnp.stack([aux["load_balancing_loss"], aux["router_z_loss"]])


class DbrxForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``."""

    config: DbrxConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(
        self, input_ids, positions=None, deterministic: bool = True,
        segment_ids=None, padding_mask=None,
    ) -> Tuple[jax.Array, dict]:
        cfg = self.config
        x = ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        freqs = rope_frequencies(cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta)
        aux_sum = jnp.zeros((2,), jnp.float32)
        block_cls = nn.remat(DbrxBlock) if cfg.remat else DbrxBlock
        for i in range(cfg.num_layers):
            x, aux = block_cls(
                cfg, self.attention_impl, deterministic, self.mode,
                name=f"blocks_{i}",
            )(x, freqs, positions, segment_ids, padding_mask)
            aux_sum = aux_sum + aux
        x = LayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps, use_bias=False,
                      dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                      name="final_norm")(x)
        if self.mode == "prefill":
            x = x[:, -1:]
        logits = ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            quantization_config=cfg.quantization, name="lm_head",
        )(x)
        return logits, {
            "load_balancing_loss": aux_sum[0], "router_z_loss": aux_sum[1]
        }

    def loss(self, params, input_ids, labels, deterministic: bool = True,
             segment_ids=None, loss_mask=None):
        """``segment_ids``/``loss_mask``: packed-document training (see
        MixtralForCausalLM.loss)."""
        positions = None
        if segment_ids is not None:
            from neuronx_distributed_tpu.trainer.trainer import (
                segment_positions,
            )

            positions = segment_positions(segment_ids)
        logits, aux = self.apply(
            params, input_ids, positions=positions,
            deterministic=deterministic, segment_ids=segment_ids,
        )
        tok = parallel_cross_entropy(logits, labels)
        if loss_mask is not None:
            ce = (tok * loss_mask).sum() / jnp.maximum(loss_mask.sum(), 1)
        else:
            ce = tok.mean()
        return (
            ce
            + self.config.router_aux_loss_coef * aux["load_balancing_loss"]
            + self.config.router_z_loss_coef * aux["router_z_loss"]
        )
