"""CodeGen 2.5 family, TPU-native (reference analogue:
``examples/training/codegen25`` — GPT-J/CodeGen architecture through the §2.1
sharded layers).

CodeGen specifics: GPT-J-style PARALLEL residual with a SINGLE input
LayerNorm feeding both attention and MLP (unlike NeoX's two norms), partial
rotary over ``rotary_dim`` channels, biased MLP but bias-free attention
projections."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.modules.attention import ParallelMLP, ParallelSelfAttention
from neuronx_distributed_tpu.modules.layer_norm import LayerNorm
from neuronx_distributed_tpu.modules.remat import remat_layer_cls
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
)
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy


@dataclasses.dataclass(frozen=True)
class CodeGenConfig:
    vocab_size: int = 51200
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_layers: int = 32
    num_heads: int = 32
    max_seq_len: int = 2048
    rotary_dim: int = 64
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    # activation checkpointing per block: the backward pass re-runs a block's
    # forward from its input, but for what ``remat_policy`` keeps
    remat: bool = False
    # what a remat'd block keeps (modules/remat.py has the table and the
    # measurements). The default keeps, a layer a chip, the MLP
    # up-projection's pre-activation (``B S intermediate_size / tp`` values:
    # 134 MB in bf16 at 8 x 2048 tokens, 16384 and tp=4), q, k and v after
    # rotary (``3 B S hidden_size / tp``: 101 MB) and the flash forward's
    # outputs (34 MB + the row sums): no matmul and no kernel of the block is
    # then run twice, where "save nothing" runs 7 of the block's 12 H^2 of
    # matmuls and the flash forward again. "mlp_up" keeps the first alone;
    # None = save nothing, for whoever sits at the memory limit; "dots" /
    # "dots_saveable" as LlamaConfig's.
    remat_policy: Optional[str] = "mlp_up+attn"

    @property
    def head_dim_(self) -> int:
        return self.hidden_size // self.num_heads


def codegen25_7b(**over) -> CodeGenConfig:
    return CodeGenConfig(**over)


def tiny_codegen(**over) -> CodeGenConfig:
    return CodeGenConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=256, num_layers=2,
        num_heads=8, max_seq_len=64, rotary_dim=4, dtype=jnp.float32,
    ), **over})


class CodeGenBlock(nn.Module):
    config: CodeGenConfig
    mode: str = "train"

    @nn.compact
    def __call__(self, x, positions=None, segment_ids=None, padding_mask=None):
        cfg = self.config
        common = dict(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                      sequence_parallel_enabled=cfg.sequence_parallel)
        # single shared LN feeds both branches (GPT-J formulation)
        h = LayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="input_norm")(x)
        attn = ParallelSelfAttention(
            hidden_size=cfg.hidden_size, num_heads=cfg.num_heads, causal=True,
            use_bias=False, rotary_pct=cfg.rotary_dim / cfg.head_dim_,
            rope_theta=cfg.rope_theta, max_seq_len=cfg.max_seq_len,
            mode=self.mode, name="attn", **common,
        )(h, positions, padding_mask, segment_ids)
        mlp = ParallelMLP(
            hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
            activation="gelu_new", use_bias=True, name="mlp", **common,
        )(h)
        return x + attn + mlp


class CodeGenForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``."""

    config: CodeGenConfig
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 padding_mask=None):
        cfg = self.config
        x = ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        block_cls = remat_layer_cls(CodeGenBlock, cfg.remat, cfg.remat_policy)
        for i in range(cfg.num_layers):
            x = block_cls(cfg, self.mode, name=f"blocks_{i}")(
                x, positions, segment_ids, padding_mask
            )
        x = LayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="final_norm")(x)
        if self.mode == "prefill":
            x = x[:, -1:]
        return ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, use_bias=True, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="lm_head",
        )(x)

    def loss(self, params, input_ids, labels):
        return parallel_cross_entropy(self.apply(params, input_ids), labels).mean()
