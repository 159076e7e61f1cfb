"""Medusa multi-head model (reference: ``utils/medusa_utils.py`` buffers +
``examples/inference/run_llama_medusa.py`` — the Medusa-1 architecture:
a frozen base LM plus K extra decoding heads, each a residual SiLU block
followed by an lm_head-shaped projection, predicting tokens t+2..t+K+1).

The wrapper shares the Llama backbone (mode/cache threading included), so the
same params serve train, prefill, decode and Medusa tree-verify calls."""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaModel
from neuronx_distributed_tpu.parallel.layers import ColumnParallelLinear


class MedusaResBlock(nn.Module):
    """h + SiLU(W·h) — the reference medusa head block."""

    hidden_size: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = ColumnParallelLinear(
            self.hidden_size, self.hidden_size, use_bias=True,
            gather_output=True, dtype=self.dtype, param_dtype=self.param_dtype,
            name="proj",
        )(x)
        return x + jax.nn.silu(h)


def medusa_head_loss(model, params, input_ids, labels):
    """Medusa-1 head-training objective (reference: the medusa training recipe
    behind examples/inference/run_llama_medusa.py): head i predicts the token
    ``i+2`` positions ahead, so its CE target is ``labels`` shifted left by
    ``i+1``; positions without a target are masked. The base LM is typically
    frozen — close over base params and differentiate w.r.t. the head subtree
    only (the functional-freeze pattern modules/lora.py uses)."""
    from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy

    _logits, med = model.apply(params, input_ids)  # med: (B, S, heads, V)
    b, s, n_heads, _v = med.shape
    total = jnp.zeros((), jnp.float32)
    for i in range(n_heads):
        shift = i + 1
        tgt = jnp.roll(labels, -shift, axis=1)
        valid = (jnp.arange(s) < s - shift).astype(jnp.float32)[None]
        losses = parallel_cross_entropy(med[:, :, i], tgt)
        total = total + (losses * valid).sum() / jnp.maximum(valid.sum() * b, 1.0)
    return total / n_heads


class MedusaForCausalLM(nn.Module):
    """Base Llama + ``num_medusa_heads`` decoding heads. Returns
    ``(logits (B,S,V), medusa_logits (B,S,heads,V))``. In ``prefill`` mode the
    head AND the Medusa heads are applied to the LAST position alone (S = 1):
    the contract every causal LM here keeps, stated in ``models/__init__.py``."""

    config: LlamaConfig
    num_medusa_heads: int = 4
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, attn_mask=None) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        x = LlamaModel(cfg, self.attention_impl, self.mode, name="model")(
            input_ids, positions, attn_mask
        )
        if self.mode == "prefill":
            x = x[:, -1:]
        head = ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="lm_head",
        )
        logits = head(x)
        med = []
        for i in range(self.num_medusa_heads):
            h = MedusaResBlock(
                cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name=f"medusa_{i}",
            )(x)
            med.append(
                ColumnParallelLinear(
                    cfg.hidden_size, cfg.vocab_size, use_bias=False,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    name=f"medusa_head_{i}",
                )(h)
            )
        return logits, jnp.stack(med, axis=-2)  # (B, S, heads, V)
