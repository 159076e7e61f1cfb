"""RMSNorm (reference: ``modules/rms_norm.py`` — fp32-upcast RMS norm whose
weight is tagged ``sequence_parallel_enabled`` so the trainer all-reduces its
grad over the TP group, grads.py:330).

On TPU the grad handling is automatic: when activations are sequence-sharded
over tp, XLA partitions the weight-grad reduction itself — no marked-parameter
bookkeeping. The ``sequence_parallel_enabled`` flag here only constrains the
OUTPUT layout so the next layer sees SP activations.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain


class RMSNorm(nn.Module):
    hidden_size: int
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    sequence_parallel_enabled: bool = False
    axis: str = mesh_lib.TP_AXIS
    # the gain every channel starts at (a trained checkpoint carries its own)
    weight_init: float = 1.0

    @nn.compact
    def __call__(self, x):
        # 1.0 keeps ``ones_init``: every other model's init program stays as it was
        init = (nn.initializers.ones_init() if self.weight_init == 1.0
                else nn.initializers.constant(self.weight_init))
        weight = self.param(
            "weight",
            nn.with_partitioning(init, (None,)),
            (self.hidden_size,),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        y = (y * weight.astype(jnp.float32)).astype(self.dtype)
        if self.sequence_parallel_enabled and y.ndim >= 3:
            y = constrain(y, P(*([UNC] * (y.ndim - 2)), self.axis))
        return y
