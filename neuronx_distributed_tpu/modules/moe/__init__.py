"""Mixture-of-Experts stack (reference: ``src/neuronx_distributed/modules/moe/``).

Layout mirrors the reference package:
  * :mod:`routing` — linear router + TopK / Sinkhorn selection
    (reference routing.py:12,127,169)
  * :mod:`expert_mlps` — the expert computation strategies
    (reference expert_mlps.py:30, dispatch policy at :595)
  * :mod:`moe_parallel_layers` — expert-fused 3D-weight sharded linears
    (reference moe_parallel_layers.py:166,256)
  * :mod:`token_shuffling` — DP load-balance shuffle (token_shuffling.py:64)
  * :mod:`loss_function` — Switch-style load-balancing loss (loss_function.py:5)
  * :mod:`model` — the MoE orchestrator layer (model.py:10)
"""

from neuronx_distributed_tpu.modules.moe.expert_mlps import ExpertMLPs
from neuronx_distributed_tpu.modules.moe.loss_function import load_balancing_loss_func
from neuronx_distributed_tpu.modules.moe.model import (
    MOE_CHUNK_STATS,
    MOE_PREFILL_STATS,
    MoE,
    moe_chunk_stats,
    moe_prefill_stats,
)
from neuronx_distributed_tpu.modules.moe.moe_parallel_layers import (
    ExpertFusedColumnParallelLinear,
    ExpertFusedRowParallelLinear,
)
from neuronx_distributed_tpu.modules.moe.routing import RouterSinkhorn, RouterTopK
from neuronx_distributed_tpu.modules.moe.token_shuffling import (
    shuffle_tokens,
    unshuffle_tokens,
)

__all__ = [
    "MOE_CHUNK_STATS",
    "moe_chunk_stats",
    "MOE_PREFILL_STATS",
    "moe_prefill_stats",
    "MoE",
    "ExpertMLPs",
    "RouterTopK",
    "RouterSinkhorn",
    "ExpertFusedColumnParallelLinear",
    "ExpertFusedRowParallelLinear",
    "load_balancing_loss_func",
    "shuffle_tokens",
    "unshuffle_tokens",
]
