"""MoE orchestrator layer (reference: ``modules/moe/model.py`` ``MoE:10``,
forward at :116-220).

Reference flow: optional token shuffle over the shuffle group → (SP exit)
all-gather sequence → router → ExpertMLPs → delayed reduce-scatter/all-reduce
back into SP layout → unshuffle. Under GSPMD the SP enter/exit are sharding
constraints and the delayed reduction is the combine einsum inside ExpertMLPs;
the affinity grad copy-to-TP-region trick (model.py:176) is unnecessary —
autodiff of the combine einsum produces exactly that gradient.

Returns ``(output, aux)`` where ``aux`` carries the Switch balance loss and
z-loss terms for the trainer to weight and add (the reference returns router
logits for the same purpose).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.kernels.moe_stream import hit_mask
from neuronx_distributed_tpu.modules.attention import ParallelMLP
from neuronx_distributed_tpu.modules.moe.expert_mlps import (
    LATEST,
    MOE_PREFILL_STATS,
    ExpertMLPs,
)
from neuronx_distributed_tpu.modules.moe.loss_function import (
    load_balancing_loss_func,
    router_z_loss_func,
)
from neuronx_distributed_tpu.modules.moe.routing import make_router
from neuronx_distributed_tpu.modules.moe.token_shuffling import (
    shuffle_tokens,
    unshuffle_tokens,
)
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain

Dtype = Any


# the counters a layer that holds every expert sows each step
MOE_CHUNK_STATS = ("hit_experts", "routed_rows")


def moe_chunk_stats(config) -> Tuple[str, ...]:
    """What a model of such layers names as its ``chunk_stats``, which
    ``inference/generate.chunked_decode_step`` sums over a chunk's steps and
    layers; scanned layers carry no ``stats`` collection."""
    return () if config.scan_layers else MOE_CHUNK_STATS


def moe_prefill_stats(config) -> Tuple[str, ...]:
    """What a model of such layers names as its ``prefill_stats``: the counters
    a layer that is given a ``row_mask`` sows, which the engine's prefill
    program sums over the layers (``serving/engine.py`` ``_prefill_fn``)."""
    return () if config.scan_layers else MOE_PREFILL_STATS


class MoE(nn.Module):
    """Router + experts, on ``(B, S, H)`` activations."""

    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    router_kind: str = "top_k"  # top_k | sinkhorn | mlp
    # ``mlp`` (``routing.RouterMLP``): the width of the router's own state,
    # which the caller passes from layer to layer (``router_state``), and the
    # eps of its RMSNorm
    router_state_size: int = 256
    router_eps: float = 1e-5
    router_act_fn: str = "softmax"
    router_jitter_eps: float = 0.0
    hidden_act: str = "silu"
    glu_mlp: bool = True
    capacity_factor: Optional[float] = None  # None → dropless
    expert_strategy: str = "auto"
    sequence_parallel_enabled: bool = False
    token_shuffle: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # weight-only serving quantization of the EXPERT weights (the router
    # stays float — reference keeps router math in fp32)
    quantization_config: Optional[Any] = None
    # top-k softmax affinities renormalised over the k chosen (Mixtral) or
    # left as they are (DeepSeek-V2, ``norm_topk_prob`` false), then scaled
    normalize_top_k_affinities: bool = True
    routed_scaling_factor: float = 1.0
    # shared experts (DeepSeek): ONE gated MLP of this width (the model's
    # ``n_shared_experts * moe_intermediate_size``) that every token goes
    # through, added to the routed sum; None: no such branch
    shared_intermediate_size: Optional[int] = None
    # DeepSeek-V3's ``noaux_tc``: a learned bias chooses the experts and is no
    # part of their weights (``RouterTopK.selection_bias``)
    router_selection_bias: bool = False
    router_selection_bias_init_std: float = 0.0
    # a linear router's weight starts with every run of this many consecutive
    # experts summing to zero (``routing.zero_sum_runs_lecun_normal``); 0: not
    router_zero_sum_group: int = 0
    # ``(first, count)``: this device holds experts ``[first, first + count)``
    # of ``num_experts`` and computes their part of the routed sum alone, plus
    # the shared expert (``ExpertMLPs.held_experts``). The stats ``held_rows``
    # and ``routed_rows`` (slots routed to a held expert / all slots) are sown
    # into the ``stats`` collection for whoever makes it mutable. A layer that
    # holds every expert sows ``hit_experts`` (distinct experts with at least
    # one row: ``hit_experts x 3 H I`` values are what a streamed decode step
    # reads, ``kernels/moe_stream.py``) and ``routed_rows`` (``MOE_CHUNK_STATS``)
    held_experts: Optional[Tuple[int, int]] = None

    @nn.compact
    def __call__(
        self, x: jax.Array, deterministic: bool = True,
        router_state: Optional[jax.Array] = None,
        row_mask: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """``router_state`` (B, S, ``router_state_size``): the previous
        layer's router state for a router that keeps one (``router_kind=
        "mlp"``; ``None``: the first such layer); this layer's comes back as
        ``aux["router_state"]``. ``row_mask`` (B, S) bool: the rows that hold
        content (a prefill's ``padding_mask``); the router and the shared
        expert see every row, the ROUTED experts give the others zero and,
        where the form can, do no work for them (``ExpertMLPs.__call__``).
        ``None``: every row counts."""
        B, S, H = x.shape
        if self.sequence_parallel_enabled:
            # exit SP: routing needs the full sequence per data shard
            # (reference SP exit all-gather, model.py:116)
            x = constrain(x, P(UNC))
        tokens = x.reshape(B * S, H)

        perm = None
        if self.token_shuffle and not deterministic:
            tokens, perm = shuffle_tokens(tokens, self.make_rng("token_shuffle"))

        router_options = {}
        if not self.normalize_top_k_affinities:
            router_options["normalize_top_k_affinities"] = False
        if self.router_kind == "mlp":
            router_options.update(state_size=self.router_state_size, eps=self.router_eps)
        if self.router_zero_sum_group:
            router_options["zero_sum_group"] = self.router_zero_sum_group
        if self.router_selection_bias:
            router_options.update(
                selection_bias=True,
                selection_bias_init_std=self.router_selection_bias_init_std,
                # a share's biases, and so its rows, are the same under every key
                selection_bias_init_group=self.held_experts[1] if self.held_experts else 0)
        router = make_router(
            self.router_kind,
            hidden_size=self.hidden_size,
            num_experts=self.num_experts,
            top_k=self.top_k,
            act_fn=self.router_act_fn,
            jitter_eps=self.router_jitter_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="router",
            **router_options,
        )
        # named scopes (with the modules' own: this block is ``moe``) so a
        # device trace can be cut into router / dispatch / experts / combine
        stateful = self.router_kind == "mlp"
        if row_mask is not None:
            row_mask = row_mask.reshape(B * S).astype(bool)
            if perm is not None:
                row_mask = row_mask[perm]
        if router_state is not None and (not stateful or perm is not None):
            raise ValueError(
                "router_state is the mlp router's, on tokens in their order: "
                f"router_kind {self.router_kind!r}, token_shuffle {perm is not None}")
        with jax.named_scope("moe.router"):
            if stateful:
                route, new_state = router(
                    tokens,
                    None if router_state is None else router_state.reshape(B * S, -1),
                    deterministic=deterministic)
            else:
                route = router(tokens, deterministic=deterministic)

        top_w = route.top_w
        if self.routed_scaling_factor != 1.0:
            top_w = top_w * self.routed_scaling_factor
        experts = ExpertMLPs(
            num_experts=self.num_experts,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            top_k=self.top_k,
            hidden_act=self.hidden_act,
            glu_mlp=self.glu_mlp,
            capacity_factor=self.capacity_factor,
            strategy=self.expert_strategy,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            quantization_config=self.quantization_config,
            held_experts=self.held_experts,
            name="experts",
        )
        out = experts(tokens, route.top_e, top_w, row_mask)
        if self.held_experts is not None and not self.is_initializing():
            held = experts.held_slots(route.top_e)[1]
            self.sow("stats", "held_rows", jnp.sum(held, dtype=jnp.int32), **LATEST)
            self.sow("stats", "routed_rows", jnp.asarray(held.size, jnp.int32), **LATEST)
        elif self.is_mutable_collection("stats") and not self.is_initializing():
            # only for whoever collects them: a program that does not (a
            # train step) is what it was, and a prefill returns
            # ``MOE_PREFILL_STATS`` alone, so these two are dead code there
            hit = hit_mask(route.top_e, self.num_experts)
            self.sow("stats", "hit_experts", jnp.sum(hit, dtype=jnp.int32), **LATEST)
            self.sow("stats", "routed_rows", jnp.asarray(route.top_e.size, jnp.int32), **LATEST)

        if self.shared_intermediate_size is not None:
            with jax.named_scope("moe.shared"):
                out = out + ParallelMLP(
                    self.hidden_size, self.shared_intermediate_size,
                    activation=self.hidden_act, use_bias=False, glu=True,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="shared",
                )(tokens).astype(out.dtype)

        if perm is not None:
            out = unshuffle_tokens(out, perm)
        out = out.reshape(B, S, H).astype(x.dtype)
        if self.sequence_parallel_enabled:
            # re-enter SP layout (reference delayed reduce-scatter, model.py:200)
            out = constrain(out, P(UNC, (mesh_lib.CP_AXIS, mesh_lib.TP_AXIS)))

        aux = {
            "load_balancing_loss": load_balancing_loss_func(
                route.probs, route.top_e, self.num_experts
            ),
            "router_z_loss": router_z_loss_func(route.logits),
        }
        if stateful:
            aux["router_state"] = new_state.reshape(B, S, -1)
        return out, aux
