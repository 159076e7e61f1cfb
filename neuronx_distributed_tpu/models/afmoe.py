"""Arcee's Trinity language models (``model_type: afmoe``), TPU-native: a
stack that MIXES two kinds of attention layer, window layers (a query reads
its last ``sliding_window`` tokens) and full layers, gated grouped-query
attention with rotary on the window layers only, four norms a block, a few
leading dense layers and then a sparse layer of sigmoid-routed experts under a
selection bias with one shared expert. Built from the parallel layers,
``RMSNorm`` and ``modules/moe`` as ``models/glm_moe_dsa.py`` is. Config:
``arcee-ai/Trinity-Large-Preview`` (``config.json``, 400B-A13B).

Layer equations (``h`` hidden, ``H`` query and ``Hkv`` key/value heads of
``D``, RMSNorm eps ``rms_eps`` with a learned scale everywhere, no biases):

* ``x0 = E[ids] * sqrt(h)`` (``mup_enabled``).
* block ``l``: ``a = Attn_l(N1(x))``; ``x = x + N2(a)``; ``m = F_l(N3(x))``;
  ``x = x + N4(m)`` (sandwich norm: input, post-attention, pre-MLP,
  post-MLP).
* ``Attn_l(u)``: ``q = Wq u`` (H x D), ``k = Wk u``, ``v = Wv u`` (Hkv x D),
  ``g = Wg u`` (H D); ``q, k`` through an RMSNorm over each head's D
  channels. ``layer_types[l] == "sliding_attention"``: rotary over all D
  channels (channel ``i`` paired with ``i + D/2``) and key ``j`` visible to
  query ``i`` iff ``i - sliding_window < j <= i``. ``"full_attention"``: NO
  rotary, causal. Scores ``/ sqrt(D)``, softmax, GQA. ``Attn = Wo (o *
  sigmoid(g))``.
* ``F_l``, ``l < num_dense_layers``: a SwiGLU MLP of ``intermediate_size``.
  Else ``s = sigmoid(Wr u)`` in float32; ``S = top_k(s + b)`` (``b`` the
  selection bias: it selects and does not weigh); ``w = s[S] / sum s[S] *
  route_scale``; ``F = Shared(u) + sum_{e in S} w_e Expert_e(u)``, every
  expert and the shared expert a SwiGLU of ``moe_intermediate_size``. One
  routing group: no group limit.
* ``logits = Wh N(x)``, untied.

The cache is a :class:`~neuronx_distributed_tpu.modules.attention.
JoinedKVCache` a layer: K and V one joined leaf, a window layer's node
carrying its window, so the paged cache manager gives the window kind a block
table and a pool of its own and frees its pages behind the window
(``serving/paging.py``). Prefill runs a window layer through the banded flash
forward and a full layer through the flash forward every other model's
prefill runs, decode the kernel that walks the blocks a slot maps (both kinds
of layer); training runs the prefill mathematics through the float32 einsum
(the banded kernel has no backward; serving is what this model is here for).

Assumed (the config has no key for them; the published modelling code as
remembered): the embedding scale ``sqrt(h)``; the normaliser's ``1e-20``
(``modules/moe`` guards with ``max(., 1e-9)``: the same for any positive
sum); "depth-scaled" names the initialisation of the norm gains, not an
operation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.modules.attention import (
    ATTN_FULL_SCOPE,
    ATTN_GATE_SCOPE,
    ATTN_WINDOW_SCOPE,
    JoinedKVCache,
    ParallelMLP,
    apply_rope,
    joined_decode_attention,
    prefill_positions,
    rope_frequencies,
    window_prefill_attention,
)
from neuronx_distributed_tpu.modules.moe import MoE, moe_prefill_stats
from neuronx_distributed_tpu.modules.qkv_linear import GQAQKVColumnParallelLinear
from neuronx_distributed_tpu.modules.rms_norm import RMSNorm
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
)
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288          # the leading dense layers' MLP
    moe_intermediate_size: int = 3072       # one routed expert
    num_layers: int = 60
    num_dense_layers: int = 6
    # one entry a layer that is RUN, SLIDING or FULL; None: the published
    # pattern, a full layer every ``global_attn_every_n_layers``-th
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 4096
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256                  # the router's outputs
    top_k: int = 4
    num_shared_experts: int = 1
    route_scale: float = 2.448
    route_norm: bool = True
    mup_enabled: bool = True
    # (first, count): the routed experts this device holds; None: all
    held_experts: Optional[Tuple[int, int]] = None
    # the normal whose quantiles the selection bias is drawn from at init, the
    # same values in a held share under every key (published checkpoints
    # carry a trained one)
    router_bias_init_std: float = 0.0
    # the gains the q/k head norms and the post-attention norm start at
    # ("depth-scaled" names an initialisation of the gains; published
    # checkpoints carry trained ones). With random weights they decide how
    # peaked attention is (scores have std ``qk_norm_init ** 2``) and how
    # much of the stream it is beside the scaled embedding
    qk_norm_init: float = 1.0
    post_attn_norm_init: float = 1.0
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    expert_strategy: str = "auto"
    router_aux_loss_coef: float = 5e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # the serving engine's fused paged path reads this (layers are unrolled)
    scan_layers: bool = False
    # what the serving engine must know of the cache: K and V one joined
    # leaf (modules/attention.py JoinedKVCache); its paged kernel has no
    # sharded form
    kv_cache_kind: str = "joined"

    def __post_init__(self):
        types = self.layer_types
        if types is None:
            every = self.global_attn_every_n_layers
            types = tuple(FULL if (i + 1) % every == 0 else SLIDING
                          for i in range(self.num_layers))
            object.__setattr__(self, "layer_types", types)
        if len(types) != self.num_layers or any(t not in (SLIDING, FULL) for t in types):
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each {SLIDING!r} or "
                f"{FULL!r}; got {types}")

    def layer_window(self, i: int) -> Optional[int]:
        """Layer ``i``'s window; ``None`` for a full-attention layer."""
        return self.sliding_window if self.layer_types[i] == SLIDING else None

    @property
    def kv_cache_window(self) -> Optional[int]:
        """What the serving engine must know of the cache's window kind: the
        window its window layers' pages are freed behind, ``None`` without
        such layers."""
        return self.sliding_window if SLIDING in self.layer_types else None


def trinity_large(**over) -> AfmoeConfig:
    """``arcee-ai/Trinity-Large-Preview`` as published."""
    return AfmoeConfig(**over)


def tiny_afmoe(**over) -> AfmoeConfig:
    """Shrunk config for tests with every mechanism present: one dense layer,
    then window, window, full (a window of 32, so a context past 32 tokens is
    banded), gated GQA with head norms, 16 experts top-2 under a selection
    bias with a shared expert."""
    return AfmoeConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
        num_layers=4, num_dense_layers=1, layer_types=(SLIDING, SLIDING, SLIDING, FULL),
        sliding_window=32, num_heads=4, num_kv_heads=2, head_dim=16, num_experts=16,
        top_k=2, router_bias_init_std=0.1, max_seq_len=128, dtype=jnp.float32,
    ), **over})


# --- attention ------------------------------------------------------------------


class AfmoeAttention(nn.Module):
    """Gated GQA of one KIND (module docstring): ``window`` the layer's
    window, ``None`` a full layer. ``mode``: ``train`` / ``prefill`` attend
    the prompt inside the band; prefill also writes K and V into a
    :class:`JoinedKVCache`; ``decode`` attends that cache."""

    config: AfmoeConfig
    window: Optional[int] = None
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, padding_mask=None):
        cfg = self.config
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]

        q, k, v = GQAQKVColumnParallelLinear(
            hidden_size=cfg.hidden_size, num_heads=h, num_kv_heads=hkv, head_dim=d,
            name="qkv", **lin,
        )(x)
        with jax.named_scope(ATTN_GATE_SCOPE):
            gate = ColumnParallelLinear(cfg.hidden_size, h * d, name="gate_proj", **lin)(x)
        windowed = self.window is not None
        # the scope names the layer's kind for a trace reader; a Pallas kernel
        # called inside it is named after it
        with jax.named_scope(ATTN_WINDOW_SCOPE if windowed else ATTN_FULL_SCOPE):
            head_norm = dict(norm, weight_init=cfg.qk_norm_init)
            q = RMSNorm(d, name="q_norm", **head_norm)(q.reshape(b, s, h, d))
            k = RMSNorm(d, name="k_norm", **head_norm)(k.reshape(b, s, hkv, d))
            v = v.reshape(b, s, hkv, d)
            q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))

            def rope(pos):   # the window layers alone carry positions
                return (apply_rope(q, freqs, pos), apply_rope(k, freqs, pos)) if windowed else (q, k)

            if self.mode == "decode":
                cache = JoinedKVCache(self, b, cfg.max_seq_len, hkv, d, k.dtype, self.window)
                pos, rope_pos = cache.decode_positions(s, positions)
                q, k = rope(rope_pos)
                cache.decode_write(k, v, padding_mask)
                out = joined_decode_attention(
                    q, cache.kv.value, pos, cache.valid.value, self.window)
            else:
                if self.mode == "prefill":
                    if positions is None and padding_mask is not None:
                        positions = prefill_positions(padding_mask)
                elif self.mode != "train":
                    raise ValueError(f"unknown attention mode {self.mode!r}")
                q, k = rope(positions)
                if self.mode == "prefill":
                    if s > cfg.max_seq_len:
                        raise ValueError(
                            f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}")
                    JoinedKVCache(
                        self, b, cfg.max_seq_len, hkv, d, k.dtype, self.window
                    ).prefill_write(k, v, padding_mask)
                out = window_prefill_attention(
                    q, k, v, self.window,
                    # training differentiates: the banded flash kernel is forward only
                    impl="xla" if self.mode == "train" else self.attention_impl,
                    mask=padding_mask,
                )
        with jax.named_scope(ATTN_GATE_SCOPE):
            out = out.reshape(b, s, h * d) * jax.nn.sigmoid(gate)
        return RowParallelLinear(h * d, cfg.hidden_size, name="o_proj", **lin)(out)


# --- the model ------------------------------------------------------------------


class AfmoeDecoderLayer(nn.Module):
    config: AfmoeConfig
    layer_index: int
    attention_impl: str = "auto"
    deterministic: bool = True
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, padding_mask=None):
        cfg = self.config
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)

        def rms(name, y, init=1.0):
            return RMSNorm(cfg.hidden_size, name=name, weight_init=init, **norm)(y)

        attn = AfmoeAttention(
            cfg, cfg.layer_window(self.layer_index), self.attention_impl, self.mode,
            name="attn",
        )(rms("input_norm", x), freqs, positions, padding_mask)
        x = x + rms("post_attn_norm", attn, cfg.post_attn_norm_init)
        h = rms("pre_mlp_norm", x)
        if self.layer_index < cfg.num_dense_layers:
            out = ParallelMLP(
                cfg.hidden_size, cfg.intermediate_size, activation="silu",
                use_bias=False, glu=True, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="mlp",
            )(h)
            aux = jnp.zeros((2,), jnp.float32)
        else:
            out, losses = MoE(
                num_experts=cfg.num_experts,
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.moe_intermediate_size,
                top_k=cfg.top_k,
                router_act_fn="sigmoid",
                router_selection_bias=True,
                router_selection_bias_init_std=cfg.router_bias_init_std,
                expert_strategy=cfg.expert_strategy,
                normalize_top_k_affinities=cfg.route_norm,
                routed_scaling_factor=cfg.route_scale,
                shared_intermediate_size=(
                    cfg.num_shared_experts * cfg.moe_intermediate_size
                    if cfg.num_shared_experts else None),
                held_experts=cfg.held_experts,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe",
            )(h, deterministic=self.deterministic,
              row_mask=padding_mask if self.mode == "prefill" else None)
            aux = jnp.stack([losses["load_balancing_loss"], losses["router_z_loss"]])
        return x + rms("post_mlp_norm", out), aux


class AfmoeModel(nn.Module):
    """Backbone without the LM head: ``(hidden, aux_losses)``."""

    config: AfmoeConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError("packed documents inside a window are not modelled")
        x = ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        if cfg.mup_enabled:
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
        freqs = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        layer_cls = nn.remat(AfmoeDecoderLayer) if cfg.remat else AfmoeDecoderLayer
        aux_sum = jnp.zeros((2,), jnp.float32)
        for i in range(cfg.num_layers):
            x, aux = layer_cls(
                cfg, i, self.attention_impl, deterministic, self.mode,
                name=f"layers_{i}",
            )(x, freqs, positions, padding_mask)
            aux_sum = aux_sum + aux
        x = RMSNorm(
            cfg.hidden_size, eps=cfg.rms_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="final_norm",
        )(x)
        return x, {"load_balancing_loss": aux_sum[0], "router_z_loss": aux_sum[1]}


class AfmoeForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``. Logits at every position of a context:
    ``mode="train"``, or ``AfmoeModel`` in ``prefill`` mode and the head's
    kernel.

    ``chunk_stats``: the counters a model with held experts sows into the
    ``stats`` collection each decode step (``modules/moe.MoE``). ``prefill_stats``: those a
    prefill's expert layers sow of the rows its ``padding_mask`` kept
    (``modules/moe.moe_prefill_stats``)."""

    config: AfmoeConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @property
    def chunk_stats(self) -> Tuple[str, ...]:
        return ("held_rows", "routed_rows") if self.config.held_experts is not None else ()

    @property
    def prefill_stats(self) -> Tuple[str, ...]:
        return moe_prefill_stats(self.config)

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None) -> Tuple[jax.Array, dict]:
        cfg = self.config
        x, aux = AfmoeModel(cfg, self.attention_impl, self.mode, name="model")(
            input_ids, positions, deterministic, segment_ids, padding_mask)
        if self.mode == "prefill":
            x = x[:, -1:]
        logits = ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="lm_head",
        )(x)
        return logits, aux

    def loss(self, params, input_ids, labels, deterministic: bool = True,
             rngs=None, segment_ids=None, loss_mask=None):
        """Cross entropy plus the weighted router balance loss (as
        ``MixtralForCausalLM.loss``)."""
        logits, aux = self.apply(
            params, input_ids, deterministic=deterministic,
            segment_ids=segment_ids, rngs=rngs,
        )
        tok = parallel_cross_entropy(logits, labels)
        if loss_mask is not None:
            ce = (tok * loss_mask).sum() / jnp.maximum(loss_mask.sum(), 1)
        else:
            ce = tok.mean()
        return ce + self.config.router_aux_loss_coef * aux["load_balancing_loss"]
