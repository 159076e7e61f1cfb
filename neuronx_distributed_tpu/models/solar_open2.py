"""Upstage's Solar Open 2 language models (``model_type: solar_open2``),
TPU-native: a stack in which three layers of four are gated delta-rule LINEAR
attention with a per-channel decay (KDA), whose per-request memory is a
recurrent STATE and no cache, and every fourth is gated grouped-query softmax
attention with NO positional term; every layer a mixture of softmax-routed
experts with one shared expert. Built from the parallel layers, ``RMSNorm``
and ``modules/moe`` as ``models/afmoe.py`` is. Config:
``upstage/Solar-Open2-250B`` (``config.json``, 250B-A15B).

Block ``l``, stream ``x`` (``h``), RMSNorm eps ``rms_eps`` with a learned
scale: ``x <- x + Mixer_l(N1(x))``; ``x <- x + MoE(N2(x))``; after the last a
final RMSNorm and an untied head.

**Linear layer** (``l`` not in ``gqa_layers``), ``H`` heads of ``d``, a
request's token ``t``, ``u = N1(x)``:

1. ``q~, k~, v~ = Wq u, Wk u, Wv u`` (each ``H d``); a causal depthwise
   convolution of ``conv_kernel`` taps over the request's tokens on each of the
   three (zero history before its first token), then SiLU. A head each: ``q =
   q~ / |q~| * d^-1/2``, ``k = k~ / |k~|``; ``v = v~``.
2. log decay a channel ``g = -exp(A_log_h) * softplus(Wa_up (Wa_down u) +
   dt_bias)`` < 0 (``kda_use_full_proj`` false: the low-rank form, ``h ->
   low_rank -> H d``); write strength ``beta = 2 sigmoid(Wb u)`` in (0, 2) a
   head (``kda_allow_neg_eigval``).
3. state ``S`` (``d x d`` a head, key x value, float32): ``S' = Diag(e^g)
   S_{t-1}``; ``S_t = S' + beta k (v - S'^T k)^T``; ``o = S_t^T q``
   (``kernels/delta_rule.py``).
4. ``Wo [ RMSNorm_head(o) * sigmoid(Wg_up (Wg_down u) + b_g) ]``.

What the next token needs of the request so far is ``S`` and the last
``conv_kernel - 1`` inputs of the three convolutions: the layer's per-slot
leaves ``recur`` and ``conv`` (:class:`~neuronx_distributed_tpu.modules.
attention.RecurrentStateCache`) and ALL it keeps: no column, no page. A
prefill leaves both at each row's last token (padding columns, on either
side, change nothing: ``beta = 0``, ``g = 0``, zero convolution input), a
decode step reads and replaces them, a slot that takes no token keeps them.

**GQA layer** (``l`` in ``gqa_layers``): ``q`` (``Hq d``), ``k, v`` (``Hkv
d``), no rotary and no positional term of any kind (``use_rope`` false), no
head norms, causal softmax at ``d^-1/2``, the output times ``sigmoid(Wgate
u)`` before ``Wo`` (``use_gqa_gate``). Cache: K and V one joined leaf
(:class:`~neuronx_distributed_tpu.modules.attention.JoinedKVCache`), the
layer Trinity's full-attention layers are.

**MoE**, every layer (``first_k_dense_replace`` 0): ``p = softmax(Wr u)`` in
float32, the ``top_k`` largest, renormalised over the chosen, times
``routed_scaling_factor``; SwiGLU experts of ``moe_intermediate_size`` and one
shared SwiGLU expert for every token.

Assumed (the config has no key for them; the published kernels and modelling
code as remembered): the float32 state; convolution, then SiLU, then the unit
norm, and ``d^-1/2`` on q; the low-rank width; beta's factor 2; the gate's
form in both kinds of layer; softmax routing without a selection bias; no
q/k norm. Published checkpoints carry trained ``A_log``, ``dt_bias`` and
projections; random weights draw ``A = exp(A_log)`` log-uniform in [1, 16] a
head and ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in ``dt_range``
a channel (a channel forgets over 1 to 1,000 tokens), ``qk_init_gain`` scales
the GQA layers' q and k projections (softmax scores of std ``qk_init_gain **
2`` over random keys: how peaked attention is), and ``router_zero_sum_group``
starts every device's run of router outputs with weights that sum to zero
(what the tokens' inputs have in common then prefers no device's experts).
Forward only: the recurrence has no backward here, ``mode="train"`` runs it
under ``lax.scan``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.modules.attention import (
    ATTN_FULL_SCOPE,
    ATTN_GATE_SCOPE,
    ATTN_KDA_CONV_SCOPE,
    ATTN_KDA_GATE_SCOPE,
    ATTN_KDA_OUT_SCOPE,
    ATTN_KDA_PROJECT_SCOPE,
    ATTN_KDA_RECUR_SCOPE,
    ATTN_KDA_SCOPE,
    JoinedKVCache,
    RecurrentStateCache,
    attention_op,
    fused_paged_frame_active,
    joined_decode_attention,
)
from neuronx_distributed_tpu.modules.moe import MoE, moe_prefill_stats
from neuronx_distributed_tpu.modules.rms_norm import RMSNorm
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
)
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain

LINEAR, FULL = "linear_attention", "full_attention"
# random weights draw ``exp(A_log)`` a head log-uniform in this range (module docstring)
DECAY_RATE_RANGE = (1.0, 16.0)


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    moe_intermediate_size: int = 1280
    num_layers: int = 48
    # the softmax GQA layers; None: the published pattern, every (gqa_interval + 1)-th from layer 0
    gqa_layers: Optional[Tuple[int, ...]] = None
    gqa_interval: int = 3
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    conv_kernel: int = 4
    low_rank: int = 128
    num_experts: int = 320                  # the router's outputs
    top_k: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # (first, count): the routed experts this device holds; None: all
    held_experts: Optional[Tuple[int, int]] = None
    max_seq_len: int = 4096
    rms_eps: float = 1e-5
    # what random weights start the learned pieces at (module docstring)
    dt_range: Tuple[float, float] = (1e-3, 1e-1)
    qk_init_gain: float = 1.0
    # every run of this many consecutive router outputs (one device's experts)
    # starts with weights that sum to zero (``MoE.router_zero_sum_group``); 0: not
    router_zero_sum_group: int = 0
    expert_strategy: str = "auto"
    router_aux_loss_coef: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # the serving engine's fused paged path reads this (layers are unrolled)
    scan_layers: bool = False
    # what the serving engine must know of the cache: a GQA layer's K and V one
    # joined leaf (its paged kernel has no sharded form), a linear layer's
    # per-slot state and nothing else
    kv_cache_kind: str = "joined_recurrent"
    kv_cache_slot_state: bool = True

    def __post_init__(self):
        if self.gqa_layers is None:
            object.__setattr__(self, "gqa_layers", tuple(range(0, self.num_layers, self.gqa_interval + 1)))
        object.__setattr__(self, "gqa_layers", tuple(int(i) for i in self.gqa_layers))
        if not self.gqa_layers or any(not 0 <= i < self.num_layers for i in self.gqa_layers):
            raise ValueError(
                f"gqa_layers must name at least one of the {self.num_layers} layers run (the stack's write "
                f"cursor is its attention layers'), got {self.gqa_layers}")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(FULL if i in self.gqa_layers else LINEAR for i in range(self.num_layers))

    @property
    def conv_channels(self) -> int:
        """q~, k~ and v~ of every linear head."""
        return 3 * self.linear_num_heads * self.linear_head_dim


def solar_open2_250b(**over) -> SolarOpen2Config:
    """``upstage/Solar-Open2-250B`` as published."""
    return SolarOpen2Config(**over)


def tiny_solar_open2(**over) -> SolarOpen2Config:
    """Shrunk config for tests with every mechanism present: GQA, linear,
    linear, GQA, linear (a state handed past an attention layer), 4 heads of
    16, 16 experts top-2 with a shared expert, channels that forget within a
    few tokens beside channels that remember."""
    return SolarOpen2Config(**{**dict(
        vocab_size=256, hidden_size=64, moe_intermediate_size=48, num_layers=5, gqa_layers=(0, 3),
        num_heads=4, num_kv_heads=2, head_dim=16, linear_num_heads=4, linear_head_dim=16, low_rank=8,
        num_experts=16, top_k=2, max_seq_len=128, dt_range=(1e-2, 1.0), qk_init_gain=1.5,
        dtype=jnp.float32,
    ), **over})


# --- the linear layer -----------------------------------------------------------


def log_uniform(lo: float, hi: float, transform=lambda a: a):
    """An initializer: ``transform`` of draws log-uniform in ``[lo, hi]``."""

    def init(key, shape, dtype=jnp.float32):
        draws = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(lo), jnp.log(hi)))
        return transform(draws).astype(dtype)

    return init


def unit_heads(t, scale: float = 1.0):
    """Each head's ``d`` channels scaled to length ``scale``, in float32."""
    t = t.astype(jnp.float32)
    return t * (scale * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-12))


def last_tokens(a, valid, taps: int):
    """``a`` (B, S, C), zero at columns that hold no token, at each row's last
    ``taps`` tokens (B, taps, C), oldest first; zeros where the row has
    fewer. The row's tokens are adjacent (padding on one side)."""
    s = a.shape[1]
    last = (s - 1) - jnp.argmax(valid[:, ::-1], axis=1)               # (B,) the last token's column
    padded = jnp.pad(a, ((0, 0), (taps, 0), (0, 0)))
    return jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(row, i + 1, taps, axis=0))(padded, last)


class SolarOpen2LinearAttention(nn.Module):
    """Steps 1-4 of the module docstring. ``mode``: ``train`` / ``prefill`` run
    the prompt from a zero state (prefill through the chunked kernel, and
    leaves the state and the convolutions' last inputs in a
    :class:`RecurrentStateCache`); ``decode`` takes one token a slot through
    that state, in place."""

    config: SolarOpen2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, padding_mask=None):
        cfg = self.config
        h, d, taps = cfg.linear_num_heads, cfg.linear_head_dim, cfg.conv_kernel - 1
        b, s = x.shape[0], x.shape[1]
        f32 = jnp.float32
        if self.mode not in ("train", "prefill", "decode"):
            raise ValueError(f"unknown attention mode {self.mode!r}")
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)

        def par(name, init, shape):
            return self.param(name, nn.with_partitioning(init, (None,) * len(shape)), shape, cfg.param_dtype)

        def dense(name, n_in, n_out, y):
            return ColumnParallelLinear(n_in, n_out, name=name, **lin)(y)

        with jax.named_scope(ATTN_KDA_PROJECT_SCOPE):
            qkv = jnp.concatenate(
                [dense(f"{n}_proj", cfg.hidden_size, h * d, x) for n in "qkv"], axis=-1)   # (B, S, 3 H d)
            decay_low = dense("decay_down", cfg.hidden_size, cfg.low_rank, x)
            gate_low = dense("gate_down", cfg.hidden_size, cfg.low_rank, x)
            beta_logit = dense("beta_proj", cfg.hidden_size, h, x)

        valid = jnp.ones((b, s), jnp.bool_) if padding_mask is None else padding_mask.astype(jnp.bool_)
        cache = None
        if self.mode != "train":
            cache = RecurrentStateCache(self, b, h, d, taps, cfg.conv_channels, qkv.dtype)
        if self.mode == "decode" and s != 1:
            raise ValueError(f"a decode step takes one token a slot through the state, got {s}")

        with jax.named_scope(ATTN_KDA_CONV_SCOPE):
            w = par("conv_weight", nn.initializers.normal(cfg.conv_kernel ** -0.5),
                    (cfg.conv_kernel, cfg.conv_channels)).astype(cfg.dtype)
            qkv = jnp.where(valid[..., None], qkv, 0)            # a padding column is no token's input
            if self.mode == "decode":
                before = cache.conv.value                        # the slot's last inputs, oldest first
                window = jnp.concatenate([before, qkv], axis=1)
                cache.conv.value = jnp.where(valid[:, :1, None], window[:, 1:], before)
            else:
                window = jnp.pad(qkv, ((0, 0), (taps, 0), (0, 0)))   # zero history before the first token
                if cache is not None:
                    cache.conv.value = last_tokens(qkv, valid, taps)
            conv = sum(w[j] * window[:, j:j + s] for j in range(cfg.conv_kernel))
            q, k, v = jnp.split(jax.nn.silu(conv).reshape(b, s, 3 * h, d), 3, axis=2)
            q = unit_heads(q, d ** -0.5).astype(cfg.dtype)
            k = unit_heads(k).astype(cfg.dtype)

        with jax.named_scope(ATTN_KDA_GATE_SCOPE):
            rate = jnp.exp(par("A_log", log_uniform(*DECAY_RATE_RANGE, jnp.log), (h,)).astype(f32))
            dt_bias = par("dt_bias", log_uniform(*cfg.dt_range, lambda dt: jnp.log(jnp.expm1(dt))), (h * d,))
            g = -rate[:, None] * jax.nn.softplus(
                dense("decay_up", cfg.low_rank, h * d, decay_low).astype(f32) + dt_bias.astype(f32)
            ).reshape(b, s, h, d)
            beta = 2.0 * jax.nn.sigmoid(beta_logit.astype(f32))
            # a column that holds no token (padding; a slot that takes none) changes no state
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
            gate = jax.nn.sigmoid(
                dense("gate_up", cfg.low_rank, h * d, gate_low)
                + par("gate_bias", nn.initializers.zeros_init(), (h * d,)).astype(cfg.dtype))

        kernels = backend.resolve_attention_impl(self.attention_impl) == "flash"
        with jax.named_scope(ATTN_KDA_RECUR_SCOPE):
            from neuronx_distributed_tpu.kernels.delta_rule import (
                delta_rule_scan,
                kda_chunk_prefill,
                kda_decode_step,
            )

            if self.mode == "decode":
                # inside a fused paged frame the kernel, as the attention layers' (interpreted in tests)
                if kernels or fused_paged_frame_active():
                    o, state = kda_decode_step(
                        cache.recur.value, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                    o = o[:, None]
                else:
                    o, state = delta_rule_scan(q, k, v, g, beta, cache.recur.value)
                cache.recur.value = state
            elif kernels and self.mode == "prefill":
                o, state = kda_chunk_prefill(q, k, v, g, beta, valid)
                cache.recur.value = state
            else:
                o, state = delta_rule_scan(q, k, v, g, beta)
                if cache is not None:
                    cache.recur.value = state

        with jax.named_scope(ATTN_KDA_GATE_SCOPE):
            o = RMSNorm(d, eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="head_norm")(o)
            o = o.reshape(b, s, h * d) * gate
        with jax.named_scope(ATTN_KDA_OUT_SCOPE):
            return RowParallelLinear(h * d, cfg.hidden_size, name="o_proj", **lin)(o)


# --- the GQA layer --------------------------------------------------------------


class SolarOpen2Attention(nn.Module):
    """Gated GQA with no positional term (module docstring): ``models/afmoe.
    py``'s full-attention layer without its head norms, on the same cache and
    kernels."""

    config: SolarOpen2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, padding_mask=None):
        cfg = self.config
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]
        # q and k alone carry the gain: scores over random keys have std qk_init_gain ** 2
        qk_init = nn.initializers.variance_scaling(cfg.qk_init_gain ** 2, "fan_in", "truncated_normal")
        q = ColumnParallelLinear(cfg.hidden_size, h * d, kernel_init=qk_init, name="q_proj", **lin)(x)
        k = ColumnParallelLinear(cfg.hidden_size, hkv * d, kernel_init=qk_init, name="k_proj", **lin)(x)
        v = ColumnParallelLinear(cfg.hidden_size, hkv * d, name="v_proj", **lin)(x)
        with jax.named_scope(ATTN_GATE_SCOPE):
            gate = ColumnParallelLinear(cfg.hidden_size, h * d, name="gate_proj", **lin)(x)
        with jax.named_scope(ATTN_FULL_SCOPE):
            q, k, v = q.reshape(b, s, h, d), k.reshape(b, s, hkv, d), v.reshape(b, s, hkv, d)
            q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))
            if self.mode == "decode":
                cache = JoinedKVCache(self, b, cfg.max_seq_len, hkv, d, k.dtype)
                pos, _ = cache.decode_positions(s, None)
                cache.decode_write(k, v, padding_mask)
                out = joined_decode_attention(q, cache.kv.value, pos, cache.valid.value)
            else:
                if self.mode == "prefill":
                    if s > cfg.max_seq_len:
                        raise ValueError(f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}")
                    JoinedKVCache(self, b, cfg.max_seq_len, hkv, d, k.dtype).prefill_write(k, v, padding_mask)
                elif self.mode != "train":
                    raise ValueError(f"unknown attention mode {self.mode!r}")
                out = attention_op(q, k, v, causal=True, impl=self.attention_impl, mask=padding_mask)
        with jax.named_scope(ATTN_GATE_SCOPE):
            out = out.reshape(b, s, h * d) * jax.nn.sigmoid(gate)
        return RowParallelLinear(h * d, cfg.hidden_size, name="o_proj", **lin)(out)


# --- the model ------------------------------------------------------------------


class SolarOpen2DecoderLayer(nn.Module):
    config: SolarOpen2Config
    layer_index: int
    attention_impl: str = "auto"
    deterministic: bool = True
    mode: str = "train"

    @nn.compact
    def __call__(self, x, padding_mask=None):
        cfg = self.config
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        u = RMSNorm(cfg.hidden_size, name="input_norm", **norm)(x)
        if cfg.layer_types[self.layer_index] == FULL:
            mixed = SolarOpen2Attention(cfg, self.attention_impl, self.mode, name="attn")(u, padding_mask)
        else:
            with jax.named_scope(ATTN_KDA_SCOPE):
                mixed = SolarOpen2LinearAttention(cfg, self.attention_impl, self.mode, name="linear_attn")(
                    u, padding_mask)
        x = x + mixed
        out, losses = MoE(
            num_experts=cfg.num_experts,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            top_k=cfg.top_k,
            router_act_fn="softmax",
            expert_strategy=cfg.expert_strategy,
            normalize_top_k_affinities=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            shared_intermediate_size=(
                cfg.num_shared_experts * cfg.moe_intermediate_size if cfg.num_shared_experts else None),
            held_experts=cfg.held_experts,
            router_zero_sum_group=cfg.router_zero_sum_group,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="moe",
        )(RMSNorm(cfg.hidden_size, name="pre_moe_norm", **norm)(x), deterministic=self.deterministic,
          row_mask=padding_mask if self.mode == "prefill" else None)
        return x + out, jnp.stack([losses["load_balancing_loss"], losses["router_z_loss"]])


class SolarOpen2Model(nn.Module):
    """Backbone without the LM head: ``(hidden, aux_losses)``."""

    config: SolarOpen2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError("packed documents through a recurrent state are not modelled")
        if positions is not None:
            raise NotImplementedError(
                "no layer carries positions, and a decode window at explicit positions (a speculative "
                "round) would have to rewind the state: one token a slot, in order")
        x = ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        layer_cls = nn.remat(SolarOpen2DecoderLayer) if cfg.remat else SolarOpen2DecoderLayer
        aux_sum = jnp.zeros((2,), jnp.float32)
        for i in range(cfg.num_layers):
            x, aux = layer_cls(
                cfg, i, self.attention_impl, deterministic, self.mode, name=f"layers_{i}",
            )(x, padding_mask)
            aux_sum = aux_sum + aux
        x = RMSNorm(
            cfg.hidden_size, eps=cfg.rms_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="final_norm",
        )(x)
        return x, {"load_balancing_loss": aux_sum[0], "router_z_loss": aux_sum[1]}


class SolarOpen2ForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``. ``chunk_stats``: the counters a model with held
    experts sows into the ``stats`` collection each decode step
    (``modules/moe.MoE``). ``prefill_stats``: those a
    prefill's expert layers sow of the rows its ``padding_mask`` kept
    (``modules/moe.moe_prefill_stats``)."""

    config: SolarOpen2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @property
    def chunk_stats(self) -> Tuple[str, ...]:
        return ("held_rows", "routed_rows") if self.config.held_experts is not None else ()

    @property
    def prefill_stats(self) -> Tuple[str, ...]:
        return moe_prefill_stats(self.config)

    def init(self, rngs, *args, **kwargs):
        """The weights are DRAWN in float32 and then rounded to
        ``param_dtype``. ``jax.random``'s normal drawn IN bfloat16 takes 128
        distinct values with a mean of -0.018 standard deviations (72 standard
        errors over a 4096 x 4096 matrix): every matrix then adds a multiple of
        the ones vector to its output wherever its input has a mean (after a
        SiLU, a sigmoid gate), two thirds of the final stream is one vector
        common to every position, and a greedy answer collapses onto one token
        within a few dozen steps."""
        cfg = self.config
        if cfg.param_dtype == jnp.float32:
            return super().init(rngs, *args, **kwargs)
        wide = self.clone(config=dataclasses.replace(cfg, param_dtype=jnp.float32))
        variables = wide.init(rngs, *args, **kwargs)
        return {**variables, "params": jax.tree.map(lambda a: a.astype(cfg.param_dtype), variables["params"])}

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None) -> Tuple[jax.Array, dict]:
        cfg = self.config
        x, aux = SolarOpen2Model(cfg, self.attention_impl, self.mode, name="model")(
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
        ``AfmoeForCausalLM.loss``)."""
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
