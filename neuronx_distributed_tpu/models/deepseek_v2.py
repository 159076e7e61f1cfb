"""DeepSeek-V2 model family, TPU-native: multi-head LATENT attention (MLA)
over a cache of one latent row and one rotated key per token, YaRN rotary,
one leading dense layer, then sparse layers of routed experts plus shared
experts. Built from the parallel layers, ``RMSNorm`` and ``modules/moe`` as
``models/mixtral.py`` is. (The reference library has no MLA model; the
equations are the published ``modeling_deepseek.py``'s, config
``deepseek-ai/DeepSeek-V2-Lite``.)

Layer equations (``h`` a token's hidden vector, ``H`` heads; V2-Lite: no
query compression, ``q_lora_rank`` null. MLA WITH a query latent, ``q = W_q_b
RMSNorm(W_q_a h)``, lives in ``models/glm_moe_dsa.py``, where the latent also
feeds the sparse-attention indexer):

* block: ``x += attn(norm1(x))``; ``x += ffn(norm2(x))``; RMSNorm, eps 1e-6;
  a final RMSNorm; an untied output head; no biases anywhere.
* attention: ``q = W_q h`` -> ``H x (d_nope + d_rope)``, split into
  ``q_nope``, ``q_pe``. ``W_kv_a h`` -> ``d_c + d_rope``: ``c = RMSNorm(first
  d_c)``, ``k_pe = rope(last d_rope)``, ONE of each per token for all heads.
  ``W_kv_b c`` -> ``H x (d_nope + d_v)``: ``k_nope``, ``v``. ``q_pe =
  rope(q_pe)``. Scores ``(q_nope . k_nope + q_pe . k_pe) * scale``, causal
  softmax in float32, ``. v``, then ``W_o`` (``H x d_v`` -> hidden).
  ``scale = (d_nope + d_rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1`` (1.2608 for V2-Lite).
* YaRN over the ``d_rope`` channels (:func:`yarn_frequencies`).
* ABSORBED form (decode; the cache holds ``c`` and ``k_pe`` only):
  ``score_h(t, s) = (q_nope_h(t) W_uk_h) . c(s) + q_pe_h(t) . k_pe(s)``;
  ``out_h(t) = (sum_s p_h(t, s) c(s)) W_uv_h``, with ``W_uk_h``, ``W_uv_h``
  the two halves of ``W_kv_b``'s head ``h``. Prefill and training run the
  MATERIALISED form (``W_kv_b`` expanded; q/k of ``d_nope + d_rope``, v of
  ``d_v``), which has the fewer operations when queries are as many as keys.
* FFN: the first ``first_k_dense`` layers a SwiGLU MLP of ``intermediate_size``.
  The others: gate logits in float32, softmax over the routed experts, greedy
  top-k, weights NOT renormalised (``norm_topk_prob`` false), times
  ``routed_scaling_factor``; ``sum_i w_i expert_i(h)`` (SwiGLU, dropless)
  ``+ shared(h)``, ``shared`` ONE SwiGLU MLP of ``n_shared_experts *
  moe_intermediate_size`` on every token.

Departures from the published code, each a relabelling that random weights
cannot tell apart (loading published weights needs the permutation):

* rotary pairing: the published code de-interleaves the rope channels (even,
  odd -> halves) before ``rotate_half``; ``apply_rope`` pairs channel ``i``
  with ``i + d/2`` directly: a fixed permutation of the columns of ``W_q``'s
  and ``W_kv_a``'s rope parts (as ``references/common.py::rope_half_split``
  notes for CodeGen).
* group-limited routing (``n_group``/``topk_group``; V2-Lite has one group)
  and the sequence-level auxiliary loss (``seq_aux``) are not implemented;
  training uses the Switch balance loss ``modules/moe`` has.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.modules.attention import (
    LatentKVCache,
    ParallelMLP,
    apply_rope,
    attention_op,
    latent_decode_attention,
    prefill_positions,
)
from neuronx_distributed_tpu.modules.moe import MoE, moe_chunk_stats, moe_prefill_stats
from neuronx_distributed_tpu.modules.rms_norm import RMSNorm
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
)
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """The ``rope_scaling`` group of a YaRN config."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944          # the leading dense layers' MLP
    moe_intermediate_size: int = 1408       # one routed expert
    num_layers: int = 27
    first_k_dense: int = 1
    num_heads: int = 16
    kv_lora_rank: int = 512                 # d_c, the latent
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 64
    top_k: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = YarnScaling()
    rms_eps: float = 1e-6
    expert_strategy: str = "auto"
    router_aux_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # the layers differ (dense, then sparse), so they are never scanned; the
    # serving engine's fused paged path reads this
    scan_layers: bool = False
    # what the serving engine must know of the cache: one latent row and one
    # rotated key per token, no head axis to shard (modules/attention.py
    # LatentKVCache)
    kv_cache_kind: str = "latent"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        rs = self.rope_scaling
        if rs is not None and rs.mscale_all_dim:
            scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
        return scale


def deepseek_v2_lite(**over) -> DeepseekV2Config:
    """``deepseek-ai/DeepSeek-V2-Lite`` as published (27 layers, 15.7 B)."""
    return DeepseekV2Config(**over)


def tiny_deepseek_v2(**over) -> DeepseekV2Config:
    """Shrunk config for tests with EVERY mechanism present: a dense layer
    and two sparse ones, shared experts, q.k and v of different sizes, YaRN
    with ``max_seq_len`` past the original positions."""
    return DeepseekV2Config(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_layers=3, first_k_dense=1, num_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, top_k=3, n_shared_experts=2,
        max_seq_len=128,
        rope_scaling=YarnScaling(factor=4.0, original_max_position_embeddings=32),
        dtype=jnp.float32,
    ), **over})


# --- YaRN ---------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, max_seq_len: int, theta: float,
                     scaling: Optional[YarnScaling]) -> Tuple[jax.Array, float]:
    """``(angles (max_seq_len, dim/2), cos/sin scale)`` of the rotary
    embedding over ``dim`` channels. Plain rope without ``scaling``; YaRN
    with it: ``f_extra = theta^(-2i/dim)``, ``f_inter = f_extra / factor``;
    ``d(r) = dim * ln(orig / (2 pi r)) / (2 ln theta)``, ``low =
    floor(d(beta_fast))``, ``high = ceil(d(beta_slow))`` clamped to ``[0, dim
    - 1]``; ``ramp = clip((i - low) / (high - low), 0, 1)`` over ``i < dim /
    2``; ``inv_freq = f_inter * ramp + f_extra * (1 - ramp)``. Cos and sin
    are scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
    (1 for V2-Lite), which the caller applies to the rotated channels."""
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    f_extra = 1.0 / (theta ** (i / dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    if scaling is None:
        return jnp.outer(t, f_extra), 1.0
    orig = scaling.original_max_position_embeddings

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # the published guard against a zero-width ramp
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq = (f_extra / scaling.factor) * ramp + f_extra * (1 - ramp)
    ratio = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
        scaling.factor, scaling.mscale_all_dim)
    return jnp.outer(t, inv_freq), ratio


# --- attention ------------------------------------------------------------------


# --- what every latent attention shares (GLM-5's, under its indexer, too) ------------


def compress_kv(module, cfg, x):
    """Inside ``module``'s compact ``__call__``: ``(c (B, S, 1, d_c), k_pe (B,
    S, 1, d_r) before its rotary, W_kv_b (d_c, H, d_nope + d_v))`` of block
    inputs ``x``; parameters ``kv_a_proj``, ``kv_a_norm``, ``kv_b_proj``."""
    h, d_c = cfg.num_heads, cfg.kv_lora_rank
    d_n, d_r, d_v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla.compress"):
        kv_a = ColumnParallelLinear(
            cfg.hidden_size, d_c + d_r, gather_output=True, axis=None,
            name="kv_a_proj", use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )(x)
        c = RMSNorm(
            d_c, eps=cfg.rms_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="kv_a_norm",
        )(kv_a[..., :d_c])[:, :, None, :]                      # (B, S, 1, d_c)
        k_pe = kv_a[..., d_c:][:, :, None, :]                  # (B, S, 1, d_r)
    # W_kv_b as (d_c, H, d_nope + d_v): a matmul in the materialised form,
    # its two per-head halves W_uk, W_uv in the absorbed one
    w_kv_b = module.param(
        "kv_b_proj",
        nn.with_partitioning(
            nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
            (None, mesh_lib.TP_AXIS, None),
        ),
        (d_c, h, d_n + d_v), cfg.param_dtype,
    ).astype(cfg.dtype)
    return c, k_pe, w_kv_b


def expand_kv(c, k_pe, w_kv_b, d_n: int):
    """The materialised form of latents ``c`` and ROTATED keys ``k_pe`` (the
    caller's ``mla.expand`` scope): ``(k (B, S, H, d_nope + d_rope), kv (B, S,
    H, d_nope + d_v))``, the values ``kv[..., d_nope:]``."""
    kv = jnp.einsum("bsc,chd->bshd", c[:, :, 0], w_kv_b)
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(k_pe, kv.shape[:3] + k_pe.shape[3:])], -1)
    return k, kv


def absorb_query(q_nope, w_kv_b):
    """``W_uk`` folded into the content queries: (B, S, H, d_c)."""
    with jax.named_scope("mla.absorb"):
        return jnp.einsum("bshd,chd->bshc", q_nope, w_kv_b[..., :q_nope.shape[-1]])


def absorb_values(o_c, w_kv_b, d_n: int):
    """``W_uv`` on the latent-space output: (B, S, H, d_v)."""
    with jax.named_scope("mla.absorb"):
        return jnp.einsum("bshc,chd->bshd", o_c, w_kv_b[..., d_n:])


class MLAttention(nn.Module):
    """Multi-head latent attention (module docstring). ``mode``: ``train`` /
    ``prefill`` run the materialised form through :func:`attention_op`
    (flash on the TPU, q/k of ``d_nope + d_rope`` and v of ``d_v``); prefill
    also writes ``c``/``k_pe`` into a :class:`LatentKVCache`; ``decode`` runs
    the absorbed form against that cache. Heads shard over tp in ``W_q``,
    ``W_kv_b`` and ``W_o``; ``W_kv_a`` and the cache are replicated (one row
    for all heads)."""

    config: DeepseekV2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, rope_scale, positions=None, segment_ids=None,
                 padding_mask=None):
        cfg = self.config
        h, d_c = cfg.num_heads, cfg.kv_lora_rank
        d_n, d_r, d_v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]

        q = ColumnParallelLinear(
            cfg.hidden_size, h * (d_n + d_r), name="q_proj", **lin
        )(x).reshape(b, s, h, d_n + d_r)
        q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))
        q_nope, q_pe = q[..., :d_n], q[..., d_n:]
        c, k_pe, w_kv_b = compress_kv(self, cfg, x)

        def rope(t, pos):
            out = apply_rope(t, freqs, pos)
            return out if rope_scale == 1.0 else (out * rope_scale).astype(t.dtype)

        if self.mode == "decode":
            out = self._absorbed_decode(q_nope, q_pe, c, k_pe, w_kv_b, rope,
                                        positions, padding_mask)
        else:
            if self.mode == "prefill":
                if positions is None and padding_mask is not None:
                    positions = prefill_positions(padding_mask)
            elif self.mode != "train":
                raise ValueError(f"unknown attention mode {self.mode!r}")
            with jax.named_scope("mla.compress"):
                q_pe, k_pe = rope(q_pe, positions), rope(k_pe, positions)
                if self.mode == "prefill":
                    if s > cfg.max_seq_len:
                        raise ValueError(
                            f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}")
                    LatentKVCache(
                        self, b, cfg.max_seq_len, d_c, d_r, c.dtype
                    ).prefill_write(c, k_pe, padding_mask)
            with jax.named_scope("mla.expand"):
                k, kv = expand_kv(c, k_pe, w_kv_b, d_n)
                # attention_op scales by (d_nope + d_rope)^-0.5; YaRN's m^2
                # rides on the query
                q = jnp.concatenate([q_nope, q_pe], -1) * jnp.asarray(
                    cfg.softmax_scale * cfg.qk_head_dim ** 0.5, q.dtype)
            out = self._cached_attention(
                lambda: attention_op(
                    q, k, kv[..., d_n:], causal=True, impl=self.attention_impl,
                    mask=padding_mask, segment_ids=segment_ids,
                ))
        return RowParallelLinear(
            h * d_v, cfg.hidden_size, name="o_proj", **lin
        )(out.reshape(b, s, h * d_v))

    def _cached_attention(self, attend):
        # a Pallas kernel is named after the scope it is called in: this
        # method's name is what trace readers find BOTH forms' kernels by, as
        # the other attention modules' (``attn._cached_attention``)
        return attend()

    def _absorbed_decode(self, q_nope, q_pe, c, k_pe, w_kv_b, rope, positions,
                         padding_mask):
        cfg = self.config
        s = q_nope.shape[1]
        d_n = cfg.qk_nope_head_dim
        with jax.named_scope("mla.compress"):
            cache = LatentKVCache(
                self, c.shape[0], cfg.max_seq_len, cfg.kv_lora_rank,
                cfg.qk_rope_head_dim, c.dtype)
            pos, rope_pos = cache.decode_positions(s, positions)
            q_pe, k_pe = rope(q_pe, rope_pos), rope(k_pe, rope_pos)
            cache.decode_write(c, k_pe, padding_mask)
        q_c = absorb_query(q_nope, w_kv_b)
        o_c = self._cached_attention(
            lambda: latent_decode_attention(
                q_c, q_pe, cache.k.value, cache.k_pe.value, pos,
                cfg.softmax_scale, kv_valid=cache.valid.value,
            ))
        return absorb_values(o_c, w_kv_b, d_n)


# --- the model ------------------------------------------------------------------


class DeepseekV2DecoderLayer(nn.Module):
    config: DeepseekV2Config
    layer_index: int
    attention_impl: str = "auto"
    deterministic: bool = True
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, rope_scale, positions=None, segment_ids=None,
                 padding_mask=None):
        cfg = self.config
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        h = RMSNorm(cfg.hidden_size, name="input_norm", **norm)(x)
        x = x + MLAttention(cfg, self.attention_impl, self.mode, name="attn")(
            h, freqs, rope_scale, positions, segment_ids, padding_mask)
        h = RMSNorm(cfg.hidden_size, name="post_attn_norm", **norm)(x)
        if self.layer_index < cfg.first_k_dense:
            mlp = ParallelMLP(
                cfg.hidden_size, cfg.intermediate_size, activation="silu",
                use_bias=False, glu=True, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="mlp",
            )
            return x + mlp(h), jnp.zeros((2,), jnp.float32)
        moe_out, aux = MoE(
            num_experts=cfg.num_experts,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            top_k=cfg.top_k,
            expert_strategy=cfg.expert_strategy,
            normalize_top_k_affinities=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            shared_intermediate_size=(
                cfg.n_shared_experts * cfg.moe_intermediate_size
                if cfg.n_shared_experts else None),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="moe",
        )(h, deterministic=self.deterministic,
          row_mask=padding_mask if self.mode == "prefill" else None)
        return x + moe_out, jnp.stack(
            [aux["load_balancing_loss"], aux["router_z_loss"]])


class DeepseekV2Model(nn.Module):
    """Backbone without the LM head: ``(hidden, aux_losses)``."""

    config: DeepseekV2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        x = ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        freqs, rope_scale = yarn_frequencies(
            cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta, cfg.rope_scaling)
        layer_cls = nn.remat(DeepseekV2DecoderLayer) if cfg.remat else DeepseekV2DecoderLayer
        aux_sum = jnp.zeros((2,), jnp.float32)
        for i in range(cfg.num_layers):
            x, aux = layer_cls(
                cfg, i, self.attention_impl, deterministic, self.mode,
                name=f"layers_{i}",
            )(x, freqs, rope_scale, positions, segment_ids, padding_mask)
            aux_sum = aux_sum + aux
        x = RMSNorm(
            cfg.hidden_size, eps=cfg.rms_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="final_norm",
        )(x)
        return x, {"load_balancing_loss": aux_sum[0], "router_z_loss": aux_sum[1]}


class DeepseekV2ForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``."""

    config: DeepseekV2Config
    attention_impl: str = "auto"
    mode: str = "train"

    # the expert layers' per-step counters, which a decode chunk sums
    chunk_stats = property(lambda self: moe_chunk_stats(self.config))
    prefill_stats = property(lambda self: moe_prefill_stats(self.config))

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None) -> Tuple[jax.Array, dict]:
        cfg = self.config
        x, aux = DeepseekV2Model(cfg, self.attention_impl, self.mode, name="model")(
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
        positions = None
        if segment_ids is not None:
            from neuronx_distributed_tpu.trainer.trainer import segment_positions

            positions = segment_positions(segment_ids)
        logits, aux = self.apply(
            params, input_ids, positions=positions, deterministic=deterministic,
            segment_ids=segment_ids, rngs=rngs,
        )
        tok = parallel_cross_entropy(logits, labels)
        if loss_mask is not None:
            ce = (tok * loss_mask).sum() / jnp.maximum(loss_mask.sum(), 1)
        else:
            ce = tok.mean()
        return ce + self.config.router_aux_loss_coef * aux["load_balancing_loss"]
