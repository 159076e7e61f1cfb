"""Zyphra's ZAYA1 language models (``model_type: zaya``), TPU-native: attention
in a compressed latent whose queries and keys pass two short causal
convolutions over the request's tokens and whose second value head reads the
PREVIOUS token (CCA), a router that is an MLP over a state of its own which
passes from layer to layer beside the residual stream, top-1 experts with no
shared one, and a learned scale and bias on both sides of every residual
merge. Built from the parallel layers, ``RMSNorm`` and ``modules/moe`` as
``models/afmoe.py`` is. Config: ``Zyphra/ZAYA1-8B`` (``config.json``).

Layer ``l``, a request's token ``t``, stream ``x`` (``h``), router state ``r``
(``router_hidden_size``), ``H`` query and ``Hkv`` key/value heads of ``D``,
``G = H / Hkv``, RMSNorm eps ``rms_eps`` with a learned scale:

1. ``u = N1(x)``. ``q~ = Wq u`` (H D), ``k~ = Wk u`` (Hkv D), ``v = [Wv1 u_t ;
   Wv2 u_{t-1}]``: kv head 0 reads this token, kv head 1 the previous one
   (``u_{-1} = 0``; ``Hkv = 2``).
2. ``m_q = (q~ + repeat(k~, G)) / 2`` a query head; ``m_k`` the mean of
   ``m_q`` over the ``G`` query heads of a kv head.
3. ``c = [q~ ; k~]`` ((H + Hkv) D channels). ``y0_t = w0[:, 0] c_{t-1} + w0[:,
   1] c_t + b0`` (depthwise, ``cca_time0`` = 2). ``y1_t = W1[0] y0_{t-1} +
   W1[1] y0_t + b1``, ``W1`` block-diagonal over the ``H + Hkv`` heads
   (``cca_time1`` = 2). Both causal with ZERO history (``c_{-1} = 0``,
   ``y0_{-1} = 0``).
4. ``q = y1[: H D] + m_q``, ``k = y1[H D :] + m_k``; a head each: ``q <-
   sqrt(D) q / |q|``, ``k <- tau_g sqrt(D) k / |k|`` (``tau_g`` one learned
   temperature a kv head); rotary on the first ``partial_rotary_factor * D``
   channels of each head (channel ``i`` paired with ``i + rot / 2``).
5. ``a = softmax(q k^T / sqrt(D), causal) v``, GQA; ``o = Wo a``.
6. ``x <- (x + b_r) * s_r + (o + b_f) * s_f``: a learned scale and bias a
   channel on BOTH the residual and the branch (init 1 and 0); the same form
   with its own parameters after the expert sublayer.
7. ``g = N2(x)``; the router (``modules/moe/routing.RouterMLP``): ``s = Wd g +
   bd``; ``l > 0``: ``s <- s + gamma_l * r_{l-1}``; ``r_l = s``; ``p =
   softmax(W3 gelu(W2 gelu(W1 N(s) + c1) + c2))`` in float32; ``e = argmax(p
   + bias)``; ``y = p_e * Expert_e(g)`` (SwiGLU of ``moe_intermediate_size``),
   not renormalised.
8. After the last layer an RMSNorm; ``logits = x E^T`` with the embedding
   table (tied).

"The previous token" is the request's, never the cache's previous column. A
prompt's padding is on ONE side, so its tokens are adjacent columns and the
shift is by one column under the padding mask; the first token's history is
zero. A decode step cannot find its predecessor in the cache (the shared write
cursor's jumps leave gap columns inside a slot's row, and ``c`` and ``y0`` are
never cached): each layer keeps, a slot, ``[c ; y0 ; Wv2 u]`` of the slot's
last token, ``2 (H + Hkv) D + D`` values, as the per-slot ``state`` leaf of its
:class:`~neuronx_distributed_tpu.modules.attention.JoinedKVCache` beside the
joined K/V leaf ``(2 Hkv, D)`` a token (``k`` after step 4 and ``v`` of step
1: what decode reads). A prefill leaves the state of each row's last valid
token; a decode step reads and replaces it.

Assumed (the config has no key for them; Zyphra's CCA paper, arXiv:2510.04476,
and ZAYA1 report, arXiv:2511.17127, as remembered): the order of mean and
convolution and the zero history; ``tau`` applied to ``k``; the router's depth,
its biases, the exact gelu, ``gamma`` a vector absent in layer 0; the rotary
pairing. ``zaya_use_mod`` of the sibling ``ZAYA1-base`` row (a skip output of
the router) is NOT run: the 8B's config has 16 router outputs and no key for
it. Published checkpoints carry trained ``tau``, convolutions, ``gamma`` and
selection bias; random weights start the convolutions at the mean term's size,
``gamma`` at 1 and the router's matrices at lecun's size (the last two with
zero column sums: ``routing.zero_sum_lecun_normal``), and the ``*_init``
fields say what they start ``tau``, the selection bias, the expert sublayer's
branch scale and the table at.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.modules.attention import (
    ATTN_CCA_ATTEND_SCOPE,
    ATTN_CCA_CONV_SCOPE,
    ATTN_CCA_PROJECT_SCOPE,
    ATTN_CCA_SCOPE,
    JoinedKVCache,
    apply_rope,
    attention_op,
    joined_decode_attention,
    prefill_positions,
    rope_frequencies,
)
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.modules.moe.model import moe_chunk_stats, moe_prefill_stats
from neuronx_distributed_tpu.modules.qkv_linear import GQAQKVColumnParallelLinear
from neuronx_distributed_tpu.modules.rms_norm import RMSNorm
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.layers import ParallelEmbedding, RowParallelLinear
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    moe_intermediate_size: int = 2048
    num_layers: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    num_experts: int = 16
    top_k: int = 1
    router_hidden_size: int = 256
    max_seq_len: int = 4096
    rope_theta: float = 5e6
    rms_eps: float = 1e-5
    # what random weights start the learned pieces at (module docstring)
    embed_init_std: float = 0.02            # the tied table: logits of std ~1 off a normed stream
    temperature_init: float = 1.0           # tau: scores have std ~tau over random keys
    router_bias_init_std: float = 0.0       # the selection bias, as routing.stratified_normal draws it
    moe_branch_scale_init: float = 1.0      # step 6's s_f after the expert sublayer: what a changed expert choice costs
    expert_strategy: str = "auto"
    router_aux_loss_coef: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # the serving engine's fused paged path reads this (layers are unrolled)
    scan_layers: bool = False
    # what the serving engine must know of the cache: K and V one joined leaf
    # (its paged kernel has no sharded form) and a per-slot state beside it
    kv_cache_kind: str = "joined"
    kv_cache_slot_state: bool = True

    def __post_init__(self):
        if self.num_kv_heads != 2:
            raise ValueError(
                f"the value shift is written for 2 kv heads (this token's, the previous one's), got {self.num_kv_heads}")
        if (self.cca_time0, self.cca_time1) != (2, 2):
            raise ValueError("the convolutions reach ONE token back: cca_time0 = cca_time1 = 2")
        if self.top_k != 1:
            raise ValueError("the router picks one expert a token")

    @property
    def conv_channels(self) -> int:
        return (self.num_heads + self.num_kv_heads) * self.head_dim

    @property
    def slot_state_width(self) -> int:
        """``[c ; y0 ; Wv2 u]`` of a slot's last token."""
        return 2 * self.conv_channels + self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


def zaya1_8b(**over) -> ZayaConfig:
    """``Zyphra/ZAYA1-8B`` as published."""
    return ZayaConfig(**over)


def tiny_zaya(**over) -> ZayaConfig:
    """Shrunk config for tests with every mechanism present: 3 layers (the
    router's state passes twice), 4 query over 2 kv heads of 16, 8 experts
    under a selection bias, a peaked attention."""
    return ZayaConfig(**{**dict(
        vocab_size=256, hidden_size=64, moe_intermediate_size=48, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8, router_hidden_size=32,
        max_seq_len=128, embed_init_std=0.5, temperature_init=2.0, router_bias_init_std=0.05,
        dtype=jnp.float32,
    ), **over})


# --- attention ------------------------------------------------------------------


def shift_prev(a, first, valid_prev=None):
    """``a`` (B, S, ...) a token later: row ``t`` reads ``a[t - 1]``, row 0
    ``first`` (B, ...) (a decode step: the slot's state; a prompt: zeros).
    ``valid_prev`` (B, S): whether column ``t - 1`` holds a token of the row;
    where it does not (the padding before a prompt) the row reads zeros."""
    prev = jnp.concatenate([first[:, None].astype(a.dtype), a[:, :-1]], axis=1)
    if valid_prev is not None:
        prev = jnp.where(valid_prev.reshape(valid_prev.shape + (1,) * (a.ndim - 2)), prev, 0)
    return prev


def last_valid(a, valid, otherwise):
    """``a`` (B, S, W) at each row's last valid column; ``otherwise`` (B, W)
    for a row with none."""
    s = a.shape[1]
    idx = (s - 1) - jnp.argmax(valid[:, ::-1], axis=1)
    got = jnp.take_along_axis(a, idx[:, None, None], axis=1)[:, 0]
    return jnp.where(valid.any(axis=1)[:, None], got, otherwise.astype(a.dtype))


class ZayaAttention(nn.Module):
    """Steps 1-5 of the module docstring and the output projection. ``mode``:
    ``train`` / ``prefill`` attend the prompt; prefill also writes K, V and
    the last token's state into a :class:`JoinedKVCache`; ``decode`` reads
    and replaces the state and attends that cache."""

    config: ZayaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, padding_mask=None):
        cfg = self.config
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        g, n, c = h // hkv, h + hkv, cfg.conv_channels
        b, s = x.shape[0], x.shape[1]
        if self.mode not in ("train", "prefill", "decode"):
            raise ValueError(f"unknown attention mode {self.mode!r}")
        if self.mode == "prefill" and s > cfg.max_seq_len:
            raise ValueError(f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}")
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)

        with jax.named_scope(ATTN_CCA_PROJECT_SCOPE):
            q0, k0, v = GQAQKVColumnParallelLinear(
                hidden_size=cfg.hidden_size, num_heads=h, num_kv_heads=hkv, head_dim=d,
                name="qkv", **lin,
            )(x)

        def par(name, init, shape):
            return self.param(name, nn.with_partitioning(init, (None,) * len(shape)), shape, cfg.param_dtype)

        # the convolutions start at the mean term's size: two taps of variance 1/2, a lecun matrix a head over both taps
        normal = nn.initializers.normal
        w0 = par("conv0_weight", normal(0.5 ** 0.5), (2, c)).astype(cfg.dtype)
        b0 = par("conv0_bias", nn.initializers.zeros_init(), (c,)).astype(cfg.dtype)
        w1 = par("conv1_weight", normal((2 * d) ** -0.5), (2, n, d, d)).astype(cfg.dtype)
        b1 = par("conv1_bias", nn.initializers.zeros_init(), (n, d)).astype(cfg.dtype)
        tau = par("temperature", nn.initializers.constant(cfg.temperature_init), (hkv,))

        valid = jnp.ones((b, s), jnp.bool_) if padding_mask is None else padding_mask.astype(jnp.bool_)
        cache = None
        if self.mode != "train":
            cache = JoinedKVCache(self, b, cfg.max_seq_len, hkv, d, v.dtype,
                                  state_width=cfg.slot_state_width)
        if self.mode == "decode":
            pos, rope_pos = cache.decode_positions(s, positions)
            # a step's tokens are adjacent and its first follows the slot's last
            before, valid_prev = cache.state.value, None
        else:
            if self.mode == "prefill" and positions is None and padding_mask is not None:
                positions = prefill_positions(padding_mask)
            rope_pos = positions
            # a prompt's first token has no history; the column before a token
            # holds its predecessor or padding
            before = jnp.zeros((b, cfg.slot_state_width), v.dtype)
            valid_prev = None if padding_mask is None else shift_prev(valid, jnp.zeros((b,), jnp.bool_))

        with jax.named_scope(ATTN_CCA_CONV_SCOPE):
            # step 2: the mean term
            m_q = (q0.reshape(b, s, hkv, g, d) + k0.reshape(b, s, hkv, 1, d)) * 0.5
            m_k = m_q.mean(axis=3)
            # step 3: the two convolutions, each ONE token back
            cur = jnp.concatenate([q0, k0], axis=-1)                         # (B, S, C)
            y0 = w0[0] * shift_prev(cur, before[:, :c], valid_prev) + w0[1] * cur + b0
            y0_prev = shift_prev(y0, before[:, c:2 * c], valid_prev)
            y1 = (jnp.einsum("bsnc,ncd->bsnd", y0_prev.reshape(b, s, n, d), w1[0])
                  + jnp.einsum("bsnc,ncd->bsnd", y0.reshape(b, s, n, d), w1[1]) + b1)
            # step 1's shift: kv head 1 reads the previous token's projection
            v2 = v[..., d:]
            v = jnp.stack([v[..., :d], shift_prev(v2, before[:, 2 * c:], valid_prev)], axis=2)
            if cache is not None:
                # what the NEXT token needs of this step's last one
                cache.state.value = last_valid(
                    jnp.concatenate([cur, y0, v2], axis=-1), valid, before)
            # step 4: the mean joins; unit heads; the key's temperature; rotary
            q = (y1[:, :, :h].reshape(b, s, hkv, g, d) + m_q).reshape(b, s, h, d)
            k = y1[:, :, h:] + m_k
            q, k = unit_heads(q), unit_heads(k) * tau.astype(jnp.float32)[:, None]
            rot = cfg.rotary_dim

            def rope(t):
                t = t.astype(cfg.dtype)
                return jnp.concatenate([apply_rope(t[..., :rot], freqs, rope_pos), t[..., rot:]], axis=-1)

            q, k = rope(q), rope(k)
            q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))
            if self.mode == "decode":
                cache.decode_write(k, v, padding_mask)
            elif self.mode == "prefill":
                cache.prefill_write(k, v, padding_mask)
        with jax.named_scope(ATTN_CCA_ATTEND_SCOPE):
            if self.mode == "decode":
                out = joined_decode_attention(q, cache.kv.value, pos, cache.valid.value)
            else:
                out = attention_op(q, k, v, causal=True, impl=self.attention_impl, mask=padding_mask)
        return RowParallelLinear(h * d, cfg.hidden_size, name="o_proj", **lin)(out.reshape(b, s, h * d))


def unit_heads(t):
    """Each head's ``D`` channels scaled to length ``sqrt(D)``, in float32."""
    t = t.astype(jnp.float32)
    return t * (t.shape[-1] ** 0.5 * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-12))


# --- the model ------------------------------------------------------------------


class ResidualMerge(nn.Module):
    """Step 6: ``(x + b_r) * s_r + (branch + b_f) * s_f``, a learned scale and
    bias a channel on both sides (init 1 and 0; ``branch_scale_init`` starts
    ``s_f`` elsewhere)."""

    config: ZayaConfig
    branch_scale_init: float = 1.0

    @nn.compact
    def __call__(self, x, branch):
        cfg = self.config

        def par(name, init):
            return self.param(name, nn.with_partitioning(init, (None,)), (cfg.hidden_size,),
                              cfg.param_dtype).astype(cfg.dtype)

        ones, zeros = nn.initializers.ones_init(), nn.initializers.zeros_init()
        return ((x + par("residual_bias", zeros)) * par("residual_scale", ones)
                + (branch.astype(x.dtype) + par("branch_bias", zeros))
                * par("branch_scale", nn.initializers.constant(self.branch_scale_init)))


class ZayaDecoderLayer(nn.Module):
    """``(x, r) -> (x, r, aux)``: the stream and the router's state."""

    config: ZayaConfig
    attention_impl: str = "auto"
    deterministic: bool = True
    mode: str = "train"

    @nn.compact
    def __call__(self, x, router_state, freqs, positions=None, padding_mask=None):
        cfg = self.config
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        with jax.named_scope(ATTN_CCA_SCOPE):
            attn = ZayaAttention(cfg, self.attention_impl, self.mode, name="attn")(
                RMSNorm(cfg.hidden_size, name="input_norm", **norm)(x), freqs, positions, padding_mask)
        x = ResidualMerge(cfg, name="attn_merge")(x, attn)
        out, aux = MoE(
            num_experts=cfg.num_experts,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            top_k=cfg.top_k,
            router_kind="mlp",
            router_act_fn="softmax",
            router_state_size=cfg.router_hidden_size,
            router_eps=cfg.rms_eps,
            router_selection_bias=True,
            router_selection_bias_init_std=cfg.router_bias_init_std,
            normalize_top_k_affinities=False,
            expert_strategy=cfg.expert_strategy,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="moe",
        )(RMSNorm(cfg.hidden_size, name="pre_moe_norm", **norm)(x),
          deterministic=self.deterministic, router_state=router_state,
          row_mask=padding_mask if self.mode == "prefill" else None)
        x = ResidualMerge(cfg, cfg.moe_branch_scale_init, name="moe_merge")(x, out)
        losses = jnp.stack([aux["load_balancing_loss"], aux["router_z_loss"]])
        return x, aux["router_state"], losses


class ZayaModel(nn.Module):
    """Backbone without the head: ``(hidden, aux_losses, embedding table)``."""

    config: ZayaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError("packed documents under a one-token shift are not modelled")
        embed = ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            embedding_init=nn.initializers.normal(stddev=cfg.embed_init_std),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed",
        )
        x = embed(input_ids)
        freqs = rope_frequencies(cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta)
        layer_cls = nn.remat(ZayaDecoderLayer) if cfg.remat else ZayaDecoderLayer
        aux_sum = jnp.zeros((2,), jnp.float32)
        router_state = None
        for i in range(cfg.num_layers):
            x, router_state, aux = layer_cls(
                cfg, self.attention_impl, deterministic, self.mode, name=f"layers_{i}",
            )(x, router_state, freqs, positions, padding_mask)
            aux_sum = aux_sum + aux
        x = RMSNorm(
            cfg.hidden_size, eps=cfg.rms_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="final_norm",
        )(x)
        table = nn.meta.unbox(embed.get_variable("params", "embedding"))
        return x, {"load_balancing_loss": aux_sum[0], "router_z_loss": aux_sum[1]}, table


class ZayaForCausalLM(nn.Module):
    """The head is the embedding table (tied). In ``prefill`` mode it is
    applied to the LAST position alone (logits (B, 1, V)): the contract every
    causal LM here keeps, stated in ``models/__init__.py``. ``chunk_stats``: the
    counters a layer that holds every expert sows each decode step
    (``modules/moe.MOE_CHUNK_STATS``). ``prefill_stats``: those a
    prefill's expert layers sow of the rows its ``padding_mask`` kept
    (``modules/moe.moe_prefill_stats``)."""

    config: ZayaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @property
    def chunk_stats(self) -> Tuple[str, ...]:
        return moe_chunk_stats(self.config)

    @property
    def prefill_stats(self) -> Tuple[str, ...]:
        return moe_prefill_stats(self.config)

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None) -> Tuple[jax.Array, dict]:
        cfg = self.config
        x, aux, table = ZayaModel(cfg, self.attention_impl, self.mode, name="model")(
            input_ids, positions, deterministic, segment_ids, padding_mask)
        if self.mode == "prefill":
            x = x[:, -1:]
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bsh,vh->bsv", x, table.astype(cfg.dtype))
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
