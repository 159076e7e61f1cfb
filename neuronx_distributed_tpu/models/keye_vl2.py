"""Keye-VL-2.0's language model, TPU-native: grouped-query attention with a
learned sparse-attention INDEXER beside it (DeepSeek-Sparse-Attention's
lightning indexer at this config's sizes), per-head q/k RMSNorm, multimodal
rotary (M-RoPE) and a sparse layer of 128 experts top-8 in every block. Built
from the parallel layers, ``RMSNorm`` and ``modules/moe`` as
``models/mixtral.py`` is. Config: ``Kwai-Keye/Keye-VL-2.0-30B-A3B``
(``config.json``; the vision tower is not modelled: its widths are not
published with the language model's).

Layer equations (``x = RMSNorm(h)``, position ``t``, causal):

* block: ``h += attn(norm1(h))``; ``h += moe(norm2(h))``; RMSNorm eps 1e-6; a
  final RMSNorm; an untied output head; no biases.
* main heads: ``q = W_q x`` (H x D), ``k = W_k x``, ``v = W_v x`` (Hkv x D);
  RMSNorm over each head's D channels on q and on k; rotary over all D
  channels, channel ``i`` paired with ``i + D/2``, frequency pair ``i`` of
  ``D/2`` taking the temporal, height or width position by ``mrope_section``
  (text: the three streams are equal and this is plain RoPE).
* indexer: ``q_idx = W_qI x`` (H_i x d_i), ``k_idx = LayerNorm(W_kI x)`` (ONE
  key of d_i a token), ``w = W_w x`` (H_i); rotary on both over all d_i
  channels with the temporal stream; ``I[t, s] = sum_j w[t, j] relu(q_idx[t,
  j] . k_idx[s])`` for ``s <= t`` (the published positive constants ``H_i^-0.5
  d_i^-0.5`` change no selection and are left out).
* selection: the ``min(t + 1, topk)`` positions ``s <= t`` of largest ``I[t,
  s]``, ties to the lower position; one set a token a layer for all heads.
* attention: softmax over the selected positions only of ``q_h . k_g(h) /
  sqrt(D)``, times ``v``; ``W_o``. Up to ``topk`` tokens this IS dense GQA.
* experts: ``softmax(W_r x)`` over all experts, top-k, renormalised over the
  k (``norm_topk_prob``); SwiGLU experts; no shared expert.

Assumed (the config has no key for them): the q/k head norm (the Qwen3-MoE
block whose every number this config repeats has it); the index key's
LayerNorm with eps 1e-6 and the indexer's input ``x`` (DeepSeek-V3.2 feeds its
index queries from the query latent, which does not exist here); the
indexer's rotary over all d_i channels with the temporal stream (32 pairs
cannot carry sections that sum to 64). V3.2's Hadamard rotation before its fp8
index keys is orthogonal and is left out with the fp8: the index key is
cached in the model's dtype.

The cache is an :class:`~neuronx_distributed_tpu.modules.attention.IndexedKVCache`:
K and V (one joined leaf) and the index key per token. Prefill runs causal
attention under the learned mask (one byte a pair, never wider); decode
scores the cached index keys, selects and attends the selected columns
(``modules/attention.indexed_decode_attention``). Training runs the prefill
mathematics through the float32 einsum; the masked flash kernel has no
backward (serving is what this model is here for).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.modules.attention import (
    DSA_INDEX_SCOPE,
    IndexedKVCache,
    indexed_decode_attention,
    prefill_positions,
    sparse_prefill_attention,
)
from neuronx_distributed_tpu.modules.moe import MoE, moe_chunk_stats, moe_prefill_stats
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


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    top_k: int = 8
    norm_topk_prob: bool = True
    max_seq_len: int = 4096
    rope_theta: float = 1e7
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    rms_eps: float = 1e-6
    # sa_config: the indexer
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    index_topk: int = 2048
    indexer_norm_eps: float = 1e-6
    expert_strategy: str = "auto"
    router_aux_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # the serving engine's fused paged path reads this (layers are unrolled)
    scan_layers: bool = False
    # what the serving engine must know of the cache: K, V and one index key a
    # token (modules/attention.py IndexedKVCache); its kernels have no
    # sharded form
    kv_cache_kind: str = "indexed"


def keye_vl2_30b_a3b(**over) -> KeyeVL2Config:
    """``Kwai-Keye/Keye-VL-2.0-30B-A3B``'s language model as published."""
    return KeyeVL2Config(**over)


def tiny_keye_vl2(**over) -> KeyeVL2Config:
    """Shrunk config for tests with every mechanism present: GQA with head
    norms, M-RoPE sections, an indexer that selects 16 columns (so a context
    past 16 tokens is sparse), experts top-3 of 8 renormalised."""
    return KeyeVL2Config(**{**dict(
        vocab_size=256, hidden_size=64, moe_intermediate_size=48, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8, top_k=3,
        max_seq_len=128, mrope_section=(2, 3, 3), indexer_num_heads=4,
        indexer_head_dim=8, index_topk=16, dtype=jnp.float32,
    ), **over})


# --- rotary ---------------------------------------------------------------------


def mrope_angles(positions, dim: int, theta: float, sections=None):
    """Rotary angles (B, S, dim/2). ``positions`` (B, S): every frequency
    pair reads it. ``positions`` (3, B, S), the temporal, height and width
    streams of M-RoPE: pair ``i`` reads the stream whose section of
    ``sections`` (summing to dim/2) it falls in; without ``sections`` the
    temporal stream alone."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    pos = positions.astype(jnp.float32)
    if pos.ndim == 2:
        return pos[..., None] * inv_freq
    if sections is None:
        return pos[0][..., None] * inv_freq
    if sum(sections) != dim // 2:
        raise ValueError(f"mrope_section {sections} does not sum to {dim // 2}")
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=dim // 2)
    return jnp.moveaxis(pos, 0, -1)[..., stream] * inv_freq   # (B, S, dim/2)


def rotate(x, angles):
    """x (B, S, H, D) by ``angles`` (B, S, D/2): channel ``i`` with ``i +
    D/2`` (``modules/attention.apply_rope``'s pairing)."""
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# --- attention ------------------------------------------------------------------


class KeyeSparseAttention(nn.Module):
    """GQA with the indexer (module docstring). ``mode``: ``train`` /
    ``prefill`` attend the prompt under the learned mask; prefill also writes
    K, V and the index key into an :class:`IndexedKVCache`; ``decode`` scores,
    selects and attends against that cache. ``positions``: (B, S), or (3, B,
    S) for unequal M-RoPE streams (train / prefill)."""

    config: KeyeVL2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, positions=None, padding_mask=None):
        cfg = self.config
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        h_i, d_i = cfg.indexer_num_heads, cfg.indexer_head_dim
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]

        q, k, v = GQAQKVColumnParallelLinear(
            hidden_size=cfg.hidden_size, num_heads=h, num_kv_heads=hkv, head_dim=d,
            name="qkv", **lin,
        )(x)
        q = RMSNorm(d, name="q_norm", **norm)(q.reshape(b, s, h, d))
        k = RMSNorm(d, name="k_norm", **norm)(k.reshape(b, s, hkv, d))
        v = v.reshape(b, s, hkv, d)
        q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))
        with jax.named_scope(DSA_INDEX_SCOPE):
            # one key for all index heads: replicated, like MLA's latent
            rep = dict(gather_output=True, axis=None, **lin)
            q_idx = ColumnParallelLinear(
                cfg.hidden_size, h_i * d_i, name="idx_q_proj", **rep
            )(x).reshape(b, s, h_i, d_i)
            k_idx = nn.LayerNorm(
                epsilon=cfg.indexer_norm_eps, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="idx_k_norm",
            )(ColumnParallelLinear(cfg.hidden_size, d_i, name="idx_k_proj", **rep)(x))
            k_idx = k_idx[:, :, None, :]                       # (B, S, 1, d_i)
            w_idx = ColumnParallelLinear(
                cfg.hidden_size, h_i, name="idx_w_proj", **rep)(x)

        def rope(pos):
            main = mrope_angles(pos, d, cfg.rope_theta, cfg.mrope_section)
            with jax.named_scope(DSA_INDEX_SCOPE):
                idx = mrope_angles(pos, d_i, cfg.rope_theta)
                qi, ki = rotate(q_idx, idx), rotate(k_idx, idx)
            return rotate(q, main), rotate(k, main), qi, ki

        if self.mode == "decode":
            cache = IndexedKVCache(self, b, cfg.max_seq_len, hkv, d, d_i, k.dtype)
            pos, rope_pos = cache.decode_positions(s, positions)
            q, k, q_idx, k_idx = rope(rope_pos)
            with jax.named_scope(DSA_INDEX_SCOPE):
                cache.decode_write(k, v, k_idx, padding_mask)
            out = indexed_decode_attention(
                q, q_idx, w_idx, cache.kv.value, cache.k_idx.value,
                pos, cfg.index_topk, kv_valid=cache.valid.value,
            )
        else:
            if self.mode == "prefill":
                if positions is None and padding_mask is not None:
                    positions = prefill_positions(padding_mask)
            elif self.mode != "train":
                raise ValueError(f"unknown attention mode {self.mode!r}")
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
            q, k, q_idx, k_idx = rope(positions)
            if self.mode == "prefill":
                if s > cfg.max_seq_len:
                    raise ValueError(
                        f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}")
                with jax.named_scope(DSA_INDEX_SCOPE):
                    IndexedKVCache(
                        self, b, cfg.max_seq_len, hkv, d, d_i, k.dtype
                    ).prefill_write(k, v, k_idx, padding_mask)
            out = sparse_prefill_attention(
                q, k, v, q_idx, w_idx, k_idx, cfg.index_topk,
                # training differentiates: the masked flash kernel is forward only
                impl="xla" if self.mode == "train" else self.attention_impl,
                mask=padding_mask,
            )
        return RowParallelLinear(
            h * d, cfg.hidden_size, name="o_proj", **lin
        )(out.reshape(b, s, h * d))


# --- the model ------------------------------------------------------------------


class KeyeVL2DecoderLayer(nn.Module):
    config: KeyeVL2Config
    attention_impl: str = "auto"
    deterministic: bool = True
    mode: str = "train"

    @nn.compact
    def __call__(self, x, positions=None, padding_mask=None):
        cfg = self.config
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        h = RMSNorm(cfg.hidden_size, name="input_norm", **norm)(x)
        x = x + KeyeSparseAttention(cfg, self.attention_impl, self.mode, name="attn")(
            h, positions, padding_mask)
        h = RMSNorm(cfg.hidden_size, name="post_attn_norm", **norm)(x)
        moe_out, aux = MoE(
            num_experts=cfg.num_experts,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            top_k=cfg.top_k,
            expert_strategy=cfg.expert_strategy,
            normalize_top_k_affinities=cfg.norm_topk_prob,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="moe",
        )(h, deterministic=self.deterministic,
          row_mask=padding_mask if self.mode == "prefill" else None)
        return x + moe_out, jnp.stack(
            [aux["load_balancing_loss"], aux["router_z_loss"]])


class KeyeVL2Model(nn.Module):
    """Backbone without the LM head: ``(hidden, aux_losses)``."""

    config: KeyeVL2Config
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError(
                "packed documents under a learned mask are not modelled")
        x = ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        layer_cls = nn.remat(KeyeVL2DecoderLayer) if cfg.remat else KeyeVL2DecoderLayer
        aux_sum = jnp.zeros((2,), jnp.float32)
        for i in range(cfg.num_layers):
            x, aux = layer_cls(
                cfg, self.attention_impl, deterministic, self.mode,
                name=f"layers_{i}",
            )(x, positions, padding_mask)
            aux_sum = aux_sum + aux
        x = RMSNorm(
            cfg.hidden_size, eps=cfg.rms_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="final_norm",
        )(x)
        return x, {"load_balancing_loss": aux_sum[0], "router_z_loss": aux_sum[1]}


class KeyeVL2ForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py`` (the logits of every position of a 24,576-token
    prompt at this vocabulary would be 7.1 GiB in bf16). Logits at every
    position of a context: ``mode="train"``, or ``KeyeVL2Model`` in
    ``prefill`` mode and the head's kernel."""

    config: KeyeVL2Config
    attention_impl: str = "auto"
    mode: str = "train"

    # the expert layers' per-step counters, which a decode chunk sums
    chunk_stats = property(lambda self: moe_chunk_stats(self.config))
    prefill_stats = property(lambda self: moe_prefill_stats(self.config))

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True,
                 segment_ids=None, padding_mask=None) -> Tuple[jax.Array, dict]:
        cfg = self.config
        x, aux = KeyeVL2Model(cfg, self.attention_impl, self.mode, name="model")(
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
