"""GLM-5's language model (``model_type`` ``glm_moe_dsa``), TPU-native:
multi-head LATENT attention with a QUERY latent, a learned sparse-attention
indexer that selects among the cached latents (DeepSeek-V3.2's lightning
indexer, fed from the query latent), sigmoid routing with a selection bias
(DeepSeek-V3's ``noaux_tc``) over routed experts plus one shared expert, and an
expert layer that may be told which experts it holds. Built from the parallel
layers, ``RMSNorm`` and ``modules/moe`` as ``models/deepseek_v2.py`` is.
Config: ``zai-org/GLM-5`` (``config.json``, 744B-A40B).

Layer equations (``h`` a token's hidden vector, ``H`` heads, position ``t``;
RMSNorm eps ``rms_norm_eps``, no biases, an untied head, a final RMSNorm):

* block: ``x += attn(norm1(x))``; ``x += ffn(norm2(x))``.
* query latent: ``c_q = RMSNorm(W_q_a h)`` (``q_lora_rank``); ``q = W_q_b
  c_q`` -> ``H x (d_nope + d_rope)``: ``q_nope``, ``q_pe = rope(.)``.
* key/value latent: ``W_kv_a h`` -> ``d_c + d_rope``: ``c = RMSNorm(first
  d_c)``, ``k_pe = rope(last d_rope)``, ONE of each a token for all heads.
  ``W_kv_b c`` -> ``H x (d_nope + d_v)``: ``k_nope``, ``v``.
* indexer: ``q_I = W_qI c_q`` -> ``H_i x d_i``, rotary on its first
  ``d_rope`` channels; ``k_I = LayerNorm(W_kI h)`` (``d_i``, scale and bias,
  ONE a token), rotary on its first ``d_rope`` channels; ``w = W_w h``
  (``H_i``); ``I[t, s] = sum_j w_j(t) relu(q_I,j(t) . k_I(s))`` for ``s <= t``.
  The published positive constants ``H_i^-0.5 d_i^-0.5`` change no selection
  and are left out.
* selection: the ``min(t + 1, index_topk)`` positions ``s <= t`` of largest
  ``I[t, s]``, ties to the lower position; one set a token a layer for all
  heads.
* attention: scores ``(q_nope . k_nope + q_pe . k_pe) * (d_nope +
  d_rope)^-0.5`` (plain rotary, no YaRN, so no ``mscale``), softmax in
  float32 OVER THE SELECTED KEYS ONLY, ``. v``, ``W_o``. Up to ``index_topk``
  tokens this IS dense MLA.
* ABSORBED form (decode; the cache holds ``c``, ``k_pe`` and ``k_I``):
  ``score_h(t, s) = (q_nope_h(t) W_uk_h) . c(s) + q_pe_h(t) . k_pe(s)``;
  ``out_h(t) = (sum_s p_h(t, s) c(s)) W_uv_h`` over the selected ``s``, as
  ``models/deepseek_v2.py`` states it. Prefill and training run the
  MATERIALISED form under the learned mask.
* FFN: the first ``first_k_dense`` layers a SwiGLU MLP of
  ``intermediate_size``. The others: ``s = sigmoid(W_g h)`` in float32 over
  the routed experts; the ``top_k`` experts of largest ``s + b`` (``b`` =
  ``e_score_correction_bias``, a parameter; one group, so no group limit);
  weights ``s_i / sum of the chosen s``, WITHOUT ``b``, times
  ``routed_scaling_factor``; ``sum_i w_i expert_i(h)`` (SwiGLU, dropless) ``+
  shared(h)``, ONE SwiGLU MLP of ``n_shared_experts *
  moe_intermediate_size`` on every token. With ``held_experts = (first,
  count)`` the layer computes the part of the routed sum that experts
  ``[first, first + count)`` give (one device's share of an expert-parallel
  deployment, run without its exchange) plus the shared expert.

Departures from the published code, each listed:

* rotary pairing (``rope_interleave``, ``indexer_rope_interleave`` true): the
  published code pairs even with odd channels; ``apply_rope`` pairs channel
  ``i`` with ``i + d/2``: a fixed permutation of the columns of the rope
  parts of ``W_q_b``, ``W_kv_a``, ``W_qI`` and ``W_kI``, which random weights
  cannot tell apart (as ``models/deepseek_v2.py`` lists).
* DeepSeek-V3.2's Hadamard rotation before its fp8 index keys is orthogonal
  and is left out with the fp8: the index key is cached in the model's dtype.
* multi-token prediction (``num_nextn_predict_layers`` 1) is a training
  objective and an optional draft; the main model's logits do not depend on
  it and it is not built.
* group-limited routing is not implemented (``n_group`` 1 has none).
* ``head_dim`` 64 in the published config is the ROTARY width
  (``qk_rope_head_dim``), not an attention width.

The cache is an :class:`~neuronx_distributed_tpu.modules.attention.
IndexedLatentKVCache`. Training runs the prefill mathematics through the
float32 einsum (the masked flash kernel has no backward; serving is what this
model is here for).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.models.deepseek_v2 import (
    absorb_query,
    absorb_values,
    compress_kv,
    expand_kv,
)
from neuronx_distributed_tpu.modules.attention import (
    DSA_INDEX_SCOPE,
    IndexedLatentKVCache,
    ParallelMLP,
    apply_rope,
    indexed_latent_decode_attention,
    prefill_positions,
    rope_frequencies,
    sparse_prefill_attention,
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


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288          # the leading dense layers' MLP
    moe_intermediate_size: int = 2048       # one routed expert
    num_layers: int = 78
    first_k_dense: int = 3
    num_heads: int = 64
    q_lora_rank: int = 2048                 # the query latent
    kv_lora_rank: int = 512                 # d_c, the key/value latent
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    num_experts: int = 256                  # the router's outputs
    top_k: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # (first, count): the routed experts this device holds; None: all
    held_experts: Optional[Tuple[int, int]] = None
    # the normal whose quantiles e_score_correction_bias is drawn from at init,
    # the same values in a held share under every key (published checkpoints
    # carry a trained one)
    router_bias_init_std: float = 0.0
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    expert_strategy: str = "auto"
    router_aux_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # the layers differ (dense, then sparse), so they are never scanned; the
    # serving engine's fused paged path reads this
    scan_layers: bool = False
    # what the serving engine must know of the cache: one joined latent leaf
    # and one index key a token, no head axis to shard (modules/attention.py
    # IndexedLatentKVCache)
    kv_cache_kind: str = "indexed_latent"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5


def glm5(**over) -> GlmMoeDsaConfig:
    """``zai-org/GLM-5``'s language model as published (78 layers, 744 B)."""
    return GlmMoeDsaConfig(**over)


def tiny_glm_moe_dsa(**over) -> GlmMoeDsaConfig:
    """Shrunk config for tests with every mechanism present: a dense layer and
    two sparse ones, a query latent, q.k and v of one width as published, an
    indexer of 4 heads of 16 (rotary on the first 8) that selects 16 columns
    (so a context past 16 tokens is sparse), 16 experts top-4 under a drawn
    selection bias, one shared expert. All experts held: pass
    ``held_experts=(first, count)`` for a share."""
    return GlmMoeDsaConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_layers=3, first_k_dense=1, num_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=16, top_k=4, n_shared_experts=1,
        router_bias_init_std=0.1, index_n_heads=4, index_head_dim=16,
        index_topk=16, max_seq_len=128, dtype=jnp.float32,
    ), **over})


# --- attention ------------------------------------------------------------------


class GlmSparseMLAttention(nn.Module):
    """MLA with a query latent under the indexer (module docstring).
    ``mode``: ``train`` / ``prefill`` run the materialised form under the
    learned mask; prefill also writes ``c``, ``k_pe`` and the index key into
    an :class:`IndexedLatentKVCache`; ``decode`` scores, selects and runs the
    absorbed form against the selected rows of that cache. Heads shard over
    tp in ``W_q_b``, ``W_kv_b`` and ``W_o``; the latents' projections, the
    indexer and the cache are replicated (one row a token for all heads)."""

    config: GlmMoeDsaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, padding_mask=None):
        cfg = self.config
        h, d_c, d_q = cfg.num_heads, cfg.kv_lora_rank, cfg.q_lora_rank
        d_n, d_r, d_v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        h_i, d_i = cfg.index_n_heads, cfg.index_head_dim
        if d_n + d_r != d_v:
            raise ValueError(
                f"the masked prefill attends q, k and v of ONE width: "
                f"qk {d_n} + {d_r} against v {d_v}")
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        rep = dict(gather_output=True, axis=None, **lin)   # one row a token for all heads
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]

        c_q = RMSNorm(d_q, name="q_a_norm", **norm)(
            ColumnParallelLinear(cfg.hidden_size, d_q, name="q_a_proj", **rep)(x))
        q = ColumnParallelLinear(
            d_q, h * (d_n + d_r), name="q_b_proj", **lin
        )(c_q).reshape(b, s, h, d_n + d_r)
        q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))
        q_nope, q_pe = q[..., :d_n], q[..., d_n:]
        c, k_pe, w_kv_b = compress_kv(self, cfg, x)
        with jax.named_scope(DSA_INDEX_SCOPE):
            # the index queries come from the QUERY LATENT, key and weights
            # from the block's input
            q_idx = ColumnParallelLinear(
                d_q, h_i * d_i, name="idx_q_proj", **rep
            )(c_q).reshape(b, s, h_i, d_i)
            k_idx = nn.LayerNorm(
                epsilon=cfg.indexer_norm_eps, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="idx_k_norm",
            )(ColumnParallelLinear(cfg.hidden_size, d_i, name="idx_k_proj", **rep)(x))
            k_idx = k_idx[:, :, None, :]                           # (B, S, 1, d_i)
            w_idx = ColumnParallelLinear(
                cfg.hidden_size, h_i, name="idx_w_proj", **rep)(x)

        def rope_first(t, pos):
            """Rotary on the first ``d_rope`` channels of an index head."""
            return jnp.concatenate(
                [apply_rope(t[..., :d_r], freqs, pos), t[..., d_r:]], axis=-1)

        if self.mode == "decode":
            cache = IndexedLatentKVCache(
                self, b, cfg.max_seq_len, d_c, d_r, d_i, c.dtype)
            pos, rope_pos = cache.decode_positions(s, positions)
            with jax.named_scope("mla.compress"):
                q_pe, k_pe = apply_rope(q_pe, freqs, rope_pos), apply_rope(k_pe, freqs, rope_pos)
            with jax.named_scope(DSA_INDEX_SCOPE):
                q_idx, k_idx = rope_first(q_idx, rope_pos), rope_first(k_idx, rope_pos)
                cache.decode_write(c, k_pe, k_idx, padding_mask)
            q_c = absorb_query(q_nope, w_kv_b)
            o_c = indexed_latent_decode_attention(
                q_c, q_pe, q_idx, w_idx, cache.kv.value, cache.k_idx.value, pos,
                cfg.index_topk, cfg.softmax_scale, kv_valid=cache.valid.value,
            )
            out = absorb_values(o_c, w_kv_b, d_n)
        else:
            if self.mode == "prefill":
                if positions is None and padding_mask is not None:
                    positions = prefill_positions(padding_mask)
            elif self.mode != "train":
                raise ValueError(f"unknown attention mode {self.mode!r}")
            with jax.named_scope("mla.compress"):
                q_pe, k_pe = apply_rope(q_pe, freqs, positions), apply_rope(k_pe, freqs, positions)
            with jax.named_scope(DSA_INDEX_SCOPE):
                q_idx, k_idx = rope_first(q_idx, positions), rope_first(k_idx, positions)
                if self.mode == "prefill":
                    if s > cfg.max_seq_len:
                        raise ValueError(
                            f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}")
                    IndexedLatentKVCache(
                        self, b, cfg.max_seq_len, d_c, d_r, d_i, c.dtype
                    ).prefill_write(c, k_pe, k_idx, padding_mask)
            with jax.named_scope("mla.expand"):
                k, kv = expand_kv(c, k_pe, w_kv_b, d_n)
                q = jnp.concatenate([q_nope, q_pe], -1)
            # q, k and v all d_v wide: the masked kernels' 1/sqrt(D) is the
            # model's (d_nope + d_rope)^-0.5
            out = sparse_prefill_attention(
                q, k, kv[..., d_n:], q_idx, w_idx, k_idx, cfg.index_topk,
                # training differentiates: the masked flash kernel is forward only
                impl="xla" if self.mode == "train" else self.attention_impl,
                mask=padding_mask,
            )
        return RowParallelLinear(
            h * d_v, cfg.hidden_size, name="o_proj", **lin
        )(out.reshape(b, s, h * d_v))


# --- the model ------------------------------------------------------------------


class GlmMoeDsaDecoderLayer(nn.Module):
    config: GlmMoeDsaConfig
    layer_index: int
    attention_impl: str = "auto"
    deterministic: bool = True
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, padding_mask=None):
        cfg = self.config
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        h = RMSNorm(cfg.hidden_size, name="input_norm", **norm)(x)
        x = x + GlmSparseMLAttention(cfg, self.attention_impl, self.mode, name="attn")(
            h, freqs, positions, padding_mask)
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
            router_act_fn="sigmoid",
            router_selection_bias=True,
            router_selection_bias_init_std=cfg.router_bias_init_std,
            expert_strategy=cfg.expert_strategy,
            normalize_top_k_affinities=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            shared_intermediate_size=(
                cfg.n_shared_experts * cfg.moe_intermediate_size
                if cfg.n_shared_experts else None),
            held_experts=cfg.held_experts,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="moe",
        )(h, deterministic=self.deterministic,
          row_mask=padding_mask if self.mode == "prefill" else None)
        return x + moe_out, jnp.stack(
            [aux["load_balancing_loss"], aux["router_z_loss"]])


class GlmMoeDsaModel(nn.Module):
    """Backbone without the LM head: ``(hidden, aux_losses)``."""

    config: GlmMoeDsaConfig
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
        freqs = rope_frequencies(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
        layer_cls = nn.remat(GlmMoeDsaDecoderLayer) if cfg.remat else GlmMoeDsaDecoderLayer
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


class GlmMoeDsaForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``. Logits at every position of a context:
    ``mode="train"``, or ``GlmMoeDsaModel`` in ``prefill`` mode and the
    head's kernel.

    ``chunk_stats``: the counters a model with held experts sows into the
    ``stats`` collection each decode step (``modules/moe.MoE``), which
    ``inference/generate.chunked_decode_step`` sums over a chunk's steps and
    layers and hands back with the chunk's tokens. ``prefill_stats``: those a
    prefill's expert layers sow of the rows its ``padding_mask`` kept
    (``modules/moe.moe_prefill_stats``)."""

    config: GlmMoeDsaConfig
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
        x, aux = GlmMoeDsaModel(cfg, self.attention_impl, self.mode, name="model")(
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
