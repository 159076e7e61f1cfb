"""Llama-2/3 model family, TPU-native (flagship; reference analogue:
``examples/training/llama`` modeling files + the sharded-layer stack of §2.1).

Structure: ParallelEmbedding → N × (RMSNorm → GQA attention → RMSNorm → SwiGLU
MLP) → RMSNorm → column-parallel LM head → vocab-parallel cross entropy.
All TP/SP behaviour comes from the parallel layers' sharding metadata; the
model code is pure global-logical math. Attention dispatches to the Pallas
flash kernel on TPU (kernels/flash_attention.py) or a reference XLA einsum
path (used on CPU meshes and as the numerics golden).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.modules.qkv_linear import GQAQKVColumnParallelLinear
from neuronx_distributed_tpu.modules.remat import remat_layer_cls
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
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    remat: bool = True  # activation checkpointing per decoder layer
    # rematerialization policy when remat is on (modules/remat.py has the
    # table): None = save nothing (recompute everything), "dots" =
    # jax.checkpoint_policies.dots_with_no_batch_dims_saveable (keep matmul
    # outputs, recompute the cheap elementwise ops — the usual MFU/memory
    # sweet spot at width), "dots_saveable"
    remat_policy: Optional[str] = None
    scan_layers: bool = True  # lax.scan over layers (fast compile at depth)
    # weight-only serving quantization (a QuantizationConfig): every linear
    # kernel (qkv/o/gate/up/down/lm_head — not the embedding lookup) becomes
    # int8/fp8 + scale, matching quantize_param_tree's output on a trained
    # float checkpoint (reference: module-swap convert, quantization/
    # quantize.py:18 + quantization_mappings.py:19)
    quantization: Optional[Any] = None

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads


def llama2_7b(**over) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_layers=32, num_heads=32, num_kv_heads=32, max_seq_len=4096,
    ), **over})


def llama2_70b(**over) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        vocab_size=32000, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, max_seq_len=4096,
    ), **over})


def llama3_8b(**over) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
        rope_theta=500000.0,
    ), **over})


def early_exit_draft_params(params, num_layers: int, draft_layers: int,
                            eps: float):
    """Build the EARLY-EXIT draft pair for speculative serving demos and
    benches: returns ``(target_params, draft_params)`` where the target is
    ``params`` with layers ``draft_layers..num_layers-1``'s residual
    contributions (``o_proj``/``down_proj`` kernels) scaled by ``eps``, and
    the draft is the SAME weights truncated to the first ``draft_layers``
    layers (shared embed/final_norm/lm_head).

    At ``eps=0`` draft and target are the same function (acceptance exactly
    1.0); growing ``eps`` degrades their agreement smoothly — a
    deterministic synthetic-acceptance dial with a genuinely
    ``num_layers/draft_layers``-cheaper draft. Requires the unscanned
    ``layers_i`` param naming (``scan_layers=False``)."""
    if not 0 < draft_layers < num_layers:
        raise ValueError(
            f"draft_layers must be in [1, {num_layers - 1}], got {draft_layers}"
        )
    mdl = dict(params["params"]["model"])
    if "layers_0" not in mdl:
        raise ValueError(
            "early_exit_draft_params needs scan_layers=False (per-layer "
            "'layers_i' params)"
        )
    for i in range(draft_layers, num_layers):
        def scale(path, leaf):
            keys = [getattr(k, "key", str(k)) for k in path]
            if "o_proj" in keys or "down_proj" in keys:
                return leaf * eps
            return leaf
        mdl[f"layers_{i}"] = jax.tree_util.tree_map_with_path(
            scale, mdl[f"layers_{i}"]
        )
    target_params = {"params": {**params["params"], "model": mdl}}
    draft_params = {"params": {
        "model": {
            "embed": mdl["embed"],
            **{f"layers_{i}": mdl[f"layers_{i}"] for i in range(draft_layers)},
            "final_norm": mdl["final_norm"],
        },
        "lm_head": params["params"]["lm_head"],
    }}
    return target_params, draft_params


def tiny_llama(**over) -> LlamaConfig:
    """4-layer full-width-style shrunk config for tests (the reference's
    integration trick: tiny depth, real structure —
    test/integration/llama2_70B_4layers_PP)."""
    return LlamaConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=128,
        dtype=jnp.float32, remat=False, scan_layers=False,
    ), **over})


# --- RoPE + attention dispatch live in modules/attention.py (shared across
# all families); re-exported here for the historical import surface ----------

from neuronx_distributed_tpu.modules.attention import (  # noqa: E402
    apply_rope,
    attention_op,
    rope_frequencies,
    xla_attention as _xla_attention,
)


# shared decode-attention primitive (modules/attention.py); kept under the
# old private name for this module's call sites
from neuronx_distributed_tpu.modules.attention import (  # noqa: E402
    decode_attention as _decode_attention,
)


class LlamaAttention(nn.Module):
    """GQA attention. ``mode`` selects the KV-cache behaviour (reference
    inference path: StateInitializer KV cache, trace/spmd.py:49):

    * ``"train"`` — no cache, causal attention over the input.
    * ``"prefill"`` — causal attention AND write K/V into the cache
      collection, set the cache index to the prompt length.
    * ``"decode"`` — single-token step: append K/V at the cache index,
      attend against the whole cache, advance the index.
    """

    config: LlamaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, attn_mask=None,
                 segment_ids=None, padding_mask=None):
        """``attn_mask`` (S, cache_len): Medusa tree mask (decode only).
        ``segment_ids`` (B, S): packed-document isolation (train; rides the
        flash kernel's segment path). ``padding_mask`` (B, S) True at valid
        positions: padded-batch serving — persisted in the cache so decode
        steps keep prompt padding masked."""
        cfg = self.config
        d = cfg.head_dim_
        q, k, v = GQAQKVColumnParallelLinear(
            hidden_size=cfg.hidden_size,
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=d,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            quantization_config=cfg.quantization,
            name="qkv",
        )(x)
        b, s = q.shape[0], q.shape[1]
        q = q.reshape(b, s, cfg.num_heads, d)
        k = k.reshape(b, s, cfg.num_kv_heads, d)
        v = v.reshape(b, s, cfg.num_kv_heads, d)
        # heads sharded over tp (kv heads too when divisible)
        q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))
        if self._kv_heads_shardable():
            k = constrain(k, P(UNC, UNC, mesh_lib.TP_AXIS))
            v = constrain(v, P(UNC, UNC, mesh_lib.TP_AXIS))

        if self.mode == "train":
            q = apply_rope(q, freqs, positions)
            k = apply_rope(k, freqs, positions)
            out = attention_op(
                q, k, v, causal=True, impl=self.attention_impl,
                mask=padding_mask, segment_ids=segment_ids,
            )
        else:
            out = self._cached_attention(
                q, k, v, freqs, positions, attn_mask, padding_mask
            )
        out = out.reshape(b, s, cfg.num_heads * d)
        return RowParallelLinear(
            cfg.num_heads * d,
            cfg.hidden_size,
            use_bias=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            quantization_config=cfg.quantization,
            name="o_proj",
        )(out)

    def _cached_attention(self, q, k, v, freqs, positions, attn_mask=None,
                          padding_mask=None):
        from neuronx_distributed_tpu.modules.attention import (
            KVCache,
            prefill_positions,
        )

        cfg = self.config
        b, s = q.shape[0], q.shape[1]
        cache = KVCache(self, b, cfg.max_seq_len, cfg.num_kv_heads,
                        cfg.head_dim_, q.dtype)
        if s > cfg.max_seq_len:
            raise ValueError(
                f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}"
            )
        if self.mode == "prefill":
            if positions is None and padding_mask is not None:
                positions = prefill_positions(padding_mask)
            q = apply_rope(q, freqs, positions)
            k = apply_rope(k, freqs, positions)
            cache.prefill_write(k, v, padding_mask)
            return attention_op(
                q, k, v, causal=True, impl=self.attention_impl,
                mask=padding_mask,
            )
        if self.mode != "decode":
            raise ValueError(f"unknown attention mode {self.mode!r}")
        # decode accepts s >= 1: a 1-token step, an s-token speculative verify
        # window (each row causally masked at its own position), or a Medusa
        # TREE step — explicit per-node ``positions`` (depth offsets) plus an
        # ``attn_mask`` (S, cache_len) replacing the positional mask so each
        # node attends the prefix + its ancestors only
        pos, rope_pos = cache.decode_positions(s, positions)
        q = apply_rope(q, freqs, rope_pos)
        k = apply_rope(k, freqs, rope_pos)
        cache.decode_write(k, v, padding_mask)
        return _decode_attention(
            q, cache.k.value, cache.v.value, pos, mask=attn_mask,
            kv_valid=cache.valid.value,
        )

    def _kv_heads_shardable(self) -> bool:
        if not mesh_lib.model_parallel_is_initialized():
            return True
        tp = mesh_lib.get_tensor_model_parallel_size()
        return self.config.num_kv_heads % tp == 0


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        common = dict(
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            sequence_parallel_enabled=cfg.sequence_parallel,
            quantization_config=cfg.quantization,
        )
        gate = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size, name="gate_proj", **common)(x)
        up = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size, name="up_proj", **common)(x)
        h = jax.nn.silu(gate) * up
        return RowParallelLinear(cfg.intermediate_size, cfg.hidden_size, name="down_proj", **common)(h)


class LlamaDecoderLayer(nn.Module):
    config: LlamaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions=None, attn_mask=None,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        norm = dict(
            eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            sequence_parallel_enabled=cfg.sequence_parallel,
        )
        h = RMSNorm(cfg.hidden_size, name="input_norm", **norm)(x)
        x = x + LlamaAttention(cfg, self.attention_impl, self.mode, name="attn")(
            h, freqs, positions, attn_mask, segment_ids, padding_mask
        )
        h = RMSNorm(cfg.hidden_size, name="post_attn_norm", **norm)(x)
        x = x + LlamaMLP(cfg, name="mlp")(h)
        return x


class _ScanLayerAdapter(nn.Module):
    """Adapts LlamaDecoderLayer to the (carry, out) signature ``nn.scan`` wants."""

    config: LlamaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, positions, attn_mask, segment_ids, padding_mask):
        layer_cls = remat_layer_cls(
            LlamaDecoderLayer, self.config.remat, self.config.remat_policy
        )
        x = layer_cls(self.config, self.attention_impl, self.mode, name="layer")(
            x, freqs, positions, attn_mask, segment_ids, padding_mask
        )
        return x, None


class LlamaModel(nn.Module):
    """Backbone without the LM head."""

    config: LlamaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, attn_mask=None,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        x = ParallelEmbedding(
            num_embeddings=cfg.vocab_size,
            features=cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            sequence_parallel_enabled=cfg.sequence_parallel,
            name="embed",
        )(input_ids)
        freqs = rope_frequencies(cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta)

        if cfg.scan_layers:
            scanned = nn.scan(
                _ScanLayerAdapter,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True},
                length=cfg.num_layers,
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast,
                         nn.broadcast, nn.broadcast),
                metadata_params={nn.PARTITION_NAME: None},
            )(cfg, self.attention_impl, self.mode, name="layers")
            x, _ = scanned(x, freqs, positions, attn_mask, segment_ids, padding_mask)
        else:
            layer_cls = remat_layer_cls(
                LlamaDecoderLayer, cfg.remat, cfg.remat_policy
            )
            for i in range(cfg.num_layers):
                x = layer_cls(cfg, self.attention_impl, self.mode, name=f"layers_{i}")(
                    x, freqs, positions, attn_mask, segment_ids, padding_mask
                )
        x = RMSNorm(
            cfg.hidden_size, eps=cfg.rms_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            sequence_parallel_enabled=cfg.sequence_parallel, name="final_norm",
        )(x)
        return x


class LlamaForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``."""

    config: LlamaConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, attn_mask=None,
                 segment_ids=None, padding_mask=None):
        cfg = self.config
        x = LlamaModel(cfg, self.attention_impl, self.mode, name="model")(
            input_ids, positions, attn_mask, segment_ids, padding_mask
        )
        if cfg.sequence_parallel and x.ndim >= 3:
            # leave SP for the logits: gather the sequence back
            x = constrain(x, P(UNC))
        if self.mode == "prefill":
            x = x[:, -1:]
        logits = ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            quantization_config=cfg.quantization, name="lm_head",
        )(x)
        return logits

    def loss(self, params, input_ids, labels):
        logits = self.apply(params, input_ids)
        return parallel_cross_entropy(logits, labels).mean()
