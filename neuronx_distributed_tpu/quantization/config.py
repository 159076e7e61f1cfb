"""Quantization config (reference: ``quantization/quantization_config.py``
``QuantizationType``/``QuantizedDtype`` enums + qconfig dicts :39-101).

Two levels of config live here:

* :class:`QuantizationConfig` — the per-kernel qconfig the sharded layers
  and ``quantize_param_tree`` speak (dtype, scale scheme, channel layout).
* :class:`QuantConfig` — the SERVING-level knob
  (``ServingEngine(quantize=QuantConfig(weights="int8", kv="int8"))``):
  which resources of the decode hot path are quantized — the bound params
  (weight-only int8/fp8, dequantize-on-load inside the jitted matmul) and
  the paged KV pool (int8 pages + per-page/per-head scales). It lowers to
  a :class:`QuantizationConfig` for the weight side via
  :meth:`QuantConfig.weight_qconfig`.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import jax.numpy as jnp


class QuantizationType(str, enum.Enum):
    PER_TENSOR_SYMMETRIC = "per_tensor_symmetric"
    PER_CHANNEL_SYMMETRIC = "per_channel_symmetric"


class QuantizedDtype(str, enum.Enum):
    INT8 = "int8"
    FP8E4M3 = "f8e4m3"

    @property
    def jnp_dtype(self):
        return {
            QuantizedDtype.INT8: jnp.int8,
            QuantizedDtype.FP8E4M3: jnp.float8_e4m3fn,
        }[self]

    @property
    def max_value(self) -> float:
        # symmetric clamp bound (reference quantization_utils.py:130 fp8 clamp)
        return {QuantizedDtype.INT8: 127.0, QuantizedDtype.FP8E4M3: 448.0}[self]


@dataclasses.dataclass(frozen=True)
class QuantizationConfig:
    """Typed qconfig (reference dict-based get_default_*_config)."""

    quantization_type: QuantizationType = QuantizationType.PER_CHANNEL_SYMMETRIC
    quantized_dtype: QuantizedDtype = QuantizedDtype.INT8
    # dim holding output channels in the kernel (column-parallel kernels are
    # (in, out) → channel dim 1; per-channel scales live on that dim)
    channel_dim: int = 1
    # batch dim kept out of the scale reduction — set 0 for expert-fused 3D
    # kernels (E, in, out) so every expert gets its own scales (reference
    # quantizes each expert's matrix independently, quantization_layers.py:867)
    batch_dim: int | None = None
    # serve dense linears with a NATIVE int8×int8 MXU matmul (dynamic
    # per-token activation quantization + fp32 scale epilogue) instead of
    # dequant-then-bf16-matmul. Same param tree; only the forward changes.
    # int8 kernels only; 3-D expert stacks and the fused QKV keep the
    # dequant path (see PARITY.md). Approximate: adds activation-quant
    # error (~1e-2 relative) on top of the weight quant the dequant path
    # already has — gate on your accuracy-check mode before enabling.
    use_int8_matmul: bool = False
    # with use_int8_matmul: declare a per-linear scalar ``act_scale`` param
    # (init 1.0) used as a STATIC activation scale instead of the per-token
    # dynamic absmax. Fill the leaves from a calibration pass
    # (observer.calibrate_activation_scale on each linear's input); the
    # dynamic path needs no calibration and is the default.
    use_static_act_scale: bool = False


# the serving-level spellings ServingEngine(quantize=) accepts, mapped to
# the kernel dtype each lowers to
_WEIGHT_DTYPES = {
    "int8": QuantizedDtype.INT8,
    "fp8": QuantizedDtype.FP8E4M3,
}
_KV_DTYPES = ("int8",)


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """What the serving engine quantizes (``ServingEngine(quantize=...)``).

    ``weights``: ``"int8"`` / ``"fp8"`` / ``None`` — weight-only
    quantization of the bound params, converted ONCE at engine construction
    (per-channel symmetric scales, the ``quantize_param_tree`` contract);
    the jitted decode/prefill programs dequantize-on-load inside the matmul
    (``quantization.layers.quantized_matmul``), so HBM holds 1-byte weights
    while the MXU still sees a dense GEMM — the memory-bound decode win.

    ``kv``: ``"int8"`` / ``None`` — quantize the PAGED KV pool (requires
    ``kv_page_size=``): pool pages store int8 K/V plus per-page/per-kv-head
    scales as sibling leaves; the decode chunk dequantizes on the gathered
    logical view and re-quantizes only its write-window pages on the way
    out. Half-size pages → ~2x pages at a fixed HBM budget, compounding
    with paging's ~2x slots.

    The correctness contract under quantization shifts from bit-identity to
    a LOGIT-DIVERGENCE budget (pinned in
    ``tests/serving/test_quantized_engine.py``): the quantized stream's
    per-step logits stay within a max-KL / top-1-agreement budget of the
    fp32 stream, so a greedy stream stays token-identical wherever fp32's
    own choice is not a tie that budget can flip. Keep fp32 (``quantize=None``) when bit-exact streams are
    the requirement."""

    weights: Optional[str] = "int8"
    kv: Optional[str] = None

    def __post_init__(self):
        if self.weights is not None and self.weights not in _WEIGHT_DTYPES:
            raise ValueError(
                f"unknown weight quantization {self.weights!r} "
                f"(expected one of {sorted(_WEIGHT_DTYPES)} or None)"
            )
        if self.kv is not None and self.kv not in _KV_DTYPES:
            raise ValueError(
                f"unknown KV quantization {self.kv!r} "
                f"(expected one of {sorted(_KV_DTYPES)} or None)"
            )
        if self.weights is None and self.kv is None:
            raise ValueError(
                "QuantConfig quantizes nothing (weights=None, kv=None) — "
                "pass quantize=None instead"
            )

    def weight_qconfig(self) -> Optional[QuantizationConfig]:
        """The per-kernel :class:`QuantizationConfig` the weight side lowers
        to: per-channel symmetric scales (the serving default — robust to
        per-channel outliers, sharding-compatible on every parallel
        layer), dequant-then-matmul forward."""
        if self.weights is None:
            return None
        return QuantizationConfig(
            quantization_type=QuantizationType.PER_CHANNEL_SYMMETRIC,
            quantized_dtype=_WEIGHT_DTYPES[self.weights],
        )
