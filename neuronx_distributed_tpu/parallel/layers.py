"""Tensor-parallel sharded layers (reference: ``parallel_layers/layers.py``).

Reference semantics being reproduced, the GSPMD way:

* ``ColumnParallelLinear`` (layers.py:506): weight ``(in, out)`` sharded on the
  output dim; forward optionally all-gathers sequence-parallel activations and
  the backward all-reduces the input grad (layers.py:381 and
  layers_utils.py:16-137, the hand-written async-overlap machinery). Here the
  kernel carries ``nn.Partitioned`` metadata ``(None, "tp")`` and activations
  get a sharding constraint; XLA's SPMD partitioner inserts the same
  all-gather/all-reduce pair and its latency-hiding scheduler does the
  compute/communication overlap the reference implements by hand.
* ``RowParallelLinear`` (layers.py:731): weight sharded on the input dim,
  forward all-reduce (or reduce-scatter into sequence-parallel layout).
* ``ParallelEmbedding`` (layers.py:154): table sharded on the vocab dim; the
  reference masks out-of-range ids and all-reduces (layers.py:290) — XLA emits
  exactly that pattern for a sharded gather.
* Deterministic TP-degree-invariant init: the reference materializes the full
  master weight on CPU then slices per rank (layers.py:85,:109). Under jit,
  flax inits are written against the GLOBAL logical shape, so invariance holds
  by construction (verified in tests/parallel/test_layers.py).

Not carried over: ``stride`` for fused weights (torch fuses QKV into one GEMM
and must interleave shards; XLA fuses independent matmuls itself, so GQA QKV
keeps separate q/k/v params — see modules/qkv_linear.py), and the meta-device
init path (jax.eval_shape + jit init subsume it).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain

Dtype = Any
Initializer = Callable[..., jax.Array]

default_kernel_init = nn.initializers.lecun_normal()


def _declare_kernel(module, shape, partition, kernel_init, dtype,
                    scale_partition, name="kernel", channel_dim=1,
                    batch_dim=None):
    """Kernel declaration shared by every quantizable weight (the parallel
    linears AND the 3-D expert stacks of ExpertMLPs): float by default; a
    ``quantization_config`` on the module declares the weight-only serving
    form instead — a quantized-dtype kernel plus a float scale sibling
    (``scale`` for a leaf named ``kernel``, ``<name>_scale`` otherwise — the
    exact tree ``quantization.utils.quantize_param_tree`` produces from a
    trained float checkpoint; reference ``from_float`` converters +
    module-swap ``convert``, quantization/quantize.py:18). Forward
    dequantizes; XLA fuses the scale multiply into the matmul epilogue, so
    HBM holds 1-byte weights while the MXU sees a dense GEMM.

    ``channel_dim``/``batch_dim`` pick the per-channel scale layout (stacked
    weights use channel_dim = ndim-1, batch_dim = 0 → ``(E, 1, out)``
    scales; per-tensor with a batch dim yields per-slice scalars ``(E,)``).
    This is the ONE copy of the scale-shape contract on the model side."""
    qcfg = module.quantization_config
    if qcfg is None:
        kernel = module.param(
            name,
            nn.with_partitioning(kernel_init, partition),
            shape,
            module.param_dtype,
        )
        return kernel.astype(dtype)
    from neuronx_distributed_tpu.quantization.utils import dequantize

    kernel, scale = _declare_quantized(
        module, qcfg, shape, partition, scale_partition, name, channel_dim,
        batch_dim,
    )
    if scale.ndim == 1 and len(shape) > 2:  # broadcast per-slice scalars
        scale = scale.reshape((-1,) + (1,) * (len(shape) - 1))
    return dequantize(kernel, scale, dtype)


def _declare_quantized(module, qcfg, shape, partition, scale_partition, name,
                       channel_dim, batch_dim):
    """The ONE copy of the quantized kernel+scale declaration (scale naming,
    zeros-init placeholder kernel, scale-shape contract) — shared by the
    dequant path and the raw int8-MXU path so both always produce the exact
    tree ``quantize_param_tree`` emits."""
    import dataclasses as _dc

    from neuronx_distributed_tpu.quantization.config import QuantizationType
    from neuronx_distributed_tpu.quantization.layers import _scale_shape

    kernel = module.param(
        name,
        nn.with_partitioning(
            lambda key, shp, dt: jnp.zeros(shp, dt), partition
        ),
        shape,
        qcfg.quantized_dtype.jnp_dtype,
    )
    per_tensor = qcfg.quantization_type == QuantizationType.PER_TENSOR_SYMMETRIC
    if per_tensor and batch_dim is not None:
        sshape = (shape[batch_dim],)  # per-slice scalars, e.g. (E,)
        spart = (partition[batch_dim],)
    else:
        eff = qcfg if qcfg.batch_dim == batch_dim else _dc.replace(
            qcfg, batch_dim=batch_dim
        )
        sshape = _scale_shape(eff, shape, channel_dim)
        spart = scale_partition if len(sshape) == len(shape) else ()
    scale = module.param(
        ("scale" if name == "kernel" else name + "_scale"),
        nn.with_partitioning(nn.initializers.ones_init(), spart),
        sshape,
        jnp.float32,
    )
    return kernel, scale


def _declare_kernel_q(module, shape, partition, kernel_init, dtype,
                      scale_partition, name="kernel", channel_dim=1,
                      batch_dim=None):
    """Like :func:`_declare_kernel`, but returns a 3-tuple
    ``(kernel, qscale, act_scale)`` with the RAW quantized kernel whenever
    the module carries a ``quantization_config`` — the caller routes the
    matmul itself: ``qscale is None`` means float (plain ``dot_general``);
    otherwise ``quantization.layers.quantized_matmul`` (dequantize-on-load,
    the weight-only serving path) or — when the config requests the native
    int8 MXU path (``use_int8_matmul``) — ``quantization.utils.int8_matmul``
    with the ``act_scale`` param iff ``use_static_act_scale``.
    ``quantize_param_tree`` with the same config emits exactly this tree."""
    qcfg = module.quantization_config
    if qcfg is None:
        return (
            _declare_kernel(module, shape, partition, kernel_init, dtype,
                            scale_partition, name=name,
                            channel_dim=channel_dim, batch_dim=batch_dim),
            None,
            None,
        )
    kernel, scale = _declare_quantized(
        module, qcfg, shape, partition, scale_partition, name, channel_dim,
        batch_dim,
    )
    act_scale = None
    from neuronx_distributed_tpu.quantization.utils import (
        act_scale_leaf_name,
        wants_static_act_scale,
    )

    # wants_static_act_scale subsumes the int8-MXU predicate (it requires
    # use_int8_matmul + int8 kernels itself)
    if wants_static_act_scale(qcfg):
        # scalar static activation scale, filled by a calibration pass
        # (observer.calibrate_activation_scale); init 1.0 keeps an
        # uncalibrated model runnable (clips at |x| > 127)
        act_scale = module.param(
            act_scale_leaf_name(name),
            nn.with_partitioning(nn.initializers.ones_init(), ()),
            (),
            jnp.float32,
        )
    return kernel, scale, act_scale


def _quantized_forward(qcfg, x, kernel, qscale, act_scale, dtype):
    """The one matmul-mode dispatch of a quantized linear: the native int8
    MXU path when the config asks for it, otherwise the serving-shaped
    dequantize-on-load ``quantized_matmul`` (HBM holds 1-byte weights, the
    MXU sees a dense GEMM — the memory-bound decode case)."""
    from neuronx_distributed_tpu.quantization.layers import quantized_matmul
    from neuronx_distributed_tpu.quantization.utils import (
        int8_matmul,
        wants_int8_mxu,
    )

    if wants_int8_mxu(qcfg):
        return int8_matmul(x, kernel, qscale, dtype, act_scale=act_scale)
    return quantized_matmul(x, kernel, qscale, dtype)


class ColumnParallelLinear(nn.Module):
    """Linear with output-dim sharding: ``Y = X W + b``, W sharded on columns.

    Args mirror the reference (layers.py:506): ``gather_output`` replicates the
    output instead of leaving it tp-sharded; ``sequence_parallel_enabled``
    declares the input sequence dim sharded over tp (Megatron SP), making XLA
    all-gather it into the matmul and reduce-scatter the grad on the way back.
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    gather_output: bool = False
    sequence_parallel_enabled: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = default_kernel_init
    bias_init: Initializer = nn.initializers.zeros_init()
    axis: Optional[str] = mesh_lib.TP_AXIS
    # weight-only serving quantization (int8/fp8 kernel + float scale); see
    # _declare_kernel
    quantization_config: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        kernel, qscale, act_scale = _declare_kernel_q(
            self,
            (self.input_size, self.output_size),
            (None, self.axis),
            self.kernel_init,
            self.dtype,
            scale_partition=(None, self.axis),
        )
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_partitioning(self.bias_init, (self.axis,)),
                (self.output_size,),
                self.param_dtype,
            )
        x = x.astype(self.dtype)
        if self.sequence_parallel_enabled and x.ndim >= 3:
            # Declare the incoming SP layout so the partitioner knows to
            # all-gather seq right here (reference fwd all-gather,
            # layers_utils.py:16).
            x = constrain(x, P(*([UNC] * (x.ndim - 2)), self.axis))
        if qscale is not None:
            y = _quantized_forward(
                self.quantization_config, x, kernel, qscale, act_scale,
                self.dtype,
            )
        else:
            y = jax.lax.dot_general(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ())), precision=None
            )
        if self.use_bias:
            y = y + bias.astype(self.dtype)
        if self.gather_output:
            y = constrain(y, P(*[UNC] * (y.ndim - 1)))
        else:
            y = constrain(y, P(*([UNC] * (y.ndim - 1)), self.axis))
        return y


class RowParallelLinear(nn.Module):
    """Linear with input-dim sharding: each shard computes a partial product,
    summed by an all-reduce (reference layers.py:731,:941) or reduce-scattered
    into sequence-parallel layout when ``sequence_parallel_enabled``.

    ``input_is_parallel`` declares the input already tp-sharded on its last dim
    (the usual case after a ColumnParallelLinear); otherwise XLA scatters it.
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    input_is_parallel: bool = True
    sequence_parallel_enabled: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = default_kernel_init
    bias_init: Initializer = nn.initializers.zeros_init()
    axis: Optional[str] = mesh_lib.TP_AXIS
    quantization_config: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        kernel, qscale, act_scale = _declare_kernel_q(
            self,
            (self.input_size, self.output_size),
            (self.axis, None),
            self.kernel_init,
            self.dtype,
            # per-channel scales live on the (unsharded) out dim
            scale_partition=(None, None),
        )
        if self.use_bias:
            # bias is applied after the reduction → replicated (not sharded),
            # matching the reference where only rank contributions are summed
            # and bias is added once (layers.py:941).
            bias = self.param(
                "bias",
                nn.with_partitioning(self.bias_init, (None,)),
                (self.output_size,),
                self.param_dtype,
            )
        x = x.astype(self.dtype)
        if self.input_is_parallel:
            x = constrain(x, P(*([UNC] * (x.ndim - 1)), self.axis))
        # TP serving comms (ISSUE 14): inside a ``tp_comms`` trace-scope the
        # output reduction routes through the explicit (optionally EQuARX-
        # quantized) ring all-reduce instead of the implicit GSPMD psum —
        # the TP-sharded engine's wire-byte dial. Exact mode is bit-for-bit
        # the psum; quantized mode trades the documented error budget for
        # ~4x fewer all-reduce wire bytes per decode step.
        from neuronx_distributed_tpu.parallel import (
            quantized_collectives as _qc,
        )

        _tp_cfg = _qc.current_tp_comms()
        if (
            _tp_cfg is not None
            and qscale is None
            and not self.sequence_parallel_enabled
            and _qc.tp_comms_applicable(self.axis)
        ):
            y = _qc.tp_dot_allreduce(x, kernel, _tp_cfg, self.axis)
        elif qscale is not None:
            y = _quantized_forward(
                self.quantization_config, x, kernel, qscale, act_scale,
                self.dtype,
            )
        else:
            y = jax.lax.dot_general(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ())), precision=None
            )
        if self.sequence_parallel_enabled and y.ndim >= 3:
            # partial sums → reduce-scatter over the sequence dim
            # (reference mappings.py:320 path)
            y = constrain(y, P(*([UNC] * (y.ndim - 2)), self.axis))
        else:
            y = constrain(y, P(*[UNC] * (y.ndim - 1)))
        if self.use_bias:
            y = y + bias.astype(self.dtype)
        return y


class OutputChannelParallelConv2d(nn.Module):
    """Conv2d with output channels sharded over tp (reference layers.py:1209).
    NHWC layout; kernel (kh, kw, in, out) sharded on out."""

    in_channels: int
    out_channels: int
    kernel_size: tuple
    strides: tuple = (1, 1)
    padding: str = "SAME"
    use_bias: bool = True
    gather_output: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = default_kernel_init
    axis: str = mesh_lib.TP_AXIS

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        kernel = self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (None, None, None, self.axis)),
            (kh, kw, self.in_channels, self.out_channels),
            self.param_dtype,
        )
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype),
            kernel.astype(self.dtype),
            window_strides=self.strides,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_partitioning(nn.initializers.zeros_init(), (self.axis,)),
                (self.out_channels,),
                self.param_dtype,
            )
            y = y + bias.astype(self.dtype)
        spec_tail = None if self.gather_output else self.axis
        return constrain(y, P(*([UNC] * (y.ndim - 1)), spec_tail))


class InputChannelParallelConv2d(nn.Module):
    """Conv2d with input channels sharded over tp → partial sums all-reduced
    (reference layers.py:1332)."""

    in_channels: int
    out_channels: int
    kernel_size: tuple
    strides: tuple = (1, 1)
    padding: str = "SAME"
    use_bias: bool = True
    input_is_parallel: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = default_kernel_init
    axis: str = mesh_lib.TP_AXIS

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        kernel = self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (None, None, self.axis, None)),
            (kh, kw, self.in_channels, self.out_channels),
            self.param_dtype,
        )
        x = x.astype(self.dtype)
        if self.input_is_parallel:
            x = constrain(x, P(*([UNC] * (x.ndim - 1)), self.axis))
        y = jax.lax.conv_general_dilated(
            x,
            kernel.astype(self.dtype),
            window_strides=self.strides,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = constrain(y, P(*[UNC] * (y.ndim - 1)))
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_partitioning(nn.initializers.zeros_init(), (None,)),
                (self.out_channels,),
                self.param_dtype,
            )
            y = y + bias.astype(self.dtype)
        return y


@functools.lru_cache(maxsize=None)
def _vocab_parallel_lookup(mesh, axis: str):
    """Cached jitted shard_map for the vocab-parallel lookup — jit keys on
    callable identity, so rebuilding the wrapper per call would recompile on
    every eager lookup. The jit wrapper exists because the eager shard_map
    impl rejects partial-manual specs (see modules/moe/expert_mlps.py); it
    inlines under an outer jit."""
    from neuronx_distributed_tpu.parallel.collectives import psum_cpu_safe

    def local_lookup(table_l, ids_):
        per = table_l.shape[0]
        lo = jax.lax.axis_index(axis) * per
        local_ids = ids_ - lo
        ok = (local_ids >= 0) & (local_ids < per)
        rows = jnp.take(table_l, jnp.clip(local_ids, 0, per - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, 0)
        return psum_cpu_safe(rows, axis)

    return jax.jit(
        mesh_lib.compat_shard_map(
            local_lookup,
            mesh=mesh,
            in_specs=(P(axis, None), P()),
            out_specs=P(),
            axis_names={axis},
            check_vma=False,
        )
    )


class ParallelEmbedding(nn.Module):
    """Embedding with the table sharded on the vocab dim (reference
    layers.py:154; the shard-on-embedding-dim variant maps to ``shard_dim=1``).
    """

    num_embeddings: int
    features: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    embedding_init: Initializer = nn.initializers.normal(stddev=1.0)
    axis: str = mesh_lib.TP_AXIS
    shard_dim: int = 0  # 0: vocab-sharded, 1: feature-sharded
    sequence_parallel_enabled: bool = False

    @nn.compact
    def __call__(self, ids):
        names = (self.axis, None) if self.shard_dim == 0 else (None, self.axis)
        table = self.param(
            "embedding",
            nn.with_partitioning(self.embedding_init, names),
            (self.num_embeddings, self.features),
            self.param_dtype,
        )
        y = self._lookup(table.astype(self.dtype), ids)
        if self.sequence_parallel_enabled and y.ndim >= 3:
            # hand off straight into SP layout: seq sharded over tp
            y = constrain(y, P(*([UNC] * (y.ndim - 2)), self.axis))
        elif self.shard_dim == 1:
            y = constrain(y, P(*([UNC] * (y.ndim - 1)), self.axis))
        else:
            y = constrain(y, P(*[UNC] * (y.ndim - 1)))
        return y

    def _lookup(self, table, ids):
        """Vocab-sharded lookup as an explicit masked local gather + psum
        (the reference's input-masking formulation, layers.py:154,:290),
        inside a partial-manual shard_map over tp. Besides matching reference
        semantics, this sidesteps an XLA SPMD-partitioner CHECK crash
        (spmd_partitioner_util.cc:495, jaxlib 0.9) that the auto-partitioned
        vocab-sharded gather triggers on meshes with pp > 1."""
        tp = (
            mesh_lib.get_tensor_model_parallel_size()
            if mesh_lib.model_parallel_is_initialized()
            else 1
        )
        if self.shard_dim != 0 or tp <= 1 or self.num_embeddings % tp != 0:
            return jnp.take(table, ids, axis=0)
        mesh = mesh_lib.get_mesh()
        ctx_mesh = mesh_lib.ctx_abstract_mesh()
        # gather the feature dim BEFORE entering the partial-manual region:
        # under ZeRO-1 the table arrives with H sharded over (edp, ep, cp),
        # and inside the region that sharding collides with the (B, S)-
        # sharded mask of the where() — the SPMD partitioner resolved it by
        # involuntary full rematerialization (seen in a cp=2 CPU dry run)
        table = constrain(table, P(self.axis))
        return _vocab_parallel_lookup(
            mesh if ctx_mesh.empty else ctx_mesh, self.axis
        )(table, ids)
