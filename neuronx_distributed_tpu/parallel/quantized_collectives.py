"""Quantized all-reduce (EQuARX-style, PAPERS.md arXiv 2506.17615): ring
all-reduce whose wire traffic is int8 blocks + scales instead of fp32/bf16.

Large-model TP inference spends a growing fraction of each decode step in
the row-parallel all-reduces; EQuARX shows that quantizing the PAYLOAD of
the collective — not the math around it — recovers most of that time at
negligible quality cost, because the reduction re-materializes in float at
every hop. The wrapper here reproduces that structure with jax collectives:

* **ring reduce-scatter, dequant-add-requant per hop** — each rank
  circulates one chunk of the tensor around the ring (``lax.ppermute``);
  what travels is the int8-quantized partial plus its scales, and each
  receiver dequantizes, adds its own float chunk, and requantizes before
  forwarding. N-1 hops of 1-byte traffic replace N-1 hops of 4-byte
  traffic (~4x wire bytes at ``block_size=256``; :func:`comm_bytes` does
  the exact accounting).
* **int8 all-gather of the finished chunks** — the second phase of the
  ring moves the already-quantized complete chunks, dequantized once at
  the destination.
* **blockwise scales** (default) — one symmetric absmax scale per
  ``block_size`` contiguous elements of the flattened tensor, the EQuARX
  formulation that keeps outliers from poisoning the whole tensor's grid;
  ``scale_granularity="absmax"`` is the cheap per-chunk-scalar fallback
  (fewer scale bytes, cruder grid).

Error model: each hop re-quantizes a partial sum, so the element error is
bounded by ~``(N-1) · absmax/254`` — a relative error in the 1e-2 range for
well-scaled activations/gradients (pinned in
``tests/parallel/test_quantized_collectives.py`` on the CPU mesh). This is
an APPROXIMATE collective: gate it behind :class:`QuantizedAllReduceConfig`
(``enabled=False`` routes to the exact ``psum``) and keep it off any path
whose contract is bit-exactness (losses, metrics, the serving engine's
greedy streams when bit-identity is pinned).

Like everything in ``parallel/collectives.py``, the ops here must run
inside a ``shard_map``/``pmap`` context binding ``axis_name`` — the CPU
test mesh (``--xla_force_host_platform_device_count=8``) exercises the full
ring deterministically, which is what the multi-chip TP serving item will
land on.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

QMAX = 127.0  # int8 symmetric clamp bound (quantization/config.py contract)


def _axis_size(axis_name) -> int:
    """STATIC size of a bound mesh axis (the ring hop count is a python
    loop, so it must be a python int)."""
    # graftlint: ok[GL02] axis_size is STATIC trace-time metadata (a
    # python int under shard_map), not a device value — no transfer
    return int(lax.axis_size(axis_name))


@dataclasses.dataclass(frozen=True)
class QuantizedAllReduceConfig:
    """The config flag gating the approximate collective. ``enabled=False``
    (default) keeps every all-reduce exact; flip it per call site, never
    globally — quantized comms are a per-path accuracy decision."""

    enabled: bool = False
    block_size: int = 256
    scale_granularity: str = "block"  # "block" | "absmax"

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        if self.scale_granularity not in ("block", "absmax"):
            raise ValueError(
                f"unknown scale_granularity {self.scale_granularity!r} "
                "(expected 'block' or 'absmax')"
            )


def _quantize_chunk(chunk: jax.Array, block_size: int,
                    per_tensor: bool) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization of a flat fp32 chunk (length a multiple
    of ``block_size``): blockwise scales (n_blocks, 1), or ONE per-chunk
    scalar () for the abs-max fallback — the scalar is what travels, so
    the fallback really does ship fewer scale bytes (4 per hop)."""
    blocks = chunk.reshape(-1, block_size)
    if per_tensor:
        amax = jnp.max(jnp.abs(blocks))
    else:
        amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / QMAX
    q = jnp.clip(jnp.round(blocks / scale), -QMAX, QMAX).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize_chunk(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Scale is () (absmax) or (n_blocks, 1) (blockwise); both broadcast."""
    return (q.astype(jnp.float32) * scale).reshape(-1)


def quantized_all_reduce(
    x: jax.Array,
    axis_name,
    block_size: int = 256,
    scale_granularity: str = "block",
) -> jax.Array:
    """Approximate ``lax.psum(x, axis_name)`` with int8 wire traffic (see
    module docstring). Same shape/dtype out as in; must run where
    ``axis_name`` is bound. N=1 axes return ``x`` unchanged (exact)."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    if scale_granularity not in ("block", "absmax"):
        raise ValueError(
            f"unknown scale_granularity {scale_granularity!r}"
        )
    per_tensor = scale_granularity == "absmax"
    n_ranks = _axis_size(axis_name)
    if n_ranks == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    # equal chunks of whole blocks: pad once, slice the result back
    chunk_elems = -(-n // (n_ranks * block_size)) * block_size
    flat = jnp.pad(flat, (0, n_ranks * chunk_elems - n))
    chunks = flat.reshape(n_ranks, chunk_elems)
    rank = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n_ranks) for i in range(n_ranks)]

    # phase 1 — ring reduce-scatter, dequant-add-requant per hop: at step t
    # rank r forwards its partial of chunk (r - t) mod N and folds its own
    # float contribution into the incoming partial of chunk (r - t - 1)
    acc = jnp.take(chunks, rank % n_ranks, axis=0)
    for t in range(n_ranks - 1):
        q, s = _quantize_chunk(acc, block_size, per_tensor)
        q = lax.ppermute(q, axis_name, fwd)
        s = lax.ppermute(s, axis_name, fwd)
        local = jnp.take(chunks, (rank - t - 1) % n_ranks, axis=0)
        acc = _dequantize_chunk(q, s) + local
    # rank r now owns the COMPLETE chunk (r + 1) mod N

    # phase 2 — all-gather the finished chunks (still 1-byte payload),
    # dequantize once at the destination, un-rotate the ownership shift
    q, s = _quantize_chunk(acc, block_size, per_tensor)
    gq = lax.all_gather(q, axis_name)  # (N, n_blocks, block)
    gs = lax.all_gather(s, axis_name)  # (N, n_blocks, 1) | (N,) absmax
    order = (jnp.arange(n_ranks) - 1) % n_ranks  # chunk c sits at rank c-1
    gq = jnp.take(gq, order, axis=0)
    gs = jnp.take(gs, order, axis=0)
    if per_tensor:
        gs = gs.reshape(n_ranks, 1, 1)
    out = (gq.astype(jnp.float32) * gs).reshape(-1)[:n]
    return out.reshape(shape).astype(dtype)


def all_reduce(x: jax.Array, axis_name,
               config: Optional[QuantizedAllReduceConfig] = None) -> jax.Array:
    """The gated entry point: exact ``psum`` unless ``config.enabled`` —
    call sites opt in per path, and a disabled config is byte-for-byte
    today's collective."""
    from neuronx_distributed_tpu.parallel.collectives import psum_cpu_safe

    if config is None or not config.enabled:
        return psum_cpu_safe(x, axis_name)
    return quantized_all_reduce(
        x, axis_name,
        block_size=config.block_size,
        scale_granularity=config.scale_granularity,
    )


def comm_bytes(n_elems: int, n_ranks: int, block_size: int = 256,
               fp_bytes: int = 4,
               scale_granularity: str = "block") -> dict:
    """Wire-byte accounting of one all-reduce of ``n_elems`` elements over
    ``n_ranks`` — the EQuARX claim as arithmetic, pinned by
    ``tests/parallel/test_quantized_collectives.py::test_comm_bytes_accounting``
    and, off the lowered IR, by graftverify's GV03 table. Both phases of the ring move
    ``(N-1)/N · n`` elements per rank; the quantized payload is 1 byte per
    element plus 4 scale bytes per block (blockwise) or per hop (the
    abs-max fallback's single scalar)."""
    if n_ranks < 2:
        return {"fp_bytes": 0, "quantized_bytes": 0, "ratio": 1.0}
    chunk = -(-n_elems // (n_ranks * block_size)) * block_size
    hops = 2 * (n_ranks - 1)  # per rank, both phases
    moved = hops * chunk
    fp = moved * fp_bytes
    scale = (
        (moved // block_size) * 4 if scale_granularity == "block"
        else hops * 4
    )
    q = moved * 1 + scale
    return {
        "fp_bytes": int(fp),
        "quantized_bytes": int(q),
        "ratio": round(fp / max(q, 1), 3),
    }


# --- TP serving comms routing (ISSUE 14) --------------------------------------
#
# The GSPMD serving forward has no explicit psum to reroute — XLA inserts the
# row-parallel reduction from the layers' sharding constraints. The opt-in
# below gives the TP-sharded serving engine an explicit reduction to own:
# while a ``tp_comms`` trace-scope is active, every RowParallelLinear routes
# its output reduction through :func:`tp_dot_allreduce` — a manual-SPMD
# region computing the local partial product and reducing it with the
# EQuARX ring above — instead of the implicit GSPMD psum. The scope is
# TRACE-time state: the engine wraps its jitted programs so only its own
# traces see the config, and two engines in one process (one quantized, one
# exact) never contaminate each other.

_TP_COMMS_STACK: list = []


class tp_comms:
    """Trace-scope installing a :class:`QuantizedAllReduceConfig` for the
    row-parallel layers traced inside it (``None``/disabled = exact)."""

    def __init__(self, config: Optional[QuantizedAllReduceConfig]):
        self.config = config

    def __enter__(self):
        _TP_COMMS_STACK.append(self.config)
        return self.config

    def __exit__(self, *exc):
        _TP_COMMS_STACK.pop()


def current_tp_comms() -> Optional[QuantizedAllReduceConfig]:
    return _TP_COMMS_STACK[-1] if _TP_COMMS_STACK else None


def tp_comms_applicable(axis) -> bool:
    """Whether the active mesh can route a row-parallel reduction through
    the explicit manual region: an initialized mesh with > 1 rank on
    ``axis`` and EVERY other axis trivial (the serving tp mesh) — the
    manual region claims all axes, so a live dp/pp/cp extent would need
    sharded operands this entry point does not speak."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    if not mesh_lib.model_parallel_is_initialized():
        return False
    mesh = mesh_lib.get_mesh()
    if int(mesh.shape[axis]) <= 1:
        return False
    return all(
        int(size) == 1 for name, size in mesh.shape.items() if name != axis
    )


def tp_dot_allreduce(x: jax.Array, kernel: jax.Array,
                     config: QuantizedAllReduceConfig, axis) -> jax.Array:
    """Row-parallel linear with an EXPLICIT (optionally quantized) ring
    all-reduce: ``x`` tp-sharded on its last dim, ``kernel`` tp-sharded on
    its input dim; each rank computes its partial product and the ring
    merges them — int8 wire traffic when ``config.enabled``, the exact
    ``psum`` otherwise (bit-for-bit the GSPMD reduction)."""
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    lead = x.ndim - 1
    x_spec = P(*([None] * lead), axis)
    k_spec = P(axis, None)
    out_spec = P(*([None] * lead), None)

    def body(xv, kv):
        part = lax.dot_general(
            xv, kv, (((xv.ndim - 1,), (0,)), ((), ())), precision=None
        )
        return all_reduce(part, axis, config)

    return mesh_lib.manual_shard_map(
        body, in_specs=(x_spec, k_spec), out_specs=out_spec
    )(x, kernel)
