"""Thin named-axis collective API (reference: ``parallel_layers/comm.py``).

The reference funnels every collective through one dispatch point that picks
``xm.*`` (device) or gloo (CPU mode) per call (comm.py:124,163,200). On TPU the
same choke point is trivial: every collective is a ``jax.lax`` primitive taking
an ``axis_name``, lowered by XLA to ICI/DCN collectives on TPU and to threadpool
collectives on the CPU backend — the CPU test mode needs no separate code path.

All functions here must be called inside a ``shard_map``/``pmap`` context where
``axis_name`` is bound. GSPMD-mode model code (sharding constraints under jit)
never calls these; they serve the explicitly-collective subsystems (pipeline,
ring attention, MoE all-to-all, explicit ZeRO-1).
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
from jax import lax

AxisName = Union[str, Sequence[str]]


def all_reduce(x, axis_name: AxisName):
    """Sum over the mesh axis (reference comm.py:200 all_reduce)."""
    return lax.psum(x, axis_name)


def psum_cpu_safe(x, axis_name: AxisName):
    """``lax.psum`` that upcasts bf16 to fp32 on the CPU backend: jaxlib
    0.9's CPU AllReducePromotion pass CHECK-crashes on bf16 all-reduces
    ("Invalid binary instruction opcode copy"). On TPU the bf16 psum stays
    (ICI bandwidth). Use for any psum whose operand may be bf16 on the
    virtual CPU test mesh."""
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu" and x.dtype == jnp.bfloat16:
        return lax.psum(x.astype(jnp.float32), axis_name).astype(jnp.bfloat16)
    return lax.psum(x, axis_name)


def all_reduce_max(x, axis_name: AxisName):
    return lax.pmax(x, axis_name)


def all_reduce_min(x, axis_name: AxisName):
    return lax.pmin(x, axis_name)


def all_gather(x, axis_name: AxisName, dim: int = 0):
    """Concatenate shards along ``dim`` (reference comm.py:163 all_gather)."""
    return lax.all_gather(x, axis_name, axis=dim % x.ndim, tiled=True)


def reduce_scatter(x, axis_name: AxisName, dim: int = 0):
    """Sum then scatter along ``dim`` (reference comm.py:124 reduce_scatter;
    on gloo the reference hand-rolls it — XLA has it natively)."""
    return lax.psum_scatter(
        x, axis_name, scatter_dimension=dim % x.ndim, tiled=True
    )


def all_to_all(x, axis_name: AxisName, split_dim: int, concat_dim: int):
    """Exchange equal splits between all members of the axis
    (reference mappings.py:165 via ``xm.all_to_all``)."""
    return lax.all_to_all(
        x,
        axis_name,
        split_axis=split_dim % x.ndim,
        concat_axis=concat_dim % x.ndim,
        tiled=True,
    )


def permute(x, axis_name: AxisName, perm: Sequence[tuple]):
    """Point-to-point rotation over the axis, the TPU-native replacement for
    the reference's p2p-as-2-rank-all-gather (pipeline/comm.py:40,74).
    ``perm`` is a list of (source_rank, target_rank) pairs."""
    return lax.ppermute(x, axis_name, perm)


def shift_right(x, axis_name: AxisName):
    """Ring step: send each shard to rank+1, wrapping the last rank's shard
    around to rank 0. For the zero-fill pipeline-boundary variant use
    :func:`permute` with a non-wrapping perm (absent pairs receive zeros)."""
    n = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def broadcast(x, axis_name: AxisName, root: int = 0):
    """Replicate ``root``'s value across the axis (reference loads use
    all-reduce-as-broadcast, trainer/checkpoint.py:346)."""
    idx = axis_index(axis_name)
    import jax.numpy as jnp

    masked = jax.tree.map(lambda t: jnp.where(idx == root, t, jnp.zeros_like(t)), x)
    return lax.psum(masked, axis_name)


def axis_index(axis_name: AxisName):
    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisName) -> int:
    return lax.axis_size(axis_name)
