"""Distributed top-k / argmax over a tp-sharded dim (reference:
``operators/topk.py:12``, ``operators/argmax.py:12`` — custom
``torch_neuronx`` XLA ops with a multi-stage tree reduction for tp32/tp64).

TPU formulation: two-stage candidate reduction inside ``shard_map`` — each tp
shard computes its local ``lax.top_k``, candidates (k per shard) are
all-gathered (k·tp values, tiny), and a second local top-k over the gathered
candidates with global-index correction yields the exact result on every
shard. This is the same tree idea as the reference's multi-stage kernel, with
XLA choosing the gather layout. Without a mesh (or tp=1) it degrades to plain
``lax.top_k``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel import mesh as mesh_lib


def _local_then_global_topk(x, k, axis_name):
    """Inside shard_map: x (..., V_local) → exact global (values, indices)."""
    tp = lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    v_loc = x.shape[-1]
    vals, idx = lax.top_k(x, k)  # local candidates
    idx = idx + rank * v_loc  # globalize indices
    # gather candidates from every shard: (..., tp*k)
    vals_g = lax.all_gather(vals, axis_name, axis=x.ndim - 1, tiled=True)
    idx_g = lax.all_gather(idx, axis_name, axis=x.ndim - 1, tiled=True)
    top_vals, cand_pos = lax.top_k(vals_g, k)
    top_idx = jnp.take_along_axis(idx_g, cand_pos, axis=-1)
    del tp
    return top_vals, top_idx


def topk(
    x: jax.Array, k: int, dim: int = -1, axis_name: str = mesh_lib.TP_AXIS
) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k of a (possibly tp-sharded) tensor along ``dim``.
    Returns replicated ``(values, global_indices)`` (reference topk:12)."""
    dim = dim % x.ndim
    if dim != x.ndim - 1:
        x = jnp.moveaxis(x, dim, -1)
    if (
        not mesh_lib.model_parallel_is_initialized()
        or mesh_lib.get_mesh().shape[axis_name] == 1
    ):
        vals, idx = lax.top_k(x, k)
    else:
        mesh = mesh_lib.get_mesh()
        if x.shape[-1] % mesh.shape[axis_name] != 0:
            vals, idx = lax.top_k(x, k)  # not shardable → plain path
        else:
            in_spec = P(*([None] * (x.ndim - 1)), axis_name)
            out_spec = P(*([None] * x.ndim))
            vals, idx = mesh_lib.manual_shard_map(
                lambda t: _local_then_global_topk(t, k, axis_name),
                in_specs=(in_spec,),
                out_specs=(out_spec, out_spec),
            )(x)
    if dim != x.ndim - 1:
        vals = jnp.moveaxis(vals, -1, dim)
        idx = jnp.moveaxis(idx, -1, dim)
    return vals, idx


def argmax(
    x: jax.Array, dim: int = -1, keepdim: bool = False, axis_name: str = mesh_lib.TP_AXIS
) -> jax.Array:
    """Exact argmax over a (possibly tp-sharded) dim with global indices
    (reference argmax:12)."""
    _, idx = topk(x, 1, dim=dim, axis_name=axis_name)
    if not keepdim:
        idx = jnp.squeeze(idx, axis=dim % x.ndim)
    return idx
