"""Differentiable region-mapping collectives (reference: ``parallel_layers/mappings.py``).

The reference implements each mapping as a ``torch.autograd.Function`` pair
obeying the conjugate-transpose rule: copy↔all-reduce (mappings.py:175),
scatter↔gather (mappings.py:214,235), sequence-parallel scatter/gather/
reduce-scatter (mappings.py:256-345), and expert all-to-all (mappings.py:348).

On TPU these exist for code written in the explicit-SPMD style (``shard_map``):
each function takes a local shard plus a static mesh axis name and defines a
``jax.custom_vjp`` with the conjugate collective as its backward. GSPMD-mode
model code (sharding constraints under ``jit``) does not need them — XLA inserts
the same collectives automatically — but the pipeline engine, ring attention,
MoE dispatch, and parity tests use them directly.

All ``dim`` arguments are normalized, so negative dims work; the reference needs
a transpose-to-dim0 decorator for that (mappings.py:26), XLA does not.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax

from neuronx_distributed_tpu.parallel.mesh import (  # noqa: F401
    CP_AXIS,
    EP_AXIS,
    TP_AXIS,
)


def _norm_dim(dim: int, ndim: int) -> int:
    return dim % ndim


def _local_slice(x, axis_name: str, dim: int):
    """Take this rank's chunk of a replicated tensor along ``dim``."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    dim = _norm_dim(dim, x.ndim)
    if x.shape[dim] % n != 0:
        raise ValueError(f"dim {dim} size {x.shape[dim]} not divisible by axis size {n}")
    size = x.shape[dim] // n
    return lax.dynamic_slice_in_dim(x, idx * size, size, axis=dim)


# --- copy / reduce (reference mappings.py:175,399-415) ------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tensor_model_parallel_region(x, axis_name: str = TP_AXIS):
    """Identity forward, all-reduce backward — entering a TP region where the
    same activation feeds every TP rank."""
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, g):
    return (lax.psum(g, axis_name),)


copy_to_tensor_model_parallel_region.defvjp(_copy_fwd, _copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tensor_model_parallel_region(x, axis_name: str = TP_AXIS):
    """All-reduce forward, identity backward — leaving a TP region where each
    rank holds a partial sum (e.g. after RowParallelLinear)."""
    return lax.psum(x, axis_name)


def _reduce_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _reduce_bwd(axis_name, _, g):
    return (g,)


reduce_from_tensor_model_parallel_region.defvjp(_reduce_fwd, _reduce_bwd)


# --- scatter / gather on an arbitrary dim (reference mappings.py:214,235) -----

@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scatter_to_tensor_model_parallel_region(x, axis_name: str = TP_AXIS, dim: int = -1):
    """Slice my chunk forward, all-gather backward."""
    return _local_slice(x, axis_name, dim)


def _scatter_fwd(x, axis_name, dim):
    return _local_slice(x, axis_name, dim), None


def _scatter_bwd(axis_name, dim, _, g):
    return (lax.all_gather(g, axis_name, axis=_norm_dim(dim, g.ndim), tiled=True),)


scatter_to_tensor_model_parallel_region.defvjp(_scatter_fwd, _scatter_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_tensor_model_parallel_region(x, axis_name: str = TP_AXIS, dim: int = -1):
    """All-gather forward, slice-my-chunk backward."""
    return lax.all_gather(x, axis_name, axis=_norm_dim(dim, x.ndim), tiled=True)


def _gather_fwd(x, axis_name, dim):
    return lax.all_gather(x, axis_name, axis=_norm_dim(dim, x.ndim), tiled=True), None


def _gather_bwd(axis_name, dim, _, g):
    return (_local_slice(g, axis_name, dim),)


gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


# --- sequence-parallel mappings (reference mappings.py:256-345) ---------------

def scatter_to_sequence_parallel_region(x, axis_name: str = TP_AXIS, dim: int = 0):
    """Entering SP: slice the sequence dim forward, all-gather backward. Same
    slice/all-gather conjugate as the TP scatter, just defaulting to the
    sequence dim (reference keeps two autograd classes; one VJP serves both)."""
    return scatter_to_tensor_model_parallel_region(x, axis_name, dim)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_sequence_parallel_region(x, axis_name: str = TP_AXIS, dim: int = 0):
    """Leaving SP into a TP region: all-gather the sequence forward,
    reduce-scatter backward (the SP↔TP conjugate, reference mappings.py:280)."""
    return lax.all_gather(x, axis_name, axis=_norm_dim(dim, x.ndim), tiled=True)


def _sp_gather_fwd(x, axis_name, dim):
    return lax.all_gather(x, axis_name, axis=_norm_dim(dim, x.ndim), tiled=True), None


def _sp_gather_bwd(axis_name, dim, _, g):
    return (
        lax.psum_scatter(
            g, axis_name, scatter_dimension=_norm_dim(dim, g.ndim), tiled=True
        ),
    )


gather_from_sequence_parallel_region.defvjp(_sp_gather_fwd, _sp_gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def reduce_scatter_to_sequence_parallel_region(x, axis_name: str = TP_AXIS, dim: int = 0):
    """Entering SP from a partial-sum TP region (after RowParallel):
    reduce-scatter forward, all-gather backward (reference mappings.py:320)."""
    return lax.psum_scatter(
        x, axis_name, scatter_dimension=_norm_dim(dim, x.ndim), tiled=True
    )


def _sp_rs_fwd(x, axis_name, dim):
    return (
        lax.psum_scatter(
            x, axis_name, scatter_dimension=_norm_dim(dim, x.ndim), tiled=True
        ),
        None,
    )


def _sp_rs_bwd(axis_name, dim, _, g):
    return (lax.all_gather(g, axis_name, axis=_norm_dim(dim, g.ndim), tiled=True),)


reduce_scatter_to_sequence_parallel_region.defvjp(_sp_rs_fwd, _sp_rs_bwd)


# --- expert-parallel all-to-all (reference mappings.py:348,474-548) -----------

def enter_expert_parallel_region(x, axis_name: str = EP_AXIS, split_dim: int = 0, concat_dim: int = 1):
    """Exchange token chunks for expert chunks across the ep axis. The forward
    splits ``split_dim`` (experts) and concatenates ``concat_dim`` (tokens);
    ``lax.all_to_all`` is natively differentiable with the swapped-dims
    transpose, which is exactly the reference's backward (mappings.py:348)."""
    return lax.all_to_all(
        x,
        axis_name,
        split_axis=_norm_dim(split_dim, x.ndim),
        concat_axis=_norm_dim(concat_dim, x.ndim),
        tiled=True,
    )


def exit_expert_parallel_region(x, axis_name: str = EP_AXIS, split_dim: int = 1, concat_dim: int = 0):
    """Inverse of :func:`enter_expert_parallel_region`."""
    return lax.all_to_all(
        x,
        axis_name,
        split_axis=_norm_dim(split_dim, x.ndim),
        concat_axis=_norm_dim(concat_dim, x.ndim),
        tiled=True,
    )
