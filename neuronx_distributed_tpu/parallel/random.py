"""RNG policy (reference: ``parallel_layers/random.py`` Megatron-style tracked RNG).

The reference forks a "model-parallel" RNG state seeded ``seed + 2718 + tp_rank``
so sharded weights and dropout differ per TP rank while the default (DP) state
stays synchronized (random.py:20,100). JAX needs no mutable tracker: keys are
explicit and per-rank streams come from ``jax.random.fold_in``.

Two regimes:
  * GSPMD (jit + sharding constraints): init and dropout are written against the
    GLOBAL logical tensor, so results are TP-degree-invariant by construction —
    the property the reference engineers via materialize-then-slice
    (layers.py:109). Nothing to do.
  * shard_map (explicit SPMD): fold the mesh axis index into the key with
    :func:`fold_in_axes` to get decorrelated per-rank streams.
"""

from __future__ import annotations

import jax

from neuronx_distributed_tpu.parallel import mesh as mesh_lib

# Parity constant with the reference's model-parallel seed offset (random.py:64).
TENSOR_PARALLEL_SEED_OFFSET = 2718


def model_parallel_base_key(key: jax.Array) -> jax.Array:
    """The forked model-parallel stream (before per-rank folding)."""
    return jax.random.fold_in(key, TENSOR_PARALLEL_SEED_OFFSET)


def fold_in_axes(key: jax.Array, *axis_names: str) -> jax.Array:
    """Per-rank key inside ``shard_map``: folds each mesh axis index in turn."""
    for name in axis_names:
        key = jax.random.fold_in(key, jax.lax.axis_index(name))
    return key
