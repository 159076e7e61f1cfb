"""GSPMD sharding helpers: constraints and param-spec extraction.

The reference attaches TP metadata to tensors (``tensor_model_parallel``,
``partition_dim`` — parallel_layers/utils.py:51) and moves data with explicit
collectives. In GSPMD mode the equivalent is (a) flax ``nn.Partitioned``
metadata on params, created by the parallel layers, and (b)
``with_sharding_constraint`` on activations at layer boundaries; XLA's SPMD
partitioner inserts the collectives the reference writes by hand.
"""

from __future__ import annotations

from typing import Any

import jax
from flax import linen as nn
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel import mesh as mesh_lib

UNC = P.UNCONSTRAINED

def constrain(x, spec: P):
    """``with_sharding_constraint`` over the global mesh; no-op when the mesh is
    not initialized (pure single-device use).

    Inside a partial-manual ``shard_map`` (e.g. the pipeline engine, manual
    over pp with tp/dp auto) the tracing context carries an AbstractMesh with
    Manual axis types, and a NamedSharding over the concrete mesh is rejected —
    there the bare PartitionSpec form binds to the context mesh instead. Manual
    axes must simply not appear in ``spec`` (ours name only tp/cp/ep and the
    (edp, ep) DATA_AXES pair — never pp, the pipeline's manual axis)."""
    if not mesh_lib.model_parallel_is_initialized():
        return x
    ctx_mesh = mesh_lib.ctx_abstract_mesh()
    if not ctx_mesh.empty and not ctx_mesh.are_all_axes_auto:
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh_lib.get_mesh(), spec)
    )


def shard_last_dim(x, axis=mesh_lib.TP_AXIS):
    """Constrain only the last dim (leading dims left to XLA propagation)."""
    return constrain(x, P(*([UNC] * (x.ndim - 1)), axis))


def replicate_dim(x, dim: int):
    spec = [UNC] * x.ndim
    spec[dim] = None
    return constrain(x, P(*spec))


def shard_dim(x, dim: int, axis):
    spec = [UNC] * x.ndim
    spec[dim % x.ndim] = axis
    return constrain(x, P(*spec))


def shard_activation(x, *, sequence_parallel: bool = False, batch_dim: int = 0, seq_dim: int = 1):
    """Canonical activation sharding for (batch, seq, hidden...)-shaped tensors:
    batch over dp, sequence over cp (plus tp when Megatron-SP is active)."""
    spec = [UNC] * x.ndim
    spec[batch_dim] = mesh_lib.DATA_AXES
    if sequence_parallel:
        spec[seq_dim] = (mesh_lib.CP_AXIS, mesh_lib.TP_AXIS)
    else:
        spec[seq_dim] = mesh_lib.CP_AXIS
    return constrain(x, P(*spec))


def param_partition_specs(variables) -> Any:
    """Pytree of PartitionSpecs from flax ``nn.Partitioned`` metadata
    (unannotated leaves → fully replicated P())."""
    return nn.get_partition_spec(variables)


def param_shardings(variables) -> Any:
    """Pytree of NamedShardings over the global mesh for a variables pytree."""
    mesh = mesh_lib.get_mesh()
    specs = nn.get_partition_spec(variables)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def logical_to_mesh(*names):
    """Helper for ``nn.with_partitioning`` axis tuples: passthrough today (we
    name mesh axes directly), kept as the single place to add a logical-axis
    indirection later."""
    return tuple(names)


# --- serving partitioner (ISSUE 14) -------------------------------------------
#
# The T5X pattern (SNIPPETS.md [3]): AXIS RULES own the sharding, model code
# does not. The parallel layers already attach the rules as nn.Partitioned
# metadata (mesh axis names on each kernel dim), so the serving partitioner's
# job is mechanical: read the metadata off the params tree, sanitize it
# against the live mesh (a dim an axis cannot divide falls back to
# replicated — GQA kv heads under tp > hkv, tiny vocab under big tp), and
# place every engine-owned tree — params, slot state, the KV pool — with an
# explicit committed NamedSharding so the donated hot-path programs keep one
# stable layout for the engine's whole life. jit then partitions every
# program (prefill buckets, the fused decode/spec chunks, slot write/clear,
# paged admit/seed) off the placed operands plus the layers' activation
# constraints; nothing about the programs themselves changes, which is why
# ``decode_compilations`` stays 1 and streams stay bit-identical to the
# mesh-free engine on the CPU mesh proxy.


def serving_mesh(tp: int, devices=None):
    """Initialize (or validate) the tp-only serving mesh: ``tp`` devices on
    the TP axis, every other axis 1. Reuses an already-initialized global
    mesh when its tp degree matches (two engines, one mesh); a mismatched
    live mesh is an error — serving and training cannot share a process
    with different tp without explicit teardown."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if mesh_lib.model_parallel_is_initialized():
        have = mesh_lib.get_tensor_model_parallel_size()
        if have != tp:
            raise ValueError(
                f"model-parallel state already initialized with tp={have}; "
                f"cannot build a tp={tp} serving mesh without "
                "destroy_model_parallel() first"
            )
        return mesh_lib.get_parallel_state()
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices, have {len(devices)} — on CPU "
            "hosts set --xla_force_host_platform_device_count (the "
            "dryrun_multichip fan-out) before jax initializes"
        )
    return mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=devices[:tp]
    )


class ServingPartitioner:
    """Placement policy for a TP-sharded serving engine over the global
    mesh: params by their ``nn.Partitioned`` axis rules, KV trees on the
    kv-head axis, everything else replicated."""

    def __init__(self, state=None):
        self.state = state if state is not None else mesh_lib.get_parallel_state()
        self.mesh = self.state.mesh
        self.tp = int(self.mesh.shape[mesh_lib.TP_AXIS])

    # --- spec plumbing ------------------------------------------------------

    def _axis_size(self, entry) -> int:
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for name in names:
            n *= int(self.mesh.shape[name])
        return n

    def _fit_spec(self, spec: P, shape) -> P:
        """Drop spec entries whose mesh extent cannot divide the dim —
        the rule sanitation that keeps GQA/odd-vocab layouts legal
        (replicated) instead of erroring at placement."""
        entries = list(spec) + [None] * (len(shape) - len(spec))
        out = []
        for dim, entry in zip(shape, entries):
            if entry is None or entry is UNC:
                out.append(None)
                continue
            size = self._axis_size(entry)
            out.append(entry if size > 1 and dim % size == 0 else None)
        # trim trailing Nones: P(None, None, 'tp') and P(None, None, 'tp',
        # None) are the same sharding, but the jit cache keys on the spec
        # shape — a mismatch against XLA's (trimmed) output specs would
        # recompile the decode chunk on its second dispatch
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    # --- params -------------------------------------------------------------

    def param_shardings(self, params):
        """``(unboxed values, NamedSharding tree)`` for a params pytree per
        its ``nn.Partitioned`` metadata. Unannotated leaves replicate. Needs
        only shapes, so it also serves abstract (``ShapeDtypeStruct``)
        trees — the compile-without-a-chip rehearsal."""
        from flax.core import meta

        specs = nn.get_partition_spec(params)
        values = meta.unbox(params)
        leaves, treedef = jax.tree_util.tree_flatten(values)
        spec_leaves = treedef.flatten_up_to(specs)
        shardings = [
            NamedSharding(
                self.mesh,
                self._fit_spec(spec if isinstance(spec, P) else P(), leaf.shape),
            )
            for leaf, spec in zip(leaves, spec_leaves)
        ]
        return values, jax.tree_util.tree_unflatten(treedef, shardings)

    def shard_params(self, params):
        """Place a params pytree per :meth:`param_shardings` (boxed trees
        are unboxed — the metadata has done its job once the placement is
        committed)."""
        return jax.device_put(*self.param_shardings(params))

    # --- KV / state ---------------------------------------------------------

    def kv_spec(self, name: str, ndim: int) -> P:
        """PartitionSpec for one cache-collection leaf: k/v pages and rows
        (and their quantized scale siblings) shard the kv-head axis —
        always at ``ndim - 2`` in every layout this repo speaks (row
        (..., B, L, Hkv, D), pool (..., P, ps, Hkv, D), scales
        (..., P, 1, Hkv, 1)) — over tp; bookkeeping leaves (kv_valid,
        index) replicate."""
        from neuronx_distributed_tpu.modules.attention import pool_scale_base

        base = pool_scale_base(name) or name
        if base in ("k", "v") and ndim >= 2:
            spec = [None] * ndim
            spec[ndim - 2] = mesh_lib.TP_AXIS
            return P(*spec)
        return P()

    def kv_shardings(self, tree):
        """NamedSharding tree for a cache collection (row layout or paged
        pool pytree): kv-head-axis sharding where it divides, replicated
        elsewhere. Needs only shapes."""
        from neuronx_distributed_tpu.modules.attention import cache_leaf_name

        def sharding(path, leaf):
            spec = self._fit_spec(
                self.kv_spec(cache_leaf_name(path), leaf.ndim), leaf.shape
            )
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(sharding, tree)

    def place_kv(self, tree):
        """Commit a cache collection to the mesh per :meth:`kv_shardings`.
        Applied once at allocation — the donated programs then keep the
        layout for free."""
        return jax.device_put(tree, self.kv_shardings(tree))

    def replicate(self, tree):
        """Commit a pytree fully replicated over the mesh (slot state,
        block tables — the host-authoritative leaves every rank needs)."""
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), tree)
