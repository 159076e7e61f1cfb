"""Parallel state for the TPU-native stack: one device mesh instead of process groups.

This is the TPU-first replacement for the reference's
``parallel_layers/parallel_state.py`` (``initialize_model_parallel``
parallel_state.py:343 and the dozens of ``get_*_group/rank/size`` getters). The
reference builds torch.distributed process groups from a rank-array reshape
``[PP, DP, CP, TP]`` (worked examples at parallel_state.py:351-504) and a second
expert view ``[PP, DPexp, EP, TP]`` (parallel_state.py:372-382). On TPU with
single-controller JAX the same structure is ONE ``jax.sharding.Mesh`` with named
axes ``("pp", "edp", "ep", "cp", "tp")`` — the reference's data-parallel
dimension is the combined ``("edp", "ep")`` pair (:data:`DATA_AXES`), and its
expert-view reshape [PP, DPexp, EP, TP] is simply the same mesh addressed by the
``ep`` axis. "Groups" become mesh axes, group collectives become
``lax.psum/all_gather/psum_scatter/all_to_all/ppermute`` with an ``axis_name``,
and XLA lowers them onto ICI. Keeping every strategy in one mesh (rather than a
second reshaped Mesh object) is what lets expert weights shard over ``ep``
inside the same jit as everything else — GSPMD requires a single mesh per
program.

What intentionally disappears relative to the reference:
  * process-group bootstrap / dummy warm-up all-reduce (parallel_state.py:597-607)
    — jit handles program loading;
  * replica-group compression, TCP store, gloo side channels — no processes;
  * LOGIC1/LOGIC2 topology rank orderings (parallel_state.py:102,173) — subsumed
    by ``mesh_utils.create_device_mesh`` which maps the mesh onto the physical
    ICI torus (minor-most axis gets nearest neighbours, so keep "tp" last);
  * KV-replication groups (parallel_state.py:1368) — handled at the layer level
    by weight replication in `modules/qkv_linear.py`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# Canonical mesh axis names. Order matters: minor-most (last) axis maps to the
# closest ICI neighbours, so tensor parallelism — the most latency-sensitive
# collective traffic — stays innermost, mirroring the reference's rank grid
# [PP, DP, CP, TP] with TP fastest-varying (parallel_state.py:351-504). The
# data-parallel dimension is split into (edp, ep) so expert weights can shard
# over ep within the same mesh; non-expert code addresses "dp" as the combined
# DATA_AXES tuple (PartitionSpec entries accept axis tuples).
PP_AXIS = "pp"
EDP_AXIS = "edp"
EP_AXIS = "ep"
CP_AXIS = "cp"
TP_AXIS = "tp"
# The reference's DP dimension, as a spec entry: P(DATA_AXES, ...) shards a dim
# over edp×ep jointly.
DATA_AXES = (EDP_AXIS, EP_AXIS)

MESH_AXES = (PP_AXIS, EDP_AXIS, EP_AXIS, CP_AXIS, TP_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Degrees of every parallelism strategy. ``data_parallel_size`` is inferred
    from the device count when None (reference: parallel_state.py:530)."""

    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    context_parallel_size: int = 1
    expert_parallel_size: int = 1
    data_parallel_size: Optional[int] = None

    def infer_dp(self, n_devices: int) -> int:
        denom = (
            self.tensor_parallel_size
            * self.pipeline_parallel_size
            * self.context_parallel_size
        )
        if n_devices % denom != 0:
            raise ValueError(
                f"world size {n_devices} not divisible by "
                f"tp*pp*cp = {denom} "
                f"(tp={self.tensor_parallel_size}, pp={self.pipeline_parallel_size}, "
                f"cp={self.context_parallel_size})"
            )
        dp = n_devices // denom
        if self.data_parallel_size is not None and self.data_parallel_size != dp:
            raise ValueError(
                f"explicit data_parallel_size={self.data_parallel_size} inconsistent "
                f"with inferred {dp} for world size {n_devices}"
            )
        return dp


@dataclasses.dataclass
class ParallelState:
    """Holds the live mesh. Built by :func:`initialize_model_parallel`."""

    config: MeshConfig
    mesh: Mesh  # axes (pp, edp, ep, cp, tp)
    aot_mode: bool = False

    @property
    def expert_mesh(self) -> Mesh:
        """Same mesh — the expert view is the ep axis of the primary mesh (the
        reference's second rank grid [PP, DPexp, EP, TP],
        parallel_state.py:372-382, needs no second object here)."""
        return self.mesh

    @property
    def world_size(self) -> int:
        return int(np.prod(tuple(self.mesh.shape.values())))


_STATE: Optional[ParallelState] = None


def _build_device_grid(
    shape: Sequence[int], devices: Optional[Sequence[jax.Device]]
) -> np.ndarray:
    """Arrange devices into the (pp, edp, ep, cp, tp) grid, topology-aware when possible.

    ``mesh_utils.create_device_mesh`` plays the role of the reference's LOGIC1/
    LOGIC2 ring orderings (parallel_state.py:102,173,293): it permutes devices so
    that minor mesh axes land on physically adjacent chips of the ICI torus.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod(shape))
    if n != len(devices):
        raise ValueError(f"mesh shape {tuple(shape)} needs {n} devices, have {len(devices)}")
    if devices and getattr(devices[0], "platform", "") == "tpu":
        # on the chip a failed topology-aware mesh is a fault, not a cue to
        # reshape in enumeration order (tp would silently leave its nearest
        # ICI neighbours) — let it raise
        from jax.experimental import mesh_utils

        return mesh_utils.create_device_mesh(tuple(shape), devices=devices)
    # virtual / CPU device sets carry no topology to map onto
    return np.asarray(devices, dtype=object).reshape(tuple(shape))


def _build_hybrid_device_grid(
    ici_shape: Sequence[int], dcn_shape: Sequence[int],
    devices: Optional[Sequence[jax.Device]],
) -> np.ndarray:
    """Two-level mesh for multi-slice TPU: per-axis ICI extent × DCN extent
    (``mesh_utils.create_hybrid_device_mesh``). On TPU a failure here is a
    real multi-slice misconfiguration and aborts; only non-TPU device sets
    (CPU test meshes, whose devices carry no slice topology) fall back to the
    single-level grid builder — note the fallback's enumeration-order reshape
    puts NO particular axis on the process boundary."""
    devices = list(devices if devices is not None else jax.devices())
    shape = tuple(i * d for i, d in zip(ici_shape, dcn_shape))
    try:
        from jax.experimental import mesh_utils

        return mesh_utils.create_hybrid_device_mesh(
            tuple(ici_shape), tuple(dcn_shape), devices=devices
        )
    except Exception as e:
        if devices and getattr(devices[0], "platform", "") == "tpu":
            raise  # silent degradation would put tp/pp collectives on DCN
        logger.warning(
            "hybrid (ICI×DCN) device mesh unavailable (%s); using the "
            "single-level grid builder", e,
        )
        return _build_device_grid(shape, devices)


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    context_parallel_size: int = 1,
    expert_model_parallel_size: int = 1,
    data_parallel_size: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    aot_mode: bool = False,
    dcn_data_parallel_size: int = 1,
) -> ParallelState:
    """Build the global mesh state (reference: parallel_state.py:343).

    Keyword names mirror the reference API so users can port call sites
    mechanically. Returns the new :class:`ParallelState` and installs it
    globally for the getter functions below.

    Multi-slice / multi-host: call ``jax.distributed.initialize()`` first so
    ``jax.devices()`` spans all hosts, then set ``dcn_data_parallel_size`` to
    the slice count — the (expert-)data-parallel dimension splits into
    ``dcn × ici`` and the mesh is built with
    ``mesh_utils.create_hybrid_device_mesh`` so ONLY the data-parallel
    gradient reduction crosses DCN while tp/cp/pp/ep collectives stay on ICI
    (the reference reaches multi-node the same way: DP gradient buckets over
    EFA, model parallelism inside the node).
    """
    global _STATE
    if _STATE is not None:
        raise RuntimeError(
            "model parallel state already initialized; call destroy_model_parallel() first"
        )
    cfg = MeshConfig(
        tensor_parallel_size=tensor_model_parallel_size,
        pipeline_parallel_size=pipeline_model_parallel_size,
        context_parallel_size=context_parallel_size,
        expert_parallel_size=expert_model_parallel_size,
        data_parallel_size=data_parallel_size,
    )
    devices = list(devices if devices is not None else jax.devices())
    dp = cfg.infer_dp(len(devices))
    pp, cp, tp, ep = (
        cfg.pipeline_parallel_size,
        cfg.context_parallel_size,
        cfg.tensor_parallel_size,
        cfg.expert_parallel_size,
    )
    if dp % ep != 0:
        raise ValueError(
            f"expert_parallel_size={ep} must divide dp={dp} "
            "(the dp dimension is split into edp×ep; the reference allows ep "
            "over dp×cp — here cp stays a separate mesh axis, so use cp=1 "
            "when ep should span it)"
        )
    edp = dp // ep

    if dcn_data_parallel_size > 1:
        if edp % dcn_data_parallel_size != 0:
            raise ValueError(
                f"dcn_data_parallel_size={dcn_data_parallel_size} must divide "
                f"the expert-data-parallel dimension edp={edp}"
            )
        grid = _build_hybrid_device_grid(
            ici_shape=(pp, edp // dcn_data_parallel_size, ep, cp, tp),
            dcn_shape=(1, dcn_data_parallel_size, 1, 1, 1),
            devices=devices,
        )
    else:
        grid = _build_device_grid((pp, edp, ep, cp, tp), devices)
    mesh = Mesh(grid, MESH_AXES)

    _STATE = ParallelState(config=cfg, mesh=mesh, aot_mode=aot_mode)
    logger.info(
        "initialized model parallel: pp=%d dp=%d cp=%d tp=%d ep=%d edp=%d over %d devices",
        pp, dp, cp, tp, ep, edp, len(devices),
    )
    return _STATE


def model_parallel_is_initialized() -> bool:
    return _STATE is not None


def destroy_model_parallel() -> None:
    global _STATE
    _STATE = None


def get_parallel_state() -> ParallelState:
    if _STATE is None:
        raise RuntimeError(
            "model parallel not initialized; call initialize_model_parallel() first"
        )
    return _STATE


def get_mesh() -> Mesh:
    return get_parallel_state().mesh


def get_expert_mesh() -> Mesh:
    return get_parallel_state().expert_mesh


# --- size getters (reference get_*_size; sizes are static mesh properties) ----

def get_world_size() -> int:
    return get_parallel_state().world_size


def get_tensor_model_parallel_size() -> int:
    return get_mesh().shape[TP_AXIS]


def get_pipeline_model_parallel_size() -> int:
    return get_mesh().shape[PP_AXIS]


def get_data_parallel_size() -> int:
    m = get_mesh()
    return m.shape[EDP_AXIS] * m.shape[EP_AXIS]


def get_context_parallel_size() -> int:
    return get_mesh().shape[CP_AXIS]


def get_expert_model_parallel_size() -> int:
    return get_mesh().shape[EP_AXIS]


def get_expert_data_parallel_size() -> int:
    """Replication degree of each expert shard (reference edp = dp*cp/ep,
    parallel_state.py:372-382; here = edp×cp since cp is a separate axis)."""
    m = get_mesh()
    return m.shape[EDP_AXIS] * m.shape[CP_AXIS]


# --- rank getters (meaningful only inside shard_map'ed code) ------------------

def _axis_rank(axis: str):
    return jax.lax.axis_index(axis)


def get_tensor_model_parallel_rank():
    """Rank along the tp axis. Only valid inside ``shard_map`` (single-controller
    JAX has no per-process rank; reference per-process getter:
    parallel_state.py rank getters)."""
    return _axis_rank(TP_AXIS)


def get_pipeline_model_parallel_rank():
    return _axis_rank(PP_AXIS)


def get_data_parallel_rank():
    return _axis_rank(EDP_AXIS) * jax.lax.axis_size(EP_AXIS) + _axis_rank(EP_AXIS)


def get_context_parallel_rank():
    return _axis_rank(CP_AXIS)


def get_expert_model_parallel_rank():
    return _axis_rank(EP_AXIS)


# --- sharding helpers ---------------------------------------------------------

def named_sharding(*spec) -> NamedSharding:
    """NamedSharding over the global mesh for the given PartitionSpec entries."""
    return NamedSharding(get_mesh(), P(*spec))


def zero1_sharding_axes() -> tuple:
    """Axes over which ZeRO-1 optimizer state is sharded: DP×CP, matching the
    reference's zero-1 sharding groups (parallel_state.py:1579). DP here is the
    (edp, ep) pair."""
    return (EDP_AXIS, EP_AXIS, CP_AXIS)


def get_context_parallel_ring(forward: bool = True):
    """Source/target pairs for ring attention over the cp axis, replacing the
    reference's NKI ``CollectivesConfig`` src/tgt derivation
    (parallel_state.py:16,678-690). Returns a ppermute-style permutation list."""
    cp = get_context_parallel_size()
    if forward:
        return [(i, (i + 1) % cp) for i in range(cp)]
    return [(i, (i - 1) % cp) for i in range(cp)]


def mesh_device_counts() -> dict:
    m = get_mesh()
    return {k: int(v) for k, v in m.shape.items()}


def ctx_abstract_mesh():
    """The tracing context's AbstractMesh (empty at top level). Every caller
    branches on ``.empty`` and only touches ``manual_axes``/
    ``are_all_axes_auto`` on a non-empty mesh."""
    return jax.sharding.get_abstract_mesh()


def compat_shard_map(fn, mesh, in_specs, out_specs, axis_names=None,
                     check_vma=False):
    """``jax.shard_map`` with this repo's defaults: ``check_vma`` off (the
    collective-reduction outputs of the explicit-SPMD regions do not carry
    varying-manual-axes types) and ``axis_names`` (the claimed manual axes)
    passed only when given. Every explicit-SPMD region in the repo routes
    through here or :func:`manual_shard_map` (graftlint GL04)."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return jax.shard_map(fn, **kwargs)


def manual_shard_map(fn, in_specs, out_specs):
    """``jax.shard_map`` over the global mesh claiming EVERY mesh axis not
    already manual in the tracing context.

    This is the one correct way to drop into explicit-SPMD from GSPMD code
    here: Mosaic custom calls (Pallas kernels, grouped matmuls) require all
    axes manual, and when tracing inside another partial-manual shard_map
    (e.g. the pipeline engine's pp region) the nested call must bind the
    context's AbstractMesh with only the remaining axes. Shared by the flash
    and ring attention wrappers, blockwise MoE, and the distributed topk.
    """
    mesh = get_mesh()
    ctx_mesh = ctx_abstract_mesh()
    target = mesh if ctx_mesh.empty else ctx_mesh
    already_manual = set() if ctx_mesh.empty else set(ctx_mesh.manual_axes)
    # The jit wrapper is load-bearing twice over: (a) the eager shard_map
    # impl cannot execute partial-manual specs, and (b) when NESTED inside
    # another manual region (pipeline pp), an un-jitted shard_map body's
    # ``lax.axis_index`` lowers into a manual_computation that re-binds the
    # PARENT's axes — "operates on axis 'pp' which is already bound" (hit by
    # cp×pp ring attention, round 5). Under an outer jit this inlines.
    return jax.jit(
        compat_shard_map(
            fn,
            mesh=target,
            in_specs=in_specs,
            out_specs=out_specs,
            axis_names=set(target.axis_names) - already_manual,
            check_vma=False,
        )
    )
