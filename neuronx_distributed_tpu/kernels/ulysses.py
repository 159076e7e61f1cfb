"""Ulysses-style (DeepSpeed) all-to-all sequence parallelism.

NOT in the reference (SURVEY §2.10: NxD ships Megatron-SP and ring/CP only —
this is a deliberate extra): instead of rotating K/V around a ring, one
all-to-all re-shards activations from sequence-sharded to HEAD-sharded, full
attention runs locally on S with H/cp heads (so the Pallas flash kernel
applies unchanged — no online-softmax merging), and a second all-to-all
restores the sequence sharding.

Communication trade vs ring: Ulysses moves Q, K, V and O once each
(4·B·S·H·D/cp per device, independent of cp), the ring moves K/V cp-1 times;
Ulysses needs cp ≤ kv-heads (heads must split), the ring has no head
constraint. Both live behind ``attention_op``'s ``impl`` switch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.kernels.backend import (
    interpret_mode,
    resolve_attention_impl,
)
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    axis_name: str = mesh_lib.CP_AXIS,
    inner_impl: str = "auto",
) -> jax.Array:
    """Local shards (B, S/cp, H, D) → all-to-all → full-seq attention on H/cp
    heads → all-to-all back. Call inside shard_map with seq over
    ``axis_name``."""
    from neuronx_distributed_tpu.modules.attention import xla_attention

    cp = lax.axis_size(axis_name)
    b, s_loc, h, d = q.shape
    hkv = k.shape[2]

    def scatter_heads(x):
        # (B, S/cp, H, D) --all_to_all--> (B, S, H/cp, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def gather_seq(x):
        # inverse: (B, S, H/cp, D) → (B, S/cp, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    inner_impl = resolve_attention_impl(inner_impl)
    if inner_impl == "flash":
        from neuronx_distributed_tpu.kernels.flash_attention import (
            _flash_attention_bhsd,
            _pick_block,
        )

        # the kernel serves GQA natively — K/V stay at their (scattered)
        # Hkv/cp head count, no HBM replication
        bq = bk = _pick_block(q.shape[1], 512)
        out = _flash_attention_bhsd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), None, None, causal, bq, bk,
            interpret_mode(None),
        )
        out = jnp.swapaxes(out, 1, 2)
    else:
        out = xla_attention(q, k, v, causal=causal)
    return gather_seq(out)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    inner_impl: str = "auto",
) -> jax.Array:
    """Global (B, S, H, D) entry point: shard_map with seq over cp, heads over
    tp (same layout contract as ``ring_attention_sharded``). Falls back to
    the ring formulation when cp does not divide the kv-head count (Ulysses'
    head-split constraint)."""
    from neuronx_distributed_tpu.kernels.ring_attention import (
        ring_attention_sharded,
    )

    if not mesh_lib.model_parallel_is_initialized():
        return ring_attention_sharded(q, k, v, causal)
    mesh = mesh_lib.get_mesh()
    b, s, h, _ = q.shape
    hkv = k.shape[2]
    cp = mesh.shape[mesh_lib.CP_AXIS]
    tp = mesh.shape[mesh_lib.TP_AXIS]
    if cp <= 1:
        return ring_attention_sharded(q, k, v, causal, impl=inner_impl)
    # heads available per cp shard after any tp split
    shard_heads = tp > 1 and h % tp == 0 and hkv % tp == 0
    hkv_local = hkv // tp if shard_heads else hkv
    h_local = h // tp if shard_heads else h
    if s % cp != 0 or hkv_local % cp != 0 or h_local % cp != 0:
        logger.warning(
            "ulysses: cp=%d cannot split heads (h=%d, hkv=%d after tp) or "
            "seq %d; using ring attention", cp, h_local, hkv_local, s,
        )
        return ring_attention_sharded(q, k, v, causal)
    dp = mesh.shape[mesh_lib.EDP_AXIS] * mesh.shape[mesh_lib.EP_AXIS]
    bspec = mesh_lib.DATA_AXES if (dp > 1 and b % dp == 0) else None
    hspec = mesh_lib.TP_AXIS if shard_heads else None
    spec = P(bspec, mesh_lib.CP_AXIS, hspec, None)
    fn = mesh_lib.manual_shard_map(
        partial(
            ulysses_attention, causal=causal, axis_name=mesh_lib.CP_AXIS,
            inner_impl=inner_impl,
        ),
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
