"""Content fingerprints — the one owner of every integrity hash in the repo.

Three families, three trust boundaries:

* **Host bytes** (:func:`page_fingerprint`, :func:`bytes_fingerprint`) —
  CRC-32 over raw host bytes. Used by the host page tier
  (``serving/tiering.py``, extracted from there so spilled pages hashed
  before the refactor still validate byte-identically) and by checkpoint
  shard digests (``trainer/checkpoint.py`` manifests). Pure host numpy /
  zlib; never touches a device.

* **Device trees** (:func:`tree_fingerprint`) — a jittable bit-level
  reduction over every leaf of a pytree, returning ONE uint32 scalar.
  Each leaf is bitcast to a same-width unsigned integer view (64-bit
  folds high^low so no bit is dropped), widened to uint32, multiplied by
  odd position weights ``2*i + 1`` (so a flipped bit at position i and a
  swapped pair of elements both move the hash), and summed with natural
  uint32 wraparound. Leaves combine order-sensitively via
  ``total * PRIME + leaf``. Under GSPMD the sharded dims of a leaf are
  reduced with intra-replica collectives only — a *replicated* leaf is
  reduced locally per device with NO cross-replica traffic, so the
  "replicated" output scalar's physical per-device copies diverge exactly
  when one device's copy of the data diverges. The SDC sentinel's
  cross-replica vote (``integrity/voting.py``) is built on that property.

* **Device cache prefixes** (:func:`cache_fingerprint`,
  :func:`pool_pages_fingerprint`) — the serving engine's prefix-reuse
  validation. ``cache_fingerprint`` is the float32 position-weighted
  reduction the dense prefix cache has always used (moved here from
  ``modules/attention.py``, which re-exports it); ``pool_pages_fingerprint``
  extends the same idea to the paged pool: one uint32 fingerprint PER
  page id, so a reuse can validate exactly the page prefix it maps.

None of these are cryptographic: they detect corruption (bit flips, rot,
chaos poison), not adversaries.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "page_fingerprint",
    "bytes_fingerprint",
    "tree_fingerprint",
    "cache_fingerprint",
    "pool_pages_fingerprint",
    "FINGERPRINT_SEED",
    "FINGERPRINT_PRIME",
]

# FNV-ish mixing constants; the exact values only matter in that they are
# odd (bijective as uint32 multipliers) and pinned forever — fingerprints
# are persisted in checkpoint manifests and compared across processes.
FINGERPRINT_SEED = 0x9E3779B9
FINGERPRINT_PRIME = 0x01000193


# --- host bytes (CRC-32) ------------------------------------------------------


def page_fingerprint(blocks) -> int:
    """CRC-32 chained over a spilled page's per-leaf blocks in storage
    order (the flatten order is deterministic for a fixed pool layout, so
    the same bytes always hash the same). ``blocks`` is the host tier's
    ``[(path_keys, np block)]`` page representation."""
    fp = 0
    for _, block in blocks:
        fp = zlib.crc32(np.ascontiguousarray(block).tobytes(), fp)
    return fp


def bytes_fingerprint(data: bytes, fp: int = 0) -> int:
    """CRC-32 of raw bytes, chainable (pass the previous value as ``fp``)
    so large checkpoint shards can be digested in bounded-memory chunks."""
    return zlib.crc32(data, fp)


# --- device trees (jittable uint32 bit-mix) -----------------------------------


def _uint32_bits(x):
    """Same-shape uint32 view of a leaf's BITS (not its values): bitcast
    to the same-width unsigned type, fold 64-bit high^low, widen. Exact —
    every flipped bit changes the result."""
    x = jnp.asarray(x)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    nbits = np.dtype(x.dtype).itemsize * 8  # host metadata, not a sync
    if nbits == 64:
        u = jax.lax.bitcast_convert_type(x, jnp.uint64)
        return ((u >> 32) ^ u).astype(jnp.uint32)
    if nbits == 32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if nbits == 16:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)


def _leaf_fingerprint(leaf):
    flat = _uint32_bits(leaf).reshape(-1)
    # odd weights make the mix position-sensitive (a swap changes the sum)
    # while staying a pure elementwise-multiply + wrapping sum — the whole
    # leaf reduces in one pass with no host interaction
    w = (jnp.arange(flat.shape[0], dtype=jnp.uint32) << 1) | jnp.uint32(1)
    return jnp.sum(flat * w, dtype=jnp.uint32)


def tree_fingerprint(tree):
    """One uint32 scalar over every leaf of ``tree``. Jit this (the
    sentinel and the serving probe each wrap it once); tracing order is
    the deterministic pytree flatten order, so the same tree always
    produces the same program and the same value."""
    total = jnp.uint32(FINGERPRINT_SEED)
    for leaf in jax.tree_util.tree_leaves(tree):
        total = total * jnp.uint32(FINGERPRINT_PRIME) + _leaf_fingerprint(leaf)
    return total


# --- device cache prefixes ----------------------------------------------------


def cache_fingerprint(cache):
    """Cheap integrity fingerprint of a cache(-prefix) tree: a float32
    reduction over every leaf, position-weighted along the column axis so a
    corrupted element OR a shifted block changes the value. Recomputed on
    the same data by the same program it is bit-deterministic, so the
    serving engine's prefix-reuse validation compares it with exact float
    equality — this is corruption detection (bit flips, injected poison),
    not cryptographic integrity."""
    from neuronx_distributed_tpu.modules.attention import (
        cache_leaf_name,
        cache_length_axis,
    )

    total = jnp.zeros((), jnp.float32)
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    for path, leaf in flat:
        name = cache_leaf_name(path)
        col = cache_length_axis(name, leaf.ndim)
        x = jnp.abs(leaf.astype(jnp.float32)) if jnp.issubdtype(
            leaf.dtype, jnp.floating
        ) else leaf.astype(jnp.float32)
        if col is not None:  # a cursor or a per-slot state leaf has no column
            shape = [1] * leaf.ndim
            shape[col] = leaf.shape[col]
            w = (1.0 + jnp.arange(leaf.shape[col], dtype=jnp.float32)).reshape(shape)
            x = x * w
        total = total + jnp.sum(x)
    return total


def pool_pages_fingerprint(pool_tree, page_ids):
    """Per-page uint32 fingerprints of the KV pool pages at ``page_ids``
    (int32 vector): gathers each PAGE-CARRYING pool leaf's pages along its
    page axis (``ndim - 4``, the pool storage convention — k/v blocks and
    their quantized scale siblings; ``kv_valid``/cursor leaves are
    slot-shaped, not page-shaped, and are skipped), bit-mixes every page's
    content independently, and combines leaves order-sensitively — the
    paged twin of :func:`cache_fingerprint`. Jittable; callers pad
    ``page_ids`` to a bucketed length for bounded compiles (a padded slot
    hashes whatever page it aliases; the CALLER masks padded positions
    out of the comparison)."""
    from neuronx_distributed_tpu.modules.attention import (
        PAGED_LEAVES,
        cache_leaf_name,
        pool_scale_base,
    )

    n = page_ids.shape[0]
    total = jnp.full((n,), FINGERPRINT_SEED, jnp.uint32)
    flat_leaves, _ = jax.tree_util.tree_flatten_with_path(pool_tree)
    for path, leaf in flat_leaves:
        name = cache_leaf_name(path)
        if (pool_scale_base(name) or name) not in PAGED_LEAVES:
            continue
        pax = leaf.ndim - 4
        pages = jnp.take(leaf, page_ids, axis=pax)
        flat = _uint32_bits(jnp.moveaxis(pages, pax, 0)).reshape(n, -1)
        w = (jnp.arange(flat.shape[1], dtype=jnp.uint32) << 1) | jnp.uint32(1)
        leaf_fp = jnp.sum(flat * w[None, :], axis=1, dtype=jnp.uint32)
        total = total * jnp.uint32(FINGERPRINT_PRIME) + leaf_fp
    return total
