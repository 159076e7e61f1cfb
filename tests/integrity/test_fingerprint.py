"""utils/fingerprint — the one owner of every integrity hash.

Pins three contracts: (a) the CRC family is byte-identical to the
pre-refactor inline math (spilled pages and checkpoint digests persist
across processes, so the exact value is an interface), (b) the device
tree fingerprint is bit-sensitive, position-sensitive, and deterministic
across dtypes, (c) the per-page pool fingerprint isolates corruption to
the page that holds it and is prefix-stable (a reuse validates exactly
the pages it maps, no matter how the id vector was bucketed)."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.utils.fingerprint import (
    FINGERPRINT_PRIME,
    FINGERPRINT_SEED,
    bytes_fingerprint,
    page_fingerprint,
    pool_pages_fingerprint,
    tree_fingerprint,
)


# --- CRC family (host bytes) --------------------------------------------------


def test_page_fingerprint_is_the_pre_refactor_crc_chain():
    """Byte-identical pin: the extracted helper must produce EXACTLY the
    chained ``zlib.crc32`` the host tier computed inline before the
    refactor — pages spilled by an old build still validate."""
    rng = np.random.default_rng(0)
    blocks = [
        (("k",), rng.standard_normal((2, 3, 4)).astype(np.float32)),
        (("v",), rng.integers(0, 255, (5,), dtype=np.uint8)),
        (("k_scale",), rng.standard_normal((2, 1)).astype(np.float16)),
    ]
    expected = 0
    for _, block in blocks:
        expected = zlib.crc32(np.ascontiguousarray(block).tobytes(), expected)
    assert page_fingerprint(blocks) == expected


def test_page_fingerprint_orders_and_detects_flips():
    a = np.arange(8, dtype=np.float32)
    b = np.arange(8, 16, dtype=np.float32)
    assert page_fingerprint([((), a), ((), b)]) != page_fingerprint(
        [((), b), ((), a)]
    )
    raw = bytearray(a.tobytes())
    raw[0] ^= 0x01
    flipped = np.frombuffer(bytes(raw), dtype=np.float32)
    assert page_fingerprint([((), a)]) != page_fingerprint([((), flipped)])


def test_bytes_fingerprint_chains_like_crc32():
    data = b"shard-bytes" * 100
    assert bytes_fingerprint(data) == zlib.crc32(data)
    # chunked digest == whole-buffer digest (bounded-memory shard walks)
    fp = 0
    for i in range(0, len(data), 64):
        fp = bytes_fingerprint(data[i : i + 64], fp)
    assert fp == zlib.crc32(data)


# --- device tree fingerprint --------------------------------------------------


def _tree():
    rng = np.random.default_rng(7)
    return {
        "w": jnp.asarray(rng.standard_normal((4, 8)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((8,)).astype(np.float32)),
        "h": jnp.asarray(rng.standard_normal((3, 3)).astype(jnp.bfloat16)),
        "i": jnp.asarray(rng.integers(-5, 5, (6,), dtype=np.int32)),
        "q": jnp.asarray(rng.integers(0, 255, (4,), dtype=np.uint8)),
        "m": jnp.asarray([True, False, True]),
    }


def test_tree_fingerprint_deterministic_uint32():
    fp1 = jax.jit(tree_fingerprint)(_tree())
    fp2 = jax.jit(tree_fingerprint)(_tree())
    assert fp1.dtype == jnp.uint32 and fp1.shape == ()
    assert int(fp1) == int(fp2)


@pytest.mark.parametrize("leaf", ["w", "h", "i", "q", "m"])
def test_tree_fingerprint_sees_one_flipped_bit(leaf):
    """The least significant bit of one element — the corruption no
    loss/grad-norm guard ever sees — must change the fingerprint, in
    every dtype family the TrainState can hold."""
    from neuronx_distributed_tpu.integrity.chaos import flip_array_bit

    t = _tree()
    clean = int(jax.jit(tree_fingerprint)(t))
    host = np.asarray(t[leaf])
    t[leaf] = jnp.asarray(
        flip_array_bit(host), dtype=t[leaf].dtype
    ).reshape(t[leaf].shape)
    assert int(jax.jit(tree_fingerprint)(t)) != clean


def test_tree_fingerprint_position_sensitive():
    a = {"x": jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)}
    b = {"x": jnp.asarray([2.0, 1.0, 3.0, 4.0], jnp.float32)}
    assert int(tree_fingerprint(a)) != int(tree_fingerprint(b))


def test_tree_fingerprint_leaf_order_sensitive():
    # same leaves, swapped names → different combine order → different fp
    x = jnp.asarray([1.0, 2.0], jnp.float32)
    y = jnp.asarray([3.0, 4.0], jnp.float32)
    assert int(tree_fingerprint({"a": x, "b": y})) != int(
        tree_fingerprint({"a": y, "b": x})
    )


def test_tree_fingerprint_64bit_folds_high_and_low():
    """Both halves of a 64-bit word are live: a flip in the high 32 bits
    (dropped by a naive truncation) changes the fingerprint."""
    import jax.experimental

    with jax.enable_x64(True):
        base = np.arange(4, dtype=np.int64)
        high = base.copy()
        high[0] ^= 1 << 40
        low = base.copy()
        low[0] ^= 1
        fp = lambda a: int(tree_fingerprint({"x": jnp.asarray(a)}))
        assert fp(base) != fp(high)
        assert fp(base) != fp(low)


def test_empty_tree_is_seed():
    assert int(tree_fingerprint({})) == FINGERPRINT_SEED
    assert FINGERPRINT_SEED % 2 == 1 and FINGERPRINT_PRIME % 2 == 1


# --- per-page pool fingerprints -----------------------------------------------


def _pool(quantized=False, pages=6, page=4, heads=2, dim=3):
    rng = np.random.default_rng(11)
    pool = {
        "k": jnp.asarray(
            rng.standard_normal((pages, page, heads, dim)).astype(np.float32)
        ),
        "v": jnp.asarray(
            rng.standard_normal((pages, page, heads, dim)).astype(np.float32)
        ),
        # slot-shaped (NOT page-shaped) leaves ride along in real pools —
        # the fingerprint walker must skip them, not gather on ndim-4
        "kv_valid": jnp.zeros((8, 16), jnp.bool_),
    }
    if quantized:
        pool["k_scale"] = jnp.asarray(
            rng.standard_normal((pages, page, heads, 1)).astype(np.float32)
        )
    return pool


def test_pool_pages_fingerprint_per_page_isolation():
    pool = _pool()
    ids = jnp.asarray([0, 2, 4], jnp.int32)
    clean = np.asarray(jax.jit(pool_pages_fingerprint)(pool, ids))
    assert clean.shape == (3,) and clean.dtype == np.uint32

    # flip one bit inside page 2 → ONLY its position changes
    host = np.asarray(pool["k"])
    raw = bytearray(host[2].tobytes())
    raw[0] ^= 0x01
    host = host.copy()
    host[2] = np.frombuffer(bytes(raw), dtype=host.dtype).reshape(host[2].shape)
    corrupt = dict(pool, k=jnp.asarray(host))
    after = np.asarray(jax.jit(pool_pages_fingerprint)(corrupt, ids))
    assert after[1] != clean[1]
    assert after[0] == clean[0] and after[2] == clean[2]


def test_pool_pages_fingerprint_prefix_stable():
    """Bucketed callers pad the id vector; positions covering the same
    pages must hash the same regardless of what follows them."""
    pool = _pool()
    short = np.asarray(pool_pages_fingerprint(pool, jnp.asarray([1, 3], jnp.int32)))
    padded = np.asarray(
        pool_pages_fingerprint(pool, jnp.asarray([1, 3, 0, 0], jnp.int32))
    )
    np.testing.assert_array_equal(short, padded[:2])


def test_pool_pages_fingerprint_covers_scale_siblings():
    pool = _pool(quantized=True)
    ids = jnp.asarray([1], jnp.int32)
    clean = np.asarray(pool_pages_fingerprint(pool, ids))
    host = np.asarray(pool["k_scale"]).copy()
    raw = bytearray(host[1].tobytes())
    raw[0] ^= 0x01
    host[1] = np.frombuffer(bytes(raw), dtype=host.dtype).reshape(host[1].shape)
    after = np.asarray(
        pool_pages_fingerprint(dict(pool, k_scale=jnp.asarray(host)), ids)
    )
    assert after[0] != clean[0]


def test_pool_pages_fingerprint_ignores_slot_leaves():
    pool = _pool()
    ids = jnp.asarray([0, 1], jnp.int32)
    clean = np.asarray(pool_pages_fingerprint(pool, ids))
    # corrupt kv_valid wholesale: page fingerprints must not move
    after = np.asarray(
        pool_pages_fingerprint(
            dict(pool, kv_valid=jnp.ones((8, 16), jnp.bool_)), ids
        )
    )
    np.testing.assert_array_equal(clean, after)


def test_cache_fingerprint_reexport_unchanged():
    """modules.attention keeps its historical cache_fingerprint name as a
    delegating wrapper — the serving engine's dense prefix validation
    keeps its import path AND its values."""
    from neuronx_distributed_tpu.modules import attention

    from neuronx_distributed_tpu.utils import fingerprint as fp

    cache = {"k": jnp.ones((1, 2, 3, 4), jnp.float32) * 0.25,
             "index": jnp.asarray([2], jnp.int32)}
    assert float(attention.cache_fingerprint(cache)) == float(
        fp.cache_fingerprint(cache)
    )
