"""Flash-decode kernel vs the einsum decode golden (interpret mode on CPU).
The golden is ``decode_attention`` — the _block_attn einsum path serving
decode today (modules/attention.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.flash_decode import flash_decode_attention
from neuronx_distributed_tpu.modules.attention import decode_attention
from tests.kernels import page_runs

B, L, D = 2, 256, 32


def _setup(key, s, h, hkv, idx):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, s, h, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, L, hkv, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, L, hkv, D), jnp.float32)
    # slots >= idx are stale garbage the positional mask must exclude
    pos = idx - s + jnp.arange(s, dtype=jnp.int32) + 0
    return q, kc, vc, pos


@pytest.mark.parametrize("s,h,hkv", [(1, 4, 4), (4, 8, 2), (1, 8, 2)])
def test_matches_einsum_decode(s, h, hkv):
    q, kc, vc, pos = _setup(jax.random.PRNGKey(0), s, h, hkv, idx=100)
    out = flash_decode_attention(q, kc, vc, pos, block_l=64)
    ref = decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_kv_valid_mask():
    q, kc, vc, pos = _setup(jax.random.PRNGKey(1), 1, 4, 4, idx=200)
    valid = np.ones((B, L), bool)
    valid[0, :17] = False   # left-padded prompt row 0
    valid[1, 40:60] = False  # an arbitrary invalid stretch
    valid = jnp.asarray(valid)
    out = flash_decode_attention(q, kc, vc, pos, kv_valid=valid, block_l=64)
    ref = decode_attention(q, kc, vc, pos, kv_valid=valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_early_slot_bound_skip():
    # position near the cache start: almost every block is skipped; result
    # must still be exact
    q, kc, vc, _ = _setup(jax.random.PRNGKey(2), 1, 4, 2, idx=0)
    pos = jnp.asarray([5], jnp.int32)
    out = flash_decode_attention(q, kc, vc, pos, block_l=64)
    ref = decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_tp_splits_cache_length():
    """tp=4 > hkv=2: the excess splits the cache length; exp-weighted psum
    merge must reproduce the unsharded result exactly (the reference's
    num_cores_per_group flash-decode groups)."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    q, kc, vc, pos = _setup(jax.random.PRNGKey(3), 2, 8, 2, idx=150)
    valid = np.ones((B, L), bool)
    valid[0, :9] = False
    valid = jnp.asarray(valid)
    ref = decode_attention(q, kc, vc, pos, kv_valid=valid)
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=4)
    try:
        out = jax.jit(
            lambda q, kc, vc: flash_decode_attention(
                q, kc, vc, pos, kv_valid=valid, block_l=32
            )
        )(q, kc, vc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    finally:
        mesh_lib.destroy_model_parallel()


def test_irregular_geometry_routes_through_manual_shard_map(monkeypatch):
    """tp=4 > hkv=2 with L % tp != 0 (ADVICE round 5): the irregular
    fallback must enter the SAME replicated manual region as the tp<=1
    branch — a bare kernel call under an active mesh asks GSPMD to
    partition a Mosaic custom call, which it cannot. The manual_shard_map
    spy proves the routing; running its body unsharded proves the numerics
    are still the exact einsum result."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    L_irr = 250  # 250 % 4 != 0 → length-split unavailable
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, 1, 8, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, L_irr, 2, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, L_irr, 2, D), jnp.float32)
    pos = jnp.asarray([200], jnp.int32)
    ref = decode_attention(q, kc, vc, pos)

    calls = []

    def spy(fn, in_specs, out_specs):
        calls.append({"in_specs": in_specs, "out_specs": out_specs})
        return fn  # run the body unsharded: numerics must be unchanged

    monkeypatch.setattr(mesh_lib, "manual_shard_map", spy)
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=4)
    try:
        out = flash_decode_attention(q, kc, vc, pos, block_l=64)
    finally:
        mesh_lib.destroy_model_parallel()
    assert len(calls) == 1, "fallback bypassed the manual region"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_tp_shards_kv_heads():
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    q, kc, vc, pos = _setup(jax.random.PRNGKey(4), 1, 8, 4, idx=150)
    ref = decode_attention(q, kc, vc, pos)
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=4)
    try:
        out = jax.jit(
            lambda q, kc, vc: flash_decode_attention(q, kc, vc, pos, block_l=64)
        )(q, kc, vc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    finally:
        mesh_lib.destroy_model_parallel()


# --- fused paged decode: block table IN the kernel's index map (ISSUE 13) -----

def _paged_setup(key, s=1, h=4, hkv=2, ps=16, n_log=8, pool_pages=24,
                 ctx=70):
    """A pool + block tables whose gathered logical view has ``ctx`` valid
    columns per slot (distinct physical pages per slot, rest unmapped →
    null page 0, masked invalid)."""
    ks = jax.random.split(key, 3)
    k_pool = jax.random.normal(ks[0], (pool_pages, ps, hkv, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (pool_pages, ps, hkv, D), jnp.float32)
    mapped = -(-ctx // ps)
    bt = np.zeros((B, n_log), np.int32)
    nxt = 1
    for b in range(B):
        for j in range(mapped):
            bt[b, j] = nxt
            nxt = nxt % (pool_pages - 1) + 1
    q = jax.random.normal(ks[2], (B, s, h, D), jnp.float32)
    valid = np.zeros((B, n_log * ps), bool)
    valid[:, :ctx] = True
    pos = ctx - s + jnp.arange(s, dtype=jnp.int32)
    return q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(valid), pos


@pytest.mark.parametrize("s,h,hkv", [(1, 4, 4), (4, 8, 2), (1, 8, 2)])
def test_paged_kernel_bit_identical_to_gather_path(s, h, hkv):
    """The fused block-index-map kernel reproduces gather-then-kernel
    BIT-FOR-BIT at the matching block partition (block_l=page_size) — the
    satellite's pinned contract; the gather path stays the non-TPU
    fallback."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_flash_decode_attention,
        paged_gather_leaf,
    )

    ps = 16
    q, kp, vp, bt, valid, pos = _paged_setup(
        jax.random.PRNGKey(0), s=s, h=h, hkv=hkv, ps=ps
    )
    fused = paged_flash_decode_attention(
        q, kp, vp, bt, pos, valid, page_size=ps, interpret=True
    )
    k_log = paged_gather_leaf(kp, bt, ps)
    v_log = paged_gather_leaf(vp, bt, ps)
    ref = flash_decode_attention(
        q, k_log, v_log, pos, valid, block_l=ps, interpret=True
    )
    assert np.array_equal(np.asarray(fused), np.asarray(ref))


@pytest.mark.parametrize("case", page_runs.CASES)
def test_paged_kernel_is_the_gather_path_on_tables_with_runs_of_adjacent_pages(case):
    """The pool deals a slot's pages in runs of adjacent ones
    (``serving/paging.PAGE_RUN``) for the kernels that fetch blocks
    themselves; this one maps a page a grid step through its index map, and
    stays the gather path bit for bit on every shape of table the runs make."""
    from neuronx_distributed_tpu.kernels.flash_decode import paged_flash_decode_attention, paged_gather_leaf
    ps, n_log, h, hkv = 16, 14 if case == "short_last_block" else 16, 4, 2
    cur = n_log * ps - 5
    table = page_runs.table(case, B, n_log, [(1, n_log), (n_log // 2, n_log)])
    valid = jnp.asarray(np.repeat(table != 0, ps, axis=1) & (np.arange(n_log * ps) <= cur))
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    pages = page_runs.pool_pages(B, n_log)
    kp, vp = (jax.random.normal(k, (pages, ps, hkv, D), jnp.float32) for k in ks[:2])
    q = jax.random.normal(ks[2], (B, 1, h, D), jnp.float32)
    bt, pos = jnp.asarray(table), jnp.asarray([cur], jnp.int32)
    fused = paged_flash_decode_attention(q, kp, vp, bt, pos, valid, page_size=ps, interpret=True)
    ref = flash_decode_attention(q, paged_gather_leaf(kp, bt, ps), paged_gather_leaf(vp, bt, ps), pos, valid,
                                 block_l=ps, interpret=True)
    assert np.array_equal(np.asarray(fused), np.asarray(ref)) and np.asarray(fused).any()


def test_paged_kernel_matches_einsum_golden():
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_flash_decode_attention,
        paged_gather_leaf,
    )

    ps = 16
    q, kp, vp, bt, valid, pos = _paged_setup(jax.random.PRNGKey(1))
    fused = paged_flash_decode_attention(
        q, kp, vp, bt, pos, valid, page_size=ps, interpret=True
    )
    ref = decode_attention(
        q, paged_gather_leaf(kp, bt, ps), paged_gather_leaf(vp, bt, ps),
        pos, kv_valid=valid,
    )
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=2e-5)


def test_paged_kernel_null_pages_never_attend():
    """Unmapped logical pages point at the reserved null page; with the
    serving kv_valid mask they must not influence the output — poisoning
    the null page's content must change nothing."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_flash_decode_attention,
    )

    ps = 16
    q, kp, vp, bt, valid, pos = _paged_setup(jax.random.PRNGKey(2))
    out = paged_flash_decode_attention(
        q, kp, vp, bt, pos, valid, page_size=ps, interpret=True
    )
    kp2 = kp.at[0].set(1e9)
    vp2 = vp.at[0].set(-1e9)
    out2 = paged_flash_decode_attention(
        q, kp2, vp2, bt, pos, valid, page_size=ps, interpret=True
    )
    assert np.array_equal(np.asarray(out), np.asarray(out2))


def test_paged_kernel_never_becomes_the_gather_path(monkeypatch):
    """Off the TPU, and without the tests' explicit interpretation request,
    the fused entry FAILS to lower — it never swaps itself for the gather
    reference (which used to make ``paged_attention="fused"`` a silent
    no-op on every backend but one)."""
    from neuronx_distributed_tpu.kernels import backend
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_flash_decode_attention,
    )

    ps = 16
    q, kp, vp, bt, valid, pos = _paged_setup(jax.random.PRNGKey(3))
    monkeypatch.setattr(backend, "INTERPRET", False)
    with pytest.raises(ValueError, match="interpret mode"):
        paged_flash_decode_attention(q, kp, vp, bt, pos, valid, page_size=ps)
    # the explicit argument still interprets (what every kernel test asks)
    out = paged_flash_decode_attention(
        q, kp, vp, bt, pos, valid, page_size=ps, interpret=True
    )
    assert np.isfinite(np.asarray(out)).all()


def test_row_cache_kernel_raises_uninterpreted_off_tpu(monkeypatch):
    from neuronx_distributed_tpu.kernels import backend

    q, kc, vc, pos = _setup(jax.random.PRNGKey(4), 1, 4, 4, idx=100)
    monkeypatch.setattr(backend, "INTERPRET", False)
    with pytest.raises(ValueError, match="interpret mode"):
        flash_decode_attention(q, kc, vc, pos)


