"""What is particular to the indexed cache kind (K and V joined in one leaf
and one index key a token) on the serving path (the contract it shares with
the other kinds: ``test_cache_kinds.py``): prefix sharing on the row cache,
per-page fingerprints, fault injection's walkers and the host tier on the
two-leaf pool."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.modules.attention import PAGED_LEAVES
from neuronx_distributed_tpu.serving import PrefixCache
from tests.serving.test_cache_kinds import built, serve

PS = 16


@pytest.fixture(scope="module")
def setup():
    return tuple(built("indexed"))


def test_prefix_sharing_on_the_row_cache_extracts_and_seeds_both_leaves(setup):
    cfg, model, params, _, _ = setup
    rng = np.random.default_rng(4)
    system = rng.integers(1, cfg.vocab_size, size=33).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, size=4 + i).astype(np.int32)])
               for i in range(3)]
    _, plain = serve(model, params, prompts, new_tokens=8)
    eng, shared = serve(model, params, prompts, new_tokens=8, prefix_cache=PrefixCache(min_match=8))
    assert shared == plain and eng.metrics.snapshot()["prefix_hits"] >= 2


def test_page_fingerprints_and_the_fault_walkers_see_the_index_key(setup):
    """A flipped bit in a ``k_idx`` page is a corruption the per-page
    fingerprint catches, as one in ``kv`` is."""
    from neuronx_distributed_tpu.utils.fingerprint import cache_fingerprint

    _, model, params, prompts, _ = setup
    eng, _ = serve(model, params, prompts[:2], new_tokens=4, kv_page_size=PS)
    pool = eng.cache.cache["pool"]
    base = np.asarray(cache_fingerprint(pool))
    for name in ("kv", "k_idx"):
        def flip(path, leaf, name=name):
            return leaf.at[1, 0, 0, 0].add(1.0) if path[-1].key == name and "layers_0" in str(path) else leaf
        changed = np.asarray(cache_fingerprint(jax.tree_util.tree_map_with_path(flip, pool)))
        assert not np.array_equal(changed, base), name


def test_the_host_tier_round_trips_two_leaf_pages(setup):
    """The tier's two walkers on the two-leaf pool: pages spilled to the
    host carry ``kv`` and ``k_idx`` blocks of every layer (2 x 2 heads of 16
    + one key of 8 in float32 a token), and come back, at fresh page ids, bit
    for bit."""
    _, model, params, prompts, _ = setup
    eng, _ = serve(model, params, prompts[:2], new_tokens=4, kv_page_size=PS)
    mgr = eng.cache
    ids = mgr._alloc_pages(2)     # held, so that the pages that come back are others
    before = {jax.tree_util.keystr(p): np.asarray(leaf[jnp.asarray(ids)])
              for p, leaf in jax.tree_util.tree_flatten_with_path(mgr.cache["pool"])[0]
              if p[-1].key in PAGED_LEAVES}
    items, nbytes = mgr.spill_pages(ids)
    assert sorted({keys[-1] for keys, _ in items}) == ["k_idx", "kv"]
    assert len(items) == 2 * 2 and nbytes == sum(a.nbytes for a in before.values())
    assert nbytes == len(ids) * PS * 2 * (2 * 2 * 16 + 8) * 4      # pages x tokens x layers x bytes a token a layer
    fresh = mgr.prefetch_pages(items, len(ids))
    assert not set(fresh) & set(ids)
    for p, leaf in jax.tree_util.tree_flatten_with_path(mgr.cache["pool"])[0]:
        if p[-1].key in PAGED_LEAVES:
            np.testing.assert_array_equal(np.asarray(leaf[jnp.asarray(fresh)]), before[jax.tree_util.keystr(p)])
    mgr.unpin_pages(fresh)
    for pid in ids:
        mgr.alloc.deref(pid)
    mgr.check()
