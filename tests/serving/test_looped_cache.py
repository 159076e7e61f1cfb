"""What is particular to a LOOPED stack's cache (Ouro: layers run several
times over one set of weights, a K/V cache node a layer a PASS) on the serving
path, tiny (2 layers x 3 passes), on ``built("looped")`` of
``test_cache_kinds.py``: the execution-order rule and every other family's
order under it; the pool, its bytes and the engine's counters by NODE under
one block table; a prefix hit that maps every pass's pages with no byte copied
and gives the reference's logits; the engine's defaults (prefix cache on)."""

import re

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.modules.attention import (
    LOOP_PASS_NODE,
    _execution_order,
    cache_token_bytes,
    ordered_kv_pool_pairs,
)
from neuronx_distributed_tpu.serving import PrefixCache, ServingEngine
from tests.serving.span_spy import overhear
from tests.serving.test_cache_kinds import KINDS, TOLERANCE, built, largest_gap, node_name, serve

PAGE = 16


def _natural_alone(layers):
    """The rule as it stood before a stack could be run more than once."""
    def natural(keys):
        return tuple(tuple(int(p) if p.isdigit() else p for p in re.split(r"(\d+)", str(k)) if p != "") for k in keys)
    return sorted(layers, key=natural)


def test_the_frames_order_is_pass_major_and_the_rule_is_one():
    nodes = [("model", f"layers_{i}", "attn", f"{LOOP_PASS_NODE}{t}") for i in (10, 9, 0, 2) for t in (3, 0, 11)]
    order = _execution_order(nodes)
    assert order == [("model", f"layers_{i}", "attn", f"{LOOP_PASS_NODE}{t}") for t in (0, 3, 11) for i in (0, 2, 9, 10)]
    # a path without a pass is a stack run once: natural order of the layers, as before
    flat = [("model", f"layers_{i}", "attn") for i in (10, 1, 9, 2, 0)]
    assert _execution_order(flat) == _natural_alone(flat) == [("model", f"layers_{i}", "attn") for i in (0, 1, 2, 9, 10)]
    # only ``pass_<digits>`` names a pass
    odd = [("model", "pass_through", "layers_1"), ("model", "pass_through", "layers_0")]
    assert _execution_order(odd) == _natural_alone(odd)


@pytest.mark.parametrize("kind", [name for name in KINDS if name != "looped"])
def test_every_other_familys_order_is_what_it_was(kind):
    """The pool's nodes of every cache kind, ordered by the rule and by the
    rule as it stood: the same list (their lowered decode programs are pinned
    besides: ``test_latent_cache.py``'s digests)."""
    b = built(kind)
    pool = b.stream("fused")[0].cache.cache["pool"]
    order = list(ordered_kv_pool_pairs(pool))
    assert order == _natural_alone(order) and len(order) >= 2
    assert not any(str(k).startswith(LOOP_PASS_NODE) for path in order for k in path)


@pytest.fixture(scope="module")
def looped():
    return built("looped")


def test_the_pool_and_the_counters_count_nodes_under_one_block_table(looped):
    cfg = looped.cfg
    eng = looped.stream("fused")[0]
    paged = eng.cache.cache
    assert set(paged) == {"pages", "pool"}                      # ONE block table: every pass holds the same tokens
    pairs = ordered_kv_pool_pairs(paged["pool"])
    assert [node_name(n) for n in pairs] == [f"layers_{i}/pass_{t}" for t in range(3) for i in range(2)]
    assert len(pairs) == cfg.kv_cache_nodes == 6
    pages = 2 * (cfg.max_seq_len // PAGE) + 1
    token = 2 * cfg.num_kv_heads * cfg.head_dim * 4              # K and V, float32
    assert all(pair[0].shape == (pages, PAGE, 2 * cfg.num_kv_heads, cfg.head_dim) for pair in pairs.values())
    assert eng.cache.page_nbytes == 6 * PAGE * token             # a page is a page of EVERY node
    leaves = 6 * pages * PAGE * token
    assert leaves < eng.cache.nbytes < leaves + 6 * 2 * cfg.max_seq_len + 4096    # + validity, cursors, the table
    assert cache_token_bytes(paged) == (6 * token, 6)
    snap = eng.metrics.snapshot()
    assert (snap["kv_cache_nodes"], snap["kv_bytes_per_token_layer"]) == (6, token) and "kv_bytes_per_token" not in snap
    eng.cache.check()


def test_the_dispatch_span_carries_the_nodes_and_the_bytes_a_token(looped):
    _, model, params, prompts, _ = looped
    eng = ServingEngine(model, params, num_slots=2, decode_chunk_size=4, kv_page_size=PAGE)
    stats = overhear(eng, "nxd.step.decode.dispatch")
    eng.submit(prompts[2], GenerationConfig(max_new_tokens=6, temperature=0.0), key=jax.random.PRNGKey(0))
    eng.run()
    assert stats and all((s["kv_cache_nodes"], s["kv_bytes_per_token_layer"]) == (6, 512)
                         for s in stats)


def test_a_prefix_hit_maps_every_passes_pages_copies_no_byte_and_gives_the_references_logits(looped):
    """The engine's DEFAULTS (paged, prefix cache on): two requests share 35
    tokens, two whole pages; the hit maps those pages for all six nodes through
    the one table (a page id names a page of every node), the suffix is
    prefilled through the decode path, and every emitted token is the
    reference's."""
    cfg, model, params, _, ref = looped
    rng = np.random.default_rng(5)
    system = rng.integers(1, cfg.vocab_size, size=35).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, size=6 + i).astype(np.int32)]) for i in range(3)]
    _, plain = serve(model, params, prompts, new_tokens=8, kv_page_size=PAGE)
    for transport in ("gather", "fused"):
        eng = ServingEngine(model, params, num_slots=2, decode_chunk_size=4, kv_page_size=PAGE,
                            paged_attention=transport, prefix_cache=PrefixCache(min_match=8))
        gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
        first = eng.submit(prompts[0], gcfg, key=jax.random.PRNGKey(0))
        eng.run()
        rest = [eng.submit(p, gcfg, key=jax.random.PRNGKey(i + 1)) for i, p in enumerate(prompts[1:])]
        eng.run()
        assert [list(r.tokens) for r in (first, *rest)] == plain
        snap = eng.metrics.snapshot()
        assert snap["prefix_hits"] == 2 and snap["prefix_pages_shared"] == 4 and eng.cache.alloc.copy_bytes == 0
        eng.cache.check()
    assert largest_gap(ref, prompts, plain) <= TOLERANCE


def test_the_engines_defaults_serve_it_with_the_prefix_cache_on(looped):
    _, model, params, prompts, ref = looped
    eng = ServingEngine(model, params, num_slots=2, kv_page_size=PAGE, paged_attention="fused")
    assert eng.prefix is not None and eng.prefix.enabled and eng.cache.window is None and not eng.cache.slot_state
    assert eng.programs.resolved["decode_attention"] == "paged_walk_fused"
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=10, temperature=0.0), key=jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    eng.run()
    assert largest_gap(ref, prompts, [list(r.tokens) for r in reqs]) <= TOLERANCE
    eng.cache.check()


def _rows_served(model, params, prompts, **engine):
    """(tokens, the engine) of the prompts served two slots at a time, the
    first alone so the others can hit its pages."""
    eng = ServingEngine(model, params, num_slots=2, decode_chunk_size=4, kv_page_size=PAGE, **engine)
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    first = eng.submit(prompts[0], gcfg, key=jax.random.PRNGKey(0))
    eng.run()
    rest = [eng.submit(p, gcfg, key=jax.random.PRNGKey(i + 1)) for i, p in enumerate(prompts[1:])]
    eng.run()
    eng.cache.check()
    return [list(r.tokens) for r in (first, *rest)], eng


def test_a_prefill_gives_out_a_row_of_its_buckets_columns_and_the_pages_are_the_whole_rows(looped):
    """``bucket_prefill_rows`` (the config's, on): the bucket's program is the
    model cloned with ``max_seq_len`` the bucket's, so its row has 64 columns
    of the cache's 128 and a hit's seeded row at most twice its bucket's; the
    pages the admission cuts out of them, and every token, are those of whole
    rows (the flag off), at a fresh prefill and at a prefix hit."""
    import dataclasses
    import jax.numpy as jnp

    cfg, model, params, _, _ = looped
    rng = np.random.default_rng(11)
    system = rng.integers(1, cfg.vocab_size, size=35).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, size=4 + 5 * i).astype(np.int32)]) for i in range(4)]
    whole_model = model.clone(config=dataclasses.replace(cfg, bucket_prefill_rows=False))
    short, eng = _rows_served(model, params, prompts, prefix_cache=PrefixCache(min_match=8))
    whole, eng_whole = _rows_served(whole_model, params, prompts, prefix_cache=PrefixCache(min_match=8))
    assert eng._bucket_rows and not eng_whole._bucket_rows
    assert short == whole
    assert eng.metrics.snapshot()["prefix_hits"] == eng_whole.metrics.snapshot()["prefix_hits"] == 3
    assert eng.cache.alloc.copy_bytes == 0

    def columns(engine):
        ids = jnp.ones((1, 64), jnp.int32)
        _, row = engine._prefill_fn(64)(params, ids, jnp.ones((1, 64), jnp.bool_))
        return {leaf.shape[1] for leaf in jax.tree.leaves(row) if leaf.ndim >= 2}

    assert columns(eng) == {64} and columns(eng_whole) == {cfg.max_seq_len}
    # the pages every slot maps hold the same bytes either way
    for path, pool in ordered_kv_pool_pairs(eng.cache.cache["pool"]).items():
        other = ordered_kv_pool_pairs(eng_whole.cache.cache["pool"])[path]
        np.testing.assert_array_equal(eng.cache._tables, eng_whole.cache._tables)
        mapped = np.unique(eng.cache._tables[eng.cache._tables > 0])
        for a, b in zip(pool, other):
            np.testing.assert_array_equal(np.asarray(a)[mapped], np.asarray(b)[mapped])


@pytest.mark.parametrize("cursor", [48, 64, 96, 128])
def test_a_short_rows_pages_land_where_a_whole_rows_do_at_any_cursor(looped, cursor):
    """The paged admission alone: a 32-token context in a bucket of 48, as a
    row of 48 columns and as a whole row of 128, admitted at the same cursor
    (the last one at the row's very end, where the window of pages is
    clamped): the same pages, validity and cursor."""
    import jax.numpy as jnp
    from neuronx_distributed_tpu.serving.paging import PagedCacheManager

    cfg = looped.cfg
    rng = np.random.default_rng(cursor)
    hkv2, d, length, padded, p = 2 * cfg.num_kv_heads, cfg.head_dim, cfg.max_seq_len, 48, 32
    kv = rng.standard_normal((1, padded, hkv2, d)).astype(np.float32)
    valid = np.zeros((1, padded), bool)
    valid[0, padded - p:] = True

    def row(columns):
        pad = columns - padded
        return {"layers_0": {"attn": {f"{LOOP_PASS_NODE}0": {
            "kv": jnp.asarray(np.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0)))),
            "kv_valid": jnp.asarray(np.pad(valid, ((0, 0), (0, pad)))),
            "index": jnp.asarray(padded, jnp.int32)}}}}

    pools = []
    for columns in (padded, length):
        mgr = PagedCacheManager(2, length, PAGE)
        slot = mgr.acquire()
        mgr.admit(row(columns), slot, padded, cursor=cursor, p=p)
        mgr.check()
        node = mgr.cache["pool"]["layers_0"]["attn"][f"{LOOP_PASS_NODE}0"]
        own = mgr._tables[slot][mgr._tables[slot] > 0]
        pools.append((np.asarray(node["kv"])[own], np.asarray(node["kv_valid"]), int(node["index"]), own))
    short, whole = pools
    # a page's columns past the context hold what the row held there (nothing valid): compare the context's
    np.testing.assert_array_equal(short[3], whole[3])
    flat = lambda pages: pages.reshape((-1,) + pages.shape[2:])[:p]
    np.testing.assert_array_equal(flat(short[0]), flat(whole[0]))
    np.testing.assert_array_equal(flat(short[0]), kv[0, padded - p:])
    np.testing.assert_array_equal(short[1], whole[1])
    assert short[2] == whole[2] == cursor and short[1][0].sum() == p
