"""Paged KV cache (ISSUE 10): block-table attention + zero-copy CoW prefix
sharing.

The load-bearing contracts, each pinned here:

* allocator algebra — alloc/ref/deref/quarantine and the ``check()``
  invariant actually catching orphans, double-maps, and bad refcounts;
* streams BIT-IDENTICAL to the row-per-slot engine for plain greedy,
  sampled, mixed-length staggered traffic, prefix hits, and speculative
  decode — the paged chunk is the same program over a gathered view;
* ``decode_compilations == 1`` across block-table layouts (tables are
  data, not shape);
* prefix hits copy ZERO KV bytes, asserted via allocator accounting
  (``copy_bytes`` never moves; ``prefix_pages_shared`` does);
* free-page admission: a pool a fraction of the row-equivalent HBM still
  serves mixed-length traffic the row manager could not hold concurrently,
  and the permanently-unplaceable rejection stays exact.
"""

import dataclasses

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, generate
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import (
    PageAllocator,
    PagedCacheManager,
    PageExhausted,
    PrefixCache,
    RequestState,
    ServingEngine,
)
from tests.serving.span_spy import overhear

PS = 8  # page size used throughout


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


# --- PageAllocator ------------------------------------------------------------


def test_allocator_alloc_ref_deref_roundtrip():
    a = PageAllocator(8)  # pages 1..7 usable
    assert a.free_pages == 7 and a.capacity == 7
    ids = a.alloc(3)
    assert len(ids) == 3 and 0 not in ids
    assert a.free_pages == 4 and all(a.refcount(p) == 1 for p in ids)
    a.ref(ids[0])
    a.deref(ids[0])
    assert a.refcount(ids[0]) == 1  # still held by the original mapping
    for p in ids:
        a.deref(p)
    assert a.free_pages == 7 and a.referenced_pages == 0


def test_allocator_exhaustion_and_quarantine():
    a = PageAllocator(4)
    ids = a.alloc(3)
    with pytest.raises(PageExhausted):
        a.alloc(1)
    a.quarantine(ids[0])  # referenced: retires on last deref
    a.deref(ids[0])
    assert a.capacity == 2 and a.free_pages == 0
    a.deref(ids[1])
    a.deref(ids[2])
    assert a.free_pages == 2  # the quarantined page never came back
    with pytest.raises(ValueError):
        a.ref(ids[0])  # dead page cannot be re-referenced


def test_allocator_reserved_null_page():
    a = PageAllocator(4)
    assert 0 not in a.alloc(3)
    with pytest.raises(ValueError):
        a.quarantine(0)


def test_manager_check_catches_leaks_and_double_maps():
    mgr = PagedCacheManager(num_slots=2, max_seq_len=32, page_size=PS)
    mgr.check()  # empty: fine
    ids = mgr.alloc.alloc(2)
    with pytest.raises(AssertionError, match="refcount"):
        mgr.check()  # allocated but mapped/pinned nowhere = leak
    mgr._tables[0, 0], mgr._tables[0, 1] = ids
    mgr.check()
    mgr._tables[1, 0] = ids[0]  # second mapper without a ref
    with pytest.raises(AssertionError, match="refcount"):
        mgr.check()
    mgr.alloc.ref(ids[0])
    mgr.check()
    mgr._tables[1, 1] = ids[0]  # one slot, same page twice
    with pytest.raises(AssertionError, match="double-maps"):
        mgr.check()
    # clean up so the suite-wide teardown fixture stays green
    mgr._tables[:] = 0
    mgr.alloc.deref(ids[0])
    for p in ids:
        mgr.alloc.deref(p)
    mgr.check()


def test_manager_geometry_validation():
    with pytest.raises(ValueError, match="multiple"):
        PagedCacheManager(num_slots=2, max_seq_len=30, page_size=PS)
    m = PagedCacheManager(num_slots=2, max_seq_len=32, page_size=PS)
    assert m.pages_per_row == 4
    # default pool = row-equivalent HBM + the reserved null page
    assert m.alloc.num_pages == 2 * 4 + 1
    assert m.aligned_target(10, 6) == 14  # (14-6) % 8 == 0
    assert m.aligned_target(8, 8) == 8
    assert m.page_span(0, 17) == 3 and m.page_span(8, 16) == 1


# --- stream bit-identity across layouts ---------------------------------------


def _run_engine(model, params, prompts, gcfg, keys, **kw):
    eng = ServingEngine(model, params, **kw)
    reqs = [
        eng.submit(p, gcfg, key=k) for p, k in zip(prompts, keys)
    ]
    eng.run()
    return eng, [r.tokens for r in reqs]


def test_streams_bit_identical_mixed_lengths(setup):
    """Plain greedy + sampled mixed-length staggered traffic: the paged
    engine's streams equal the row engine's AND solo generate()'s."""
    cfg, model, params = setup
    rng = np.random.RandomState(7)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
        for n in (5, 23, 9, 14, 3, 31)
    ]
    gcfg = GenerationConfig(max_new_tokens=9, temperature=0.8, top_k=17)
    keys = [jax.random.PRNGKey(40 + i) for i in range(len(prompts))]
    _, row_toks = _run_engine(
        model, params, prompts, gcfg, keys,
        num_slots=3, decode_chunk_size=4, prefix_cache=None,
    )
    pg, pg_toks = _run_engine(
        model, params, prompts, gcfg, keys,
        num_slots=3, decode_chunk_size=4, prefix_cache=None, kv_page_size=PS,
    )
    assert pg_toks == row_toks
    solo = np.asarray(
        generate(
            model, params, jax.numpy.asarray(prompts[0])[None], keys[0], gcfg
        )
    )[0].tolist()
    assert pg_toks[0] == solo
    assert pg.decode_compilations == 1
    pg.cache.check()


def test_decode_compilations_stay_one_across_table_layouts(setup):
    """Three waves with drain/rewind between them churn the block tables
    through disjoint physical pages — the table is DATA, so XLA still
    compiled exactly one decode program."""
    cfg, model, params = setup
    eng = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        prefix_cache=None, kv_page_size=PS,
    )
    gcfg = GenerationConfig(max_new_tokens=5, temperature=0.0)
    for wave in range(3):
        for i in range(3):
            eng.submit(
                np.arange(1 + i, 7 + wave + 2 * i, dtype=np.int32), gcfg,
                key=jax.random.PRNGKey(wave * 10 + i),
            )
        eng.run()
    assert eng.decode_compilations == 1
    assert eng.metrics.snapshot()["completed"] == 9
    eng.cache.check()


@pytest.mark.slow  # heavy spec×paged A/B variant (tier-1 budget, PR 5/13
# lean-core policy): paged A/Bs stay tier-1 in this file, spec-decode
# bit-identity in tests/serving/test_spec_decode.py
def test_speculative_paged_streams_match_row(setup):
    cfg, model, params = setup
    draft = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    d_params = draft.init(jax.random.PRNGKey(9), ids)
    prompts = [
        np.arange(1, 8, dtype=np.int32), np.arange(4, 17, dtype=np.int32)
    ]
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    keys = [jax.random.PRNGKey(60 + i) for i in range(2)]
    kw = dict(
        num_slots=2, decode_chunk_size=3, draft_model=draft,
        draft_params=d_params, gamma=3, prefix_cache=None,
    )
    _, row_toks = _run_engine(model, params, prompts, gcfg, keys, **kw)
    pg, pg_toks = _run_engine(
        model, params, prompts, gcfg, keys, kv_page_size=PS, **kw
    )
    assert pg_toks == row_toks
    assert pg.decode_compilations == 1
    pg.cache.check()
    pg.draft_cache.check()


@pytest.mark.slow  # heavy paged x preemption composition (tier-1 budget,
# PR 5/13 lean-core policy): each leg stays tier-1 via
# test_streams_bit_identical_mixed_lengths and
# test_engine.py::test_preemption_resumes_token_identical
def test_preemption_resume_bit_identical(setup):
    """Eager admission with a short row: the paged engine hits the wall
    (alignment gaps spend columns faster), preempts, and resumes — streams
    still equal the row engine's."""
    cfg, model, params = setup
    cfg2 = dataclasses.replace(cfg, max_seq_len=32)
    model2 = LlamaForCausalLM(cfg2, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params2 = model2.init(jax.random.PRNGKey(1), ids)
    prompts = [
        np.arange(1, 9, dtype=np.int32), np.arange(2, 12, dtype=np.int32)
    ]
    gcfg = GenerationConfig(max_new_tokens=12, temperature=0.6, top_k=11)
    keys = [jax.random.PRNGKey(70 + i) for i in range(2)]
    kw = dict(
        num_slots=2, decode_chunk_size=4, admission="eager",
        prefix_cache=None,
    )
    _, row_toks = _run_engine(model2, params2, prompts, gcfg, keys, **kw)
    pg, pg_toks = _run_engine(
        model2, params2, prompts, gcfg, keys, kv_page_size=PS, **kw
    )
    assert pg_toks == row_toks
    assert pg.metrics.snapshot()["preemptions"] > 0  # the wall actually hit
    pg.cache.check()


# --- zero-copy CoW prefix sharing ---------------------------------------------


def test_prefix_hit_is_zero_copy_and_bit_identical(setup):
    """Shared-system-prompt traffic: hits map pool pages into the new
    slot's table (ref-counted), allocator ``copy_bytes`` stays 0, streams
    equal the prefix-off and row engines."""
    cfg, model, params = setup
    sys_p = np.arange(1, 18, dtype=np.int32)  # 17 tokens -> 2 whole pages
    rng = np.random.RandomState(3)
    prompts = [
        np.concatenate([
            sys_p, rng.randint(1, cfg.vocab_size, size=4 + i).astype(np.int32)
        ])
        for i in range(4)
    ]
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    keys = [jax.random.PRNGKey(80 + i) for i in range(4)]
    _, off_toks = _run_engine(
        model, params, prompts, gcfg, keys,
        num_slots=2, decode_chunk_size=4, prefix_cache=None, kv_page_size=PS,
    )
    _, row_toks = _run_engine(
        model, params, prompts, gcfg, keys,
        num_slots=2, decode_chunk_size=4,
        prefix_cache=PrefixCache(min_match=8),
    )
    pg, pg_toks = _run_engine(
        model, params, prompts, gcfg, keys,
        num_slots=2, decode_chunk_size=4,
        prefix_cache=PrefixCache(min_match=8), kv_page_size=PS,
    )
    assert pg_toks == off_toks == row_toks
    snap = pg.metrics.snapshot()
    assert snap["prefix_hits"] >= 3
    assert snap["prefix_pages_shared"] >= snap["prefix_hits"] * 2
    # THE zero-copy assertion: allocator accounting, not timing
    assert pg.cache.alloc.copy_bytes == 0
    # entries hold pins, shared pages hold multiple refs while decoding
    assert pg.cache.prefix_pages_shared_total >= 6
    pg.cache.check()


def test_prefix_insert_pins_pages_and_eviction_releases(setup):
    cfg, model, params = setup
    eng = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        prefix_cache=PrefixCache(max_entries=8, min_match=8), kv_page_size=PS,
    )
    gcfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    r = eng.submit(np.arange(1, 20, dtype=np.int32), gcfg,
                   key=jax.random.PRNGKey(0))
    eng.run()
    assert r.state is RequestState.DONE
    entries = eng.prefix.entries
    assert len(entries) == 1 and entries[0].page_ids
    pinned = entries[0].page_ids
    # the slot retired, but the entry keeps its pages alive
    assert all(eng.cache.alloc.refcount(p) == 1 for p in pinned)
    eng.cache.check()
    # eviction releases them (on_evict hook)
    eng.prefix.evict_entry(entries[0])
    assert all(eng.cache.alloc.refcount(p) == 0 for p in pinned)
    eng.cache.check()


def test_weight_swap_clears_paged_entries_and_pins(setup):
    cfg, model, params = setup
    eng = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        prefix_cache=PrefixCache(min_match=8), kv_page_size=PS,
    )
    gcfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    eng.submit(np.arange(1, 20, dtype=np.int32), gcfg,
               key=jax.random.PRNGKey(0))
    eng.run()
    assert len(eng.prefix) == 1
    eng.params = params  # swap clears the store; pins must release
    assert len(eng.prefix) == 0
    assert eng.cache.alloc.referenced_pages == 0
    eng.cache.check()


# --- free-page admission accounting -------------------------------------------


def test_small_pool_serves_more_slots_than_row_equivalent(setup):
    """Fixed KV budget of ONE row-equivalent (16 pages = 128 columns): the
    paged engine runs 4 short requests CONCURRENTLY where the row manager
    could hold exactly 1 slot at that budget."""
    cfg, model, params = setup
    eng = ServingEngine(
        model, params, num_slots=4, decode_chunk_size=4, prefix_cache=None,
        kv_page_size=PS, kv_num_pages=cfg.max_seq_len // PS + 1,
    )
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    reqs = [
        eng.submit(np.arange(1, 5 + i, dtype=np.int32), gcfg,
                   key=jax.random.PRNGKey(i))
        for i in range(4)
    ]
    eng.run()
    assert all(r.state is RequestState.DONE and len(r.tokens) == 8
               for r in reqs)
    assert eng.metrics.snapshot()["mean_occupancy"] == 4.0
    eng.cache.check()


def test_unplaceable_page_footprint_rejected_at_submit(setup):
    """The up-front permanently-unplaceable rejection stays exact: a
    request whose solo worst-case page footprint exceeds the pool fails at
    the door; one page under the line is accepted."""
    cfg, model, params = setup
    eng = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4, prefix_cache=None,
        kv_page_size=PS, kv_num_pages=5,  # 4 usable pages = 32 columns
    )
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit(np.arange(1, 27, dtype=np.int32), gcfg)  # 26 + 8 > 32
    r = eng.submit(np.arange(1, 24, dtype=np.int32), gcfg,
                   key=jax.random.PRNGKey(0))  # 23 + 8 = 31 <= 32: placeable
    eng.run()
    assert r.state is RequestState.DONE and len(r.tokens) == 8
    eng.cache.check()


@pytest.mark.parametrize("admission", ["conservative", "eager"])
def test_minimal_pool_short_tail_completes(setup, admission):
    """Review regression: the per-chunk page window is clamped to the
    active slots' REMAINING work, so a request the door check admits into
    a minimal pool (2 pages) completes instead of livelocking at the
    page-pressure wall when decode_chunk_size alone would demand more
    window pages than it was ever charged for."""
    cfg, model, params = setup
    eng = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=8, prefix_cache=None,
        kv_page_size=4, kv_num_pages=3, admission=admission,
    )
    r = eng.submit(
        np.arange(1, 5, dtype=np.int32),
        GenerationConfig(max_new_tokens=2, temperature=0.0),
        key=jax.random.PRNGKey(0),
    )
    eng.run(max_steps=50)
    assert r.state is RequestState.DONE and len(r.tokens) == 2
    assert eng.metrics.snapshot()["preemptions"] == 0
    eng.cache.check()


def test_conservative_admission_queues_on_page_pressure(setup):
    """Two placeable-but-not-together requests: the second queues until
    the first retires (no preemption on the conservative path), then runs."""
    cfg, model, params = setup
    eng = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4, prefix_cache=None,
        kv_page_size=PS, kv_num_pages=7,  # 6 usable pages = 48 columns
    )
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    r1 = eng.submit(np.arange(1, 24, dtype=np.int32), gcfg,
                    key=jax.random.PRNGKey(0))
    r2 = eng.submit(np.arange(1, 20, dtype=np.int32), gcfg,
                    key=jax.random.PRNGKey(1))
    eng.step()
    assert r1.state is RequestState.DECODE
    assert r2.state is RequestState.QUEUED  # pages would not cover both
    eng.run()
    assert r1.state is RequestState.DONE and r2.state is RequestState.DONE
    assert eng.metrics.snapshot()["preemptions"] == 0
    eng.cache.check()


# --- pages dealt in runs (ISSUE 52) ---------------------------------------------


def _is_run(ids):
    return all(b - a == 1 for a, b in zip(ids, ids[1:]))


def test_allocator_deals_a_logical_run_from_one_group_of_adjacent_pages():
    """The four pages of an owner's aligned logical run come from one aligned
    group of the pool, over as many calls as the owner takes to ask for them;
    a row that starts mid-run, and nobody's incomplete run, take single pages
    and leave the whole groups whole."""
    a = PageAllocator(41)                       # groups 4-7 .. 36-39; 1, 2, 3 and 40 are loose
    ids = a.deal([("x", j) for j in range(6, 13)])             # logical 6, 7 | 8 .. 11 | 12
    assert ids == [1, 2, 4, 5, 6, 7, 8]
    assert a.deal([("y", 0)]) == [12]                          # another owner: the next whole group
    assert a.deal([("x", 13)]) == [9]                          # x's run goes on where it was dealt
    assert a.deal([("x", 14), ("x", 15), ("x", 16)]) == [10, 11, 16]
    assert a.alloc(2) == [3, 40]                               # nobody's two pages: loose ones
    assert a.alloc(5) == [20, 21, 22, 23, 24]                  # nobody's five: a run, and a group broken for the fifth
    a.quarantine(13)
    assert a.deal([("y", 1)]) == [14]                          # its page gone meanwhile: a single one, the group broken
    rest = a.alloc(a.free_pages)
    assert sorted(rest[-3:]) == [17, 18, 19] and a.free_pages == 0   # what is dealt to x's run goes last
    for pid in rest:
        a.deref(pid)
    for pid in ids[2:6]:
        a.deref(pid)
    assert a.deal([("z", 0)]) == [4]                           # a freed run comes back whole


def test_allocator_never_refuses_what_a_count_of_free_pages_grants():
    """The run is a preference among FREE pages: with the pool down to single
    free pages (no group whole), and with every free page dealt to somebody's
    run, whatever number is free can be had, and one more is PageExhausted
    with nothing taken."""
    a = PageAllocator(65)
    assert sorted(a.alloc(64)) == list(range(1, 65)) and a.free_pages == 0
    for pid in range(2, 65, 4):                 # one page of every group: no run anywhere
        a.deref(pid)
    with pytest.raises(PageExhausted):
        a.deal([("x", j) for j in range(17)])
    assert a.free_pages == 16
    assert sorted(a.deal([("x", j) for j in range(16)])) == list(range(2, 65, 4))
    b = PageAllocator(9)
    assert b.deal([("x", 0)]) == [4]                              # x is dealt group 4-7 and takes its first page
    assert sorted(b.deal([("y", j) for j in range(1, 8)])) == [1, 2, 3, 5, 6, 7, 8]   # the rest of it among them
    assert b.free_pages == 0
    b.deref(5)
    assert b.deal([("x", 1)]) == [5]                              # given back meanwhile: x's run goes on
    b.deref(1)
    assert b.deal([("x", 2)]) == [1]                              # 6 is y's now: a single page in its place


def _bare_row(length, padded, window=None):
    """A batch-1 prefill row of two layers with one joined leaf each (the
    second a window layer when ``window`` is given): what an admission needs
    of a model, without one."""
    import jax.numpy as jnp

    def node(w):
        leaves = {"kv": jnp.ones((1, length, 2, 4), jnp.float32), "index": jnp.asarray(padded, jnp.int32),
                  "kv_valid": jnp.arange(length)[None] < padded}
        if w is not None:
            leaves["window"] = jnp.zeros((0, w), bool)
        return leaves

    return {"layers_0": {"attn": node(None)}, "layers_1": {"attn": node(window)}}


@pytest.mark.parametrize("window,sharing", [(None, False), (None, True), (480, False)],
                         ids=["full", "full_shared_prefixes", "window"])
def test_a_random_life_of_the_pool_leaks_nothing_and_deals_runs(window, sharing):
    """A seeded random sequence of admissions (some onto a pinned prefix's
    pages), decode windows, frees and preempt-and-rewinds over both kinds of
    table: ``check()`` holds after every step, an allocation is refused only
    when the pages are not there, nothing is copied and nothing leaks; and
    with no sharing nine pages in ten lie in runs the kernels fetch whole."""
    ps, slots, length, width = 4, 4, 2048, 8
    mgr = PagedCacheManager(slots, length, ps, num_pages=1200, window=window, window_write_cols=width)
    rng = np.random.default_rng(52)
    rows = {padded: _bare_row(length, padded, window) for padded in (256, 512)}
    active, pinned, shares = [], [], []
    for step in range(160):
        free_before = mgr.alloc.free_pages
        roll = rng.random()
        if roll < 0.25 and mgr.free_slots:
            slot = mgr.acquire()
            padded = int(rng.choice(list(rows)))
            p = int(rng.integers(padded // 2 + 1, padded + 1))
            shared, m = (), 0
            if sharing and pinned and rng.random() < 0.5:
                shared = pinned[int(rng.integers(len(pinned)))]
                m = len(shared) * ps
            try:
                mgr.admit(rows[padded], slot, padded, p=p, shared_ids=shared, m_shared=min(m, p // ps * ps))
                active.append(slot)
                if sharing and rng.random() < 0.5:
                    ids = mgr.slot_context_pages(slot, int(rng.integers(1, p // ps + 1)))
                    mgr.pin_pages(ids)
                    pinned.append(tuple(ids))
            except PageExhausted:
                assert free_before < padded // ps + 1
                mgr.free(slot)
        elif roll < 0.85 and active:
            if mgr.cursor + width > length or not mgr.ensure_decode_window(active, width):
                assert mgr.cursor + width > length or free_before < 3 * len(active) * (1 + (window is not None))
                mgr.reset()                          # the wall: preempt and rewind
                mgr.release_all_slots()
                active.clear()
            else:
                mgr.update_after_decode(mgr.cache, steps=int(rng.integers(1, width + 1)))
                if not sharing and mgr.page_stats["full_pages_mapped"] > 400:
                    shares.append(mgr.page_stats["full_pages_in_runs"] / mgr.page_stats["full_pages_mapped"])
                    if window is not None:
                        shares.append(mgr.page_stats["window_pages_in_runs"] / mgr.page_stats["window_pages_mapped"])
        elif active:
            mgr.free(active.pop(int(rng.integers(len(active)))))
        mgr.check()
    for slot in active:
        mgr.free(slot)
    for ids in pinned:
        mgr.unpin_pages(ids)
    mgr.check()
    assert mgr.alloc.free_pages == mgr.alloc.num_pages - 1 and mgr.alloc.copy_bytes == 0
    assert not mgr.alloc._dealt and (window is None or not mgr.alloc_w._dealt)
    if window is not None:
        assert mgr.alloc_w.free_pages == mgr.alloc_w.num_pages - 1
    if not sharing:
        assert len(shares) > 10 and min(shares) >= 0.9, (len(shares), sorted(shares)[:8])


def test_a_pool_down_to_single_free_pages_still_grants_what_is_free():
    """Every group of the pool broken (one free page in each): an admission
    that needs exactly the free pages gets them, the next decode window meets
    the page-pressure wall and not an error, and the pages come back."""
    ps, length = 4, 512
    mgr = PagedCacheManager(2, length, ps, num_pages=129)
    held = mgr.alloc.alloc(128)
    singles = sorted(pid for pid in held if pid % 4 == 2)        # 32 pages, no two adjacent
    for pid in singles:
        mgr.alloc.deref(pid)
    slot = mgr.acquire()
    mgr.admit(_bare_row(length, 128), slot, 128, p=128)          # 128 tokens: 32 pages
    assert sorted(mgr.slot_pages(slot)) == singles and mgr.alloc.free_pages == 0
    assert mgr.page_stats == {"full_pages_mapped": 32, "full_pages_in_runs": 0}
    assert mgr.ensure_decode_window([slot], 8) is False
    for pid in held:
        if pid not in singles:
            mgr.alloc.deref(pid)
    assert mgr.ensure_decode_window([slot], 8) is True           # two pages; every group still holds one of the slot's
    assert len(mgr.slot_pages(slot)) == 34 and mgr.page_stats["full_pages_in_runs"] == 0
    mgr.free(slot)
    mgr.check()
    assert mgr.alloc.free_pages == 128 and mgr.alloc.copy_bytes == 0


def test_the_dispatch_span_counts_the_pages_mapped_and_those_in_runs(setup):
    """Every paged engine, on ``nxd.step.decode.dispatch``: the pages the
    table maps when the chunk is dispatched, and those of them in runs of
    adjacent pool pages (here the 50-token prompt's whole groups of four)."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, decode_chunk_size=4, prefix_cache=None, kv_page_size=PS)
    eng.submit(np.arange(1, 51, dtype=np.int32), GenerationConfig(max_new_tokens=6, temperature=0.0))
    seen = overhear(eng, "nxd.step.decode.dispatch", probe=lambda: int((eng.cache._tables != 0).sum()))
    eng.run()
    assert seen and all(stats["full_pages_mapped"] == stats["probe"] for stats in seen)
    assert all(4 <= stats["full_pages_in_runs"] < stats["full_pages_mapped"] for stats in seen)
    assert "window_pages_mapped" not in seen[0]
