"""Prefix-cache KV reuse: the engine's admission path may skip recomputing
a shared prompt prefix, but token streams must stay BIT-IDENTICAL to the
cache-off path for every hit / miss / partial-match / eviction-then-readmit
/ preemption-resume pattern — the reused prefix lands in exactly the
columns (and RoPE positions) a full prefill of the same context would have
produced. The PrefixCache itself is exercised at the unit level too:
trie longest-match, LRU eviction, ref-count pinning, weight-swap
invalidation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, generate
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import (
    PrefixCache,
    RequestState,
    ServingEngine,
)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _solo(model, params, prompt, key, gcfg):
    toks = np.asarray(
        generate(model, params, jnp.asarray(prompt)[None], key, gcfg)
    )[0].tolist()
    if gcfg.eos_token_id is not None and gcfg.eos_token_id in toks:
        toks = toks[: toks.index(gcfg.eos_token_id) + 1]
    return toks


def _shared_workload(cfg, n=6, share=12, seed=0, duplicate_first=True):
    """n prompts sharing a `share`-token system prefix with variable-length
    random tails (partial matches at several tail lengths → several padded
    buckets), plus an exact duplicate of the first prompt (the full-match
    pattern, reuse capped at p-1)."""
    rng = np.random.RandomState(seed)
    shared = rng.randint(1, cfg.vocab_size, size=share).astype(np.int32)
    prompts = [
        np.concatenate([
            shared,
            rng.randint(1, cfg.vocab_size,
                        size=int(rng.randint(2, 8))).astype(np.int32),
        ])
        for _ in range(n)
    ]
    if duplicate_first:
        prompts.append(prompts[0].copy())
    gcfgs = [
        GenerationConfig(max_new_tokens=6, temperature=0.0),
        GenerationConfig(max_new_tokens=9, temperature=0.8, top_k=17),
        GenerationConfig(max_new_tokens=5, temperature=0.0, eos_token_id=5),
        GenerationConfig(max_new_tokens=10, temperature=1.1, top_p=0.9),
        GenerationConfig(max_new_tokens=7, temperature=0.6, top_k=30, top_p=0.95),
        GenerationConfig(max_new_tokens=8, temperature=0.9),
        GenerationConfig(max_new_tokens=8, temperature=0.7, top_k=11),
    ][: len(prompts)]
    keys = [jax.random.PRNGKey(700 + i) for i in range(len(prompts))]
    return prompts, gcfgs, keys


def _run(model, params, prompts, gcfgs, keys, prefix_cache, **kw):
    engine = ServingEngine(
        model, params, num_slots=3, prefix_cache=prefix_cache, **kw
    )
    reqs = [
        engine.submit(p, c, key=k) for p, c, k in zip(prompts, gcfgs, keys)
    ]
    engine.run()
    return engine, reqs


# --- bit-identity acceptance --------------------------------------------------


@pytest.mark.slow  # heavy hit/miss matrix (tier-1 budget, PR 5/13 lean-core
# policy): prefix bit-identity stays tier-1 via
# test_eviction_then_readmit_streams_bit_identical,
# test_exact_resubmit_hits_and_matches, and
# test_preemption_resume_with_prefix_cache_streams_identical
def test_hit_miss_partial_and_full_match_streams_bit_identical(setup):
    """Acceptance: cache-on vs cache-off vs solo generate() on a
    shared-prefix workload — misses (the seeding request), partial matches
    (shared system prefix, distinct tails, multiple padded buckets), and a
    full match (duplicate prompt, reuse capped at p-1) all produce the
    exact same token streams, greedy AND sampled."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _shared_workload(cfg)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    e_off, r_off = _run(model, params, prompts, gcfgs, keys, None)
    e_on, r_on = _run(
        model, params, prompts, gcfgs, keys,
        PrefixCache(max_entries=16, min_match=4),
    )
    for i, (a, b, ref) in enumerate(zip(r_off, r_on, refs)):
        assert a.state is RequestState.DONE and b.state is RequestState.DONE
        assert a.tokens == ref, f"cache-OFF request {i} diverged from solo"
        assert b.tokens == ref, f"cache-ON request {i} diverged"
    snap = e_on.metrics.snapshot()
    assert snap["prefix_hits"] >= len(prompts) - 2  # everything after seeding
    assert snap["prefix_misses"] >= 1
    assert snap["prefix_tokens_reused"] >= 12 * snap["prefix_hits"]
    assert 0 < snap["prefix_hit_rate"] < 1
    # the full-match duplicate reused all but its last token, so reuse
    # exceeds the shared-prefix floor by at least the first prompt's tail
    dup_p = len(prompts[-1])
    assert snap["prefix_tokens_reused"] >= 12 * (snap["prefix_hits"] - 1) + (
        dup_p - 1
    )
    # cache-off engine ran today's exact path: no prefix programs, no events
    off = e_off.metrics.snapshot()
    assert e_off.prefix is None
    assert off["prefix_hits"] == off["prefix_misses"] == 0
    assert e_off.prefix_compilations == 0
    assert e_off.prefill_compilations == len(e_off._prefill_fns)


def test_prefix_cache_size_zero_is_disabled(setup):
    """`prefix_cache=0` restores the legacy path exactly — no store, no
    prefix programs, no counters."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=2, prefix_cache=0)
    assert engine.prefix is None
    req = engine.submit(
        np.arange(1, 14, dtype=np.int32),
        GenerationConfig(max_new_tokens=4, temperature=0.0),
        key=jax.random.PRNGKey(2),
    )
    engine.run()
    assert req.state is RequestState.DONE
    assert engine.prefix_compilations == 0
    assert engine.metrics.snapshot()["prefix_misses"] == 0


@pytest.mark.slow  # heavy eviction A/B variant (tier-1 budget, PR 5/13
# lean-core policy): hit/readmit correctness stays tier-1 via
# test_exact_resubmit_hits_and_matches, pin/release accounting via
# test_paged_cache.py::test_prefix_insert_pins_pages_and_eviction_releases
def test_eviction_then_readmit_streams_bit_identical(setup):
    """Acceptance pattern: a prefix evicted under LRU pressure and then
    re-admitted (miss → full prefill → re-insert) keeps the stream exact,
    and the evictions are counted."""
    cfg, model, params = setup
    rng = np.random.RandomState(3)
    a = rng.randint(1, cfg.vocab_size, size=10).astype(np.int32)
    b = rng.randint(1, cfg.vocab_size, size=11).astype(np.int32)
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.8, top_k=13)
    ref_a = _solo(model, params, a, jax.random.PRNGKey(41), gcfg)
    ref_b = _solo(model, params, b, jax.random.PRNGKey(42), gcfg)
    engine = ServingEngine(
        model, params, num_slots=1,
        prefix_cache=PrefixCache(max_entries=1, min_match=4),
    )
    ra1 = engine.submit(a, gcfg, key=jax.random.PRNGKey(41))
    engine.run()  # seeds entry A
    rb = engine.submit(b, gcfg, key=jax.random.PRNGKey(42))
    engine.run()  # B evicts A (capacity 1)
    ra2 = engine.submit(a, gcfg, key=jax.random.PRNGKey(41))
    engine.run()  # A again: MISS (evicted), full prefill, re-insert
    snap = engine.metrics.snapshot()
    assert snap["prefix_evictions"] >= 2  # A evicted by B, B evicted by A
    assert snap["prefix_hits"] == 0  # nothing ever matched across prompts
    assert ra1.tokens == ref_a and ra2.tokens == ref_a
    assert rb.tokens == ref_b
    assert len(engine.prefix) == 1  # capacity respected throughout


def test_exact_resubmit_hits_and_matches(setup):
    """The same prompt+key resubmitted is the canonical hit: second run
    reuses p-1 tokens and reproduces the identical stream."""
    cfg, model, params = setup
    prompt = np.arange(3, 19, dtype=np.int32)  # 16 tokens
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.9, top_p=0.9)
    ref = _solo(model, params, prompt, jax.random.PRNGKey(77), gcfg)
    engine = ServingEngine(
        model, params, num_slots=2,
        prefix_cache=PrefixCache(max_entries=4, min_match=4),
    )
    r1 = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(77))
    engine.run()
    r2 = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(77))
    engine.run()
    assert r1.tokens == ref and r2.tokens == ref
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] == 1
    assert snap["prefix_tokens_reused"] == len(prompt) - 1


@pytest.mark.slow  # heavy prefix x preemption composition (tier-1
# budget, PR 5/13 lean-core policy): each leg stays tier-1 via
# test_exact_resubmit_hits_and_matches and
# test_engine.py::test_preemption_resumes_token_identical
def test_preemption_resume_with_prefix_cache_streams_identical(setup):
    """Acceptance pattern: eager admission preempts under cursor pressure;
    resumes re-prefill through the prefix cache (the preempted context was
    inserted at admission, so resume is a near-full hit) — sampled streams
    still match solo generate() exactly."""
    cfg0, model0, params = setup
    cfg = tiny_llama(max_seq_len=48)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    gcs = [
        GenerationConfig(max_new_tokens=30, temperature=0.9),
        GenerationConfig(max_new_tokens=20, temperature=0.7, top_k=25),
        GenerationConfig(max_new_tokens=25, temperature=1.1, top_p=0.95),
    ]
    prompts = [
        np.asarray([3, 5, 7, 11], np.int32),
        np.asarray([13, 17, 19, 23], np.int32),
        np.asarray([29, 31, 37, 41], np.int32),
    ]
    refs = [
        _solo(model, params, p, jax.random.PRNGKey(95 + i), gc)
        for i, (p, gc) in enumerate(zip(prompts, gcs))
    ]
    engine = ServingEngine(
        model, params, num_slots=2, admission="eager",
        prefix_cache=PrefixCache(max_entries=16, min_match=2),
    )
    reqs = [
        engine.submit(p, gc, key=jax.random.PRNGKey(95 + i))
        for i, (p, gc) in enumerate(zip(prompts, gcs))
    ]
    engine.run()
    assert engine.metrics.preemptions > 0  # the scenario must preempt
    assert engine.metrics.prefix_hits > 0  # resumes rode the cache
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.tokens == ref, f"request {i} diverged across preemption"


def test_params_swap_invalidates_prefix_store(setup):
    """A weight swap must clear the store — prefix KV computed under the
    old weights serving new-weight traffic would silently corrupt streams
    (the cache-off path recomputes everything)."""
    cfg, model, params = setup
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 1, cfg.vocab_size)
    params2 = model.init(jax.random.PRNGKey(7), ids)
    prompt = np.arange(2, 16, dtype=np.int32)
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    ref2 = _solo(model, params2, prompt, jax.random.PRNGKey(9), gcfg)
    engine = ServingEngine(
        model, params, num_slots=1,
        prefix_cache=PrefixCache(max_entries=4, min_match=4),
    )
    r1 = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(9))
    engine.run()
    assert len(engine.prefix) == 1  # old-weight entry stored
    engine.params = params2
    assert len(engine.prefix) == 0  # swap cleared it
    r2 = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(9))
    engine.run()
    assert r2.tokens == ref2  # new weights, no stale KV
    assert engine.metrics.snapshot()["prefix_evictions"] >= 1


def test_prefix_timeline_events(setup, tmp_path):
    """prefix_hit / prefix_miss instants land on the timeline with
    matched-length args."""
    import json

    from neuronx_distributed_tpu.utils.timeline import Timeline

    cfg, model, params = setup
    trace = tmp_path / "prefix_trace.json"
    tl = Timeline(str(trace))
    engine = ServingEngine(
        model, params, num_slots=1, timeline=tl,
        prefix_cache=PrefixCache(max_entries=4, min_match=4),
    )
    prompt = np.arange(5, 17, dtype=np.int32)
    gcfg = GenerationConfig(max_new_tokens=3, temperature=0.0)
    engine.submit(prompt, gcfg, key=jax.random.PRNGKey(0))
    engine.run()
    engine.submit(prompt, gcfg, key=jax.random.PRNGKey(0))
    engine.run()
    tl.save()
    events = json.loads(trace.read_text())["traceEvents"]
    misses = [e for e in events if e["name"] == "prefix_miss"]
    hits = [e for e in events if e["name"] == "prefix_hit"]
    assert misses and misses[0]["args"]["prompt"] == len(prompt)
    assert hits and hits[0]["args"]["matched"] == len(prompt) - 1
    # prefill spans carry the reused-token count
    prefills = [e for e in events if e["name"] == "nxd.step.prefill"]
    assert any(
        e.get("args", {}).get("reused", 0) > 0 for e in prefills
    )


def test_prefill_latency_stats_in_snapshot(setup):
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=1)
    engine.submit(
        np.arange(1, 10, dtype=np.int32),
        GenerationConfig(max_new_tokens=3, temperature=0.0),
    )
    engine.run()
    snap = engine.metrics.snapshot()
    assert snap["prefill_count"] == 1
    assert snap["prefill_wall_s"] > 0
    assert 0 < snap["prefill_mean_s"] <= snap["prefill_p95_s"] or (
        snap["prefill_mean_s"] == snap["prefill_p95_s"]
    )
    assert snap["prefill_full_wall_s"] == snap["prefill_wall_s"]
    assert snap["prefill_suffix_wall_s"] == 0.0


# --- PrefixCache unit level ---------------------------------------------------


def _dummy_tree(m, bucket=None):
    bucket = bucket or m
    k = jnp.arange(bucket, dtype=jnp.float32).reshape(1, bucket, 1, 1)
    return {
        "layers_0": {
            "attn": {
                "k": k, "v": -k,
                "index": jnp.asarray(m, jnp.int32),
                "kv_valid": jnp.arange(bucket)[None] < m,
            }
        }
    }


def test_trie_longest_match_and_min_match():
    pc = PrefixCache(max_entries=8, min_match=3)
    toks = tuple(range(10, 20))  # 10 tokens
    entry, evicted = pc.insert(toks, _dummy_tree(10), 1.0, 16)
    assert entry is not None and evicted == 0
    # full-length context: capped at p-1
    hit = pc.lookup(list(toks))
    assert hit is not None and hit[1] == 9
    # extension of the stored path: full 10-token reuse
    hit = pc.lookup(list(toks) + [99, 98])
    assert hit is not None and hit[1] == 10
    # divergence at depth 5: partial reuse of the stored entry
    hit = pc.lookup(list(toks[:5]) + [1, 2, 3])
    assert hit is not None and hit[1] == 5
    assert hit[0] is entry  # the same entry serves the shorter prefix
    # below min_match: miss
    assert pc.lookup(list(toks[:2]) + [7]) is None
    assert pc.match_len(list(toks[:2]) + [7]) == 0
    assert pc.match_len(list(toks) + [99]) == 10
    # insert covered by an existing longer entry is skipped
    again, _ = pc.insert(toks[:6], _dummy_tree(6), 2.0, 8)
    assert again is None
    assert len(pc) == 1


def test_lru_eviction_respects_pins():
    pc = PrefixCache(max_entries=2, min_match=2)
    e1, _ = pc.insert((1, 2, 3), _dummy_tree(3), 1.0, 4)
    e2, _ = pc.insert((4, 5, 6), _dummy_tree(3), 2.0, 4)
    pc.pin(e1)  # e1 backs an in-flight suffix prefill
    e3, evicted = pc.insert((7, 8, 9), _dummy_tree(3), 3.0, 4)
    assert evicted == 1
    assert e1.tokens in pc._lru  # pinned LRU entry SURVIVED
    assert e2.tokens not in pc._lru  # the unpinned one went
    pc.release(e1)
    e4, evicted = pc.insert((2, 4, 6), _dummy_tree(3), 4.0, 4)
    assert evicted == 1
    assert e1.tokens not in pc._lru  # released → evictable again
    # all pinned: overflow rather than corrupt an in-flight admission
    for e in pc.entries:
        pc.pin(e)
    e5, evicted = pc.insert((9, 9, 9), _dummy_tree(3), 5.0, 4)
    assert e5 is not None and evicted == 0
    assert len(pc) == 3  # temporarily over capacity
    pc.release_all()
    assert all(e.refs == 0 for e in pc.entries)


def test_evict_prunes_trie():
    pc = PrefixCache(max_entries=8, min_match=2)
    e1, _ = pc.insert((1, 2, 3, 4), _dummy_tree(4), 1.0, 4)
    e2, _ = pc.insert((1, 2, 9), _dummy_tree(3), 2.0, 4)
    assert pc.evict_entry(e1)
    assert not pc.evict_entry(e1)  # already gone
    # shared (1, 2) chain survives for e2; the (3, 4) branch is pruned
    hit = pc.lookup([1, 2, 9, 5])
    assert hit is not None and hit[0] is e2 and hit[1] == 3
    assert pc.lookup([1, 2, 3, 4, 5]) is not None  # (1,2) still matches via e2
    assert pc.lookup([1, 2, 3, 4, 5])[1] == 2
    assert pc.evict_entry(e2)
    assert len(pc) == 0
    assert pc.lookup([1, 2, 9, 5]) is None
    assert not pc._root.children  # trie fully pruned


def test_disabled_cache_is_inert():
    pc = PrefixCache(max_entries=0)
    assert not pc.enabled
    assert pc.insert((1, 2, 3, 4, 5, 6, 7, 8), _dummy_tree(8), 1.0, 8) == (None, 0)
    assert pc.lookup(list(range(8))) is None
    assert pc.match_len(list(range(8))) == 0
    assert len(pc) == 0
