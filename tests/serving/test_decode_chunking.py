"""The device-resident chunked decode hot path: fused multi-token chunks
must be a pure re-batching of the same program — every stream bit-identical
to chunk=1 and to solo ``generate()`` under staggered admission, EOS
mid-chunk, and preemption/resume — while the host pays exactly ONE
synchronization per chunk and the donated cache/state buffers update in
place (no pytree copies)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, generate
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import RequestState, ServingEngine
from neuronx_distributed_tpu.serving.engine import _bucket


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


def _workload(cfg, n=6, seed=21):
    rng = np.random.RandomState(seed)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=rng.randint(3, 14)).astype(np.int32)
        for _ in range(n)
    ]
    gcfgs = [
        GenerationConfig(max_new_tokens=6, temperature=0.0),
        GenerationConfig(max_new_tokens=13, temperature=0.8, top_k=17),
        GenerationConfig(max_new_tokens=4, temperature=0.0, eos_token_id=5),
        GenerationConfig(max_new_tokens=12, temperature=1.1, top_p=0.9),
        GenerationConfig(max_new_tokens=9, temperature=0.6, top_k=30, top_p=0.95),
        GenerationConfig(max_new_tokens=10, temperature=0.9),
    ][:n]
    keys = [jax.random.PRNGKey(300 + i) for i in range(n)]
    return prompts, gcfgs, keys


def _serve(model, params, prompts, gcfgs, keys, chunk, upfront=2, **kw):
    """Staggered open-loop run: `upfront` requests submitted cold, the rest
    trickled in mid-flight (admissions land at chunk boundaries)."""
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=chunk, **kw
    )
    reqs = [
        engine.submit(prompts[i], gcfgs[i], key=keys[i])
        for i in range(upfront)
    ]
    i = upfront
    while engine.has_work or i < len(prompts):
        engine.step()
        if i < len(prompts):
            reqs.append(engine.submit(prompts[i], gcfgs[i], key=keys[i]))
            i += 1
    engine.run()
    return engine, reqs


@pytest.mark.slow  # heavy staggered A/B variant (tier-1 budget, PR 5/13
# lean-core policy): chunked bit-identity stays tier-1 via
# test_odd_chunk_size_matches, test_eos_mid_chunk_freezes_slot...,
# and test_preemption_resume_chunked_streams_identical
def test_chunked_streams_bit_identical_staggered(setup):
    """Acceptance: chunk=8 vs chunk=1 vs solo generate() — token streams
    bit-identical for a staggered stream of mixed greedy/sampled/EOS
    requests through 2 slots, with exactly one decode compilation per
    chunk size and ~chunk-fold fewer host syncs."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _workload(cfg)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    engines = {}
    for chunk in (1, 8):
        engine, reqs = _serve(model, params, prompts, gcfgs, keys, chunk)
        for i, (req, ref) in enumerate(zip(reqs, refs)):
            assert req.state is RequestState.DONE
            assert req.tokens == ref, f"chunk={chunk} request {i} diverged"
        assert engine.decode_compilations == 1
        engines[chunk] = engine
    # same emitted tokens, ~8x fewer dispatches (== host syncs)
    m1, m8 = engines[1].metrics, engines[8].metrics
    assert m1.decode_tokens == m8.decode_tokens
    assert m8.chunks < m1.chunks
    assert m8.chunks <= -(-m1.steps // 8) + len(prompts)  # boundary slack


def test_odd_chunk_size_matches(setup):
    """A chunk size that never divides the generation lengths exercises the
    mid-chunk freeze on every request."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _workload(cfg, n=4, seed=5)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    engine, reqs = _serve(model, params, prompts, gcfgs, keys, chunk=3)
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.tokens == ref, f"chunk=3 request {i} diverged"
    assert engine.decode_compilations == 1


def test_eos_mid_chunk_freezes_slot_without_disturbing_neighbour(setup):
    """EOS landing mid-chunk freezes that slot ON DEVICE (write mask) for
    the remainder of the chunk; its neighbour's stream is untouched and the
    host discards the frozen slot's filler tail."""
    cfg, model, params = setup
    gcfg_free = GenerationConfig(max_new_tokens=10, temperature=0.0)
    prompt = np.asarray([3, 5, 7, 11, 13], np.int32)
    free_run = _solo(model, params, prompt, jax.random.PRNGKey(9), gcfg_free)
    eos = free_run[3]  # EOS at token 4 of 10 — inside the first chunk of 8
    gcfg_eos = GenerationConfig(
        max_new_tokens=10, temperature=0.0, eos_token_id=eos
    )
    other = np.asarray([17, 19, 23, 29, 31, 37, 41], np.int32)
    ref_other = _solo(model, params, other, jax.random.PRNGKey(10), gcfg_free)

    engine = ServingEngine(model, params, num_slots=2, decode_chunk_size=8)
    r_eos = engine.submit(prompt, gcfg_eos, key=jax.random.PRNGKey(9))
    r_other = engine.submit(other, gcfg_free, key=jax.random.PRNGKey(10))
    engine.run()
    assert r_eos.tokens == free_run[:4]  # stopped AT its eos, tail discarded
    assert r_eos.tokens[-1] == eos
    assert r_other.tokens == ref_other  # neighbour bit-identical


def test_preemption_resume_chunked_streams_identical(setup):
    """Eager admission with chunk=8 runs the cursor into the on-device
    clamp, preempts at the chunk boundary, re-prefills — sampled streams
    still match solo generate() exactly (device-held keys are pulled
    per-slot at preemption, frozen at each slot's true position)."""
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
        model, params, num_slots=2, admission="eager", decode_chunk_size=8
    )
    reqs = [
        engine.submit(p, gc, key=jax.random.PRNGKey(95 + i))
        for i, (p, gc) in enumerate(zip(prompts, gcs))
    ]
    engine.run()
    assert engine.metrics.preemptions > 0  # the scenario must preempt
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.tokens == ref, f"request {i} diverged across preemption"
    assert engine.decode_compilations == 1


def test_single_host_sync_per_chunk(setup):
    """Acceptance: between admission events a decode chunk performs exactly
    ONE host synchronization (the token-block device_get) — no per-token
    mirror pulls, no key readbacks."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=2, decode_chunk_size=8)
    engine.submit(
        np.asarray([2, 3, 4, 5], np.int32),
        GenerationConfig(max_new_tokens=30, temperature=0.7),
        key=jax.random.PRNGKey(1),
    )
    engine.step()  # admission + prefill + first chunk (compiles)
    real_get = jax.device_get
    calls = []

    def counting_get(x):
        calls.append(x)
        return real_get(x)

    jax.device_get = counting_get
    try:
        engine.step()  # steady-state chunk: no admission, no finish
    finally:
        jax.device_get = real_get
    assert len(calls) == 1, f"expected 1 host sync, saw {len(calls)}"
    # 8 tokens rode that single sync
    assert engine.metrics.chunks == 2
    assert len(engine.scheduler.get(0).tokens) == 1 + 8 + 8


def test_donated_cache_and_state_consumed(setup):
    """Acceptance: the decode jit donates the KV cache and slot state —
    after a chunk the previous buffers are DELETED (aliased in place), not
    copied; same for the cache-manager's admit/free programs."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=2, decode_chunk_size=8)
    req = engine.submit(
        np.asarray([2, 3, 4], np.int32),
        GenerationConfig(max_new_tokens=20, temperature=0.0),
    )
    engine.step()  # admit + first chunk
    old_cache_leaves = jax.tree_util.tree_leaves(engine.cache.cache)
    old_keys = engine._state["keys"]
    engine.step()  # pure decode chunk
    assert all(leaf.is_deleted() for leaf in old_cache_leaves), (
        "decode chunk copied the cache pytree instead of donating it"
    )
    assert old_keys.is_deleted(), "slot state was copied, not donated"
    # the free path donates too: finish the request, old buffers consumed
    old_cache_leaves = jax.tree_util.tree_leaves(engine.cache.cache)
    engine.run()
    assert req.state is RequestState.DONE
    assert all(leaf.is_deleted() for leaf in old_cache_leaves)


def test_failed_dispatch_recovers_without_raising(setup):
    """A decode dispatch that raises routes through the recovery state
    machine (serving robustness layer): the in-flight request is requeued
    with its tokens and key intact, the salvaged cache storage survives
    (the buffers were not consumed), and the resumed stream is exactly the
    solo generate() stream — the failure never escapes step()."""
    cfg, model, params = setup
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    prompt = np.asarray([2, 3, 4], np.int32)
    ref = _solo(model, params, prompt, jax.random.PRNGKey(0), gcfg)
    engine = ServingEngine(model, params, num_slots=2, decode_chunk_size=2,
                           sleep_fn=lambda s: None)
    req = engine.submit(prompt, gcfg)  # default key = PRNGKey(rid=0)
    engine.step()
    real = engine._decode_chunk

    def boom(*a, **k):
        raise RuntimeError("injected dispatch failure")

    engine._decode_chunk = boom
    engine.step()  # failure handled, not raised
    engine._decode_chunk = real
    assert engine.cache.cache is not None  # unconsumed storage salvaged
    assert req.state is RequestState.QUEUED  # requeued, tokens kept
    assert engine.metrics.dispatch_retries == 1
    engine.run()
    assert req.state is RequestState.DONE
    assert req.tokens == ref
    # KeyboardInterrupt is the operator's, not a fault: it escapes with the
    # cache reference restored (recovery is for Exception only)
    req2 = engine.submit(prompt, GenerationConfig(max_new_tokens=8))

    def interrupt(*a, **k):
        raise KeyboardInterrupt

    engine.step()  # admit req2
    engine._decode_chunk = interrupt
    with pytest.raises(KeyboardInterrupt):
        engine.step()
    engine._decode_chunk = real
    assert engine.cache.cache is not None
    engine.run()
    assert req2.state is RequestState.DONE


def test_mid_chunk_cancel_does_not_inflate_decode_tokens(setup):
    """Regression (review): tokens the device computed past a mid-chunk
    cancellation are discarded by the host and must not count as
    decode_tokens (which would inflate chunk tok/s vs tokens delivered)."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=1, decode_chunk_size=8)
    req = engine.submit(
        np.asarray([6, 7, 8], np.int32),
        GenerationConfig(max_new_tokens=20, temperature=0.0),
        key=jax.random.PRNGKey(11),
        on_token=lambda r, t: len(r.tokens) == 3 and engine.cancel(r.rid),
    )
    engine.run()
    assert req.state is RequestState.CANCELLED
    assert len(req.tokens) == 3  # tok0 + 2 delivered decode tokens
    assert engine.metrics.decode_tokens == 2  # not the chunk's device 8


def test_prefill_compilations_bounded_by_buckets(setup):
    """Satellite: ``prefill_compilations`` counts one program per padded
    bucket actually used — growth is bounded by the number of distinct
    ``_bucket`` outputs, never by the number of requests."""
    cfg, model, params = setup
    rng = np.random.RandomState(17)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
        for n in (3, 5, 6, 9, 11, 13, 4, 7)
    ]
    gcfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    engine = ServingEngine(model, params, num_slots=2, decode_chunk_size=4)
    for i, p in enumerate(prompts):
        engine.submit(p, gcfg, key=jax.random.PRNGKey(40 + i))
    engine.run()
    expected_buckets = {
        _bucket(len(p), cfg.max_seq_len, gcfg.max_new_tokens) for p in prompts
    }
    assert set(engine._prefill_fns) <= expected_buckets
    assert len(engine._prefill_fns) <= len(expected_buckets)
    assert engine.prefill_compilations == len(engine._prefill_fns)
    # each bucket's program compiled exactly once (fixed shapes inside)
    assert all(
        int(fn._cache_size()) == 1 for fn in engine._prefill_fns.values()
    )


def test_params_rebind_takes_effect(setup):
    """Regression (review): binding params once at construction must not
    freeze them forever — assigning ``engine.params`` rebinds the pytree
    the jitted programs receive, so a weight swap changes the very next
    request's stream (and still costs nothing per step)."""
    cfg, model, params = setup
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 1, cfg.vocab_size)
    params2 = model.init(jax.random.PRNGKey(7), ids)
    prompt = np.asarray([4, 6, 8, 10], np.int32)
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    ref1 = _solo(model, params, prompt, jax.random.PRNGKey(3), gcfg)
    ref2 = _solo(model, params2, prompt, jax.random.PRNGKey(3), gcfg)
    engine = ServingEngine(model, params, num_slots=1, decode_chunk_size=4)
    r1 = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(3))
    engine.run()
    engine.params = params2  # hot weight swap between requests
    r2 = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(3))
    engine.run()
    assert r1.tokens == ref1
    assert r2.tokens == ref2
    assert engine.decode_compilations == 1  # same program, new weights


def test_chunk_metrics_accounting(setup):
    """Chunk metrics: dispatch/readback spans accumulate, steps count the
    executed scan steps (not chunk * chunks when slots freeze early), and
    emitted tokens agree with the streams."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=2, decode_chunk_size=8)
    r = engine.submit(
        np.asarray([5, 6, 7], np.int32),
        GenerationConfig(max_new_tokens=5, temperature=0.0),
    )
    engine.run()
    m = engine.metrics
    snap = m.snapshot()
    assert r.state is RequestState.DONE
    assert snap["chunks"] == 1  # 4 decode tokens fit one chunk of 8
    assert m.steps == 4  # on-device freeze stopped the scan at 4 used steps
    assert snap["decode_tokens"] == 4
    assert snap["decode_dispatch_s"] >= 0.0
    assert snap["decode_readback_s"] >= 0.0
    assert snap["chunk_tokens_per_sec"] > 0
    # cursor advanced exactly `used` columns, same as 4 single steps
    assert engine.metrics.cursor_high_water == 8 + 4  # bucket(3) + used


def test_chunked_throughput_beats_single_step(setup):
    """A sustained decode workload at chunk=8 emits the same tokens as at
    chunk=1 with at most a quarter of the decode dispatches per emitted
    token (each dispatch is one host sync: what the chunk amortizes,
    counted off the engine's program ledger; how much wall that buys is
    the chip's to say, ``tpot_mean_ms`` in every serve cell)."""
    cfg, model, params = setup
    gcfg = GenerationConfig(max_new_tokens=48, temperature=0.8, top_k=20)
    prompts = [
        np.asarray([3 + i, 5, 7, 11], np.int32) for i in range(4)
    ]
    per_token = {}
    for chunk in (1, 8):
        engine = ServingEngine(
            model, params, num_slots=4, decode_chunk_size=chunk
        )
        reqs = [
            engine.submit(p, gcfg, key=jax.random.PRNGKey(10 + i))
            for i, p in enumerate(prompts)
        ]
        engine.run()
        emitted = sum(len(r.tokens) for r in reqs)
        assert emitted == len(prompts) * gcfg.max_new_tokens
        per_token[chunk] = (
            engine.programs.dispatches("decode_chunk") / emitted
        )
    assert per_token[8] <= per_token[1] / 4, per_token
