"""ServingEngine: continuous batching must be a SCHEDULER around the same
program `generate()` runs, not a different generator — every request's token
stream is asserted identical to its solo `generate()` call, under slot churn,
staggered arrivals, mixed per-request sampling configs, and preemption. The
fixed-shape invariant (exactly ONE decode-step compilation) and the metrics
contract ride the same scenarios."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, generate
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import RequestState, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _solo(model, params, prompt, key, gcfg):
    """Golden: per-request generate(), truncated at EOS like the engine
    retires a slot (generate fills the tail with EOS instead)."""
    toks = np.asarray(
        generate(model, params, jnp.asarray(prompt)[None], key, gcfg)
    )[0].tolist()
    if gcfg.eos_token_id is not None and gcfg.eos_token_id in toks:
        toks = toks[: toks.index(gcfg.eos_token_id) + 1]
    return toks


def _prompts(rng, n, lo=3, hi=14, vocab=256):
    return [
        rng.randint(1, vocab, size=rng.randint(lo, hi)).astype(np.int32)
        for _ in range(n)
    ]


@pytest.mark.slow  # heavy staggered A/B variant (tier-1 budget, PR 5/13
# lean-core policy): staggered engine-vs-generate equality stays tier-1 via
# test_sched_engine.py::test_slo_engine_streams_bit_identical_to_fifo_and_generate,
# per-slot retirement via test_per_slot_eos_and_max_new_tokens
def test_staggered_stream_matches_generate(setup):
    """Acceptance: a staggered stream of 8 variable-length requests through
    a 4-slot engine is token-identical to per-request generate() — greedy
    AND sampled configs (the per-row sampler + per-request key evolution
    reproduce `sample`'s stream bit-for-bit) — with exactly one decode-step
    compilation and non-degenerate metrics."""
    cfg, model, params = setup
    rng = np.random.RandomState(3)
    prompts = _prompts(rng, 8, vocab=cfg.vocab_size)
    gcfgs = [
        GenerationConfig(max_new_tokens=6, temperature=0.0),
        GenerationConfig(max_new_tokens=9, temperature=0.8, top_k=17),
        GenerationConfig(max_new_tokens=4, temperature=0.0, eos_token_id=5),
        GenerationConfig(max_new_tokens=12, temperature=1.1, top_p=0.9),
        GenerationConfig(max_new_tokens=7, temperature=0.0),
        GenerationConfig(max_new_tokens=10, temperature=0.6, top_k=30, top_p=0.95),
        GenerationConfig(max_new_tokens=5, temperature=0.0, eos_token_id=7),
        GenerationConfig(max_new_tokens=8, temperature=0.9),
    ]
    keys = [jax.random.PRNGKey(100 + i) for i in range(8)]
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]

    engine = ServingEngine(model, params, num_slots=4)
    reqs = [engine.submit(prompts[i], gcfgs[i], key=keys[i]) for i in range(3)]
    i = 3
    while engine.has_work or i < 8:  # trickle the rest in mid-flight
        engine.step()
        if i < 8:
            reqs.append(engine.submit(prompts[i], gcfgs[i], key=keys[i]))
            i += 1
    engine.run()

    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.state is RequestState.DONE
        assert req.tokens == ref, f"request {i} diverged from generate()"
    assert engine.decode_compilations == 1

    snap = engine.metrics.snapshot()
    assert snap["completed"] == 8
    assert snap["prefills"] == 8
    assert 0 < snap["mean_occupancy"] <= 4
    assert snap["mean_ttft"] > 0
    assert snap["mean_decode_tokens_per_sec"] > 0
    for req in reqs:
        r = engine.metrics.request_snapshot(req.rid)
        assert 0 <= r["ttft"] <= r["latency"]
        assert r["queue_wait"] <= r["ttft"]


@pytest.mark.slow  # heavy lifecycle variant (tier-1 budget, PR 5/13
# lean-core policy): slot retire/reuse legs stay tier-1 via
# test_per_slot_eos_and_max_new_tokens, test_cancel_queued_and_running,
# and test_preemption_resumes_token_identical
def test_slot_reuse_and_lifecycle(setup):
    """More requests than slots: slots free and re-admit (QUEUED→PREFILL→
    DECODE→DONE), every stream still exact."""
    cfg, model, params = setup
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, 6, vocab=cfg.vocab_size)
    gcfg = GenerationConfig(max_new_tokens=5, temperature=0.0)
    refs = [
        _solo(model, params, p, jax.random.PRNGKey(50 + i), gcfg)
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(model, params, num_slots=2)
    reqs = [
        engine.submit(p, gcfg, key=jax.random.PRNGKey(50 + i))
        for i, p in enumerate(prompts)
    ]
    assert all(r.state is RequestState.QUEUED for r in reqs[2:])
    engine.run()
    for req, ref in zip(reqs, refs):
        assert req.state is RequestState.DONE
        assert req.tokens == ref
    # 6 requests through 2 slots — reuse must have happened, decode program
    # compiled once regardless
    assert engine.metrics.prefills == 6
    assert engine.decode_compilations == 1
    assert engine.cache.free_slots == 2


def test_per_slot_eos_and_max_new_tokens(setup):
    """EOS and max_new_tokens are honored PER SLOT inside the shared decode
    step: a row hitting its own EOS retires without disturbing neighbours."""
    cfg, model, params = setup
    gcfg_free = GenerationConfig(max_new_tokens=10, temperature=0.0)
    prompt = np.asarray([3, 5, 7, 11, 13], np.int32)
    free_run = _solo(model, params, prompt, jax.random.PRNGKey(9), gcfg_free)
    # force EOS mid-stream for one request; its neighbour runs unconstrained
    eos = free_run[3]
    gcfg_eos = GenerationConfig(
        max_new_tokens=10, temperature=0.0, eos_token_id=eos
    )
    other = np.asarray([17, 19, 23, 29, 31, 37, 41], np.int32)
    ref_other = _solo(model, params, other, jax.random.PRNGKey(10), gcfg_free)

    engine = ServingEngine(model, params, num_slots=4)
    r_eos = engine.submit(prompt, gcfg_eos, key=jax.random.PRNGKey(9))
    r_other = engine.submit(other, gcfg_free, key=jax.random.PRNGKey(10))
    engine.run()
    assert r_eos.tokens == free_run[:4]  # stopped AT its eos
    assert r_eos.tokens[-1] == eos
    assert len(r_other.tokens) == 10  # neighbour unaffected
    assert r_other.tokens == ref_other


def test_preemption_resumes_token_identical(setup):
    """Eager admission runs the shared cursor into max_seq_len; the engine
    preempts, rewinds the cache, re-prefills each request's context — and
    the streams still match solo generate() exactly."""
    cfg0, model0, params = setup
    cfg = tiny_llama(max_seq_len=48)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    gc_long = GenerationConfig(max_new_tokens=30, temperature=0.0)
    gc_mid = GenerationConfig(max_new_tokens=20, temperature=0.0)
    gc_late = GenerationConfig(max_new_tokens=25, temperature=0.0)
    prompts = [
        np.asarray([3, 5, 7, 11], np.int32),
        np.asarray([13, 17, 19, 23], np.int32),
        np.asarray([29, 31, 37, 41], np.int32),
    ]
    gcs = [gc_long, gc_mid, gc_late]
    refs = [
        _solo(model, params, p, jax.random.PRNGKey(60 + i), gc)
        for i, (p, gc) in enumerate(zip(prompts, gcs))
    ]
    engine = ServingEngine(model, params, num_slots=2, admission="eager")
    reqs = [
        engine.submit(p, gc, key=jax.random.PRNGKey(60 + i))
        for i, (p, gc) in enumerate(zip(prompts, gcs))
    ]
    engine.run()
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.tokens == ref, f"request {i} diverged across preemption"
    assert engine.metrics.preemptions > 0
    assert engine.decode_compilations == 1
    assert max(r.preemptions for r in reqs) > 0


@pytest.mark.slow  # heavy sampled-preemption A/B variant (tier-1 budget,
# PR 5/13 lean-core policy): the greedy preempt+resume leg stays tier-1 via
# test_preemption_resumes_token_identical
def test_preemption_with_sampling_keeps_key_streams_independent(setup):
    """Regression: req.key once aliased a VIEW of the engine's key mirror,
    so re-admission into a different slot after preemption overwrote a
    neighbour's key and silently corrupted its SAMPLED stream (greedy
    masked it). Non-zero temperatures across a preemption must still match
    solo generate() exactly."""
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
    engine = ServingEngine(model, params, num_slots=2, admission="eager")
    reqs = [
        engine.submit(p, gc, key=jax.random.PRNGKey(95 + i))
        for i, (p, gc) in enumerate(zip(prompts, gcs))
    ]
    engine.run()
    assert engine.metrics.preemptions > 0  # the scenario must actually preempt
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.tokens == ref, f"sampled request {i} diverged"


def test_submit_over_budget_footprint_raises(setup):
    """Regression: a footprint larger than max_tokens_in_flight could never
    be admitted — it used to queue forever and livelock run()."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=2, max_tokens_in_flight=20)
    with pytest.raises(ValueError, match="max_tokens_in_flight"):
        engine.submit(
            np.arange(1, 16, dtype=np.int32),
            GenerationConfig(max_new_tokens=10),
        )


def test_callback_cancel_wins_over_finish(setup):
    """Regression: a cancel() issued from an on_token callback on the very
    token that also satisfies max_new_tokens must leave the request
    CANCELLED (not DONE) and keep the metrics consistent."""
    cfg, model, params = setup
    gcfg = GenerationConfig(max_new_tokens=3, temperature=0.0)
    engine = ServingEngine(model, params, num_slots=1)
    req = engine.submit(
        np.asarray([2, 3, 4], np.int32), gcfg, key=jax.random.PRNGKey(8),
        on_token=lambda r, t: len(r.tokens) == 3 and engine.cancel(r.rid),
    )
    engine.run()
    assert req.state is RequestState.CANCELLED
    assert engine.metrics.cancelled == 1
    assert engine.metrics.completed == 0
    assert engine.cache.free_slots == 1


def test_callback_cancel_on_first_token_wins(setup):
    """Regression: a cancel() issued from the on_token callback on the
    FIRST (prefill-sampled) token used to be erased by the DECODE state
    transition — the request would decode its whole stream and count as
    both cancelled AND completed."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=2)
    req = engine.submit(
        np.asarray([2, 3, 4], np.int32),
        GenerationConfig(max_new_tokens=8, temperature=0.0),
        key=jax.random.PRNGKey(8),
        on_token=lambda r, t: engine.cancel(r.rid),
    )
    engine.run()
    assert req.state is RequestState.CANCELLED
    assert len(req.tokens) == 1  # nothing decoded past the cancel
    assert engine.metrics.cancelled == 1
    assert engine.metrics.completed == 0
    assert engine.cache.free_slots == 2  # the acquired slot was returned


def test_cancel_queued_drops_callback(setup):
    """Regression: cancelling a still-queued request must drop its
    on_token callback (queued requests never reach _release_slot, so the
    entry used to leak for the engine's lifetime)."""
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=1)
    blocker = engine.submit(
        np.asarray([1, 2], np.int32),
        GenerationConfig(max_new_tokens=6, temperature=0.0),
    )
    engine.step()  # blocker occupies the only slot
    queued = engine.submit(
        np.asarray([3, 4], np.int32),
        GenerationConfig(max_new_tokens=6, temperature=0.0),
        on_token=lambda r, t: None,
    )
    assert queued.rid in engine._on_token
    assert engine.cancel(queued.rid)
    assert queued.rid not in engine._on_token
    engine.run()
    assert blocker.state is RequestState.DONE


@pytest.mark.slow  # heavy admission A/B variant (tier-1 budget, PR 5/13
# lean-core policy): conservative admission under pressure stays tier-1 via
# test_paged_cache.py::test_conservative_admission_queues_on_page_pressure
def test_conservative_admission_never_preempts(setup):
    """Default policy defers admission instead of overrunning the cache —
    the preemption counter stays 0."""
    cfg0, model0, params = setup
    cfg = tiny_llama(max_seq_len=48)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    gc = GenerationConfig(max_new_tokens=20, temperature=0.0)
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, 5, lo=4, hi=16, vocab=cfg.vocab_size)
    refs = [
        _solo(model, params, p, jax.random.PRNGKey(70 + i), gc)
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(model, params, num_slots=3)
    reqs = [
        engine.submit(p, gc, key=jax.random.PRNGKey(70 + i))
        for i, p in enumerate(prompts)
    ]
    engine.run()
    for req, ref in zip(reqs, refs):
        assert req.tokens == ref
    assert engine.metrics.preemptions == 0


def test_long_prompt_cursor_jump_does_not_strand_running_slots(setup):
    """A long prompt arriving mid-flight jumps the shared cursor past the
    running slots' columns; conservative admission must account for THEIR
    remaining generation too (cursor's final resting place = admission
    cursor + longest remaining in flight), or defer — never preempt."""
    cfg0, model0, params = setup
    cfg = tiny_llama(max_seq_len=48)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    gc_long = GenerationConfig(max_new_tokens=30, temperature=0.0)
    gc_short = GenerationConfig(max_new_tokens=8, temperature=0.0)
    a_prompt = np.asarray([3, 5, 7, 11], np.int32)
    b_prompt = np.arange(1, 21, dtype=np.int32)  # bucket pads to 32
    ref_a = _solo(model, params, a_prompt, jax.random.PRNGKey(90), gc_long)
    ref_b = _solo(model, params, b_prompt, jax.random.PRNGKey(91), gc_short)
    engine = ServingEngine(model, params, num_slots=2)
    ra = engine.submit(a_prompt, gc_long, key=jax.random.PRNGKey(90))
    for _ in range(4):  # let A run a few steps before B arrives
        engine.step()
    rb = engine.submit(b_prompt, gc_short, key=jax.random.PRNGKey(91))
    engine.run()
    assert ra.tokens == ref_a
    assert rb.tokens == ref_b
    assert engine.metrics.preemptions == 0  # B deferred, never admitted hot


def test_cancel_queued_and_running(setup):
    cfg, model, params = setup
    gcfg = GenerationConfig(max_new_tokens=12, temperature=0.0)
    prompts = _prompts(np.random.RandomState(13), 4, vocab=cfg.vocab_size)
    engine = ServingEngine(model, params, num_slots=2)
    reqs = [
        engine.submit(p, gcfg, key=jax.random.PRNGKey(80 + i))
        for i, p in enumerate(prompts)
    ]
    engine.step()  # admits the first two
    assert reqs[0].state is RequestState.DECODE
    assert engine.cancel(reqs[0].rid)  # running
    assert engine.cancel(reqs[3].rid)  # still queued
    engine.run()
    assert reqs[0].state is RequestState.CANCELLED
    assert reqs[3].state is RequestState.CANCELLED
    assert reqs[1].state is RequestState.DONE
    assert reqs[2].state is RequestState.DONE
    assert engine.metrics.cancelled == 2
    assert not engine.cancel(reqs[1].rid)  # finished: not cancellable
    assert engine.cache.free_slots == 2


def test_submit_infeasible_raises(setup):
    cfg, model, params = setup
    engine = ServingEngine(model, params, num_slots=2)
    long_prompt = np.arange(1, cfg.max_seq_len, dtype=np.int32)
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.submit(long_prompt, GenerationConfig(max_new_tokens=8))
    with pytest.raises(ValueError, match="empty"):
        engine.submit(np.asarray([], np.int32), GenerationConfig())


def test_max_new_tokens_one_retires_at_prefill(setup):
    cfg, model, params = setup
    gcfg = GenerationConfig(max_new_tokens=1, temperature=0.0)
    prompt = np.asarray([2, 4, 6, 8], np.int32)
    ref = _solo(model, params, prompt, jax.random.PRNGKey(5), gcfg)
    engine = ServingEngine(model, params, num_slots=2)
    req = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(5))
    engine.step()
    assert req.state is RequestState.DONE
    assert req.tokens == ref
    assert engine.metrics.steps == 0  # never needed a decode step


def test_on_token_streaming_callback(setup):
    cfg, model, params = setup
    gcfg = GenerationConfig(max_new_tokens=5, temperature=0.0)
    prompt = np.asarray([9, 8, 7], np.int32)
    seen = []
    engine = ServingEngine(model, params, num_slots=1)
    req = engine.submit(
        prompt, gcfg, key=jax.random.PRNGKey(6),
        on_token=lambda r, t: seen.append((r.rid, t)),
    )
    engine.run()
    assert [t for _, t in seen] == req.tokens
    assert all(rid == req.rid for rid, _ in seen)


def test_timeline_wiring(setup, tmp_path):
    """With a Timeline attached, the engine's ``nxd.step*`` spans (prefill,
    decode dispatch/readback/emit; the emit carrying the chunk's delivered
    tokens as args) and occupancy counters land in valid Chrome-trace JSON."""
    import json

    from neuronx_distributed_tpu.utils.timeline import Timeline

    cfg, model, params = setup
    trace = tmp_path / "serving_trace.json"
    tl = Timeline(str(trace))
    engine = ServingEngine(model, params, num_slots=2, timeline=tl)
    engine.submit(
        np.asarray([1, 2, 3], np.int32),
        GenerationConfig(max_new_tokens=4, temperature=0.0),
    )
    engine.run()
    tl.save()
    events = json.loads(trace.read_text())["traceEvents"]
    names = {e["name"] for e in events}
    assert "nxd.step.decode.dispatch" in names and "nxd.step.prefill" in names
    assert "slots_active" in names  # counter track
    readbacks = [e for e in events if e["name"] == "nxd.step.decode.readback"]
    assert readbacks  # the one host sync per chunk is a first-class span
    assert all(e["args"]["steps"] > 0 for e in readbacks)
    emits = [e for e in events if e["name"] == "nxd.step.decode.emit"]
    assert sum(e["args"]["delivered"] for e in emits) == 3  # 4 - first
    assert "chunk_tokens" in names  # per-chunk counter track


def _prefill_spans(tl, path):
    import json

    tl.save()
    return [e["args"] for e in json.loads(path.read_text())["traceEvents"] if e["name"] == "nxd.step.prefill"]


def test_building_an_engine_traces_the_model_zero_times(setup, tmp_path):
    """Set-up pays for no trace of the model (PR 48 was refused for ONE
    abstract trace in ``__init__``: 1.5-4.9 s of every serving cell's
    ``setup_s``): from construction to the first ``submit`` the model's
    ``__call__`` is never entered, and the engine has no resolved key that
    would need it. The first prefill of a bucket traces its program once, and
    stat ``head_rows`` of ``nxd.step.prefill`` is that trace's own output
    shape: 1, on the bucket's first prefill and on every later one."""
    from unittest import mock

    from neuronx_distributed_tpu.utils.timeline import Timeline

    cfg, model, params = setup
    calls = []
    real = LlamaForCausalLM.__call__

    def counted(self, *args, **kw):
        calls.append(self.mode)
        return real(self, *args, **kw)

    path = tmp_path / "trace.json"
    tl = Timeline(str(path))
    gcfg = GenerationConfig(max_new_tokens=3, temperature=0.0)
    with mock.patch.object(LlamaForCausalLM, "__call__", counted):
        engine = ServingEngine(model, params, num_slots=1, prefix_cache=None, timeline=tl)
        assert set(engine.programs.resolved) == {"attention", "decode_attention", "paged_attention"}
        engine.submit(np.asarray([5, 6, 7, 8, 9], np.int32), gcfg)
        assert calls == []
        engine.run()
        assert calls.count("prefill") == 1     # the bucket's one trace, where head_rows is read
        engine.submit(np.asarray([9, 8, 7, 6], np.int32), gcfg)
        engine.run()
        assert calls.count("prefill") == 1     # the same bucket: the compiled program, no trace
    first, second = _prefill_spans(tl, path)
    assert first["padded"] == second["padded"] and first["head_rows"] == second["head_rows"] == 1


def test_head_rows_is_the_prefill_programs_own_shape_and_no_suffix_prefill_has_one(setup, tmp_path):
    """``head_rows`` is read from what the bucket's program returns, not from
    what ``models/__init__.py`` promises: a model that applies its head to
    every position reads ``padded``, and its stream is the same (the engine
    reads the last row either way). A suffix prefill runs the decode
    program's chunk, every row of it, and reports no ``head_rows``."""
    from flax import linen as nn

    from neuronx_distributed_tpu.models.llama import LlamaModel
    from neuronx_distributed_tpu.parallel.layers import ColumnParallelLinear
    from neuronx_distributed_tpu.serving import PrefixCache
    from neuronx_distributed_tpu.utils.timeline import Timeline

    class EveryPosition(LlamaForCausalLM):
        @nn.compact
        def __call__(self, input_ids, positions=None, attn_mask=None, segment_ids=None, padding_mask=None):
            cfg = self.config
            x = LlamaModel(cfg, self.attention_impl, self.mode, name="model")(
                input_ids, positions, attn_mask, segment_ids, padding_mask)
            return ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                                        param_dtype=cfg.param_dtype, name="lm_head")(x)

    cfg, model, params = setup
    gcfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    shared = np.arange(1, 13, dtype=np.int32)
    prompts = [np.concatenate([shared, tail]).astype(np.int32) for tail in ([20, 21], [30, 31, 32])]
    streams = {}
    for name, lm in (("one", model), ("every", EveryPosition(cfg, attention_impl="xla"))):
        path = tmp_path / f"{name}.json"
        tl = Timeline(str(path))
        engine = ServingEngine(lm, params, num_slots=1, timeline=tl,
                               prefix_cache=PrefixCache(max_entries=4, min_match=4))
        streams[name] = []
        for prompt in prompts:
            streams[name].append(engine.submit(prompt, gcfg, key=jax.random.PRNGKey(2)))
            engine.run()
        full, suffix = _prefill_spans(tl, path)
        assert (full["reused"], suffix["reused"] > 0) == (0, True)
        assert full["head_rows"] == (1 if name == "one" else full["padded"])
        assert "head_rows" not in suffix
    assert [r.tokens for r in streams["one"]] == [r.tokens for r in streams["every"]]
