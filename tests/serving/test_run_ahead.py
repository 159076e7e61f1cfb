"""The decode chunk run ahead by one (PR 57; ``serving/engine.py``, "Decode
hot path"): with every slot held and nothing due at the boundary, the engine
calls chunk N+1 before it reads chunk N back. Tiny Llama on the CPU, chunks of
4 steps. The same traffic through the same engine held to one chunk at a time
(the rule, ``_can_run_ahead``, patched to say no: from here, the program has no
option for it) gives every request's tokens and final key; what the rule cannot
see (an EOS, a cancel) costs one chunk and is counted; what it can see (a free
slot, a budget's end, a wall, an armed injector, a draft model) holds it back.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.observability import tracing
from neuronx_distributed_tpu.serving import EngineHealth, RequestState, ServingEngine
from neuronx_distributed_tpu.serving.faults import FaultInjector
from tests.serving.span_spy import overhear

CHUNK = 4
ANSWERS = (30, 22, 27, 19)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    params = model.init(jax.random.PRNGKey(1), jnp.ones((1, 8), jnp.int32))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32) for n in (5, 9, 7, 12)]
    return cfg, model, params, prompts


def engine_of(setup, ahead=True, model=None, **kw):
    _, built, params, _ = setup
    kw.setdefault("kv_page_size", 8)
    eng = ServingEngine(model or built, params, num_slots=2, decode_chunk_size=CHUNK, prefix_cache=None, **kw)
    if not ahead:
        eng._can_run_ahead = lambda: False
    return eng


def config_of(i, n, **kw):
    """Greedy and sampled by turns: a sampled stream holds the keys too."""
    return GenerationConfig(max_new_tokens=n, temperature=0.7 if i % 2 else 0.0, top_k=20 if i % 2 else None, **kw)


def submit_all(eng, prompts, answers=ANSWERS, **kw):
    return [eng.submit(p, config_of(i, n), key=jax.random.PRNGKey(40 + i), **kw)
            for i, (p, n) in enumerate(zip(prompts, answers))]


def streams(reqs):
    return [(list(r.tokens), np.asarray(r.key).tolist()) for r in reqs]


def counted_run(eng):
    """``eng.run()``; returns the ``jax.device_get`` calls it made."""
    real, calls = jax.device_get, []

    def counting(x):
        calls.append(x)
        return real(x)

    jax.device_get = counting
    try:
        eng.run()
    finally:
        jax.device_get = real
    return len(calls)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "rows"])
def test_full_slots_run_ahead_and_give_the_streams_of_one_chunk_at_a_time(setup, paged):
    prompts = setup[3]
    got = {}
    for ahead in (False, True):
        eng = engine_of(setup, ahead, kv_page_size=8 if paged else None)
        dispatches = overhear(eng, tracing.STEP_DISPATCH)
        reqs = submit_all(eng, prompts)
        syncs = counted_run(eng)
        m = eng.metrics
        # one sync a fresh request's first token and ONE a chunk, run ahead or not
        assert syncs == len(reqs) + m.chunks and m.chunks == m.chunks_dispatched
        assert eng._in_flight is None and not eng.has_work
        assert [r.state for r in reqs] == [RequestState.DONE] * len(reqs)
        assert sum(d["ahead"] for d in dispatches) == m.chunks_run_ahead
        assert eng.decode_compilations == 1
        if paged:
            eng.cache.check()
        got[ahead] = streams(reqs), m.snapshot(analyze_programs=False)
    (plain, plain_snap), (ran, snap) = got[False], got[True]
    assert ran == plain and [len(t) for t, _ in ran] == list(ANSWERS)
    assert plain_snap["chunks_run_ahead"] == 0 and plain_snap["run_ahead_share"] == 0.0
    assert 0 < snap["chunks_run_ahead"] < snap["chunks"] == plain_snap["chunks"]
    assert snap["run_ahead_share"] == snap["chunks_run_ahead"] / snap["chunks_dispatched"]
    assert snap["late_found_ends"] == 0        # budgets alone ended these: none was run over


def test_a_free_slot_holds_the_engine_to_one_chunk_at_a_time(setup):
    """An open loop below its knee: an arriving request waits for the running
    chunk at most, as before."""
    eng = engine_of(setup)
    req = eng.submit(setup[3][0], config_of(0, 30), key=jax.random.PRNGKey(1))
    eng.step()
    late = eng.submit(setup[3][1], config_of(1, 20), key=jax.random.PRNGKey(2))
    assert eng._in_flight is None          # nothing stands between the arrival and its prefill
    eng.step()
    assert late.state is RequestState.DECODE and eng.metrics.chunks_run_ahead == 1   # both held now
    eng.run()
    assert len(req.tokens) == 30 and len(late.tokens) == 20
    # alone on two slots to its end: never
    alone = engine_of(setup)
    alone.submit(setup[3][0], config_of(0, 30), key=jax.random.PRNGKey(1))
    alone.run()
    assert alone.metrics.chunks_run_ahead == 0 and alone.metrics.chunks == 8


def test_a_budgets_end_inside_the_running_chunk_is_never_run_over(setup):
    """The host knows every budget: a chunk is called ahead only when no slot
    ends inside the unread one, so a slot that ends by budget frees at its own
    chunk's emit and the request waiting for it is prefilled in the very step
    it would have been: step for step the admissions of one chunk at a time."""
    prompts = setup[3]
    admitted = {}
    for ahead in (False, True):
        eng = engine_of(setup, ahead)
        left = overhear(eng, tracing.STEP_DISPATCH,
                        probe=lambda e=eng: [r.remaining_new_tokens for r in e._slot_req if r is not None])
        submit_all(eng, prompts + prompts, ANSWERS + (9, 14, 6, 11))
        trail = []
        while eng.has_work:
            eng.step()
            trail.append((eng.metrics.prefills, eng.metrics.chunks))
        admitted[ahead] = trail
        for d in left:
            if d["ahead"]:
                # what each slot had left BEFORE the unread chunk's tokens: past that chunk
                assert min(d["probe"]) > CHUNK and d["active"] == 2
        assert eng.metrics.late_found_ends == 0
    assert admitted[True] == admitted[False] and admitted[True][-1][0] == 8
    assert eng.metrics.chunks_run_ahead > 0


@pytest.mark.parametrize("how", ["cancel", "eos"])
def test_an_end_found_while_the_next_chunk_is_in_flight_costs_one_chunk(setup, how):
    """An ``on_token`` cancel, or an EOS, inside chunk N's emit, chunk N+1
    already called: N+1's tokens for that slot are discarded (the cancel: the
    device decoded on) or none (the EOS: the device froze the slot in N), the
    slot is found free one chunk late, re-admitted, and the pages' invariant
    holds. Every stream is what one chunk at a time gives; the counter reads 1."""
    cfg, _, _, prompts = setup
    eos = None
    if how == "eos":
        # a token the first request emits for the first time inside its third chunk
        probe = engine_of(setup, ahead=False)
        first = probe.submit(prompts[0], GenerationConfig(max_new_tokens=40, temperature=0.0), key=jax.random.PRNGKey(40))
        probe.run()
        eos = next(t for i, t in enumerate(first.tokens) if 1 + 2 * CHUNK <= i < 1 + 3 * CHUNK and t not in first.tokens[:i])
    got = {}
    for ahead in (False, True):
        eng = engine_of(setup, ahead)
        emits = overhear(eng, tracing.STEP_EMIT)

        def on_token(req, tok, eng=eng):
            if how == "cancel" and len(req.tokens) == 1 + 2 * CHUNK + 2:     # inside the third chunk's emit
                eng.cancel(req.rid)

        reqs = [eng.submit(prompts[0], GenerationConfig(max_new_tokens=40, temperature=0.0, eos_token_id=eos),
                           key=jax.random.PRNGKey(40), on_token=on_token)]
        reqs += [eng.submit(p, config_of(i, 40), key=jax.random.PRNGKey(40 + i)) for i, p in enumerate(prompts[1:3], 1)]
        eng.run()
        eng.cache.check()
        assert eng.cache.alloc.free_pages == eng.cache.alloc.num_pages - 1 and eng._in_flight is None
        assert sum(e["late_end"] for e in emits) == eng.metrics.late_found_ends     # the span says which chunk
        got[ahead] = streams(reqs), eng.metrics.late_found_ends, [r.state for r in reqs]
    assert got[True][0] == got[False][0]
    ended = RequestState.CANCELLED if how == "cancel" else RequestState.DONE
    assert got[True][2] == [ended, RequestState.DONE, RequestState.DONE]
    assert 1 + 2 * CHUNK < len(got[True][0][0][0]) <= 1 + 3 * CHUNK      # the first request ended in its third chunk
    assert len(got[True][0][2][0]) == 40                                  # the queued one took its slot
    assert (got[False][1], got[True][1]) == (0, 1)


def test_the_cursor_wall_falls_back_and_preempts_as_before(setup):
    """A 64-column row under eager admission: near the wall the engine goes
    back to one chunk at a time (a chunk is called ahead only with room for
    both write windows) and the wall's remedy is the same preempt-and-rewind,
    at the same boundaries."""
    cfg, _, params, prompts = setup
    short = LlamaForCausalLM(tiny_llama(max_seq_len=64), attention_impl="xla")
    got = {}
    for ahead in (False, True):
        eng = engine_of(setup, ahead, model=short, admission="eager")
        dispatches = overhear(eng, tracing.STEP_DISPATCH)
        reqs = submit_all(eng, prompts, (40, 34, 37, 30))
        eng.run(max_steps=400)
        eng.cache.check()
        assert all(d["cursor"] + CHUNK <= d["row_columns"] for d in dispatches if d["ahead"])
        got[ahead] = streams(reqs), eng.metrics.preemptions, eng.metrics.chunks_run_ahead
    assert got[True][0] == got[False][0]
    assert got[True][1] == got[False][1] > 0
    assert got[True][2] > 0


def test_the_page_pressure_wall_falls_back_and_preempts_as_before(setup):
    """A pool too small for the traffic: where it cannot back the next write
    window at the projected cursor the call ahead is not made, the unread
    chunk is read back alone, and the next step meets the wall as before."""
    prompts = setup[3]
    long_row = LlamaForCausalLM(tiny_llama(max_seq_len=256), attention_impl="xla")   # the pool's wall, not the cursor's
    got = {}
    for ahead in (False, True):
        eng = engine_of(setup, ahead, model=long_row, admission="eager", kv_num_pages=10)
        reqs = submit_all(eng, prompts, (60, 20, 50, 10))
        eng.run(max_steps=200)
        eng.cache.check()
        got[ahead] = streams(reqs), eng.metrics.preemptions, eng.metrics.chunks_run_ahead
    assert got[True][0] == got[False][0]
    assert got[True][1] == got[False][1] > 0
    assert got[True][2] > 0


def test_a_step_reads_back_one_chunk_and_a_drained_or_fenced_engine_leaves_none_in_flight(setup):
    prompts = setup[3]
    eng = engine_of(setup)
    reqs = submit_all(eng, prompts)
    eng.step()
    # the admitting step called two chunks and read back one
    assert eng._in_flight is not None and (eng.metrics.chunks_dispatched, eng.metrics.chunks) == (2, 1)
    eng.step()
    assert (eng.metrics.chunks_dispatched, eng.metrics.chunks) == (3, 2)
    # drain: the work already admitted runs to its end, the pipeline with it
    eng.drain()
    assert eng.health() is EngineHealth.DRAINING
    eng.run()
    assert eng._in_flight is None and not eng.has_work
    assert [len(r.tokens) for r in reqs[:2]] == list(ANSWERS[:2]) and not reqs[2].tokens
    eng.resume()
    eng.run()
    assert [len(r.tokens) for r in reqs] == list(ANSWERS)
    # every request cancelled under a chunk in flight: the next step reads it back and the engine rests
    more = submit_all(eng, prompts[:2])
    eng.step()
    assert eng._in_flight is not None
    for r in more:
        eng.cancel(r.rid)
    assert eng.has_work
    eng.run()
    assert eng._in_flight is None
    eng.step()                             # the drained engine rewinds at its next step
    assert eng.cache.cursor == 0
    # fenced under a chunk in flight: dropped unread, the work requeued host-current
    last = submit_all(eng, prompts[:2])
    eng.step()
    held = [list(r.tokens) for r in last]
    assert eng._in_flight is not None
    eng.fence("test")
    assert eng._in_flight is None and eng.health() is EngineHealth.HALTED
    assert [list(r.tokens) for r in last] == held and eng.scheduler.queued == 2
    eng.cache.check()


def test_an_armed_injector_a_draft_model_and_a_preempting_policy_never_run_ahead(setup):
    _, model, params, prompts = setup
    for kw in ({"fault_injector": FaultInjector()},
               {"draft_model": model, "draft_params": params, "gamma": 2},
               {"scheduling": "slo"}):
        eng = engine_of(setup, **kw)
        reqs = submit_all(eng, prompts)
        eng.run()
        assert eng.metrics.chunks_run_ahead == 0 and eng.metrics.chunks > 0, kw
        assert [len(r.tokens) for r in reqs] == list(ANSWERS)


def test_a_call_ahead_that_fails_emits_the_unread_chunk_before_it_recovers(setup):
    """No injector is armed where the engine runs ahead, so the fault is the
    program's own: the second call of the chunk raises. The chunk before it is
    read back and emitted first (every stream host-current through it), then
    recovery is the one-chunk-at-a-time engine's, and the streams are exact."""
    prompts = setup[3]
    plain = engine_of(setup, ahead=False)
    want_reqs = submit_all(plain, prompts)
    plain.run()
    eng = engine_of(setup, sleep_fn=lambda s: None)
    real, calls = eng._nonspec_chunk(), []

    def failing(*args):
        calls.append(len(calls))
        if len(calls) == 2:
            raise RuntimeError("the runtime refused the enqueue")
        return real(*args)

    failing.last_call_compiled = False
    eng._decode_chunk = failing
    reqs = submit_all(eng, prompts)
    eng.step()
    # the admitting step: chunk 1 called, chunk 2's call failed, chunk 1 emitted, then the recovery
    assert eng.metrics.chunks == 1 and eng.metrics.recoveries == 1 and eng._in_flight is None
    assert [len(r.tokens) for r in reqs[:2]] == [1 + CHUNK] * 2
    eng.run()
    eng.cache.check()
    assert streams(reqs) == streams(want_reqs)


def test_the_chunks_own_clocks_count_no_second_twice(setup):
    """``decode_dispatch_s + decode_readback_s`` is the sum of the chunks'
    walls: a chunk called before the one before it was read back starts its
    wall at that readback, so the sum stays under the run's wall."""
    eng = engine_of(setup)
    submit_all(eng, setup[3])
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    m = eng.metrics
    assert m.chunks_run_ahead > 0
    assert 0.0 < m.decode_dispatch_s + m.decode_readback_s <= wall
