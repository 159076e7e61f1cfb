"""A decode step samples only when a live slot asks for it (PR 36): the
engine's side of ``utils/sampling.sample_per_row``'s one branch. A sampled
request among greedy ones keeps every stream what ``generate()`` gives, a
retired sampled request holds nothing open, and the engine says how often the
greedy side ran: ``sampled_slots`` on the dispatch span and in the step
ledger's record, and the counters ``serving_decode_chunks_dispatched`` /
``serving_greedy_chunks_dispatched``. All of it host scalars: no sync added
(``test_host_sync.py`` holds the budget)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, generate
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.observability import tracing
from neuronx_distributed_tpu.observability.registry import MetricsRegistry
from neuronx_distributed_tpu.serving import RequestState, ServingEngine
from neuronx_distributed_tpu.utils.timeline import Timeline

# the sampled request (index 1) is the shortest: it retires while the greedy
# ones still decode, and its slot keeps its temperature (``_slot_clear``)
CONFIGS = (
    GenerationConfig(max_new_tokens=22, temperature=0.0),
    GenerationConfig(max_new_tokens=7, temperature=0.8, top_k=17, top_p=0.9),
    GenerationConfig(max_new_tokens=18, temperature=0.0),
)
SAMPLED = 1


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One run of the three requests on three slots, chunks of 4 steps:
    ``(engine, requests, solo generate() tokens, dispatch spans, registry)``."""
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    params = model.init(jax.random.PRNGKey(1), jnp.ones((1, 8), jnp.int32))
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32) for n in (5, 9, 7)]
    keys = [jax.random.PRNGKey(200 + i) for i in range(3)]
    solo = [
        np.asarray(generate(model, params, jnp.asarray(p)[None], k, c))[0].tolist()
        for p, k, c in zip(prompts, keys, CONFIGS)
    ]
    path = tmp_path_factory.mktemp("sampled") / "tl.json"
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=3, decode_chunk_size=4, prefix_cache=None,
                           timeline=Timeline(str(path)), registry=registry)
    reqs = [engine.submit(p, c, key=k) for p, c, k in zip(prompts, CONFIGS, keys)]
    engine.run()
    engine.timeline.save()
    dispatches = [e["args"] for e in json.loads(path.read_text())["traceEvents"]
                  if e["ph"] == "X" and e["name"] == tracing.STEP_DISPATCH]
    return engine, reqs, solo, dispatches, registry


@pytest.mark.parametrize("index", range(3), ids=["greedy_long", "sampled", "greedy"])
def test_each_stream_of_a_mixed_batch_is_its_solo_generate(served, index):
    _, reqs, solo, _, _ = served
    assert reqs[index].state is RequestState.DONE
    assert reqs[index].tokens == solo[index]


def test_sampled_slots_on_the_dispatch_span_counts_the_live_sampled_requests(served):
    """1 while the sampled request decodes, 0 from the chunk after it retires
    on: its slot still holds its temperature and decides nothing."""
    engine, reqs, _, dispatches, _ = served
    counts = [d["sampled_slots"] for d in dispatches]
    assert all(type(c) is int for c in counts)
    mixed = -(-(CONFIGS[SAMPLED].max_new_tokens - 1) // 4)     # its chunks, the first token apart
    assert counts[:mixed] == [1] * mixed and len(counts) > mixed
    assert counts[mixed:] == [0] * (len(counts) - mixed)
    assert [d["active"] for d in dispatches[:mixed]] == [3] * mixed
    # the freed slot was never written again: the device state still holds 0.8 there
    slot = int(np.flatnonzero(np.asarray(engine._state["temp"]) != 0.0)[0])
    assert not bool(np.asarray(engine._state["active"])[slot])


def test_the_two_counters_say_how_many_chunks_took_the_greedy_side(served):
    engine, _, _, dispatches, registry = served
    zero = sum(1 for d in dispatches if d["sampled_slots"] == 0)
    m = engine.metrics
    assert m.chunks_dispatched == len(dispatches) == m.chunks
    assert m.greedy_chunks_dispatched == zero and 0 < zero < len(dispatches)
    assert registry.get("serving_decode_chunks_dispatched").value == len(dispatches)
    assert registry.get("serving_greedy_chunks_dispatched").value == zero
    snap = m.snapshot()
    assert (snap["chunks_dispatched"], snap["greedy_chunks_dispatched"]) == (len(dispatches), zero)


def test_the_step_ledgers_record_carries_sampled_slots(served):
    engine, _, _, dispatches, _ = served
    chunks = [r for r in engine.flight.steps.records() if r["chunk"]]
    assert [r["sampled_slots"] for r in chunks] == [d["sampled_slots"] for d in dispatches]
    assert all(r["sampled_slots"] <= r["active"] for r in chunks)


def test_all_greedy_traffic_reads_greedy_chunks_equal_to_all_chunks(served):
    """What every serve cell of the benchmark is: the mechanism engages in
    every chunk and the counters say so."""
    engine = served[0]
    fresh = ServingEngine(engine.model, engine._params, num_slots=2, decode_chunk_size=4,
                          prefix_cache=None)
    for i in range(3):
        fresh.submit(np.asarray([3 + i, 5, 7], np.int32),
                     GenerationConfig(max_new_tokens=6 + i, temperature=0.0))
    fresh.run()
    m = fresh.metrics
    assert m.chunks_dispatched == m.greedy_chunks_dispatched == m.chunks > 0
    assert all(r["sampled_slots"] == 0 for r in fresh.flight.steps.records())
