"""The program's own spans (ISSUE 24): ``observability.tracing.span`` and
what ``ServingEngine.step()`` and ``Trainer.fit`` emit through it, read back
from a profiler trace recorded by a process of its own (``_span_driver.py``)."""

import glob
import json
import os
import statistics
import subprocess
import sys

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.observability import tracing
from neuronx_distributed_tpu.serving import ServingEngine
from neuronx_distributed_tpu.utils.timeline import Timeline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the siblings that tile a step: not the step, not what nests in a sibling
CHILDREN = tuple(n for n in tracing.SERVE_SPANS
                 if n not in (tracing.STEP, tracing.STEP_FIRST_TOKEN, tracing.PROGRAM))


def _host_events(trace_dir):
    """``{name: [(start ns, end ns, stats, thread)]}`` of the nxd.* events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nxd."):
                    a = int(ev.start_ns)
                    out.setdefault(ev.name, []).append(
                        (a, a + int(ev.duration_ns), dict(ev.stats), line.name))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("spans"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, os.path.join(HERE, "_span_driver.py"), out],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    with open(os.path.join(out, "facts.json")) as f:
        facts = json.load(f)
    facts["paged_events"] = _host_events(os.path.join(out, "paged"))
    return facts, _host_events(os.path.join(out, "trace")), _host_events(os.path.join(out, "empty"))


def _inside(parent, events):
    a, b, _, thread = parent
    return [e for e in events if e[3] == thread and a <= e[0] and e[1] <= b]


def test_every_name_of_the_contract_is_in_the_host_plane(traced):
    facts, events, _ = traced
    assert facts["on"]["preemptions"] > 0          # the scenario reaches the wall
    # the page dealing's span is a paged engine's: the driver's second scenario
    assert set(tracing.SERVE_SPANS) - {tracing.STEP_PAGES} <= set(events)
    assert tracing.STEP_PAGES in facts["paged_events"]
    assert set(tracing.TRAIN_SPANS) <= set(events)
    assert len(events[tracing.STEP_PREFILL]) == facts["on"]["prefills"]
    assert len(events[tracing.STEP_READBACK]) == facts["on"]["chunks"]
    assert len(events[tracing.TRAIN_STEP]) == 3
    assert [e[2]["step_num"] for e in events[tracing.TRAIN_STEP]] == [0, 1, 2]


def test_children_nest_inside_the_step_and_tile_it(traced):
    _, events, _ = traced
    steps = events[tracing.STEP]
    for name in CHILDREN + (tracing.STEP_FIRST_TOKEN,):
        for e in events.get(name, []):               # no page dealing in the unpaged scenario
            assert sum(1 for s in steps if s[3] == e[3] and s[0] <= e[0] and e[1] <= s[1]) == 1, name
    for e in events[tracing.STEP_FIRST_TOKEN]:     # and the first token inside its prefill
        assert len([p for p in events[tracing.STEP_PREFILL] if p[0] <= e[0] and e[1] <= p[1]]) == 1
    # siblings do not overlap; in a decode-only step they cover the step
    covered = []
    for step in steps:
        kids = sorted(k for name in CHILDREN for k in _inside(step, events.get(name, [])))
        for left, right in zip(kids, kids[1:]):
            assert left[1] <= right[0]
        decode_only = (_inside(step, events[tracing.STEP_READBACK])
                       and not _inside(step, events[tracing.STEP_PREFILL]))
        if decode_only:
            covered.append(sum(k[1] - k[0] for k in kids) / (step[1] - step[0]))
    assert len(covered) >= 3
    assert statistics.median(covered) >= 0.95, covered
    # the trainer's phases lie inside their step
    for name in tracing.TRAIN_SPANS[1:]:
        for e in events[name]:
            if name == tracing.PROGRAM and e[2]["program"] != "train_step":
                continue                             # the engine's calls share the name
            assert any(s[0] <= e[0] and e[1] <= s[1] for s in events[tracing.TRAIN_STEP]), name


def test_stats_are_host_ints_and_rid_links_a_request(traced):
    facts, events, _ = traced
    for name, rows in events.items():
        for _, _, stats, _ in rows:
            for key, value in stats.items():
                # but for nxd.program's two names, fixed when the program is wrapped
                assert isinstance(value, str if name == tracing.PROGRAM else int), (name, key, value)
    first = {e[2]["rid"] for e in events[tracing.STEP_FIRST_TOKEN]}
    assert first == set(facts["on"]["rids"])       # a fresh request samples one first token
    prefills = events[tracing.STEP_PREFILL]
    assert {e[2]["rid"] for e in prefills} == first
    for _, _, stats, _ in prefills:
        assert {"rid", "prompt_tokens", "padded", "reused", "decoding_slots"} <= set(stats)
    fresh = [s for _, _, s, _ in prefills if "ttft_us" in s]
    assert len(fresh) == len(first)                # a re-admission has no TTFT and waited once
    # what the flash forward does with the prompt in its bucket: a bucket of one block here
    assert all((s["flash_steps"], s["flash_tiles"], s["flash_edge_tiles"], s["flash_needed_tiles"]) == (1, 1, 1, 1)
               for _, _, s, _ in prefills if s["reused"] == 0)
    # the rows of logits a fresh prompt's prefill computes for the one its caller reads
    assert all(s["head_rows"] == 1 for _, _, s, _ in prefills if s["reused"] == 0)
    assert all(s["ttft_us"] >= s["queue_wait_us"] >= 0 for s in fresh)
    assert any(s["decoding_slots"] > 0 for _, _, s, _ in prefills)
    # a chunk's executed steps and the tokens that reached a stream
    assert all(e[2]["steps"] > 0 for e in events[tracing.STEP_READBACK])
    assert all(e[2]["active"] > 0 for e in events[tracing.STEP_DISPATCH])
    assert sum(e[2]["delivered"] for e in events[tracing.STEP_EMIT]) == \
        sum(len(t) for t in facts["on"]["tokens"]) - len(first)
    # the shared write cursor against its row, in columns (48 here): it runs into the wall
    cursors = [e[2]["cursor"] for e in events[tracing.STEP_DISPATCH]]
    assert all(e[2]["row_columns"] == 48 for e in events[tracing.STEP_DISPATCH])
    assert all(0 < c < 48 for c in cursors) and max(cursors) >= 40
    assert any(b < a for a, b in zip(cursors, cursors[1:]))      # the preemption rewound it
    # the active slots whose request samples (the driver's second does): what the chunk's
    # sampler branches on, and at 0 what ``serving_greedy_chunks_dispatched`` counts
    sampled = [e[2]["sampled_slots"] for e in events[tracing.STEP_DISPATCH]]
    assert set(sampled) == {0, 1} and all(s <= e[2]["active"] for s, e in zip(sampled, events[tracing.STEP_DISPATCH]))
    assert len(sampled) == facts["on"]["chunks_dispatched"] == facts["on"]["chunks"]
    assert sampled.count(0) == facts["on"]["greedy_chunks_dispatched"]
    # the step ledger's two, at the step's close: the stepping thread's CPU time
    # inside the step, and the engine's overrun seconds so far (no step stalled here)
    for start, end, stats, _ in events[tracing.STEP]:
        assert set(stats) == {"cpu_us", "overrun_us"}
        assert 0 <= stats["cpu_us"] <= (end - start) / 1e3 + 1000 and stats["overrun_us"] == 0
    assert sum(e[2]["cpu_us"] for e in events[tracing.STEP]) > 0
    # what nothing reads is not emitted
    for name in (tracing.STEP_REAP, tracing.STEP_PREEMPT, tracing.STEP_ADMIT, tracing.STEP_HEALTH,
                 tracing.STEP_CLOSE):
        assert all(e[2] == {} for e in events[name]), name
    assert all(e[2] == {} for e in facts["paged_events"][tracing.STEP_PAGES])


def test_every_ledgered_call_is_one_program_span_with_its_two_names(traced):
    """``nxd.program``: one a call of a ledgered program, the engine's and the
    trainer's alike, with the ledger's name and the module's. The paged
    scenario counts its ledger's dispatches while the session is open."""
    facts, events, _ = traced
    paged = facts["paged_events"]
    seen = {}
    for _, _, stats, _ in paged[tracing.PROGRAM]:
        assert set(stats) == {"program", "module"}
        seen[stats["program"]] = seen.get(stats["program"], 0) + 1
    assert seen == facts["paged_calls"] and seen["decode_chunk"] >= 3
    modules = {stats["program"].split("[")[0]: stats["module"]
               for _, _, stats, _ in paged[tracing.PROGRAM] + events[tracing.PROGRAM]}
    # the names the device's ``XLA Modules`` line gives those programs' runs
    # (tests/observability/test_scope_names.py pins the first two on the compiled programs)
    assert modules["decode_chunk"] == "jit_chunk_fn" and modules["prefill"] == "jit_fn"
    assert modules["first_token"] == "jit_sample_row" and modules["paged_admit"] == "jit__paged_admit"
    assert modules["train_step"] == "jit_step_fn"
    # a chunk's call lies inside its step's dispatch span, a first token's inside its span
    for a, b, stats, thread in paged[tracing.PROGRAM]:
        if stats["program"] == "decode_chunk":
            assert any(d[3] == thread and d[0] <= a and b <= d[1] for d in paged[tracing.STEP_DISPATCH])


def test_a_decode_only_step_leaves_no_stretch_outside_a_child_span(traced):
    """``step()`` of a paged engine, decode only: reap, pages, dispatch,
    readback, emit, health, close, and between two of them (and before the
    first and after the last) a few statements: under 60 us each in the
    median over the steps, on a CPU that other tests share."""
    facts, _, _ = traced
    paged = facts["paged_events"]
    order = [tracing.STEP_REAP, tracing.STEP_PAGES, tracing.STEP_DISPATCH, tracing.STEP_READBACK,
             tracing.STEP_EMIT, tracing.STEP_HEALTH, tracing.STEP_CLOSE]
    stretches = []
    for step in paged[tracing.STEP]:
        kids = sorted((k[0], k[1], name) for name in CHILDREN for k in _inside(step, paged.get(name, [])))
        if [name for _, _, name in kids] != order:
            continue                                 # the step that prefilled, the one that retired
        edges = [step[0]] + [t for a, b, _ in kids for t in (a, b)] + [step[1]]
        stretches.append([edges[i + 1] - edges[i] for i in range(0, len(edges), 2)])
    assert len(stretches) >= 3
    for i in range(len(order) + 1):
        assert statistics.median(s[i] for s in stretches) < 60_000, (i, stretches)

def test_no_session_no_event_and_no_extra_sync(traced):
    facts, _, empty = traced
    assert empty == {}                             # spans emitted with no session open went nowhere
    off, on = facts["off"], facts["on"]
    assert off["tokens"] == on["tokens"]
    # the pinned budget (tests/serving/test_host_sync.py): one sync a submit,
    # one a fresh request's first token, one a chunk; the session adds none
    assert off["syncs"] == on["syncs"] == 3 + 3 + on["chunks"]


def test_span_without_a_timeline_is_the_bare_annotation():
    assert type(tracing.span("x", None, a=1)) is jax.profiler.TraceAnnotation
    assert type(tracing.span("x", Timeline(None), a=1)) is jax.profiler.TraceAnnotation
    step = tracing.span("x", None, annotation=jax.profiler.StepTraceAnnotation, step_num=3)
    assert type(step) is jax.profiler.StepTraceAnnotation
    with tracing.span("x", None, a=1) as sp:
        sp.set_metadata(b=2)                       # no session: a no-op, not an error


def test_timeline_gets_the_same_names_and_stats(tmp_path):
    """The second sink: with a Timeline the engine's phases are Chrome ``X``
    events under the names of the contract, stats as args, end-of-span stats
    included."""
    path = tmp_path / "tl.json"
    tl = Timeline(str(path))
    model = LlamaForCausalLM(tiny_llama(), attention_impl="xla")
    params = model.init(jax.random.PRNGKey(1), np.ones((1, 8), np.int32))
    engine = ServingEngine(model, params, num_slots=2, timeline=tl)
    req = engine.submit(np.asarray([1, 2, 3], np.int32),
                        GenerationConfig(max_new_tokens=4, temperature=0.0))
    engine.run()
    tl.save()
    events = [e for e in json.loads(path.read_text())["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in events}
    assert names <= set(tracing.SERVE_SPANS)
    # no preemption here, no paged cache, and nxd.program is the profiler's alone
    assert set(tracing.SERVE_SPANS) - names == {tracing.STEP_PREEMPT, tracing.STEP_PAGES, tracing.PROGRAM}
    assert all(e["cat"] == tracing.SPAN_CATEGORY for e in events)
    (prefill,) = [e for e in events if e["name"] == tracing.STEP_PREFILL]
    assert prefill["args"]["rid"] == req.rid and prefill["args"]["ttft_us"] >= 0
    assert prefill["args"]["padded"] >= 3 and prefill["args"]["reused"] == 0
    emits = [e for e in events if e["name"] == tracing.STEP_EMIT]
    assert sum(e["args"]["delivered"] for e in emits) == 3


@pytest.mark.parametrize("model,kinds", [
    ("llama", {"flash"}), ("keye", {"flash", "masked"}), ("glm5", {"flash", "masked"}), ("trinity", {"flash", "band"})])
def test_prefill_span_counts_the_tiles_of_the_kernels_the_model_runs(model, kinds):
    """``nxd.step.prefill``'s tile stats, by the kernels' own rule: ``flash_*``
    for every model, ``masked_*`` for one with an indexer, ``band_*`` for one
    with window layers (a KV head a layer: Keye's group of 8 tiles its queries
    by 256), visited = needed for a left-padded prompt; nothing for a suffix
    prefill or an exact-length fallback."""
    from neuronx_distributed_tpu.kernels.flash_attention import flash_tile_plan, group_tile_plan
    from neuronx_distributed_tpu.models.afmoe import trinity_large
    from neuronx_distributed_tpu.models.glm_moe_dsa import GlmMoeDsaConfig
    from neuronx_distributed_tpu.models.keye_vl2 import keye_vl2_30b_a3b
    from neuronx_distributed_tpu.serving.engine import _flash_tiles

    config = {"llama": tiny_llama, "keye": keye_vl2_30b_a3b, "glm5": GlmMoeDsaConfig, "trinity": trinity_large}[model]()
    stats = _flash_tiles(16384, 8862, config)
    assert {name.split("_")[0] for name in stats} == kinds and len(stats) == 4 * len(kinds)
    names = ("steps", "tiles", "edge_tiles", "needed_tiles")
    assert tuple(stats[f"flash_{n}"] for n in names) == flash_tile_plan(16384, 8862) == (528, 171, 35, 171)
    want = {"keye": (1056, 341, 341, 341), "glm5": (528, 171, 171, 171),
            "trinity": group_tile_plan(16384, 8862, 6, 4096)}.get(model)
    for kind in kinds - {"flash"}:
        assert tuple(stats[f"{kind}_{n}"] for n in names) == want
        assert stats[f"{kind}_tiles"] == stats[f"{kind}_needed_tiles"] < stats[f"{kind}_steps"]
    assert _flash_tiles(16384, 0, config) == {} and _flash_tiles(9003, 9003, config) == {}
