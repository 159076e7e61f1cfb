"""The step ledger and the overrun watchdog (``observability/flight_recorder.py``
``StepLedger``, fed by ``tracing.span``'s third sink): one record a
``ServingEngine.step()``, a verdict per step from the ledger's own history,
and, for a step that overran, ONE ``slow_step`` flight event and ONE warning
line that say which phase it sat in and where the thread was.

The stalls are injected through the engine's fault hooks: ``on_dispatch``
runs inside the ``nxd.step.decode.dispatch`` span, ``on_readback`` inside
``nxd.step.decode.emit``, ``on_prefill`` inside ``nxd.step.prefill``."""

import functools
import json
import logging
import signal
import threading
import time

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.observability import flight_recorder as fr
from neuronx_distributed_tpu.observability import tracing
from neuronx_distributed_tpu.observability.flight_recorder import (
    FlightRecorder,
    StackSamples,
    StepLedger,
    redact,
)
from neuronx_distributed_tpu.serving import EngineHealth, ServingEngine
from neuronx_distributed_tpu.serving.faults import FaultInjector

WATCHDOG = "nxd-step-watchdog"
SIBLINGS = tuple(n for n in tracing.SERVE_SPANS[1:] if n != tracing.STEP_FIRST_TOKEN)
STALL_S = 0.8


def limit(seconds):
    """A time limit of the test's own (the suite has no timeout plugin):
    SIGALRM on the worker's main thread, where pytest runs the test."""
    def wrap(test):
        @functools.wraps(test)
        def run(*args, **kwargs):
            def over(signum, frame):
                raise TimeoutError(f"{test.__name__} ran over {seconds} s")
            before = signal.signal(signal.SIGALRM, over)
            signal.alarm(seconds)
            try:
                return test(*args, **kwargs)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, before)
        return run
    return wrap


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    return cfg, model, model.init(jax.random.PRNGKey(1), ids)


def _engine(setup, **kw):
    _, model, params = setup
    return ServingEngine(model, params, num_slots=2, decode_chunk_size=4,
                         prefix_cache=None, **kw)


def _submit(engine, n_new, prompt_len=6, seed=0):
    rng = np.random.RandomState(seed)
    return engine.submit(
        rng.randint(1, 200, size=prompt_len).astype(np.int32),
        GenerationConfig(max_new_tokens=n_new, temperature=0.0),
        key=jax.random.PRNGKey(seed),
    )


class Stall(FaultInjector):
    """Sleeps once, inside one hook, at the hook's ``at``-th call."""

    def __init__(self, hook, at):
        super().__init__()
        self.hook, self.at = hook, at

    def _maybe(self, hook, index):
        if hook == self.hook and index == self.at:
            time.sleep(STALL_S)

    def on_dispatch(self, attempt):
        self._maybe("dispatch", attempt)
        return super().on_dispatch(attempt)

    def on_readback(self, readback, toks, counts, active=None):
        self._maybe("readback", readback)
        return super().on_readback(readback, toks, counts, active)


class Capture(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def warnings_of_the_engine():
    log = logging.getLogger("neuronx_distributed_tpu.serving.engine")
    handler = Capture()
    log.addHandler(handler)
    yield handler.lines
    log.removeHandler(handler)


# --- the ledger under a real engine ---------------------------------------------------


@limit(120)
def test_one_record_a_step_and_the_phases_add_up(setup):
    engine = _engine(setup)
    _submit(engine, 90), _submit(engine, 70, prompt_len=9, seed=1)
    steps = 0
    while engine.has_work:
        engine.step()
        steps += 1
    records = engine.flight.steps.records()
    assert len(records) == steps == len(engine.flight.steps)
    assert [r["step"] for r in records] == list(range(steps))
    assert all(a["t0"] + a["wall_s"] <= b["t0"] for a, b in zip(records, records[1:]))
    first = records[0]
    warm = [r for r in records[2:] if r["chunk"] and not r["prefills"] and not r["compiles"]]
    assert first["compiles"] > 0 and first["cold"] and first["expected_s"] is None
    assert len(first["prefills"]) == 2 and all(isinstance(p, int) for p in first["prefills"])
    assert len(warm) >= 10
    for r in warm:
        assert set(r["phases"]) <= set(tracing.SERVE_SPANS[1:])
        assert r["active"] > 0 and r["compiles"] == 0 and not r["cold"] and not r["overran"]
        assert 0 <= r["thread_cpu_s"] <= r["process_cpu_s"] + 1e-3
    # the siblings tile the step (the first token lies inside its prefill)
    shares = sorted(sum(r["phases"].get(n, 0.0) for n in SIBLINGS) / r["wall_s"] for r in warm)
    assert 0.95 <= shares[len(shares) // 2] <= 1.0, shares
    with_prefill = [r for r in records if r["prefills"]]
    assert all(r["phases"][tracing.STEP_FIRST_TOKEN] < r["phases"][tracing.STEP_PREFILL]
               for r in with_prefill)
    # nothing of a step reaches the event ring, and no step overran
    assert not [e for e in engine.flight.events() if e["kind"] == "slow_step"]
    assert engine.flight.steps.overruns == 0 and engine.flight.steps.overrun_seconds == 0.0


@limit(120)
def test_without_a_recorder_nothing_is_recorded_and_no_thread_starts(setup):
    before = {t for t in threading.enumerate() if t.name == WATCHDOG}
    engine = _engine(setup, flight_recorder=None, timeline=None)
    assert engine._ledger is None
    assert type(engine._span(tracing.STEP_REAP)) is jax.profiler.TraceAnnotation
    _submit(engine, 8)
    engine.run()
    assert engine.metrics.chunks > 0
    assert {t for t in threading.enumerate() if t.name == WATCHDOG} <= before


@limit(120)
@pytest.mark.parametrize("hook, phase, caller", [
    ("dispatch", tracing.STEP_DISPATCH, "_maybe"),
    ("readback", tracing.STEP_EMIT, "_maybe"),
])
def test_an_injected_stall_leaves_one_slow_step_record(setup, warnings_of_the_engine, hook, phase, caller):
    """Twenty warm chunks, then 0.8 s asleep inside one phase: ONE event, the
    stalled phase the largest, the samples naming the phase and the sleeper,
    the thread's CPU time a fraction of the wall, ONE warning line, the two
    counters."""
    engine = _engine(setup, fault_injector=Stall(hook, at=22))
    _submit(engine, 110), _submit(engine, 110, seed=1)
    engine.run()
    assert engine.metrics.chunks >= 25
    (event,) = [e for e in engine.flight.events() if e["kind"] == "slow_step"]
    assert max(event["phases"], key=event["phases"].get) == phase
    assert event["phases"][phase] >= STALL_S
    assert STALL_S <= event["wall_s"] < STALL_S + 1.0 and event["expected_s"] < 0.1
    assert event["thread_cpu_s"] < 0.1 * event["wall_s"]
    assert event["since_start_s"] > 0 and event["active"] == 2 and event["prefills"] == []
    samples = event["samples"]
    assert 1 <= len(samples) <= fr.MAX_SAMPLES
    for sample in samples:
        assert 0.5 < sample["t_s"] <= event["wall_s"] and 1 <= len(sample["frames"]) <= fr.MAX_FRAMES
    # all of them (but perhaps the last, taken as the sleeper woke) name the phase and time.sleep's caller
    asleep = [s for s in samples if s["phase"] == phase
              and s["frames"][0].startswith("observability/test_step_ledger.py:")
              and s["frames"][0].endswith(" " + caller)]
    assert asleep and len(asleep) >= len(samples) - 1, samples
    assert all(any(f.startswith("serving/engine.py:") for f in s["frames"]) for s in asleep)
    json.dumps(event)
    # the record of the same step in the ring, and the ledger's totals
    (record,) = [r for r in engine.flight.steps.records() if r["overran"]]
    assert record["step"] == event["step"] and record["wall_s"] == event["wall_s"]
    ledger = engine.flight.steps
    assert ledger.overruns == 1
    assert ledger.overrun_seconds == pytest.approx(event["wall_s"] - event["expected_s"])
    # the operator's side: one warning line that holds the record, two counters
    (line,) = [ln for ln in warnings_of_the_engine if ln.startswith("slow_step ")]
    said = json.loads(line[len("slow_step "):])
    assert said["step"] == event["step"] and said["samples"] == samples
    registry = engine.metrics.registry
    assert registry.get("serving_step_overruns").value == 1
    assert registry.get("serving_step_overrun_seconds").value == pytest.approx(ledger.overrun_seconds)
    if "run_delay_s" in event:      # Linux: the thread slept, it was not kept off a CPU
        assert event["run_delay_s"] < 0.5 * STALL_S and event["voluntary_switches"] >= 1


class SlowPrefill(FaultInjector):
    """Every prefill from the second on takes 0.7 s."""

    def on_prefill(self, call):
        if call >= 1:
            time.sleep(0.7)
        return super().on_prefill(call)


@limit(120)
def test_a_long_prefill_of_a_bucket_seen_before_is_its_buckets_expected_wall(setup, warnings_of_the_engine):
    """The first prefill of a bucket compiles (cold); the first step of its
    kind that compiles nothing is cold too (no history) and gives the bucket
    its wall; every one after it is judged against that: long, and not
    overrun."""
    engine = _engine(setup, fault_injector=SlowPrefill())
    for seed in range(6):
        _submit(engine, 6, seed=seed)
        engine.run()
    prefills = [r for r in engine.flight.steps.records() if r["prefills"]]
    assert [r["prefills"] for r in prefills] == [prefills[0]["prefills"]] * 6
    cold = [r["cold"] for r in prefills]
    assert prefills[0]["compiles"] > 0 and cold[0]
    assert cold == sorted(cold, reverse=True) and cold.count(False) >= 3, cold
    first_warm = next(r for r in prefills[1:] if not r["compiles"])
    assert first_warm["cold"] and first_warm["expected_s"] is None
    for r in (r for r in prefills if not r["cold"]):
        assert r["wall_s"] > 0.7 and r["expected_s"] > 0.7 and not r["overran"]
    assert not [e for e in engine.flight.events() if e["kind"] == "slow_step"]
    assert not warnings_of_the_engine and engine.flight.steps.overruns == 0


@limit(120)
def test_the_post_mortem_of_a_halt_holds_the_last_steps(setup, tmp_path):
    engine = _engine(
        setup, fault_injector=FaultInjector().fail_dispatch(at=15, times=None),
        flight_dir=str(tmp_path), sleep_fn=lambda s: None,
    )
    _submit(engine, 110), _submit(engine, 110, seed=1)
    engine.run()
    assert engine.health() is EngineHealth.HALTED
    with open(engine.flight.last_dump_path) as f:
        dump = json.load(f)
    steps = dump["steps"]
    assert 1 <= len(steps) <= fr.POSTMORTEM_STEPS
    assert [s["step"] for s in steps] == list(range(steps[0]["step"], steps[-1]["step"] + 1))
    # the dump is written INSIDE the halting step: it holds the steps before it
    halting = engine.flight.steps.records()[-1]
    assert steps[-1]["step"] == halting["step"] - 1 and not halting["chunk"]
    assert all(isinstance(s["wall_s"], float) and isinstance(s["phases"], dict) for s in steps)
    assert sum(s["chunk"] for s in steps) == 15
    assert dump["events"][-1]["kind"] == "halt"


# --- the ledger by itself ---------------------------------------------------------------


def _step(ledger, parts, compiles=0):
    """One step through the span sink: ``parts`` = [(name, seconds asleep,
    stats set while open)]."""
    with tracing.span(tracing.STEP, None, ledger=ledger):
        for name, seconds, stats in parts:
            with tracing.span(name, None, ledger=ledger, active=1) as sp:
                if stats:
                    sp.set_metadata(**stats)
                if seconds:
                    time.sleep(seconds)
        return ledger.finish(compiles)


CHUNK = [(tracing.STEP_DISPATCH, 0.001, None), (tracing.STEP_READBACK, 0.002, {"steps": 4}),
         (tracing.STEP_EMIT, 0, {"delivered": 4})]


def _prefill(seconds, padded=512, reused=0):
    return (tracing.STEP_PREFILL, seconds, {"padded": padded, "reused": reused})


@limit(60)
@pytest.mark.parametrize("expected, limit_s", [
    (0.0, 0.5), (0.13, 0.63), (0.5, 1.0), (1.0, 2.0), (1.4 + 0.17, 3.14),
])
def test_the_limit_is_a_floor_over_and_twice_the_expected_wall(expected, limit_s):
    """The cells' honest steps (a chunk of 0.08-0.17 s, a prefill of up to
    1.4 s on full slots) sit at their expected wall; the stalls on record
    (1.2-4.4 s around a 0.08-0.17 s chunk and at most a 1.0 s prefill) are
    all over the limit."""
    assert fr.overrun_limit(expected) == pytest.approx(limit_s)
    assert fr.overrun_limit(0.17) < 1.2 and fr.overrun_limit(0.17 + 1.0) < 4.4


@limit(60)
def test_a_compiling_step_and_a_first_of_its_kind_get_no_verdict():
    ledger = StepLedger()
    first = _step(ledger, CHUNK)
    assert first["cold"] and first["expected_s"] is None        # no chunk seen before
    warm = _step(ledger, CHUNK)
    assert not warm["cold"] and warm["expected_s"] == pytest.approx(
        sum(first["phases"][n] for n, _, _ in CHUNK))
    compiled = _step(ledger, [(tracing.STEP_DISPATCH, 0.6, None)] + CHUNK[1:], compiles=1)
    assert compiled["cold"] and not compiled["overran"] and compiled["compiles"] == 1
    # its wall entered no median: the same step without a compile overruns the same expectation
    slow = _step(ledger, [(tracing.STEP_DISPATCH, 0.6, None)] + CHUNK[1:])
    assert slow["overran"] and slow["expected_s"] < 0.05
    unseen = _step(ledger, [_prefill(0.6)] + CHUNK)
    assert unseen["cold"] and unseen["prefills"] == [512] and not unseen["overran"]
    suffix = _step(ledger, [_prefill(0.0, reused=256)] + CHUNK)   # another bucket: the reused tokens count
    assert suffix["cold"]
    seen = _step(ledger, [_prefill(0.6)] + CHUNK)
    assert not seen["cold"] and seen["expected_s"] > 0.6 and not seen["overran"]
    assert ledger.overruns == 1 and [r["step"] for r in ledger.records()] == list(range(7))


@limit(60)
def test_a_prefills_bucket_raises_the_limit_before_it_runs():
    ledger = StepLedger()
    _step(ledger, [_prefill(0.3)] + CHUNK)
    _step(ledger, CHUNK)
    with tracing.span(tracing.STEP, None, ledger=ledger):
        assert ledger._open.expected == 0.0
        with tracing.span(tracing.STEP_PREFILL, None, ledger=ledger, rid=1) as sp:
            assert ledger._open.expected == 0.0 and not ledger._open.cold
            sp.set_metadata(padded=512, reused=0)
            assert 0.3 <= ledger._open.expected < 0.4           # known before the program runs
        with tracing.span(tracing.STEP_DISPATCH, None, ledger=ledger, active=2):
            assert ledger._open.expected > 0.3 + 0.002 and ledger._open.active == 2
        ledger.finish(0)
    assert ledger._open is None


@limit(60)
def test_the_rings_are_bounded_and_steps_stay_out_of_the_events():
    recorder = FlightRecorder(capacity=8)
    recorder.steps = ledger = StepLedger(capacity=5)
    for _ in range(12):
        _step(ledger, [])
    assert len(ledger) == 5 and [r["step"] for r in ledger.records()] == [7, 8, 9, 10, 11]
    assert len(recorder) == 0
    assert [s["step"] for s in recorder.build_postmortem("x")["steps"]] == [7, 8, 9, 10, 11]
    assert "steps" not in FlightRecorder().build_postmortem("x")     # a trainer's recorder
    with pytest.raises(ValueError):
        StepLedger(capacity=0)
    # a bucket table that cannot grow without bound
    for padded in range(fr.MAX_BUCKETS + 10):
        ledger._bucket((padded, 0))
    assert len(ledger._buckets) == fr.MAX_BUCKETS and (0, 0) not in ledger._buckets


@limit(60)
def test_frames_pass_the_redaction_and_nothing_else_is_loosened():
    frames = [f"serving/engine.py:{i} f{i}" for i in range(20)]
    samples = StackSamples([(0.6123456, tracing.STEP_READBACK, frames)] * 40)
    got = redact({"samples": samples, "plain": frames, "text": "x" * 500})
    assert got["plain"] == {"len": 20} and len(got["text"]) < 250      # as before
    assert len(got["samples"]) == fr.MAX_SAMPLES
    assert got["samples"][0] == {"t_s": 0.612, "phase": tracing.STEP_READBACK,
                                 "frames": frames[:fr.MAX_FRAMES]}
    long = StackSamples([(1.0, "p" * 500, ["f" * 500])])
    (one,) = redact(long)
    assert len(one["phase"]) <= 200 and len(one["frames"][0]) <= 200
    json.dumps(got)


@limit(60)
def test_the_watchdog_samples_only_an_overrunning_step_and_ends_itself_when_idle(monkeypatch):
    monkeypatch.setattr(fr, "WATCH_IDLE_S", 0.3)
    ledger = StepLedger()
    assert ledger._watchdog is None                     # no step yet: no thread
    _step(ledger, CHUNK), _step(ledger, CHUNK)
    dog = ledger._watchdog
    assert dog.name == WATCHDOG and dog.daemon and dog.is_alive()
    honest = _step(ledger, [(tracing.STEP_DISPATCH, 0.45, None)] + CHUNK[1:])
    assert not honest["overran"] and "samples" not in honest
    cold = _step(ledger, [_prefill(1.0)] + CHUNK)
    assert cold["cold"] and "samples" not in cold and not ledger.records()[-1].get("samples")
    stalled = _step(ledger, [CHUNK[0], (tracing.STEP_READBACK, 1.0, None), CHUNK[2]])
    assert stalled["overran"]
    assert 1 <= len(stalled["samples"]) <= 8          # every 100 ms from at most 0.2 s after the limit
    assert all(phase == tracing.STEP_READBACK for _, phase, _ in stalled["samples"])
    dog.join(timeout=5)
    assert not dog.is_alive() and ledger._watchdog is None
    _step(ledger, CHUNK)                                # the next step starts another
    assert ledger._watchdog is not None and ledger._watchdog is not dog


@limit(60)
def test_spans_outside_a_step_and_a_step_left_by_an_exception_leave_no_record():
    ledger = StepLedger()
    _step(ledger, CHUNK)
    # a phase outside any step (a drain's preemption) opens none
    with tracing.span(tracing.STEP_PREEMPT, None, ledger=ledger):
        assert ledger._open is None
    assert ledger.finish(0) is None
    with pytest.raises(RuntimeError):
        with tracing.span(tracing.STEP, None, ledger=ledger):
            with tracing.span(tracing.STEP_DISPATCH, None, ledger=ledger, active=1):
                raise RuntimeError("boom")
    assert ledger._open is None and len(ledger) == 1
    assert _step(ledger, CHUNK)["step"] == 2            # the ordinal counts the step that was left
