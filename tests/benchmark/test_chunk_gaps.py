"""Who owns the gap between two decode chunks (``perfbench/chunk_gaps.py`` and
the fourteen per-layer metrics on it, PR 54): on synthetic planes whose answers
are known, on every degenerate trace PR 53's readers withheld on, and on a
slice of a v5e trace of cell 8 (``data/v5e_chunk_gap.xplane.pb``).

The rule under test: on a trace with a device plane, an ``nxd.step`` span and an
``nxd.program`` span EVERY reader gives a float, whatever else the trace lacks."""

import json
import math
import os

import pytest

from perfbench import chunk_gaps as cg
from perfbench import program_spans as ps
from perfbench import run as harness
from perfbench import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "tests", "benchmark", "data")
PARTS = tuple(f"idle_by_phase_pct.{part}" for part in cg.PARTS)
COMMON = PARTS + ("step_pages_ms", "other_programs_dev_share_pct", "unscoped_dev_share_pct",
                  "host_device_clock_offset_ms", "clock_fit_violation_ms")
PAGED_ONLY = ("pages_in_runs_share_pct",)            # the cells whose kernels walk the block table
NAMES = COMMON + PAGED_ONLY
US = 1000                                            # the builder's times are ns


class Trace:
    """A hand-made ``.xplane.pb``: host lines by thread, one device plane. Host
    times are on the host's clock; ``run`` takes host times too and writes them
    on a device clock that reads ``offset + drift * (t - middle)`` behind."""

    def __init__(self, offset=0, drift=0.0, middle=0):
        self.offset, self.drift, self.middle = offset, drift, middle
        self.lines = {}
        self.ops, self.modules = [], []
        self.paths = {}                              # op name -> op_name path ("" for none)

    def host(self, thread, name, start, duration, **stats):
        self.lines.setdefault(thread, []).append((name, start, duration, stats))

    def device_time(self, t):
        return t - self.offset - int(self.drift * (t - self.middle))

    def run(self, module, program_id, start, end, run_id=None, ops=(("%fusion.1 = bf16[8]{0} fusion()", "mlp"),)):
        """One module run over ``[start, end)``, its ops tiling it evenly."""
        a, b = self.device_time(start), self.device_time(end)
        self.modules.append((f"{module}({program_id})", a, b - a, {} if run_id is None else {"run_id": run_id}))
        width = (b - a) // len(ops)
        for i, (name, scope) in enumerate(ops):
            self.paths[name] = (f"jit({module[4:]})/{scope}/op:" if scope else "", program_id)
            self.ops.append((name, a + i * width, width if i < len(ops) - 1 else b - a - i * width, {}))

    def serialized(self):
        from jax.profiler import ProfileData

        def plane(name, lines, metas=None):
            names, stat_ids = {}, {"tf_op": 1, "program_id": 2}
            out = [f'planes {{ name: "{name}"']
            for i, (line, events) in enumerate(lines):
                out.append(f'lines {{ id: {i + 1} name: "{line}" timestamp_ns: 0')
                for event, start, duration, stats in events:
                    meta = names.setdefault(event, len(names) + 1)
                    said = ""
                    for key, value in stats.items():
                        kind = "str_value" if isinstance(value, str) else "int64_value"
                        value = f'"{value}"' if isinstance(value, str) else value
                        said += f" stats {{ metadata_id: {stat_ids.setdefault(key, len(stat_ids) + 1)} {kind}: {value} }}"
                    out.append(f"events {{ metadata_id: {meta} offset_ps: {start * 1000} "
                               f"duration_ps: {duration * 1000}{said} }}")
                out.append("}")
            for event, i in names.items():
                path, program = (metas or {}).get(event, ("", None))
                said = f' stats {{ metadata_id: 1 str_value: "{path}" }}' if path else ""
                said += f" stats {{ metadata_id: 2 uint64_value: {program} }}" if program else ""
                text = event.replace('"', '\\"')
                out.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{text}"{said} }} }}')
            out += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in stat_ids.items()]
            out.append("}")
            return "\n".join(out)

        host = plane("/host:CPU", sorted(self.lines.items()))
        device = plane("/device:TPU:0", [(xplane.OPS_LINE, self.ops), (xplane.MODULES_LINE, self.modules)], self.paths)
        return ProfileData.text_proto_to_serialized_xspace(host + "\n" + device)

    def as_run(self):
        return {"trace": {}, ps._CACHE: ps.from_serialized(self.serialized())}


# one decode-only step, us from its start: the host's phases, the chunk's call,
# and where the runtime enqueues the run, the device runs it and the host hears of it
CYCLE = 1100
STEP = dict(step=(0, 1000), reap=(0, 10), pages=(12, 40), dispatch=(40, 200), program=(60, 180),
            enqueue=250, run=(250, 800), complete=830, readback=(200, 850), emit=(850, 950),
            health=(950, 980), close=(980, 995))
# the gap before a step's run, [-300, 250) of its own clock, by the part that owns each piece
WANT = {"completion": 50, "emit": 100, "admit": 30 + 15 + 10, "unowned": 5 + 2, "outside": 100, "pages": 28,
        "dispatch": 20 + 20, "launch": 120 + 50}


def serve(steps=6, run_ids=True, window=None, prefill_at=(), eager_at=(), modules=True, pages_stats=True, launch=0,
          early_completion=None):
    """``steps`` decode-only steps a ``CYCLE`` apart from t = 10 ms on; a
    prefill (admit, prefill, its program, the admission's, the first token)
    before the dispatch of the steps in ``prefill_at``; an eager program's run
    no span names after the chunk of the steps in ``eager_at``."""
    t0 = 10_000 * US
    end = t0 + steps * CYCLE * US
    trace = Trace()
    window = window or (t0 - 500 * US, end)
    trace.host("python3", xplane.WINDOW_SPAN, window[0], window[1] - window[0])
    run_id = 100
    for k in range(steps):
        s = t0 + k * CYCLE * US
        at = lambda name: (s + STEP[name][0] * US, (STEP[name][1] - STEP[name][0]) * US)  # noqa: E731
        trace.host("python3", "engine.step", *at("step"))
        trace.host("python3", ps.STEP, *at("step"), cpu_us=300, overrun_us=0)
        trace.host("python3", "nxd.step.reap", *at("reap"))
        if k in prefill_at:
            # inside what is the unowned 2 us and the pages span of the other steps: 28 us of admission
            trace.host("python3", "nxd.step.admit", s + 10 * US, 2 * US)
            trace.host("python3", ps.PREFILL, s + 12 * US, 28 * US, rid=k, decoding_slots=1, padded=64)
            trace.host("python3", cg.PROGRAM, s + 13 * US, 5 * US, program="prefill[64]", module="jit_fn" if modules else "")
            trace.host("python3", cg.PROGRAM, s + 19 * US, 3 * US, program="paged_admit",
                       module="jit__paged_admit" if modules else "")
            trace.host("python3", ps.FIRST_TOKEN, s + 24 * US, 14 * US, rid=k)
            trace.host("python3", cg.PROGRAM, s + 25 * US, 3 * US, program="first_token",
                       module="jit_sample_row" if modules else "")
        else:
            trace.host("python3", cg.PAGES, *at("pages"))
        stats = dict(active=8, sampled_slots=0)
        if pages_stats:
            stats.update(full_pages_mapped=100 + k, full_pages_in_runs=96, window_pages_mapped=20, window_pages_in_runs=16)
        trace.host("python3", ps.DISPATCH, *at("dispatch"), **stats)
        trace.host("python3", cg.PROGRAM, *at("program"), program="decode_chunk", module="jit_chunk_fn" if modules else "")
        trace.host("python3", ps.READBACK, *at("readback"), steps=8)
        trace.host("python3", ps.EMIT, *at("emit"), delivered=64)
        trace.host("python3", "nxd.step.health", *at("health"))
        trace.host("python3", "nxd.step.close", *at("close"))
        a, b = s + STEP["run"][0] * US, s + STEP["run"][1] * US
        ids = {}
        if run_ids:
            ids = {"run_id": run_id}
            trace.host("tfrt-non-blocking-queue/358", cg.ENQUEUE, s + STEP["enqueue"] * US - launch, 40 * US, **ids)
            done = s + STEP["complete"] * US if early_completion != k else a
            trace.host("futex-default/438", cg.COMPLETE, done, 100 * US, **ids)
        if k in prefill_at:
            # the prefill's three runs, each enqueued as it starts, inside the spans that called them;
            # the host hears of the first token's 1 us after its last op
            for j, (module, pid, x, y) in enumerate((("jit_fn", 7, 15, 20), ("jit__paged_admit", 8, 22, 24),
                                                     ("jit_sample_row", 9, 28, 32))):
                extra = {"run_id": run_id + 1 + j} if run_ids else {}
                if run_ids:
                    trace.host("pjrt-tpu-tasks/333", cg.ENQUEUE, s + x * US, 1 * US, **extra)
                    trace.host("futex-default/438", cg.COMPLETE, s + (y + 1) * US, 1 * US, **extra)
                op = ("%copy.3 = bf16[8]{0} copy()", "") if module == "jit__paged_admit" else \
                     ("%fusion.2 = f32[8]{0} fusion()", "attn")
                trace.run(module, pid, s + x * US, s + y * US, ops=(op,), **extra)
        trace.run("jit_chunk_fn", 5, a, b, ops=(("%fusion.1 = bf16[8]{0} fusion()", "mlp"),
                                               ("%copy.9 = bf16[8]{0} copy()", "")), **ids)
        if k in eager_at:
            trace.run("jit_convert_element_type", 11, b, b + 30 * US, run_id + 5 if run_ids else None,
                      ops=(("%convert.1 = f32[] convert()", ""),))
        run_id += 10
    return trace


def _read_all(run, names=NAMES):
    return {name: harness.load_reader(name)(run) for name in names}


def _assert_total(values):
    for name, value in values.items():
        assert isinstance(value, float) and math.isfinite(value), (name, value)


def _assert_parts_add_up(run):
    """The eight parts are the idle intervals of at least the floor, to the nanosecond."""
    got = cg.analyse(run)
    assert sum(got["parts"].values()) + got["below_floor_ns"] == got["window_ns"] - got["busy_ns"]
    assert all(v >= 0 for v in got["parts"].values())
    return got


def test_each_piece_of_a_gap_goes_to_the_part_that_owns_it():
    """Six identical decode-only steps on one clock (a launch of 0 us, so the
    fitted offset is 0): every gap between two chunks splits as ``WANT``."""
    run = serve(steps=6, window=(10_000 * US + 800 * US, 10_000 * US + (5 * CYCLE + 800) * US)).as_run()
    got = _assert_parts_add_up(run)
    assert got["join"] == "run_id+order" and got["unjoined"] == 0
    assert (got["shift_ns"], got["drift_us_per_s"], got["violation_ns"]) == (0, 0.0, 0)
    # the window runs from one chunk's end to another's: five whole gaps
    assert got["parts"] == {part: 5 * WANT[part] * US for part in cg.PARTS}
    values = _read_all(run)
    _assert_total(values)
    window_us = 5 * CYCLE
    for part in cg.PARTS:
        assert values[f"idle_by_phase_pct.{part}"] == pytest.approx(100.0 * 5 * WANT[part] / window_us)
    assert sum(values[name] for name in PARTS) == pytest.approx(100.0 * 550 / CYCLE)    # 1 - busy
    assert values["step_pages_ms"] == pytest.approx(0.028)
    assert values["other_programs_dev_share_pct"] == 0.0
    # half of every chunk is an op without a scope path
    assert values["unscoped_dev_share_pct"] == pytest.approx(50.0)
    # the stats of the dispatch spans the window holds (the first step's lies before it)
    assert values["pages_in_runs_share_pct"] == pytest.approx(100.0 * 5 * 112 / sum(120 + k for k in range(1, 6)))


def test_a_prefill_in_the_window_and_the_programs_no_span_names():
    """A step that prefills: the idle time under its programs is ``launch``,
    what is left of the prefill span ``admit``; the admission's copy has no
    scope; an eager program's run joins nothing and is class ``other``."""
    run = serve(steps=6, prefill_at=(2,), eager_at=(3,)).as_run()
    got = _assert_parts_add_up(run)
    assert got["unjoined"] == 1
    plain = cg.analyse(serve(steps=6).as_run())["parts"]
    # the prefilling step had no page dealing; its 28 us lie in the prefill, 11 of them under its three programs
    assert got["parts"]["pages"] == plain["pages"] - 28 * US
    values = _read_all(run)
    _assert_total(values)
    busy = 6 * 550 + (5 + 2 + 4) + 30                 # six chunks, the prefill's three runs, the eager one
    assert values["other_programs_dev_share_pct"] == pytest.approx(100.0 * (2 + 30) / busy)   # the admission, the eager run
    assert values["unscoped_dev_share_pct"] == pytest.approx(100.0 * (6 * 275 + 2) / busy)    # joined runs alone


@pytest.mark.parametrize("offset_us,drift_us_per_s", [(318, 0), (1370, 35), (-200, -60), (1224, 150)])
def test_a_known_offset_and_drift_are_recovered_inside_their_bounds(offset_us, drift_us_per_s):
    """The device plane written on a clock that reads ``offset`` behind the
    host's and drifts: over 6.6 s the bounds (a launch of at least 20 us, a
    completion 30 us after the last op) cross for any one offset once the
    drift passes ~8 us/s, and the fit finds a drift that uncrosses them."""
    steps, spread = 12, 550_000                       # a cycle of 550 ms: the trace spans 6.6 s
    trace = Trace(offset_us * US, drift_us_per_s * 1e-6, 10_000 * US + steps * spread * US // 2)
    trace.host("python3", xplane.WINDOW_SPAN, 10_000 * US, steps * spread * US)
    for k in range(steps):
        s = 10_000 * US + k * spread * US
        trace.host("python3", ps.STEP, s, 1000 * US)
        trace.host("python3", ps.DISPATCH, s + 40 * US, 160 * US, active=8)
        trace.host("python3", cg.PROGRAM, s + 60 * US, 120 * US, program="decode_chunk", module="jit_chunk_fn")
        trace.host("python3", ps.READBACK, s + 200 * US, 650 * US, steps=8)
        trace.host("tfrt-non-blocking-queue/358", cg.ENQUEUE, s + (230 - (k % 3) * 5) * US, 10 * US, run_id=k)
        trace.host("futex-default/438", cg.COMPLETE, s + 830 * US, 10 * US, run_id=k)
        trace.run("jit_chunk_fn", 5, s + 250 * US, s + 800 * US, run_id=k)
    got = _assert_parts_add_up(trace.as_run())
    assert got["violation_ns"] == 0 and got["joined"] == steps
    fitted, true = got["drift_us_per_s"], drift_us_per_s
    assert abs(fitted) <= abs(true) and fitted * true >= 0
    assert (fitted == 0) == (abs(true) * 6.05 <= 50)  # one offset fits while the drift over the trace stays inside the slack
    # the offset at the middle: the true one less the quickest launch (20 us) at most, never above it
    slack = 20 + abs(true - fitted) * 3.4
    assert (offset_us - slack) * US - 1000 <= got["shift_ns"] <= offset_us * US + abs(true - fitted) * 3.4 * US + 1000
    _assert_total(_read_all(trace.as_run()))


DEGENERATE = {
    # one CompleteCallbacks stamped at its run's START: lower - upper = 550 us, past any drift
    "bounds crossed past any drift": dict(early_completion=3),
    "no run_id stats": dict(run_ids=False),
    "no run joined": dict(modules=False, prefill_at=(1,)),
    "no prefill in the window": dict(prefill_at=()),
    "a run in flight at either window edge": dict(window=(10_000 * US + 500 * US, 10_000 * US + (4 * CYCLE + 600) * US)),
    "no page stats on the dispatch span": dict(pages_stats=False),
    # every chunk's enqueue stamped 40 us AFTER its first op, and the host hears of the first token
    # 1 us after its last: crossed by 39 us, as a chip trace in eight is by 20-50
    "bounds crossed by a little": dict(launch=-40 * US),
}


@pytest.mark.parametrize("case", sorted(DEGENERATE))
def test_every_reader_gives_a_float_on_a_degenerate_trace(case):
    run = serve(steps=6, **{"prefill_at": (2,), **DEGENERATE[case]}).as_run()
    got = _assert_parts_add_up(run)
    values = _read_all(run)
    _assert_total(values)
    if case == "bounds crossed past any drift":
        # the midpoint of the crossed pair, and the crossing as the line's error bar
        assert values["clock_fit_violation_ms"] == pytest.approx(0.550) and got["drift_us_per_s"] == 0.0
        assert values["host_device_clock_offset_ms"] == pytest.approx(-0.275)
    elif case == "bounds crossed by a little":
        assert values["clock_fit_violation_ms"] == pytest.approx(0.039)
        assert values["host_device_clock_offset_ms"] == pytest.approx(0.0205)
    else:
        assert values["clock_fit_violation_ms"] == 0.0
    if case == "no run_id stats":
        # the span's own open stands in for the enqueue, the readback's return for the completion
        assert got["join"] == "module+order" and got["unjoined"] == 0
        assert values["host_device_clock_offset_ms"] == pytest.approx(-(15 - 13) / 1000)   # the prefill's launch
    if case == "no run joined":
        assert got["joined"] == 0 and values["host_device_clock_offset_ms"] == 0.0
        assert values["other_programs_dev_share_pct"] == pytest.approx(100.0)
        assert values["unscoped_dev_share_pct"] == 0.0
    if case == "no page stats on the dispatch span":
        assert values["pages_in_runs_share_pct"] == 0.0


def test_a_device_that_is_never_idle_reads_eight_zeros():
    trace = serve(steps=4)
    # a loop's op that encloses everything the device ran
    trace.ops.append(("%while.1 = (s32[]) while()", trace.device_time(9_000 * US), 10_000 * US, {}))
    trace.paths["%while.1 = (s32[]) while()"] = ("jit(chunk_fn)/while:", 5)
    run = trace.as_run()
    got = _assert_parts_add_up(run)
    assert got["busy_ns"] == got["window_ns"]
    values = _read_all(run)
    _assert_total(values)
    assert [values[name] for name in PARTS] == [0.0] * 8


@pytest.mark.parametrize("stage", ["read", "join", "fit", "split", "classes", "scopes"])
def test_a_stage_that_raises_costs_only_what_it_fills(stage, monkeypatch, capsys):
    target = {"read": (cg, "_read"), "join": (cg, "_join"), "fit": (cg, "_fit_clock"), "split": (cg, "_split"),
              "classes": (xplane, "self_times"), "scopes": (ps, "op_paths")}[stage]

    def boom(*args, **kwargs):
        raise RuntimeError(f"the {stage} stage, made to raise")

    whole = cg.analyse(serve(steps=6, prefill_at=(2,)).as_run())
    monkeypatch.setattr(*target, boom)
    run = serve(steps=6, prefill_at=(2,)).as_run()
    values = _read_all(run)
    _assert_total(values)
    assert f"the {stage} stage failed" in capsys.readouterr().err
    got = cg.analyse(run)
    if stage in ("classes", "scopes"):
        assert got["parts"] == whole["parts"]          # the idle parts keep their values
    if stage == "scopes":
        assert got["busy_by_class"] == whole["busy_by_class"] and values["unscoped_dev_share_pct"] == 0.0
    if stage == "fit":
        assert got["shift_ns"] == 0 and sum(got["parts"].values()) == sum(whole["parts"].values())
    if stage == "join":
        assert got["joined"] == 0 and values["other_programs_dev_share_pct"] == pytest.approx(100.0)


def test_none_keeps_its_meaning():
    """No trace, a CPU rehearsal (no device plane), and the parent of PR 54 (no
    ``nxd.program`` span): every reader on the clock gives ``None`` and raises
    nothing; the two on host spans alone read what the host plane holds."""
    for run in ({}, {"trace": {}, ps._CACHE: None}):
        assert set(_read_all(run).values()) == {None}
    cpu = serve(steps=4)
    cpu.ops, cpu.modules = [], []
    serialized = cpu.serialized()
    from jax.profiler import ProfileData
    planes = [p for p in ProfileData.from_serialized_xspace(serialized).planes if p.name.startswith("/host:")]
    run = {"trace": {}, ps._CACHE: dict(ps.from_serialized(serialized), planes=planes)}
    values = _read_all(run)
    assert values.pop("step_pages_ms") == pytest.approx(0.028) and values.pop("pages_in_runs_share_pct") > 0
    assert set(values.values()) == {None}
    parent = serve(steps=4)
    parent.lines["python3"] = [row for row in parent.lines["python3"] if row[0] not in (cg.PROGRAM, cg.PAGES)]
    values = _read_all(parent.as_run())
    assert values.pop("pages_in_runs_share_pct") > 0     # PR 52's stats: the parent carries them
    assert set(values.values()) == {None}


@pytest.fixture(scope="module")
def recorded():
    """Cell 8 (``zaya1_reasoning_closed``) on a v5e, PR 53's tree (the spans PR 54
    emits, ``nxd.step.close`` apart): 0.29 s of the traced window around four
    decode chunks and one prefill, the ops' events elided to their intervals."""
    with open(os.path.join(DATA, "v5e_chunk_gap.xplane.pb"), "rb") as f:
        return {"trace": {}, ps._CACHE: ps.from_serialized(f.read())}


def test_on_a_v5e_trace_the_parts_add_up_and_the_clock_is_fitted(recorded):
    got = _assert_parts_add_up(recorded)
    assert got["join"] == "run_id+order" and got["joined"] == 9
    # on the chip the host plane reads 1.5 ms ahead of the device plane: not one clock
    assert 1.4e6 < got["shift_ns"] < 1.9e6 and got["violation_ns"] == 0
    idle = 100.0 * (1 - got["busy_ns"] / got["window_ns"])
    values = _read_all(recorded)
    assert sum(values[name] for name in PARTS) == pytest.approx(idle, abs=0.05)
    # completion and launch are half of it; the stepping thread's own phases the rest
    assert values["idle_by_phase_pct.completion"] + values["idle_by_phase_pct.launch"] > 0.5 * idle
    assert 0.1 < values["step_pages_ms"] < 2.0 and values["idle_by_phase_pct.pages"] > 0


def test_every_appended_metric_has_its_reader_and_reads_a_float_on_the_v5e_trace(recorded):
    """Pinned by NAME: where the entries stand in ``per_layer`` is the next PR's to move."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    serving = [w["name"] for w in bench["workloads"] if "tpot_mean_ms" in
               {m["name"] for m in harness.metrics_of_cell(bench, "end_to_end", w["name"])}]
    assert len(serving) == 8
    for name in NAMES:
        entry = entries[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["moves"] == "tpot_mean_ms" and entry["source"] in ("device_trace", "program_span")
        assert entry["workloads"] == (serving if name in COMMON else serving[-3:]), name
        value = harness.load_reader(name)(recorded)
        assert isinstance(value, float) and math.isfinite(value) and value >= 0, (name, value)
    assert entries["pages_in_runs_share_pct"]["better"] == "higher"
    assert all(entries[name]["better"] == "lower" for name in COMMON)
