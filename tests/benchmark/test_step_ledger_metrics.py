"""The three per-layer metrics that read the step ledger's stats off the
program's spans (``cursor_high_water_pct``, ``step_host_cpu_ms``,
``step_overrun_ms``): on a hand-made trace whose answers are known, on the
traces recorded before the stats existed (left out, nothing raised), on the
CPU rehearsal of a serve cell, and each entry against its reader."""

import json
import math
import os
import subprocess
import sys

import pytest

from perfbench import program_spans as ps
from perfbench import run as harness
from perfbench import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "tests", "benchmark", "data")
US = 1_000_000  # picoseconds in a microsecond
NEW = ("cursor_high_water_pct", "step_host_cpu_ms", "step_overrun_ms")
SERVE_CELLS = ["codegen2_lines_steady", "mixtral_chat_closed", "dsv2lite_docs_closed",
               "keye_longdocs_closed", "glm5_agentdocs_closed"]


def _run_of(spans, window=(0, 1000)):
    """A run record whose trace is one host thread's ``spans``:
    [(name, start us, duration us, {stat: int})]."""
    from jax.profiler import ProfileData

    names, stats = {xplane.WINDOW_SPAN: 1}, {}
    out = ['planes { name: "/host:CPU"', 'lines { id: 1 name: "main" timestamp_ns: 0']
    rows = [(xplane.WINDOW_SPAN, window[0], window[1] - window[0], {})] + list(spans)
    for name, start, dur, kv in rows:
        meta = names.setdefault(name, len(names) + 1)
        stat = "".join(f" stats {{ metadata_id: {stats.setdefault(k, len(stats) + 1)} int64_value: {v} }}"
                       for k, v in kv.items())
        out.append(f"events {{ metadata_id: {meta} offset_ps: {start * US} duration_ps: {dur * US}{stat} }}")
    out.append("}")
    out += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in names.items()]
    out += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in stats.items()]
    out.append("}")
    serialized = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    return {"trace": {}, ps._CACHE: ps.from_serialized(serialized)}


def _read(name, run):
    return harness.load_reader(name)(run)


# a prefill landing on a decoding slot, three decode-only steps (the second
# stalled: the engine's overrun seconds grow at its close), and a step the
# window cuts
SERVE = [
    ("nxd.step", 0, 300, {"cpu_us": 90, "overrun_us": 0}),
    ("nxd.step.prefill", 15, 100, {"rid": 7, "decoding_slots": 1}),
    ("nxd.step.decode.dispatch", 200, 10, {"active": 3, "cursor": 4000, "row_columns": 6144}),
    ("nxd.step.decode.readback", 210, 70, {"steps": 8}),
    ("nxd.step.decode.emit", 280, 15, {"delivered": 24}),
    ("nxd.step", 400, 100, {"cpu_us": 30, "overrun_us": 0}),
    ("nxd.step.decode.dispatch", 405, 10, {"active": 3, "cursor": 4008, "row_columns": 6144}),
    ("nxd.step.decode.readback", 415, 70, {"steps": 8}),
    ("nxd.step", 500, 100, {"cpu_us": 20, "overrun_us": 1_300_000}),
    ("nxd.step.decode.dispatch", 505, 10, {"active": 3, "cursor": 4016, "row_columns": 6144}),
    ("nxd.step.decode.readback", 515, 80, {"steps": 8}),
    ("nxd.step", 700, 120, {"cpu_us": 60, "overrun_us": 1_300_000}),
    ("nxd.step.decode.dispatch", 705, 10, {"active": 2, "cursor": 4608, "row_columns": 6144}),
    ("nxd.step.decode.readback", 715, 80, {"steps": 8}),
    # an idle step (no chunk): its CPU time is no decode step's
    ("nxd.step", 850, 10, {"cpu_us": 9, "overrun_us": 1_300_000}),
    ("nxd.step", 900, 200, {"cpu_us": 500, "overrun_us": 1_450_000}),
    ("nxd.step.decode.dispatch", 905, 10, {"active": 2, "cursor": 4616, "row_columns": 6144}),
]


@pytest.mark.parametrize("name, want", [
    ("cursor_high_water_pct", 100.0 * 4616 / 6144),     # the largest, not the last's alone nor a mean
    ("step_host_cpu_ms", 0.110 / 3),                    # the MEAN over decode-only steps with a readback: 30, 20, 60 us
    ("step_overrun_ms", 1450.0),                        # the last traced step's running total
])
def test_the_readers_on_a_hand_made_trace(name, want):
    assert _read(name, _run_of(SERVE)) == pytest.approx(want)


def test_a_rewound_cursor_keeps_its_high_water_and_a_quiet_run_reads_zero():
    spans = [s for s in SERVE if s[0] != "nxd.step"] + [
        ("nxd.step.decode.dispatch", 950, 5, {"active": 1, "cursor": 16, "row_columns": 6144}),
        ("nxd.step", 940, 30, {"cpu_us": 12, "overrun_us": 0}),
        ("nxd.step.decode.readback", 956, 10, {"steps": 8}),
    ]
    run = _run_of(spans)
    assert _read("cursor_high_water_pct", run) == pytest.approx(100.0 * 4616 / 6144)
    assert _read("step_overrun_ms", run) == 0.0         # a value: the line holds it
    assert _read("step_host_cpu_ms", run) == pytest.approx(0.012)


def test_a_clock_that_counts_in_ticks_is_read_by_its_mean():
    """The chip's host counts a thread's CPU time in ticks of 10 ms: of ten
    decode-only steps of 1.5 ms one or two read 10,000 us and the rest 0. The
    median would say 0."""
    spans = []
    for i, cpu_us in enumerate([0, 0, 10_000, 0, 0, 0, 0, 0, 10_000, 0]):
        spans += [("nxd.step", 100 * i, 90, {"cpu_us": cpu_us, "overrun_us": 0}),
                  ("nxd.step.decode.dispatch", 100 * i + 1, 5, {"active": 8, "cursor": 100 + 8 * i, "row_columns": 2560}),
                  ("nxd.step.decode.readback", 100 * i + 10, 70, {"steps": 8})]
    assert _read("step_host_cpu_ms", _run_of(spans)) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("run", [
    "v5e_small.xplane.pb", "v5e_decode_slice.xplane.pb", "stats_stripped", "no_trace", "empty_record",
])
def test_a_trace_without_the_stats_leaves_the_metric_out(name, run):
    """The parent of this PR: its ``nxd.step*`` spans carry neither ``cursor``
    nor ``cpu_us`` nor ``overrun_us`` (``v5e_decode_slice`` is PR 24's tree on
    the chip, ``v5e_small`` predates the spans). ``None``, nothing raised."""
    if run.endswith(".pb"):
        with open(os.path.join(DATA, run), "rb") as f:
            record = {"trace": {}, ps._CACHE: ps.from_serialized(f.read())}
    elif run == "stats_stripped":
        keep = ("active", "steps", "delivered", "rid", "decoding_slots")
        record = _run_of([(n, a, d, {k: v for k, v in kv.items() if k in keep}) for n, a, d, kv in SERVE])
        assert ps.spans(record, ps.STEP) and ps.spans(record, ps.DISPATCH)
    else:
        record = {"trace": {}, ps._CACHE: None} if run == "no_trace" else {}
    assert _read(name, record) is None


def test_each_new_entry_has_its_reader_and_is_appended_in_the_issues_order():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    for name in NEW:
        entry = entries[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["workloads"] == SERVE_CELLS and entry["better"] == "lower"
        assert entry["source"] == "program_span" and entry["layer"] == "serving engine"
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        # every cell that lists the metric reports the end-to-end metric it moves
        assert set(entry["workloads"]) <= set(end_to_end[entry["moves"]]["workloads"])
        assert callable(harness.load_reader(name))
        assert os.path.exists(os.path.join(ROOT, "perfbench", "layer_metrics", f"{name}.py"))


def test_the_rehearsal_of_a_serve_cell_prints_all_three():
    """One traced CPU rehearsal in a process of its own, as
    ``test_rehearsal.py`` runs them: the harness finds the readers by name and
    the engine's spans carry the stats."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", NXD_TPU_PERSISTENT_CACHE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--rehearse", DATA,
         "--workload", "mixtral_chat_closed", "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    assert "reader failed" not in done.stderr
    metrics = json.loads([ln for ln in done.stdout.splitlines() if ln.strip()][-1])["metrics"]
    for name in NEW:
        assert math.isfinite(metrics[name]["value"]) and metrics[name]["value"] >= 0, name
    assert 0 < metrics["cursor_high_water_pct"]["value"] <= 100
    assert metrics["step_host_cpu_ms"]["value"] > 0
    # 0 unless the machine under the tests held a step up for half a second; then the
    # warning line is on stderr and the runner's count of the recorder's events has it
    stalled = metrics["step_overrun_ms"]["value"] > 0
    assert ("slow_step {" in done.stderr) == stalled
    assert ("'slow_step'" in done.stderr) == stalled
    assert "flight recorder, whole run: {" in done.stderr
