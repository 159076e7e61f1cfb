"""The readers of the program's own spans and scopes (``perfbench/
program_spans.py`` and the ten per-layer metrics on it): on hand-made traces
whose answers are known, on the trace recorded on a v5e, and on the CPU
rehearsal's trace of a serve cell and of the train cell."""

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
NEW = ("queue_wait_p90_ms", "engine_ttft_p50_ms", "prefill_stall_pct", "step_host_ms",
       "step_idle_pct.admit", "step_idle_pct.dispatch", "step_idle_pct.emit",
       "kv_view_dev_share_pct", "moe_block_dev_share_pct", "train_dispatch_ms_p50")
STAT_IDS = {"tf_op": 1, "program_id": 2}


def _run_of(*planes):
    """A run record whose trace is the hand-made ``planes``."""
    from jax.profiler import ProfileData

    serialized = ProfileData.text_proto_to_serialized_xspace("\n".join(planes))
    return {"trace": {}, ps._CACHE: ps.from_serialized(serialized)}


def _host(spans, window=(0, 1000)):
    """``spans``: [(name, start us, duration us, {stat: int})] on one thread."""
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
    return "\n".join(out)


def _device(ops, modules, metas):
    """``ops`` / ``modules``: [(metadata id, start us, duration us)];
    ``metas``: {id: (name, op_name path or None, program id or None)}."""
    out = ['planes { name: "/device:TPU:0"']
    for i, (line, events) in enumerate((("XLA Ops", ops), ("XLA Modules", modules))):
        out.append(f'lines {{ id: {i + 1} name: "{line}" timestamp_ns: 0')
        out += [f"events {{ metadata_id: {m} offset_ps: {a * US} duration_ps: {d * US} }}" for m, a, d in events]
        out.append("}")
    for key, (name, path, program) in metas.items():
        stat = f' stats {{ metadata_id: 1 str_value: "{path}" }}' if path else ""
        stat += f" stats {{ metadata_id: 2 uint64_value: {program} }}" if program else ""
        out.append(f'event_metadata {{ key: {key} value {{ id: {key} name: "{name}"{stat} }} }}')
    out += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in STAT_IDS.items()]
    out.append("}")
    return "\n".join(out)


def _read(name, run):
    return harness.load_reader(name)(run)


# one prefill landing on a decoding slot, then two decode-only steps
SERVE = [
    ("nxd.step", 0, 300, {}),
    ("nxd.step.reap", 0, 5, {}),
    ("nxd.step.admit", 5, 10, {}),
    ("nxd.step.prefill", 15, 100, {"rid": 7, "queue_wait_us": 2000, "decoding_slots": 1, "ttft_us": 90000}),
    ("nxd.step.prefill.first_token", 60, 50, {"rid": 7}),
    ("nxd.step.prefill", 115, 80, {"rid": 8, "queue_wait_us": 102000, "decoding_slots": 0, "ttft_us": 180000}),
    ("nxd.step.decode.dispatch", 200, 10, {"active": 3}),
    ("nxd.step.decode.readback", 210, 70, {"steps": 8}),
    ("nxd.step.decode.emit", 280, 15, {"delivered": 24}),
    ("nxd.step.health", 295, 5, {}),
    ("nxd.step", 400, 100, {}),
    ("nxd.step.decode.dispatch", 405, 10, {"active": 3}),
    ("nxd.step.decode.readback", 415, 70, {"steps": 8}),
    ("nxd.step.decode.emit", 485, 10, {"delivered": 24}),
    ("nxd.step", 600, 120, {}),
    ("nxd.step.decode.dispatch", 605, 10, {"active": 2}),
    ("nxd.step.decode.readback", 615, 80, {"steps": 8}),
    ("nxd.step.decode.emit", 695, 20, {"delivered": 16}),
    # a re-admission after a preemption: no queue wait, no TTFT of its own
    ("nxd.step", 900, 200, {}),
    ("nxd.step.prefill", 910, 150, {"rid": 7, "decoding_slots": 2}),
]


def test_host_span_readers_on_a_hand_made_trace():
    run = _run_of(_host(SERVE))
    # two fresh requests: waits 2 and 102 ms, TTFTs 90 and 180 ms
    assert _read("queue_wait_p90_ms", run) == pytest.approx(2 + 0.9 * 100)
    assert _read("engine_ttft_p50_ms", run) == pytest.approx(135.0)
    # prefills on decoding slots: 100 us, and 90 of the re-admission's 150 (clipped to the window)
    assert _read("prefill_stall_pct", run) == pytest.approx(100.0 * (100 + 90) / 1000)
    # decode-only steps: 100 - 70 and 120 - 80 us of host time
    assert _read("step_host_ms", run) == pytest.approx(0.035)
    # nothing of the device, nothing of the trainer: left out, not zero
    for name in ("step_idle_pct.admit", "kv_view_dev_share_pct", "moe_block_dev_share_pct", "train_dispatch_ms_p50"):
        assert _read(name, run) is None


def test_stats_keep_their_type_and_spans_are_clipped_to_the_window():
    run = _run_of(_host(SERVE))
    last = ps.spans(run, ps.PREFILL)[-1]
    assert (last[1] - last[0]) == 90_000 and last[2] == {"rid": 7, "decoding_slots": 2}
    assert all(isinstance(v, int) for _, _, s, _ in ps.spans(run, ps.PREFILL) for v in s.values())
    assert ps.window_ns(run) == 1_000_000
    step = ps.spans(run, ps.STEP)[0]
    assert [c[2]["rid"] for c in ps.children(run, step, ps.PREFILL)] == [7, 8]


def test_idle_gaps_go_to_the_phase_over_their_middle_and_add_up():
    # the device is busy but for [190,215) (middle in the first dispatch),
    # [230,255) (the host already in the readback: the dispatch's too),
    # [275,300) and [475,500) (in an emit), [720,760) (between two steps) and
    # [905,1000) (in the re-admission's prefill)
    ops = [(1, 0, 190), (1, 215, 15), (1, 255, 20), (1, 300, 175), (1, 500, 220), (1, 760, 145)]
    metas = {1: ("%fusion.1 = bf16[8]{0} fusion()", "jit(chunk_fn)/mlp/dot_general:", 5),
             9: ("jit_chunk_fn(5)", None, None)}
    steps = [("engine.step", a, d, {}) for name, a, d, _ in SERVE if name == "nxd.step"]
    run = _run_of(_device(ops, [(9, 0, 1000)], metas), _host(SERVE + steps))
    gaps = ps.step_idle(run)["gaps"]
    assert gaps["nxd.step.decode.dispatch"] == gaps["nxd.step.decode.readback"] == pytest.approx(25e-6)
    assert _read("step_idle_pct.dispatch", run) == pytest.approx(5.0)
    assert _read("step_idle_pct.emit", run) == pytest.approx(5.0)
    assert _read("step_idle_pct.admit", run) == pytest.approx(9.5)
    # what the benchmark's own breakdown gives the whole of step() is the three together
    outside = xplane.reduce_planes(ps.load(run)["planes"], span_names=("engine.step",))
    assert dict(outside["idle_gaps"])["engine.step"] == pytest.approx(195e-6)
    assert sum(_read(f"step_idle_pct.{g}", run) for g in ("admit", "dispatch", "emit")) == pytest.approx(19.5)


KERNEL = 'custom_call_target=\\"tpu_custom_call\\"'
SCOPED = {
    1: ("%copy.3 = bf16[8]{0} copy(bf16[8]{0} %x)", "jit(chunk_fn)/while/body/kv_view/copy:", 5),
    # the same instruction text in another program: the program id decides
    2: ("%copy.3 = bf16[8]{0} copy(bf16[8]{0} %x)", "jit(fn)/Model/blocks_0/mlp/copy:", 7),
    # the compiler's own op_name: no path
    3: (f"%ragged-dot-none.4 = bf16[8]{{0}} custom-call(bf16[8]{{0}} %y), {KERNEL}", "ragged-dot-none", 5),
    4: ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %z)",
        "jit(chunk_fn)/while/body/closed_call/Model/layers_0/moe/moe.router/dot_general:", 5),
    5: ("%while.1 = (s32[]) while((s32[]) %t)", "jit(chunk_fn)/while:", 5),
    6: ("%copy-start.1 = (bf16[8]{0}, u32[]) copy-start(bf16[8]{0} %w)", None, 7),
    20: ("jit_chunk_fn(5)", None, None), 21: ("jit_fn(7)", None, None),
}


def test_scopes_reach_device_ops_through_the_event_metadata():
    # a while of 400 us encloses the view's copy (100), the router (50) and a
    # grouped matmul (200); the prefill program runs a copy of its own and a
    # pathless op: busy 550 us
    ops = [(5, 0, 400), (1, 0, 100), (4, 100, 50), (3, 150, 200), (2, 500, 100), (6, 600, 50)]
    run = _run_of(_device(ops, [(20, 0, 500), (21, 500, 500)], SCOPED), _host([]))
    seconds = ps.scope_seconds(run)
    assert seconds["busy_s"] == pytest.approx(550e-6)
    assert seconds["named_s"] == pytest.approx(500e-6)          # all but the copy-start
    assert _read("kv_view_dev_share_pct", run) == pytest.approx(100.0 * 100 / 550)   # not the prefill's copy
    assert ps.scope_share_pct(run, "mlp") == pytest.approx(100.0 * 100 / 550)
    # self times: the while's own 50 us, and what its path-carrying body ops take
    assert ps.scope_share_pct(run, "while") == pytest.approx(100.0 * (50 + 100 + 50) / 550)
    # the sparse block: what is under ``moe`` and the pathless grouped matmuls
    assert ps.scope_share_pct(run, "moe") == pytest.approx(100.0 * 50 / 550)
    assert _read("moe_block_dev_share_pct", run) == pytest.approx(100.0 * 250 / 550)
    assert ps.scope_share_pct(run, "no_such_scope") is None


def test_a_trace_without_the_programs_spans_leaves_every_metric_out():
    """The parent of the PR that added the spans, and a run that left no
    trace: ``None`` from every reader, nothing raised."""
    bare = _run_of(_device([(6, 0, 50)], [(21, 0, 100)], SCOPED), _host([("engine.step", 0, 300, {})]))
    for run in (bare, {"trace": {}, ps._CACHE: None}, {}):
        for name in NEW:
            assert _read(name, run) is None, name


def test_every_new_metric_has_its_entry_and_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    for name in NEW:
        assert set(entries[name]) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert set(entries[name]["workloads"]) <= cells and entries[name]["better"] == "lower"
        assert callable(harness.load_reader(name))
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)     # appended, in the issue's order


def test_paths_of_the_trace_recorded_on_a_v5e():
    """How a scope reaches a device op on the chip: the op line's event
    metadata carries ``tf_op`` (the ``op_name`` path) and ``program_id``."""
    with open(os.path.join(DATA, "v5e_small.xplane.pb"), "rb") as f:
        serialized = f.read()
    paths = ps.op_paths(serialized)["/device:TPU:0"]
    by_name = {xplane.base_name(name): (program, path) for (program, name), path in paths.items() if program}
    assert by_name["step"] == (1707662122187997888, "jit(step)/pallas_call:")       # the Pallas kernel
    assert by_name["fusion"][1] == "jit(step)/dot_general:"
    run = {"trace": {}, ps._CACHE: ps.from_serialized(serialized)}
    seconds = ps.scope_seconds(run)
    assert 0 < seconds["named_s"] <= seconds["busy_s"]
    assert ps.scope_share_pct(run, "jit(step)") == pytest.approx(100.0 * seconds["named_s"] / seconds["busy_s"])
    assert ps.spans(run, ps.STEP) == [] and _read("step_host_ms", run) is None    # recorded before the spans


def test_scopes_of_a_decode_chunk_recorded_on_a_v5e():
    """One decode-only ``step()`` of cell 1 as this program ran it on a v5e
    (PR 24's tree, seed 3200000017, the fourth step of the traced window):
    the host's ``nxd.*`` events of that step and the first 22 ms of the
    chunk's device ops with the metadata of those ops, cut out of the
    benchmark's own ``.xplane.pb``. A schema change that empties the decoder
    fails here."""
    with open(os.path.join(DATA, "v5e_decode_slice.xplane.pb"), "rb") as f:
        serialized = f.read()
    run = {"trace": {}, ps._CACHE: ps.from_serialized(serialized)}
    (module,) = [name for plane in run[ps._CACHE]["planes"] if plane.name == "/device:TPU:0"
                 for line in plane.lines if line.name == xplane.MODULES_LINE
                 for _, _, name, _ in xplane._events(line)]
    assert module.startswith("jit_chunk_fn(")
    program = int(ps._MODULE_ID.search(module).group(1))
    paths = ps.op_paths(serialized)["/device:TPU:0"]
    assert paths
    view = [path for (prog, _), path in paths.items() if prog == program and "kv_view" in path.split("/")]
    assert view and all(path.startswith("jit(chunk_fn)/") for path in view)
    seconds = ps.scope_seconds(run)
    assert {"attn._cached_attention", "copy", "fusion"} <= {base for base, _ in seconds["ops"]}
    assert not any("kv_view" in parts for (base, parts) in seconds["ops"] if base == "attn._cached_attention")
    assert 0 < _read("kv_view_dev_share_pct", run) < 100
    assert _read("moe_block_dev_share_pct", run) is None            # a dense model
    # the host side of the same step: decode only, its children cover it
    (step,) = ps.spans(run, ps.STEP)
    kids = [ps.children(run, step, name) for name in ("nxd.step.reap", ps.DISPATCH, ps.READBACK, ps.EMIT, "nxd.step.health")]
    assert all(len(k) == 1 for k in kids) and not ps.spans(run, ps.PREFILL)
    assert sum(k[0][1] - k[0][0] for k in kids) >= 0.95 * (step[1] - step[0])
    assert 0 < _read("step_host_ms", run) < 5


def _generated_xplane_pb2():
    """The one generated ``xplane_pb2`` this environment has is TensorFlow's.
    Its file needs only ``google.protobuf``, so it is loaded by path:
    importing ``tensorflow`` takes a quarter of a minute."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    path = spec and os.path.join(os.path.dirname(spec.origin), "tsl", "profiler", "protobuf", "xplane_pb2.py")
    if not path or not os.path.exists(path):
        pytest.skip("no generated xplane_pb2 in this environment")
    spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("recorded", ["v5e_small.xplane.pb", "v5e_decode_slice.xplane.pb"])
def test_the_wire_decoder_agrees_with_the_generated_protobuf_classes(recorded):
    """``op_paths`` reads the event metadata by field number. The generated
    classes read it by the schema: both must give the same table."""
    pb2 = _generated_xplane_pb2()
    with open(os.path.join(DATA, recorded), "rb") as f:
        serialized = f.read()
    space = pb2.XSpace()
    space.ParseFromString(serialized)
    want = {}
    for plane in space.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {key: meta.name for key, meta in plane.stat_metadata.items()}
        table = want.setdefault(plane.name, {})
        for meta in plane.event_metadata.values():
            path = program = None
            for stat in meta.stats:
                key = stat_names.get(stat.metadata_id)
                if key == "tf_op":
                    path = stat.str_value if stat.HasField("str_value") else stat_names.get(stat.ref_value)
                elif key == "program_id":
                    program = stat.uint64_value if stat.HasField("uint64_value") else stat.int64_value
            if path:
                for name in filter(None, (meta.name, meta.display_name)):
                    table[(program, name)] = path
    got = ps.op_paths(serialized)
    assert set(got) == set(want) and any(want.values())
    for plane, table in want.items():
        assert {k: v for k, v in got[plane].items() if k[0] is not None} == table


def _rehearse(cell):
    """One traced CPU rehearsal of ``cell`` in a process of its own, as
    ``test_rehearsal.py`` runs them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", NXD_TPU_PERSISTENT_CACHE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--rehearse", DATA, "--workload", cell,
         "--seed", str(2**31 + 5), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    assert "reader failed" not in done.stderr
    return json.loads([ln for ln in done.stdout.splitlines() if ln.strip()][-1])["metrics"]


def test_host_span_metrics_on_the_rehearsal_of_a_serve_cell():
    metrics = _rehearse("mixtral_chat_closed")
    for name in ("queue_wait_p90_ms", "engine_ttft_p50_ms", "prefill_stall_pct", "step_host_ms"):
        assert math.isfinite(metrics[name]["value"]) and metrics[name]["value"] >= 0, name
    assert metrics["step_host_ms"]["value"] > 0 and metrics["engine_ttft_p50_ms"]["value"] > 0
    # a CPU trace has no device plane: nothing is written under a device metric's name
    assert not {n for n in NEW if n.startswith(("step_idle", "kv_view", "moe_block"))} & set(metrics)


def test_host_span_metrics_on_the_rehearsal_of_the_train_cell():
    metrics = _rehearse("codegen2_train_tp4")
    assert metrics["train_dispatch_ms_p50"]["value"] > 0
    assert not set(NEW) - {"train_dispatch_ms_p50"} & set(metrics)
