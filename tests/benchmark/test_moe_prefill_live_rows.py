"""``moe_prefill_live_rows_pct`` (PR 55): the reader on a hand-made trace of
each listed cell's prefills (its tape's prompts in the engine's buckets, the
stats as the expert layers sum them), with no prefill in the traced window, on
traces of the PARENT's tree (first-token spans without the two stats: left
out, nothing raised), on the CPU rehearsal of a serve cell, and the entry
against its reader."""

import json
import os
import subprocess
import sys

import pytest

from neuronx_distributed_tpu.serving.engine import _bucket
from perfbench import program_spans as ps
from perfbench import run as harness
from perfbench import tape
from tests.benchmark.test_step_ledger_metrics import _run_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "tests", "benchmark", "data")
NAME = "moe_prefill_live_rows_pct"
# cell -> (its traffic, its row's columns, its expert layers, the sum of a block's prompts, the sum of
# their buckets: ISSUE 55's table)
CELLS = {
    "mixtral_chat_closed": ("chat_closed", 6144, 3, 7258, 9984),
    "dsv2lite_docs_closed": ("docs_closed", 32768, 6, 76141, 98816),
    "keye_longdocs_closed": ("longdocs_closed", 32768, 5, 107283, 125952),
    "glm5_agentdocs_closed": ("agentdocs_closed", 32768, 5, 71812, 94208),
    "trinity_mixedctx_closed": ("mixedctx_closed", 32768, 4, 72665, 94208),
    "zaya1_reasoning_closed": ("reasoning_closed", 16384, 10, 40832, 56576),
    "solar2_analysis_closed": ("analysis_closed", 32768, 8, 56712, 76800),
}


def _read(run):
    return harness.load_reader(NAME)(run)


def _prefills(cell, with_stats=True):
    """One block of the cell's tape as the engine prefills it: a prefill span
    and its first token's, 10 us apart, and a decode chunk after each."""
    traffic, row, layers, _, _ = CELLS[cell]
    spans = []
    for i, (prompt, answer) in enumerate(tape.block_lengths(tape.load_traffic(traffic, None))):
        bucket = _bucket(prompt, row, answer + 8 - 1)
        kept = {"moe_live_rows": prompt * layers, "moe_rows": bucket * layers} if with_stats else {}
        spans += [(ps.PREFILL, 20 * i, 8, {"rid": i, "prompt_tokens": prompt, "padded": bucket}),
                  (ps.FIRST_TOKEN, 20 * i + 5, 3, {"rid": i, **kept}),
                  (ps.READBACK, 20 * i + 10, 8, {"steps": 8})]
    return spans


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_reader_on_each_listed_cells_prefills(cell):
    """100 x the block's prompt tokens / its buckets, whatever the expert
    layers' count: the issue's table, from the cell's own tape."""
    _, _, _, prompts, buckets = CELLS[cell]
    spans = _prefills(cell)
    assert sum(s["moe_rows"] for n, _, _, s in spans if n == ps.FIRST_TOKEN) == buckets * CELLS[cell][2]
    assert _read(_run_of(spans)) == pytest.approx(100.0 * prompts / buckets, rel=1e-12)
    assert 70 < _read(_run_of(spans)) < 90
    # the window cuts the block: only the prefills whose first token lies in it count
    first = [s for s in spans if s[0] == ps.FIRST_TOKEN][0][3]
    assert _read(_run_of(spans, window=(0, 15))) == pytest.approx(100.0 * first["moe_live_rows"] / first["moe_rows"])


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_traced_window_without_a_prefill_reads_100_and_the_parents_tree_reads_nothing(cell):
    decode_only = [s for s in _prefills(cell) if s[0] == ps.READBACK]
    assert _read(_run_of(decode_only)) == 100.0
    # PR 54's tree: the spans are there, the two stats are not
    assert ps.spans(_run_of(_prefills(cell, with_stats=False)), ps.FIRST_TOKEN)
    assert _read(_run_of(_prefills(cell, with_stats=False))) is None


@pytest.mark.parametrize("run", ["v5e_small.xplane.pb", "v5e_decode_slice.xplane.pb", "v5e_chunk_gap.xplane.pb",
                                 "no_trace", "empty_record"])
def test_recorded_traces_of_earlier_trees_and_runs_without_a_trace_never_raise(run):
    if run.endswith(".pb"):
        with open(os.path.join(DATA, run), "rb") as f:
            record = {"trace": {}, ps._CACHE: ps.from_serialized(f.read())}
        assert _read(record) in (None, 100.0)    # 100.0: the recorded window holds no first token at all
    else:
        assert _read({"trace": {}, ps._CACHE: None} if run == "no_trace" else {}) is None


def test_the_entry_is_the_last_and_has_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "model step", "moves": "tpot_mean_ms", "workloads": list(CELLS)}
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert callable(harness.load_reader(NAME))


def test_the_rehearsal_of_a_serve_cell_prints_it():
    """One traced CPU rehearsal in a process of its own, as ``test_rehearsal.py``
    runs them: the engine's first-token spans carry the stats and the reader
    finds them (the tiny tape's prompts are padded to their buckets)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", NXD_TPU_PERSISTENT_CACHE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--rehearse", DATA,
         "--workload", "dsv2lite_docs_closed", "--seed", str(2**31 + 55), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    assert "reader failed" not in done.stderr
    metrics = json.loads([ln for ln in done.stdout.splitlines() if ln.strip()][-1])["metrics"]
    assert 0 < metrics[NAME]["value"] <= 100 and metrics[NAME]["unit"] == "%"
