"""A CPU rehearsal of every cell at tiny size: the same command path, the
same last line. Nothing here is a measurement (the line says ``cpu``)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "benchmark", "data")    # configs/ and traffic/ at a CPU's size
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _harness(*args):
    """``perfbench/run.py`` as the driver starts it: a process of its own. A
    rehearsal in the test's process would leave a profiler session, a mesh and
    a compile-cache setting behind for the tests that follow it (a worker that
    had traced in-process aborted in a later, unrelated test)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", NXD_TPU_PERSISTENT_CACHE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


def _run(cell, trace, seed=2**31 + 17):
    done = _harness("--rehearse", TINY, "--workload", cell, "--seed", str(seed),
                    "--seconds", "2", "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    return json.loads(lines[-1])


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in _bench()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(cell):
    line = _run(cell, 0)
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in harness.metrics_of_cell(_bench(), "end_to_end", cell)}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["platform"] == "cpu" and line["device"]["kind"]
    assert line["device"]["count"] == next(w["chips"] for w in _bench()["workloads"] if w["name"] == cell)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line(cell):
    line = _run(cell, 1)
    assert KEYS | {"breakdown"} <= set(line)
    allowed = {m["name"] for m in harness.metrics_of_cell(_bench(), "per_layer", cell)}
    assert set(line["metrics"]) <= allowed and line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_no_result():
    done = _harness("--workload", CELLS[0], "--seconds", "1")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


def test_every_cell_and_metric_is_found_by_name():
    """What BENCHMARK.json names exists as a file of its own."""
    bench = _bench()
    for config in bench["configs"]:
        body = json.load(open(os.path.join(ROOT, config["file"])))
        assert {"source", "reduced", "assumed", "model", "family", "runner"} <= set(body)
        assert body["reduced"] == config["reduced"]
        assert os.path.exists(os.path.join(ROOT, "perfbench", "families", body["family"] + ".py"))
        assert os.path.exists(os.path.join(ROOT, "perfbench", "references", body["family"] + ".py"))
        assert os.path.exists(os.path.join(ROOT, "perfbench", "runners", body["runner"] + ".py"))
    for cell in bench["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "perfbench", "traffic", cell["traffic"] + ".json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "perfbench", "layer_metrics", metric["name"] + ".py"))
        assert metric["moves"] in e2e
