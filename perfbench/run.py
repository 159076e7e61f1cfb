#!/usr/bin/env python3
"""One run of one benchmark cell: load, warm up, measure, print one JSON line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data. It reads ``BENCHMARK.json`` for the cell's
configuration and traffic mix and for the metrics the cell reports, then finds
everything else by name:

    perfbench/configs/<config>.json        sizes as run, source, reduced, assumed
    perfbench/traffic/<traffic>.json       parameters of the one tape generator
    perfbench/families/<family>.py         config keys -> the program's model class
    perfbench/references/<family>.py       the plain float32 reference
    perfbench/runners/<runner>.py          what "one run" means (serve, train)
    perfbench/layer_metrics/<metric>.py    one small reader per per-layer metric

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics (and ``device.busy_s``/``window_s`` and a ``breakdown``) from a run
whose last seconds are traced. No TPU, or fewer chips than the cell asks for:
exit code 2 and no result. ``--rehearse <dir>`` (tests, README) lets a CPU
stand in and takes the cell's configuration and traffic, at a size a CPU can
run, from ``<dir>/configs/<config>.json`` and ``<dir>/traffic/<traffic>.json``;
its line names ``"platform": "cpu"`` and is no measurement.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # process start, as near as Python lets us stamp it

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"perfbench: no workload {name!r} in the benchmark file")


def metrics_of_cell(bench: dict, group: str, cell: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports: all
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[group] if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    """The reader of per-layer metric ``name``: ``layer_metrics/<name>.py``
    with a ``read(run) -> float | None``. Loaded by path: a metric's name may
    hold dots."""
    path = os.path.join(ROOT, "perfbench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("perfbench_reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", metavar="DIR", default=None,
                   help="let a CPU stand in for the chip, with the tiny configs/ and traffic/ "
                        "of DIR (tests and README only)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(args.rehearse, "configs", cell["config"] + ".json") if args.rehearse
                       else os.path.join(ROOT, config_entry["file"]))
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        log(f"needs a TPU, found platform {devices[0].platform!r}: no result")
        return 2
    if args.rehearse:
        devices = devices[: int(cell["chips"])]
    if len(devices) != int(cell["chips"]):
        log(f"cell {cell['name']} needs {cell['chips']} chips, JAX reports {len(devices)}: no result")
        return 2

    # the program owns the compile-cache knob; it leaves a directory placed
    # from outside (JAX_COMPILATION_CACHE_DIR) alone and otherwise takes this
    # fixed path inside the checkout
    from neuronx_distributed_tpu.inference import aot

    cache_dir = aot.enable_persistent_cache(os.path.join(ROOT, ".jax_cache"))
    log(f"cell {cell['name']} seed {args.seed} seconds {seconds:g} trace {args.trace} on "
        f"{len(devices)} x {devices[0].device_kind} ({devices[0].platform}); compile cache {cache_dir}")

    from perfbench import tape

    traffic = tape.load_traffic(
        cell["traffic"], os.path.join(args.rehearse, "traffic") if args.rehearse else None)
    runner = importlib.import_module(f"perfbench.runners.{config['runner']}")
    run = runner.run(
        config=config, traffic=traffic, seed=int(args.seed), seconds=seconds,
        trace=bool(args.trace), devices=devices, t_start=_T_START,
        out_dir=os.path.join(ROOT, "perfbench_out"), log=log,
    )

    device = run["device"] = device_record(devices)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in metrics_of_cell(bench, group, cell["name"]):
        if args.trace:
            try:
                value = load_reader(entry["name"])(run)
            except Exception as e:  # one reader's fault must not cost the run its other metrics
                log(f"per-layer metric {entry['name']} left out: reader failed with {e!r}")
                value = None
        else:
            value = run["end_to_end"].get(entry["name"])
        if value is None:
            if not args.trace:
                log(f"end-to-end metric {entry['name']} has no value in this run")
                run["correct"] = False
            continue
        metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}

    line = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        reduced = run["trace"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    for note in run.get("notes", []):
        log(note)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
