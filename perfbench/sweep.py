#!/usr/bin/env python3
"""Find a serving cell's knee once, by a sweep on the chip.

    python3 perfbench/sweep.py --workload <cell> --rates 1,2,3 --seconds 24 --out <file>

One process: the cell's engine is built and warmed up once, then the cell's
own tape is offered at each rate in turn (lowest first, drained in between).
The knee is the highest rate at which the backlog when the window closes is no
larger than after its first quarter. The cell then runs at a FIXED rate
written into its traffic file (0.75 of the knee); the benchmark never
searches. The result is kept beside the traffic file so that a later
``benchmark`` PR can see when the knee has moved.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def depth_at(rows, t):
    """Queue depth at the last step that began before ``t``."""
    seen = [d for when, d in rows if when <= t]
    return seen[-1] if seen else 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests a second")
    p.add_argument("--seconds", type=float, default=24.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    import jax

    from perfbench import run as harness
    from perfbench import stats, tape
    from perfbench.runners import serve
    from perfbench.spans import Spans

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"needs a TPU, found {devices[0].platform!r}: no result")
        return 2
    from neuronx_distributed_tpu.inference import aot

    aot.enable_persistent_cache(os.path.join(ROOT, ".jax_cache"))
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    traffic = tape.load_traffic(cell["traffic"])

    spans = Spans()
    engine, _family, _params, vocab = serve.build(config, args.seed, spans, harness.log)
    serve._warm_up(engine, traffic, vocab, args.seed, spans)
    rows = []
    for rate in sorted(float(r) for r in args.rates.split(",")):
        offered = dict(traffic, rate_rps=rate, drain_cap_s=60.0)
        w = serve.window(engine, offered, args.seed, args.seconds, vocab, spans)
        t_open, t_close = w["window"]
        row = {
            "rate_rps": rate,
            "requests": w["sample"],
            "failed": w["failed"],
            "backlog_first_quarter": depth_at(w["depths"], t_open + args.seconds / 4.0),
            "backlog_end": w["backlog_end"],
            "ttft_p50_ms": stats.tail_with_missing(w["ttfts"], 50),
            "ttft_p90_ms": w["ttft_p90_ms"],
            "gen_lag_p90_ms": stats.percentile(w["lags_ms"], 90),
            "tpot_mean_ms": w["tpot_mean_ms"],
            "tokens_per_s": w["serve_tokens_per_s"],
            "drain_s": w["t_end"] - t_close,
            "preemptions": w["preemptions_in_window"],
            "compiles": w["compiles_in_window"],
        }
        harness.log(json.dumps(row))
        rows.append(row)
        engine.run()   # whatever the drain cap left behind
    sustained = [r["rate_rps"] for r in rows if r["backlog_end"] <= r["backlog_first_quarter"]]
    result = {
        "workload": cell["name"], "seconds": args.seconds, "seed": args.seed,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)},
        "knee_rps": max(sustained) if sustained else None,
        "rule": "highest rate whose backlog at the window's close is no larger than after its first quarter",
        "rows": rows,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
