"""graftverify command line.

    python -m neuronx_distributed_tpu.scripts.graftverify [--tp N] ...

graftlint scans files; graftverify needs LIVE lowered programs, so the CLI
builds the repo's reference workload — a tiny paged ServingEngine (tp
meshes and tp_comms routing on request) — drives a short request wave to
register every hot program in its ledger, then verifies the lowered IR and
ratchets against ``graftverify_baseline.json``. Findings print as
``<ledger/program>:0:0: RULE message`` (the graftlint report convention);
exit codes: 0 clean, 1 new findings or a stale baseline, 2 usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from neuronx_distributed_tpu.scripts.graftverify import runner as runner_mod
from neuronx_distributed_tpu.scripts.graftverify.core import (
    DEFAULT_BASELINE_NAME,
    EXPLAINS,
    TITLES,
)


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="graftverify",
        description=(
            "IR-level verification of the ledgered hot programs: donation "
            "aliasing, transfer census, the collective wire-byte ratchet "
            "dispatch-key stability and AOT manifest coverage (checks "
            "GV01-GV05; see --explain RULE)."
        ),
    )
    p.add_argument(
        "--explain", metavar="RULE",
        help="print the catalog entry for RULE (GV01-GV05) and exit",
    )
    p.add_argument(
        "--select", metavar="RULES",
        help="comma-separated check subset to run (e.g. GV01,GV03)",
    )
    p.add_argument(
        "--baseline", metavar="PATH",
        help=(
            "baseline file (default: <repo-root>/"
            f"{DEFAULT_BASELINE_NAME})"
        ),
    )
    p.add_argument(
        "--no-baseline", action="store_true",
        help="ignore the baseline: report every finding and fail on any",
    )
    p.add_argument(
        "--write-baseline", action="store_true",
        help=(
            "regenerate the baseline from this run's findings (the only "
            "way to shrink — or knowingly re-pin — the wire-byte ratchet)"
        ),
    )
    p.add_argument(
        "--tp", type=int, default=1,
        help=(
            "verify the TP-sharded engine at this degree (CPU mesh proxy; "
            "adds the collective wire-byte table to the report)"
        ),
    )
    p.add_argument(
        "--tp-comms", default="off", choices=["off", "exact", "quant"],
        help=(
            "route row-parallel reductions through the explicit ring "
            "(exact psum or the EQuARX int8 ring) so GV03 sees the "
            "collectives — 'off' leaves them to GSPMD (invisible at "
            "lowering, by design)"
        ),
    )
    p.add_argument(
        "--json", action="store_true",
        help="emit the stats + collective tables as one JSON object",
    )
    p.add_argument(
        "--manifest", metavar="PATH",
        help=(
            "AOT manifest (file or cache dir) to check GV05 coverage "
            "against: every program the workload dispatches must be in it, "
            "and it must name no program the workload doesn't know"
        ),
    )
    p.add_argument(
        "--write-manifest", metavar="PATH",
        help=(
            "after driving the workload, save its ledger's AOT manifest "
            "to PATH (a dir gets manifest.json inside) for prewarm/GV05"
        ),
    )
    return p


def _build_ledgers(tp: int, tp_comms: str):
    """The reference workload: tiny paged engine, one request wave. Import
    and device setup stay inside so ``--explain`` never touches jax."""
    if tp > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={max(tp, 8)}"
            ).strip()
    import jax

    # the reference workload is a CPU proxy by contract (bit-exact
    # arithmetic, real IR, no chip dependency), whatever the machine holds.
    # The pin must land BEFORE the first backend touch — jax.devices()
    # initializes and caches backends, after which a jax_platforms update
    # is a silent no-op.
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
    from neuronx_distributed_tpu.serving import ServingEngine

    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), ids)
    kw = {}
    if tp > 1:
        kw["tp"] = tp
        if tp_comms != "off":
            from neuronx_distributed_tpu.parallel.quantized_collectives import (
                QuantizedAllReduceConfig,
            )

            kw["tp_comms"] = QuantizedAllReduceConfig(
                enabled=(tp_comms == "quant")
            )
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4, kv_page_size=8,
    **kw)
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    for i in range(2):
        prompt = rng.randint(1, cfg.vocab_size, size=6 + i).astype(np.int32)
        engine.submit(prompt, gcfg, key=jax.random.PRNGKey(i))
    engine.run()
    return {"serving": engine.programs}


def main(argv: Optional[List[str]] = None) -> int:
    args = _build_parser().parse_args(argv)

    if args.explain is not None:
        rule = args.explain.upper()
        text = EXPLAINS.get(rule)
        if text is None:
            print(
                f"graftverify: unknown rule {rule!r} "
                f"(known: {', '.join(sorted(EXPLAINS))})",
                file=sys.stderr,
            )
            return 2
        print(text)
        return 0

    select = None
    if args.select:
        select = {r.strip().upper() for r in args.select.split(",") if r.strip()}
        unknown = select - set(TITLES)
        if unknown:
            print(
                f"graftverify: unknown rule(s) {', '.join(sorted(unknown))}",
                file=sys.stderr,
            )
            return 2

    if args.tp < 1:
        print(f"graftverify: --tp must be >= 1, got {args.tp}",
              file=sys.stderr)
        return 2
    if args.tp_comms != "off" and args.tp == 1:
        print("graftverify: --tp-comms needs --tp > 1 (no reduction to "
              "route on a mesh-free engine)", file=sys.stderr)
        return 2

    from neuronx_distributed_tpu.scripts.graftlint.runner import find_repo_root

    root = find_repo_root(os.path.dirname(os.path.abspath(__file__)))
    baseline_path = args.baseline or os.path.join(root, DEFAULT_BASELINE_NAME)

    # one baseline file, one slice per workload configuration: pinning the
    # tp=2 tables must never make the default tp=1 CI run see stale entries
    scope = f"tp{args.tp}" + (
        "" if args.tp_comms == "off" else f"+{args.tp_comms}"
    )
    ledgers = _build_ledgers(args.tp, args.tp_comms)

    if args.write_manifest:
        saved = ledgers["serving"].manifest().save(args.write_manifest)
        print(f"graftverify: wrote AOT manifest to {saved}")

    report = runner_mod.verify(
        ledgers, root=root, baseline_path=baseline_path, select=select,
        use_baseline=not args.no_baseline, scope=scope,
        manifest=args.manifest,
    )

    if args.write_baseline:
        n = runner_mod.write_baseline(baseline_path, report, scope=scope)
        print(
            f"graftverify: wrote {n} finding(s) to "
            f"{os.path.relpath(baseline_path, root)} [scope {scope}]"
        )
        return 0

    if args.json:
        print(json.dumps(
            {
                "stats": report.stats(),
                "by_rule": report.by_rule(),
                "collective_tables": report.collective_tables(),
                "failed": report.failed,
            },
            indent=2, sort_keys=True,
        ))

    diff = report.diff
    to_print = diff.new if diff is not None else report.findings
    for v in to_print:
        print(v.format())
    if diff is not None:
        for e in diff.stale:
            print(
                f"{e['path']}: stale baseline entry "
                f"[{e['rule']} {e.get('snippet', '')!r}] — the finding is "
                "gone; shrink the ratchet with --write-baseline"
            )

    stats = report.stats()
    n_total = len(report.findings)
    n_new = len(diff.new) if diff is not None else n_total
    n_base = len(diff.grandfathered) if diff is not None else 0
    n_stale = len(diff.stale) if diff is not None else 0
    print(
        f"graftverify: {stats['programs_checked']} program(s), "
        f"{stats['variants_checked']} variant(s) lowered, "
        f"{stats['donations_declared']} donation(s) declared / "
        f"{stats['donations_aliased']} aliased / "
        f"{stats['donations_deferred']} deferred / "
        f"{stats['donations_pruned']} pruned / "
        f"{stats['donations_dropped']} dropped, "
        f"{stats['transfer_ops']} transfer op(s), "
        f"{stats['collective_ops']} collective op(s) "
        f"({stats['collective_wire_bytes']}B/rank), "
        f"{n_total} finding(s) ({n_new} new, {n_base} baselined, "
        f"{n_stale} stale baseline entr{'y' if n_stale == 1 else 'ies'}, "
        f"{len(report.suppressed)} waived)"
    )
    if report.failed:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
