"""graftlint command line.

    python -m neuronx_distributed_tpu.scripts.graftlint [paths...]

Exit codes: 0 clean (every finding baselined/pragma'd), 1 new violations or
a stale baseline, 2 usage error. Findings print as ``path:line:col: RULE
message`` — the repo's clickable convention.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from neuronx_distributed_tpu.scripts.graftlint import baseline as baseline_mod
from neuronx_distributed_tpu.scripts.graftlint import runner
from neuronx_distributed_tpu.scripts.graftlint.rules import EXPLAINS, TITLES


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="graftlint",
        description=(
            "Repo-native static analysis enforcing the donation, host-sync, "
            "recompile, explicit-SPMD seam, determinism, sharding-spec, "
            "trace-scope, hold-pairing and metrics-label invariants the hot "
            "paths depend on (rules GL01-GL09; see --explain RULE)."
        ),
    )
    p.add_argument(
        "paths", nargs="*", default=["neuronx_distributed_tpu"],
        help="files/directories to scan (default: the library package)",
    )
    p.add_argument(
        "--explain", metavar="RULE",
        help="print the catalog entry for RULE (GL00-GL09) and exit",
    )
    p.add_argument(
        "--select", metavar="RULES",
        help="comma-separated rule subset to run (e.g. GL01,GL04)",
    )
    p.add_argument(
        "--baseline", metavar="PATH",
        help="baseline file (default: <repo-root>/graftlint_baseline.json)",
    )
    p.add_argument(
        "--no-baseline", action="store_true",
        help="ignore the baseline: report every violation and fail on any",
    )
    p.add_argument(
        "--write-baseline", action="store_true",
        help=(
            "regenerate the baseline from this run's violations (the only "
            "way to shrink it after fixing a grandfathered finding — a "
            "stale baseline otherwise FAILS the run)"
        ),
    )
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = _build_parser().parse_args(argv)

    if args.explain is not None:
        rule = args.explain.upper()
        text = EXPLAINS.get(rule)
        if text is None:
            print(
                f"graftlint: unknown rule {rule!r} "
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
                f"graftlint: unknown rule(s) {', '.join(sorted(unknown))}",
                file=sys.stderr,
            )
            return 2

    for p in args.paths:
        if not os.path.exists(p):
            print(f"graftlint: no such path: {p}", file=sys.stderr)
            return 2

    root = runner.find_repo_root(args.paths[0])
    baseline_path = args.baseline or os.path.join(
        root, baseline_mod.DEFAULT_NAME
    )
    report = runner.run(
        args.paths, root=root, baseline_path=baseline_path, select=select,
        use_baseline=not args.no_baseline,
    )

    if args.write_baseline:
        # scope-aware: a subset-path or --select run refreshes only the
        # entries it actually re-checked and preserves the rest of the
        # grandfathered debt (save_merged)
        n = baseline_mod.save_merged(
            baseline_path, report.violations, report.scanned_relpaths,
            select=select, root=root,
        )
        print(
            f"graftlint: wrote {n} violation(s) to "
            f"{os.path.relpath(baseline_path, root)} "
            f"({len(report.violations)} from this run's scope)"
        )
        return 0

    diff = report.diff
    to_print = diff.new if diff is not None else report.violations
    for v in to_print:
        print(v.format())
    if diff is not None:
        for e in diff.stale:
            print(
                f"{e['path']}: stale baseline entry "
                f"[{e['rule']} {e.get('snippet', '')!r}] — the violation is "
                "gone; shrink the debt with --write-baseline"
            )

    n_total = len(report.violations)
    n_new = len(diff.new) if diff is not None else n_total
    n_base = len(diff.grandfathered) if diff is not None else 0
    n_stale = len(diff.stale) if diff is not None else 0
    summary = (
        f"graftlint: {report.files_scanned} file(s), {n_total} violation(s)"
        f" ({n_new} new, {n_base} baselined, {n_stale} stale baseline "
        f"entr{'y' if n_stale == 1 else 'ies'}, "
        f"{len(report.suppressed)} pragma-suppressed)"
    )
    print(summary)
    if report.failed:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
