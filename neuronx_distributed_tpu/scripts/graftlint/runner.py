"""graftlint orchestration: collect files, run rules, apply pragmas and the
baseline ratchet. Importable API (the tier-1 test calls
:func:`run`) — the CLI in ``cli.py`` is a thin shell over it."""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence

from neuronx_distributed_tpu.scripts.graftlint import baseline as baseline_mod
from neuronx_distributed_tpu.scripts.graftlint import pragmas
from neuronx_distributed_tpu.scripts.graftlint.core import (
    SourceFile,
    Violation,
    assign_occurrences,
)
from neuronx_distributed_tpu.scripts.graftlint.rules import run_rules


@dataclasses.dataclass
class Report:
    """One run's outcome. ``violations`` are post-pragma findings;
    ``diff`` applies the baseline ratchet (None when run baseline-less)."""

    violations: List[Violation]
    suppressed: List[Violation]
    files_scanned: int
    scanned_relpaths: List[str] = dataclasses.field(default_factory=list)
    diff: Optional[baseline_mod.BaselineDiff] = None

    @property
    def failed(self) -> bool:
        if self.diff is not None:
            return not self.diff.clean
        return bool(self.violations)

    def by_rule(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for v in self.violations:
            counts[v.rule] = counts.get(v.rule, 0) + 1
        return dict(sorted(counts.items()))


def find_repo_root(start: str) -> str:
    """Nearest ancestor holding a pyproject.toml (violation paths and the
    default baseline location are anchored there); falls back to ``start``."""
    d = os.path.abspath(start)
    if os.path.isfile(d):
        d = os.path.dirname(d)
    while True:
        if os.path.exists(os.path.join(d, "pyproject.toml")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return os.path.abspath(start if os.path.isdir(start) else os.path.dirname(start))
        d = parent


def collect_sources(paths: Sequence[str], root: str) -> List[SourceFile]:
    files: List[str] = []
    for p in paths:
        p = os.path.abspath(p)
        if os.path.isfile(p):
            if p.endswith(".py"):
                files.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(
                d for d in dirnames
                if not d.startswith(".") and d != "__pycache__"
            )
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    files.append(os.path.join(dirpath, fn))
    out: List[SourceFile] = []
    for f in sorted(set(files)):
        rel = os.path.relpath(f, root)
        src = SourceFile.load(f, rel)
        if src is not None:
            out.append(src)
    return out


def scan(paths: Sequence[str], root: Optional[str] = None,
         select: Optional[set] = None) -> Report:
    """Run the rules + pragma layer over ``paths`` (no baseline)."""
    if root is None:
        root = find_repo_root(paths[0] if paths else os.getcwd())
    violations: List[Violation] = []
    suppressed: List[Violation] = []
    sources = collect_sources(paths, root)
    for src in sources:
        raw = run_rules(src, select=select)
        kept, supp = pragmas.apply(src, raw)
        violations.extend(kept)
        suppressed.extend(supp)
    return Report(
        violations=assign_occurrences(violations),
        suppressed=suppressed,
        files_scanned=len(sources),
        scanned_relpaths=[s.relpath for s in sources],
    )


def run(paths: Sequence[str], root: Optional[str] = None,
        baseline_path: Optional[str] = None,
        select: Optional[set] = None,
        use_baseline: bool = True) -> Report:
    """Full run: scan + ratchet against the checked-in baseline."""
    if root is None:
        root = find_repo_root(paths[0] if paths else os.getcwd())
    report = scan(paths, root=root, select=select)
    if use_baseline:
        if baseline_path is None:
            baseline_path = os.path.join(root, baseline_mod.DEFAULT_NAME)
        report.diff = baseline_mod.diff(
            report.violations, baseline_mod.load(baseline_path)
        )
    return report
