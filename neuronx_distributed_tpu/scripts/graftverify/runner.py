"""graftverify orchestration: enumerate ledgered programs, lower, check,
ratchet.

The importable API (the tests and the CLI both call :func:`verify`)
mirrors graftlint's runner: a run produces a report whose findings are
graftlint ``Violation``s, diffed against the checked-in
``graftverify_baseline.json`` with the SAME fingerprint ratchet (new
finding fails; a fixed finding leaves a stale entry that also fails until
the baseline is regenerated — debt only shrinks consciously).

Suppression is by WAIVER, not pragma — lowered IR has no comment lines:
``verify(..., waivers={"decode_chunk": {"GV04": "lazy fallback rebuild"}})``
suppresses a rule for one program WITH its mandatory reason; a reasonless
waiver is itself a finding (GV00, graftlint's pragma-hygiene contract).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Mapping, Optional

from neuronx_distributed_tpu.scripts.graftlint import baseline as baseline_mod
from neuronx_distributed_tpu.scripts.graftlint.core import (
    Violation,
    assign_occurrences,
)
from neuronx_distributed_tpu.scripts.graftverify import ir as ir_mod
from neuronx_distributed_tpu.scripts.graftverify.core import (
    DEFAULT_BASELINE_NAME,
    finding,
)


@dataclasses.dataclass
class VariantAudit:
    """Everything graftverify derived from ONE lowered signature."""

    signature: str
    donations: dict  # donation_table()
    transfers: List[dict]  # transfer_census()
    collectives: dict  # collective_table()


@dataclasses.dataclass
class ProgramAudit:
    """One ledgered program's verification record."""

    ledger: str
    name: str
    dispatches: int
    compiles: int
    variants: List[VariantAudit] = dataclasses.field(default_factory=list)
    uncaptured: int = 0  # variants with no retraceable signature (AOT)
    lower_errors: List[str] = dataclasses.field(default_factory=list)

    @property
    def collective_table(self) -> dict:
        """Merged per-program collective table (all captured variants)."""
        merged: Dict[str, Dict[str, int]] = {}
        detail: Dict[tuple, int] = {}
        for v in self.variants:
            for kind, row in v.collectives["by_kind"].items():
                dst = merged.setdefault(
                    kind,
                    {"ops": 0, "elements": 0, "payload_bytes": 0,
                     "wire_bytes": 0},
                )
                for k in dst:
                    dst[k] += row[k]
            for d in v.collectives.get("detail", ()):
                key = (d["kind"], d["elements"], d["elt_bytes"],
                       d["ranks"], d["wire_bytes"])
                detail[key] = detail.get(key, 0) + d["ops"]
        total = sum(r["wire_bytes"] for r in merged.values())
        ops = sum(r["ops"] for r in merged.values())
        return {
            "by_kind": dict(sorted(merged.items())),
            "detail": [
                {"kind": k, "elements": e, "elt_bytes": b, "ranks": r,
                 "wire_bytes": wb, "ops": n}
                for (k, e, b, r, wb), n in sorted(
                    detail.items(),
                    key=lambda it: (it[0][0], it[0][1], it[0][2]),
                )
            ],
            "ops": ops,
            "wire_bytes": total,
        }


@dataclasses.dataclass
class VerifyReport:
    """One run's outcome, shaped like graftlint's Report: post-waiver
    findings plus the audit data the byte tables read."""

    findings: List[Violation]
    suppressed: List[Violation]
    audits: List[ProgramAudit]
    diff: Optional[baseline_mod.BaselineDiff] = None

    @property
    def failed(self) -> bool:
        if self.diff is not None:
            return not self.diff.clean
        return bool(self.findings)

    def audit(self, name: str, ledger: Optional[str] = None
              ) -> Optional[ProgramAudit]:
        for a in self.audits:
            if a.name == name and (ledger is None or a.ledger == ledger):
                return a
        return None

    def by_rule(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for v in self.findings:
            counts[v.rule] = counts.get(v.rule, 0) + 1
        return dict(sorted(counts.items()))

    # --- aggregates (CLI summary) ---------------------------------------------

    def stats(self) -> dict:
        donations_declared = 0
        donations_aliased = 0
        donations_deferred = 0
        donations_pruned = 0
        donations_dropped = 0
        transfer_ops = 0
        variants = 0
        uncaptured = 0
        wire_bytes = 0
        collective_ops = 0
        for a in self.audits:
            uncaptured += a.uncaptured
            for v in a.variants:
                variants += 1
                donations_declared += len(v.donations["declared"])
                donations_aliased += len(
                    set(v.donations["declared"])
                    & set(v.donations["aliased"])
                )
                donations_deferred += len(v.donations["deferred"])
                donations_pruned += len(v.donations["pruned"])
                donations_dropped += len(v.donations["dropped"])
                transfer_ops += sum(t["count"] for t in v.transfers)
                wire_bytes += v.collectives["wire_bytes"]
                collective_ops += v.collectives["ops"]
        return {
            "programs_checked": len(self.audits),
            "variants_checked": variants,
            "variants_uncaptured": uncaptured,
            "donations_declared": donations_declared,
            "donations_aliased": donations_aliased,
            "donations_deferred": donations_deferred,
            "donations_pruned": donations_pruned,
            "donations_dropped": donations_dropped,
            "transfer_ops": transfer_ops,
            "collective_ops": collective_ops,
            "collective_wire_bytes": wire_bytes,
            "findings": len(self.findings),
            "suppressed": len(self.suppressed),
        }

    def collective_tables(self) -> Dict[str, dict]:
        """program → merged collective table, only programs that move
        bytes (the per-step wire-byte table tests pin)."""
        out = {}
        for a in self.audits:
            table = a.collective_table
            if table["ops"]:
                out[f"{a.ledger}/{a.name}"] = table
        return out


def _normalize_ledgers(ledgers) -> Dict[str, object]:
    from neuronx_distributed_tpu.observability.programs import ProgramLedger

    if isinstance(ledgers, ProgramLedger):
        return {"programs": ledgers}
    if isinstance(ledgers, Mapping):
        return dict(ledgers)
    raise TypeError(
        "verify() takes a ProgramLedger or a {name: ProgramLedger} mapping, "
        f"got {type(ledgers).__name__}"
    )


def _audit_program(ledger_key: str, info) -> ProgramAudit:
    audit = ProgramAudit(
        ledger=ledger_key, name=info.name,
        dispatches=info.dispatches, compiles=info.compiles,
    )
    for var in info.variants:
        if not var.captured:
            audit.uncaptured += 1
            continue
        try:
            lowered = var.lower()
        except Exception as e:  # a hot program that cannot re-trace is a
            # verification gap the report must carry, never a crash
            audit.lower_errors.append(
                f"{var.signature}: {type(e).__name__}: {str(e)[:200]}"
            )
            continue
        if lowered is None:
            audit.uncaptured += 1
            continue
        audit.variants.append(VariantAudit(
            signature=var.signature,
            donations=ir_mod.donation_table(lowered),
            transfers=ir_mod.transfer_census(lowered),
            collectives=ir_mod.collective_table(lowered),
        ))
    return audit


def _check_findings(audit: ProgramAudit) -> List[Violation]:
    out: List[Violation] = []
    key, name = audit.ledger, audit.name
    for err in audit.lower_errors:
        out.append(finding(
            "GV00", key, name, snippet=f"{name}:lower-failed",
            message=(
                "program could not be re-lowered for verification "
                f"({err}) — a ledgered hot program must stay traceable "
                "or carry a waiver"
            ),
        ))
    for v in audit.variants:
        d = v.donations
        if d["dropped"]:
            dropped = ", ".join(
                f"arg{i}={d['dropped_avals'].get(i, '?')}"
                for i in d["dropped"]
            )
            out.append(finding(
                "GV01", key, name,
                snippet=(
                    f"{v.signature}:donated={len(d['declared'])}"
                    f":aliased={len(d['aliased'])}"
                ),
                message=(
                    f"{len(d['dropped'])} of {len(d['declared'])} declared "
                    "donation(s) did NOT materialize as input_output_alias "
                    f"in the lowered IR ({dropped}) — the donated buffer is "
                    "silently copied every dispatch (double HBM on the hot "
                    "path); make the donated leaf's dtype/shape reachable "
                    "in an output or waive with the reason"
                ),
            ))
        for t in v.transfers:
            tgt = f" target={t['target']}" if t["target"] else ""
            out.append(finding(
                "GV02", key, name,
                snippet=f"{v.signature}:{t['op']}:{t['target']}",
                message=(
                    f"{t['count']} {t['op']}{tgt} op(s) inside a ledgered "
                    "hot program — compiled-in host transfers serialize "
                    "every dispatch and never show up in the source-level "
                    "sync budget (GL02); remove the callback or waive with "
                    "the reason"
                ),
            ))
        if v.collectives["ops"]:
            basis = ir_mod.stable_table_basis(v.collectives)
            out.append(finding(
                "GV03", key, name,
                snippet=f"{v.signature}:{basis}",
                message=(
                    "collective wire-byte table: "
                    f"{basis} (total {v.collectives['wire_bytes']}B/rank "
                    "per dispatch). Pin it with --write-baseline; once in "
                    "graftverify_baseline.json any byte movement here "
                    "fails the ratchet until consciously regenerated"
                ),
            ))
    known_sigs = (
        len(audit.variants) + audit.uncaptured + len(audit.lower_errors)
    )
    if audit.compiles > max(known_sigs, 1):
        out.append(finding(
            "GV04", key, name,
            snippet=f"{name}:recompile-hazard",
            message=(
                f"{audit.compiles} XLA compiles for "
                f"{known_sigs} distinct "
                "shape/dtype signature(s) — the dispatch cache is churning "
                "on something the aval skeleton cannot see (weak_type, "
                "uncommitted inputs, sharding/layout flips: the GL03 "
                "class, observed at the cache layer). Stabilize the "
                "dispatch key or waive an intentional rebuild"
            ),
        ))
    return out


def _manifest_findings(audits: List[ProgramAudit], manifest
                       ) -> List[Violation]:
    """GV05: every program runtime traffic dispatched must appear in the
    prewarmed manifest; every manifest entry must name a program some
    ledger knows. ``dispatches`` excludes prewarm replays by construction
    (the ledger routes those to ``prewarm_dispatches``), so a replay can
    never fake coverage."""
    names = (
        set(manifest.names()) if hasattr(manifest, "names")
        else set(manifest)
    )
    out: List[Violation] = []
    known = set()
    for a in audits:
        known.add(a.name)
        if a.dispatches > 0 and a.name not in names:
            out.append(finding(
                "GV05", a.ledger, a.name,
                snippet=f"{a.name}:missing-from-manifest",
                message=(
                    f"program dispatched {a.dispatches}x at runtime but "
                    "absent from the prewarm manifest — its compile lands "
                    "inside the first request's TTFT on every cold start; "
                    "regenerate the manifest from a run that exercises "
                    "this path (ledger.manifest()) or waive with the "
                    "reason"
                ),
            ))
    for name in sorted(names - known):
        out.append(finding(
            "GV05", "manifest", name,
            snippet=f"{name}:stale-manifest-entry",
            message=(
                "manifest names a program no audited ledger knows — a "
                "stale entry (renamed program, removed code path) that "
                "prewarm will silently skip forever; regenerate the "
                "manifest or waive with the reason"
            ),
        ))
    return out


def _apply_waivers(
    findings: List[Violation],
    waivers: Optional[Mapping[str, Mapping[str, str]]],
    audits: List[ProgramAudit],
):
    """Split findings into (kept, suppressed) per the waiver map. A waiver
    with an empty reason is invalid and surfaces as GV00 (the graftlint
    mandatory-reason contract)."""
    kept: List[Violation] = []
    suppressed: List[Violation] = []
    bad: List[Violation] = []
    waivers = waivers or {}
    for prog, rules in waivers.items():
        for rule, reason in rules.items():
            if not str(reason or "").strip():
                bad.append(finding(
                    "GV00", "waivers", prog, snippet=f"{prog}:{rule}",
                    message=(
                        f"waiver for {rule} on {prog!r} is missing its "
                        "mandatory reason — say WHY the finding is "
                        "acceptable"
                    ),
                ))
    for v in findings:
        prog = v.path.strip("<>").split("/", 1)[-1]
        rules = waivers.get(prog, {})
        reason = rules.get(v.rule)
        if reason is not None and str(reason).strip():
            suppressed.append(v)
        else:
            kept.append(v)
    kept.extend(bad)
    return kept, suppressed


def verify(
    ledgers,
    root: Optional[str] = None,
    baseline_path: Optional[str] = None,
    select: Optional[set] = None,
    use_baseline: bool = True,
    waivers: Optional[Mapping[str, Mapping[str, str]]] = None,
    scope: str = "tp1",
    manifest=None,
) -> VerifyReport:
    """Run every IR check over every program of ``ledgers`` (a
    ProgramLedger or ``{name: ProgramLedger}``), then ratchet against the
    checked-in baseline. Lowering is a trace per captured signature —
    ZERO XLA compiles, zero device→host syncs.

    ``scope`` names the workload configuration (the CLI passes e.g.
    ``tp2+quant``): one shared baseline file holds every configuration's
    pinned tables side by side, and a run only diffs against — and
    :func:`write_baseline` only refreshes — the entries of ITS scope, so
    pinning the tp=2 byte table can never turn the tp=1 CI run stale.

    ``manifest`` (a :class:`~...inference.aot.ProgramManifest`, a path to
    one, or a bare set of program names) arms GV05: runtime-dispatched
    programs must be covered by it, and it must carry no stale names.
    Without a manifest GV05 does not run."""
    audits: List[ProgramAudit] = []
    for key, ledger in _normalize_ledgers(ledgers).items():
        for info in ledger.programs().values():
            audits.append(_audit_program(key, info))
    findings: List[Violation] = []
    for audit in audits:
        for f in _check_findings(audit):
            if select is not None and f.rule not in select:
                continue
            findings.append(f)
    if manifest is not None:
        if isinstance(manifest, (str, os.PathLike)):
            from neuronx_distributed_tpu.inference.aot import ProgramManifest

            manifest = ProgramManifest.load(os.fspath(manifest))
        for f in _manifest_findings(audits, manifest):
            if select is not None and f.rule not in select:
                continue
            findings.append(f)
    findings, suppressed = _apply_waivers(findings, waivers, audits)
    report = VerifyReport(
        findings=assign_occurrences(findings),
        suppressed=suppressed,
        audits=audits,
    )
    if use_baseline:
        if baseline_path is None:
            if root is None:
                from neuronx_distributed_tpu.scripts.graftlint.runner import (
                    find_repo_root,
                )

                root = find_repo_root(os.getcwd())
            baseline_path = os.path.join(root, DEFAULT_BASELINE_NAME)
        # entries are stored with scope-qualified fingerprints
        # ("<scope>::<fp>", see write_baseline) so the same finding pinned
        # under two scopes stays two entries; strip the qualifier back off
        # for the diff (legacy unqualified entries pass through unchanged)
        in_scope = {
            fp.split("::", 1)[-1]: e
            for fp, e in baseline_mod.load(baseline_path).items()
            if e.get("scope", scope) == scope
        }
        report.diff = baseline_mod.diff(report.findings, in_scope)
    return report


def write_baseline(path: str, report: VerifyReport,
                   scope: str = "tp1") -> int:
    """Regenerate THIS scope's slice of the graftverify baseline from the
    run's findings (the only way to shrink — or knowingly re-pin — the
    ratchet); other scopes' pinned entries are preserved verbatim.
    Returns the number of entries written for ``scope``."""
    existing = baseline_mod.load(path) if os.path.exists(path) else {}
    entries = [
        e for e in existing.values() if e.get("scope", scope) != scope
    ]
    for v in report.findings:
        entry = baseline_mod._entry(v)
        entry["scope"] = scope
        entry["fingerprint"] = f"{scope}::{v.fingerprint}"
        entries.append(entry)
    baseline_mod._write_entries(path, entries)
    return len(report.findings)
