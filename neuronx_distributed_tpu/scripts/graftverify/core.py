"""graftverify check catalog and finding model.

Findings REUSE graftlint's :class:`Violation` (and therefore its baseline
ratchet, fingerprints and report format verbatim): ``path`` carries the
program coordinate (``<ledger>/<program>``), ``snippet`` carries the
check's stable basis — for GV03 that basis EMBEDS the wire-byte table, so
any change to a program's collective bytes changes the fingerprint, fails
the ratchet, and forces a conscious ``--write-baseline``.
"""

from __future__ import annotations

from typing import Dict

from neuronx_distributed_tpu.scripts.graftlint.core import Violation

DEFAULT_BASELINE_NAME = "graftverify_baseline.json"

GV01 = "GV01"
GV02 = "GV02"
GV03 = "GV03"
GV04 = "GV04"
GV05 = "GV05"

TITLES: Dict[str, str] = {
    "GV00": "verification hygiene",
    GV01: "donation aliasing (IR)",
    GV02: "transfer census",
    GV03: "collective wire-byte ratchet",
    GV04: "dispatch-key stability",
    GV05: "manifest coverage (AOT)",
}

EXPLAINS: Dict[str, str] = {
    "GV00": """\
GV00 verification hygiene

Emitted by the runner itself, not an IR check: a ledgered program that
could not be re-lowered for verification (a hot program must stay
traceable or carry a waiver), or a waiver missing its MANDATORY reason —
graftlint's GL00 contract, carried over: a suppression without a
documented why is how the incident classes crept in the first time.
""",
    GV01: """\
GV01 donation-aliasing (IR)

Incident: graftlint GL01 proves no SOURCE line reads a donated buffer, but
a donation can also be dropped by XLA itself — a dtype/layout mismatch
between the donated input and every output, or a host-cached leaf, makes
the lowering silently skip the input_output_alias. The program still runs;
it just holds TWO copies of the cache/state tree on the hot path, and
nothing in the repo caught it until graftverify.

Check: every flattened argument declared donated (``Lowered.args_info``)
that pjit KEEPS must materialize in the lowered StableHLO as either a
``tf.aliasing_output`` attribute (jax paired it at lowering — the
mesh-free path) or ``jax.buffer_donor = true`` (pairing is deferred to
XLA: a mesh program, whose output shardings are compile-time, or a donor
that matches an unpaired output in size but not in aval — the
declaration provably reached the IR). A donated-but-UNUSED arg is pruned
by pjit (keep_unused=False): freed, never copied, counted separately. A
kept, used, unmarked donation is the dropped-donation bug; the finding
lists the flat positions and their avals.

Fix the program (make the donated leaf's dtype/shape reachable in an
output) or waive with a reason (``verify(waivers=...)``).
""",
    GV02: """\
GV02 transfer-census

Incident: GL02 pins the HOST side of the sync budget by walking source
text, but a ``jax.debug.callback``, ``io_callback``, infeed/outfeed or
host-transfer custom_call reaches the compiled program through helpers no
single module shows. The lowered IR is ground truth: a hot program
(decode chunk, train step, slot/page transport) must contain ZERO
host-transfer ops, or the pinned budgets (submit=1, admission=2, steady
chunk=1) are fiction.

Check: walk every op of the lowered module (call-graph aware); flag
stablehlo.infeed / outfeed / send / recv and every custom_call whose
target names a python/host callback. Sharding markers (``Sharding``,
``SPMDFullToShardShape``/``SPMDShardToFullShape``) are not transfers.
""",
    GV03: """\
GV03 collective wire-byte ratchet

The EQuARX quantized all-reduce path (PAPERS.md arXiv 2506.17615) claims a
~3.94x wire-byte reduction per decode step. A timed run can only observe it;
the lowered IR can PIN it: every collective op (all_reduce, all_gather,
reduce_scatter, collective_permute, all_to_all) is enumerated with its
element count, element bytes, and a per-rank ring-model wire-byte figure.
The table is embedded in the finding's fingerprint and ratcheted through
graftverify_baseline.json — a TP-path change that moves a program's
collective bytes (a layer that stopped sharding, a quantized ring that
silently fell back to fp32) changes the fingerprint and FAILS CI until the
baseline is consciously regenerated.

Wire model (per rank, ring algorithm): all_reduce 2*(R-1)/R*n, all_gather
(R-1)*n_shard, reduce_scatter (R-1)/R*n, collective_permute n, all_to_all
(R-1)/R*n — n in element-bytes of the per-shard operand the IR shows.
""",
    GV04: """\
GV04 dispatch-key stability

Incident class GL03 (weak-type literals, uncommitted device arrays,
trailing-None PartitionSpecs) shows up at the source layer as a hazard and
at the CACHE layer as a fact: a program that compiled MORE times than it
has distinct shape/dtype signatures was recompiled by something the aval
skeleton cannot see — weak_type flips, sharding/layout churn, donation
mismatches. The ledger already holds both counts; graftverify cross-checks
them per program. ``compiles > variants`` fails; an intentional rebuild
(an engine's lazy plain-chunk fallback after a spec failure) gets a
waiver with its reason.
""",
    GV05: """\
GV05 manifest-coverage (AOT)

The AOT prewarm contract (inference/aot.py, ISSUE 17) is only as good as
its manifest: a hot program the ledger saw DISPATCHED at runtime but the
prewarmed manifest never named pays its compile inside the first
request's TTFT — exactly the cold-start bill prewarm exists to remove.
The inverse is debt too: a manifest entry naming a program the ledger
does not know is stale (a renamed program, a removed code path) and will
silently skip forever.

Check (runs only when ``verify(..., manifest=...)`` is given): every
audited program with ``dispatches > 0`` (runtime traffic — prewarm
replays are counted separately and do NOT satisfy coverage) must appear
in the manifest; every manifest program must be known to some ledger.
Prewarmed-but-unused programs are fine in both directions.
""",
}

CHECKS = tuple(sorted(TITLES))


def finding(rule: str, ledger_key: str, program: str, snippet: str,
            message: str) -> Violation:
    """One graftverify finding as a graftlint Violation: ``path`` is the
    program coordinate (stable across runs — the fingerprint basis), line
    and column are meaningless for IR and pinned to 0."""
    return Violation(
        rule=rule,
        path=f"<{ledger_key}/{program}>",
        line=0,
        col=0,
        message=message,
        snippet=snippet,
    )
