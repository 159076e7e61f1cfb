"""Trainer-side SDC sentinel — fingerprint scheduling, voting, rollback.

graftlint: hot-path — consulted from the training loop's inner body. The
sentinel itself NEVER syncs: every device value it touches (fingerprint
scalars, state snapshots) is produced by jitted programs the loop wraps
through its ledger and read exclusively through the loop's one deferred
``device_get`` (``Trainer._account_guard``). ``judge`` receives already-
host integers; ``post_dispatch`` returns device scalars for the loop to
fold into that readback.

Detection model (see ``integrity/__init__`` and the README section):

* **vote** (dp >= 2) — params/opt-state replicated across dp replicas
  must fingerprint identically on every device. The fingerprint program
  (``utils.fingerprint.tree_fingerprint``) reduces sharded dims with
  intra-replica collectives only, so its "replicated" uint32 output has
  one physical copy per device, each computed from that device's data.
  A check step reads every copy through the deferred readback; a
  strict-minority copy convicts its device(s). Detects corruption that
  *persists in memory* until a check step (weight decay shrinks a param
  delta slowly; optimizer state not at all). ZeRO-1 *sharded* opt-state
  leaves are EXCLUDED from the vote fingerprint (the loop strips them
  before the jitted program): reducing a dp-sharded leaf would force a
  cross-replica collective whose result is identical on every device,
  and that one uniform term poisons the whole combined scalar — the vote
  would go blind even to corruption in the still-replicated params.
  Checkpoint shard digests are the cover for sharded opt leaves.
  Localization granularity:
  a strike that trains through a gradient all-reduce before the next
  check stays exactly localized only when the backend's all-reduce is
  bitwise rank-uniform (real TPUs are; the CPU proxy's multi-threaded
  emulation is not, so there a mid-window strike can widen to extra
  devices or an unlocalized verdict — still detected, still rolled
  back, see tests/integrity/test_sentinel.py).
* **canary** (solo) — at a check step the pre-step state is copied, the
  step re-executed from the copy, and both outcomes' fingerprints
  compared: any divergence between two executions of the same program on
  the same data is corruption (compute SDC at the check step, or memory
  corruption of the live state between dispatch and re-execution).
  Corruption striking *between* checks and gone quiet by the next one is
  outside the canary's reach — dp voting is the stronger mode; run it
  whenever the topology allows.

Fence-and-continue: the sentinel retains a verified known-good snapshot
``(state, step, data cursor, tokens)``. A conviction rolls the loop back
to it — training re-runs the discarded steps deterministically, so the
final state is bit-identical to an uninterrupted clean run. When no
snapshot can cover the rollback (no data-source cursor), the loop falls
through to the ``TrainerHalted``/resume contract instead.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from neuronx_distributed_tpu.integrity.voting import VoteVerdict, vote

__all__ = ["SentinelConfig", "TrainerSentinel", "SentinelVerdict"]


@dataclasses.dataclass(frozen=True)
class SentinelConfig:
    """SDC sentinel knobs (attach as ``Trainer.integrity``; None = off).

    ``check_every`` — steps between integrity checks. Detection latency
    is bounded by it; so is overhead (one fingerprint reduction per check
    in vote mode, one extra train step per check in canary mode; not
    measured on the chip). ``mode`` — ``auto`` resolves to
    ``vote`` when the mesh has dp >= 2 replicas, else ``canary``."""

    check_every: int = 16
    mode: str = "auto"  # auto | vote | canary


@dataclasses.dataclass(frozen=True)
class SentinelVerdict:
    """One check's outcome, judged from host integers."""

    step: int
    mode: str
    clean: bool
    convicted_devices: Tuple[int, ...] = ()
    localized: bool = True
    values: Dict = dataclasses.field(default_factory=dict)

    @property
    def detected(self) -> bool:
        return not self.clean


class TrainerSentinel:
    """Host-side sentinel state machine driven by ``Trainer.fit``.

    The loop owns every dispatch and the single deferred readback; the
    sentinel owns scheduling, snapshot retention, and judgement. All
    programs (``fingerprint_fn`` over ``{"params", "opt_state"}``,
    ``copy_fn`` over a full TrainState) arrive pre-jitted and
    ledger-wrapped."""

    def __init__(
        self,
        config: SentinelConfig,
        *,
        dp_size: int,
        fingerprint_fn,
        copy_fn,
    ):
        if config.check_every < 1:
            raise ValueError(
                f"check_every must be >= 1, got {config.check_every}"
            )
        if config.mode not in ("auto", "vote", "canary"):
            raise ValueError(f"unknown sentinel mode {config.mode!r}")
        self.config = config
        self.mode = (
            config.mode
            if config.mode != "auto"
            else ("vote" if dp_size >= 2 else "canary")
        )
        self._fp = fingerprint_fn
        self._copy = copy_fn
        # verified snapshot: {"state", "step", "data_state", "tokens_seen"}
        self._known_good: Optional[dict] = None
        self._candidate: Optional[dict] = None
        self._canary: Optional[Tuple[Any, Any]] = None
        # (kind, device_ids, step) for the payload awaiting readback
        self._pending: Optional[Tuple[str, Any, int]] = None
        self.quarantined_devices: list = []
        self.counters: Dict[str, int] = {
            "integrity_checks": 0,
            "sdc_detected": 0,
            "sdc_unlocalized": 0,
            "sdc_rollbacks": 0,
        }

    # --- scheduling ----------------------------------------------------------

    def is_check_step(self, step_index: int) -> bool:
        """True when the 0-based step ``step_index`` closes a check window."""
        return (step_index + 1) % self.config.check_every == 0

    def wants_pre_copy(self, step_index: int) -> bool:
        """Canary mode needs the PRE-step state copied before dispatch."""
        return self.mode == "canary" and self.is_check_step(step_index)

    # --- snapshots -----------------------------------------------------------

    def set_baseline(self, state, step: int, data_state, tokens_seen: int):
        """First known-good point: the verified state fit() starts (or
        resumes) from — a checkpoint restore is digest-verified upstream,
        a fresh init is trusted by definition."""
        self._known_good = {
            "state": self._copy(state),
            "step": step,
            "data_state": data_state,
            "tokens_seen": tokens_seen,
        }
        self._candidate = None
        self._pending = None
        self._canary = None

    def snapshot_states(self):
        """Live snapshot trees, for the loop's HBM-ledger resident."""
        return [
            s["state"]
            for s in (self._known_good, self._candidate)
            if s is not None
        ]

    def can_rollback(self) -> bool:
        return self._known_good is not None

    def rollback(self) -> dict:
        """Hand the loop a fresh copy of the known-good point (the
        retained snapshot survives, so a second conviction can roll back
        again). The caller restores state/step/cursor/tokens and simply
        keeps looping — re-training is deterministic, so the final state
        is bit-identical to a run that never saw the corruption."""
        kg = self._known_good
        if kg is None:
            raise RuntimeError("no known-good snapshot to roll back to")
        self._candidate = None
        self._canary = None
        self._pending = None
        self.counters["sdc_rollbacks"] += 1
        return {
            "state": self._copy(kg["state"]),
            "step": kg["step"],
            "data_state": kg["data_state"],
            "tokens_seen": kg["tokens_seen"],
        }

    # --- the check itself ----------------------------------------------------

    def pre_dispatch(self, state, prepared) -> None:
        """Canary only, at check steps, BEFORE the step dispatches: retain
        a copy of the pre-step state plus the prepared batch so the same
        step can be re-executed after the real dispatch."""
        self._canary = (self._copy(state), prepared)

    def post_dispatch(self, train_step, state, step: int, data_state,
                      tokens_seen: int) -> Tuple:
        """At a check step, AFTER the step dispatched (and after any chaos
        ``on_state`` hook ran): compute the fingerprint payload and stage
        the candidate snapshot. Returns device uint32 scalars for the loop
        to append to its one deferred ``device_get``; ``judge`` consumes
        their host values at the next accounting point."""
        self.counters["integrity_checks"] += 1
        fp = self._fp({"params": state.params, "opt_state": state.opt_state})
        if self.mode == "vote":
            shards = fp.addressable_shards
            payload = tuple(s.data for s in shards)
            self._pending = (
                "vote", tuple(s.device.id for s in shards), step,
            )
        else:
            c_state, prepared = self._canary or (None, None)
            self._canary = None
            if c_state is None:
                raise RuntimeError(
                    "canary check without pre_dispatch — loop wiring bug"
                )
            # re-execute the SAME jitted program (no retrace: identical
            # avals and shardings) from the pre-step copy; donation
            # consumes the copy, the outcome only needs fingerprinting
            c_out = train_step(c_state, prepared)
            c_next = c_out[0] if isinstance(c_out, tuple) else c_out
            fp_canary = self._fp(
                {"params": c_next.params, "opt_state": c_next.opt_state}
            )
            payload = (fp, fp_canary)
            self._pending = ("canary", None, step)
        self._candidate = {
            "state": self._copy(state),
            "step": step,
            "data_state": data_state,
            "tokens_seen": tokens_seen,
        }
        return payload

    def judge(self, host_values) -> Optional[SentinelVerdict]:
        """Judge the pending check from the readback's HOST integers.
        Clean promotes the candidate snapshot to known-good; a detection
        discards it (it was copied from the corrupt state) and leaves the
        previous known-good in place for ``rollback``."""
        if self._pending is None:
            return None
        kind, device_ids, step = self._pending
        self._pending = None
        if kind == "vote":
            values = {
                int(d): int(v) for d, v in zip(device_ids, host_values)
            }
            v = vote(values)
        else:
            a, b = (int(x) for x in host_values)
            v = (
                VoteVerdict(clean=True, quorum_value=a)
                if a == b
                else VoteVerdict(clean=False, localized=False,
                                 values={"state": a, "canary": b})
            )
        if v.clean:
            if self._candidate is not None:
                self._known_good = self._candidate
                self._candidate = None
            return SentinelVerdict(step=step, mode=kind, clean=True)
        self._candidate = None
        self.counters["sdc_detected"] += 1
        if not v.localized:
            self.counters["sdc_unlocalized"] += 1
        convicted = tuple(v.convicted) if kind == "vote" else ()
        self.quarantined_devices.extend(
            d for d in convicted if d not in self.quarantined_devices
        )
        return SentinelVerdict(
            step=step, mode=kind, clean=False,
            convicted_devices=convicted, localized=v.localized,
            values=dict(v.values),
        )
