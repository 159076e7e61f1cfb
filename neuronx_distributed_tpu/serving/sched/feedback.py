"""Feedback-driven SLO scheduling (ISSUE 16 tentpole (c)+(d)): the policy
that closes the loop from the PR-10 judge back into admission.

PR 10/11 built the measurement half — per-tenant ``ttft_*``/``tpot_*``
histograms and :class:`SLOTracker` attainment — and left the scheduler
FIFO. :class:`SloPolicy` reads those live host-side counters as its
CONTROL SIGNAL:

* **ordering** — the queue is stably reordered each admission round by a
  composite key: priority tier with starvation-free aging
  (:mod:`.priority`), the DWRR fairness rank (:mod:`.fairness`), and an
  attainment-pressure boost — a tenant whose attainment has dipped below
  target (or whose LIVE ttft p99 is already over its spec, the
  early-warning the finish-time tracker can't see mid-burst) admits ahead
  of over-attaining tenants. The scan itself is the shared single path in
  :mod:`.policy` (same budget guard, same no-overtaking).
* **preemption** — when the slot set is full and a pressured tenant's
  work is waiting behind a healthy tenant's, :meth:`SloPolicy.victims`
  nominates the cheapest victim: cost = pages held x resume-prefill work
  (``PrefixCache.match_len`` makes the resume work cheap to estimate —
  a victim whose context is prefix-cached re-prefills almost nothing).
  The engine vacates victims through the existing preempt/resume
  machinery, so the victim's stream is bit-identical; a cooldown and a
  per-round victim cap keep the controller from thrashing.
* **routing** — :meth:`SloPolicy.route_bias` exposes per-tenant pressure
  in slot units for ``ServingEngine.load_score(tenant=)``: the router
  steers a tenant's next request toward the replica where its SLO is
  healthiest.

Every input is host state the loop already owns (tracker counters,
log-bucketed histogram reads, host block tables): ZERO added device→host
syncs, re-pinned with this policy ON in tests/serving/test_host_sync.py.
GL02-hot module.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, TYPE_CHECKING

from neuronx_distributed_tpu.serving.sched.fairness import (
    DeficitRoundRobin,
    FairnessConfig,
)
from neuronx_distributed_tpu.serving.sched.policy import (
    SchedulingPolicy,
    order_round,
    scan_queue,
)
from neuronx_distributed_tpu.serving.sched.priority import (
    PriorityConfig,
    effective_rank,
)

if TYPE_CHECKING:  # pragma: no cover
    from neuronx_distributed_tpu.serving.scheduler import Request


@dataclasses.dataclass(frozen=True)
class FeedbackConfig:
    """Controller dials. ``target_attainment`` is the floor below which a
    tenant counts as pressured; ``min_decided`` gates the attainment term
    until enough requests have been classified to mean anything (the live
    ttft early-warning has no such gate — one bad burst is signal).
    ``pressure_boost`` converts pressure (0..1) into tier units: the
    default (2.0 tiers at full pressure) lets a fully-failing batch tenant
    overtake healthy interactive work, but a mildly-dipping one only edges
    ahead of its own tier. Preemption is deliberately conservative:
    at most ``max_victims`` per round, never from a pressured tenant,
    never within ``cooldown_s`` of the last preemption (the resume
    re-prefill costs real work — thrash would burn more SLO than it
    buys)."""

    target_attainment: float = 0.9
    min_decided: int = 4
    pressure_boost: float = 2.0
    fairness_gain: float = 1.0
    preempt: bool = True
    max_victims: int = 1
    cooldown_s: float = 0.25
    # don't victimize a request about to finish — its slot frees itself
    # cheaper than a preempt/resume cycle can
    min_victim_remaining: int = 4


class SloFeedback:
    """Host-side reads over the live SLO surfaces: tracker attainment +
    histogram percentiles, normalized to a 0..1 pressure per tenant."""

    def __init__(self, metrics, config: FeedbackConfig):
        self._metrics = metrics
        self._config = config

    def pressure(self, tenant: str) -> float:
        """0.0 = attaining (or no spec / no signal yet); 1.0 = fully
        failing. Max of the attainment gap (normalized to the target) and
        the live-ttft overshoot early-warning."""
        tracker = self._metrics.slo
        if tracker is None:
            return 0.0
        spec = tracker.spec_for(tenant)
        if spec is None:
            return 0.0  # no contract, no pressure
        cfg = self._config
        p = 0.0
        if tracker.decided(tenant) >= cfg.min_decided:
            gap = cfg.target_attainment - tracker.attainment(tenant)
            if gap > 0.0:
                p = min(1.0, gap / max(cfg.target_attainment, 1e-9))
        if spec.ttft_p99_s is not None:
            live = self._metrics.tenant_latency("ttft", tenant, 0.99)
            if live > spec.ttft_p99_s:
                p = max(p, min(1.0, live / spec.ttft_p99_s - 1.0))
        return p

    def attaining(self, tenant: str) -> bool:
        return self.pressure(tenant) == 0.0


def victim_cost(engine, req: "Request") -> float:
    """What preempting ``req`` throws away: pages held x the prefill work
    its resume must redo. ``PrefixCache.match_len`` is a read-only peek
    (no LRU state moves), and a victim admitted through the prefix cache
    usually re-prefills only its generated tail — cheap. Row engines hold
    no pages; their cost is pure resume work."""
    ctx = req.context_ids
    work = len(ctx)
    if engine.prefix is not None:
        work -= engine.prefix.match_len(ctx)
    work = max(work, 1)
    pages = 1
    if engine._page_size is not None and req.slot is not None:
        pages = max(len(engine.cache.slot_pages(req.slot)), 1)
    return float(pages * work)


class SloPolicy(SchedulingPolicy):
    """Priority tiers + DWRR fairness + attainment feedback, composed over
    the shared selection path."""

    name = "slo"

    def __init__(
        self,
        priority: Optional[PriorityConfig] = None,
        fairness: Optional[FairnessConfig] = None,
        feedback: Optional[FeedbackConfig] = None,
    ):
        self.priority = priority or PriorityConfig()
        self.fairness = DeficitRoundRobin(fairness or FairnessConfig())
        self.config = feedback or FeedbackConfig()
        self._engine = None
        self._feedback: Optional[SloFeedback] = None
        self._last_preempt_t: Optional[float] = None
        self.preemptions_requested = 0

    # --- wiring -------------------------------------------------------------

    def bind(self, engine) -> None:
        self._engine = engine
        self._feedback = SloFeedback(engine.metrics, self.config)

    # --- ordering -----------------------------------------------------------

    def _order_key(self, req: "Request", now: float):
        rank = effective_rank(req, now, self.priority)
        rank += self.fairness.rank(req.tenant) * self.config.fairness_gain
        if self._feedback is not None:
            rank -= (
                self._feedback.pressure(req.tenant)
                * self.config.pressure_boost
            )
        return (rank, req.rid)  # rid tiebreak: stable arrival order

    def select(self, queue, free_slots, in_flight_tokens,
               max_tokens_in_flight, fits=None, prefill_cost=None,
               now=None):
        now = 0.0 if now is None else now
        live = {}
        for r in queue:
            if not r.finished:
                live.setdefault(r.tenant, r.priority)
        self.fairness.replenish(live.items())
        if live:
            # stable in-place reorder of the scheduler's own deque: the
            # policy's order IS the queue order (requeued victims keep
            # their aged seniority through the key, not their position)
            ordered = sorted(queue, key=lambda r: self._order_key(r, now))
            queue.clear()
            queue.extend(ordered)
        selected = scan_queue(
            queue, free_slots, in_flight_tokens, max_tokens_in_flight, fits
        )
        # charge admissions' context work? No — fairness meters DECODE
        # tokens only (the engine's on_tokens hook); prefill cost is
        # already priced by the longest-first round order below
        return order_round(selected, prefill_cost)

    # --- preemption ---------------------------------------------------------

    @property
    def preempts(self) -> bool:
        return bool(self.config.preempt)

    def victims(self, now: float) -> List["Request"]:
        cfg = self.config
        eng = self._engine
        if not cfg.preempt or eng is None or self._feedback is None:
            return []
        if eng.cache.free_slots > 0:
            return []  # admission can proceed without violence
        if (
            self._last_preempt_t is not None
            and now - self._last_preempt_t < cfg.cooldown_s
        ):
            return []
        # who is waiting and hurting?
        pressured = {}
        for req in eng.scheduler.queued_requests:
            p = self._feedback.pressure(req.tenant)
            if p > 0.0:
                pressured[req.tenant] = max(pressured.get(req.tenant, 0.0), p)
        if not pressured:
            return []
        # who can pay? active requests of ATTAINING tenants only, with
        # enough work left that a preempt/resume cycle beats waiting out
        # their natural retirement
        candidates = [
            r for r in eng._slot_req
            if r is not None
            and not r.finished
            and r.tenant not in pressured
            and self._feedback.attaining(r.tenant)
            and r.remaining_new_tokens >= cfg.min_victim_remaining
        ]
        if not candidates:
            return []
        candidates.sort(key=lambda r: (victim_cost(eng, r), r.rid))
        chosen = candidates[: cfg.max_victims]
        self._last_preempt_t = now
        self.preemptions_requested += len(chosen)
        return chosen

    # --- accounting / routing ----------------------------------------------

    def on_tokens(self, tenant: str, n: int) -> None:
        self.fairness.charge(tenant, n)

    def route_bias(self, tenant: Optional[str]) -> float:
        if tenant is None or self._feedback is None:
            return 0.0
        return self._feedback.pressure(tenant)

    def snapshot(self) -> dict:
        return {
            "policy": self.name,
            "preemptions_requested": self.preemptions_requested,
            "fairness": self.fairness.snapshot(),
        }
