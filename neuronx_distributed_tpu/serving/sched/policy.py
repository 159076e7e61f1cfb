"""The scheduling-policy seam: ONE selection path, pluggable order.

ISSUE 16 tentpole. Before this package, the admission selection logic
lived in two places: ``Scheduler.select`` owned the budget-guarded queue
scan plus the longest-prefill-first round ordering, while the engine's
``_admit`` built the capacity predicate and the effective-prefill-cost
key around it. The scan and the ordering now live HERE — :func:`scan_queue`
and :func:`order_round` are the single selection path every policy rides —
and ``Scheduler.select`` is a thin delegate to its bound policy.

A :class:`SchedulingPolicy` decides three things, all host-side over
already-host state (the hard constraint: ZERO added device→host syncs —
these modules are on graftlint GL02's hot list):

* **queue order** — :meth:`SchedulingPolicy.select` may reorder the live
  queue before the scan (the scan itself never overtakes: the first
  request that does not fit blocks the rest, which is what makes the
  budget guard starvation-free *within the policy's order*);
* **preemption** — :meth:`SchedulingPolicy.victims` nominates active
  requests to vacate (the engine preempts them through the existing
  resume machinery, so streams stay bit-identical);
* **routing bias** — :meth:`SchedulingPolicy.route_bias` feeds the
  replica router's per-tenant attainment term.

:class:`FifoPolicy` is the default and reproduces the pre-policy
``Scheduler.select`` decision-for-decision: same scan, same ordering,
no reorder, no victims, zero bias — streams are bit-identical to the
pre-policy engine (regression-pinned in tests/serving/test_sched_policy.py
and the whole existing serving test matrix, which runs through it).
"""

from __future__ import annotations

from typing import Callable, Deque, List, Optional, TYPE_CHECKING

if TYPE_CHECKING:  # pragma: no cover
    from neuronx_distributed_tpu.serving.scheduler import Request


def scan_queue(
    queue: "Deque[Request]",
    free_slots: int,
    in_flight_tokens: int,
    max_tokens_in_flight: Optional[int],
    fits: Optional[Callable[["Request"], bool]] = None,
) -> List["Request"]:
    """THE selection scan (moved verbatim from ``Scheduler.select``): pick
    the queue-order prefix that fits ``free_slots``, the token budget, and
    the engine's capacity predicate ``fits`` (checked in queue order, so
    ``fits`` may accumulate a projected cursor). Selected requests leave
    the queue in state PREFILL. Strict no-overtaking: the first request
    that does not fit blocks everything behind it — under FIFO that is the
    classic head-of-line guarantee; under a reordering policy it means the
    policy's chosen head is never starved by smaller work behind it."""
    from neuronx_distributed_tpu.serving.scheduler import RequestState

    selected: List["Request"] = []
    budget = in_flight_tokens
    while queue and len(selected) < free_slots:
        req = queue[0]
        if req.finished:  # cancelled/shed while queued — drop in place
            queue.popleft()
            continue
        if (
            max_tokens_in_flight is not None
            and budget + req.token_footprint > max_tokens_in_flight
        ):
            break  # nothing overtakes the blocked head
        if fits is not None and not fits(req):
            break
        queue.popleft()
        req.state = RequestState.PREFILL
        budget += req.token_footprint
        selected.append(req)
    return selected


def order_round(
    selected: List["Request"],
    prefill_cost: Optional[Callable[["Request"], int]] = None,
) -> List["Request"]:
    """THE round ordering (the other half moved from ``Scheduler.select``):
    hand the selected round back longest-prefill-first — the longest
    prompt sets the shared cache cursor, so prefilling it first lets the
    shorter prompts roll in under the same cursor without gap columns.
    ``prefill_cost`` substitutes EFFECTIVE work (the prefix-cache-aware
    engine passes context length minus reusable tokens). Ordering only —
    selection already happened, so token streams are unaffected."""
    key = prefill_cost or (lambda r: len(r.context_ids))
    selected.sort(key=key, reverse=True)
    return selected


class SchedulingPolicy:
    """Interface every queue policy implements. Stateless against device
    data by construction: every hook takes and returns host scalars."""

    name = "base"

    def bind(self, engine) -> None:
        """Late wiring to the engine whose queue this policy orders (the
        SLO policy reads its metrics/prefix/cache feedback surfaces; FIFO
        ignores it). Called once from ``ServingEngine.__init__``."""

    def select(
        self,
        queue: "Deque[Request]",
        free_slots: int,
        in_flight_tokens: int,
        max_tokens_in_flight: Optional[int],
        fits: Optional[Callable[["Request"], bool]] = None,
        prefill_cost: Optional[Callable[["Request"], int]] = None,
        now: Optional[float] = None,
    ) -> List["Request"]:
        raise NotImplementedError

    def victims(self, now: float) -> List["Request"]:
        """Active requests this policy wants preempted RIGHT NOW (the
        engine vacates them through the resume machinery — tokens and key
        host-current, streams bit-identical). Default: never."""
        return []

    @property
    def preempts(self) -> bool:
        """Whether :meth:`victims` can ever name one: the engine runs a
        decode chunk ahead only over boundaries at which nothing is due,
        and cannot ask ``victims`` twice."""
        return False

    def on_tokens(self, tenant: str, n: int) -> None:
        """Decode-token accounting hook (host ints the loop already owns);
        the fairness layer charges tenant budgets here."""

    def route_bias(self, tenant: Optional[str]) -> float:
        """Additive per-tenant load-score term for the replica router, in
        slot units (>= 0; 0.0 = no opinion). A replica where ``tenant``'s
        SLO is unhealthy reads as more loaded for that tenant's work."""
        return 0.0

    def snapshot(self) -> dict:
        return {"policy": self.name}


class FifoPolicy(SchedulingPolicy):
    """The pre-policy scheduler, verbatim: FIFO scan + longest-prefill-
    first round ordering, no reordering, no preemption, no routing bias.
    Selecting this policy IS the pre-PR engine (bit-identical streams)."""

    name = "fifo"

    def select(self, queue, free_slots, in_flight_tokens,
               max_tokens_in_flight, fits=None, prefill_cost=None,
               now=None):
        selected = scan_queue(
            queue, free_slots, in_flight_tokens, max_tokens_in_flight, fits
        )
        return order_round(selected, prefill_cost)


def make_policy(spec) -> SchedulingPolicy:
    """``ServingEngine(scheduling=)`` resolver: ``"fifo"`` (default),
    ``"slo"`` (the ISSUE 16 SLO-aware policy with priority tiers, DWRR
    fairness, and attainment feedback), or a ready
    :class:`SchedulingPolicy` instance."""
    if isinstance(spec, SchedulingPolicy):
        return spec
    if spec in (None, "fifo"):
        return FifoPolicy()
    if spec == "slo":
        from neuronx_distributed_tpu.serving.sched.feedback import SloPolicy

        return SloPolicy()
    raise ValueError(
        f"unknown scheduling policy {spec!r} (expected 'fifo', 'slo', or a "
        "SchedulingPolicy instance)"
    )
