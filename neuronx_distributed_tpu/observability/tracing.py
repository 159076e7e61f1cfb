"""Spans and request-scoped flows: what the program says about its own time.

Two things live here. :func:`span` is the ONE way the serving engine and the
trainer record a duration; :class:`RequestTracer` links the events of one
request into a Perfetto flow.

``span(name, timeline, **stats)`` is a context manager with three sinks:

* a ``jax.profiler.TraceAnnotation(name, **stats)``. It is recorded only
  while a profiler session is open (``observability.profile_window`` or any
  ``jax.profiler.start_trace``), so the session IS the on-switch, and the
  span lands in the host plane of the same ``.xplane.pb`` as the device's
  ``XLA Ops`` line. The two planes are NOT on one clock: on a v5e host the
  host plane read +0.32..0.43 ms, +1.22..1.37 ms and +1.34..1.43 ms ahead
  of the device plane in three traces, and the two drift apart by 10-35 us
  a second (PR 53's chip runs; causality over the runs joined to their
  ``nxd.program`` span bounds the offset). Whoever lays a span against
  device ops fits that offset first (``perfbench/chunk_gaps.py``). With no
  session the annotation costs under a microsecond and records nothing.
* the caller's :class:`~neuronx_distributed_tpu.utils.timeline.Timeline`,
  if it has one that is enabled: a Chrome ``X`` event of the same name with
  the stats as its ``args``.
* the caller's step ledger (``ledger=``, a
  :class:`~neuronx_distributed_tpu.observability.flight_recorder.StepLedger`),
  if it has one: the span's wall on ``time.perf_counter()`` goes to the
  account of the step in flight, session or no session, so every step of a
  run leaves a record and not only those a trace happened to cover.

Stats are host scalars the caller already owns. One known only at the
span's end (``ttft_us``, ``delivered``) is added with ``set_metadata``
before the span closes. The names the engine and the trainer emit are
listed in ``SERVE_SPANS`` / ``TRAIN_SPANS`` below: they are a contract with
whoever reads a trace (PERF.md section 3, ``perfbench/program_spans.py``).

Request-scoped tracing: one connected Perfetto flow per request.

The serving engine's :class:`~neuronx_distributed_tpu.utils.timeline.
Timeline` events were global — a Perfetto view showed prefill/decode spans
and shed/quarantine instants, but nothing tied the events of ONE request
together across scheduler, cache manager, and engine. ``RequestTracer``
fixes that: every request gets a trace id at ``submit()`` (its rid — unique
per engine, which is the scope of a trace file), and every lifecycle
transition emits a causally-linked Chrome flow event (``ph`` s/t/f keyed by
that id) alongside a normal instant carrying the payload, so Perfetto draws
the arrows queue wait → admission → prefix-cache lookup → prefill →
each decode chunk → retire/shed/quarantine/recovery and one trace explains
a single request's whole life.

Hot-path contract (this module is on graftlint GL02's hot-path list): every
emit takes host scalars the engine already owns — token counts from the
chunk readback that already happened, rids, reasons. **No method here may
touch a device value.** With no timeline (or a disabled one) every call is
a cheap early-return, so the bare engine pays two attribute loads per
lifecycle event.
"""

from __future__ import annotations

from typing import Optional

from jax.profiler import TraceAnnotation

from neuronx_distributed_tpu.utils.timeline import Timeline

__all__ = ["RequestTracer", "SERVE_SPANS", "TRAIN_SPANS", "span"]

# Timeline category of every span
SPAN_CATEGORY = "nxd"

# ServingEngine.step(): siblings on the calling thread, parent by containment
STEP = "nxd.step"
STEP_REAP = "nxd.step.reap"
STEP_PREEMPT = "nxd.step.preempt"
STEP_ADMIT = "nxd.step.admit"
STEP_PREFILL = "nxd.step.prefill"
STEP_FIRST_TOKEN = "nxd.step.prefill.first_token"
STEP_PAGES = "nxd.step.decode.pages"
STEP_DISPATCH = "nxd.step.decode.dispatch"
STEP_READBACK = "nxd.step.decode.readback"
STEP_EMIT = "nxd.step.decode.emit"
STEP_HEALTH = "nxd.step.health"
STEP_CLOSE = "nxd.step.close"
# around every call of a ledgered program (observability/programs.py), in the
# engine and in the trainer alike: a bare profiler annotation whose two stats
# are strings fixed when the program is wrapped, ``program`` (the ledger's
# name) and ``module`` (the name of its runs on the device's ``XLA Modules``
# line), so that a trace reader can tell which call made which run
PROGRAM = "nxd.program"
SERVE_SPANS = (
    STEP, STEP_REAP, STEP_PREEMPT, STEP_ADMIT, STEP_PREFILL, STEP_FIRST_TOKEN,
    STEP_PAGES, STEP_DISPATCH, STEP_READBACK, STEP_EMIT, STEP_HEALTH, STEP_CLOSE,
    PROGRAM,
)

# Trainer.fit: TRAIN_STEP is a StepTraceAnnotation around one iteration
TRAIN_STEP = "nxd.train.step"
TRAIN_FETCH = "nxd.train.fetch"
TRAIN_DISPATCH = "nxd.train.dispatch"
TRAIN_READBACK = "nxd.train.readback"
TRAIN_CALLBACKS = "nxd.train.callbacks"
TRAIN_SPANS = (
    TRAIN_STEP, TRAIN_FETCH, TRAIN_DISPATCH, TRAIN_READBACK, TRAIN_CALLBACKS,
    PROGRAM,
)


class _SinkSpan:
    """A span with more sinks than the profiler's annotation: an ``X``
    event on the timeline (which carries the stats as its ``args``) and the
    step ledger's account of the phase."""

    __slots__ = ("_annotation", "_timeline", "_ledger", "_name", "_stats")

    def __init__(self, annotation, timeline, ledger, name: str, stats: dict):
        self._annotation = annotation
        self._timeline = timeline
        self._ledger = ledger
        self._name = name
        self._stats = stats

    def __enter__(self):
        self._annotation.__enter__()
        if self._timeline is not None:
            self._timeline.mark_event_start(self._name, SPAN_CATEGORY)
        if self._ledger is not None:
            self._ledger.enter(self._name, self._stats)
        return self

    def set_metadata(self, **stats) -> None:
        self._annotation.set_metadata(**stats)
        self._stats.update(stats)
        if self._ledger is not None:
            self._ledger.note(self._name, stats)

    def __exit__(self, *exc):
        if self._ledger is not None:
            self._ledger.exit(self._name)
        if self._timeline is not None:
            self._timeline.mark_event_end(
                self._name, SPAN_CATEGORY, args=self._stats
            )
        return self._annotation.__exit__(*exc)


def span(name: str, timeline: Optional[Timeline] = None, *, ledger=None,
         annotation=TraceAnnotation, **stats):
    """The one span primitive (module docstring). Returns a context manager
    with ``set_metadata(**stats)``; with no enabled ``timeline`` and no
    ``ledger`` that is the bare profiler annotation, so an uninstrumented
    run pays for nothing else. ``annotation`` is the profiler class to enter
    (``jax.profiler.StepTraceAnnotation`` for a training step)."""
    ann = annotation(name, **stats)
    if timeline is not None and not timeline.enabled:
        timeline = None
    if timeline is None and ledger is None:
        return ann
    return _SinkSpan(ann, timeline, ledger, name, stats)

# flow category: one namespace for request-lifecycle flows so trace
# processors can select them structurally
FLOW_CATEGORY = "request"


class RequestTracer:
    """Emits one connected flow per request onto a shared Timeline.

    Phases: ``begin`` opens the flow (at submit), ``step`` adds a linked
    waypoint (admission, prefill, first token, decode chunk, preemption,
    recovery, quarantine-requeue), ``end`` closes it (retire, shed,
    cancel, fail). The flow events double as instants (same name/ts) so
    the payload args are visible in the event pane and the flow always
    has a slice to bind to."""

    def __init__(self, timeline: Optional[Timeline]):
        self.timeline = timeline

    @property
    def enabled(self) -> bool:
        tl = self.timeline
        return tl is not None and tl.enabled

    def _emit(self, rid: int, stage: str, phase: str,
              args: Optional[dict] = None) -> None:
        tl = self.timeline
        payload = {"rid": rid, "stage": stage}
        if args:
            payload.update(args)
        tl.flow(f"r{rid}", rid, phase, FLOW_CATEGORY, args=payload)
        tl.instant(f"{stage} r{rid}", FLOW_CATEGORY, args=payload)

    def begin(self, rid: int, args: Optional[dict] = None) -> None:
        """Open the request's flow (submit time)."""
        if not self.enabled:
            return
        self._emit(rid, "submit", "s", args)

    def step(self, rid: int, stage: str, args: Optional[dict] = None) -> None:
        """Linked waypoint inside the request's life."""
        if not self.enabled:
            return
        self._emit(rid, stage, "t", args)

    def end(self, rid: int, stage: str, args: Optional[dict] = None) -> None:
        """Close the request's flow (terminal state)."""
        if not self.enabled:
            return
        self._emit(rid, stage, "f", args)
