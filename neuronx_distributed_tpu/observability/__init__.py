"""Unified observability layer (ISSUE 8): one registry, request-scoped
traces, a flight recorder, and device profiler hooks shared by serving and
training.

* :mod:`registry` — :class:`MetricsRegistry` with counter/gauge/histogram
  primitives. Histograms are log-bucketed (fixed memory over unbounded
  streams, quantiles exact to the bucket — ≤5% relative error at the
  default growth), exported as a JSON ``snapshot()`` or Prometheus text
  (``prometheus_text()``). Serving's ``ServingMetrics`` is backed by one;
  the trainer's per-step dict flows in through :class:`MetricsCallback`.
* :mod:`tracing` — :class:`RequestTracer`: every serving request gets a
  trace id at ``submit()`` and emits causally-linked Perfetto flow events
  (queue wait → admission → prefix lookup → prefill → decode chunks →
  retire/shed/quarantine/recovery) on the shared ``utils.timeline.
  Timeline``, so one trace explains a single request's whole life.
* :mod:`flight_recorder` — :class:`FlightRecorder`: bounded ring of recent
  structured events, auto-dumped as a redacted JSON post-mortem on serving
  ``HALTED``, ``TrainerHalted``, and emergency checkpoints; its second ring
  (``steps``, a ``StepLedger``) holds one record per serving step, judges
  each against its own history and samples the stack of a step that overran.
* :mod:`profiler` — :func:`profile_window` (``jax.profiler`` start/stop
  around a block), :func:`install_compile_listener` (compile-event
  counter/duration histogram), :func:`record_device_memory` (per-device
  memory gauges).
* :mod:`programs` — :class:`ProgramLedger` (ISSUE 12): every jit site in
  the serving engine, cache/paging managers, inference builders, and
  trainer registers through it — per compiled program: dispatch counts,
  compile count/wall, compiler-reported FLOPs / bytes accessed
  (``cost_analysis``), donation map, opt-in ``memory_analysis`` HBM
  numbers, and roofline telemetry (achieved FLOPs / MFU / HBM-bandwidth
  utilization derived at export from caller-fed measured walls against
  :func:`device_peaks`). Backend gaps degrade to explicit
  ``"unavailable"`` fields.
* :mod:`hbm` — :class:`HBMLedger`: named static residents (params, KV
  pool, draft cache, slot state, prefix store) reconciled against
  ``Device.memory_stats()`` limits, with ``plan()`` answering capacity
  questions (max pages/slots/adapters that fit a budget).
* :mod:`slo` — :class:`SLOSpec` (per-request TTFT/TPOT bounds per tenant
  or priority class) + :class:`SLOTracker` (attained/violated counts,
  attainment rate, and **goodput** — tokens from SLO-attaining requests
  per second — per tenant, exported through the same registry as labeled
  families). The feedback signal and judge for the SLO-aware scheduler
  work (ISSUE 11).

Hard constraint carried by the whole package (and enforced by graftlint
GL02, whose hot-path list covers the emit paths here): instrumentation
adds **zero** device→host syncs on the serving/training hot paths — the
pinned budgets in ``tests/serving/test_host_sync.py`` hold with full
instrumentation enabled.
"""

from neuronx_distributed_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricFamily,
    MetricsRegistry,
)
from neuronx_distributed_tpu.observability.slo import SLOSpec, SLOTracker
from neuronx_distributed_tpu.observability.tracing import RequestTracer
from neuronx_distributed_tpu.observability.flight_recorder import FlightRecorder
from neuronx_distributed_tpu.observability.profiler import (
    install_compile_listener,
    profile_window,
    record_device_memory,
)
from neuronx_distributed_tpu.observability.callback import MetricsCallback
from neuronx_distributed_tpu.observability.spec_stats import SpecStats
from neuronx_distributed_tpu.observability.programs import (
    UNAVAILABLE,
    ProgramLedger,
    device_peaks,
)
from neuronx_distributed_tpu.observability.hbm import HBMLedger, tree_nbytes

__all__ = [
    "Counter",
    "FlightRecorder",
    "Gauge",
    "HBMLedger",
    "Histogram",
    "MetricFamily",
    "MetricsCallback",
    "MetricsRegistry",
    "ProgramLedger",
    "RequestTracer",
    "SLOSpec",
    "SLOTracker",
    "SpecStats",
    "UNAVAILABLE",
    "device_peaks",
    "install_compile_listener",
    "profile_window",
    "record_device_memory",
    "tree_nbytes",
]
