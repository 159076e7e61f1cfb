"""Request-level serving engine: continuous batching over a fixed-shape
decode step (ROADMAP north star — serving heavy traffic needs an engine that
admits/retires REQUESTS, not a batch `generate()` call; cf. the TPU serving
stacks in PAPERS.md, which all converge on slot-based continuous batching so
XLA compiles the decode step once and requests flow through slots).

Layers:

* :mod:`engine` — ``ServingEngine``: the host-side loop interleaving prefill
  of admitted requests with ONE jitted fixed-shape decode program over all
  active slots — ``decode_chunk_size`` fused steps per dispatch, donated
  device-resident cache/slot-state, one host sync per chunk. With
  ``draft_model=`` bound, each chunk becomes that many fused draft–verify
  ROUNDS (speculative decoding, ISSUE 9): 1..gamma tokens per slot per
  round with per-slot variable advance, greedy streams bit-identical to
  the spec-off engine and the solo speculative path, still one sync per
  chunk.
* :mod:`scheduler` — FIFO + longest-prefill-first admission with a
  token-budget guard and the request lifecycle
  (QUEUED→PREFILL→DECODE→DONE/CANCELLED).
* :mod:`cache_manager` — slot allocation/roll-in/reset on top of the
  ``modules/attention.KVCache`` collection layout (no reallocation between
  requests), plus :class:`PrefixCache` — the host-managed, ref-counted,
  LRU-evicted store of prompt-prefix KV blocks behind the engine's
  shared-prompt admission path (longest-match lookup → suffix prefill of
  the uncached tail → insert-on-miss; streams bit-identical to cache-off,
  disable with ``ServingEngine(prefix_cache=None)``).
* :mod:`paging` — the PAGED KV layout (``ServingEngine(kv_page_size=)``,
  ISSUE 10): :class:`PageAllocator` (ref-counted, free-listed page pool) +
  :class:`PagedCacheManager` (per-slot device-resident block tables; the
  decode chunk gathers the logical view, runs the exact row math, and
  scatters back its write window). Buys free-page admission packing under
  mixed-length traffic, ZERO-COPY copy-on-write prefix sharing (insert
  pins pages, hits map them — ``copy_bytes`` stays 0), and page-granular
  poison quarantine; streams stay bit-identical to the row layout and
  ``decode_compilations`` stays 1.
* :mod:`tiering` — the host-RAM page tier (``ServingEngine(
  kv_host_pages=)``, ISSUE 19): :class:`HostPageStore` holds spilled pool
  pages as fingerprinted host numpy blocks; the reclaim valve spills cold
  prefix entries there instead of evicting, and admission prefetches
  matched pages back while the request queues — eviction cliff becomes a
  hit-rate slope, streams stay bit-identical, host-sync budgets unchanged.
* :mod:`metrics` — TTFT / decode throughput / queue wait / occupancy /
  preemption counters plus the fault-tolerance counters (sheds, rejects,
  quarantines, dispatch retries, health), exported as a plain dict snapshot
  and (optionally) onto a ``utils.timeline.Timeline``.
* :mod:`faults` — deterministic, schedule-driven fault injection
  (``FaultInjector``) for the engine's chaos hooks: dispatch failures,
  poisoned readbacks, prefill faults, clock skew.
* :mod:`router` — :class:`ReplicaRouter` (ISSUE 14): N engine replicas
  behind one ``submit()`` — queue-depth + projected-page-pressure
  balancing, shared-prefix affinity with an overcommit guard,
  drain-around DEGRADED/HALTED, and bit-identical re-homing of a halted
  replica's requeued work to survivors (``tokens_lost == 0``).
* :mod:`disagg` — :class:`DisaggregatedServer`/:class:`PrefillWorker`
  (ISSUE 14): dedicated prefill workers hand finished contexts to the
  paged decode engine as zero-copy PAGE-TABLE handoffs
  (``PageAllocator.copy_bytes`` stays 0 on the shared-pool path; an
  explicit export/import device transfer covers distinct pools), so
  bursty prefill load cannot inflate steady-state decode TPOT.
* :mod:`traffic` — the deterministic open-loop load harness (ISSUE 11):
  seeded multi-tenant workload generation (Poisson + bursty/diurnal
  arrivals, chat vs long-doc length mixes) materialized as a
  byte-identical arrival tape, replayed through the engine on a
  :class:`VirtualClock` so the per-tenant TTFT/TPOT/goodput/attainment
  report is reproducible to the byte
  (``tests/serving/test_traffic.py::test_same_seed_identical_slo_report``).

Observability (ISSUE 8, ``neuronx_distributed_tpu/observability``): the
metrics above live in a shared ``MetricsRegistry`` (Prometheus/JSON
export, log-bucketed latency histograms incl. TTFT/TPOT percentiles);
with a ``Timeline`` attached every request renders as one connected
Perfetto flow (submit → admission → prefill → decode chunks → retire);
a ``FlightRecorder`` auto-dumps a redacted post-mortem on ``HALTED``
(``flight_dir=``); every phase of ``step()`` is a ``nxd.step*`` span on the
profiler's clock while a ``jax.profiler`` session is open
(``observability.profile_window`` around the run). All of it adds ZERO device→host syncs on the hot
path (tests/serving/test_host_sync.py pins the budgets).

Robustness contract (chaos-tested in ``tests/serving/test_faults.py``):
deadlines and queue timeouts shed to ``TIMED_OUT``; a failed donated decode
dispatch recovers through the preemption machinery (streams bit-identical)
with bounded consecutive retries before ``HALTED``; poisoned slots are
quarantined out of the rotation without corrupting neighbors; a bounded
queue rejects with :class:`~neuronx_distributed_tpu.serving.engine.
RejectedError`; ``drain()`` finishes in-flight work while admitting nothing
new; ``health()`` reports ``OK/DEGRADED/DRAINING/HALTED``.
"""

from neuronx_distributed_tpu.quantization.config import QuantConfig
from neuronx_distributed_tpu.serving.cache_manager import (
    PrefixCache,
    PrefixEntry,
    SlotCacheManager,
)
from neuronx_distributed_tpu.serving.engine import (
    EngineHealth,
    RejectedError,
    ServingEngine,
)
from neuronx_distributed_tpu.serving.disagg import (
    DisaggregatedServer,
    PrefillWorker,
)
from neuronx_distributed_tpu.serving.faults import (
    FaultInjector,
    InjectedDispatchError,
    InjectedDraftError,
    InjectedFault,
    InjectedHandoffError,
    InjectedPrefetchError,
    InjectedPrefillError,
    InjectedSpillError,
)
from neuronx_distributed_tpu.serving.metrics import ServingMetrics
from neuronx_distributed_tpu.serving.paging import (
    ExportedContext,
    PageAllocator,
    PagedCacheManager,
    PageExhausted,
    StagedContext,
)
from neuronx_distributed_tpu.serving.router import (
    RID_STRIDE,
    ReplicaRouter,
    WatchdogConfig,
)
from neuronx_distributed_tpu.serving.sched import (
    FairnessConfig,
    FeedbackConfig,
    FifoPolicy,
    PriorityConfig,
    SchedulingPolicy,
    SloPolicy,
    make_policy,
)
from neuronx_distributed_tpu.serving.scheduler import (
    Request,
    RequestState,
    Scheduler,
)
from neuronx_distributed_tpu.serving.tiering import HostPageStore
from neuronx_distributed_tpu.serving.transport import (
    ChaosTransport,
    Envelope,
    InProcessTransport,
    PartitionedError,
    TransportError,
    TransportTimeout,
)
from neuronx_distributed_tpu.serving.traffic import (
    Arrival,
    TenantProfile,
    VirtualClock,
    build_report,
    generate_tape,
    replay,
    tape_bytes,
)

__all__ = [
    "Arrival",
    "ChaosTransport",
    "DisaggregatedServer",
    "EngineHealth",
    "Envelope",
    "ExportedContext",
    "FairnessConfig",
    "FaultInjector",
    "FeedbackConfig",
    "FifoPolicy",
    "HostPageStore",
    "InProcessTransport",
    "InjectedDispatchError",
    "InjectedDraftError",
    "InjectedFault",
    "InjectedHandoffError",
    "InjectedPrefetchError",
    "InjectedPrefillError",
    "InjectedSpillError",
    "PageAllocator",
    "PageExhausted",
    "PagedCacheManager",
    "PartitionedError",
    "PrefillWorker",
    "PrefixCache",
    "PrefixEntry",
    "PriorityConfig",
    "QuantConfig",
    "RID_STRIDE",
    "RejectedError",
    "ReplicaRouter",
    "Request",
    "RequestState",
    "Scheduler",
    "SchedulingPolicy",
    "ServingEngine",
    "ServingMetrics",
    "SloPolicy",
    "SlotCacheManager",
    "StagedContext",
    "TenantProfile",
    "TransportError",
    "TransportTimeout",
    "VirtualClock",
    "WatchdogConfig",
    "build_report",
    "generate_tape",
    "make_policy",
    "replay",
    "tape_bytes",
]
