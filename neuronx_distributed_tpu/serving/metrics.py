"""Serving metrics: per-request latency breakdown + engine counters.

Everything is plain host-side accounting (the engine clock is injectable for
deterministic tests) exported as a dict ``snapshot()``; when the engine holds
a ``utils.timeline.Timeline``, per-step occupancy and queue depth also land
on counter tracks next to the prefill/decode duration events, so one Perfetto
view shows the whole scheduling story.

Since ISSUE 8 the counters live in a shared
:class:`~neuronx_distributed_tpu.observability.registry.MetricsRegistry`
(pass one in to co-export serving and trainer metrics from a single
Prometheus surface; ``metrics.registry.prometheus_text()`` is the scrape
payload). The attribute surface is unchanged — ``metrics.steps`` etc. read
through to the registry — and the ``snapshot()`` keys are preserved
bit-for-bit in name and type.

Latency percentiles come from log-bucketed histograms (exact to the bucket,
fixed memory): the prefill p95 no longer reads a 512-sample recent window —
whose value drifted with stream phase on long runs — and new TTFT/TPOT
histograms (``ttft_p50_s``..``tpot_p99_s`` in the snapshot) feed the SLO
scheduling work the ROADMAP names. Recording stays sync-free: every sample
is a host scalar the engine already owned.

Tenant attribution + SLO accounting (ISSUE 11):

* Every request carries ``tenant``/``priority`` (threaded through
  ``ServingEngine.submit``); TTFT, TPOT, and queue-wait additionally land
  in per-tenant labeled histogram families
  (``serving_tenant_ttft_s{tenant="..."}``), and sheds / timeouts /
  rejects / failures / completions / delivered tokens get per-tenant
  attribution counters — the ``snapshot()``'s ``tenants`` breakdown.
* ``slo=`` (an :class:`~neuronx_distributed_tpu.observability.slo.SLOSpec`
  or a ``{tenant: SLOSpec}`` dict) attaches an
  :class:`~neuronx_distributed_tpu.observability.slo.SLOTracker`: each
  request is classified once at its terminal state (attained / violated),
  goodput = tokens from attaining requests per second, all per tenant —
  the ``snapshot()``'s ``slo`` block and the labeled ``serving_slo_*``
  Prometheus families.
* ``engine_label=`` retires the PR 7 one-engine-per-registry restriction:
  with a label, EVERY serving metric registers as a child of an
  ``engine``-labeled family, so two labeled engines share one registry
  (one scrape endpoint for a multi-engine host) without merging a single
  counter. Unlabeled engines keep the loud rejection.

All of it rides the same host scalars — zero added device→host syncs
(re-pinned in tests/serving/test_host_sync.py with tenants + SLO on).
"""

from __future__ import annotations

import weakref
from typing import Dict, Optional

from neuronx_distributed_tpu.observability.registry import (
    MetricFamily,
    MetricsRegistry,
    MetricsView,
)
from neuronx_distributed_tpu.observability.slo import SLOSpec, SLOTracker
from neuronx_distributed_tpu.observability.spec_stats import SpecStats


def _mean(xs):
    return sum(xs) / len(xs) if xs else 0.0


# (attribute, registry metric name, int-valued) — the engine/test-visible
# counter surface, all backed by registry counters
_COUNTERS = (
    ("steps", "serving_steps", True),
    ("chunks", "serving_chunks", True),
    ("prefills", "serving_prefills", True),
    ("decode_tokens", "serving_decode_tokens", True),
    ("completed", "serving_completed", True),
    ("cancelled", "serving_cancelled", True),
    ("preemptions", "serving_preemptions", True),
    ("sheds", "serving_sheds", True),
    ("rejects", "serving_rejects", True),
    ("quarantines", "serving_quarantines", True),
    ("dispatch_retries", "serving_dispatch_retries", True),
    ("recoveries", "serving_recoveries", True),
    ("prefill_failures", "serving_prefill_failures", True),
    ("failed", "serving_failed", True),
    ("timed_out", "serving_timed_out", True),
    ("prefix_hits", "serving_prefix_hits", True),
    ("prefix_misses", "serving_prefix_misses", True),
    ("prefix_tokens_reused", "serving_prefix_tokens_reused", True),
    ("prefix_evictions", "serving_prefix_evictions", True),
    ("prefix_validation_failures", "serving_prefix_validation_failures", True),
    # paged KV (kv_page_size=): pages retired for poison, and pool pages
    # mapped copy-on-write into a hitting slot (zero bytes moved)
    ("page_quarantines", "serving_page_quarantines", True),
    ("prefix_pages_shared", "serving_prefix_pages_shared", True),
    # tiered KV (ISSUE 19): host spill-tier traffic and its failure modes.
    # late = prefetch issued at rebind time instead of the queue pre-pass
    # (the overlap window was missed); wasted = prefetched pages whose
    # entry was evicted before any request consumed them
    ("kv_pages_spilled", "serving_kv_spill_pages", True),
    ("kv_spill_bytes", "serving_kv_spill_bytes", True),
    ("kv_spill_failures", "serving_kv_spill_failures", True),
    ("kv_pages_prefetched", "serving_kv_spill_prefetch_pages", True),
    ("kv_prefetch_bytes", "serving_kv_spill_prefetch_bytes", True),
    ("kv_prefetch_late", "serving_kv_spill_prefetch_late", True),
    ("kv_prefetch_wasted", "serving_kv_spill_prefetch_wasted", True),
    ("kv_prefetch_failures", "serving_kv_spill_prefetch_failures", True),
    ("kv_host_poisoned", "serving_kv_spill_host_poisoned", True),
    # elastic fabric (ISSUE 18): requests brought back by a warm restart
    ("restored", "serving_restored_requests", True),
    ("occupied_slot_steps", "serving_occupied_slot_steps", True),
    ("prefill_full_wall_s", "serving_prefill_full_wall_s", False),
    ("prefill_suffix_wall_s", "serving_prefill_suffix_wall_s", False),
    ("decode_dispatch_s", "serving_decode_dispatch_s", False),
    ("decode_readback_s", "serving_decode_readback_s", False),
    # the step ledger's verdicts (observability/flight_recorder.py): steps
    # that overran their expected wall, and the excess seconds: what an
    # operator alerts on; the ``slow_step`` flight event says where
    ("step_overruns", "serving_step_overruns", True),
    ("step_overrun_s", "serving_step_overrun_seconds", False),
    # decode chunks dispatched, and those of them with no sampled slot
    # (``sampled_slots`` 0 on the dispatch span): every step of such a chunk
    # takes its tokens by argmax alone (utils/sampling.sample_per_row), so
    # the two read equal on all-greedy traffic
    ("chunks_dispatched", "serving_decode_chunks_dispatched", True),
    ("greedy_chunks_dispatched", "serving_greedy_chunks_dispatched", True),
    # chunks called before the chunk before them was read back (two on the
    # device: ``ahead`` 1 on the dispatch span), and those of them that ran
    # over a slot whose request had ended at the boundary they ran over (an
    # EOS, a cancel, a deadline: found one chunk late, the rule's bounded cost)
    ("chunks_run_ahead", "serving_decode_chunks_run_ahead", True),
    ("late_found_ends", "serving_decode_late_found_ends", True),
    # pages of the window kind's pool given back behind a slot's window (a
    # model with window layers: serving/paging.py)
    ("window_pages_freed", "serving_window_pages_freed", True),
)

_HEALTH_CODES = {"ok": 0, "degraded": 1, "draining": 2, "halted": 3}

# per-tenant attribution counters (ISSUE 11): attr suffix -> registry
# family name. Rejects/sheds/timeouts answer "WHO is being turned away",
# completed/decode_tokens feed the per-tenant goodput/throughput story
_TENANT_COUNTERS = (
    ("submitted", "serving_tenant_submitted"),
    ("completed", "serving_tenant_completed"),
    ("decode_tokens", "serving_tenant_decode_tokens"),
    ("sheds", "serving_tenant_sheds"),
    ("timed_out", "serving_tenant_timed_out"),
    ("rejects", "serving_tenant_rejects"),
    ("failed", "serving_tenant_failed"),
)


def _tenant_of(req) -> str:
    return getattr(req, "tenant", "default")


class ServingMetrics:
    """Aggregates the engine's request lifecycle events into a registry."""

    def __init__(self, num_slots: int = 0,
                 registry: Optional[MetricsRegistry] = None,
                 engine_label: Optional[str] = None,
                 slo=None):
        self.num_slots = num_slots
        self.engine_label = engine_label
        if registry is not None:
            existing = registry.get(_COUNTERS[0][1])
            if existing is not None:
                # an unlabeled second engine on the same registry would
                # SILENTLY merge its counters into the first's (and
                # last-writer-wins the export gauges). Labeled metric
                # families retire that restriction: when BOTH engines pass
                # a distinct engine_label, every serving metric is a child
                # of an `engine`-labeled family and the series stay
                # separate. Anything else still fails loudly. Sharing
                # across SUBSYSTEMS (serving_ + train_ prefixes) needs no
                # labels either way
                shareable = (
                    engine_label is not None
                    and isinstance(existing, MetricFamily)
                    and existing.label_names == ("engine",)
                    and not existing.has_child(engine_label)
                )
                if not shareable:
                    raise ValueError(
                        "registry already holds serving metrics (another "
                        "ServingEngine registered into it) — pass a "
                        "distinct engine_label= on every engine to share "
                        "one registry via labeled families, or a distinct "
                        "MetricsRegistry per engine"
                    )
        self.registry = registry if registry is not None else MetricsRegistry()

        # with an engine label, every serving metric resolves through an
        # engine-scoped MetricsView (a family child keyed by the label);
        # without one, the original unlabeled series. The record paths
        # below are identical either way — children ARE plain
        # Counter/Gauge/Histogram instances. The view is the ONE owner of
        # the labeling scheme (SpecStats and SLOTracker ride it too)
        self.view = (
            MetricsView(self.registry, ("engine",), (engine_label,))
            if engine_label is not None else MetricsView(self.registry)
        )
        own_counter = self.view.counter
        own_histogram = self.view.histogram
        self.own_gauge = self.view.gauge  # the engine's export gauges
        self._c = {}
        for attr, name, is_int in _COUNTERS:
            self._c[attr] = (own_counter(name), is_int)
        # latency histograms: log-bucketed, fixed memory, quantiles exact
        # to the bucket (observability/registry.py) — prefill feeds the
        # legacy prefill_p95_s key; TTFT/TPOT feed the SLO roadmap item
        self._h_prefill = own_histogram(
            "serving_prefill_latency_s",
            help="wall time of one successful prefill dispatch (s)",
        )
        self._h_ttft = own_histogram(
            "serving_ttft_s", help="submit -> first token (s)"
        )
        self._h_tpot = own_histogram(
            "serving_tpot_s",
            help="per-request mean time per output token after the first (s)",
        )
        self._h_queue_wait = own_histogram(
            "serving_queue_wait_s", help="submit -> first admission (s)"
        )
        self._h_restore_downtime = own_histogram(
            "serving_restore_downtime_s",
            help="snapshot -> restore_serving_state clock gap (s): how "
                 "long a warm-restarted replica's work was dark",
        )
        # tiered KV (ISSUE 19): transfer batch sizes — spill efficiency
        # lives in pages-per-event, not event counts
        self._h_spill_batch = own_histogram(
            "serving_kv_spill_batch_pages",
            help="pages moved device->host per spill event",
        )
        self._h_prefetch_batch = own_histogram(
            "serving_kv_spill_prefetch_batch_pages",
            help="pages moved host->device per prefetch event",
        )
        self._g_cursor = self.view.gauge(
            "serving_cursor_high_water", help="highest shared cache cursor seen"
        )
        self._g_kv_bytes = self.view.gauge(
            "serving_kv_bytes_per_token_layer",
            help="bytes one token holds in one attention layer's cache",
        )
        self._g_kv_nodes = self.view.gauge(
            "serving_kv_cache_nodes",
            help="attention nodes of the cache: one a layer, one a layer a pass for a looped stack",
        )
        self._g_slot_state = self.view.gauge(
            "serving_slot_state_bytes",
            help="bytes every slot's per-slot state leaves hold, all layers",
        )
        self._g_health = self.view.gauge(
            "serving_health", help="0=ok 1=degraded 2=draining 3=halted"
        )
        self._g_health.set_fn(lambda: _HEALTH_CODES.get(self.health, -1))
        # per-tenant labeled families (tenant label; engine+tenant when
        # this engine itself is labeled — the view prepends its scope).
        # Registered up front so the exposition surface exists before
        # traffic arrives; children materialize per tenant on first use
        self._tc: Dict[str, MetricFamily] = {
            attr: self.view.family("counter", name)
            for attr, name in _TENANT_COUNTERS
        }
        self._th_ttft = self.view.family(
            "histogram", "serving_tenant_ttft_s",
            help="submit -> first token per tenant (s)",
        )
        self._th_tpot = self.view.family(
            "histogram", "serving_tenant_tpot_s",
            help="per-request mean time per output token per tenant (s)",
        )
        self._th_queue_wait = self.view.family(
            "histogram", "serving_tenant_queue_wait_s",
            help="submit -> first admission per tenant (s)",
        )
        # tiered KV (ISSUE 19): which tier the matched prefix entry's
        # pages lived in when the hit was consumed — device (CoW share)
        # or host (spilled, prefetched back)
        self._f_hit_tier = self.view.family(
            "counter", "serving_prefix_hit_tier", labels=("tier",),
            help="prefix hits by residency tier of the matched entry",
        )
        self._tenants_seen = set()
        # SLO accounting (observability/slo.py): classify every request
        # once at its terminal state against its tenant's SLOSpec; export
        # attainment + goodput per tenant through the same registry
        if slo is not None and not isinstance(slo, SLOTracker):
            slo = SLOTracker(
                slo, registry=self.registry, prefix="serving_slo",
                view=self.view,
            )
        self.slo: Optional[SLOTracker] = slo
        # speculative-decoding acceptance stats: the SHARED recorder (solo
        # speculative_generate reports through the same class, so both
        # paths expose identical names/keys); always registered so the
        # snapshot surface is stable whether or not a draft model is bound
        self.spec = SpecStats(self.registry, prefix="spec", view=self.view)
        self.view.gauge("serving_num_slots").set(num_slots)
        self.health = "ok"  # engine-owned mirror of ServingEngine.health()
        self.cursor_high_water = 0
        # bytes a token holds per attention layer, from the allocated cache
        # leaves (0 until the first admission allocates them)
        self.kv_bytes_per_token_layer = 0.0
        self.kv_cache_nodes = 0
        # device-efficiency ledgers (ISSUE 12): attached weakly by the
        # engine so snapshot() can carry "programs"/"hbm" without a kept
        # metrics object pinning a retired engine's ledgers
        self._programs_ref = None
        self._hbm_ref = None
        # per-request
        self._requests: Dict[int, dict] = {}

    def attach_device_efficiency(self, programs, hbm) -> None:
        """Wire the engine's :class:`ProgramLedger`/:class:`HBMLedger`
        into ``snapshot()["programs"]``/``["hbm"]`` (weak references)."""
        self._programs_ref = weakref.ref(programs) if programs is not None else None
        self._hbm_ref = weakref.ref(hbm) if hbm is not None else None

    def _tenant_inc(self, attr: str, tenant: str, n=1) -> None:
        self._tenants_seen.add(tenant)
        self.view.child(self._tc[attr], tenant).inc(n)

    def _tenant_observe(self, family: MetricFamily, tenant: str,
                        value: float) -> None:
        self._tenants_seen.add(tenant)
        self.view.child(family, tenant).observe(value)

    def __getattr__(self, name):
        # counter attributes (``metrics.steps`` etc.) read through to the
        # registry; only consulted when no instance attribute exists
        c = self.__dict__.get("_c")
        if c is not None and name in c:
            counter, is_int = c[name]
            v = counter.value
            return int(v) if is_int else float(v)
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}"
        )

    def _inc(self, attr: str, n=1) -> None:
        self._c[attr][0].inc(n)

    # --- request lifecycle --------------------------------------------------

    def record_submit(self, req, now: float) -> None:
        tenant = _tenant_of(req)
        self._requests[req.rid] = {
            "rid": req.rid,
            "prompt_len": int(len(req.prompt)),
            "submit_time": now,
            "tenant": tenant,
            "priority": getattr(req, "priority", "standard"),
        }
        self._tenant_inc("submitted", tenant)
        if self.slo is not None:
            # goodput's denominator starts at the FIRST submit, not the
            # first finish — idle-tail truncation would inflate it
            self.slo.touch(now)

    def record_adopt(self, req, now: float) -> None:
        """Register a RE-HOMED request (the replica router moved it here
        from a halted replica): per-request bookkeeping only, keyed to the
        ORIGINAL submit time so TTFT/latency spans the request's whole
        life — the submit itself was already counted where it happened."""
        tenant = _tenant_of(req)
        self._requests[req.rid] = {
            "rid": req.rid,
            "prompt_len": int(len(req.prompt)),
            "submit_time": (
                req.submit_time if req.submit_time is not None else now
            ),
            "tenant": tenant,
            "priority": getattr(req, "priority", "standard"),
        }
        if req.first_token_time is not None:
            # the dead replica already streamed its first token — keep the
            # TTFT it measured out of this engine's histograms (it was
            # observed there) but make decode-span math exact here
            self._requests[req.rid]["first_token_time"] = (
                req.first_token_time
            )
        if self.slo is not None:
            self.slo.touch(now)

    def record_restore(self, n_requests: int, downtime_s: float) -> None:
        """Warm-restart accounting (ISSUE 18): how many requests
        ``restore_serving_state`` brought back and how long they were
        dark. The per-request bookkeeping itself rides
        :meth:`record_adopt` (restore calls it per request)."""
        if n_requests:
            self._inc("restored", n_requests)
        self._h_restore_downtime.observe(float(downtime_s))

    def record_admit(self, req, now: float) -> None:
        r = self._requests[req.rid]
        # first admission sets the queue wait; re-admissions after preemption
        # keep the original (the request never left the engine's care)
        r.setdefault("admit_time", now)
        if "queue_wait" not in r:
            r["queue_wait"] = now - r["submit_time"]
            self._h_queue_wait.observe(r["queue_wait"])
            self._tenant_observe(
                self._th_queue_wait, _tenant_of(req), r["queue_wait"]
            )
        self._inc("prefills")

    def record_first_token(self, req, now: float) -> None:
        r = self._requests[req.rid]
        r["first_token_time"] = now
        r["ttft"] = now - r["submit_time"]
        self._h_ttft.observe(r["ttft"])
        self._tenant_observe(self._th_ttft, _tenant_of(req), r["ttft"])

    def record_finish(self, req, now: float) -> None:
        r = self._requests[req.rid]
        tenant = _tenant_of(req)
        r["finish_time"] = now
        r["latency"] = now - r["submit_time"]
        r["tokens"] = len(req.tokens)
        decode_span = now - r.get("first_token_time", now)
        # tokens after the first are decode-step products
        r["decode_tokens_per_sec"] = (
            (len(req.tokens) - 1) / decode_span if decode_span > 0 else 0.0
        )
        # TPOT is undefined for single-token requests (None: an SLO TPOT
        # bound passes vacuously); a 0-span multi-chunk finish under a
        # virtual clock observes 0 (the histogram's zero bucket)
        tpot = (
            decode_span / (len(req.tokens) - 1)
            if len(req.tokens) > 1 else None
        )
        if tpot is not None:
            self._h_tpot.observe(tpot)
            self._tenant_observe(self._th_tpot, tenant, tpot)
        r["preemptions"] = req.preemptions
        self._inc("completed")
        self._tenant_inc("completed", tenant)
        self._tenant_inc("decode_tokens", tenant, len(req.tokens))
        if self.slo is not None:
            # the ONE terminal classification of a finished request —
            # preemption/recovery requeues re-admit but never re-finish,
            # so a requeued-then-finished request counts exactly once
            r["slo_attained"] = self.slo.record_finish(
                tenant, r.get("ttft"), tpot, len(req.tokens), now
            )

    def record_cancel(self, req, now: float) -> None:
        # a user cancellation is neither attained nor violated — the
        # engine met whatever contract the caller abandoned
        r = self._requests.get(req.rid)
        if r is not None:
            r["finish_time"] = now
            r["cancelled"] = True
        self._inc("cancelled")

    def record_preemption(self, req) -> None:
        self._inc("preemptions")

    def record_step_overrun(self, excess_s: float) -> None:
        self._inc("step_overruns")
        self._inc("step_overrun_s", excess_s)

    def record_chunk_dispatch(self, sampled_slots: int, ahead: bool = False) -> None:
        """A decode chunk went to the device with ``sampled_slots`` of its
        active slots asking ``temperature != 0``; ``ahead``: before the
        chunk before it was read back."""
        self._inc("chunks_dispatched")
        if not sampled_slots:
            self._inc("greedy_chunks_dispatched")
        if ahead:
            self._inc("chunks_run_ahead")

    def record_late_found_end(self) -> None:
        """A chunk run ahead was read back over a slot whose request had
        ended at the boundary it ran over."""
        self._inc("late_found_ends")

    def record_window_pages_freed(self, n: int) -> None:
        """``n`` pages of the window kind went back to its allocator."""
        self._inc("window_pages_freed", n)

    # --- fault tolerance ----------------------------------------------------

    def record_shed(self, req, now: float, where: str) -> None:
        """A request timed out — ``where`` is ``"queue"`` (shed before
        prefill) or ``"inflight"`` (deadline hit at a chunk boundary)."""
        tenant = _tenant_of(req)
        r = self._requests.get(req.rid)
        if r is not None:
            r["finish_time"] = now
            r["timed_out"] = True
            r["shed_where"] = where
            r["tokens"] = len(req.tokens)
        self._inc("sheds")
        self._inc("timed_out")
        self._tenant_inc("sheds", tenant)
        self._tenant_inc("timed_out", tenant)
        if self.slo is not None:
            # tokens a shed request already streamed are wasted work:
            # total, never goodput
            self.slo.record_violation(
                tenant, now,
                reason=f"shed_{where}", tokens=len(req.tokens),
            )

    def record_reject(self, queue_depth: int, reason: str,
                      tenant: str = "default",
                      now: Optional[float] = None) -> None:
        """A submission refused at the door (queue full, draining,
        halted) — no Request exists, so the tenant (and the engine-clock
        timestamp) ride in directly. Rejected traffic is an SLO
        violation: shedding a tenant's load must never read as improving
        its attainment."""
        self._inc("rejects")
        self._tenant_inc("rejects", tenant)
        if self.slo is not None:
            self.slo.record_violation(tenant, now, reason="reject")

    def record_quarantine(self, slot: int, rid) -> None:
        self._inc("quarantines")

    def record_dispatch_retry(self) -> None:
        self._inc("dispatch_retries")

    def record_recovery(self, requeued: int) -> None:
        self._inc("recoveries")

    def record_failed(self, req, now: float, kind: str = "engine") -> None:
        """A request the engine failed for cause (``req.error`` has the
        reason): ``kind`` is ``"prefill"`` (OOM-like admission fault) or
        ``"quarantine"`` (poisoned slot under the fail policy)."""
        tenant = _tenant_of(req)
        r = self._requests.get(req.rid)
        if r is not None:
            r["finish_time"] = now
            r["failed"] = True
            r["failed_kind"] = kind
        self._inc("failed")
        self._tenant_inc("failed", tenant)
        if kind == "prefill":
            self._inc("prefill_failures")
        if self.slo is not None:
            self.slo.record_violation(
                tenant, now, reason=f"failed_{kind}", tokens=len(req.tokens)
            )

    # --- prefix cache -------------------------------------------------------

    def record_prefix_hit(self, matched: int, prompt_len: int,
                          tier: str = "device") -> None:
        """An admission reused ``matched`` stored prefix tokens of a
        ``prompt_len``-token context (only the tail was prefilled).
        ``tier`` is where the entry's pages lived when the hit was
        consumed: ``"device"`` (resident, CoW share) or ``"host"``
        (spilled to the host tier and prefetched back)."""
        self._inc("prefix_hits")
        self._inc("prefix_tokens_reused", matched)
        self.view.child(self._f_hit_tier, tier).inc()

    def record_prefix_pages_shared(self, n: int) -> None:
        """A paged prefix hit mapped ``n`` pool pages copy-on-write into
        the admitted slot's block table (zero KV bytes copied)."""
        self._inc("prefix_pages_shared", n)

    def record_page_quarantine(self, page: int, victims: int) -> None:
        """A poisoned pool page was retired; ``victims`` requests mapping
        it were requeued (page-granular fault domain)."""
        self._inc("page_quarantines")

    def record_prefix_miss(self) -> None:
        self._inc("prefix_misses")

    def record_prefix_eviction(self, n: int = 1) -> None:
        self._inc("prefix_evictions", n)

    def record_prefix_validation_failure(self) -> None:
        """A stored entry failed its reuse-time checksum/shape validation —
        it was evicted and the admission fell back to a full prefill."""
        self._inc("prefix_validation_failures")

    # --- tiered KV (ISSUE 19) -----------------------------------------------

    def record_spill(self, pages: int, nbytes: int) -> None:
        """The reclaim valve moved a cold prefix entry's ``pages`` pool
        pages (``nbytes`` total) device->host in one batched pull."""
        self._inc("kv_pages_spilled", pages)
        self._inc("kv_spill_bytes", nbytes)
        self._h_spill_batch.observe(float(pages))

    def record_spill_failure(self) -> None:
        """A spill attempt failed (injected or real) — the entry degraded
        to plain eviction, the pre-tiering behavior."""
        self._inc("kv_spill_failures")

    def record_prefetch(self, pages: int, nbytes: int,
                        late: bool = False) -> None:
        """``pages`` spilled pages were written back device-side.
        ``late=True`` means the write happened at rebind time (the queue
        pre-pass missed it) — correct, but the overlap window was lost."""
        self._inc("kv_pages_prefetched", pages)
        self._inc("kv_prefetch_bytes", nbytes)
        if late:
            self._inc("kv_prefetch_late")
        self._h_prefetch_batch.observe(float(pages))

    def record_prefetch_failure(self) -> None:
        """A prefetch attempt failed — the entry was dropped and the
        admission fell back to a full prefill."""
        self._inc("kv_prefetch_failures")

    def record_prefetch_wasted(self, pages: int) -> None:
        """``pages`` prefetched pages were evicted before any request
        consumed them — the prefetch's work was thrown away."""
        self._inc("kv_prefetch_wasted", pages)

    def record_host_page_poisoned(self, n: int = 1) -> None:
        """A host-tier fetch was rejected because at least one of its
        pages failed the fingerprint check (bit rot / chaos poison)."""
        self._inc("kv_host_poisoned", n)

    def record_prefill_wall(self, seconds: float, kind: str = "full") -> None:
        """Wall time of one successful prefill dispatch (``kind`` is
        ``"full"`` or ``"suffix"``); feeds the latency histogram (count/
        mean/p95 in :meth:`snapshot`) and the per-kind wall split."""
        self._h_prefill.observe(seconds)
        if kind == "suffix":
            self._inc("prefill_suffix_wall_s", seconds)
        else:
            self._inc("prefill_full_wall_s", seconds)

    # --- engine step --------------------------------------------------------

    def record_decode_step(self, active_slots: int, cursor: int) -> None:
        """Single-step accounting — the chunk-size-1 special case."""
        self.record_decode_chunk(active_slots, 1, cursor, active_slots)

    def record_kv_bytes(self, per_token: float, nodes: int) -> None:
        """What a token holds over all ``nodes`` attention nodes of the cache
        (``modules/attention.cache_token_bytes``)."""
        self.kv_cache_nodes = int(nodes)
        self.kv_bytes_per_token_layer = per_token / nodes if nodes else 0.0
        self._g_kv_bytes.set(self.kv_bytes_per_token_layer)
        self._g_kv_nodes.set(nodes)

    def record_slot_state_bytes(self, nbytes: int) -> None:
        self._g_slot_state.set(nbytes)

    def record_decode_chunk(
        self,
        tokens: int,
        steps: int,
        cursor: int,
        active_slots: int,
        dispatch_s: float = 0.0,
        readback_s: float = 0.0,
        spec_accepts=None,
        gamma: int = 0,
    ) -> None:
        """One fused decode chunk: ``tokens`` DELIVERED to requests across
        ``steps`` executed scan steps by ``active_slots`` slots held at
        dispatch. Occupancy counts slots HELD, not tokens — a slot frozen
        mid-chunk (early EOS) still owns its cache row until the chunk
        boundary, so it occupies all ``steps``. ``dispatch_s``/
        ``readback_s`` split the wall time around the chunk's single host
        sync.

        Speculative chunks (``steps`` = executed ROUNDS) additionally pass
        ``spec_accepts`` — one accepted-draft length per (live round, slot)
        pair, already host scalars from the chunk's single readback — and
        ``gamma``; the draft/verify split (drafted vs accepted vs wasted
        draft tokens) lands in the shared ``SpecStats`` recorder."""
        self._inc("chunks")
        self._inc("steps", steps)
        self._inc("decode_tokens", tokens)
        self._inc("occupied_slot_steps", active_slots * steps)
        if cursor > self.cursor_high_water:
            self.cursor_high_water = cursor
            self._g_cursor.set(cursor)
        self._inc("decode_dispatch_s", dispatch_s)
        self._inc("decode_readback_s", readback_s)
        if spec_accepts is not None:
            for a in spec_accepts:
                self.spec.record_round(int(a), gamma)

    def record_spec_fallback(self) -> None:
        """A speculative dispatch failed and the chunk was decoded
        non-speculatively instead (streams unaffected)."""
        self.spec.record_fallback()

    # --- export -------------------------------------------------------------

    @property
    def prefill_count(self) -> int:
        """Successful prefill dispatches (full + suffix)."""
        return self._h_prefill.count

    @property
    def prefill_wall_s(self) -> float:
        return float(self._h_prefill.sum)

    @property
    def mean_occupancy(self) -> float:
        """Mean active slots per decode step (≤ num_slots)."""
        return self.occupied_slot_steps / self.steps if self.steps else 0.0

    def request_snapshot(self, rid: int) -> Optional[dict]:
        r = self._requests.get(rid)
        return dict(r) if r is not None else None

    def _tenant_child_value(self, attr: str, tenant: str) -> int:
        fam = self._tc[attr]
        if not self.view.has_child(fam, tenant):
            return 0
        return int(self.view.child(fam, tenant).value)

    def tenant_latency(self, kind: str, tenant: str, q: float) -> float:
        """Live read of one tenant's latency quantile off its labeled
        histogram child (``kind`` = ``"ttft"`` | ``"tpot"`` |
        ``"queue_wait"``): the SLO-aware scheduler's early-warning signal
        (ISSUE 16) — the attainment tracker only classifies at finish
        time, but a burst's damage shows here first. READ-only (0.0 for a
        tenant that never recorded — never materializes an empty child)
        and pure host arithmetic over bucket counts: safe on the
        admission path, zero syncs."""
        fam = {
            "ttft": self._th_ttft,
            "tpot": self._th_tpot,
            "queue_wait": self._th_queue_wait,
        }[kind]
        if not self.view.has_child(fam, tenant):
            return 0.0
        return float(self.view.child(fam, tenant).percentile(q))

    def tenant_snapshot(self) -> Dict[str, dict]:
        """Per-tenant breakdown (tenant-sorted, deterministic keys):
        attribution counters + the tenant's latency percentiles off its
        labeled histogram children. READ-only: a tenant that never
        recorded a latency (e.g. only ever rejected at the door) reports
        0.0 percentiles without materializing empty histogram children —
        a snapshot must not change what the next scrape exports."""
        out: Dict[str, dict] = {}
        for tenant in sorted(self._tenants_seen):
            row = {
                attr: self._tenant_child_value(attr, tenant)
                for attr, _ in _TENANT_COUNTERS
            }
            for key, fam in (
                ("ttft", self._th_ttft),
                ("tpot", self._th_tpot),
            ):
                h = (
                    self.view.child(fam, tenant)
                    if self.view.has_child(fam, tenant) else None
                )
                for q in (50, 95, 99):
                    row[f"{key}_p{q}_s"] = (
                        h.percentile(q / 100.0) if h is not None else 0.0
                    )
            row["queue_wait_p95_s"] = (
                self.view.child(self._th_queue_wait, tenant).percentile(0.95)
                if self.view.has_child(self._th_queue_wait, tenant) else 0.0
            )
            out[tenant] = row
        return out

    def snapshot(self, analyze_programs: bool = True) -> dict:
        """Plain-dict export (log lines, tests, dashboards). Every key of
        the pre-registry snapshot is preserved in name and type; the
        percentile keys now read bucket-exact histogram quantiles, and the
        ``ttft_*``/``tpot_*`` families are new. ``analyze_programs=False``
        skips any not-yet-run program cost analysis (halt paths)."""
        programs = self._programs_ref() if self._programs_ref else None
        hbm = self._hbm_ref() if self._hbm_ref else None
        done = [r for r in self._requests.values() if "latency" in r]
        ttfts = [r["ttft"] for r in self._requests.values() if "ttft" in r]
        waits = [
            r["queue_wait"] for r in self._requests.values()
            if "queue_wait" in r
        ]
        decode_wall = self.decode_dispatch_s + self.decode_readback_s
        return {
            "num_slots": self.num_slots,
            "steps": self.steps,
            "chunks": self.chunks,
            "chunks_dispatched": self.chunks_dispatched,
            "greedy_chunks_dispatched": self.greedy_chunks_dispatched,
            "chunks_run_ahead": self.chunks_run_ahead,
            "run_ahead_share": (
                self.chunks_run_ahead / self.chunks_dispatched
                if self.chunks_dispatched else 0.0
            ),
            "late_found_ends": self.late_found_ends,
            "window_pages_freed": self.window_pages_freed,
            "decode_dispatch_s": self.decode_dispatch_s,
            "decode_readback_s": self.decode_readback_s,
            "chunk_tokens_per_sec": (
                self.decode_tokens / decode_wall if decode_wall > 0 else 0.0
            ),
            "prefills": self.prefills,
            "decode_tokens": self.decode_tokens,
            "completed": self.completed,
            "cancelled": self.cancelled,
            "preemptions": self.preemptions,
            "sheds": self.sheds,
            "rejects": self.rejects,
            "quarantines": self.quarantines,
            "dispatch_retries": self.dispatch_retries,
            "recoveries": self.recoveries,
            "prefill_failures": self.prefill_failures,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_rate": (
                self.prefix_hits / (self.prefix_hits + self.prefix_misses)
                if self.prefix_hits + self.prefix_misses else 0.0
            ),
            "prefix_tokens_reused": self.prefix_tokens_reused,
            "prefix_evictions": self.prefix_evictions,
            "prefix_validation_failures": self.prefix_validation_failures,
            "prefix_pages_shared": self.prefix_pages_shared,
            "page_quarantines": self.page_quarantines,
            # tiered KV (ISSUE 19): spill/prefetch traffic, failure modes,
            # and the per-tier split of consumed prefix hits
            "kv_pages_spilled": self.kv_pages_spilled,
            "kv_spill_bytes": self.kv_spill_bytes,
            "kv_spill_failures": self.kv_spill_failures,
            "kv_pages_prefetched": self.kv_pages_prefetched,
            "kv_prefetch_bytes": self.kv_prefetch_bytes,
            "kv_prefetch_late": self.kv_prefetch_late,
            "kv_prefetch_wasted": self.kv_prefetch_wasted,
            "kv_prefetch_failures": self.kv_prefetch_failures,
            "kv_host_poisoned": self.kv_host_poisoned,
            "prefix_hit_tier": {
                tier: int(self.view.child(self._f_hit_tier, tier).value)
                for tier in ("device", "host")
                if self.view.has_child(self._f_hit_tier, tier)
            },
            "prefill_count": self.prefill_count,
            "prefill_wall_s": self.prefill_wall_s,
            "prefill_mean_s": self._h_prefill.mean,
            "prefill_p95_s": self._h_prefill.percentile(0.95),
            "prefill_full_wall_s": self.prefill_full_wall_s,
            "prefill_suffix_wall_s": self.prefill_suffix_wall_s,
            "failed": self.failed,
            "timed_out": self.timed_out,
            # warm restart (ISSUE 18): requests admitted from a serving-
            # state snapshot, and how long the work was dark
            "restored": self.restored,
            "restore_downtime_p95_s": self._h_restore_downtime.percentile(
                0.95
            ),
            "health": self.health,
            "cursor_high_water": self.cursor_high_water,
            "kv_bytes_per_token_layer": self.kv_bytes_per_token_layer,
            "kv_cache_nodes": self.kv_cache_nodes,
            "mean_occupancy": self.mean_occupancy,
            "mean_ttft": _mean(ttfts),
            "max_ttft": max(ttfts) if ttfts else 0.0,
            "mean_queue_wait": _mean(waits),
            "mean_latency": _mean([r["latency"] for r in done]),
            "mean_decode_tokens_per_sec": _mean(
                [r["decode_tokens_per_sec"] for r in done]
            ),
            # SLO-facing percentile families (log-bucketed histograms:
            # exact to the bucket, stable over unbounded streams)
            "ttft_p50_s": self._h_ttft.percentile(0.50),
            "ttft_p95_s": self._h_ttft.percentile(0.95),
            "ttft_p99_s": self._h_ttft.percentile(0.99),
            "tpot_p50_s": self._h_tpot.percentile(0.50),
            "tpot_p95_s": self._h_tpot.percentile(0.95),
            "tpot_p99_s": self._h_tpot.percentile(0.99),
            "queue_wait_p95_s": self._h_queue_wait.percentile(0.95),
            # per-tenant attribution (ISSUE 11): who submitted, who got
            # served, who was shed — plus each tenant's own latency
            # percentiles (labeled histogram families)
            "tenants": self.tenant_snapshot(),
            # device efficiency (ISSUE 12): the compiled-program ledger
            # (compiler-reported cost, dispatch counts, roofline) and the
            # HBM resident accounting — {} when no engine attached them
            "programs": (
                programs.snapshot(analyze=analyze_programs)
                if programs is not None else {}
            ),
            "hbm": hbm.snapshot() if hbm is not None else {},
            # SLO accounting (present only with slo= specs): attainment +
            # goodput, totals and per tenant
            **(
                {"slo": self.slo.snapshot()} if self.slo is not None else {}
            ),
            # speculative serving (ISSUE 9): identical keys to the solo
            # speculative path's registry reporting — all zero without a
            # draft model
            **self.spec.snapshot(),
        }
