"""Disaggregated prefill/decode serving (ISSUE 14).

A coupled engine runs prefill INSIDE its serving loop: at a chunk boundary
it admits a whole selection round, paying every prefill's wall before the
next decode chunk dispatches. Under bursty arrivals that is exactly when a
burst lands — steady-state decoders stall behind a queue of prompt
prefills, and TPOT p99 inflates with the arrival rate (not measured on
the chip: no cell runs a disaggregated server; the bound itself is pinned
by ``tests/serving/test_disagg.py``).

This module splits the two phases:

* :class:`PrefillWorker` owns the bucketed prefill programs — a dedicated
  worker (its own jitted programs, its own fault domain; on real hardware
  its own chips) that turns a queued request into a prefilled context.
* :class:`DisaggregatedServer` fronts a PAGED decode engine
  (``external_prefill=True`` — the engine never self-admits): it pulls
  queued requests, prefills them on workers (at most
  ``prefills_per_step`` per loop iteration, the knob that bounds how much
  prefill wall can ever sit between two decode chunks), and hands each
  finished context to the engine as a PAGE-TABLE handoff.

The handoff is the PR 9 payoff: the worker stages the context's K/V pages
directly in the decode engine's pool
(:meth:`~neuronx_distributed_tpu.serving.paging.PagedCacheManager.
stage_context`), and ``ServingEngine.admit_staged`` binds them to a slot by
block-table mapping plus one metadata program — zero KV bytes move,
``PageAllocator.copy_bytes`` stays 0 (acceptance-pinned). When prefill and
decode pools are DISTINCT (different hosts/meshes), the explicit
``export_pages()/import_pages()`` device transfer is the fallback, and the
copy is charged to ``copy_bytes`` — the accounting that proves the
shared-pool path moved nothing.

Fault contract (chaos-tested in tests/serving/test_disagg.py): a worker
whose prefill raises leaves the rotation and its request falls back to
COUPLED prefill on the decode engine (no workers left → the server flips
the engine back to self-admission entirely); a failed handoff
(``FaultInjector.fail_handoff``) releases the staged pages and falls back
the same way. Streams stay bit-identical in every case — the fallback is
the very program a coupled engine runs.

With a ``transport=`` (ISSUE 18) every prefill→decode interaction — the
handoff admit, the distinct-pool page export/import — becomes a message
on the elastic-fabric seam: retried under the transport's policy, deduped
by ``(rid, seq)`` so a duplicated handoff can never double-admit, and a
message the ChaosTransport drops lands in the SAME coupled-fallback path
an injected handoff failure does. The server also speaks the router's
replica surface (``adopt``/``fence``/``load_score``/…), so a
disaggregated server can sit behind a :class:`~neuronx_distributed_tpu.
serving.router.ReplicaRouter` and its watchdog; ``fence()`` first
releases pending staged pages and requeues their requests so the re-home
path sees every unfinished request in the queue.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax

from neuronx_distributed_tpu.inference.generate import (
    pack_padded_prompt,
    serving_clones,
)
from neuronx_distributed_tpu.inference.utils import unwrap_logits
from neuronx_distributed_tpu.serving.engine import ServingEngine, _bucket
from neuronx_distributed_tpu.serving.paging import (
    PagedCacheManager,
    StagedContext,
)
from neuronx_distributed_tpu.serving.scheduler import Request, RequestState


class PrefillWorker:
    """One dedicated prefill worker: the bucketed prefill programs for the
    decode engine's model, staging results into ``pool`` (the decode
    engine's pool on the shared-pool path; the worker's own pool on the
    distinct-pool path, where the server exports/imports)."""

    def __init__(self, model, params, pool: PagedCacheManager,
                 label: str = "prefill0", fault_injector=None,
                 programs=None):
        self.label = label
        self._prefill_model, _ = serving_clones(model)
        self._params = dict(params)
        self.pool = pool
        self._faults = fault_injector
        self._programs = programs
        self._fns: Dict[int, Callable] = {}
        self.calls = 0

    def _fn(self, padded_len: int):
        fn = self._fns.get(padded_len)
        if fn is None:
            prefill = self._prefill_model

            @jax.jit
            def fn(params, ids, mask):
                out, variables = prefill.apply(
                    params, ids, padding_mask=mask, mutable=["cache"]
                )
                return unwrap_logits(out)[0, -1], variables["cache"]

            if self._programs is not None:
                fn = self._programs.wrap(
                    f"{self.label}_prefill[{padded_len}]", fn
                )
            self._fns[padded_len] = fn
        return fn

    def prefill(self, req: Request, max_seq_len: int):
        """Run the bucketed prefill for ``req`` and stage the context in
        ``pool``. Returns ``(staged, logits)`` — the page-table handoff
        unit plus the last-token logits the decode side samples the first
        token from. Raises whatever the prefill raises (the server's
        worker-failure path)."""
        call = self.calls
        self.calls += 1
        if self._faults is not None:
            self._faults.on_prefill(call)
        ctx = req.context_ids
        p = len(ctx)
        padded = _bucket(p, max_seq_len, req.remaining_new_tokens)
        ids, mask = pack_padded_prompt(ctx, padded)
        import jax.numpy as jnp

        logits, row_cache = self._fn(padded)(
            self._params, jnp.asarray(ids), jnp.asarray(mask)
        )
        staged = self.pool.stage_context(row_cache, p, padded)
        return staged, logits

    @property
    def prefill_compilations(self) -> int:
        return sum(int(fn._cache_size()) for fn in self._fns.values())


class DisaggregatedServer:
    """Prefill/decode disaggregation facade over one paged decode engine.

    Drives the same ``submit()/step()/run()`` surface as the engine (and
    the traffic-replay harness: ``metrics``/``scheduler``/``_clock``
    forward), so coupled-vs-disaggregated comparisons swap one object."""

    def __init__(self, engine: ServingEngine, n_workers: int = 1,
                 prefills_per_step: int = 1, shared_pool: bool = True,
                 fault_injector=None, transport=None):
        if engine._page_size is None:
            raise ValueError(
                "disaggregation needs a PAGED decode engine "
                "(kv_page_size=) — the handoff is a block-table operation"
            )
        if engine.draft_model is not None:
            raise ValueError(
                "disaggregation does not speak speculative engines yet "
                "(the draft cache would need its own handoff)"
            )
        if getattr(engine.cache, "window", None) is not None or getattr(
                getattr(engine.model, "config", None), "kv_cache_slot_state", False):
            from neuronx_distributed_tpu.serving.paging import (
                CacheKindUnsupported,
            )

            raise CacheKindUnsupported(
                "disaggregation hands a context over by its pages, and a "
                "model with window layers frees a window layer's pages "
                "behind the window, a model with per-slot state keeps what "
                "the next token needs in no page: serve it coupled"
            )
        if n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {n_workers}")
        if prefills_per_step < 1:
            raise ValueError(
                f"prefills_per_step must be >= 1, got {prefills_per_step}"
            )
        self.engine = engine
        self.shared_pool = shared_pool
        self.prefills_per_step = prefills_per_step
        self._faults = fault_injector
        self.transport = transport
        engine.external_prefill = True
        self.workers: List[PrefillWorker] = []
        for i in range(n_workers):
            pool = engine.cache if shared_pool else PagedCacheManager(
                1, engine.max_seq_len, engine._page_size,
                engine.cache.alloc.num_pages,
            )
            self.workers.append(
                PrefillWorker(
                    engine.model, engine._params, pool,
                    label=f"prefill{i}", fault_injector=fault_injector,
                    programs=engine.programs,
                )
            )
        self._rotation = 0
        # completed prefills awaiting a chunk-boundary handoff
        self._pending: List[tuple] = []
        self._handoff_attempts = 0
        self.stats: Dict[str, int] = {
            "prefills": 0,
            "handoffs": 0,
            "handoff_failures": 0,
            "worker_failures": 0,
            "coupled_fallbacks": 0,
            "imported_contexts": 0,
        }

    # --- engine surface (traffic replay compatibility) ----------------------

    @property
    def metrics(self):
        return self.engine.metrics

    @property
    def scheduler(self):
        return self.engine.scheduler

    @property
    def _clock(self):
        return self.engine._clock

    def health(self):
        return self.engine.health()

    def submit(self, *args, **kwargs) -> Request:
        return self.engine.submit(*args, **kwargs)

    @property
    def has_work(self) -> bool:
        return bool(self._pending) or self.engine.has_work

    # --- router replica surface (ISSUE 18) ----------------------------------
    # a DisaggregatedServer can stand behind a ReplicaRouter: balancing,
    # affinity, watchdog probes, and re-homing all speak these

    @property
    def prefix(self):
        return self.engine.prefix

    @property
    def flight(self):
        return self.engine.flight

    @property
    def _on_token(self):
        return self.engine._on_token

    @property
    def _next_rid(self):
        return self.engine._next_rid

    def load_score(self, tenant: Optional[str] = None) -> float:
        return self.engine.load_score(tenant=tenant)

    def page_pressure(self) -> float:
        return self.engine.page_pressure()

    def adopt(self, req: Request, on_token=None) -> Request:
        return self.engine.adopt(req, on_token=on_token)

    def release_queued(self, rid: int):
        return self.engine.release_queued(rid)

    def drain(self) -> None:
        self.engine.drain()

    def resume(self) -> None:
        self.engine.resume()

    def fence(self, reason: str = "fenced") -> None:
        """Kill switch through the engine's halt contract, made
        handoff-aware: contexts prefilled but not yet admitted release
        their staged pages and their requests rejoin the queue FIRST, so
        the post-fence queue (what a router re-homes) holds every
        unfinished request — none marooned in ``_pending``."""
        pending, self._pending = self._pending, []
        for req, staged, _logits in pending:
            self._release(staged, self.engine.cache)
            if not req.finished:
                self.engine.scheduler.requeue_front([req])
        self.engine.fence(reason)

    # --- transport seam (ISSUE 18) ------------------------------------------

    def _send(self, target: str, op: str, fn, rid: int = -1):
        """Route one prefill/decode interaction over the fabric transport
        (retries + ``(rid, seq)`` dedup), or call directly when no
        transport is bound — bit-identical either way."""
        if self.transport is None:
            return fn()
        return self.transport.call(target, op, fn, rid=rid)

    # --- the serving loop ----------------------------------------------------

    def _coupled_fallback(self, req: Request) -> None:
        """Prefill ``req`` on the DECODE engine's own coupled path — the
        exact program a non-disaggregated engine runs, so the stream is
        bit-identical; no free slot right now just requeues it."""
        self.stats["coupled_fallbacks"] += 1
        if req.finished:
            return
        if self.engine.cache.free_slots == 0:
            self.engine.scheduler.requeue_front([req])
            return
        self.engine._prefill_into_slot(req, self.engine.cache.acquire())

    def _release(self, staged: Optional[StagedContext], pool) -> None:
        if staged is not None and staged.page_ids:
            pool.release_staged(staged)

    def _try_handoffs(self, now: float) -> None:
        still: List[tuple] = []
        for req, staged, logits in self._pending:
            if req.finished:  # cancelled/shed while pending
                self._release(staged, self.engine.cache)
                continue
            if req.deadline is not None and now >= req.deadline:
                self._release(staged, self.engine.cache)
                req.state = RequestState.TIMED_OUT
                req.error = "deadline exceeded awaiting handoff"
                req.finish_time = now
                self.engine.metrics.record_shed(req, now, where="queue")
                self.engine.tracer.end(
                    req.rid, "shed",
                    args={"where": "handoff", "tenant": req.tenant},
                )
                continue
            attempt = self._handoff_attempts
            self._handoff_attempts += 1
            try:
                if self._faults is not None:
                    self._faults.on_handoff(attempt)
                admitted = self._send(
                    "decode", "handoff",
                    lambda r=req, s=staged, lg=logits:
                        self.engine.admit_staged(r, s, lg, now),
                    rid=req.rid,
                )
            except Exception:
                # injected handoff failure, an undeliverable handoff
                # message (transport gave up), or a staged context voided
                # by pool recovery/page quarantine: nothing is half-mapped
                # — release the pages and fall back to coupled prefill
                self.stats["handoff_failures"] += 1
                self._release(staged, self.engine.cache)
                self._coupled_fallback(req)
                continue
            if admitted:
                self.stats["handoffs"] += 1
            else:
                still.append((req, staged, logits))
        self._pending = still

    def _run_prefills(self, now: float) -> None:
        if not self.workers:
            return
        budget = self.prefills_per_step
        while budget > 0 and self.engine.scheduler.queued > 0:
            pending_tokens = sum(
                r.token_footprint for r, _, _ in self._pending
            )
            selected = self.engine.scheduler.select(
                1,
                self.engine._in_flight_tokens() + pending_tokens,
                fits=None,
            )
            if not selected:
                break
            req = selected[0]
            budget -= 1
            worker = self.workers[self._rotation % len(self.workers)]
            self._rotation += 1
            try:
                staged, logits = worker.prefill(req, self.engine.max_seq_len)
            except Exception:
                # the worker is now suspect: pull it from the rotation and
                # serve this request through the coupled path. Losing the
                # last worker flips the engine back to full self-admission
                # — disaggregation degrades to a coupled engine, never to
                # an outage
                self.stats["worker_failures"] += 1
                try:
                    self.workers.remove(worker)
                except ValueError:
                    pass
                if not self.workers:
                    self.engine.external_prefill = False
                self._coupled_fallback(req)
                continue
            if not self.shared_pool:
                # distinct pools: explicit device transfer — charged to
                # the decode pool's copy_bytes, unlike the shared path
                # which moves nothing. Transfer failures are HANDOFF
                # failures, never worker failures: a transient
                # PageExhausted on the decode pool must not dismantle a
                # healthy worker — this request just prefills coupled
                # (whose own page-pressure machinery absorbs it)
                try:
                    exported = self._send(
                        "prefill", "page_export",
                        lambda w=worker, s=staged: w.pool.export_pages(s),
                        rid=req.rid,
                    )
                except Exception:
                    self._release(staged, worker.pool)
                    self.stats["handoff_failures"] += 1
                    self._coupled_fallback(req)
                    continue
                self._release(staged, worker.pool)
                try:
                    if self.engine.cache.cache is None:
                        self.engine.cache.allocate_like(worker.pool)
                    staged = self._send(
                        "decode", "page_import",
                        lambda e=exported: self.engine.cache.import_pages(e),
                        rid=req.rid,
                    )
                except Exception:
                    self.stats["handoff_failures"] += 1
                    self._coupled_fallback(req)
                    continue
                self.stats["imported_contexts"] += 1
            self.stats["prefills"] += 1
            self._pending.append((req, staged, logits))

    def step(self) -> bool:
        """One disaggregated iteration: bind completed handoffs (cheap
        page-table ops), run the decode engine's step (its chunk never
        waits on a prefill), then run at most ``prefills_per_step`` worker
        prefills — the bound on prefill wall between chunks that a coupled
        engine does not have."""
        now = self.engine._now()
        self._try_handoffs(now)
        self.engine.step()
        self._run_prefills(self.engine._now())
        return self.has_work

    def run(self, max_steps: int = 1_000_000) -> Dict[int, Request]:
        steps = 0
        while self.has_work and steps < max_steps:
            self.step()
            steps += 1
        return {
            r.rid: r for r in self.engine.scheduler.requests.values()
        }

    def snapshot(self) -> dict:
        return {
            "disagg": {
                **self.stats,
                "workers_live": len(self.workers),
                "pending_handoffs": len(self._pending),
                "copy_bytes": self.engine.cache.alloc.copy_bytes,
            },
            "engine": self.engine.metrics.snapshot(),
        }
