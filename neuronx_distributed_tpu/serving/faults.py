"""Deterministic fault injection for the serving engine.

Chaos testing a donated, device-resident decode path needs failures that are
*schedulable*, not random: a dispatch that raises at exactly chunk ``k``, a
readback poisoned for exactly slot ``s``, a prefill that OOMs on exactly the
``n``-th admission, a clock that jumps mid-run. ``FaultInjector`` is a
passive schedule the engine consults at its four hook points; with no
injector (the default) every hook is a no-op and the hot path is untouched.

Injection points (all indices are 0-based and deterministic):

* ``fail_dispatch(at=k, times=t)`` — the k-th..(k+t-1)-th decode *dispatch
  attempts* raise ``InjectedDispatchError`` before the jitted chunk runs
  (the donated buffers are NOT consumed, mirroring a host-side enqueue
  failure). ``times=None`` fails every attempt from ``at`` on — the way to
  drive the engine into ``HALTED``.
* ``poison_readback(at=k, slot=s, token=v)`` — mutates the host token block
  of the k-th *successful* readback: slot ``s``'s first token becomes ``v``
  (out-of-vocab by default), modeling a corrupted device buffer. Neighbor
  slots' columns are untouched, so isolation is testable. If slot ``s`` is
  not active at readback ``k`` the poison DEFERS to the next readback
  (firing into an empty slot would prove nothing).
* ``fail_prefill(at=n, times=t)`` — the n-th prefill call raises
  ``InjectedPrefillError`` (an OOM-like admission failure).
* ``poison_prefix(at=k, times=t)`` — corrupts the STORED prefix-cache entry
  the k-th prefix *reuse attempt* is about to copy from (every float leaf of
  its KV block is perturbed), modeling silent corruption of host-managed
  prefix storage. The engine's reuse-time checksum/shape validation must
  evict the entry and fall back to a full prefill — poisoned KV must never
  reach a slot.
* ``poison_page(at=k, slot=s)`` — PAGED engines (``kv_page_size=``): at the
  k-th successful readback, the first pool page mapped by slot ``s``'s
  block table is declared poisoned. The engine must quarantine THE PAGE
  (retire it from the pool) and requeue exactly the requests whose tables
  map it — CoW sharers included, neighbors untouched, the slot index
  itself back in rotation. Defers like ``poison_readback`` when the slot
  is not active (or maps nothing) at that readback.
* ``skew_clock(by=s)`` / ``skew_clock(by=s, after=t)`` — the engine clock
  reads ``s`` seconds ahead (optionally only once real time passes
  ``after``), driving deadline/queue-timeout shedding paths without
  sleeping.
* ``fail_draft_dispatch(at=k, times=t)`` — the k-th speculative dispatch
  attempts raise ``InjectedDraftError`` before the fused draft–verify chunk
  runs; the engine decodes the affected chunk non-speculatively (streams
  bit-identical) and resyncs the draft cache.
* ``poison_draft(at=k, times=t)`` — the k-th speculative dispatches run
  with a corrupted COPY of the draft params (mid-chunk all-reject rounds:
  every proposal garbage); the stream must stay bit-identical regardless.
* ``fail_spill(at=k, times=t)`` — TIERED engines (``kv_host_pages=``): the
  k-th..(k+t-1)-th spill attempts raise ``InjectedSpillError`` before the
  device->host pull runs. The engine must degrade to plain eviction (the
  pre-tiering reclaim behavior) — never a leak, never a crash, streams
  untouched.
* ``fail_prefetch(at=k, times=t)`` — the k-th prefetch attempts raise
  ``InjectedPrefetchError`` before anything is written device-side. The
  engine must drop the host-tier entry and fall back to a full prefill,
  bit-identically (K/V is position-relative — re-prefilling the tokens
  rebuilds the same pages).
* ``poison_host_page(at=k, times=t)`` — the k-th prefetch attempts find
  their entry's FIRST host page corrupted in place (one byte flipped),
  modeling host-RAM bit rot. The store's fingerprint verification must
  reject the whole fetch and the engine must fall back to a full prefill
  — corrupted host bytes never reach the pool.
* ``flip_bits(target=..., at=k, times=t)`` — SILENT data corruption
  (ISSUE 20): flips ONE low-order bit, numerically near-invisible, so no
  loud guard (readback garbage, NaN logits) ever fires and only bit-level
  integrity fingerprints can catch it. ``target="params"`` corrupts the
  engine's bound weights at the start of the k-th..(k+t-1)-th ``step()``
  calls — the replica keeps serving plausibly-wrong tokens until the
  router's cross-replica fingerprint vote fences it. ``target="kv_pool"``
  corrupts the first pool page of the entry the k-th prefix *reuse
  attempt* maps (before validation) — the engine's per-page fingerprint
  check must reject the reuse and fall back to a full prefill.
* ``drop_send / drop_ack / dup_send / delay_send / partition`` — transport
  fault schedules consulted by ``serving/transport.ChaosTransport`` per
  delivery-attempt index (transport-wide monotone, so deterministic for a
  deterministic workload): drop the k-th send in flight, deliver the k-th
  send but lose its ack (forcing a retry into the idempotency cache),
  deliver the k-th send twice, delay it against its message deadline, or
  make a specific target unreachable for a window of sends.

``counters`` records every fault actually fired so chaos tests can assert
the schedule ran (an injection that never fired proves nothing).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple


class InjectedFault(RuntimeError):
    """Base class for injected failures (never raised by real code paths)."""


class InjectedDispatchError(InjectedFault):
    """Scheduled decode-dispatch failure."""


class InjectedDraftError(InjectedFault):
    """Scheduled SPECULATIVE decode-dispatch failure (the draft side of a
    fused draft–verify chunk): the engine must fall back to non-speculative
    decode for the affected chunk, streams bit-identical."""


class InjectedPrefillError(InjectedFault):
    """Scheduled prefill failure (OOM-like admission fault)."""


class InjectedHandoffError(InjectedFault):
    """Scheduled DISAGGREGATED handoff failure (ISSUE 14): the page-table
    transfer between a prefill worker and the decode engine fails — the
    disaggregation server must fall back to coupled prefill on the decode
    engine, streams bit-identical, zero tokens lost."""


class InjectedSpillError(InjectedFault):
    """Scheduled KV spill failure (ISSUE 19): the device->host pull of a
    cold prefix entry's pages fails — the engine must degrade to plain
    eviction, never a leak or a crash."""


class InjectedPrefetchError(InjectedFault):
    """Scheduled KV prefetch failure (ISSUE 19): the host->device re-home
    of a spilled prefix entry fails — the engine must drop the host copy
    and fall back to a full prefill, bit-identically."""


class FaultInjector:
    """Schedule-driven fault source consulted by ``ServingEngine`` hooks."""

    def __init__(self):
        # [at, end) half-open attempt windows; end=None → open-ended
        self._dispatch_windows: List[Tuple[int, Optional[int]]] = []
        self._poisons: Dict[int, List[Tuple[int, int]]] = {}  # readback -> [(slot, token)]
        self._prefill_windows: List[Tuple[int, Optional[int]]] = []
        self._prefix_windows: List[Tuple[int, Optional[int]]] = []
        self._draft_dispatch_windows: List[Tuple[int, Optional[int]]] = []
        self._draft_poison_windows: List[Tuple[int, Optional[int]]] = []
        self._handoff_windows: List[Tuple[int, Optional[int]]] = []
        self._page_poisons: Dict[int, List[int]] = {}  # readback -> [slot]
        # tiered KV (ISSUE 19), keyed by spill / prefetch attempt index
        self._spill_windows: List[Tuple[int, Optional[int]]] = []
        self._prefetch_windows: List[Tuple[int, Optional[int]]] = []
        self._host_page_windows: List[Tuple[int, Optional[int]]] = []
        # silent bit flips (ISSUE 20): params keyed by engine step index,
        # kv_pool keyed by prefix reuse-attempt index
        self._params_flip_windows: List[Tuple[int, Optional[int]]] = []
        self._pool_flip_windows: List[Tuple[int, Optional[int]]] = []
        # transport fault schedules, all keyed by delivery-attempt index
        self._send_drops: List[Tuple[int, Optional[int]]] = []
        self._ack_drops: List[Tuple[int, Optional[int]]] = []
        self._send_dups: List[Tuple[int, Optional[int]]] = []
        self._send_delays: List[Tuple[int, Optional[int], float]] = []
        self._partitions: Dict[object, List[Tuple[int, Optional[int]]]] = {}
        self._skew: float = 0.0
        self._skew_after: Optional[float] = None
        self.counters: Dict[str, int] = {
            "dispatch_failures": 0,
            "poisoned_readbacks": 0,
            "prefill_failures": 0,
            "poisoned_prefixes": 0,
            "draft_dispatch_failures": 0,
            "poisoned_drafts": 0,
            "poisoned_pages": 0,
            "handoff_failures": 0,
            "spill_failures": 0,
            "prefetch_failures": 0,
            "poisoned_host_pages": 0,
            "bit_flips": 0,
            "dropped_sends": 0,
            "dropped_acks": 0,
            "dup_sends": 0,
            "delayed_sends": 0,
            "partitioned_sends": 0,
        }

    # --- schedule construction ----------------------------------------------

    def fail_dispatch(self, at: int = 0, times: Optional[int] = 1) -> "FaultInjector":
        end = None if times is None else at + times
        self._dispatch_windows.append((at, end))
        return self

    def poison_readback(self, at: int, slot: int, token: int = -1) -> "FaultInjector":
        self._poisons.setdefault(at, []).append((slot, token))
        return self

    def fail_prefill(self, at: int = 0, times: Optional[int] = 1) -> "FaultInjector":
        end = None if times is None else at + times
        self._prefill_windows.append((at, end))
        return self

    def poison_prefix(self, at: int = 0, times: Optional[int] = 1) -> "FaultInjector":
        end = None if times is None else at + times
        self._prefix_windows.append((at, end))
        return self

    def fail_draft_dispatch(
        self, at: int = 0, times: Optional[int] = 1
    ) -> "FaultInjector":
        """The ``at``-th..(at+times-1)-th SPECULATIVE dispatch attempts
        raise :class:`InjectedDraftError` before the fused draft–verify
        chunk runs (donated buffers unconsumed, mirroring a host-side
        enqueue failure on the draft program). The engine must decode the
        affected chunk NON-speculatively — streams bit-identical, zero
        tokens lost — then resync the draft cache."""
        end = None if times is None else at + times
        self._draft_dispatch_windows.append((at, end))
        return self

    def poison_draft(
        self, at: int = 0, times: Optional[int] = 1
    ) -> "FaultInjector":
        """Corrupt the DRAFT params the ``at``-th..(at+times-1)-th
        speculative dispatches use (every float leaf perturbed on a copy —
        the engine's bound pytree is untouched), driving mid-chunk
        all-reject rounds: every proposal garbage, every round emitting
        only its correction. The test this exists for: the stream must
        stay bit-identical anyway (speculation's output never depends on
        draft quality)."""
        end = None if times is None else at + times
        self._draft_poison_windows.append((at, end))
        return self

    def poison_page(self, at: int = 0, times: int = 1,
                    slot: int = 0) -> "FaultInjector":
        """At the ``at``-th..(at+times-1)-th successful readbacks of a
        PAGED engine, poison the first pool page slot ``slot``'s block
        table maps — modeling one corrupted HBM page. The engine's
        page-granular quarantine must retire the page and requeue exactly
        the requests mapping it (bit-identically), nothing else."""
        for i in range(times):
            self._page_poisons.setdefault(at + i, []).append(slot)
        return self

    def fail_handoff(self, at: int = 0,
                     times: Optional[int] = 1) -> "FaultInjector":
        """The ``at``-th..(at+times-1)-th disaggregated HANDOFF attempts
        raise :class:`InjectedHandoffError` before the page-table transfer
        binds a slot (nothing half-mapped — the staged context survives
        for the server to release). The server must fall back to coupled
        prefill on the decode engine for the affected request; streams
        stay bit-identical and ``tokens_lost == 0``."""
        end = None if times is None else at + times
        self._handoff_windows.append((at, end))
        return self

    def on_handoff(self, attempt: int) -> None:
        """Called by the disaggregation server with the 0-based handoff
        attempt index before ``admit_staged`` runs."""
        if self._hit(self._handoff_windows, attempt):
            self.counters["handoff_failures"] += 1
            raise InjectedHandoffError(
                f"injected handoff failure at attempt {attempt}"
            )

    def fail_spill(self, at: int = 0,
                   times: Optional[int] = 1) -> "FaultInjector":
        """The ``at``-th..(at+times-1)-th KV SPILL attempts (ISSUE 19)
        raise :class:`InjectedSpillError` before the device->host pull —
        nothing leaves the pool, the entry's pins are intact. The engine
        must degrade to plain eviction: pins released, pages freed,
        ``check()`` clean, streams untouched."""
        end = None if times is None else at + times
        self._spill_windows.append((at, end))
        return self

    def fail_prefetch(self, at: int = 0,
                      times: Optional[int] = 1) -> "FaultInjector":
        """The ``at``-th..(at+times-1)-th KV PREFETCH attempts (ISSUE 19)
        raise :class:`InjectedPrefetchError` before any device write. The
        engine must drop the host-tier entry (host pages released) and
        serve the request through a full prefill — bit-identical, zero
        tokens lost."""
        end = None if times is None else at + times
        self._prefetch_windows.append((at, end))
        return self

    def poison_host_page(self, at: int = 0,
                         times: Optional[int] = 1) -> "FaultInjector":
        """Corrupt one byte of the FIRST host page the ``at``-th..
        (at+times-1)-th prefetch attempts are about to fetch (ISSUE 19) —
        host-RAM bit rot, injected through the store's own ``corrupt``.
        The fingerprint verification must reject the whole fetch and the
        engine must fall back to a full prefill: corrupted host bytes
        never reach the pool."""
        end = None if times is None else at + times
        self._host_page_windows.append((at, end))
        return self

    def flip_bits(self, target: str, at: int = 0,
                  times: Optional[int] = 1) -> "FaultInjector":
        """Schedule single-bit SILENT corruption (ISSUE 20). ``target`` is
        ``params`` (flip one low-order bit of the engine's bound weights
        at the ``at``-th..(at+times-1)-th ``step()`` calls — the
        router-probe/fence path's model) or ``kv_pool`` (flip one bit of
        the first pool page the ``at``-th prefix reuse attempts map,
        BEFORE validation — the per-page fingerprint check's model)."""
        end = None if times is None else at + times
        if target == "params":
            self._params_flip_windows.append((at, end))
        elif target == "kv_pool":
            self._pool_flip_windows.append((at, end))
        else:
            raise ValueError(
                f"flip_bits target must be params|kv_pool, got {target!r}"
            )
        return self

    def on_spill(self, attempt: int) -> None:
        """Called by TIERED engines with the 0-based spill attempt index
        before the device->host pull."""
        if self._hit(self._spill_windows, attempt):
            self.counters["spill_failures"] += 1
            raise InjectedSpillError(
                f"injected spill failure at attempt {attempt}"
            )

    def on_prefetch(self, attempt: int, store=None, host_ids=()) -> None:
        """Called with the 0-based prefetch attempt index, the host store
        and the host ids about to be fetched, BEFORE verification. A
        scheduled ``poison_host_page`` corrupts the first page in place
        (the fingerprint check downstream must catch it); a scheduled
        ``fail_prefetch`` raises."""
        if self._hit(self._host_page_windows, attempt):
            if store is not None and host_ids:
                store.corrupt(host_ids[0])
                self.counters["poisoned_host_pages"] += 1
        if self._hit(self._prefetch_windows, attempt):
            self.counters["prefetch_failures"] += 1
            raise InjectedPrefetchError(
                f"injected prefetch failure at attempt {attempt}"
            )

    def drop_send(self, at: int = 0, times: Optional[int] = 1) -> "FaultInjector":
        """The ``at``-th..(at+times-1)-th transport delivery attempts are
        dropped in flight (``TransportError`` before the target runs —
        nothing delivered, the sender's retry delivers fresh)."""
        end = None if times is None else at + times
        self._send_drops.append((at, end))
        return self

    def drop_ack(self, at: int = 0, times: Optional[int] = 1) -> "FaultInjector":
        """The ``at``-th.. transport deliveries RUN at the target but their
        replies are lost — the sender retries a message that already
        executed, which MUST land in the transport's idempotency cache.
        This is the schedule that proves exactly-once admission."""
        end = None if times is None else at + times
        self._ack_drops.append((at, end))
        return self

    def dup_send(self, at: int = 0, times: Optional[int] = 1) -> "FaultInjector":
        """The ``at``-th.. transport deliveries arrive TWICE; the second
        copy must be absorbed by the idempotency cache, never the app."""
        end = None if times is None else at + times
        self._send_dups.append((at, end))
        return self

    def delay_send(self, at: int = 0, times: Optional[int] = 1,
                   by: float = 1.0) -> "FaultInjector":
        """The ``at``-th.. transport deliveries are delayed ``by`` seconds
        — a delay past the message's deadline becomes a terminal
        ``TransportTimeout`` (probes use this to go unanswered)."""
        end = None if times is None else at + times
        self._send_delays.append((at, end, by))
        return self

    def partition(self, target, at: int = 0,
                  times: Optional[int] = None) -> "FaultInjector":
        """Make ``target`` (a replica index or disagg address) unreachable
        for the ``at``-th.. delivery attempts — every send in the window
        fails with ``PartitionedError``. ``times=None`` partitions forever:
        the way to drive a live replica watchdog-DEAD."""
        end = None if times is None else at + times
        self._partitions.setdefault(target, []).append((at, end))
        return self

    def on_transport_send(self, send: int, target, op: str):
        """Called by ``ChaosTransport`` with the transport-wide 0-based
        delivery-attempt index, the target address and the op name.
        Returns a fault action tuple — ``("partition",)``, ``("drop",)``,
        ``("drop_ack",)``, ``("dup",)``, ``("delay", by)`` — or ``None``
        for a clean delivery. Partition wins over per-send faults (an
        unreachable target can't also deliver)."""
        windows = self._partitions.get(target)
        if windows is not None and self._hit(windows, send):
            self.counters["partitioned_sends"] += 1
            return ("partition",)
        if self._hit(self._send_drops, send):
            self.counters["dropped_sends"] += 1
            return ("drop",)
        if self._hit(self._ack_drops, send):
            self.counters["dropped_acks"] += 1
            return ("drop_ack",)
        if self._hit(self._send_dups, send):
            self.counters["dup_sends"] += 1
            return ("dup",)
        for at, end, by in self._send_delays:
            if send >= at and (end is None or send < end):
                self.counters["delayed_sends"] += 1
                return ("delay", by)
        return None

    def skew_clock(self, by: float, after: Optional[float] = None) -> "FaultInjector":
        self._skew = by
        self._skew_after = after
        return self

    # --- engine hooks --------------------------------------------------------

    @staticmethod
    def _hit(windows, index: int) -> bool:
        return any(
            index >= at and (end is None or index < end)
            for at, end in windows
        )

    def on_dispatch(self, attempt: int) -> None:
        """Called with the 0-based dispatch ATTEMPT index (failed attempts
        count, so a retry schedule is deterministic). Raises when the
        schedule says this attempt fails."""
        if self._hit(self._dispatch_windows, attempt):
            self.counters["dispatch_failures"] += 1
            raise InjectedDispatchError(
                f"injected dispatch failure at attempt {attempt}"
            )

    def on_readback(self, readback: int, toks, counts, active=None):
        """Called with the 0-based successful-readback index, the HOST
        copies of the chunk's token block ``(chunk, slots)`` and per-slot
        counts, and the active-slot mask. Returns the (possibly poisoned)
        pair. A poison whose slot is not active yet DEFERS to the next
        readback instead of firing into the void — the counter increments
        only when garbage actually lands where the engine must catch it,
        so asserting on it really proves the quarantine path ran."""
        deferred = []
        for slot, token in self._poisons.pop(readback, ()):
            if active is not None and not bool(active[slot]):
                deferred.append((slot, token))
                continue
            toks = toks.copy()
            counts = counts.copy()
            if counts[slot] <= 0:
                counts[slot] = 1  # a poisoned slot claims at least one token
            toks[0, slot] = token
            self.counters["poisoned_readbacks"] += 1
        if deferred:
            self._poisons.setdefault(readback + 1, []).extend(deferred)
        return toks, counts

    def on_spec_dispatch(self, attempt: int) -> None:
        """Called with the 0-based dispatch ATTEMPT index before a
        SPECULATIVE chunk dispatch (shares the attempt counter with
        ``on_dispatch``, so mixed schedules stay deterministic)."""
        if self._hit(self._draft_dispatch_windows, attempt):
            self.counters["draft_dispatch_failures"] += 1
            raise InjectedDraftError(
                f"injected draft dispatch failure at attempt {attempt}"
            )

    def on_spec_params(self, attempt: int, draft_params):
        """Called with the dispatch attempt index and the draft param
        pytree the speculative chunk is about to receive. When the poison
        schedule hits, returns a CORRUPTED COPY (every float leaf
        perturbed) — proposals become garbage and every round all-rejects;
        otherwise returns the tree untouched."""
        if not self._hit(self._draft_poison_windows, attempt):
            return draft_params
        import jax
        import jax.numpy as jnp

        def corrupt(leaf):
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                leaf.dtype, jnp.floating
            ):
                return -leaf + jnp.asarray(3.7, leaf.dtype)
            return leaf

        self.counters["poisoned_drafts"] += 1
        return jax.tree_util.tree_map(corrupt, draft_params)

    def on_spec_readback(self, readback: int, toks, counts, active=None):
        """Speculative edition of :meth:`on_readback`: the token block is
        ``(rounds, slots, gamma)`` and counts ``(rounds, slots)``. A
        scheduled poison lands in the victim slot's FIRST round (same
        defer-until-active contract)."""
        deferred = []
        for slot, token in self._poisons.pop(readback, ()):
            if active is not None and not bool(active[slot]):
                deferred.append((slot, token))
                continue
            toks = toks.copy()
            counts = counts.copy()
            if counts[0, slot] <= 0:
                counts[0, slot] = 1
            toks[0, slot, 0] = token
            self.counters["poisoned_readbacks"] += 1
        if deferred:
            self._poisons.setdefault(readback + 1, []).extend(deferred)
        return toks, counts

    def on_page_readback(self, readback: int, slot_pages, active=None):
        """Called by PAGED engines with the 0-based successful-readback
        index and ``slot_pages`` — a callable mapping a slot index to the
        pool page ids its block table maps. Returns the page ids the
        schedule poisons at this readback. A scheduled slot that is not
        active (or maps nothing) DEFERS to the next readback — the counter
        increments only when a real page is actually poisoned, so chaos
        tests asserting on it prove the quarantine path ran."""
        pages: List[int] = []
        deferred: List[int] = []
        for slot in self._page_poisons.pop(readback, ()):
            mapped = (
                slot_pages(slot)
                if active is None or bool(active[slot]) else []
            )
            if not mapped:
                deferred.append(slot)
                continue
            pages.append(int(mapped[0]))
            self.counters["poisoned_pages"] += 1
        if deferred:
            self._page_poisons.setdefault(readback + 1, []).extend(deferred)
        return pages

    def on_prefill(self, call: int) -> None:
        """Called with the 0-based prefill call index before the prefill
        dispatch."""
        if self._hit(self._prefill_windows, call):
            self.counters["prefill_failures"] += 1
            raise InjectedPrefillError(
                f"injected prefill failure at call {call} "
                "(RESOURCE_EXHAUSTED: out of memory)"
            )

    def on_engine_params(self, step: int, params):
        """Called with the 0-based engine ``step()`` index and the bound
        (sharded) params pytree. When a ``flip_bits("params")`` window
        hits, returns the tree with ONE low-order bit of its first leaf
        flipped on every device copy — numerically near-invisible SDC
        only a bit-level fingerprint probe can see; otherwise returns the
        tree untouched."""
        if not self._hit(self._params_flip_windows, step):
            return params
        from neuronx_distributed_tpu.integrity.chaos import flip_tree_bit

        self.counters["bit_flips"] += 1
        return flip_tree_bit(params)

    def on_prefix_reuse(self, reuse: int, entry, cache=None) -> None:
        """Called with the 0-based prefix REUSE-attempt index, the matched
        ``PrefixEntry`` the engine is about to reuse, and (paged engines)
        the cache manager — BEFORE validation. A scheduled
        ``poison_prefix`` corrupts a dense entry's stored KV block IN
        PLACE (every float leaf perturbed, shapes untouched) — so the
        test proves the engine's checksum validation catches silent data
        corruption, not a shape mismatch. A scheduled
        ``flip_bits("kv_pool")`` instead flips ONE bit inside the first
        pool page a PAGED entry maps — the per-page fingerprint check
        must reject the reuse."""
        if (
            cache is not None
            and getattr(entry, "page_ids", None)
            and self._hit(self._pool_flip_windows, reuse)
        ):
            self._flip_pool_page(cache, int(entry.page_ids[0]))
            self.counters["bit_flips"] += 1
        if not self._hit(self._prefix_windows, reuse):
            return
        if getattr(entry, "tree", None) is None:
            # paged CoW entry: no host-managed KV copy to corrupt — page
            # corruption is poison_page's / flip_bits("kv_pool")'s
            # territory
            return
        import jax
        import jax.numpy as jnp

        def corrupt(leaf):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf + jnp.asarray(1024.0, leaf.dtype)
            return leaf

        entry.tree = jax.tree_util.tree_map(corrupt, entry.tree)
        self.counters["poisoned_prefixes"] += 1

    @staticmethod
    def _flip_pool_page(cache, pid: int) -> None:
        """Flip one bit of pool page ``pid``'s content in the first
        page-carrying pool leaf (host round-trip, re-placed with the
        original sharding — HBM bit rot, modeled from the host)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from neuronx_distributed_tpu.integrity.chaos import flip_array_bit
        from neuronx_distributed_tpu.modules.attention import (
            PAGED_LEAVES,
            cache_leaf_name,
            pool_scale_base,
        )

        pool = cache.cache["pool"]
        flat, treedef = jax.tree_util.tree_flatten_with_path(pool)
        leaves = [leaf for _, leaf in flat]
        for i, (path, leaf) in enumerate(flat):
            name = cache_leaf_name(path)
            if (pool_scale_base(name) or name) not in PAGED_LEAVES:
                continue
            pax = leaf.ndim - 4
            host = np.array(jax.device_get(leaf))
            idx = (slice(None),) * pax + (pid,)
            host[idx] = flip_array_bit(host[idx])
            # jnp.copy forces an XLA-owned buffer: device_put of host
            # numpy can be zero-copy on CPU backends, and the pool is
            # about to be donated by the decode dispatch (see
            # integrity/chaos.flip_leaf_bit for the full story)
            leaves[i] = jnp.copy(jax.device_put(host, leaf.sharding))
            break
        cache.cache = dict(
            cache.cache,
            pool=jax.tree_util.tree_unflatten(treedef, leaves),
        )

    def now(self, real_now: float) -> float:
        """Clock hook: the engine's view of time, skewed per schedule."""
        if self._skew and (
            self._skew_after is None or real_now >= self._skew_after
        ):
            return real_now + self._skew
        return real_now
