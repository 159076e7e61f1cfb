"""Continuous-batching serving engine.

``ServingEngine`` turns the repo's batch ``generate()`` math into a
request-level server: a host-side loop interleaves prefill of admitted
requests with ONE jitted fixed-shape decode program over all ``num_slots``
slots. The decode program's shapes never change — cache ``(num_slots,
max_seq_len)``, per-slot token/key/config arrays — so XLA compiles it exactly
once and every request, whatever its arrival time, length, or sampling
config, flows through the same program (``decode_compilations`` asserts
this). Prefill compiles once per padded-length bucket (powers of two,
``prefill_compilations`` / the bucket map bound it), the standard serving
trade.

Decode hot path — device-resident between admission events:

* All per-slot state (pending token, PRNG key, active mask, sampling
  sentinels, remaining-token budget, EOS id) lives ON DEVICE in
  ``self._state``; the jitted step updates it in place. The host writes a
  slot's row only when admission/free/cancel dirties it (one tiny jitted
  scatter per event). Each chunk's single readback carries a COPIED
  post-chunk key snapshot, and the engine mirrors it into every active
  request's ``req.key`` — so preemption, recovery, and retirement all run
  off host state, with zero extra device syncs (``device_get`` on the state
  leaf itself would cache a host value and silently demote the next chunk's
  donation to a copy — regression-tested).
* The KV cache and the slot-state dict are DONATED into the decode jit
  (``donate_argnums``): XLA aliases the buffers instead of copying the
  ``(num_slots, max_seq_len)`` cache pytree every token.
* ``decode_chunk_size`` (default 8; ``1`` reproduces a per-token loop)
  decode steps fuse into one jitted ``lax.scan``
  (:func:`~neuronx_distributed_tpu.inference.generate.chunked_decode_step`,
  shared with the one-shot ``generate`` module): per-slot EOS/budget
  freezing happens on device via the decode write mask, and the host pays
  ONE synchronization per chunk — a ``(chunk, num_slots)`` token block plus
  per-slot counts. Chunk boundaries are the admission/cancellation points,
  so larger chunks trade a little TTFT/cancel latency for per-token host
  overhead amortized ``chunk``-fold.
* RUN AHEAD BY ONE CHUNK: chunk N+1 needs nothing of chunk N's readback to
  be CALLED (it takes N's output cache and state as they are, not yet
  computed, donated as ever), so the engine calls N+1 first, only then
  blocks on N's readback, and emits N's tokens while the device runs N+1:
  still one sync a chunk and one chunk read back a ``step()``, at most TWO
  chunks on the device, the host's bookkeeping ahead of the device by one
  chunk. It does so only when the boundary after N can bring the host
  nothing that N+1 would have to wait for, decided at the moment of the
  call from what the engine observes (``_can_run_ahead``; no option sets
  it): every usable slot held and no slot's budget ending inside N (so no
  admission can happen there: the host knows every budget); no draft
  model, no armed fault injector, health ``OK`` (every recovery,
  quarantine and page-poison path keeps its one-chunk-at-a-time order); no
  handoff from outside, host tier or preempting policy; room under the
  cursor for both write windows, and a pool that backs N+1's at the
  projected cursor. Otherwise the step is one chunk at a time to the
  letter: an open loop with a slot free never runs ahead, and an arriving
  request waits for the running chunk at most. What the rule cannot see,
  an EOS, an ``on_token`` cancel or a deadline found at N's emit, is
  honoured there as ever; the slot rides N+1 frozen (the EOS: the device
  froze it in N) or its N+1 tokens are discarded as the rest of a block is,
  and the slot is found free ONE CHUNK LATE: the bounded cost, counted
  (``serving_decode_late_found_ends``). While a chunk is unread a step does
  nothing at the boundary but retire cancels and deadlines and read it
  back; admission, preemption and the walls wait for the step that finds
  nothing unread. Streams are bit for bit those of one chunk at a time:
  same program, same inputs, same order of device work for every slot that
  still emits.

Prefix-cache KV reuse — the admission-path optimization for shared-prompt
traffic (system prompts, few-shot templates, multi-turn histories):

* A host-managed :class:`~neuronx_distributed_tpu.serving.cache_manager.
  PrefixCache` (token trie, ref-counted entries, LRU eviction) stores
  compact COPIES of previously-prefilled context KV. On admission the
  engine looks up the longest stored prefix of the incoming context
  (capped at ``context - 1``), validates the entry (shape + fingerprint —
  a corrupted entry is evicted and the admission falls back to the full
  prefill), seeds a fresh cache row from it, and prefills ONLY the
  uncached tail through the decode-mode cache-write path at an explicit
  start cursor (``suffix_prefill_step``): QKV/MLP compute for ``s``
  suffix tokens instead of the whole prompt. Misses insert the admitted
  context so the next shared-prefix request hits.
* Alignment is the load-bearing invariant: the seeded row reproduces the
  exact left-padded layout a full prefill of the same context would build
  (prefix at ``[padded - p, padded - s)``, suffix written at
  ``padded - s``, RoPE continuing at the prefix's valid count), so slot
  roll-in, cursor arithmetic, and every later decode step are unchanged —
  token streams stay bit-identical to the cache-off path across hit /
  miss / partial-match / eviction / preemption-resume patterns.
* Donation safety: entries are extracted as fresh copies BEFORE the row
  enters the donating admit program and are pinned (ref-counted) while a
  suffix prefill is in flight — eviction never frees an entry backing an
  admission, and no stored buffer is ever aliased into a donated pytree.
  A weight swap (``engine.params = ...``) clears the store (old-weight KV
  must not serve new-weight traffic); recovery/halt drop any in-flight
  pins. ``prefix_cache=None`` (or size 0) disables everything and
  restores the exact legacy admission path.

Speculative decoding (ISSUE 9) — the decode path's multi-token transport:

* With ``draft_model=``/``draft_params=`` bound, each decode chunk runs
  ``decode_chunk_size`` fused draft–verify ROUNDS
  (:func:`~neuronx_distributed_tpu.inference.spec_decode.
  speculative_decode_chunk`): the draft proposes ``gamma`` tokens through
  its own donated cache, the target verifies the window in one forward,
  and each slot accepts its own longest matching prefix + correction —
  1..gamma tokens per slot per round, still ONE ``device_get`` per chunk.
* Per-slot variable advance rides the validity machinery: both caches
  write each round's window at the shared cursor, acceptance invalidates
  each row's rejected suffix, and per-row valid counts make the gap
  columns invisible — one fixed-shape program for every acceptance
  pattern (``decode_compilations`` stays 1).
* The draft side mirrors the slot lifecycle 1:1: a second
  ``SlotCacheManager`` (admit at the same cursor, free/quarantine/
  recover/reset in lockstep), per-bucket draft prefill programs (the
  draft always full-prefills — prefix-cache hits compose on the target
  side only), and the same donation regime.
* Greedy streams are bit-identical to the spec-off engine and to solo
  ``generate()``/``speculative_generate``; sampled slots emit one
  exactly-sampled token per round (same key evolution), also
  bit-identical. ``draft_model=None`` is byte-for-byte today's engine.
* A failed speculative dispatch with live buffers decodes that chunk
  non-speculatively (the exact spec-off program — zero tokens lost),
  then preempts to resync the draft cache; consumed buffers take the
  bounded recovery/HALT path. Speculation consumes ``gamma`` columns per
  round whatever it accepts, so poor acceptance reaches the
  preempt-and-rewind wall earlier — admission stays token-optimistic and
  preemption keeps streams exact.

Token-stream fidelity: a request served through the engine produces EXACTLY
the tokens of a solo ``generate(prompt, key)`` call — same prefill math
(left-padded prompts are already proven token-identical to unpadded ones),
same per-step key evolution (``split`` then sample with the sub-key), and a
per-row sampler that is bit-identical to ``sample`` (utils/sampling.py) —
for every ``decode_chunk_size``, including across preemption/resume AND
across dispatch-failure recovery. The engine is a scheduler around the same
program, not a different generator.

Fault tolerance — the contract that makes the donated hot path safe to run
unattended (chaos-tested in ``tests/serving/test_faults.py``):

* **Deadlines & shedding** — ``submit(..., deadline_s=, queue_timeout_s=)``
  attaches absolute deadlines; queue-expired requests are shed (terminal
  ``TIMED_OUT``) before prefill ever runs, and in-flight deadlines are
  enforced at chunk boundaries (the natural host-visibility points of the
  fused decode path). A shed request keeps the tokens it already streamed.
* **Dispatch recovery** — a failed donated decode dispatch no longer
  crashes the host loop: the engine restores/salvages the cache, requeues
  every in-flight request through the preemption machinery (their streams
  resume bit-identically — tokens and keys are host-current at every chunk
  boundary), waits per the shared decrementing-jitter
  :class:`~neuronx_distributed_tpu.utils.retry.RetryPolicy`, and retries on
  the next step. ``dispatch_retry.max_attempts`` CONSECUTIVE failures land
  the engine in ``HALTED`` with the work requeued, not lost.
* **Output validation & quarantine** — the per-chunk readback is validated
  on host (vocab-range tokens, sane counts); a poisoned slot is quarantined
  out of the rotation permanently, its request requeued from the last chunk
  boundary (or failed, under ``quarantine_policy="fail"``), and its
  neighbors' streams are untouched. Losing slots degrades capacity
  (``DEGRADED``); losing all of them halts.
* **Backpressure, drain & health** — ``max_queue`` bounds the queue with an
  explicit :class:`RejectedError` (carrying the depth), ``drain()`` stops
  admission while finishing in-flight work, and ``health()`` reports
  ``OK/DEGRADED/DRAINING/HALTED``; every fault shows up in
  ``metrics.snapshot()`` (sheds/rejects/quarantines/dispatch_retries/
  recoveries/health) and as Timeline instant events.
* **Fault injection** — every recovery path above is drivable
  deterministically through a
  :class:`~neuronx_distributed_tpu.serving.faults.FaultInjector` hook
  (dispatch raise at attempt k, poisoned readback for slot s, prefill
  OOM-like error, clock skew); with no injector the hooks are no-ops.

Observability (ISSUE 8 — zero syncs added; the pinned budgets above hold
with everything enabled):

* Metrics live in a shared ``MetricsRegistry`` (``metrics.registry``,
  injectable via ``registry=``) with log-bucketed TTFT/TPOT/prefill
  histograms and Prometheus/JSON export.
* With a ``timeline``, every request emits a connected Perfetto flow
  (submit → admission → prefix lookup → prefill → first token → decode
  chunks → retire/shed/quarantine/recovery) via ``self.tracer``; the
  timeline auto-saves (atomically) on halt.
* ``self.flight`` (a ``FlightRecorder``, ``flight_dir=`` for the dump
  location) records health transitions and fault events and writes a
  redacted JSON post-mortem the moment the engine HALTs — including, under
  multi-tenant load, per-tenant queue depths and the SLO attainment state
  (who was being starved when it died).
* Every phase of ``step()`` is a ``nxd.step*`` span
  (``observability.tracing.span``): recorded on the profiler's clock while a
  ``jax.profiler`` session is open (``observability.profile_window`` around
  the run), on the ``timeline`` if the engine has one, and, whenever the
  flight recorder is on, in its step ledger (``self.flight.steps``): one
  record a ``step()`` all run long (each phase's wall, the prefills'
  buckets, CPU time), a verdict per step against the running medians of
  what it is made of, and for a step that OVERRAN (seconds where a chunk
  takes a tenth) one ``slow_step`` flight event and one warning line that
  say which phase it sat in and where a watchdog thread found the stepping
  thread's stack. With none of the three a span costs under a microsecond
  and no sync.
* Device efficiency (ISSUE 12): every jitted program the engine (and its
  cache/paging managers) dispatches registers in ``self.programs`` — a
  :class:`~neuronx_distributed_tpu.observability.programs.ProgramLedger`
  recording dispatch counts, compile wall, compiler-reported FLOPs/bytes
  (cost analysis runs lazily at export, never on the hot path) and
  per-chunk roofline telemetry off the walls the loop already measures;
  ``self.hbm`` (:class:`~neuronx_distributed_tpu.observability.hbm.
  HBMLedger`) reconciles the engine's static residents (params, KV pool,
  draft cache, slot state, prefix store) against device limits and
  answers capacity questions (``hbm.plan()``). Both ride
  ``metrics.snapshot()["programs"]``/``["hbm"]`` and the halt
  post-mortem; backend gaps degrade to explicit ``"unavailable"``.
* SLO observability (ISSUE 11): ``submit(..., tenant=, priority=)``
  attributes every request (per-tenant TTFT/TPOT/queue-wait histogram
  families, shed/timeout/reject counters, tenant-tagged flows and flight
  events); ``slo=`` (``SLOSpec`` or ``{tenant: SLOSpec}``) classifies each
  request once at its terminal state and reports attainment + goodput per
  tenant; ``engine_label=`` lets N engines share one registry as labeled
  families. ``serving/traffic.py`` replays seeded multi-tenant load
  through the engine on a virtual clock for reproducible SLO reports.

Multi-chip serving (ISSUE 14): ``tp=``/``mesh=`` shards this whole engine
over a TP mesh — params by their ``nn.Partitioned`` axis rules (the T5X
partitioner pattern, ``parallel/sharding.py``), KV storage on the kv-head
axis, slot state replicated — with every guarantee above intact: one
decode program, the same host-sync budgets (the chunk readback is
replicated scalars/tokens, never sharded KV), and streams bit-identical
to the mesh-free engine. ``tp_comms=`` optionally routes the row-parallel
all-reduces through the EQuARX int8 ring; ``paged_attention="fused"``
streams paged attention straight off the pool pages on TPU. N engines
scale out behind ``serving/router.py``'s ReplicaRouter, and
``serving/disagg.py`` splits prefill from decode with zero-copy
page-table handoffs (``admit_staged``).

Cache capacity: all slots share one write cursor (see
``serving/cache_manager.py``), which advances every decode step while ANY
slot is active. The fused chunk clamps itself against ``max_seq_len`` on
device and stops advancing once every slot froze, so the cursor lands
exactly where ``used`` single steps would have left it. The HOST's cursor
and pages may run one chunk ahead of the device, which runs in order: a
chunk's output cache is the manager's from its call, and where the next
chunk is called before its readback its columns are PROJECTED at the
chunk's size (the next window's pages dealt and uploaded, a window kind's
pages freed behind, on that projection) and settled when ``used`` arrives
(short only when every slot froze, and then the chunk called ahead executes
no step: the device's cursor is right by construction). Every device
operation the host enqueues on the strength of bookkeeping it did early (a
freed page dealt again, the validity clear of a retired slot, an admit's
row copy) is enqueued AFTER the chunk called ahead and so runs after it.
Admission guards against running past ``max_seq_len``:

* ``admission="conservative"`` (default) — admit only when the request's
  whole remaining generation fits under the cursor; requests queue
  otherwise, and the cursor rewinds whenever the engine drains.
* ``admission="eager"`` — admit whenever the prefill itself fits; when the
  cursor hits the wall the engine preempts every active request (their
  progress is kept), rewinds the cache, and resumes them by re-prefilling
  their context — trading re-prefill compute for slot utilization.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import os
import re
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.inference.generate import (
    GenerationConfig,
    chunked_decode_step,
    pack_padded_prompt,
    serving_clones,
    sown_sums,
    suffix_prefill_step,
    validate_generate_args,
)
from neuronx_distributed_tpu.inference.spec_decode import (
    speculative_decode_chunk,
)
from neuronx_distributed_tpu.inference.utils import unwrap_logits
from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.kernels.flash_attention import flash_tile_plan, group_tile_plan
from neuronx_distributed_tpu.modules.attention import (
    cache_token_bytes,
    cache_fingerprint,
    PAGED_LEAVES,
    SLOT_STATE_LEAVES,
    extract_cache_prefix,
    resolve_decode_impl,
    seed_cache_prefix,
    slot_state_bytes_per_layer,
)
from neuronx_distributed_tpu.observability.flight_recorder import FlightRecorder
from neuronx_distributed_tpu.observability.hbm import HBMLedger, tree_nbytes
from neuronx_distributed_tpu.observability.programs import (
    ProgramLedger,
    per_instance,
    weak_reader,
)
from neuronx_distributed_tpu.observability import tracing
from neuronx_distributed_tpu.observability.tracing import RequestTracer
from neuronx_distributed_tpu.serving.cache_manager import (
    PrefixCache,
    SlotCacheManager,
)
from neuronx_distributed_tpu.serving.paging import (
    PagedCacheManager,
    PageExhausted,
)
from neuronx_distributed_tpu.serving.tiering import HostPageStore
from neuronx_distributed_tpu.serving.metrics import ServingMetrics
from neuronx_distributed_tpu.serving.scheduler import (
    Request,
    RequestState,
    Scheduler,
)
from neuronx_distributed_tpu.utils.logger import get_logger
from neuronx_distributed_tpu.utils.retry import RetryPolicy
from neuronx_distributed_tpu.utils.sampling import sample_row

logger = get_logger(__name__)


class EngineHealth(enum.Enum):
    """Engine health snapshot (``ServingEngine.health()``).

    ``OK`` — serving normally. ``DEGRADED`` — serving, but a recent
    dispatch failure was recovered from or quarantines have shrunk slot
    capacity. ``DRAINING`` — finishing in-flight work, admitting nothing
    new. ``HALTED`` — consecutive dispatch failures (or total slot loss)
    exhausted the retry budget; in-flight work is requeued and the loop
    stops making progress until an operator intervenes."""

    OK = "ok"
    DEGRADED = "degraded"
    DRAINING = "draining"
    HALTED = "halted"


class RejectedError(RuntimeError):
    """A submission the engine refused (bounded queue backpressure, drain,
    or halt). ``queue_depth`` is the queue occupancy at rejection time so
    callers can implement load-aware retry/spillover."""

    def __init__(self, message: str, queue_depth: int = 0):
        super().__init__(message)
        self.queue_depth = queue_depth


def _key_data(key) -> np.ndarray:
    """Raw (2,) uint32 view of a PRNG key (typed or legacy)."""
    dt = getattr(key, "dtype", None)
    if dt is not None and jnp.issubdtype(dt, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    if isinstance(key, jax.Array):
        # graftlint: ok[GL02] submit-time key capture — one tiny read on
        # the admission API, not the decode loop; explicit so a
        # transfer-guarded run can tell it from an accidental sync
        key = jax.device_get(key)
    return np.asarray(key, np.uint32)


def _config_sentinels(cfg: GenerationConfig):
    """(temperature, top_k, top_p) with the traced-sampler sentinels:
    top_k<=0 and top_p>=1 disable the filters (sample_row's contract)."""
    return (
        np.float32(cfg.temperature),
        np.int32(cfg.top_k if cfg.top_k is not None else 0),
        np.float32(cfg.top_p if cfg.top_p is not None else 1.0),
    )


def _bucket(p: int, max_seq_len: int, remaining: int, floor: int = 8) -> int:
    """Padded prefill length for a p-token context: next power of two
    (compile-count control), clamped so the padded prompt still leaves room
    for the remaining generation — falling back to the exact length keeps
    every feasible request admittable at the cost of a per-length compile."""
    b = max(floor, 1 << max(p - 1, 0).bit_length())
    b = min(b, max_seq_len)
    if b < p or b + remaining > max_seq_len:
        # ... to the next multiple of 512 where that still fits: an odd
        # length leaves the flash kernel no block to tile by (a
        # 20,566-token prompt in a 32,768 row would get blocks of 2)
        b = -(-p // 512) * 512
        if b + remaining > max_seq_len:
            b = p
    return b


def _flash_tiles(padded: int, p: int, config=None) -> Dict[str, int]:
    """What the prefill's attention kernels do with a fresh ``p``-token prompt
    in its bucket, for the prefill span, by the kernels' own rule
    (``kernels/flash_attention.flash_tile_plan``, a head a layer that runs the
    flash forward): ``flash_steps`` the grid walks, ``flash_tiles`` whose body
    runs (``1 - flash_tiles / flash_steps``: how often a step runs nothing),
    ``flash_edge_tiles`` of them in the masked body, ``flash_needed_tiles``
    that hold a content row and a content key. The same four as ``masked_*``
    for a model with an indexer (``config.index_topk``: its prefills run the
    byte-masked forward) and as ``band_*`` for one with window layers
    (``config.kv_cache_window``), a KV head a layer
    (``group_tile_plan``). Nothing for a suffix prefill (it runs the decode
    path's attention) or for an exact-length fallback over 512 tokens, which
    the kernels tile in blocks of a few rows."""
    if p <= 0 or (padded > 512 and padded % 128):
        return {}
    plans = {"flash": flash_tile_plan(padded, p)}
    heads = getattr(config, "num_heads", None)
    if heads:
        group = heads // getattr(config, "num_kv_heads", heads)
        if getattr(config, "index_topk", None) is not None:
            plans["masked"] = group_tile_plan(padded, p, group)
        window = getattr(config, "kv_cache_window", None)
        if window is not None:
            plans["band"] = group_tile_plan(padded, p, group, window)
    return {f"{kernel}_{stat}": n for kernel, plan in plans.items()
            for stat, n in zip(("steps", "tiles", "edge_tiles", "needed_tiles"), plan)}


def _suffix_bucket(s: int, padded: int, max_seq_len: int) -> int:
    """Padded chunk length for an s-token suffix prefill: next power of two
    (one compiled suffix program per chunk bucket), falling back to the
    exact length whenever the padded chunk's writes — which start at
    ``padded - s``, the reused prefix's end — would run past the cache row
    (a clamped ``dynamic_update_slice`` would silently shift them onto the
    prefix). This is the reused-token side of the admission fits
    arithmetic: the prefix occupies its columns for free, so only the
    suffix chunk needs write room."""
    b = max(1, 1 << max(s - 1, 0).bit_length())
    if padded - s + b > max_seq_len:
        b = s
    return b


def _prefix_bucket(p: int, max_seq_len: int) -> int:
    """Storage bucket for a p-token prefix entry: next power of two clamped
    to the cache length (one compiled extract program per bucket)."""
    return min(max(1, 1 << max(p - 1, 0).bit_length()), max_seq_len)


def _validate_readback(toks, counts, chunk_size: int, vocab: Optional[int],
                       slots) -> Dict[int, str]:
    """Host-side sanity check of a chunk readback — the one-per-chunk sync
    is the only place device output is visible, so it is where a poisoned
    slot must be caught before its garbage reaches a stream. Returns
    ``{slot: reason}`` for every active slot whose token column fails the
    invariants (count within [0, chunk], token ids within [0, vocab))."""
    bad: Dict[int, str] = {}
    for slot in slots:
        slot = int(slot)
        c = int(counts[slot])
        if c < 0 or c > chunk_size:
            bad[slot] = f"token count {c} outside [0, {chunk_size}]"
            continue
        if c == 0:
            continue
        col = np.asarray(toks[:c, slot])
        if (col < 0).any() or (vocab is not None and (col >= vocab).any()):
            offender = col[
                (col < 0) | ((col >= vocab) if vocab is not None else False)
            ][0]
            bad[slot] = (
                f"token {int(offender)} outside vocab [0, {vocab})"
            )
    return bad


def _validate_spec_readback(toks, counts, gamma: int, vocab: Optional[int],
                            slots) -> Dict[int, str]:
    """Speculative edition of :func:`_validate_readback`: the token block
    is ``(rounds, slots, gamma)`` ragged by per-round counts. A slot is
    poisoned when any round's count leaves [0, gamma] or any EMITTED token
    leaves the vocab."""
    bad: Dict[int, str] = {}
    rounds = counts.shape[0]
    for slot in slots:
        slot = int(slot)
        for r in range(rounds):
            c = int(counts[r, slot])
            if c < 0 or c > gamma:
                bad[slot] = (
                    f"round {r} token count {c} outside [0, {gamma}]"
                )
                break
            if c == 0:
                continue
            col = np.asarray(toks[r, slot, :c])
            if (col < 0).any() or (
                vocab is not None and (col >= vocab).any()
            ):
                offender = col[
                    (col < 0) | ((col >= vocab) if vocab is not None else False)
                ][0]
                bad[slot] = (
                    f"round {r} token {int(offender)} outside vocab "
                    f"[0, {vocab})"
                )
                break
    return bad


def _slot_write(state, slot, tok, key, temp, topk, topp, remaining, eos):
    """One admission's device-side slot update. Every operand is a traced
    scalar/row, so slot churn reuses a single compiled program; jitted with
    the state donated — the update happens in place."""
    return dict(
        state,
        tok=state["tok"].at[slot].set(tok),
        keys=state["keys"].at[slot].set(key),
        active=state["active"].at[slot].set(True),
        temp=state["temp"].at[slot].set(temp),
        topk=state["topk"].at[slot].set(topk),
        topp=state["topp"].at[slot].set(topp),
        remaining=state["remaining"].at[slot].set(remaining),
        eos=state["eos"].at[slot].set(eos),
    )


def _slot_clear(state, slot):
    """Deactivate one slot on device (free/cancel); state donated."""
    return dict(state, active=state["active"].at[slot].set(False))


class _TraceScope:
    """Forwarding wrapper entering a context manager around every call of
    a jitted program — the program's (lazy) TRACE then happens inside the
    scope, which is how the engine's ``tp_comms`` config reaches the
    row-parallel layers without global state leaking between engines.
    Attribute reads (``_cache_size``, ``lower``, ``last_call_compiled``)
    forward to the wrapped callable so every compile-count property and
    ledger proxy keeps working unchanged."""

    def __init__(self, fn, make_ctx):
        self._fn = fn
        self._make_ctx = make_ctx

    def __call__(self, *args, **kwargs):
        with self._make_ctx():
            return self._fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        # lowering must enter the SAME scope as dispatch: a lower() outside
        # it traces the exact-psum program even on a tp_comms engine, so
        # every IR-level consumer (ledger cost analysis, graftverify's
        # donation/collective checks) would verify a program the engine
        # never runs — the trace-scope-leakage class GL07 encodes
        with self._make_ctx():
            return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


@dataclasses.dataclass
class _Chunk:
    """A decode chunk that was called and is not read back yet."""

    outputs: tuple            # on the device: toks, counts, used, the key snapshot, the model's stats
    active: int               # slots decoding at its call
    t0: float                 # its call, and the call's return
    t1: float
    compiled: bool            # the call compiled: its wall is no chunk's
    # the rule's word on calling the next chunk before this one is read
    # back (``_can_run_ahead``), taken inside a span of the step that reads
    # it: this chunk's dispatch span or, called a step earlier, the reap span
    followed: bool
    # the slots' requests at the call of a chunk called AHEAD of the one
    # before it (``None`` for a chunk called with nothing unread)
    reqs: Optional[tuple] = None
    # columns the host's cursor went ahead by when the NEXT chunk was called
    # on this one's projection (0: the cursor waits for the readback)
    projected: int = 0


class ServingEngine:
    """Slot-based continuous batching over a mode-capable causal LM."""

    def __init__(
        self,
        model,
        params,
        num_slots: int,
        max_tokens_in_flight: Optional[int] = None,
        admission: str = "conservative",
        scheduling="fifo",
        decode_chunk_size: int = 8,
        max_queue: Optional[int] = None,
        draft_model=None,
        draft_params=None,
        gamma: int = 4,
        kv_page_size: Optional[int] = None,
        kv_num_pages: Optional[int] = None,
        kv_host_pages: Optional[int] = None,
        quantize=None,
        tp: Optional[int] = None,
        mesh=None,
        tp_comms=None,
        paged_attention: str = "auto",
        rid_base: int = 0,
        prefix_cache="auto",
        dispatch_retry: Optional[RetryPolicy] = None,
        degraded_cooldown_chunks: int = 8,
        quarantine_policy: str = "requeue",
        fault_injector=None,
        timeline=None,
        registry=None,
        engine_label: Optional[str] = None,
        slo=None,
        flight_recorder="auto",
        flight_dir: Optional[str] = None,
        time_fn: Callable[[], float] = time.monotonic,
        sleep_fn: Callable[[float], None] = time.sleep,
    ):
        if admission not in ("conservative", "eager"):
            raise ValueError(f"unknown admission policy {admission!r}")
        if decode_chunk_size < 1:
            raise ValueError(
                f"decode_chunk_size must be >= 1, got {decode_chunk_size}"
            )
        if quarantine_policy not in ("requeue", "fail"):
            raise ValueError(
                f"unknown quarantine policy {quarantine_policy!r}"
            )
        # quantized serving (ISSUE 13): quantize= is a QuantConfig.
        # Weights: the TARGET model is rebound to its quantization_config'd
        # clone HERE — every jitted program below (prefill buckets, the
        # fused decode/spec chunks, the suffix prefill) traces the
        # dequantize-on-load quantized_matmul forward, one program per
        # bucket exactly like fp32 — and the params setter converts any
        # float tree ONCE per assignment (construction AND later weight
        # swaps). KV: the paged pool stores int8 pages + scale siblings
        # (PagedCacheManager(kv_quant=)); the chunk's gather/scatter
        # transports de/re-quantize in-program. The draft model (if any)
        # stays float: drafts only steer speculation, and the emitted
        # stream never depends on draft numerics. Correctness contract
        # shifts from bit-identity to the pinned logit-divergence budget
        # (tests/serving/test_quantized_engine.py).
        self.quantize = quantize
        self._weight_qcfg = None
        if quantize is not None:
            if quantize.kv is not None and kv_page_size is None:
                raise ValueError(
                    "quantize.kv needs kv_page_size= (quantized KV is "
                    "page-granular — the row layout stays fp32)"
                )
            wq = quantize.weight_qconfig()
            if wq is not None:
                cfg = getattr(model, "config", None)
                if not dataclasses.is_dataclass(cfg) or not any(
                    f.name == "quantization"
                    for f in dataclasses.fields(cfg)
                ):
                    raise ValueError(
                        "quantize.weights needs a model whose config "
                        "carries a 'quantization' field (the llama/mixtral "
                        "families); got "
                        f"{type(cfg).__name__ if cfg is not None else None}"
                    )
                if getattr(cfg, "quantization", None) is not None:
                    raise ValueError(
                        "model already carries a quantization config — "
                        "pass the float model (the engine quantizes) or "
                        "drop quantize="
                    )
                model = model.clone(
                    config=dataclasses.replace(cfg, quantization=wq)
                )
                self._weight_qcfg = wq
        max_seq_len = getattr(getattr(model, "config", None), "max_seq_len", None)
        if max_seq_len is None:
            raise ValueError(
                "ServingEngine needs model.config.max_seq_len (the fixed "
                "slot cache length)"
            )
        # multi-chip serving (ISSUE 14): tp= shards the WHOLE hot path over
        # the TP mesh — params by their nn.Partitioned axis rules (the T5X
        # partitioner pattern: rules own the sharding, the engine's program
        # code never changes), the KV pool/rows on the kv-head axis, slot
        # state and block tables replicated. Every jitted program below then
        # partitions off the placed operands plus the layers' activation
        # constraints: decode_compilations stays 1, the chunk readback stays
        # ONE device_get of replicated scalars/tokens (never sharded KV),
        # and streams are bit-identical to the mesh-free engine at any tp
        # on the CPU mesh proxy. tp_comms= (QuantizedAllReduceConfig)
        # optionally routes the row-parallel all-reduces through the EQuARX
        # int8 ring — a wire-byte dial behind an explicit accuracy opt-in.
        from neuronx_distributed_tpu.parallel.sharding import (
            ServingPartitioner,
            serving_mesh,
        )

        if mesh is not None and tp is None:
            tp = int(mesh.mesh.shape["tp"]) if hasattr(mesh, "mesh") else None
        # a latent (MLA) cache holds ONE row a token for all heads
        # (modules/attention.py LatentKVCache): nothing to shard over tp; an
        # indexed cache's one index key a token likewise (IndexedKVCache), and
        # an indexed latent cache is both (IndexedLatentKVCache)
        cache_kind = getattr(
            getattr(model, "config", None), "kv_cache_kind", "kv"
        )
        if cache_kind != "kv" and (tp or 1) > 1:
            raise ValueError(
                f"ServingEngine(tp>1) does not serve a {cache_kind}-cache "
                "model: its one-row-a-token leaf has no head axis to shard "
                "and its paged decode kernels have no sharded form — serve "
                "it on one chip (tp=None)"
            )
        # window layers (modules/attention.py JoinedKVCache(window=)): the
        # paged manager frees their pages behind the window, so whatever
        # holds a context by its PAGES (the prefix cache and zero-copy
        # sharing, tiering, a disaggregated handoff) lacks the freed ones,
        # and a draft model or a quantized pool has no window kind
        kv_window = getattr(
            getattr(model, "config", None), "kv_cache_window", None
        )
        # per-slot state beside the pages (modules/attention.py
        # SLOT_STATE_LEAVES): what the next token needs of the last one is in
        # no page, so the same holders lack it at a context's end
        kv_slot_state = bool(getattr(
            getattr(model, "config", None), "kv_cache_slot_state", False
        ))
        # a prefill's row (its OUTPUT, beside the pool until the admission has
        # dealt it into pages) has the cache's columns by every model's
        # construction, ``max_seq_len``; a model whose config says
        # ``bucket_prefill_rows`` is cloned a bucket with ``max_seq_len`` the
        # bucket's, so the row has the bucket's columns and the paged
        # admission cuts its pages out of that (serving/paging.py
        # ``_paged_admit``). With few slots and many cache nodes a whole row
        # is half the pool again. Paged caches only: a row-per-slot cache
        # rolls whole rows
        self._bucket_rows = kv_page_size is not None and bool(getattr(
            getattr(model, "config", None), "bucket_prefill_rows", False
        ))
        if (kv_window is not None and kv_page_size is not None) or kv_slot_state:
            from neuronx_distributed_tpu.serving.paging import (
                CacheKindUnsupported,
            )

            asked = {
                "prefix_cache": prefix_cache not in ("auto", None, 0)
                and getattr(prefix_cache, "enabled", True),
                "kv_host_pages": kv_host_pages is not None,
                "draft_model": draft_model is not None,
                "quantize.kv": quantize is not None and quantize.kv is not None,
            }
            why = (
                f"a model with window layers (window {kv_window}): their "
                "pages behind the window are freed, so no context can be "
                "held, shared, spilled or drafted by its pages yet"
                if kv_window is not None else
                "a model whose layers keep per-slot state beside their "
                "pages: the state at a context's end is in no page, so no "
                "context can be held, shared, spilled or drafted by its "
                "pages yet"
            )
            for what, on in asked.items():
                if on:
                    raise CacheKindUnsupported(
                        f"{what} is not available for {why}"
                    )
            prefix_cache = None   # "auto": on wherever the cache can have one
        self.tp = tp
        self._partitioner = None
        if tp is not None:
            state = mesh if mesh is not None else serving_mesh(tp)
            self._partitioner = ServingPartitioner(state)
        self._tp_comms = tp_comms
        if tp_comms is not None and self._partitioner is None:
            raise ValueError(
                "tp_comms= needs a TP mesh (pass tp=/mesh=) — there is no "
                "all-reduce to route on a mesh-free engine"
            )
        # fused paged attention (ISSUE 14, the PR 12 leftover): "fused"
        # streams K/V straight from the physical pool pages through
        # paged_flash_decode_attention's scalar-prefetch block table. It IS
        # the kernel wherever it is asked for — off the TPU it only runs
        # interpreted, in tests — and never degrades to the gather
        # transport. "auto" = fused exactly where the kernel compiles (TPU,
        # plain chunk, float pool), gather elsewhere; what it resolved to
        # is recorded in ``programs.resolved`` below.
        if paged_attention not in ("auto", "gather", "fused"):
            raise ValueError(
                f"unknown paged_attention {paged_attention!r} "
                "(expected 'auto', 'gather' or 'fused')"
            )
        _fusable = (
            kv_page_size is not None
            and draft_model is None
            and (quantize is None or quantize.kv is None)
            and not getattr(
                getattr(model, "config", None), "scan_layers", False
            )
        )
        if paged_attention == "auto":
            paged_attention = (
                "fused" if _fusable and backend.on_tpu() else "gather"
            )
        elif paged_attention == "fused" and not _fusable:
            raise ValueError(
                "paged_attention='fused' needs a paged (kv_page_size=), "
                "non-speculative engine with a float KV pool and "
                "scan_layers=False (the fused transport pairs per-layer "
                "pool leaves with attention calls by layer name)"
            )
        self.paged_attention = paged_attention
        # speculative decoding (ISSUE 9): a draft model turns every decode
        # chunk into `decode_chunk_size` fused draft–verify ROUNDS, each
        # emitting 1..gamma tokens per slot. draft_model=None is a strict
        # no-op: every code path below is byte-for-byte today's
        if draft_model is not None:
            if draft_params is None:
                raise ValueError("draft_model needs draft_params")
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
            d_cfg = getattr(draft_model, "config", None)
            if getattr(d_cfg, "max_seq_len", None) != max_seq_len:
                raise ValueError(
                    "draft_model.config.max_seq_len "
                    f"({getattr(d_cfg, 'max_seq_len', None)}) must equal the "
                    f"target's ({max_seq_len}) — both caches share the slot "
                    "row length"
                )
            t_vocab = getattr(getattr(model, "config", None), "vocab_size", None)
            if (
                t_vocab is not None
                and getattr(d_cfg, "vocab_size", None) != t_vocab
            ):
                raise ValueError(
                    "draft and target models must share a vocabulary "
                    f"({getattr(d_cfg, 'vocab_size', None)} != {t_vocab})"
                )
        self.model = model
        self.params = params  # property: binds self._params once per assign
        self.draft_model = draft_model
        self.gamma = gamma if draft_model is not None else 1
        # columns one decode dispatch iteration consumes: gamma per
        # speculative round, 1 per plain step — the unit of every cursor
        # wall/admission computation below
        self._round_cols = self.gamma
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len
        self.admission = admission
        self.decode_chunk_size = decode_chunk_size
        self.max_queue = max_queue
        self.timeline = timeline
        self._clock = time_fn
        self._sleep = sleep_fn
        self._vocab = getattr(getattr(model, "config", None), "vocab_size", None)
        # dispatch-recovery policy: bounded consecutive failures, waits from
        # the shared decrementing-jitter schedule (utils/retry.py — the same
        # policy class the checkpoint object-store path rides)
        self._dispatch_retry = dispatch_retry or RetryPolicy(
            max_attempts=3, first_wait=0.05, min_wait=0.01
        )
        self._degraded_cooldown = degraded_cooldown_chunks
        self._quarantine_policy = quarantine_policy
        self._faults = fault_injector
        # prefix cache: "auto" (default) builds the standard store; an int
        # sizes it (0 disables); None or a disabled instance restores the
        # full-prefill-only admission path exactly
        if prefix_cache == "auto":
            prefix_cache = PrefixCache()
        elif isinstance(prefix_cache, int):
            prefix_cache = (
                PrefixCache(max_entries=prefix_cache) if prefix_cache > 0
                else None
            )
        if prefix_cache is not None and not prefix_cache.enabled:
            prefix_cache = None
        self.prefix = prefix_cache
        if prefix_cache is not None:
            # paged entries hold ref-counted pool pages instead of copies;
            # whatever drops an entry (LRU churn, poison, clear-on-swap)
            # must release those refs or the pool leaks
            prefix_cache.on_evict = self._on_prefix_evict
        self._prefix_reuses = 0  # reuse-attempt index (poison-hook schedule)
        self._window_pages_freed_seen = 0
        self._slot_state = None   # the dispatch span's stats, read once (_measure_slot_state)
        self._steps_seen = 0  # step() index (flip_bits("params") schedule)
        self._prefill_model, self._decode_model = serving_clones(model)
        # scheduling policy (ISSUE 16): "fifo" (default — bit-identical to
        # the pre-policy engine), "slo" (priority tiers + DWRR token
        # fairness + attainment-feedback admission/preemption), or a
        # SchedulingPolicy instance. The policy owns queue ORDER and
        # victim choice; every mechanism it rides (requeue/resume, slot
        # release) is the existing bit-identical machinery
        self.scheduler = Scheduler(max_tokens_in_flight, policy=scheduling)
        self.policy = self.scheduler.policy
        # paged KV (ISSUE 10): kv_page_size switches the cache path from
        # row-per-slot to block/page granularity — a ref-counted page pool
        # with per-slot device-resident block tables, free-page admission
        # accounting, and ZERO-COPY copy-on-write prefix sharing. None is
        # byte-for-byte the legacy row manager (streams are bit-identical
        # either way; paged buys HBM packing under mixed-length traffic)
        self._page_size = kv_page_size
        if kv_page_size is not None:
            self.cache = PagedCacheManager(
                num_slots, max_seq_len, kv_page_size, kv_num_pages,
                kv_quant=quantize.kv if quantize is not None else None,
                # a block table and a pool a layer KIND for a model with
                # window layers; None builds exactly the one-kind tree
                window=kv_window, window_write_cols=decode_chunk_size,
            )
            self.cache.reclaim = self._reclaim_prefix_entry
        else:
            if kv_num_pages is not None:
                raise ValueError("kv_num_pages needs kv_page_size")
            if kv_host_pages is not None:
                raise ValueError("kv_host_pages needs kv_page_size")
            self.cache = SlotCacheManager(num_slots)
        # tiered KV (ISSUE 19): a bounded host-RAM store behind the page
        # pool — the reclaim valve spills cold prefix entries' pages there
        # instead of dropping them, and the admission pre-pass prefetches
        # matched pages back while the hitting request still queues. None
        # (the default) keeps the single-tier engine byte-identical
        self.tier = (
            HostPageStore(kv_host_pages)
            if kv_host_pages is not None else None
        )
        self._spill_index = 0     # spill-attempt index (chaos schedules)
        self._prefetch_index = 0  # prefetch-attempt index (chaos schedules)
        if self._partitioner is not None:
            # KV storage commits to the mesh at allocation (kv-head axis
            # over tp where it divides); every donated successor keeps it
            self.cache.placement = self._partitioner.place_kv
        # draft-side twins: mode clones, a SECOND donated cache collection
        # (admit/free/recover/quarantine mirrored 1:1 with the target's),
        # and per-bucket draft prefill programs. The draft cache cursor
        # tracks the target's in lockstep — both consume gamma columns per
        # executed round
        if draft_model is not None:
            self.draft_params = draft_params  # property: binds once
            self._draft_prefill_model, self._draft_decode_model = (
                serving_clones(draft_model)
            )
            # the draft twin rides the same manager class (and, when paged,
            # its own pool of the same geometry — lifecycles mirror 1:1)
            self.draft_cache = (
                PagedCacheManager(
                    num_slots, max_seq_len, kv_page_size, kv_num_pages
                )
                if kv_page_size is not None
                else SlotCacheManager(num_slots)
            )
            if self._partitioner is not None:
                self.draft_cache.placement = self._partitioner.place_kv
        else:
            self._draft_params_src = None
            self._draft_params = None
            self.draft_cache = None
        self._draft_prefill_fns: Dict[int, Callable] = {}
        # tenant/SLO attribution (ISSUE 11): slo= is an SLOSpec (one
        # contract for every tenant) or a {tenant: SLOSpec} dict;
        # engine_label= makes every metric a child of an engine-labeled
        # family so N engines can share one registry/scrape endpoint
        self.metrics = ServingMetrics(
            num_slots, registry=registry, engine_label=engine_label, slo=slo
        )
        # late policy wiring: the SLO policy reads metrics/prefix/cache
        # feedback surfaces (all host state; FIFO ignores the engine)
        self.policy.bind(self)
        # observability layer (ISSUE 8): request-scoped flow tracing on the
        # shared timeline, and an always-on flight recorder whose ring is
        # dumped as a redacted post-mortem the moment the engine HALTs.
        # Every emit below takes host scalars the loop already owns — the
        # pinned host-sync budgets (tests/serving/test_host_sync.py) hold
        # with all of this enabled
        self.tracer = RequestTracer(timeline)
        if flight_recorder == "auto":
            flight_recorder = FlightRecorder(
                dump_dir=flight_dir, subsystem="serving"
            )
        self.flight = flight_recorder  # None disables
        # the step ledger: every phase of step() accounts to it through
        # _span (observability/tracing.py's third sink); gone with the
        # recorder
        self._ledger = (
            flight_recorder.steps if flight_recorder is not None else None
        )
        # device-efficiency observability (ISSUE 12): every jitted program
        # below registers in a ProgramLedger — dispatch counts, compile
        # wall, compiler-reported FLOPs/bytes (lazy cost analysis at
        # export, never on the hot path) and roofline telemetry off the
        # chunk walls the loop already measures. It rides the engine's
        # labeled metrics view
        self.programs = ProgramLedger(
            view=self.metrics.view, prefix="serving",
            subsystem="serving", timeline=timeline,
        )
        self.cache.register_programs(self.programs)
        if self.draft_cache is not None:
            self.draft_cache.register_programs(self.programs, prefix="draft_")
        # what every platform-dependent "auto" resolved to on THIS device —
        # the snapshot a run asserts its kernels from (chip_smoke.py)
        self.programs.resolved.update(
            attention=backend.resolve_attention_impl(
                getattr(model, "attention_impl", "auto")
            ),
            decode_attention=(
                {"latent": "paged_latent_fused",
                 "indexed": "paged_sparse_fused",
                 "indexed_latent": "paged_sparse_latent_fused",
                 "joined": "paged_walk_fused",
                 "joined_recurrent": "paged_walk_fused",
                 }.get(cache_kind, "paged_fused")
                if self.paged_attention == "fused"
                else "einsum" if cache_kind != "kv"  # the one-row-a-token kinds' only other path
                else resolve_decode_impl(max_seq_len)
            ),
            paged_attention=(
                self.paged_attention if kv_page_size is not None else "none"
            ),
        )
        model_cfg = getattr(model, "config", None)
        if hasattr(model_cfg, "num_experts") and hasattr(model_cfg, "expert_strategy"):
            from neuronx_distributed_tpu.modules.moe.expert_mlps import decode_form

            self.programs.resolved["moe_decode"] = decode_form(
                model_cfg, num_slots, sharded=(tp or 1) > 1)
        # host-side slot bookkeeping (scheduling only — the decode-visible
        # per-slot state lives on device in self._state)
        self._active = np.zeros((num_slots,), bool)
        self._slot_req: List[Optional[Request]] = [None] * num_slots
        self._on_token: Dict[int, Callable[[Request, int], None]] = {}
        # rid_base namespaces request ids across engines (the replica
        # router re-homes live Request objects between replicas — two
        # engines must never mint the same rid)
        self._next_rid = int(rid_base)
        self._prefill_fns: Dict[int, Callable] = {}
        # bucket -> the rows of logits its prefill program computes, written
        # while the program is traced (_prefill_fn): stat head_rows
        self._prefill_head_rows: Dict[int, int] = {}
        self._state = self._fresh_slot_state()
        # disaggregated serving (ISSUE 14): True routes ALL prefill work to
        # external workers — step() keeps decoding but never self-admits;
        # the DisaggregatedServer pulls from the queue, prefills on its
        # workers, and hands contexts back through admit_staged()
        self.external_prefill = False
        # the decode chunk called and not yet read back when a step ends
        # (run ahead: module docstring, "Decode hot path"), and when the
        # last chunk was read back
        self._in_flight: Optional[_Chunk] = None
        self._last_readback_t = 0.0
        # fault-tolerance state machine
        self._halted = False
        self._halt_reason: Optional[str] = None
        self._draining = False
        self._consecutive_dispatch_failures = 0
        self._had_dispatch_failure = False
        self._chunks_since_failure = 0
        self._dispatch_attempts = 0  # includes failed attempts (hook index)
        self._readbacks = 0  # successful readbacks (poison-hook index)
        self._prefill_calls = 0  # prefill attempts (prefill-hook index)
        self._consecutive_prefill_failures = 0
        self._last_health = EngineHealth.OK
        # the fused decode chunk: cache AND slot state donated — XLA updates
        # both in place instead of materializing a fresh cache pytree. With
        # a draft model the SPECULATIVE chunk (both caches + state donated)
        # is the hot program; the plain chunk is then built LAZILY, only if
        # a failed speculative dispatch ever needs the non-speculative
        # fallback
        if draft_model is not None:
            self._spec_chunk = jax.jit(
                speculative_decode_chunk(
                    self._decode_model, self._draft_decode_model,
                    decode_chunk_size, gamma, max_seq_len,
                    page_size=kv_page_size,
                ),
                donate_argnums=(2, 3, 4),
            )
            # ledger proxies rebind AFTER the jax.jit assignment so
            # graftlint's donation index (GL01) keeps seeing the literal
            # donate_argnums on the binding; the proxy forwards
            # _cache_size()/lower, so the compile-count properties below
            # read through unchanged
            self._spec_chunk = self.programs.wrap(
                "spec_decode_chunk", self._comms_scoped(self._spec_chunk)
            )
            self._decode_chunk = None
        else:
            self._spec_chunk = None
            chunk = chunked_decode_step(
                self._decode_model, decode_chunk_size, max_seq_len,
                page_size=kv_page_size,
                paged_attention=(
                    self.paged_attention
                    if kv_page_size is not None else "gather"
                ),
            )
            self._decode_chunk = jax.jit(chunk, donate_argnums=(1, 2))
            self._decode_chunk = self.programs.wrap(
                "decode_chunk", self._comms_scoped(self._decode_chunk)
            )
        # per_instance: module-level helpers share a pjit cache across
        # engines in this jax (PR 4's lambda-wrapper note) — a fresh
        # function object per engine keeps compile counts, and the
        # ledger's compile/signature detection, per-engine truthful
        self._slot_write = jax.jit(per_instance(_slot_write), donate_argnums=(0,))
        self._slot_write = self.programs.wrap("slot_write", self._slot_write)
        self._slot_clear = jax.jit(per_instance(_slot_clear), donate_argnums=(0,))
        self._slot_clear = self.programs.wrap("slot_clear", self._slot_clear)
        self._first_token = self.programs.wrap(
            "first_token", jax.jit(per_instance(sample_row))
        )
        # prefix-reuse programs (compiled lazily, only when the cache hits):
        # suffix prefill keys on the chunk bucket, extract/seed on the
        # storage bucket, the fingerprint on the entry shapes. NOTHING here
        # donates — a stored entry must stay a live COPY (the decode chunk's
        # donation regime must never be able to consume prefix storage)
        self._suffix_fn = self.programs.wrap(
            "suffix_prefill",
            self._comms_scoped(jax.jit(suffix_prefill_step(self._decode_model))),
        )
        # per-engine lambda wrappers: _cache_size()
        # is SHARED between jax.jit wrappers of the same function object
        # (two jax.jit(f) both read 1 after either is called — verified),
        # so jitting the module-level helpers directly would cross-pollute
        # the compile counts across engines
        self._extract_fn = self.programs.wrap("prefix_extract", jax.jit(
            lambda cache, start, m, bucket: extract_cache_prefix(
                cache, start, m, bucket
            ),
            static_argnums=(3,),
        ))
        self._seed_fn = self.programs.wrap("prefix_seed", jax.jit(
            lambda prefix, m, start, length: seed_cache_prefix(
                prefix, m, start, length
            ),
            static_argnums=(3,),
        ))
        self._fingerprint_fn = self.programs.wrap(
            "prefix_fingerprint", jax.jit(lambda tree: cache_fingerprint(tree))
        )
        # integrity sentinel (ISSUE 20): bit-level fingerprint programs,
        # built LAZILY — an engine that is never probed (and a pool whose
        # paged entries never reach fingerprint validation) compiles
        # nothing extra, so existing workloads' compile ledgers and
        # host-sync budgets are untouched
        self._integrity_fp_fn = None
        self._pages_fp_fn = None
        # HBM ledger (ISSUE 12): the engine's static residents registered
        # as weakref closures over live trees — bytes are leaf.nbytes
        # metadata (readable even mid-donation), reconciled against
        # Device.memory_stats() limits at snapshot time. plan() sizes
        # budgets in KV pages (paged) / slot rows (row mode)
        self.hbm = HBMLedger(view=self.metrics.view)

        def _res(fn):
            return weak_reader(self, fn)

        self.hbm.add_resident("params", _res(lambda e: tree_nbytes(e._params)))
        self.hbm.add_resident(
            "slot_state", _res(lambda e: tree_nbytes(e._state))
        )
        if kv_page_size is not None:
            self.hbm.add_resident(
                "kv_pages", _res(lambda e: e.cache.nbytes),
                unit_bytes=_res(lambda e: e.cache.page_nbytes),
                count=_res(lambda e: e.cache.alloc.capacity), unit="page",
            )
            if self.tier is not None:
                # host-tier resident (ISSUE 19): spilled pages' host
                # bytes, sized in pages against plan(host_budget_bytes=)
                # — never against device headroom
                self.hbm.add_resident(
                    "kv_host_pages", _res(lambda e: e.tier.nbytes),
                    unit_bytes=_res(lambda e: e.cache.page_nbytes),
                    count=_res(lambda e: e.tier.used_pages), unit="page",
                    tier="host",
                )
        else:
            self.hbm.add_resident(
                "kv_cache", _res(lambda e: e.cache.nbytes),
                unit_bytes=_res(lambda e: e.cache.slot_nbytes),
                count=num_slots, unit="slot",
            )
        if draft_model is not None:
            self.hbm.add_resident(
                "draft_params", _res(lambda e: tree_nbytes(e._draft_params))
            )
            self.hbm.add_resident(
                "draft_kv", _res(lambda e: e.draft_cache.nbytes)
            )
        if prefix_cache is not None:
            self.hbm.add_resident(
                "prefix_cache",
                _res(lambda e: e.prefix.nbytes if e.prefix is not None else 0),
            )
        # snapshot()["programs"] / ["hbm"] ride the metrics export (weakly
        # — a kept metrics object never pins a retired engine's ledgers)
        self.metrics.attach_device_efficiency(self.programs, self.hbm)
        # compile-event gauges: evaluated lazily at registry export (a
        # _cache_size read is host metadata), zero cost per step. WEAK
        # self-reference: a registry an operator keeps for a final scrape
        # must not pin a retired engine (model, params, KV cache)
        ref = weakref.ref(self)

        def _export(attr):
            def fn():
                engine = ref()
                return getattr(engine, attr) if engine is not None else -1
            return fn

        # own_gauge honors engine_label: labeled engines export these as
        # engine-labeled family children, so shared registries never
        # last-writer-wins another engine's export gauges
        gauge = self.metrics.own_gauge
        gauge(
            "serving_decode_compilations",
            help="distinct decode programs XLA compiled (invariant: 1)",
        ).set_fn(_export("decode_compilations"))
        gauge(
            "serving_prefill_compilations",
            help="distinct full+suffix prefill programs compiled",
        ).set_fn(_export("prefill_compilations"))
        gauge(
            "serving_queue_depth", help="queued (unfinished) requests"
        ).set_fn(_export("queue_depth"))
        if kv_page_size is not None:
            def _page_export(fn):
                def read():
                    engine = ref()
                    return fn(engine.cache) if engine is not None else -1
                return read

            gauge(
                "serving_kv_pages_total",
                help="usable KV pool pages (reserved + quarantined excluded)",
            ).set_fn(_page_export(lambda c: c.alloc.capacity))
            gauge(
                "serving_kv_pages_free", help="KV pool pages on the free list"
            ).set_fn(_page_export(lambda c: c.alloc.free_pages))
            gauge(
                "serving_kv_pages_mapped",
                help="KV pool pages mapped by some slot's block table",
            ).set_fn(_page_export(lambda c: c.pages_mapped))
            if self.tier is not None:
                def _tier_export(fn):
                    def read():
                        engine = ref()
                        return (
                            fn(engine.tier) if engine is not None else -1
                        )
                    return read

                gauge(
                    "serving_kv_host_pages_used",
                    help="spilled KV pages resident in the host tier",
                ).set_fn(_tier_export(lambda t: t.used_pages))
                gauge(
                    "serving_kv_host_bytes",
                    help="host-RAM bytes held by spilled KV pages",
                ).set_fn(_tier_export(lambda t: t.nbytes))

    def _fresh_slot_state(self):
        b = self.num_slots
        state = {
            "tok": jnp.zeros((b,), jnp.int32),
            "keys": jnp.zeros((b, 2), jnp.uint32),
            "active": jnp.zeros((b,), jnp.bool_),
            "temp": jnp.ones((b,), jnp.float32),
            "topk": jnp.zeros((b,), jnp.int32),
            "topp": jnp.ones((b,), jnp.float32),
            "remaining": jnp.zeros((b,), jnp.int32),
            "eos": jnp.full((b,), -1, jnp.int32),
        }
        if self._partitioner is not None:
            # committed-replicated over the mesh so every donated
            # successor keeps the layout (and no uncommitted-operand
            # recompile can ever hide here — the PR 5 zeros lesson)
            state = self._partitioner.replicate(state)
        return state

    # --- paged-KV helpers ---------------------------------------------------

    def _comms_scoped(self, fn):
        """Wrap a model-forward jit so its trace runs under the engine's
        ``tp_comms`` scope (no-op without one)."""
        if self._tp_comms is None:
            return fn
        from neuronx_distributed_tpu.parallel.quantized_collectives import (
            tp_comms,
        )

        return _TraceScope(fn, lambda: tp_comms(self._tp_comms))

    def _on_prefix_evict(self, entry) -> None:
        """PrefixCache eviction hook: a PAGED entry leaving the store (LRU
        churn, poison, clear-on-swap) releases its pool page refs — pages
        still mapped by a decoding slot's block table survive through that
        slot's own refs (CoW), pages held only by the entry free now.
        Tiered: a host-resident entry drops its host pages too, and a
        prefetched-but-unconsumed entry's device pages count as wasted
        prefetch work before their holds are voided."""
        if entry.page_ids:
            if self._page_size is not None and (
                self.cache.prefetch_held(entry.page_ids)
            ):
                self.metrics.record_prefetch_wasted(len(entry.page_ids))
                self.cache.release_prefetched(entry.page_ids)
            self.cache.unpin_pages(entry.page_ids)
            entry.page_ids = None
        if entry.host_ids and self.tier is not None:
            self.tier.drop(entry.host_ids)
            entry.host_ids = None

    def _reclaim_prefix_entry(self) -> bool:
        """Page-pressure valve (installed as ``cache.reclaim``): evict the
        least-recently-used UNPINNED prefix entry so its pages can serve a
        new admission. Never frees a still-mapped page — eviction only
        drops the entry's refs. With a host tier the entry's pages are
        SPILLED there first (one batched device->host pull) and the entry
        stays in the trie host-resident; a full/failed spill degrades to
        the plain eviction above. Entries whose pages a queued request's
        prefetch already claimed are skipped — reclaiming them would
        un-do work the admission fit math has already counted."""
        if self.prefix is None:
            return False
        for e in self.prefix.entries:  # LRU first
            if e.refs == 0 and e.page_ids:
                if self.cache.prefetch_held(e.page_ids):
                    continue
                if self._spill_entry(e):
                    return True
                self.prefix.evict_entry(e)
                self.metrics.record_prefix_eviction()
                return True
        return False

    def _spill_entry(self, entry) -> bool:
        """Move one cold prefix entry's pages device->host. True = the
        pages are free-able (the entry is now host-resident); False = no
        tier / no room / spill fault — the caller falls back to plain
        eviction. A failed spill NEVER leaks: nothing is unpinned until
        the host copy is stored."""
        if self.tier is None or not entry.page_ids:
            return False
        n = len(entry.page_ids)
        if n > self.tier.free_pages:
            return False
        attempt = self._spill_index
        self._spill_index += 1
        try:
            if self._faults is not None:
                self._faults.on_spill(attempt)
            items, nbytes = self.cache.spill_pages(entry.page_ids)
            host_ids = self.tier.put(entry.page_ids, items)
        except Exception:
            self.metrics.record_spill_failure()
            return False
        self.cache.unpin_pages(entry.page_ids)
        entry.page_ids = None
        entry.host_ids = host_ids
        entry.hit_tier = "host"
        self.metrics.record_spill(n, nbytes)
        if self.timeline is not None:
            self.timeline.instant(
                "kv_spill", "serving",
                args={"pages": n, "bytes": nbytes},
            )
        return True

    def _prefetch_entry(self, entry, late: bool = False) -> bool:
        """Bring one host-resident prefix entry's pages back device-side.
        The device write is the pool's existing import program — an async
        host->device dispatch (zero syncs) that overlaps the in-flight
        decode chunk. Returns True when the entry is device-resident
        after the call. Fingerprint mismatch or an injected prefetch
        fault evicts the entry (the admission falls back to a full
        prefill — bit-identical by construction); pool exhaustion leaves
        the entry host-resident to retry later."""
        if self.tier is None or not entry.host_ids:
            return entry.page_ids is not None
        host_ids = entry.host_ids
        n = len(host_ids)
        attempt = self._prefetch_index
        self._prefetch_index += 1
        try:
            if self._faults is not None:
                self._faults.on_prefetch(
                    attempt, store=self.tier, host_ids=host_ids
                )
            if not self.tier.verify(host_ids):
                # corrupted host copy: reject the WHOLE fetch, drop the
                # entry — the next admission re-prefills from tokens
                self.metrics.record_host_page_poisoned()
                self.metrics.record_prefix_validation_failure()
                if self.timeline is not None:
                    self.timeline.instant(
                        "kv_host_poisoned", "serving", args={"pages": n}
                    )
                self.prefix.evict_entry(entry)
                self.metrics.record_prefix_eviction()
                return False
            items, nbytes = self.tier.get(host_ids)
            ids = self.cache.prefetch_pages(items, n)
        except PageExhausted:
            return False  # stay host-resident; retry on a later pass
        except Exception:
            self.metrics.record_prefetch_failure()
            self.prefix.evict_entry(entry)
            self.metrics.record_prefix_eviction()
            return False
        self.cache.hold_prefetched(ids)
        entry.page_ids = tuple(int(i) for i in ids)
        entry.host_ids = None
        entry.hit_tier = "host"
        self.tier.drop(host_ids)
        self.metrics.record_prefetch(n, nbytes, late=late)
        if self.timeline is not None:
            self.timeline.instant(
                "kv_prefetch", "serving",
                args={"pages": n, "bytes": nbytes, "late": late},
            )
        return True

    def _prefetch_for_queue(self) -> None:
        """Admission pre-pass (ISSUE 19): peek the front of the queue and
        start host->device prefetches for any matched SPILLED prefix
        entries before the requests are admitted — the transfer rides the
        pool's async import dispatch and overlaps the current chunk's
        device time, so the hit is device-resident by rebind time.
        Policy-blind and LRU-neutral (``peek`` does not refresh recency);
        a prefetch for a request admitted later is merely early."""
        if self.tier is None or self.prefix is None:
            return
        window = max(self.cache.free_slots, 1)
        for req in self.scheduler.upcoming(window):
            hit = self.prefix.peek(req.context_ids)
            if hit is None:
                continue
            entry, m_use = hit
            if entry.host_ids and m_use > 0:
                self._prefetch_entry(entry)

    def _paged_layout(self, p: int, rem_cols: int, proj: int):
        """(padded, cursor target) for a paged admission at projected
        cursor ``proj``: the padded bucket as ever, with the target bumped
        (< page_size gap columns) so the context START lands on a page
        boundary — the alignment that makes whole context pages shareable.
        When the bump would push the request past the row end that the
        exact-length bucket avoids, fall back to ``padded = p`` (the same
        keep-every-feasible-request-admittable trade ``_bucket`` makes)."""
        padded = _bucket(p, self.max_seq_len, rem_cols)
        target = self.cache.aligned_target(max(proj, padded), p)
        if padded > p and target + rem_cols > self.max_seq_len:
            padded = p
            target = self.cache.aligned_target(max(proj, p), p)
        return padded, target

    def _chunk_width_cols(self, active, unread: int = 0) -> int:
        """Columns the next chunk can actually WRITE: the fused chunk
        freezes a slot when its budget runs out, so no more than the
        largest remaining generation among active slots ever executes
        (steps on the plain path, rounds — gamma columns each — on the
        speculative path; less the ``unread`` tokens a chunk still on the
        device takes of every budget). Clamping the page demand to this keeps the
        per-chunk window consistent with the admission/door accounting,
        which sizes requests by their REMAINING tokens — an unclamped full
        chunk window could demand pages the door check never charged and
        livelock a tightly-sized pool at the page-pressure wall."""
        max_rem = max(
            (
                self._slot_req[int(s)].remaining_new_tokens
                for s in active
                if self._slot_req[int(s)] is not None
            ),
            default=self.decode_chunk_size,
        )
        return min(
            self.decode_chunk_size, max(max_rem - unread, 1)
        ) * self._round_cols

    def _ensure_decode_pages(self, unread: int = 0) -> bool:
        """Map pool pages under every active slot's next write window (both
        caches on a speculative engine). False = the page-pressure wall:
        the caller preempts-and-rewinds, exactly like the cursor wall (or,
        called ahead of an ``unread`` chunk, goes back to one at a time)."""
        active = np.flatnonzero(self._active)
        width = self._chunk_width_cols(active, unread)
        if not self.cache.ensure_decode_window(active, width):
            return False
        if self.draft_cache is not None and not (
            self.draft_cache.ensure_decode_window(active, width)
        ):
            return False
        return True

    def _apply_page_poison(self, readback: int) -> set:
        """Consult the injector's page-poison schedule (paged engines):
        each scheduled page is retired from the pool and every ACTIVE
        request whose block table maps it is requeued from the last chunk
        boundary (its chunk output discarded, tokens/keys host-current —
        bit-identical resume in a different slot/pages). The slot indices
        return to rotation; only the PAGE is lost. Prefix entries pinning a
        poisoned page are evicted (their shared content is suspect).
        Returns the victim slot set (the caller skips their readback)."""
        victims: set = set()
        if self._faults is None or self._page_size is None:
            return victims
        pages = self._faults.on_page_readback(
            readback, lambda s: self.cache.slot_pages(int(s)), self._active
        )
        if not pages:
            return victims
        now = self._now()
        for page in pages:
            slots = self.cache.quarantine_page(int(page))
            self.metrics.record_page_quarantine(int(page), len(slots))
            if self.timeline is not None:
                self.timeline.instant(
                    f"quarantine page {int(page)}", "serving",
                    args={"slots": [int(s) for s in slots]},
                )
            if self.flight is not None:
                self.flight.record("page_quarantine", page=int(page),
                                   slots=[int(s) for s in slots])
            if self.prefix is not None:
                for e in list(self.prefix.entries):
                    if e.page_ids and int(page) in e.page_ids:
                        self.prefix.evict_entry(e)
                        self.metrics.record_prefix_eviction()
            victims.update(int(s) for s in slots if self._active[int(s)])
        requeue = []
        for slot in sorted(victims):
            req = self._slot_req[slot]
            self._slot_req[slot] = None
            self._active[slot] = False
            self._state = self._slot_clear(self._state, np.int32(slot))
            self.cache.free(slot)
            if self.draft_cache is not None:
                self.draft_cache.free(slot)
            if req is None:
                continue
            req.slot = None
            if self._quarantine_policy == "requeue" and not req.finished:
                self.tracer.step(req.rid, "page_quarantine_requeue",
                                 args={"slot": slot})
                requeue.append(req)
            else:
                req.state = RequestState.FAILED
                req.error = f"slot {slot} mapped a poisoned KV page"
                req.finish_time = now
                self.metrics.record_failed(req, now, kind="quarantine")
                self.tracer.end(req.rid, "failed",
                                args={"kind": "page_quarantine", "slot": slot})
                self._on_token.pop(req.rid, None)
        if requeue:
            self.scheduler.requeue_front(requeue)
        if self.cache.alloc.capacity == 0:
            self._halt("all KV pages quarantined")
        return victims

    # --- public API ---------------------------------------------------------

    @property
    def params(self):
        """The engine's weights. Assignment rebinds the params pytree the
        jitted prefill/decode programs receive — ONCE per assignment, not
        per step (the hot path never rebuilds it), so weight swaps still
        take effect on the next dispatch."""
        return self._params_src

    @params.setter
    def params(self, value):
        qcfg = getattr(self, "_weight_qcfg", None)
        if qcfg is not None:
            from neuronx_distributed_tpu.quantization.utils import (
                is_quantized_tree,
                quantize_param_tree,
            )

            # a float tree converts ONCE per assignment (construction and
            # hot weight swaps alike); a pre-quantized tree — an offline
            # quantize_param_tree output, a loaded quantized checkpoint —
            # binds as-is. Either way the bound tree matches the quantized
            # model clone's declaration structure exactly
            if not is_quantized_tree(value):
                value = quantize_param_tree(value, qcfg)
        self._params_src = value
        if getattr(self, "_partitioner", None) is not None:
            # TP placement happens HERE, once per assignment — the axis
            # rules (nn.Partitioned metadata) own the layout, every jitted
            # program below just follows the committed operands
            value = self._partitioner.shard_params(value)
        self._params = dict(value)
        # a weight swap invalidates every stored prefix: its KV was computed
        # under the OLD weights, and the cache-off path would recompute it —
        # serving it would silently break bit-identity (and correctness)
        prefix = getattr(self, "prefix", None)  # None during __init__
        if prefix is not None:
            dropped = prefix.clear()
            metrics = getattr(self, "metrics", None)
            if dropped and metrics is not None:
                metrics.record_prefix_eviction(dropped)

    @property
    def draft_params(self):
        """The draft model's weights (speculative serving only). Assignment
        rebinds the pytree the speculative chunk receives once, like
        ``params``. A draft swap mid-flight is SAFE for correctness — the
        emitted stream never depends on draft quality — but slots admitted
        under the old draft keep old-draft KV until they retire, so
        acceptance may dip until the fleet turns over."""
        return self._draft_params_src

    @draft_params.setter
    def draft_params(self, value):
        if value is None:
            raise ValueError("draft_params cannot be unset on a live engine")
        self._draft_params_src = value
        if getattr(self, "_partitioner", None) is not None:
            value = self._partitioner.shard_params(value)
        self._draft_params = dict(value)

    def _now(self) -> float:
        """The engine's scheduling clock — the injected ``time_fn``,
        optionally skewed by the fault injector (chaos tests drive deadline
        paths without sleeping)."""
        now = self._clock()
        if self._faults is not None:
            now = self._faults.now(now)
        return now

    def submit(
        self,
        prompt_ids,
        config: GenerationConfig = GenerationConfig(),
        key=None,
        on_token: Optional[Callable[[Request, int], None]] = None,
        deadline_s: Optional[float] = None,
        queue_timeout_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
    ) -> Request:
        """Enqueue one request; returns its live ``Request`` (``tokens``
        fills in as the engine steps). ``key`` defaults to a per-request
        PRNGKey; pass the key you would give ``generate`` to reproduce its
        stream exactly.

        ``deadline_s`` bounds the request end to end (sheds to ``TIMED_OUT``
        at the next chunk boundary once exceeded, keeping any tokens already
        streamed); ``queue_timeout_s`` sheds it if it has not been admitted
        in time — both relative to submission on the engine clock. The
        queue timeout governs FIRST admission only: once admitted, a
        request requeued by preemption or dispatch recovery answers only to
        ``deadline_s``.

        ``tenant``/``priority`` (ISSUE 11) attribute the request for
        observability: per-tenant latency histograms, shed/timeout/reject
        attribution, SLO attainment (``slo=`` specs), trace-flow and
        flight-recorder tagging. Host strings only — attribution adds no
        device work and no host syncs. Scheduling is unaffected in this
        PR; the SLO-aware scheduler consumes these fields.

        Raises :class:`RejectedError` when the engine is draining/halted or
        the bounded queue (``max_queue``) is full, and ``ValueError`` for
        requests that could NEVER be placed (so an impossible request fails
        at the door instead of livelocking ``run()`` at the queue head)."""
        tenant = str(tenant) if tenant is not None else "default"
        priority = str(priority) if priority is not None else "standard"
        health = self.health()
        if health in (EngineHealth.DRAINING, EngineHealth.HALTED):
            depth = self.scheduler.queued
            self.metrics.record_reject(
                depth, health.value, tenant=tenant, now=self._now()
            )
            raise RejectedError(
                f"engine is {health.value}; not accepting new requests",
                queue_depth=depth,
            )
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if config.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if queue_timeout_s is not None and queue_timeout_s <= 0:
            raise ValueError(
                f"queue_timeout_s must be > 0, got {queue_timeout_s}"
            )
        # permanently-unplaceable guards: queueing a request no admission
        # round can ever select would livelock run() behind a FIFO head.
        # The seq-len class (prompt + generation over max_seq_len) is the
        # shared generate() contract below — it also subsumes the prefill
        # bucket, because _bucket falls back to the exact prompt length
        # whenever padding would not leave room for the generation; the
        # token-budget class needs its own check against the scheduler
        validate_generate_args(
            self.model, prompt[None], config.max_new_tokens, None
        )
        if self.draft_model is not None and (
            prompt.size + config.max_new_tokens + self.gamma - 1
            > self.max_seq_len
        ):
            # the speculative twin of the solo guard: the LAST round's
            # gamma-token verify window must fit the row even when the
            # context has grown to prompt + max_new - 1 (otherwise the
            # final token could never be emitted — rewind/preempt would
            # livelock re-admitting a context whose window never fits)
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({config.max_new_tokens}) + gamma-1 ({self.gamma - 1}) "
                f"exceeds max_seq_len ({self.max_seq_len}) — speculative "
                "serving needs window headroom for the final round"
            )
        budget = self.scheduler.max_tokens_in_flight
        if budget is not None and prompt.size + config.max_new_tokens > budget:
            raise ValueError(
                f"request footprint ({prompt.size + config.max_new_tokens}) "
                f"exceeds max_tokens_in_flight ({budget}); it could never "
                "be admitted"
            )
        if self._page_size is not None:
            # free-page twin of the seq-len-class guard, kept EXACT: the
            # request's worst-case page footprint ALONE (empty engine,
            # cursor rewound) must fit the pool or no admission round can
            # ever select it — fail at the door, not livelocked at the head
            rem_cols = config.max_new_tokens + self._round_cols - 1
            _, t0 = self._paged_layout(prompt.size, rem_cols, 0)
            span0 = self.cache.page_span(
                t0 - prompt.size, min(self.max_seq_len, t0 + rem_cols)
            )
            if span0 > self.cache.alloc.capacity:
                raise ValueError(
                    f"request needs {span0} KV pages even alone; the pool "
                    f"holds {self.cache.alloc.capacity} usable pages — it "
                    "could never be placed"
                )
        # backpressure: a bounded queue rejects loudly instead of absorbing
        # an unserviceable backlog
        depth = self.scheduler.queued
        if self.max_queue is not None and depth >= self.max_queue:
            self.metrics.record_reject(
                depth, "queue full", tenant=tenant, now=self._now()
            )
            if self.timeline is not None:
                self.timeline.instant(
                    "reject", "serving",
                    args={"queue_depth": depth, "tenant": tenant},
                )
            raise RejectedError(
                f"queue full ({depth} >= max_queue {self.max_queue})",
                queue_depth=depth,
            )
        rid = self._next_rid
        self._next_rid += 1
        if key is None:
            key = jax.random.PRNGKey(rid)
        req = Request(
            rid=rid, prompt=prompt, config=config, key=_key_data(key),
            tenant=tenant, priority=priority,
        )
        req.submit_time = self._now()
        if deadline_s is not None:
            req.deadline = req.submit_time + deadline_s
        if queue_timeout_s is not None:
            req.queue_deadline = req.submit_time + queue_timeout_s
        if on_token is not None:
            self._on_token[rid] = on_token
        self.scheduler.submit(req)
        self.metrics.record_submit(req, req.submit_time)
        if self.timeline is not None:
            self.timeline.instant(f"submit r{rid}", "serving")
        # open the request's trace flow: every later lifecycle event links
        # back to this id, so one Perfetto flow is the request's whole life
        # (tenant/priority on the opening event tag the whole flow)
        self.tracer.begin(
            rid,
            args={
                "prompt_len": int(prompt.size),
                "tenant": tenant,
                "priority": priority,
            },
        )
        return req

    def cancel(self, rid: int) -> bool:
        """Cancel a request. Queued: dropped immediately; running: its slot
        is reaped at the next chunk boundary."""
        req = self.scheduler.get(rid)
        if req is None or req.finished:
            return False
        was_queued = req.slot is None
        ok = self.scheduler.cancel(rid)
        if ok and was_queued:
            self.metrics.record_cancel(req, self._now())
            self.tracer.end(rid, "cancelled", args={"where": "queued"})
            # queued requests never reach _release_slot — drop the callback
            # here or it leaks for the engine's lifetime
            self._on_token.pop(rid, None)
        return ok

    # --- router / disaggregation surface (ISSUE 14) -------------------------

    def page_pressure(self) -> float:
        """Projected page demand of all admitted + queued work relative to
        pool capacity (0.0 on row engines). This is the router's
        overcommit signal: queue depth alone says nothing about how much
        POOL a replica's backlog will claim, so shared-prefix affinity
        steering a long-context burst at one replica would overcommit its
        pages while its queue still looked short. Worst-case accounting
        (per-request aligned spans, sharing ignored) — a value >= 1.0
        means the backlog cannot coexist and the replica will be churning
        the preemption wall.

        Tiered (ISSUE 19): pages held by cold prefix entries that CAN
        spill to host room are reclaimable-without-loss, so they relieve
        pressure — capacity grows by min(host free, reclaimable). The
        untiered math is byte-identical to the pre-tier engine."""
        if self._page_size is None:
            return 0.0
        cap = self.cache.alloc.capacity
        if self.tier is not None:
            cap += min(self.tier.free_pages, self.cache.reclaimable_pages())
        cap = max(cap, 1)
        span = 0
        live = [r for r in self._slot_req if r is not None]
        live += [r for r in self.scheduler.queued_requests]
        for r in live:
            cols = (
                len(r.prompt) + len(r.tokens) + r.remaining_new_tokens
                + self._round_cols - 1
            )
            # +1: the page-alignment gap a paged admission may pay
            span += self.cache.page_span(0, min(cols, self.max_seq_len)) + 1
        return span / cap

    def load_score(self, tenant: Optional[str] = None) -> float:
        """The router's balancing signal: work in the building (active
        slots + queued requests) plus the page-pressure term scaled to
        slot units, so a replica whose pool is nearly committed reads as
        loaded even with a short queue.

        With ``tenant=`` the score grows the policy's per-tenant
        attainment term (ISSUE 16 tentpole (d)): a replica where THIS
        tenant's SLO is under water reads as more loaded for its next
        request, so the router steers toward the replica where the
        tenant's SLO is healthiest. The FIFO policy's bias is always 0.0
        — tenant-blind routing is unchanged."""
        score = (
            float(int(self._active.sum()) + self.scheduler.queued)
            + self.page_pressure() * self.num_slots
        )
        if tenant is not None:
            score += self.policy.route_bias(tenant) * self.num_slots
        return score

    def adopt(self, req: Request, on_token=None) -> Request:
        """Take over a live ``Request`` minted by ANOTHER engine (the
        replica router's re-homing path — a HALTED replica's requeued work
        moves to survivors). The request keeps its rid (engines under one
        router mint from disjoint ``rid_base`` ranges), its streamed
        tokens, and its host-current key, so the continuation here is
        bit-identical to the stream the dead replica would have produced:
        admission re-prefills ``context_ids`` and resumes at ``req.key``
        — the same contract as preemption-resume."""
        health = self.health()
        if health in (EngineHealth.DRAINING, EngineHealth.HALTED):
            raise RejectedError(
                f"engine is {health.value}; cannot adopt re-homed work",
                queue_depth=self.scheduler.queued,
            )
        if req.rid in self.scheduler.requests:
            raise ValueError(
                f"rid {req.rid} already known to this engine — replicas "
                "under one router must mint from disjoint rid_base ranges"
            )
        req.slot = None
        self.scheduler.submit(req)
        self.metrics.record_adopt(req, self._now())
        if on_token is not None:
            self._on_token[req.rid] = on_token
        self.tracer.begin(
            req.rid,
            args={
                "prompt_len": int(len(req.prompt)),
                "tenant": req.tenant,
                "priority": req.priority,
                "rehomed": True,
                "tokens_streamed": len(req.tokens),
            },
        )
        return req

    def release_queued(self, rid: int):
        """Withdraw a never-admitted QUEUED request from this engine so
        another replica can :meth:`adopt` it (the router's live-join
        rebalancing and drain paths). Returns ``(request, on_token)`` —
        the live request plus its streaming callback, both now owned by
        the caller — or ``(None, None)`` when the rid is not releasable
        (unknown, finished, already admitted, or preempted-after-
        admission: in-flight work belongs to this engine until it halts
        or finishes)."""
        req = self.scheduler.get(rid)
        if req is None or req.admit_time is not None:
            return None, None
        released = self.scheduler.release(rid)
        if released is None:
            return None, None
        cb = self._on_token.pop(rid, None)
        self.tracer.end(rid, "released", args={"rehomed_away": True})
        if self.flight is not None:
            self.flight.record("release", rid=rid, tenant=released.tenant)
        return released, cb

    # --- warm restart (ISSUE 18) --------------------------------------------

    def snapshot_serving_state(self) -> Dict[str, Any]:
        """Serialize the HOST-current serving state the halt contract
        defines — the queue (actives first, in slot-roll order by rid,
        exactly as a halt would requeue them), every unfinished request's
        tokens / PRNG key / deadlines / tenant+priority attribution, the
        prefix-cache token index (which prefixes were hot — an advisory
        for re-warming, NOT the KV bytes), and the SLO tracker's counters.
        No device pytrees: programs come back via the AOT cache
        (``prewarm``), KV is re-prefilled from host tokens by the same
        resume machinery preemption uses.

        The result is JSON-safe. Timestamps are absolute on THIS engine's
        clock; :meth:`restore_serving_state` shifts them onto the restored
        engine's clock so every remaining deadline budget is preserved
        across the restart."""
        active = sorted(
            (r for r in self._slot_req if r is not None and not r.finished),
            key=lambda r: r.rid,
        )
        seen = {r.rid for r in active}
        ordered = active + [
            r for r in self.scheduler.queued_requests if r.rid not in seen
        ]
        reqs = []
        for req in ordered:
            cfg = req.config
            reqs.append({
                "rid": int(req.rid),
                "prompt": [int(x) for x in req.prompt],
                "tokens": [int(t) for t in req.tokens],
                "key": [int(k) for k in np.asarray(req.key, np.uint32).reshape(-1)],
                "config": {
                    "max_new_tokens": int(cfg.max_new_tokens),
                    "temperature": float(cfg.temperature),
                    "top_k": None if cfg.top_k is None else int(cfg.top_k),
                    "top_p": None if cfg.top_p is None else float(cfg.top_p),
                    "eos_token_id": (
                        None if cfg.eos_token_id is None else int(cfg.eos_token_id)
                    ),
                },
                "tenant": req.tenant,
                "priority": req.priority,
                "preemptions": int(req.preemptions),
                "deadline": req.deadline,
                "queue_deadline": req.queue_deadline,
                "submit_time": req.submit_time,
                "admit_time": req.admit_time,
                "first_token_time": req.first_token_time,
            })
        snap: Dict[str, Any] = {
            "version": 1,
            "now": self._now(),
            "next_rid": int(self._next_rid),
            "halted": self._halted,
            "halt_reason": self._halt_reason,
            "requests": reqs,
            "prefix_index": (
                [list(e.tokens) for e in self.prefix.entries]
                if self.prefix is not None and self.prefix.enabled
                else None
            ),
            "tenant_queue_depths": self.scheduler.queued_by_tenant(),
            "slo": (
                self.metrics.slo.state() if self.metrics.slo is not None else None
            ),
        }
        if self.flight is not None:
            self.flight.record("snapshot", requests=len(reqs))
        return snap

    def restore_serving_state(self, snap: Dict[str, Any],
                              on_token=None) -> Dict[str, Any]:
        """Bring a freshly-constructed (or resumed) engine back WARM from
        :meth:`snapshot_serving_state`: every unfinished request rejoins
        the queue in snapshot order with its streamed tokens and
        host-current key (the resume machinery continues each stream
        bit-identically), and every absolute timestamp is shifted by the
        clock delta between the snapshot and now — a request that had 4s
        of deadline budget left when the replica died has exactly 4s left
        here. ``on_token`` (optional) streams every restored request.

        Raises ``ValueError`` if any snapshot rid is already known to this
        engine — restore composes with the transport's idempotency the
        same way adopt does: state is admitted exactly once."""
        if snap.get("version") != 1:
            raise ValueError(
                f"unknown serving-state snapshot version {snap.get('version')!r}"
            )
        if self._halted:
            raise RejectedError("engine is halted; cannot restore work")
        now = self._now()
        delta = now - float(snap["now"])
        for r in snap["requests"]:
            if int(r["rid"]) in self.scheduler.requests:
                raise ValueError(
                    f"rid {r['rid']} already known to this engine — "
                    "a serving-state snapshot restores exactly once"
                )

        def _shift(t):
            return None if t is None else t + delta

        restored = 0
        for r in snap["requests"]:
            req = Request(
                rid=int(r["rid"]),
                prompt=np.asarray(r["prompt"], np.int32),
                config=GenerationConfig(**r["config"]),
                key=np.asarray(r["key"], np.uint32),
                tenant=r.get("tenant", "default"),
                priority=r.get("priority", "standard"),
            )
            req.tokens = [int(t) for t in r["tokens"]]
            req.preemptions = int(r.get("preemptions", 0))
            req.deadline = _shift(r.get("deadline"))
            req.queue_deadline = _shift(r.get("queue_deadline"))
            req.submit_time = _shift(r.get("submit_time"))
            req.admit_time = _shift(r.get("admit_time"))
            req.first_token_time = _shift(r.get("first_token_time"))
            req.slot = None
            self.scheduler.submit(req)
            self.metrics.record_adopt(req, now)
            if on_token is not None:
                self._on_token[req.rid] = on_token
            self.tracer.begin(
                req.rid,
                args={
                    "prompt_len": int(len(req.prompt)),
                    "tenant": req.tenant,
                    "priority": req.priority,
                    "restored": True,
                    "tokens_streamed": len(req.tokens),
                },
            )
            restored += 1
        self._next_rid = max(self._next_rid, int(snap["next_rid"]))
        if snap.get("slo") and self.metrics.slo is not None:
            self.metrics.slo.restore_state(snap["slo"], shift_s=delta)
        downtime = max(delta, 0.0)
        self.metrics.record_restore(restored, downtime)
        if self.flight is not None:
            self.flight.record(
                "restore", requests=restored, downtime_s=downtime
            )
        if self.timeline is not None:
            self.timeline.instant(
                "restore", "serving", args={"requests": restored}
            )
        self._sync_health()
        return {
            "restored": restored,
            "downtime_s": downtime,
            "rid_floor": int(self._next_rid),
        }

    # --- health / drain -----------------------------------------------------

    def integrity_fingerprint(self) -> int:
        """Bit-level uint32 fingerprint of this replica's PARAMS — the
        router's cross-replica integrity evidence (ISSUE 20). Params only,
        deliberately: replicas serving the same model must hold
        bit-identical weights, while KV/slot state legitimately diverges
        with each replica's traffic (KV integrity is covered separately,
        by per-page reuse validation and the page-quarantine path). The
        jitted reduction compiles on FIRST probe (lazy — un-probed engines
        compile nothing); the readback is one uint32 scalar per probe
        period, never per chunk."""
        if self._integrity_fp_fn is None:
            from neuronx_distributed_tpu.utils.fingerprint import (
                tree_fingerprint,
            )

            # per-engine lambda (see the _extract_fn note: jitting the
            # module-level helper directly would share _cache_size across
            # engines in this jax)
            self._integrity_fp_fn = self.programs.wrap(
                "integrity_fingerprint",
                jax.jit(lambda tree: tree_fingerprint(tree)),
            )
        # graftlint: ok[GL02] periodic watchdog probe readback — one uint32
        # scalar per probe period (router cadence), not a per-chunk sync
        return int(jax.device_get(self._integrity_fp_fn(self._params)))

    def health(self) -> EngineHealth:
        """Current health state (``OK/DEGRADED/DRAINING/HALTED``)."""
        if self._halted:
            return EngineHealth.HALTED
        if self._draining:
            return EngineHealth.DRAINING
        if (
            self.cache.usable_slots < self.num_slots
            or getattr(self.cache, "degraded", False)
            or (
                self._had_dispatch_failure
                and self._chunks_since_failure < self._degraded_cooldown
            )
        ):
            return EngineHealth.DEGRADED
        return EngineHealth.OK

    @property
    def halt_reason(self) -> Optional[str]:
        return self._halt_reason

    def drain(self) -> None:
        """Stop admitting NEW work: submissions are rejected, never-admitted
        queued requests stay queued (and stop counting as work), requests
        already admitted — active in a slot or preempted back to the queue —
        run to completion. ``run()`` returns once in-flight work finishes."""
        self._draining = True
        if self.timeline is not None:
            self.timeline.instant("drain", "serving")
        self._sync_health()

    def resume(self) -> None:
        """Leave DRAINING and accept work again (no-op while HALTED)."""
        self._draining = False
        self._sync_health()

    def fence(self, reason: str = "fenced") -> None:
        """Operator/watchdog kill switch: take the engine to HALTED *now*
        through the standard halt contract — in-flight work is vacated
        with host-current tokens/keys and requeued (never stranded), the
        post-mortem flight dump is written, and ``run()`` stops making
        progress. The router's watchdog calls this when a replica is
        declared dead so its queue can be re-homed or snapshot-restored;
        idempotent on an already-halted engine."""
        if self._halted:
            return
        self._halt(f"fenced: {reason}")

    def _halt(self, reason: str) -> None:
        # the HALTED contract: in-flight work is REQUEUED, never stranded.
        # The dispatch-recovery and quarantine paths vacate their slots
        # before halting (this is a no-op there); a prefill-failure halt
        # reaches here with requests still actively decoding — push them
        # back to the queue with their host-current tokens/keys so an
        # operator handing off scheduler.requests loses nothing
        requeued = self._vacate_active()
        # a chunk still on the device is dropped unread: every stream is
        # host-current through the chunk before it, and the cache it wrote
        # is rewound with the rest
        unread, self._in_flight = self._in_flight, None
        if requeued or unread is not None:
            self.scheduler.requeue_front(requeued)
            self.cache.release_all_slots()
            self.cache.reset()
            if self.draft_cache is not None:
                self.draft_cache.release_all_slots()
                self.draft_cache.reset()
            self._state = self._fresh_slot_state()
        if self.prefix is not None:
            # PR 3 recovery contract, prefix edition: no in-flight suffix
            # prefill survives a halt, so no pin may either — a leaked ref
            # would block eviction forever. Entries themselves stay valid
            # (independent copies, untouched by cache loss)
            self.prefix.release_all()
        self._halted = True
        self._halt_reason = reason
        if self.timeline is not None:
            self.timeline.instant("halted", "serving", args={"reason": reason})
        self._sync_health()
        # post-mortem: the flight ring (recent transitions/faults) plus the
        # metrics snapshot, written atomically BEFORE control returns to the
        # operator; the timeline flushes too so the trace survives a crash
        if self.flight is not None:
            self.flight.record("halt", reason=reason)
            # who was being starved when the engine died: per-tenant queue
            # depths AFTER the requeue (so in-flight victims count), plus
            # the SLO attainment state — kept FLAT enough that the flight
            # recorder's depth-capped redaction preserves every scalar
            # (tests/observability/test_flight_recorder.py pins the schema)
            # analyze_programs=False: an error path must not start
            # tracing programs for cost analysis — and the nested
            # efficiency blocks are dropped from the embedded snapshot
            # (the depth-3 redaction would collapse them to key-count
            # stubs anyway; the FLAT tables below are the readable
            # carriers)
            metrics_snap = self.metrics.snapshot(analyze_programs=False)
            metrics_snap.pop("programs", None)
            metrics_snap.pop("hbm", None)
            extra = {
                "requeued": len(requeued),
                "metrics": metrics_snap,
                "tenant_queue_depths": self.scheduler.queued_by_tenant(),
                # where HBM actually went and which programs were hot when
                # the engine died — flat scalar tables shaped to survive
                # the flight recorder's depth-3 redaction
                "hbm": self.hbm.halt_summary(),
                "programs": self.programs.halt_summary(),
            }
            if self.tier is not None:
                # where the spill tier stood when the engine died — flat
                # scalars (occupancy + lifetime traffic), same redaction
                # contract as the hbm/programs tables above
                extra["kv_host_tier"] = self.tier.summary()
            if self.metrics.slo is not None:
                extra["slo"] = self.metrics.slo.per_tenant()
                extra["slo_totals"] = self.metrics.slo.totals()
            self.flight.dump(reason, extra=extra)
        if self.timeline is not None:
            self.timeline.save()

    def _sync_health(self) -> None:
        h = self.health()
        self.metrics.health = h.value
        if h is not self._last_health:
            if self.timeline is not None:
                self.timeline.instant(f"health {h.value}", "serving")
            if self.flight is not None:
                self.flight.record(
                    "health", value=h.value, was=self._last_health.value
                )
            self._last_health = h

    @property
    def has_work(self) -> bool:
        if self._halted:
            # requeued work survives in the queue for inspection/handoff,
            # but a halted engine makes no progress — run() must exit
            return False
        if self._in_flight is not None:
            return True  # a chunk on the device is read back by a step
        if self._draining:
            return any(self._active) or any(
                r.admit_time is not None
                for r in self.scheduler.queued_requests
            )
        return self.scheduler.queued > 0 or any(self._active)

    @property
    def queue_depth(self) -> int:
        """Queued (unfinished) requests — the export-gauge source."""
        return self.scheduler.queued

    @property
    def decode_compilations(self) -> int:
        """How many distinct decode programs XLA compiled. Stays 1 across
        arbitrary slot churn AND arbitrary per-slot acceptance patterns —
        the continuous-batching invariant (one program per engine, whatever
        the chunk size or gamma; ragged speculative advance is data, not
        shape). A speculative engine that ever exercised the
        non-speculative fallback counts that program too (so the invariant
        is 1 on any fault-free run)."""
        n = 0
        if self._decode_chunk is not None:
            n += int(self._decode_chunk._cache_size())
        if self._spec_chunk is not None:
            n += int(self._spec_chunk._cache_size())
        return n

    @property
    def prefill_compilations(self) -> int:
        """How many distinct prefill programs XLA compiled — full prefills
        (one per padded ``_bucket`` length actually used, target plus
        draft), plus suffix prefills (one per ``_suffix_bucket`` chunk
        length), so growth is bounded by the bucket counts (powers of two
        plus exact fallbacks), never by request count or prefix-cache
        churn."""
        return (
            sum(int(fn._cache_size()) for fn in self._prefill_fns.values())
            + sum(
                int(fn._cache_size())
                for fn in self._draft_prefill_fns.values()
            )
            + int(self._suffix_fn._cache_size())
        )

    @property
    def prefix_compilations(self) -> int:
        """Prefix-cache maintenance programs XLA compiled (extract + seed,
        one per storage bucket; fingerprint, one per entry shape) — bounded
        by the ``_prefix_bucket`` count. Paged engines count their
        seed-from-pages programs instead (one per shared page count)."""
        return sum(
            int(fn._cache_size())
            for fn in (self._extract_fn, self._seed_fn, self._fingerprint_fn)
        ) + getattr(self.cache, "seed_compilations", 0)

    # --- AOT serving (inference/aot.py) ----------------------------------

    def manifest(self):
        """:class:`~..inference.aot.ProgramManifest` of every program this
        engine has compiled so far — the prewarm input for the NEXT
        process (persist it next to the checkpoint via ``.save(dir)``)."""
        return self.programs.manifest()

    # manifest program name → engine attribute, for resolve and install
    _AOT_FIXED = {
        "decode_chunk": "_decode_chunk",
        "spec_decode_chunk": "_spec_chunk",
        "slot_write": "_slot_write",
        "slot_clear": "_slot_clear",
        "first_token": "_first_token",
        "suffix_prefill": "_suffix_fn",
        "prefix_extract": "_extract_fn",
        "prefix_seed": "_seed_fn",
        "prefix_fingerprint": "_fingerprint_fn",
    }
    # cache-manager program stem → manager attribute (Slot + Paged)
    _AOT_CACHE = {
        "cache_admit": "_admit_fn", "cache_free": "_free_fn",
        "cache_reset": "_reset_fn",
        "paged_admit": "_admit_fn", "paged_seed": "_seed_fn",
        "paged_free": "_free_fn", "paged_reset": "_reset_fn",
        "paged_stage": "_stage_fn", "paged_map": "_map_fn",
        "paged_import": "_import_fn",
    }

    def _aot_cache_site(self, name: str):
        """(manager, attr) for a cache-manager program name, else None."""
        mgr, stem = self.cache, name
        if name.startswith("draft_"):
            mgr, stem = self.draft_cache, name[len("draft_"):]
        attr = self._AOT_CACHE.get(stem)
        if mgr is None or attr is None or not hasattr(mgr, attr):
            return None
        return mgr, attr

    def _aot_resolve(self, name: str):
        """Live ledger proxy for a manifest program name — building lazy
        per-bucket programs on demand. None when this engine cannot host
        the program (e.g. a draft program on a non-speculative engine)."""
        attr = self._AOT_FIXED.get(name)
        if attr is not None:
            fn = getattr(self, attr, None)
            if fn is None and name == "decode_chunk" and self._spec_chunk is not None:
                # speculative engine: the plain-chunk fallback is built
                # lazily — a manifest that saw it means prewarm should too
                fn = self._nonspec_chunk()
            return fn
        m = re.fullmatch(r"(draft_)?prefill\[(\d+)\]", name)
        if m is not None:
            try:
                if m.group(1):
                    return self._draft_prefill_fn(int(m.group(2)))
                return self._prefill_fn(int(m.group(2)))
            except Exception:
                return None
        site = self._aot_cache_site(name)
        if site is not None:
            return getattr(site[0], site[1])
        return None

    def _aot_install(self, name: str, shim) -> bool:
        """Install a deserialized-executable shim at the program's
        dispatch site, re-wrapped by the ledger so counting survives."""
        wrapped = self.programs.wrap(name, shim)
        attr = self._AOT_FIXED.get(name)
        if attr is not None:
            setattr(self, attr, wrapped)
            return True
        m = re.fullmatch(r"(draft_)?prefill\[(\d+)\]", name)
        if m is not None:
            fns = (
                self._draft_prefill_fns if m.group(1) else self._prefill_fns
            )
            fns[int(m.group(2))] = wrapped
            return True
        site = self._aot_cache_site(name)
        if site is not None:
            setattr(site[0], site[1], wrapped)
            return True
        return False

    def prewarm(self, manifest=None, cache_dir: Optional[str] = None,
                mode: str = "auto") -> dict:
        """Restore or compile the full program set BEFORE the first
        request — bucket prefills, decode/spec chunks, slot write/clear,
        paged admit/seed/stage/map — so the first request's TTFT contains
        zero compiles and ``decode_compilations`` stays 1 (or 0 when the
        decode chunk deserialized). ``manifest`` is a
        :class:`~..inference.aot.ProgramManifest` or a path; with
        ``cache_dir`` alone the manifest is read from
        ``cache_dir/manifest.json``, serialized executables from
        ``cache_dir/*.aotx``, and the persistent compile cache is pointed
        at ``cache_dir/xla``. ``mode="trace"`` skips executable artifacts
        (pure replay prewarm). Fail-soft throughout: skew, unportable, or
        unresolvable entries degrade to the next rung with a flight
        event; returns the per-program report."""
        from neuronx_distributed_tpu.inference import aot

        if cache_dir is not None:
            aot.enable_persistent_cache(os.path.join(cache_dir, aot.XLA_SUBDIR))
        if manifest is None:
            if cache_dir is None:
                raise ValueError("prewarm needs a manifest or a cache_dir")
            manifest = aot.ProgramManifest.load(cache_dir)
        elif isinstance(manifest, (str, os.PathLike)):
            manifest = aot.ProgramManifest.load(os.fspath(manifest))
        report = aot.prewarm_programs(
            manifest,
            self._aot_resolve,
            ledger=self.programs,
            artifact_dir=cache_dir,
            install=self._aot_install,
            mode=mode,
            flight=self.flight,
        )
        return report

    def save_aot(self, cache_dir: str) -> dict:
        """Persist this engine's full AOT bundle into ``cache_dir``:
        ``manifest.json``, one serialized executable per captured program
        signature, and the persistent compile cache under ``xla/`` (so a
        later trace-level prewarm against this dir is all disk hits). The
        per-program ``lower().compile()`` each serialization needs runs
        with the disk cache BYPASSED — a cache-loaded executable
        serializes without its object code and cannot cross a process
        boundary (aot.compile_serializable). Per-program failures are
        skipped and reported, never raised."""
        from neuronx_distributed_tpu.inference import aot

        os.makedirs(cache_dir, exist_ok=True)
        aot.enable_persistent_cache(os.path.join(cache_dir, aot.XLA_SUBDIR))
        manifest = self.manifest()
        # merge-don't-clobber: a PREWARMED engine's ledger has no captured
        # signatures for deserialized programs (they never compiled here),
        # so a blind overwrite would erase the very entries the next
        # process needs; keep prior entries for programs this run can't
        # re-describe
        try:
            prior = aot.ProgramManifest.load(cache_dir)
            for pname, entries in prior.programs.items():
                manifest.programs.setdefault(pname, entries)
        except Exception:
            pass
        manifest.save(cache_dir)
        report: Dict[str, Any] = {"saved": [], "skipped": {}}
        for name, info in self.programs.programs().items():
            for var in info.variants:
                key = (
                    f"{name}@{var.signature}"
                    if len(info.variants) > 1 else name
                )
                try:
                    lowered = var.lower()
                    if lowered is None:
                        report["skipped"][key] = "signature not captured"
                        continue
                    compiled = aot.compile_serializable(lowered)
                    aot.save_executable(
                        cache_dir, name, var.signature, compiled
                    )
                    report["saved"].append(key)
                except Exception as e:
                    report["skipped"][key] = (
                        f"{type(e).__name__}: {e}"[:200]
                    )
        if self.flight is not None:
            self.flight.record(
                "aot_save", dir=cache_dir,
                saved=len(report["saved"]), skipped=len(report["skipped"]),
            )
        return report

    def step(self) -> bool:
        """One engine iteration: reap cancellations → shed expired deadlines
        → preempt/rewind if the cursor is out of room → admit+prefill → one
        fused decode chunk (with recovery) → retire finished slots. Returns
        whether work remains."""
        if self._halted:
            return self.has_work
        if self._faults is not None:
            # chaos (ISSUE 20): a scheduled silent bit flip lands on the
            # bound weights here — every program after this step serves
            # from the corrupted tree, exactly like real HBM rot, until
            # the router's fingerprint vote fences this replica
            self._params = self._faults.on_engine_params(
                self._steps_seen, self._params
            )
        self._steps_seen += 1
        compiles = self.programs.compiles
        with self._span(tracing.STEP) as sp:
            self._step()
            if self._ledger is not None:
                # the ledger closes its step inside: this span's wall goes
                # to the profiler and the timeline, not into the record
                with self._span(tracing.STEP_CLOSE):
                    self._close_step(sp, compiles)
        return self.has_work

    def _close_step(self, sp, compiles_before: int) -> None:
        """The step's account, as its span closes: one record into the
        flight recorder's step ring, the stepping thread's CPU time and the
        run's overrun seconds onto the span, and, for a step the ledger
        judged to have overrun, the ``slow_step`` record."""
        record = self._ledger.finish(self.programs.compiles - compiles_before)
        if record is None:
            return
        if record["overran"]:
            self._report_slow_step(record)
        sp.set_metadata(
            cpu_us=int(1e6 * record["thread_cpu_s"]),
            overrun_us=int(1e6 * self._ledger.overrun_seconds),
        )

    def _report_slow_step(self, record: dict) -> None:
        """ONE flight event and ONE warning line for a step that overran:
        which phase, whether the thread ran or sat, what the host did to
        it, and where the watchdog found it (README, "when a step
        stalls")."""
        fields = {
            k: record[k] for k in (
                "step", "since_start_s", "wall_s", "expected_s", "phases",
                "thread_cpu_s", "process_cpu_s", "voluntary_switches",
                "involuntary_switches", "major_faults", "run_delay_s",
                "prefills", "active", "samples",
            ) if k in record
        }
        event = self.flight.record("slow_step", **fields)
        self.metrics.record_step_overrun(
            record["wall_s"] - record["expected_s"]
        )
        logger.warning("slow_step %s", json.dumps(event))

    def _kv_bytes_stats(self) -> dict:
        """For the dispatch span, from the allocated cache leaves (whole
        bytes: a span's stats are host ints): ``kv_bytes_per_token_layer``,
        the bytes a token holds per cache node, and ``kv_cache_nodes``, the
        nodes (one an attention layer, and for a stack run more than once
        over one set of weights one a layer a PASS): their product is what a
        token holds. The ``serving_kv_*`` gauges carry the same."""
        if not self.metrics.kv_cache_nodes:
            self.metrics.record_kv_bytes(*cache_token_bytes(self.cache.cache))
        m = self.metrics
        return {
            "kv_bytes_per_token_layer": int(round(m.kv_bytes_per_token_layer)),
            "kv_cache_nodes": int(m.kv_cache_nodes),
        }

    def _held_tokens(self, unread: int = 0) -> List[int]:
        """Tokens each decoding slot holds (its prompt, what it emitted and
        the ``unread`` tokens of a chunk still on the device)."""
        return [
            len(r.prompt) + len(r.tokens) + unread
            for s, r in enumerate(self._slot_req)
            if r is not None and self._active[s]
        ]

    def _selection_stats(self, unread: int = 0) -> dict:
        """``ctx_tokens`` (tokens the decoding slots hold) and
        ``selected_tokens`` (``sum(min(held, topk))``: what a sparse-attention
        model's decode step attends) for the dispatch span: host arithmetic
        from the slots' requests, no readback. Empty for a model without an
        indexer (``config.index_topk``)."""
        topk = getattr(getattr(self.model, "config", None), "index_topk", None)
        if topk is None:
            return {}
        held = self._held_tokens(unread)
        return {
            "ctx_tokens": int(sum(held)),
            "selected_tokens": int(sum(min(n, int(topk)) for n in held)),
        }

    def _page_stats(self) -> dict:
        """For a paged cache, on the dispatch span: ``full_pages_mapped`` /
        ``full_pages_in_runs`` (the pages the block table maps, and those of
        them in runs of adjacent pool pages that the block-walking decode
        kernels fetch with one copy) and, for a model with window layers, the
        window kind's ``window_pages_mapped`` / ``window_pages_in_runs``: host
        arithmetic on the tables, made when a table is uploaded."""
        return dict(getattr(self.cache, "page_stats", None) or {})

    def _window_stats(self, unread: int = 0) -> dict:
        """For a model with window layers, on the dispatch span:
        ``ctx_tokens`` (tokens the decoding slots hold) and ``window_tokens``
        (``sum(min(held, window))``: what a window layer's decode step
        attends). Empty for every other model."""
        window = getattr(self.cache, "window", None)
        if window is None:
            return {}
        held = self._held_tokens(unread)
        return {
            "ctx_tokens": int(sum(held)),
            "window_tokens": int(sum(min(n, int(window)) for n in held)),
        }

    def _slot_state_stats(self) -> dict:
        """For a model whose layers keep per-slot state beside their pages,
        on the dispatch span: ``slot_state_bytes_per_layer``, the bytes a
        slot's state leaves hold a layer, from the allocated leaves, and,
        where some layers are RECURRENT (a ``recur`` leaf and no page), how
        many they are and how many page (``recurrent_layers`` /
        ``paged_layers``). Empty for every other model."""
        if not getattr(self.cache, "slot_state", False):
            return {}
        if self._slot_state is None:
            self._slot_state = self._measure_slot_state()
        return self._slot_state

    def _measure_slot_state(self) -> dict:
        """The dispatch span's stats from the allocated cache, read once;
        the ``serving_slot_state_bytes`` gauge takes all slots' and all
        layers' bytes."""
        tree = self.cache.cache
        tree = tree["pool"] if isinstance(tree, dict) and "pool" in tree else tree
        recurrent, paged, total = set(), set(), 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            name, layer = path[-1].key, tuple(str(k) for k in path[:-1])
            if name in PAGED_LEAVES:
                paged.add(layer)
            elif name in SLOT_STATE_LEAVES:
                total += leaf.nbytes
                if name == "recur":
                    recurrent.add(layer)
        self.metrics.record_slot_state_bytes(total)
        stats = {"slot_state_bytes_per_layer": int(round(slot_state_bytes_per_layer(tree)))}
        if recurrent:
            stats.update(recurrent_layers=len(recurrent), paged_layers=len(paged))
        return stats

    def _sampled_slots(self) -> int:
        """The decoding slots whose request samples (``temperature != 0``),
        for the dispatch span and the ``greedy_chunks_dispatched`` counter:
        the count the chunk's sampler branches on (``sample_per_row``'s
        ``kept`` rows), from the slots' requests. At 0 every step of the
        chunk takes its tokens by ``argmax`` alone."""
        return sum(
            1 for s, r in enumerate(self._slot_req)
            if r is not None and self._active[s]
            and np.float32(r.config.temperature) != 0.0
        )

    def _span(self, name: str, **stats):
        """A phase of ``step()``: the one span primitive, bound to this
        engine's timeline (``observability/tracing.py``)."""
        return tracing.span(
            name, self.timeline, ledger=self._ledger, **stats
        )

    def _step(self) -> None:
        now = self._now()
        with self._span(tracing.STEP_REAP):
            self._reap_cancelled(now)
            self._shed_expired(now)
            # a chunk run ahead is on the device: this step reads it back
            # and does nothing else at the boundary. The rule that let it
            # run saw nothing due here (_can_run_ahead); what no rule can
            # see (an EOS, a cancel, a deadline: retired above, or at the
            # last emit) waits one chunk for its free slot, until the step
            # that finds nothing unread
            unread = self._in_flight is not None
            if unread:
                self._in_flight.followed = self._can_run_ahead()
            wall = not unread and any(self._active) and (
                self.cache.cursor + self._round_cols > self.max_seq_len
            )
            if not (unread or wall or any(self._active)) and (
                self.cache.cursor > 0
            ):
                self._rewind_drained()
        # one more dispatch needs _round_cols columns (gamma per
        # speculative round, 1 per plain step); preempt-and-rewind when the
        # wall is closer than that. Speculation spends columns faster than
        # tokens (rejected drafts leave gap columns), so this wall can
        # arrive earlier than the token-based admission projected — the
        # preemption machinery keeps streams bit-identical either way
        if wall:
            self._preempt_all()
        if not unread:
            # SLO-driven preemption (ISSUE 16): when the slot set is full
            # and an under-attaining tenant's work is waiting, the policy
            # may nominate victims (FIFO never does) — vacated through the
            # same host bookkeeping as quarantine-requeue, so streams stay
            # bit-identical and the freed slots admit below in THIS step
            victims = self.policy.victims(now)
            if victims:
                with self._span(tracing.STEP_PREEMPT):
                    self._preempt_victims(victims, now)
            self._admit(now)
        if not self._halted and (unread or any(self._active)):
            self._decode()
        with self._span(tracing.STEP_HEALTH):
            if self.timeline is not None:
                self.timeline.counter(
                    "slots_active", int(self._active.sum()), "serving"
                )
                self.timeline.counter(
                    "queue_depth", self.scheduler.queued, "serving"
                )
            self._sync_health()

    def _rewind_drained(self) -> None:
        """Drained: rewind the shared cursor so the next wave starts at
        column 0 (storage reused, nothing reallocated)."""
        self.cache.reset()
        if self.draft_cache is not None:
            self.draft_cache.reset()

    def run(self, max_steps: int = 1_000_000) -> Dict[int, Request]:
        """Step until idle (or HALTED); returns every request this engine
        has seen."""
        steps = 0
        while self.has_work and steps < max_steps:
            self.step()
            steps += 1
        return {r.rid: r for r in self.scheduler.requests.values()}

    # --- deadlines ----------------------------------------------------------

    def _shed_expired(self, now: float) -> None:
        """Queue timeouts shed BEFORE prefill (no compute wasted on a
        request that already missed its window); in-flight deadlines are
        enforced here, at the chunk boundary — the shed request keeps every
        token it streamed."""
        for req, reason in self.scheduler.expire(now):
            req.state = RequestState.TIMED_OUT
            req.error = reason
            req.finish_time = now
            self.metrics.record_shed(req, now, where="queue")
            self._on_token.pop(req.rid, None)
            if self.timeline is not None:
                self.timeline.instant(
                    f"shed r{req.rid}", "serving",
                    args={"where": "queue", "reason": req.error},
                )
            self.tracer.end(
                req.rid, "shed",
                args={"where": "queue", "tenant": req.tenant},
            )
            if self.flight is not None:
                self.flight.record("shed", rid=req.rid, where="queue",
                                   reason=req.error, tenant=req.tenant)
        for req in list(self._slot_req):
            if req is None or req.deadline is None or now < req.deadline:
                continue
            req.state = RequestState.TIMED_OUT
            req.error = "deadline exceeded mid-generation"
            req.finish_time = now
            self.metrics.record_shed(req, now, where="inflight")
            if self.timeline is not None:
                self.timeline.instant(
                    f"shed r{req.rid}", "serving",
                    args={"where": "inflight", "tokens": len(req.tokens)},
                )
            self.tracer.end(
                req.rid, "shed",
                args={"where": "inflight", "tokens": len(req.tokens),
                      "tenant": req.tenant},
            )
            if self.flight is not None:
                self.flight.record("shed", rid=req.rid, where="inflight",
                                   tokens=len(req.tokens),
                                   tenant=req.tenant)
            self._release_slot(req)

    # --- admission ----------------------------------------------------------

    def _in_flight_tokens(self) -> int:
        return sum(
            r.token_footprint for r in self._slot_req if r is not None
        )

    def _admit(self, now: float) -> None:
        if self.external_prefill:
            return  # the disaggregation server owns admission
        if self.cache.free_slots == 0 or self.scheduler.queued == 0:
            return
        with self._span(tracing.STEP_ADMIT):
            selected = self._select(now)
        for idx, req in enumerate(selected):  # longest-prefill-first
            self._prefill_into_slot(req, self.cache.acquire())
            if self._halted:
                # a prefill-failure halt mid-batch: the rest of this round
                # was already popped from the queue — put it back intact
                rest = selected[idx + 1:]
                if rest:
                    self.scheduler.requeue_front(rest)
                break

    def _select(self, now: float) -> List[Request]:
        """Admission without the prefills: prefetch, the fit projection and
        the scheduler's selection for the free slots."""
        # tiered KV (ISSUE 19): start host->device prefetches for queued
        # requests whose prefix match is host-resident BEFORE selection —
        # the async import dispatch overlaps the current chunk's device
        # time, and the pages' prefetch holds keep the fit math below
        # honest (held pages are not reclaimable)
        self._prefetch_for_queue()
        proj = self.cache.cursor
        maxrem = max(
            (r.remaining_new_tokens for r in self._slot_req if r is not None),
            default=0,
        )

        # paged: the free-page accounting that replaces seq-len-class-only
        # gating — per-slot context starts feed the worst-case page spans
        spans_starts = (
            list(self.cache.active_spans())
            if self._page_size is not None else []
        )
        eager_claimed = 0

        def fits(req: Request) -> bool:
            nonlocal proj, maxrem, eager_claimed
            if self._draining and req.admit_time is None:
                # drain admits only work that was already in flight once
                # (preempted/recovered requests rejoin at the queue FRONT,
                # so fresh requests behind them cannot starve them)
                return False
            p = len(req.context_ids)
            # the padded prompt must leave room for the remaining
            # generation AND (speculative engines) the final round's
            # gamma-token window — _round_cols - 1 == 0 on the plain path
            if self._page_size is not None:
                bucket, target = self._paged_layout(
                    p, req.remaining_new_tokens + self._round_cols - 1, proj
                )
            else:
                bucket = _bucket(
                    p, self.max_seq_len,
                    req.remaining_new_tokens + self._round_cols - 1,
                )
                target = max(proj, bucket)
            if self.admission == "conservative":
                # all slots step together, so the cursor's final resting
                # place is the admission cursor plus the LONGEST remaining
                # generation in flight — a long prompt's cursor jump must
                # not strand the slots already running (they'd hit the
                # preemption wall conservative mode promises to avoid).
                # Speculation is TOKEN-optimistic here: a column costs one
                # token only when accepted, so a poor-acceptance run can
                # still hit the preempt-rewind wall (documented trade; the
                # all-accept case matches this projection exactly)
                if (
                    target + max(maxrem, req.remaining_new_tokens)
                    + self._round_cols - 1
                    > self.max_seq_len
                ):
                    return False
                if self._page_size is not None:
                    # every in-flight + selected context's pages through
                    # the projected final cursor must fit the pool (shared
                    # pages double-counted, early retirement ignored —
                    # strictly conservative, so the no-preemption promise
                    # extends to the page-pressure wall)
                    t_end = min(
                        self.max_seq_len,
                        target + max(maxrem, req.remaining_new_tokens)
                        + self._round_cols - 1,
                    )
                    spans = self.cache.page_span(target - p, t_end) + sum(
                        self.cache.page_span(s, t_end) for s in spans_starts
                    )
                    if spans > self.cache.alloc.capacity:
                        return False
            else:
                if target + self._round_cols > self.max_seq_len:
                    # eager: just the prefill + one decode round must fit;
                    # the preemption path recovers the rest
                    return False
                if self._page_size is not None:
                    # eager page gate: this round's prefill pages plus one
                    # decode window (clamped to the request's remaining
                    # work, matching _ensure_decode_pages — an unclamped
                    # window would starve short-tail requests a small pool
                    # can in fact serve), against what the pool can free
                    # up (reclaimable prefix entries included)
                    window = (
                        min(
                            self.decode_chunk_size,
                            max(req.remaining_new_tokens, 1),
                        ) * self._round_cols
                    )
                    need = self.cache.page_span(
                        target - p,
                        min(self.max_seq_len, target + window),
                    )
                    # in-flight prefetches (ISSUE 19): pages prefetched
                    # FOR THIS REQUEST are device-resident under a hold —
                    # excluded from available_pages() so the reclaim
                    # valve cannot spill them back out, yet still counted
                    # in ``need`` (the span covers the matched prefix's
                    # columns). Credit them here or a tight pool
                    # livelocks: the hold depresses availability below a
                    # bar the adoption path never actually has to clear
                    avail = self.cache.available_pages()
                    if self.prefix is not None:
                        peeked = self.prefix.peek(req.context_ids)
                        if (
                            peeked is not None
                            and peeked[0].page_ids
                            and self.cache.prefetch_held(
                                peeked[0].page_ids
                            )
                        ):
                            avail += len(peeked[0].page_ids)
                    if eager_claimed + need > avail:
                        return False
                    eager_claimed += need
            proj = target
            maxrem = max(maxrem, req.remaining_new_tokens)
            if self._page_size is not None:
                spans_starts.append(target - p)
            return True

        cost = None
        if self.prefix is not None:
            # effective prefill work: context minus the reusable prefix (a
            # read-only peek — no LRU state moves until the real lookup).
            # Longest-EFFECTIVE-prefill-first keeps the overlap rationale
            # when a long shared context is actually a cheap suffix
            def cost(req: Request) -> int:
                return len(req.context_ids) - self.prefix.match_len(
                    req.context_ids
                )

        return self.scheduler.select(
            self.cache.free_slots, self._in_flight_tokens(), fits,
            prefill_cost=cost, now=now,
        )

    def _prefill_fn(self, padded_len: int):
        fn = self._prefill_fns.get(padded_len)
        if fn is None:
            prefill = self._prefill_model
            if self._bucket_rows:
                # the row this program gives out has the bucket's columns
                prefill = prefill.clone(config=dataclasses.replace(
                    prefill.config, max_seq_len=padded_len))
            head_rows = self._prefill_head_rows
            # past the two: the model's own per-prefill counters, summed over
            # its layers (``prefill_stats``; most models: none)
            stat_names = tuple(getattr(prefill, "prefill_stats", ()))

            @jax.jit
            def fn(params, ids, mask):
                out, variables = prefill.apply(
                    params, ids, padding_mask=mask,
                    mutable=["cache", "stats"] if stat_names else ["cache"],
                )
                logits = unwrap_logits(out)
                # runs when the bucket's program is traced, not when it
                # runs: the shape is the program's own, and costs nothing
                head_rows[ids.shape[1]] = logits.shape[1]
                stats = (
                    (sown_sums(variables["stats"], stat_names),)
                    if stat_names else ()
                )
                return (logits[0, -1], variables["cache"]) + stats

            fn = self.programs.wrap(
                f"prefill[{padded_len}]", self._comms_scoped(fn)
            )
            self._prefill_fns[padded_len] = fn
        return fn

    def _draft_prefill_fn(self, padded_len: int):
        fn = self._draft_prefill_fns.get(padded_len)
        if fn is None:
            prefill = self._draft_prefill_model

            @jax.jit
            def fn(params, ids, mask):
                _, variables = prefill.apply(
                    params, ids, padding_mask=mask, mutable=["cache"]
                )
                return variables["cache"]

            fn = self.programs.wrap(
                f"draft_prefill[{padded_len}]", self._comms_scoped(fn)
            )
            self._draft_prefill_fns[padded_len] = fn
        return fn

    def _prefill_into_slot(self, req: Request, slot: int) -> None:
        """One request's prefill and slot binding. The request's clock
        starts HERE: its queue wait ends when its own prefill starts, not
        when the step that admitted it did."""
        now = self._now()
        stats = {}
        if req.admit_time is None and req.submit_time is not None:
            # a request that comes back after a preemption waited once
            stats["queue_wait_us"] = int(1e6 * (now - req.submit_time))
        with self._span(
            tracing.STEP_PREFILL, rid=req.rid,
            prompt_tokens=len(req.context_ids),
            decoding_slots=int(self._active.sum()), **stats,
        ) as sp:
            self._prefill_bind(req, slot, now, sp)

    def _prefill_bind(self, req: Request, slot: int, now: float, sp) -> None:
        ctx = req.context_ids
        p = len(ctx)
        target = None
        if self._page_size is not None:
            padded, target = self._paged_layout(
                p, req.remaining_new_tokens + self._round_cols - 1,
                self.cache.cursor,
            )
        else:
            padded = _bucket(
                p, self.max_seq_len,
                req.remaining_new_tokens + self._round_cols - 1,
            )
        self.tracer.step(req.rid, "admission", args={"slot": slot})
        plan = self._plan_prefix_reuse(ctx, p, padded)
        reused = plan[1] if plan is not None else 0
        self.tracer.step(req.rid, "prefix_lookup", args={"matched": reused})
        sp.set_metadata(padded=padded, reused=reused, **_flash_tiles(
            padded, p if plan is None else 0, getattr(self.model, "config", None)))
        call = self._prefill_calls
        self._prefill_calls += 1
        model_stats = ()
        t0 = self._clock()
        try:
            try:
                if self._faults is not None:
                    self._faults.on_prefill(call)
                if plan is not None:
                    entry, m_use, chunk = plan
                    s = p - m_use
                    if self._page_size is not None:
                        # ZERO-COPY seed: gather the entry's shared pool
                        # pages into the compute view directly — no entry
                        # copy exists, no page is allocated or written
                        row = self.cache.seed_row(
                            entry.page_ids[:m_use // self._page_size],
                            m_use, padded - p,
                            # the chunk (under 2 s) is written at padded - s
                            length=min(2 * padded, self.max_seq_len)
                            if self._bucket_rows else None,
                        )
                    else:
                        # seed a fresh row from the stored prefix COPY (the
                        # entry is pinned, read, never aliased or donated),
                        # then prefill only the uncached tail through the
                        # decode-mode cache-write path at the prefix's cursor
                        row = self._seed_fn(
                            entry.tree,
                            jnp.asarray(m_use, jnp.int32),
                            jnp.asarray(padded - p, jnp.int32),
                            self.max_seq_len,
                        )
                    sfx_ids, _ = pack_padded_prompt(
                        ctx[m_use:], chunk, pad_side="right"
                    )
                    logits, row_cache = self._suffix_fn(
                        self._params, row, jnp.asarray(sfx_ids),
                        jnp.asarray(s, jnp.int32),
                    )
                else:
                    ids, mask = pack_padded_prompt(ctx, padded)
                    logits, row_cache, *model_stats = self._prefill_fn(padded)(
                        self._params, jnp.asarray(ids), jnp.asarray(mask)
                    )
                    # a program restored without a trace reports none
                    if padded in self._prefill_head_rows:
                        sp.set_metadata(
                            head_rows=self._prefill_head_rows[padded]
                        )
                if self.draft_model is not None:
                    # the draft context ALWAYS full-prefills (a target
                    # prefix hit composes with it untouched: prefix
                    # entries hold target KV only — the draft is cheap by
                    # construction, so deduping its prefill is not worth a
                    # second store). No readback: the row is consumed by
                    # the donating draft admit below, zero added syncs
                    d_ids, d_mask = pack_padded_prompt(ctx, padded)
                    draft_row = self._draft_prefill_fn(padded)(
                        self._draft_params,
                        jnp.asarray(d_ids), jnp.asarray(d_mask),
                    )
            finally:
                if plan is not None:
                    self.prefix.release(plan[0])
        except Exception as e:
            # an OOM-like prefill fault fails ONE request for cause instead
            # of crashing the loop; the slot returns to the rotation.
            # Consecutive failures across requests are bounded like the
            # dispatch path: a persistently-failing prefill (bad weights
            # after a hot swap, real OOM) must not silently fail 100% of
            # traffic while health() reads OK
            if self.timeline is not None:
                self.timeline.instant(
                    f"prefill_failure r{req.rid}", "serving",
                    args={"error": str(e)[:200]},
                )
            now = self._now()
            self.cache.free(slot)
            req.state = RequestState.FAILED
            req.error = f"prefill failed: {e}"
            req.finish_time = now
            self.metrics.record_failed(req, now, kind="prefill")
            self.tracer.end(req.rid, "failed", args={"kind": "prefill"})
            if self.flight is not None:
                self.flight.record("prefill_failure", rid=req.rid,
                                   error=str(e), tenant=req.tenant)
            self._on_token.pop(req.rid, None)
            self._consecutive_prefill_failures += 1
            if (
                self._consecutive_prefill_failures
                >= self._dispatch_retry.max_attempts
            ):
                self._halt(
                    f"{self._consecutive_prefill_failures} consecutive "
                    f"prefill failures (last: {type(e).__name__}: {e})"
                )
            return
        self._consecutive_prefill_failures = 0
        self.metrics.record_prefill_wall(
            self._clock() - t0, kind="suffix" if plan is not None else "full"
        )
        self.tracer.step(
            req.rid,
            "suffix_prefill" if plan is not None else "full_prefill",
            args={"padded": padded, "reused": reused},
        )
        if self._page_size is None:
            self._remember_prefix(
                ctx, p, padded, row_cache,
                matched=plan[1] if plan is not None else 0,
            )
            self.cache.admit(row_cache, slot, padded)
            if self.draft_model is not None:
                # mirror the slot into the draft cache at the SAME cursor
                # the target admit just set — the two cursors stay in
                # lockstep, so every speculative round's windows line up
                # column-for-column
                self.draft_cache.admit(
                    draft_row, slot, padded, cursor=self.cache.cursor
                )
        else:
            from neuronx_distributed_tpu.serving.paging import PageExhausted

            m_shared = plan[1] if plan is not None else 0
            shared = (
                plan[0].page_ids[:m_shared // self._page_size]
                if plan is not None else ()
            )
            try:
                self.cache.admit(
                    row_cache, slot, padded, cursor=target, p=p,
                    shared_ids=shared, m_shared=m_shared,
                )
                if self.draft_model is not None:
                    # the draft twin maps its own pool pages at the same
                    # aligned cursor (it always full-prefills — no sharing)
                    self.draft_cache.admit(
                        draft_row, slot, padded, cursor=self.cache.cursor,
                        p=p,
                    )
            except PageExhausted as e:
                # eager-mode page pressure between fits() and admit (e.g. a
                # reclaim raced dry): nothing is mapped — put the slot and
                # the untouched request back; the wall/preempt machinery
                # owns the rest
                self.cache.free(slot)
                if self.draft_cache is not None:
                    self.draft_cache.free(slot)
                self.scheduler.requeue_front([req])
                if self.flight is not None:
                    self.flight.record("page_exhausted", rid=req.rid,
                                       error=str(e))
                return
            if m_shared:
                self.metrics.record_prefix_pages_shared(
                    m_shared // self._page_size
                )
            self._remember_prefix_paged(ctx, p, slot, matched=m_shared)
        self._bind_slot(req, slot, logits, now, sp, model_stats)

    def _bind_slot(self, req: Request, slot: int, logits, now: float,
                   sp=None, model_stats=()) -> None:
        """The admission tail shared by coupled prefill and the
        disaggregated page-table handoff: record the admit (``now``: when
        this request's prefill, or its handoff, started), sample the first
        token off ``logits`` (fresh requests only — one explicit device_get
        of the token+key pair), and activate the slot's device-resident
        state. The first token is stamped AFTER that readback, which is
        where the host waits for the prefill: TTFT includes the prefill.
        ``model_stats``: the prefill program's own counters (its model's
        ``prefill_stats``; most models: none), which ride that readback and
        become its span's stats."""
        self.metrics.record_admit(req, now)
        if req.admit_time is None:
            req.admit_time = now
        if not req.tokens:
            # fresh request: sample the first token exactly as generate()
            # does — split the request key, sample with the sub-key. The
            # sampled token and the advanced key ride ONE explicit
            # device_get (they used to be two implicit syncs — an int()
            # coercion plus an np.asarray — which GL02 now forbids;
            # tests/serving/test_host_sync.py pins the count at 1)
            carry, sub = jax.random.split(jnp.asarray(req.key))
            temp, topk, topp = _config_sentinels(req.config)
            with self._span(tracing.STEP_FIRST_TOKEN, rid=req.rid) as ft:
                # graftlint: ok[GL02] the admission path's single documented
                # sync: first token + advanced request key in one readback
                tok0_h, carry_h, *model_stats = jax.device_get(
                    (self._first_token(logits, sub, temp, topk, topp), carry,
                     *model_stats)
                )
                if model_stats:
                    ft.set_metadata(**{
                        name: int(value) for name, value in zip(
                            self._prefill_model.prefill_stats, model_stats[0])
                    })
            tok0 = int(tok0_h)
            req.key = np.asarray(carry_h, np.uint32)
            self.tracer.step(req.rid, "first_token")
            now = self._now()
            if sp is not None and req.submit_time is not None:
                sp.set_metadata(ttft_us=int(1e6 * (now - req.submit_time)))
            self._emit_token(req, tok0, now, first=True)
            if req.state is RequestState.CANCELLED:
                # the on_token callback cancelled on the FIRST token (while
                # req.slot was still None, so cancel() already recorded it):
                # the slot was acquired but never bound — free it and stop
                # before the DECODE transition would erase the cancellation
                req.finish_time = now
                self.cache.free(slot)
                self._on_token.pop(req.rid, None)
                return
        req.state = RequestState.DECODE
        req.slot = slot
        self._slot_req[slot] = req
        temp, topk, topp = _config_sentinels(req.config)
        self._state = self._slot_write(
            self._state,
            np.int32(slot),
            np.int32(req.tokens[-1]),
            jnp.asarray(req.key),
            temp, topk, topp,
            np.int32(req.remaining_new_tokens),
            np.int32(
                req.config.eos_token_id
                if req.config.eos_token_id is not None
                else -1
            ),
        )
        self._active[slot] = True
        # a request can be born finished (max_new_tokens == 1, or EOS as
        # its very first token) — retire before it ever decodes
        self._maybe_finish(req, now)

    def admit_staged(self, req: Request, staged, logits,
                     now: Optional[float] = None) -> bool:
        """Disaggregated handoff (ISSUE 14): bind an EXTERNALLY-prefilled
        context to a slot as a PAGE-TABLE operation. ``staged`` is a
        :class:`~neuronx_distributed_tpu.serving.paging.StagedContext`
        whose pages already live in THIS engine's pool (the prefill worker
        staged them there — shared-pool handoff moves zero KV bytes,
        ``PageAllocator.copy_bytes`` untouched; a distinct-pool worker
        routes through export/import first). ``logits`` is the prefill's
        last-token logits row (fresh requests sample their first token
        here, exactly like coupled admission — streams stay bit-identical).

        Returns False — staged context intact, request untouched — when
        the slot/cursor/page accounting cannot place it RIGHT NOW (no free
        slot, conservative-cursor overflow, page-span overflow); the
        caller retries at a later chunk boundary."""
        if self._page_size is None:
            raise ValueError(
                "admit_staged needs a paged engine (kv_page_size=) — the "
                "handoff is a block-table operation"
            )
        if self._halted:
            return False
        if not self.cache.staged_live(staged):
            # a pool recovery or page quarantine between prefill and
            # handoff voided the staged pages — fail loudly so the caller
            # re-prefills (returning False would retry a dead context
            # forever)
            raise ValueError(
                "staged context is no longer live (pool recovery or page "
                "quarantine voided it) — re-prefill"
            )
        if self.cache.free_slots == 0:
            return False
        now = self._now() if now is None else now
        p = staged.p
        rem = req.remaining_new_tokens
        maxrem = max(
            (r.remaining_new_tokens for r in self._slot_req if r is not None),
            default=0,
        )
        target = self.cache.aligned_target(max(self.cache.cursor, p), p)
        end = target + max(maxrem, rem) + self._round_cols - 1
        if end > self.max_seq_len:
            return False
        # conservative page projection, exactly the coupled fits() math:
        # every in-flight context's span plus the staged one through the
        # projected final cursor must fit the pool
        t_end = min(self.max_seq_len, end)
        spans = self.cache.page_span(target - p, t_end) + sum(
            self.cache.page_span(s, t_end)
            for s in self.cache.active_spans()
        )
        if spans > self.cache.alloc.capacity:
            return False
        slot = self.cache.acquire()
        try:
            self.cache.map_staged(slot, staged, cursor=target)
        except Exception:
            # nothing mapped — the slot must rejoin the rotation, or each
            # failed handoff would permanently shrink capacity
            self.cache.free(slot)
            raise
        self.tracer.step(
            req.rid, "admission",
            args={"slot": slot, "handoff": "page_table", "pages": len(
                staged.page_ids
            )},
        )
        if self.timeline is not None:
            self.timeline.instant(
                f"handoff r{req.rid}", "serving",
                args={"slot": slot, "pages": len(staged.page_ids)},
            )
        self._bind_slot(req, slot, logits, now)
        return True

    # --- prefix reuse -------------------------------------------------------

    def _plan_prefix_reuse(self, ctx, p: int, padded: int):
        """Admission-time prefix lookup. Returns ``(entry, m_use, chunk)``
        for a validated hit — ``entry`` PINNED (the caller releases it when
        the suffix prefill settles, success or failure) — or ``None`` for
        a miss, a match below ``min_match``, or an entry that failed its
        reuse-time checksum/shape validation (evicted on the spot and the
        admission falls back to the full prefill: poisoned KV never
        reaches a slot)."""
        if self.prefix is None:
            return None
        hit = self.prefix.lookup(ctx)
        if hit is None:
            self.metrics.record_prefix_miss()
            if self.timeline is not None:
                self.timeline.instant(
                    "prefix_miss", "serving", args={"prompt": p}
                )
            return None
        entry, m_use = hit
        if (
            self._page_size is not None and entry.page_ids is None
            and entry.host_ids
        ):
            # tiered (ISSUE 19): the matched entry is still host-resident
            # — the queue pre-pass missed it (or its device write was
            # page-starved). One LATE prefetch attempt now; the transfer
            # is still the async import dispatch, but the overlap window
            # is gone (metrics mark it late). Failure leaves page_ids
            # None and the floor-align below turns this into a miss —
            # the admission falls back to the full prefill
            self._prefetch_entry(entry, late=True)
            if entry.page_ids is None:
                self.metrics.record_prefix_miss()
                if self.timeline is not None:
                    self.timeline.instant(
                        "prefix_miss", "serving", args={"prompt": p}
                    )
                return None
        if self._page_size is not None:
            # zero-copy CoW reuse is PAGE-granular: only whole pinned pages
            # are shareable, so the usable match floor-aligns to the page
            # size (the unaligned tail re-prefills as part of the suffix)
            ps = self._page_size
            m_use = min(m_use // ps, len(entry.page_ids or ())) * ps
            if m_use < self.prefix.min_match:
                self.metrics.record_prefix_miss()
                if self.timeline is not None:
                    self.timeline.instant(
                        "prefix_miss", "serving", args={"prompt": p}
                    )
                return None
        reuse = self._prefix_reuses
        self._prefix_reuses += 1
        if self._faults is not None:
            self._faults.on_prefix_reuse(
                reuse, entry,
                cache=self.cache if self._page_size is not None else None,
            )
        if self._page_size is not None:
            # paged validation: host accounting first (the entry's pages
            # must still be allocated, pinned, and un-quarantined), then
            # CONTENT (ISSUE 20) — the used page prefix's fingerprints
            # recomputed on device against the insert-time record. An HBM
            # bit flip leaves the accounting perfectly healthy; only the
            # bit-level check catches it before the pages map into a slot
            used = entry.page_ids[:m_use // self._page_size]
            valid = self.cache.pages_live(used)
            if valid and entry.page_fp is not None and entry.hit_tier != "host":
                # a host-tier hit's bytes were CRC-verified by the store
                # at prefetch moments ago — re-validating on device would
                # re-check just-verified content and charge the prefetch
                # admission an extra sync (its budget is pinned at the
                # bare 2). The device-resident case is the one with an
                # open HBM-rot window, and it pays the one readback
                valid = self._validate_pages(entry, len(used))
        else:
            valid = self._validate_prefix(entry)
        if not valid:
            self.prefix.evict_entry(entry)
            self.metrics.record_prefix_validation_failure()
            self.metrics.record_prefix_eviction()
            self.metrics.record_prefix_miss()
            if self.timeline is not None:
                self.timeline.instant(
                    "prefix_poisoned", "serving",
                    args={"matched": m_use, "prompt": p},
                )
            return None
        self.prefix.pin(entry)
        if self._page_size is not None and entry.page_ids:
            # the hit is being consumed: the pin above protects the entry
            # from reclaim, so any in-flight prefetch hold has done its
            # job — void it (holds are claims for QUEUED work only)
            self.cache.release_prefetched(entry.page_ids)
        chunk = _suffix_bucket(p - m_use, padded, self.max_seq_len)
        tier = entry.hit_tier
        entry.hit_tier = "device"  # resident again: later hits are device
        self.metrics.record_prefix_hit(m_use, p, tier=tier)
        if self.timeline is not None:
            self.timeline.instant(
                "prefix_hit", "serving",
                args={"matched": m_use, "prompt": p, "tier": tier},
            )
        return entry, m_use, chunk

    def _validate_prefix(self, entry) -> bool:
        """Reuse-time integrity check of a stored entry: leaf shapes against
        the insert-time record, then the position-weighted fingerprint
        recomputed on device and compared with exact float equality (same
        program + same data is bit-deterministic). Cost is one scalar
        readback — the admission path syncs for the first token anyway."""
        try:
            shapes = tuple(
                tuple(leaf.shape)
                for leaf in jax.tree_util.tree_leaves(entry.tree)
            )
            if shapes != entry.shapes:
                return False
            # entry.fingerprint is a device scalar computed asynchronously
            # at insert time — long settled by now, so reading it is a
            # plain copy; the recomputation's readback is the validation
            # sync (the admission path syncs for the first token anyway).
            # Both scalars ride one explicit device_get.
            # graftlint: ok[GL02] reuse-time integrity check: one scalar
            # pair readback per prefix hit, the documented validation sync
            fp_new, fp_stored = jax.device_get(
                (self._fingerprint_fn(entry.tree), entry.fingerprint)
            )
            return float(fp_new) == float(fp_stored)
        except Exception:
            return False

    def _pages_fingerprint(self, ids):
        """Per-page content fingerprints of pool pages ``ids`` as a DEVICE
        uint32 vector — nothing syncs here (insert-time recording stays
        async, like the dense path's entry fingerprint). The id vector
        pads to the next power of two with repeats of the first id so the
        jitted program compiles once per bucket, not once per page count;
        padded positions are ignored by the caller's comparison."""
        if self._pages_fp_fn is None:
            from neuronx_distributed_tpu.utils.fingerprint import (
                pool_pages_fingerprint,
            )

            # per-engine lambda (see the _extract_fn note above)
            self._pages_fp_fn = self.programs.wrap(
                "page_fingerprint",
                jax.jit(lambda pool, pids: pool_pages_fingerprint(pool, pids)),
            )
        n = len(ids)
        bucket = 1
        while bucket < n:
            bucket <<= 1
        padded = np.asarray(
            tuple(int(i) for i in ids) + (int(ids[0]),) * (bucket - n),
            np.int32,
        )
        return self._pages_fp_fn(self.cache.cache["pool"], jnp.asarray(padded))

    def _validate_pages(self, entry, n_pages: int) -> bool:
        """Reuse-time CONTENT check of a paged entry's used page prefix:
        recompute the per-page fingerprints and compare bit-exactly with
        the insert-time record (per-page fingerprints are independent, so
        a prefix of the stored vector validates a prefix reuse). Cost is
        one small-vector readback per paged prefix hit — the same sync
        contract as the dense path's ``_validate_prefix``."""
        try:
            fp_now = self._pages_fingerprint(entry.page_ids[:n_pages])
            # graftlint: ok[GL02] reuse-time integrity check: one bucketed
            # uint32 vector readback per paged prefix hit, the documented
            # validation sync (admission syncs for the first token anyway)
            now_v, stored_v = jax.device_get((fp_now, entry.page_fp))
            return bool(np.array_equal(
                np.asarray(now_v)[:n_pages], np.asarray(stored_v)[:n_pages]
            ))
        except Exception:
            return False

    def _remember_prefix(self, ctx, p: int, padded: int, row_cache,
                         matched: int = 0) -> None:
        """Insert-on-miss (and trie extension on long partial hits):
        extract the admitted context's KV columns from the freshly built
        row into a compact COPY and store it keyed by the token path. Runs
        BEFORE ``cache.admit`` so the entry can never alias storage the
        donating slot programs will consume. Skipped for contexts too
        short to ever be reused, contexts an existing entry already
        covers, and hits whose tail extends the match by less than
        ``min_match`` (the new entry could never deliver a usefully longer
        reuse than the one that just served — and skipping keeps the hot
        hit path at three small dispatches instead of five)."""
        if self.prefix is None or p < self.prefix.min_match:
            return
        if matched and p - matched < self.prefix.min_match:
            return
        key = tuple(int(t) for t in ctx)
        if self.prefix.covers(key):
            return
        bucket = _prefix_bucket(p, self.max_seq_len)
        tree = self._extract_fn(
            row_cache,
            jnp.asarray(padded - p, jnp.int32),
            jnp.asarray(p, jnp.int32),
            bucket,
        )
        # the fingerprint stays a DEVICE scalar: forcing it to host here
        # would serialize every miss admission against the device before
        # admit/first-token even dispatch — validation (which already
        # syncs) floats it on first reuse instead
        fp = self._fingerprint_fn(tree)
        _, evicted = self.prefix.insert(key, tree, fp, bucket)
        if evicted:
            self.metrics.record_prefix_eviction(evicted)
            if self.timeline is not None:
                self.timeline.instant(
                    "prefix_evict", "serving", args={"evicted": evicted}
                )

    def _remember_prefix_paged(self, ctx, p: int, slot: int,
                               matched: int = 0) -> None:
        """Paged insert-on-miss: PIN the admitted slot's whole context
        pages instead of extracting a compact copy — zero device work, zero
        KV bytes moved, the very pages the prefill just wrote become the
        shared storage (decode writes always land beyond the aligned
        context, so a still-decoding donor can never touch them). Runs
        AFTER ``cache.admit`` (the pins ride the allocator, which donation
        cannot consume). Same skip rules as the copy path: contexts too
        short to reuse, contexts already covered, hits whose aligned tail
        adds less than ``min_match``."""
        if self.prefix is None:
            return
        ps = self._page_size
        m_ins = (p // ps) * ps
        if m_ins < self.prefix.min_match:
            return
        if matched and m_ins - matched < self.prefix.min_match:
            return
        key = tuple(int(t) for t in ctx[:m_ins])
        if self.prefix.covers(key):
            return
        ids = self.cache.slot_context_pages(slot, m_ins // ps)
        self.cache.pin_pages(ids)
        entry, evicted = self.prefix.insert(key, None, None, bucket=m_ins)
        if entry is None:  # raced covered / disabled: drop the pins
            self.cache.unpin_pages(ids)
        else:
            entry.page_ids = tuple(int(i) for i in ids)
            # integrity (ISSUE 20): record the pages' content fingerprints
            # now, while the context region is final (decode writes land
            # beyond the aligned context by construction). Stays a device
            # vector — no sync on the miss-admission path; first reuse
            # floats it alongside the recomputation
            entry.page_fp = self._pages_fingerprint(entry.page_ids)
        if evicted:
            self.metrics.record_prefix_eviction(evicted)
            if self.timeline is not None:
                self.timeline.instant(
                    "prefix_evict", "serving", args={"evicted": evicted}
                )

    # --- decode -------------------------------------------------------------

    def _decode(self) -> None:
        """One fused decode chunk: dispatch the donated jitted scan, then a
        SINGLE host synchronization for the whole token block. Between here
        and the next admission/free event no per-slot host state moves. A
        failed dispatch routes through the recovery state machine instead of
        crashing the loop."""
        if self._in_flight is not None:
            # a chunk run ahead is on the device, its pages dealt at its
            # call: this step reads it back (and may call the next first)
            self._decode_plain()
            return
        if not self._deal_decode_pages():
            # page-pressure wall: the pool cannot back every active
            # slot's next write window even after reclaiming prefix
            # entries — preempt-and-rewind, the cursor wall's exact
            # remedy (frees every slot mapping; re-admission repacks
            # from column 0)
            self._preempt_all()
            return
        if self.draft_model is not None:
            self._decode_spec()
        else:
            self._decode_plain()

    def _nonspec_chunk(self):
        """The plain fused chunk — built lazily on a speculative engine
        (only a failed speculative dispatch ever needs it)."""
        if self._decode_chunk is None:
            self._decode_chunk = jax.jit(
                chunked_decode_step(
                    self._decode_model, self.decode_chunk_size,
                    self.max_seq_len, page_size=self._page_size,
                ),
                donate_argnums=(1, 2),
            )
            self._decode_chunk = self.programs.wrap(
                "decode_chunk", self._comms_scoped(self._decode_chunk)
            )
        return self._decode_chunk

    def _decode_spec(self) -> None:
        """One fused SPECULATIVE chunk: ``decode_chunk_size`` draft–verify
        rounds through both donated caches, one host sync for the ragged
        per-slot token block. A failed dispatch falls back to a plain
        non-speculative chunk for THIS chunk when the donated buffers
        survived (streams bit-identical — the fallback is the very program
        the spec-off engine runs), then preempts to resync the draft cache;
        consumed buffers route through full dispatch recovery."""
        fault = None
        with self._span(tracing.STEP_DISPATCH) as sp:
            # the span's stats are host arithmetic over the slots: made
            # inside it (47-100 us with 32 slots), so that no stretch of
            # the step lies outside a child span
            t0 = self._clock()
            active_at_dispatch = int(self._active.sum())
            sampled_slots = self._sampled_slots()
            sp.set_metadata(
                active=active_at_dispatch, sampled_slots=sampled_slots,
                **self._kv_bytes_stats(),
                cursor=int(self.cache.cursor), row_columns=self.max_seq_len,
                **self._selection_stats(),
            )
            cache_in = self.cache.take()
            draft_in = self.draft_cache.take()
            attempt = self._dispatch_attempts
            self._dispatch_attempts += 1
            dparams = self._draft_params
            try:
                if self._faults is not None:
                    self._faults.on_dispatch(attempt)
                    self._faults.on_spec_dispatch(attempt)
                    dparams = self._faults.on_spec_params(attempt, dparams)
                (new_cache, new_draft, self._state, toks, counts, accepts,
                 used, key_snap) = self._spec_chunk(
                    self._params, dparams, cache_in, draft_in, self._state
                )
            except Exception as e:
                fault = e
            except BaseException:
                # KeyboardInterrupt/SystemExit are the operator's, not
                # faults: restore both references and re-raise
                self.cache.restore(cache_in)
                self.draft_cache.restore(draft_in)
                raise
        if fault is not None:
            self._spec_fallback(cache_in, draft_in, fault)
            return
        t1 = self._clock()
        self._consecutive_dispatch_failures = 0
        self._chunks_since_failure += 1
        self.metrics.record_chunk_dispatch(sampled_slots)
        with self._span(tracing.STEP_READBACK) as sp:
            # THE one host sync per speculative chunk: the ragged (rounds,
            # slots, gamma) token block, per-round per-slot counts +
            # accepted draft lengths, the executed round count, and the
            # post-chunk key snapshot — whatever the per-slot acceptance
            # pattern emitted
            # graftlint: ok[GL02] THE one per-chunk sync of the fused
            # speculative decode contract (pinned in test_host_sync.py)
            toks, counts, accepts, used, chunk_keys = jax.device_get(
                (toks, counts, accepts, used, key_snap)
            )
            sp.set_metadata(steps=int(used))  # executed rounds
        t2 = self._clock()
        with self._span(tracing.STEP_EMIT) as sp:
            self._emit_spec_chunk(
                sp, new_cache, new_draft, toks, counts, accepts, used,
                chunk_keys, active_at_dispatch, t0, t1, t2,
            )
            del cache_in, draft_in, key_snap  # as in _decode_plain

    def _emit_spec_chunk(self, sp, new_cache, new_draft, toks, counts,
                         accepts, used, chunk_keys, active_at_dispatch,
                         t0, t1, t2) -> None:
        """What follows the speculative chunk's readback: validation, the
        key mirror, every token to its stream, retirement, the metrics."""
        tl = self.timeline
        readback = self._readbacks
        self._readbacks += 1
        if self._faults is not None:
            toks, counts = self._faults.on_spec_readback(
                readback, toks, counts, self._active
            )
        # executed ROUNDS drive cursor arithmetic (gamma columns per round
        # in BOTH caches); clamp so corrupted output can never run away
        used = max(0, min(int(used), self.decode_chunk_size))
        self.cache.update_after_decode(new_cache, used * self.gamma)
        self.draft_cache.update_after_decode(new_draft, used * self.gamma)
        bad = _validate_spec_readback(
            toks, counts, self.gamma, self._vocab,
            np.flatnonzero(self._active),
        )
        # paged: page-poison victims leave self._active before the unpack
        self._apply_page_poison(readback)
        now = self._now()
        delivered = 0
        spec_accepts = []
        for slot in np.flatnonzero(self._active):
            req = self._slot_req[slot]
            if int(slot) in bad:
                self._quarantine_slot(int(slot), req, bad[int(slot)], now)
                continue
            req.key = np.array(chunk_keys[slot], np.uint32)
            # acceptance stats at full per-slot-per-round resolution: a
            # live round is one where the slot emitted (live slots emit
            # >= 1 per round) — all host scalars off the single sync
            live_rounds = [
                r for r in range(counts.shape[0]) if int(counts[r, slot]) > 0
            ]
            spec_accepts.extend(int(accepts[r, slot]) for r in live_rounds)
            self.tracer.step(
                req.rid, "decode_chunk",
                args={
                    "tokens": int(counts[:, slot].sum()),
                    "rounds": used,
                    "accepted": int(accepts[:, slot].sum()),
                },
            )
            for r in live_rounds:
                for tok in toks[r, slot, : int(counts[r, slot])]:
                    self._emit_token(req, int(tok), now)
                    delivered += 1
                    self._maybe_finish(req, now)
                    if req.finished:
                        break
                if req.finished:
                    break
        if tl is not None:
            tl.counter("chunk_tokens", delivered, "serving")
            tl.instant(
                "spec_accept", "serving",
                args={
                    "accepted": int(sum(spec_accepts)),
                    "drafted": self.gamma * len(spec_accepts),
                    "rounds": used,
                    "tokens": delivered,
                },
            )
        self.metrics.record_decode_chunk(
            delivered, used, self.cache.cursor, active_at_dispatch,
            dispatch_s=t1 - t0, readback_s=t2 - t1,
            spec_accepts=spec_accepts, gamma=self.gamma,
        )
        sp.set_metadata(delivered=delivered)
        # roofline feed: the chunk's measured wall (already host floats off
        # the single readback) against the ledgered program cost — a
        # compile-polluted first chunk is skipped so MFU never averages in
        # trace+compile time
        if not self._spec_chunk.last_call_compiled:
            self.programs.observe_wall("spec_decode_chunk", t2 - t0)

    def _spec_fallback(self, cache_in, draft_in, exc: Exception) -> None:
        """A SPECULATIVE dispatch failed. When the donated buffers
        survived (host-side failure — injected draft fault, enqueue
        error), decode THIS chunk with the plain non-speculative program —
        the exact program a spec-off engine runs, so streams continue
        bit-identically and no token is lost — then preempt-and-resync:
        the fallback chunk advanced the target cache without the draft
        cache, and re-prefilling both on re-admission restores lockstep
        (and full acceptance) at the cost of one re-prefill per slot.
        Consumed buffers mean the failure happened inside XLA — nothing to
        fall back ONTO — so it routes through full dispatch recovery."""
        consumed = any(
            getattr(leaf, "is_deleted", lambda: False)()
            for tree in (cache_in, draft_in, self._state)
            for leaf in jax.tree_util.tree_leaves(tree)
        )
        if consumed:
            # nothing to fall back ONTO — this is a full dispatch failure,
            # counted/recorded as one by the recovery path (spec_fallbacks
            # counts only chunks actually decoded non-speculatively)
            self._recover_dispatch(cache_in, exc, draft_in=draft_in)
            return
        self.cache.restore(cache_in)
        self.draft_cache.restore(draft_in)
        # mark the failure window (DEGRADED until the cooldown elapses);
        # NOT a consecutive-failure count — the fallback below makes
        # progress, and ITS dispatch failure is what escalates to recovery
        # and, bounded, to HALT
        self._had_dispatch_failure = True
        self._chunks_since_failure = 0
        self._decode_plain()
        if self._consecutive_dispatch_failures == 0:
            # the plain chunk really decoded (its own failure would have
            # routed through recovery and bumped the consecutive count):
            # THIS is a fallback — the counter means "chunk decoded
            # non-speculatively", never "speculative dispatch failed"
            self.metrics.record_spec_fallback()
            if self.timeline is not None:
                self.timeline.instant(
                    "spec_fallback", "serving", args={"error": str(exc)[:200]}
                )
            if self.flight is not None:
                self.flight.record("spec_fallback", error=str(exc))
        if any(self._active):
            # the fallback chunk advanced only the target cache: preempt
            # so re-admission rebuilds BOTH caches in lockstep (tokens and
            # keys are host-current — the preemption contract)
            self._preempt_all()
        self._sync_health()

    def _deal_decode_pages(self, unread: int = 0) -> bool:
        """Before a chunk's call: the host's cursor goes ahead by the
        ``unread`` columns of a chunk still on the device (its projection),
        and a paged engine deals the write window's pages under their span:
        both kinds' counts, the deal, the block tables' upload (the draft
        cache's too; ``unread`` as :meth:`_chunk_width_cols`)."""
        if self._page_size is None:
            self.cache.advance(unread)
            return True
        with self._span(tracing.STEP_PAGES):
            if unread:
                self.cache.advance(unread)
            return self._ensure_decode_pages(unread)

    def _decode_plain(self) -> None:
        """The non-speculative fused chunk (also the speculative engine's
        fallback program): ONE chunk is read back a call. With no chunk in
        flight it calls one; where the boundary after the chunk in flight
        can bring the host nothing to do (:meth:`_can_run_ahead`, asked
        inside a span of this step, and the pool backing the next write
        window at the projected cursor) it calls
        the NEXT chunk before it blocks on this one's readback, and emits
        this one's tokens while the device runs the next (module docstring,
        "Decode hot path")."""
        chunk, self._in_flight = self._in_flight, None
        if chunk is None:
            chunk = self._dispatch_chunk(None)
            if isinstance(chunk, Exception):
                self._recover_dispatch(self.cache.take(), chunk)
                return
        ahead = self._dispatch_ahead(chunk) if chunk.followed else None
        with self._span(tracing.STEP_READBACK) as sp:
            # THE one host sync per chunk: the (chunk, slots) token block,
            # the per-slot valid-prefix lengths, the executed step count —
            # and the post-chunk key SNAPSHOT (frozen at each slot's finish
            # step). The snapshot is a chunk OUTPUT, not the state leaf:
            # device_get on the leaf would cache a host value on it and
            # silently demote the next chunk's keys donation to a copy
            # graftlint: ok[GL02] THE one per-chunk sync of the fused decode
            # contract (tests/serving/test_decode_chunking.py pins it at 1)
            toks, counts, used, chunk_keys, *model_stats = jax.device_get(
                chunk.outputs
            )
            sp.set_metadata(steps=int(used), **{
                name: int(value) for name, value in zip(
                    getattr(self._decode_model, "chunk_stats", ()),
                    model_stats[0] if model_stats else ())
            })
        t2 = self._clock()
        with self._span(tracing.STEP_EMIT) as sp:
            self._emit_chunk(sp, chunk, toks, counts, used, chunk_keys, t2)
            # the donated tree's husks go here, inside the span: released
            # with this frame they took 0.11 ms of a step under no span
            del chunk
        if isinstance(ahead, Exception):
            # the call ahead FAILED: the chunk before it is emitted, every
            # stream host-current through it, and recovery is today's
            self._recover_dispatch(self.cache.take(), ahead)
        elif not self._halted:   # a halt inside the emit dropped it
            self._in_flight = ahead

    def _can_run_ahead(self) -> bool:
        """Whether the boundary after the chunk now on the device can bring
        the host nothing the next chunk would have to wait for, from what
        the engine can observe at the moment of the call (module docstring,
        "Decode hot path", has the rule and what it costs)."""
        chunk = self.decode_chunk_size
        return (
            # the plain path with nothing armed: every recovery, quarantine
            # and page-poison path keeps its one-chunk-at-a-time order
            self.draft_model is None and self._faults is None
            and self.health() is EngineHealth.OK
            # nothing else is due at the boundary: no handoff from outside,
            # no tier to move pages to or from, no policy that preempts
            and not self.external_prefill and self.tier is None
            and not self.policy.preempts
            # no admission: every usable slot is held, and no budget ends
            # inside the chunk in flight (the host knows every budget)
            and self.cache.free_slots == 0
            and all(
                r.remaining_new_tokens > chunk and not r.finished
                for r in self._slot_req if r is not None
            )
            # room under the cursor for both write windows
            and self.cache.cursor + 2 * chunk <= self.max_seq_len
        )

    def _dispatch_ahead(self, unread: "_Chunk"):
        """Call the chunk AFTER ``unread`` before ``unread`` is read back:
        the host's cursor goes ahead of the device by ``unread``'s columns
        (projected at the chunk's size; :meth:`_emit_chunk` settles the
        difference), the next window's pages are dealt and uploaded on that
        projection, and the call takes ``unread``'s output cache and state
        as they are, not yet computed. Returns the new chunk; ``None`` where
        the pool cannot back the window (the engine goes back to one chunk
        at a time, and the wall's remedy is the next step's); the exception
        of a call that failed."""
        unread.projected = self.decode_chunk_size
        if not self._deal_decode_pages(unread.projected):
            return None
        return self._dispatch_chunk(unread)

    def _dispatch_chunk(self, unread: Optional["_Chunk"]):
        """One call of the donated chunk program under its span; its output
        cache goes back to the manager at once (the cursor moves when the
        chunk's columns are known, or projected). ``unread``: the chunk on
        the device that this one is called ahead of. A call that raises
        comes back as its exception, the manager holding the input tree
        again: the caller recovers (after it has emitted ``unread``)."""
        unread_tokens = 0 if unread is None else unread.projected
        with self._span(tracing.STEP_DISPATCH) as sp:
            # as in _decode_spec: the span's stats are made inside it
            t0 = self._clock()
            active_at_dispatch = int(self._active.sum())
            sampled_slots = self._sampled_slots()
            sp.set_metadata(
                active=active_at_dispatch, sampled_slots=sampled_slots,
                ahead=int(unread is not None),
                **self._kv_bytes_stats(),
                cursor=int(self.cache.cursor), row_columns=self.max_seq_len,
                # one dict: both name ``ctx_tokens``
                **{**self._selection_stats(unread_tokens),
                   **self._window_stats(unread_tokens)},
                **self._slot_state_stats(), **self._page_stats(),
            )
            cache_in = self.cache.take()
            attempt = self._dispatch_attempts
            self._dispatch_attempts += 1
            try:
                if self._faults is not None:
                    self._faults.on_dispatch(attempt)
                # past the six: the model's own per-chunk counters
                # (chunked_decode_step's ``chunk_stats``; most models: none)
                (new_cache, self._state, toks, counts, used,
                 key_snap, *model_stats) = self._nonspec_chunk()(
                    self._params, cache_in, self._state
                )
            except BaseException as e:
                # the reference goes back either way (a consumed buffer
                # fails loudly on next use); KeyboardInterrupt/SystemExit
                # are the operator's, not faults
                self.cache.restore(cache_in)
                if not isinstance(e, Exception):
                    raise
                return e
            self.cache.restore(new_cache)
            self._consecutive_dispatch_failures = 0
            self._chunks_since_failure += 1
            self.metrics.record_chunk_dispatch(
                sampled_slots, ahead=unread is not None)
            return _Chunk(
                outputs=(toks, counts, used, key_snap, *model_stats),
                active=active_at_dispatch, t0=t0, t1=self._clock(),
                compiled=self._decode_chunk.last_call_compiled,
                followed=unread is None and self._can_run_ahead(),
                reqs=tuple(self._slot_req) if unread is not None else None,
            )

    def _emit_chunk(self, sp, chunk: "_Chunk", toks, counts, used,
                    chunk_keys, t2: float) -> None:
        """What follows the chunk's readback (at ``t2``): the cursor, the
        validation, the key mirror, every token to its stream, retirement,
        the metrics."""
        tl = self.timeline
        readback = self._readbacks
        self._readbacks += 1
        if self._faults is not None:
            toks, counts = self._faults.on_readback(
                readback, toks, counts, self._active
            )
        # the executed step count drives cursor arithmetic — clamp it to the
        # chunk bound so corrupted output can never run the cursor away
        used = max(0, min(int(used), self.decode_chunk_size))
        # the chunk's output is the manager's since its call. Its columns:
        # all of them now or, where the next chunk was called on their
        # projection, the difference (``used`` is short only when every
        # slot froze, and then the chunk called ahead executes no step)
        if used != chunk.projected:
            self.cache.advance(used - chunk.projected)
        self._count_window_pages_freed()
        late_end = chunk.reqs is not None and any(
            r is not None and self._slot_req[s] is not r
            for s, r in enumerate(chunk.reqs)
        )
        if late_end:
            # called ahead over a slot whose request had ended (an EOS, a
            # cancel or a deadline found at the boundary it ran over): that
            # slot rode this chunk frozen, or its tokens are discarded here
            self.metrics.record_late_found_end()
        # validate the block BEFORE any token reaches a stream: a poisoned
        # slot is quarantined and its chunk discarded; neighbors proceed
        bad = _validate_readback(
            toks, counts, self.decode_chunk_size, self._vocab,
            np.flatnonzero(self._active),
        )
        # paged engines: a poisoned PAGE quarantines only the requests
        # mapping it — victims leave self._active here, so the emit loop
        # below never touches their (discarded) readback columns
        self._apply_page_poison(readback)
        now = self._now()
        delivered = 0
        for slot in np.flatnonzero(self._active):
            req = self._slot_req[slot]
            if int(slot) in bad:
                self._quarantine_slot(int(slot), req, bad[int(slot)], now)
                continue
            # mirror the post-chunk key onto the host request: preemption,
            # dispatch recovery, and retirement all read req.key — keeping
            # it current at every chunk boundary costs nothing (the snapshot
            # already rode the chunk's single sync)
            req.key = np.array(chunk_keys[slot], np.uint32)
            # per-request flow waypoint: the chunk's already-host token
            # count — links this chunk into the request's trace for free
            self.tracer.step(
                req.rid, "decode_chunk",
                args={"tokens": int(counts[slot]), "steps": used},
            )
            for tok in toks[: int(counts[slot]), slot]:
                self._emit_token(req, int(tok), now)
                delivered += 1
                self._maybe_finish(req, now)
                if req.finished:
                    # EOS/budget retired it, or an on_token callback
                    # cancelled it: discard the rest of its block
                    break
        # recorded after the unpack so a mid-chunk cancellation's discarded
        # device tokens never inflate decode_tokens / chunk tok/s
        if tl is not None:
            tl.counter("chunk_tokens", delivered, "serving")
        # the chunk's own clocks: a chunk called before the one before it
        # was read back had the device only from that readback on, so its
        # wall runs from the later of its call and that readback
        t0 = max(chunk.t0, self._last_readback_t)
        t1 = max(chunk.t1, t0)
        self._last_readback_t = t2
        self.metrics.record_decode_chunk(
            delivered, used, self.cache.cursor, chunk.active,
            dispatch_s=t1 - t0, readback_s=t2 - t1,
        )
        sp.set_metadata(delivered=delivered, late_end=int(late_end))
        # roofline feed (see _decode_spec): measured chunk wall, compile
        # chunks excluded
        if not chunk.compiled:
            self.programs.observe_wall("decode_chunk", t2 - t0)

    def _count_window_pages_freed(self) -> None:
        """Counter ``serving_window_pages_freed``: the window kind's pages
        the manager gave back since the last look (a model with window
        layers; the cursor moved by a chunk or an admission)."""
        total = getattr(self.cache, "window_pages_freed_total", 0)
        if total > self._window_pages_freed_seen:
            self.metrics.record_window_pages_freed(
                total - self._window_pages_freed_seen)
            self._window_pages_freed_seen = total

    def _recover_dispatch(self, cache_in, exc: Exception,
                          draft_in=None) -> None:
        """A decode dispatch FAILED. Recovery = the preemption machinery:
        every in-flight request goes back to the queue front with its
        host-current tokens and key (both exact as of the last chunk
        boundary), the cache storage is salvaged when the donated buffers
        survived (or dropped for lazy reallocation when XLA consumed them),
        and the next step re-prefills and retries. After
        ``dispatch_retry.max_attempts`` CONSECUTIVE failures the engine
        HALTS with the work requeued instead of crashing."""
        n = self._consecutive_dispatch_failures + 1
        self._consecutive_dispatch_failures = n
        self._had_dispatch_failure = True
        self._chunks_since_failure = 0
        self.metrics.record_dispatch_retry()
        if self.timeline is not None:
            self.timeline.instant(
                "dispatch_failure", "serving",
                args={"error": str(exc)[:200], "consecutive": n},
            )
        if self.flight is not None:
            self.flight.record("dispatch_failure", error=str(exc),
                               consecutive=n)
        requeued = self._vacate_active()
        for r in requeued:
            self.tracer.step(r.rid, "recovery_requeue",
                             args={"tokens": len(r.tokens)})
        self.scheduler.requeue_front(requeued)
        self.cache.release_all_slots()
        survived = self.cache.recover(cache_in)
        if self._page_size is not None and not survived and (
            self.prefix is not None
        ):
            # the POOL was consumed: every pinned page's content is gone
            # with it, so paged entries (which hold no copies) are void —
            # clear the store; on_evict releases each entry's page refs
            dropped = self.prefix.clear()
            if dropped:
                self.metrics.record_prefix_eviction(dropped)
        if self.draft_cache is not None:
            # the draft twin recovers identically: salvage-or-drop its
            # storage and rewind — every slot was vacated, so a lazy
            # reallocation on the next admission is safe for both
            self.draft_cache.release_all_slots()
            self.draft_cache.recover(
                draft_in if draft_in is not None else self.draft_cache.take()
            )
        self._state = self._fresh_slot_state()
        if self.prefix is not None:
            # recovery never resurrects stale KV THROUGH the prefix store:
            # entries are independent copies (still valid under the same
            # weights), but any pin a failed admission might have left is
            # dropped so eviction can proceed
            self.prefix.release_all()
        if n >= self._dispatch_retry.max_attempts:
            self._halt(
                f"{n} consecutive dispatch failures (last: "
                f"{type(exc).__name__}: {exc})"
            )
            return
        self.metrics.record_recovery(len(requeued))
        if self.timeline is not None:
            self.timeline.instant(
                "recovery", "serving", args={"requeued": len(requeued)}
            )
        if self.flight is not None:
            self.flight.record("recovery", requeued=len(requeued),
                               consecutive=n)
        # shared decrementing-jitter wait before the next attempt (attempt
        # index is 0-based): ride out a transient burst without hammering
        self._sleep(self._dispatch_retry.wait(n - 1))
        self._sync_health()

    def _quarantine_slot(self, slot: int, req: Optional[Request],
                         reason: str, now: float) -> None:
        """Pull a poisoned slot out of the rotation before its chunk
        reaches a stream. The victim request resumes from the last chunk
        boundary in a DIFFERENT slot (``quarantine_policy="requeue"``,
        bit-identical — its tokens and key were untouched by the poisoned
        chunk) or fails for cause (``"fail"``). Neighbors are unaffected;
        losing the last usable slot halts the engine."""
        self.metrics.record_quarantine(slot, req.rid if req else None)
        if self.timeline is not None:
            self.timeline.instant(
                f"quarantine slot {slot}", "serving",
                args={"reason": reason, "rid": req.rid if req else None},
            )
        if self.flight is not None:
            self.flight.record("quarantine", slot=slot,
                               rid=req.rid if req else None, reason=reason,
                               tenant=req.tenant if req else None)
        self._slot_req[slot] = None
        self._active[slot] = False
        self._state = self._slot_clear(self._state, np.int32(slot))
        self.cache.quarantine(slot)
        self.cache.free(slot)  # clears the row; never rejoins the rotation
        if self.draft_cache is not None:
            # the draft row is equally suspect — quarantine it in lockstep
            self.draft_cache.quarantine(slot)
            self.draft_cache.free(slot)
        if req is not None:
            req.slot = None
            if self._quarantine_policy == "requeue" and not req.finished:
                self.tracer.step(req.rid, "quarantine_requeue",
                                 args={"slot": slot})
                self.scheduler.requeue_front([req])
            else:
                req.state = RequestState.FAILED
                req.error = f"slot {slot} quarantined: {reason}"
                req.finish_time = now
                self.metrics.record_failed(req, now, kind="quarantine")
                self.tracer.end(req.rid, "failed",
                                args={"kind": "quarantine", "slot": slot})
                self._on_token.pop(req.rid, None)
        if self.cache.usable_slots == 0:
            self._halt("all slots quarantined")
        elif (
            self._page_size is not None and self.cache.alloc.capacity == 0
        ):
            # paged: the slot index survives a quarantine (only its
            # exclusive PAGES retire), so total capacity loss shows up in
            # the pool, not the slot count
            self._halt("all KV pages quarantined")

    # --- lifecycle helpers --------------------------------------------------

    def _emit_token(self, req: Request, tok: int, now: float,
                    first: bool = False) -> None:
        req.tokens.append(tok)
        # fairness accounting (ISSUE 16): charge the tenant's decode-token
        # budget — host ints the loop already owns, FIFO's hook is a no-op
        self.policy.on_tokens(req.tenant, 1)
        if first:
            req.first_token_time = now
            self.metrics.record_first_token(req, now)
        cb = self._on_token.get(req.rid)
        if cb is not None:
            cb(req, tok)

    def _maybe_finish(self, req: Request, now: float) -> None:
        if req.state is RequestState.CANCELLED:
            # e.g. an on_token callback cancelled it this very step: the
            # cancellation wins; _reap_cancelled retires the slot next step
            return
        eos = req.config.eos_token_id
        hit_eos = eos is not None and req.tokens and req.tokens[-1] == eos
        if hit_eos or len(req.tokens) >= req.config.max_new_tokens:
            req.state = RequestState.DONE
            req.finish_time = now
            self.metrics.record_finish(req, now)
            self._release_slot(req)
            if self.timeline is not None:
                self.timeline.instant(f"done r{req.rid}", "serving")
            self.tracer.end(req.rid, "retire",
                            args={"tokens": len(req.tokens),
                                  "tenant": req.tenant})

    def _release_slot(self, req: Request) -> None:
        slot = req.slot
        if slot is None:
            return
        req.slot = None
        self._slot_req[slot] = None
        self._active[slot] = False
        self._state = self._slot_clear(self._state, np.int32(slot))
        self.cache.free(slot)
        if self.draft_cache is not None:
            self.draft_cache.free(slot)
        self._on_token.pop(req.rid, None)

    def _reap_cancelled(self, now: float) -> None:
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.state is RequestState.CANCELLED:
                self.metrics.record_cancel(req, now)
                req.finish_time = now
                self.tracer.end(req.rid, "cancelled",
                                args={"where": "slot", "slot": slot})
                self._release_slot(req)

    def _vacate_active(self) -> List[Request]:
        """Unbind every active request from its slot (host bookkeeping
        only) and return them in slot order — the shared first half of
        preemption and dispatch recovery. ``req.key``/``req.tokens`` are
        already host-current as of the last chunk boundary, so no device
        state is touched (it may not even exist after a failed donation)."""
        vacated = [r for r in self._slot_req if r is not None]
        for req in vacated:
            slot, req.slot = req.slot, None
            self._slot_req[slot] = None
            self._active[slot] = False
        return vacated

    def _preempt_victims(self, victims: List[Request], now: float) -> None:
        """Policy-chosen SELECTIVE preemption (ISSUE 16): vacate just the
        nominated slots — host bookkeeping + the per-slot clear/free the
        retirement path already uses — and requeue the victims with their
        host-current tokens and keys. Resume re-prefills ``context_ids``
        and continues at ``req.key``, the same contract as preempt-all and
        quarantine-requeue, so the victim's stream is bit-identical. The
        shared cursor is NOT rewound (other slots keep decoding); the
        paged layout gets the victim's exclusive pages back immediately."""
        for req in victims:
            slot = req.slot
            if slot is None or req.finished:
                continue  # retired or shed since nomination — nothing held
            req.slot = None
            self._slot_req[slot] = None
            self._active[slot] = False
            self._state = self._slot_clear(self._state, np.int32(slot))
            self.cache.free(slot)
            if self.draft_cache is not None:
                self.draft_cache.free(slot)
            req.preemptions += 1
            self.metrics.record_preemption(req)
            self.tracer.step(
                req.rid, "slo_preempt",
                args={"slot": slot, "tokens": len(req.tokens),
                      "tenant": req.tenant},
            )
            if self.timeline is not None:
                self.timeline.instant(
                    f"slo_preempt r{req.rid}", "serving",
                    args={"slot": slot, "tenant": req.tenant},
                )
            if self.flight is not None:
                self.flight.record("slo_preempt", rid=req.rid, slot=slot,
                                   tenant=req.tenant,
                                   tokens=len(req.tokens))
            self.scheduler.requeue_front([req])

    def _preempt_all(self) -> None:
        """Out of cache columns: push every active request back to the queue
        (keeping its generated tokens and its host-mirrored key), rewind the
        cache, and let admission re-prefill their contexts. Token streams
        are unaffected — resume replays the exact context the request had."""
        with self._span(tracing.STEP_PREEMPT):
            preempted = self._vacate_active()
            for req in preempted:
                req.preemptions += 1
                self.metrics.record_preemption(req)
                self.tracer.step(req.rid, "preempt",
                                 args={"tokens": len(req.tokens)})
            self.scheduler.requeue_front(preempted)
            # ONE device reset invalidates every row — per-slot free()
            # dispatches here would be N redundant full-cache programs; only
            # the host free-list needs per-slot bookkeeping
            self.cache.release_all_slots()
            self.cache.reset()
            if self.draft_cache is not None:
                self.draft_cache.release_all_slots()
                self.draft_cache.reset()
            # every slot is empty now; re-admission re-uploads each row, so a
            # fresh zero state is cheaper than N per-slot clears
            self._state = self._fresh_slot_state()
        if self.timeline is not None:
            self.timeline.instant(
                f"preempt x{len(preempted)}", "serving"
            )
