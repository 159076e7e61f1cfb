"""Compiled-program ledger: per-program cost/memory accounting + roofline.

The observability stack sees requests (tracing/metrics) and contracts
(SLO/tenant attribution) but was blind to the DEVICE: nothing recorded what
each compiled program costs, where HBM goes, or how close a decode chunk /
train step runs to the roofline. This module is that missing layer — the
compiler-reported cost surface (``Compiled.cost_analysis()`` /
``memory_analysis()``) folded into the same registry/snapshot/flight
machinery everything else exports through, the per-program FLOP/byte
feedback loop pjit-at-scale work presumes (PAPERS.md: arXiv 2204.06514).

Design constraints (all load-bearing):

* **Zero device→host syncs.** The dispatch wrapper (:class:`LedgeredProgram`)
  touches only host state: a dispatch counter, two ``perf_counter`` reads,
  and ``_cache_size()`` — a C++ metadata read on the pjit object (graftlint
  GL02 already treats it as host metadata). The pinned budgets
  (submit=1, admission=2, steady chunk=1) hold with the ledger fully ON.
* **Lazy, memoized analysis.** Cost analysis needs a re-``lower()`` (a
  trace, no compile — milliseconds); it runs at SNAPSHOT/export time, once
  per compiled signature, never on the hot path. A compile event only
  records the signature (``ShapeDtypeStruct`` skeleton — array metadata
  survives donation) for later analysis.
* **Explicit degradation.** Every backend gap — ``cost_analysis`` missing,
  ``memory_analysis`` needing an AOT compile the caller did not opt into
  (``memory_analysis=True`` pays one extra XLA compile per signature; the
  jit dispatch cache and the AOT cache do not share, measured on this
  jax), unknown device peaks —
  reports the literal string ``"unavailable"`` (:data:`UNAVAILABLE`),
  never a crash and never a silently-wrong number.
* **Accumulation over double-counting.** ``wrap()`` with an existing name
  returns a new proxy over the SAME record — a lazily rebuilt program (the
  speculative engine's plain-chunk fallback, a re-``fit()``) accumulates
  dispatches/compiles instead of forking or resetting the ledger.

Roofline telemetry: callers feed measured walls they already own
(:meth:`ProgramLedger.observe_wall` — the serving engine's per-chunk wall
off its single readback, the trainer's inter-step wall) into a per-program
histogram; MFU and HBM-bandwidth-utilization are DERIVED at export time as
``cost × dispatch / wall`` against :func:`device_peaks` — so the hot path
records one float and the expensive math happens at scrape/snapshot time.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import re
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from neuronx_distributed_tpu.observability import tracing

__all__ = [
    "UNAVAILABLE",
    "LedgeredProgram",
    "ProgramInfo",
    "ProgramLedger",
    "VariantInfo",
    "device_peaks",
    "per_instance",
    "weak_reader",
]


def per_instance(fn):
    """Fresh function object delegating to ``fn``. In this jax, pjit
    caches — including ``_cache_size()`` — key on the function OBJECT, so
    two ``jax.jit(helper)`` wrappers of the same module-level helper SHARE
    a compile cache (the PR 4 lambda-wrapper note): the second engine's
    first dispatch reads a warm cache and the ledger would see neither the
    compile nor the signature. Jitting ``per_instance(helper)`` instead
    isolates each instance's cache at the cost of one re-trace. ``wraps``
    keeps the helper's NAME on the clone — pjit keys on identity, not
    name, so isolation survives, while profiler traces / compile logs
    still read ``jit(_slot_write)`` instead of eight ``jit(clone)``s."""

    @functools.wraps(fn)
    def clone(*args, **kwargs):
        return fn(*args, **kwargs)

    return clone


def weak_reader(target, fn, default=0):
    """Lazy export closure over a WEAK reference: dereference ``target``,
    apply ``fn``, fall back to ``default`` when the target is gone or the
    value is not numeric. The one shape every efficiency gauge/resident
    read shares — a registry or ledger an operator keeps for a final
    scrape must never pin a retired engine/trainer (params, KV cache)."""
    ref = weakref.ref(target)

    def read():
        obj = ref()
        if obj is None:
            return default
        v = fn(obj)
        return v if isinstance(v, (int, float)) else default

    return read

UNAVAILABLE = "unavailable"

# Peak dense-matmul FLOP/s and HBM bandwidth (bytes/s) per chip, by
# device_kind substring — the roofline ceilings MFU/bandwidth-utilization
# are computed against. Published chip specs (bf16); an unknown kind (this
# container's CPU) reports UNAVAILABLE rather than a made-up ceiling.
_PEAKS = (
    ("v5 lite", 197e12, 8.19e11),
    ("v5e", 197e12, 8.19e11),
    ("v5p", 459e12, 2.765e12),
    ("v6", 918e12, 1.64e12),
    ("trillium", 918e12, 1.64e12),
    ("v4", 275e12, 1.2288e12),
)


def device_peaks(device=None) -> dict:
    """``{"flops": float|UNAVAILABLE, "hbm_bytes_per_s": ...,
    "kind": str, "platform": str, "source": str}`` for ``device`` (default:
    first local device). Unknown kinds degrade to UNAVAILABLE explicitly —
    an MFU against a guessed ceiling is worse than no MFU."""
    if device is None:
        try:
            device = jax.local_devices()[0]
        except Exception:
            device = None
    kind = str(getattr(device, "device_kind", "") or "")
    platform = str(getattr(device, "platform", "") or "")
    for sub, flops, bw in _PEAKS:
        if sub in kind.lower():
            return {
                "flops": flops,
                "hbm_bytes_per_s": bw,
                "kind": kind,
                "platform": platform,
                "source": f"spec table ({sub})",
            }
    return {
        "flops": UNAVAILABLE,
        "hbm_bytes_per_s": UNAVAILABLE,
        "kind": kind,
        "platform": platform,
        "source": f"unknown device kind {kind!r}",
    }


def _abstract_leaf(x):
    """Shape/dtype skeleton of one call-arg leaf. Array metadata is
    host-side and survives donation (a consumed buffer keeps its aval), so
    a compile event can capture the signature AFTER the triggering call
    without touching device memory. Non-array leaves (static ints, flags)
    pass through unchanged so ``lower()`` sees the original signature."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        try:
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        except Exception:
            return x
    return x


def _leaf_pedigree(x) -> dict:
    """Dispatch-key pedigree of one CONCRETE call-arg leaf, recorded at
    compile time so an AOT replay (:mod:`..inference.aot`) can materialize
    a dummy that lands in the SAME pjit dispatch-cache entry. The
    ``ShapeDtypeStruct`` skeleton alone cannot: a ``np.int32`` scalar, a
    committed jax array, and a weak-typed Python int are three DISTINCT
    cache entries at identical shape/dtype (measured on this jax). Kinds:
    ``jax`` (uncommitted arrays — jit outputs, ``jnp.*`` literals — all
    share one entry), ``jax`` + ``committed`` (explicit ``device_put``;
    ``spec`` records the partition spec when sharded), ``np`` (ndarray),
    ``np_scalar`` (``np.generic``), ``py`` (static/weak Python scalar —
    the recorded VALUE matters for ``static_argnums``)."""
    if isinstance(x, jax.Array):
        ped: Dict[str, Any] = {"kind": "jax"}
        try:
            if bool(getattr(x, "_committed", False)):
                ped["committed"] = True
                spec = getattr(getattr(x, "sharding", None), "spec", None)
                if spec is not None and tuple(spec):
                    ped["spec"] = [
                        list(p) if isinstance(p, (tuple, list))
                        else (None if p is None else str(p))
                        for p in tuple(spec)
                    ]
        except Exception:
            pass
        try:
            if bool(x.aval.weak_type):
                ped["weak"] = True
        except Exception:
            pass
        return ped
    if isinstance(x, np.ndarray):
        return {"kind": "np"}
    if isinstance(x, np.generic):
        return {"kind": "np_scalar"}
    return {"kind": "py"}


def _signature(a_args, a_kwargs) -> str:
    """Deterministic short id of an abstract call signature: a digest over
    every leaf's dtype/shape (or repr for static leaves) plus the leaf
    count and total input bytes — stable across runs, compact enough to
    live in snapshots."""
    leaves = jax.tree_util.tree_leaves((a_args, a_kwargs))
    parts = []
    in_bytes = 0
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}{list(shape)}")
            n = 1
            for s in shape:
                n *= int(s)
            in_bytes += n * getattr(dtype, "itemsize", 1)
        else:
            parts.append(repr(leaf)[:64])
    digest = hashlib.sha1("|".join(parts).encode()).hexdigest()[:10]
    return f"{digest}:{len(leaves)}leaves:{in_bytes}B"


def _normalize_cost(cost) -> Optional[dict]:
    """``cost_analysis()`` returns a flat dict on some paths and a
    one-element list of dicts on others (both observed on this jax) —
    normalize to the dict."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return cost if isinstance(cost, dict) else None


class _Variant:
    """One compiled signature of a program: the pending abstract args (for
    lazy analysis) and, once analyzed, the compiler-reported numbers.

    ``abstract_call`` is retained PAST :meth:`ensure` (``pending`` is
    consumed by it) so external verifiers — graftverify's IR checks — can
    re-``lower()`` the program on demand without racing the cost-analysis
    lifecycle."""

    __slots__ = (
        "sig", "pending", "abstract_call", "analyzed", "flops",
        "bytes_accessed", "donated_argnums", "memory", "cost_source",
        "pedigree",
    )

    def __init__(self, sig: str, pending=None):
        self.sig = sig
        self.pending = pending  # (fn, a_args, a_kwargs) until analyzed
        self.abstract_call = pending  # survives ensure(); see lower()
        self.pedigree = None  # per-leaf dispatch-key kinds (AOT manifest)
        self.analyzed = False
        self.flops: Any = UNAVAILABLE
        self.bytes_accessed: Any = UNAVAILABLE
        self.donated_argnums: Any = UNAVAILABLE
        self.memory: Dict[str, Any] = dict(_EMPTY_MEMORY)
        self.cost_source: str = UNAVAILABLE

    def lower(self):
        """Fresh ``Lowered`` handle for this signature — a TRACE of the
        wrapped callable over the captured abstract args, never a compile.
        Returns None when the signature was not captured (AOT records
        carry their analysis eagerly and keep no callable). Not memoized:
        a Lowered pins the traced jaxpr/module, and verification passes
        are episodic — holding one per variant for the process lifetime
        would be a silent memory tax on the serving ledger."""
        call = self.abstract_call
        if call is None:
            return None
        fn, a_args, a_kwargs = call
        return fn.lower(*a_args, **a_kwargs)

    def fill_from(self, lowered, compiled=None) -> None:
        """Record analysis from a ``Lowered`` (cheap — no compile) and,
        when the caller already holds one, a ``Compiled`` (post-optimization
        cost + memory). Never raises; gaps stay UNAVAILABLE with a reason."""
        self.analyzed = True
        self.pending = None
        try:
            d = getattr(lowered, "donate_argnums", None)
            if d is not None:
                self.donated_argnums = [int(i) for i in d]
        except Exception:
            pass
        cost = None
        try:
            cost = _normalize_cost(lowered.cost_analysis())
            if cost is not None:
                self.cost_source = "lowered.cost_analysis"
        except Exception as e:
            self.cost_source = f"{UNAVAILABLE}: {type(e).__name__}"
        if compiled is not None:
            try:
                c2 = _normalize_cost(compiled.cost_analysis())
                if c2 is not None:
                    cost = c2
                    self.cost_source = "compiled.cost_analysis"
            except Exception:
                pass
            try:
                ma = compiled.memory_analysis()
            except Exception:
                ma = None
            if ma is not None:
                for key, attr in _MEMORY_ATTRS:
                    v = getattr(ma, attr, None)
                    if v is not None:
                        self.memory[key] = int(v)
        if cost is not None:
            if "flops" in cost:
                self.flops = float(cost["flops"])
            if "bytes accessed" in cost:
                self.bytes_accessed = float(cost["bytes accessed"])

    def ensure(self, memory_analysis: bool) -> None:
        """Run the deferred analysis exactly once: re-``lower()`` (a trace,
        no compile) for cost; optionally an AOT ``compile()`` (one extra
        XLA compile — the opt-in) for memory. Degrades to UNAVAILABLE
        fields on any failure."""
        if self.analyzed:
            return
        pending = self.pending
        self.analyzed = True
        self.pending = None
        if pending is None:
            self.cost_source = f"{UNAVAILABLE}: signature not captured"
            return
        fn, a_args, a_kwargs = pending
        try:
            lowered = fn.lower(*a_args, **a_kwargs)
        except Exception as e:
            self.cost_source = (
                f"{UNAVAILABLE}: lower failed ({type(e).__name__})"
            )
            return
        compiled = None
        if memory_analysis:
            try:
                compiled = lowered.compile()
            except Exception:
                compiled = None
        # without the memory_analysis opt-in `compiled` stays None and the
        # memory fields keep their UNAVAILABLE markers — the numbers exist
        # on most backends, the caller just did not pay the AOT compile
        self.fill_from(lowered, compiled)


class VariantInfo:
    """Read-only view of one compiled signature of a ledgered program.

    ``signature`` is the ledger's stable digest id;
    ``abstract_args``/``abstract_kwargs`` are the captured
    ``ShapeDtypeStruct`` skeleton (None when not captured — AOT records);
    ``lower()`` re-traces the program over that skeleton and returns the
    ``jax.stages.Lowered`` (None when uncapturable). A trace, never a
    compile — the graftverify contract."""

    __slots__ = ("signature", "_variant")

    def __init__(self, variant: "_Variant"):
        self.signature = variant.sig
        self._variant = variant

    @property
    def captured(self) -> bool:
        return self._variant.abstract_call is not None

    @property
    def abstract_args(self):
        call = self._variant.abstract_call
        return call[1] if call is not None else None

    @property
    def abstract_kwargs(self):
        call = self._variant.abstract_call
        return call[2] if call is not None else None

    @property
    def pedigree(self):
        """Per-leaf dispatch-key pedigree (flatten order of
        ``(args, kwargs)``) captured at compile time — see
        :func:`_leaf_pedigree`. None when not captured (AOT records)."""
        return self._variant.pedigree

    def lower(self):
        return self._variant.lower()


class ProgramInfo:
    """Read-only view of one ledgered program for enumeration consumers."""

    __slots__ = ("name", "_record")

    def __init__(self, name: str, record: "_ProgramRecord"):
        self.name = name
        self._record = record

    @property
    def dispatches(self) -> int:
        return self._record.dispatches

    @property
    def compiles(self) -> int:
        return self._record.compiles

    @property
    def prewarm_dispatches(self) -> int:
        """Dispatches issued inside a :meth:`ProgramLedger.prewarming`
        scope (AOT replay), kept OUT of ``dispatches`` so runtime-traffic
        accounting — and graftverify GV05's "was it dispatched at
        runtime" question — stays uncontaminated by warmup replays."""
        return self._record.prewarm_dispatches

    @property
    def variants(self) -> Tuple[VariantInfo, ...]:
        return tuple(
            VariantInfo(v) for v in self._record.variants.values()
        )


# memory_analysis() field mapping (CompiledMemoryStats attribute names)
_MEMORY_ATTRS = (
    ("peak_bytes", "peak_memory_in_bytes"),
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)
_EMPTY_MEMORY = {key: UNAVAILABLE for key, _ in _MEMORY_ATTRS}


class _ProgramRecord:
    """Accumulating ledger entry for one named program."""

    __slots__ = (
        "name", "dispatches", "compiles", "compile_wall_s", "variants",
        "last_wall_s", "wall_hist", "c_dispatch", "c_compiles",
        "prewarm_dispatches",
    )

    def __init__(self, name: str):
        self.name = name
        self.dispatches = 0
        self.prewarm_dispatches = 0
        self.compiles = 0
        self.compile_wall_s = 0.0
        self.variants: "OrderedDict[str, _Variant]" = OrderedDict()
        self.last_wall_s: Optional[float] = None
        self.wall_hist = None  # registry histogram child (set by the ledger)
        self.c_dispatch = None  # registry counter children
        self.c_compiles = None

    def sole_variant(self) -> Optional[_Variant]:
        if len(self.variants) == 1:
            return next(iter(self.variants.values()))
        return None


_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")


def module_name(fn) -> str:
    """The name of a jitted callable's runs on the device's ``XLA Modules``
    line, less the fingerprint in brackets: ``jit_`` and the function's name
    as JAX spells it into the module (``chunk_fn`` gives ``jit_chunk_fn``,
    ``<lambda>`` gives ``jit__lambda``: what is no word character becomes
    ``_``, and underscores at the end are dropped). ``""`` for a callable
    without a name (an AOT ``Compiled``)."""
    name = getattr(fn, "__name__", None)
    if not isinstance(name, str):
        return ""
    return ("jit_" + _NOT_IN_A_MODULE_NAME.sub("_", name)).rstrip("_")


class LedgeredProgram:
    """Dispatch proxy over a jitted callable: counts dispatches, detects
    compiles via ``_cache_size()`` deltas, and forwards everything else
    (``_cache_size``, ``lower``, ...) to the wrapped function so existing
    compile-count properties keep working unchanged. ``last_call_compiled``
    lets callers skip a compile-polluted wall measurement. Every call runs
    inside an ``nxd.program`` span (``tracing.PROGRAM``) whose two stats,
    the ledger's name and :func:`module_name`, are fixed here: a trace
    reader joins the device's runs to the calls that made them by those."""

    def __init__(self, ledger: "ProgramLedger", record: _ProgramRecord, fn):
        self._ledger = ledger
        self._record = record
        self._inner = fn
        self._cache_size_fn = getattr(fn, "_cache_size", None)
        self._span_stats = {"program": record.name, "module": module_name(fn)}
        self.last_call_compiled = False

    @property
    def __wrapped__(self):
        return self._inner

    def _cache_size(self) -> int:
        return int(self._cache_size_fn()) if self._cache_size_fn else 0

    def __getattr__(self, name):
        # anything the proxy does not own (lower, clear_cache, ...) reads
        # through to the wrapped jit object
        return getattr(self._inner, name)

    def __call__(self, *args, **kwargs):
        rec = self._record
        cs = self._cache_size_fn
        before = cs() if cs is not None else None
        t0 = self._ledger._clock()
        self.last_call_compiled = False
        try:
            with tracing.span(tracing.PROGRAM, **self._span_stats):
                out = self._inner(*args, **kwargs)
        finally:
            # compile detection must survive a RAISING dispatch: a
            # compile-then-execution-failure (OOM under HBM pressure —
            # exactly the regime the ledger instruments) warms the pjit
            # cache, so the retry would never trip the delta and the
            # program's signature/cost would be lost for the process
            if before is not None and cs() != before:
                self.last_call_compiled = True
                self._ledger._note_compile(
                    rec, self._inner, args, kwargs,
                    self._ledger._clock() - t0,
                )
        if self._ledger._prewarm_depth:
            # AOT replay dispatches are warmup, not traffic: compiles
            # above still count (decode_compilations semantics hold), but
            # runtime dispatch counters — and GV05's coverage question —
            # must not see them
            rec.prewarm_dispatches += 1
        else:
            rec.dispatches += 1
            if rec.c_dispatch is not None:
                rec.c_dispatch.inc()
        return out


class ProgramLedger:
    """Registry of every compiled program a subsystem dispatches.

    ``view``/``registry`` wire the ledger's labeled metric families
    (``{prefix}_program_dispatches{program=...}``, compile counters/walls,
    lazily-resolved flops/MFU gauges) into the shared metrics surface; with
    neither, the ledger owns a private registry so ``snapshot()`` always
    works. ``memory_analysis=True`` opts into one extra AOT compile per
    signature to obtain ``memory_analysis()`` numbers (builder
    contexts); the default keeps those fields UNAVAILABLE with zero extra
    compiles. Export gauges hold only weak references to the ledger — a
    registry an operator keeps alive never pins retired programs."""

    def __init__(
        self,
        registry=None,
        view=None,
        prefix: str = "program",
        subsystem: Optional[str] = None,
        timeline=None,
        memory_analysis: bool = False,
        clock: Callable[[], float] = time.perf_counter,
    ):
        from neuronx_distributed_tpu.observability.registry import (
            MetricsRegistry,
            MetricsView,
        )

        if view is None:
            view = MetricsView(
                registry if registry is not None else MetricsRegistry()
            )
        self._view = view
        self.registry = view.registry
        self._prefix = prefix
        self._subsystem = subsystem or prefix
        self._timeline = timeline
        self.memory_analysis = memory_analysis
        self._clock = clock
        self._prewarm_depth = 0
        # compiles counted over every program: two reads bracket a span of
        # host time (the serving step ledger's "did this step compile?")
        self.compiles = 0
        self._records: "OrderedDict[str, _ProgramRecord]" = OrderedDict()
        # implementation choices the owner resolved by platform ("auto" →
        # the kernel or the reference it actually traces), written once by
        # the engine/trainer and exported verbatim in snapshot()["resolved"]
        # (the trainer's "remat" is a list: the names its policy saves)
        self.resolved: Dict[str, Any] = {}
        self.peaks = device_peaks()
        name = self._name
        self._fam_dispatch = view.family(
            "counter", name("program_dispatches"), labels=("program",),
            help="dispatches of each ledgered compiled program",
        )
        self._fam_compiles = view.family(
            "counter", name("program_compiles"), labels=("program",),
            help="XLA compiles observed per ledgered program",
        )
        self._fam_wall = view.family(
            "histogram", name("program_wall_s"), labels=("program",),
            help="measured wall per dispatch window (caller-fed; s)",
        )
        self._fam_flops = view.family(
            "gauge", name("program_flops"), labels=("program",),
            help="compiler-reported FLOPs per dispatch (-1 = unavailable)",
        )
        self._fam_achieved = view.family(
            "gauge", name("program_achieved_flops"), labels=("program",),
            help="FLOPs/s over the last observed wall (-1 = unavailable)",
        )
        self._fam_mfu = view.family(
            "gauge", name("program_mfu"), labels=("program",),
            help="achieved FLOPs/s over device peak (-1 = unavailable)",
        )
        self._h_compile = view.histogram(
            name("compile_wall_s"),
            help="wall of each compile-triggering dispatch (s)",
        )

    @property
    def view(self):
        """The (possibly label-scoped) metrics view this ledger exports
        through — shared with sibling ledgers (e.g. the HBM ledger)."""
        return self._view

    def _name(self, suffix: str) -> str:
        return f"{self._prefix}_{suffix}" if self._prefix else suffix

    # --- registration --------------------------------------------------------

    def _get_record(self, name: str) -> _ProgramRecord:
        rec = self._records.get(name)
        if rec is None:
            rec = _ProgramRecord(name)
            self._records[name] = rec
            view = self._view
            rec.c_dispatch = view.child(self._fam_dispatch, name)
            rec.c_compiles = view.child(self._fam_compiles, name)
            rec.wall_hist = view.child(self._fam_wall, name)
            view.child(self._fam_flops, name).set_fn(weak_reader(
                self, lambda led: led.flops_per_dispatch(name), -1.0
            ))
            view.child(self._fam_achieved, name).set_fn(weak_reader(
                self, lambda led: led._achieved_flops_last(name), -1.0
            ))
            view.child(self._fam_mfu, name).set_fn(weak_reader(
                self, lambda led: led._mfu_last(name), -1.0
            ))
        return rec

    def wrap(self, name: str, fn) -> LedgeredProgram:
        """Return a dispatch-counting proxy for ``fn`` registered under
        ``name``. Wrapping the same name again (lazy rebuild, recompile, a
        second ``fit()``) shares the existing record — counts ACCUMULATE,
        they never double-register."""
        if isinstance(fn, LedgeredProgram):
            fn = fn.__wrapped__
        return LedgeredProgram(self, self._get_record(name), fn)

    @contextlib.contextmanager
    def prewarming(self):
        """Scope marking every dispatch through this ledger's proxies as a
        PREWARM replay: compiles still count (the ``decode_compilations``
        contract is exactly that the replay eats them), but dispatch
        counters route to ``prewarm_dispatches`` so runtime traffic
        accounting stays clean. Re-entrant."""
        self._prewarm_depth += 1
        try:
            yield self
        finally:
            self._prewarm_depth -= 1

    def manifest(self):
        """Serializable :class:`~..inference.aot.ProgramManifest` of every
        captured program signature — the AOT prewarm input. Lazy import:
        the ledger stays importable without the inference package."""
        from neuronx_distributed_tpu.inference.aot import ProgramManifest

        return ProgramManifest.from_ledger(self)

    def note_aot(self, name: str, lowered, compiled, wall_s: float) -> None:
        """Record a program the caller compiled AOT (the model builder's
        ``lower().compile()`` path): compile counted, wall recorded, and —
        because the ``Compiled`` is already in hand — cost AND memory
        analysis captured eagerly at zero extra compile cost."""
        rec = self._get_record(name)
        rec.compiles += 1
        rec.compile_wall_s += float(wall_s)
        if rec.c_compiles is not None:
            rec.c_compiles.inc()
        self._h_compile.observe(float(wall_s))
        try:
            in_avals = getattr(lowered, "in_avals", None)
            sig = _signature(tuple(in_avals or ()), {})
        except Exception:
            sig = f"aot:{rec.compiles}"
        var = rec.variants.get(sig)
        if var is None:
            var = _Variant(sig)
            rec.variants[sig] = var
        var.fill_from(lowered, compiled)
        self._emit_compile_event(rec, wall_s)

    def _note_compile(self, rec: _ProgramRecord, fn, args, kwargs,
                      wall_s: float) -> None:
        rec.compiles += 1
        rec.compile_wall_s += float(wall_s)
        if rec.c_compiles is not None:
            rec.c_compiles.inc()
        self._h_compile.observe(float(wall_s))
        try:
            a_args, a_kwargs = jax.tree_util.tree_map(
                _abstract_leaf, (args, dict(kwargs))
            )
            sig = _signature(a_args, a_kwargs)
            var = rec.variants.get(sig)
            if var is None:
                var = _Variant(sig, pending=(fn, a_args, a_kwargs))
                rec.variants[sig] = var
            else:
                # A re-compile under an EXISTING signature means a
                # different function object now owns the program — a
                # second engine's `per_instance` clone sharing this
                # record, or a lazy rebuild. Refresh the captured
                # callable so lower()/manifest() trace the LIVE program,
                # not the first instance's retired clone.
                var.abstract_call = (fn, a_args, a_kwargs)
                if not var.analyzed:
                    var.pending = (fn, a_args, a_kwargs)
            var.pedigree = [
                _leaf_pedigree(leaf)
                for leaf in jax.tree_util.tree_leaves((args, dict(kwargs)))
            ]
        except Exception:
            # signature capture is best-effort — the counts above are the
            # contract, the analysis degrades to UNAVAILABLE
            pass
        self._emit_compile_event(rec, wall_s)

    def _emit_compile_event(self, rec: _ProgramRecord, wall_s: float) -> None:
        self.compiles += 1
        if self._timeline is not None:
            self._timeline.instant(
                f"compile {rec.name}", self._subsystem,
                args={"wall_s": round(float(wall_s), 4),
                      "compiles": rec.compiles},
            )

    # --- roofline feed -------------------------------------------------------

    def observe_wall(self, name: str, wall_s: float) -> None:
        """Feed one measured wall (a host float the caller already owns —
        the serving chunk's dispatch+readback wall, the trainer's
        inter-step wall) for ``name``'s dispatch window. MFU/bandwidth are
        derived from these at export; nothing here touches the device."""
        rec = self._records.get(name)
        if rec is None or wall_s <= 0:
            return
        rec.last_wall_s = float(wall_s)
        if rec.wall_hist is not None:
            rec.wall_hist.observe(float(wall_s))

    # --- derived reads -------------------------------------------------------

    def record(self, name: str) -> Optional[_ProgramRecord]:
        return self._records.get(name)

    def dispatches(self, name: str) -> int:
        rec = self._records.get(name)
        return rec.dispatches if rec is not None else 0

    def programs(self) -> "OrderedDict[str, ProgramInfo]":
        """Public enumeration of every registered program: name →
        :class:`ProgramInfo` (host-side counts plus per-variant lazy
        ``lower()`` handles). This is the supported surface for external
        verification passes (scripts/graftverify) — tools iterate THIS, not
        ``_records``. Enumeration itself is pure host metadata: zero
        compiles, zero device→host syncs (regression-pinned in
        tests/observability/test_programs.py); only an explicit
        ``VariantInfo.lower()`` call traces, and even that never compiles."""
        return OrderedDict(
            (name, ProgramInfo(name, rec))
            for name, rec in self._records.items()
        )

    def _analyzed_sole(self, name: str, analyze: bool = True):
        rec = self._records.get(name)
        if rec is None:
            return None
        var = rec.sole_variant()
        if var is None:
            return None
        if analyze:
            var.ensure(self.memory_analysis)
        return var if var.analyzed else None

    def flops_per_dispatch(self, name: str, analyze: bool = True):
        """Compiler-reported FLOPs of one dispatch of ``name`` — defined
        only while the program has exactly ONE compiled signature (the
        roofline targets: decode chunk, train step). UNAVAILABLE
        otherwise."""
        var = self._analyzed_sole(name, analyze)
        return var.flops if var is not None else UNAVAILABLE

    def bytes_per_dispatch(self, name: str, analyze: bool = True):
        var = self._analyzed_sole(name, analyze)
        return var.bytes_accessed if var is not None else UNAVAILABLE

    def _achieved_flops_last(self, name: str):
        rec = self._records.get(name)
        if rec is None or not rec.last_wall_s:
            return UNAVAILABLE
        flops = self.flops_per_dispatch(name)
        if not isinstance(flops, float):
            return UNAVAILABLE
        return flops / rec.last_wall_s

    def _mfu_last(self, name: str):
        achieved = self._achieved_flops_last(name)
        peak = self.peaks["flops"]
        if not isinstance(achieved, float) or not isinstance(peak, float):
            return UNAVAILABLE
        return achieved / peak

    # --- export --------------------------------------------------------------

    def _entry(self, rec: _ProgramRecord, analyze: bool,
               include_timing: bool) -> dict:
        if analyze:
            for var in rec.variants.values():
                var.ensure(self.memory_analysis)
        sole = rec.sole_variant()
        flops = sole.flops if sole is not None and sole.analyzed else UNAVAILABLE
        nbytes = (
            sole.bytes_accessed if sole is not None and sole.analyzed
            else UNAVAILABLE
        )
        donated = (
            sole.donated_argnums if sole is not None and sole.analyzed
            else UNAVAILABLE
        )
        if isinstance(donated, list) and len(donated) > 16:
            # Lowered.donate_argnums is FLATTENED positions — a donated
            # params pytree yields hundreds; the count is the signal
            donated = {"count": len(donated)}
        entry = {
            "dispatches": rec.dispatches,
            "compiles": rec.compiles,
            "variants": len(rec.variants),
            "donated_argnums": donated,
            "cost_source": (
                sole.cost_source if sole is not None and sole.analyzed
                else UNAVAILABLE
            ),
            "flops_per_dispatch": flops,
            "bytes_per_dispatch": nbytes,
            "arithmetic_intensity": (
                flops / nbytes
                if isinstance(flops, float) and isinstance(nbytes, float)
                and nbytes > 0 else UNAVAILABLE
            ),
            "flops_total": (
                flops * rec.dispatches if isinstance(flops, float)
                else UNAVAILABLE
            ),
            "bytes_total": (
                nbytes * rec.dispatches if isinstance(nbytes, float)
                else UNAVAILABLE
            ),
            "memory": dict(
                sole.memory if sole is not None and sole.analyzed
                else _EMPTY_MEMORY
            ),
        }
        if rec.prewarm_dispatches:
            entry["prewarm_dispatches"] = rec.prewarm_dispatches
        if len(rec.variants) > 1:
            entry["variant_cost"] = {
                var.sig: {
                    "flops": var.flops if var.analyzed else UNAVAILABLE,
                    "bytes_accessed": (
                        var.bytes_accessed if var.analyzed else UNAVAILABLE
                    ),
                }
                for var in rec.variants.values()
            }
        if include_timing:
            entry["compile_wall_s"] = round(rec.compile_wall_s, 6)
            h = rec.wall_hist
            if h is not None and h.count:
                p50 = h.percentile(0.50)
                entry["wall"] = {
                    "count": h.count,
                    "sum_s": float(h.sum),
                    "p50_s": p50,
                    "p95_s": h.percentile(0.95),
                }
                if isinstance(flops, float) and p50 > 0:
                    achieved = flops / p50
                    entry["achieved_flops_p50"] = achieved
                    peak = self.peaks["flops"]
                    entry["mfu_p50"] = (
                        achieved / peak if isinstance(peak, float)
                        else UNAVAILABLE
                    )
                else:
                    entry["achieved_flops_p50"] = UNAVAILABLE
                    entry["mfu_p50"] = UNAVAILABLE
                bw = self.peaks["hbm_bytes_per_s"]
                entry["hbm_bw_util_p50"] = (
                    (nbytes / p50) / bw
                    if isinstance(nbytes, float) and p50 > 0
                    and isinstance(bw, float) else UNAVAILABLE
                )
        return entry

    def snapshot(self, analyze: bool = True,
                 include_timing: bool = True) -> dict:
        """``{"device", "resolved", "by_program", "totals"}`` — the full
        ledger.
        ``analyze=False`` skips any not-yet-run cost analysis (halt paths:
        no tracing on an error path); ``include_timing=False`` drops every
        wall-clock-derived field, leaving a projection that is
        deterministic across identical runs (the regression pin)."""
        programs = {
            name: self._entry(rec, analyze, include_timing)
            for name, rec in sorted(self._records.items())
        }
        totals: Dict[str, Any] = {
            "programs": len(programs),
            "dispatches": sum(r.dispatches for r in self._records.values()),
            "compiles": sum(r.compiles for r in self._records.values()),
        }
        known = [
            e["flops_total"] for e in programs.values()
            if isinstance(e["flops_total"], float)
        ]
        totals["flops_total_known"] = sum(known) if known else UNAVAILABLE
        if include_timing:
            totals["compile_wall_s"] = round(
                sum(r.compile_wall_s for r in self._records.values()), 6
            )
        device = {
            "kind": self.peaks["kind"],
            "platform": self.peaks["platform"],
            "peak_flops": self.peaks["flops"],
            "peak_hbm_bytes_per_s": self.peaks["hbm_bytes_per_s"],
            "peak_source": self.peaks["source"],
        }
        return {
            "device": device,
            "resolved": dict(self.resolved),
            "by_program": programs,
            "totals": totals,
        }

    def halt_summary(self, top: int = 6) -> dict:
        """Flat top-N program table for halt post-mortems: scalars only,
        two levels deep, shaped to survive the flight recorder's depth-3
        redaction. ``analyze=False`` — an error path must not start
        tracing programs; cost fields show whatever analysis already ran."""
        ranked = sorted(
            self._records.values(),
            key=lambda r: (-r.dispatches, r.name),
        )[:top]
        out = {}
        for rec in ranked:
            flops = self.flops_per_dispatch(rec.name, analyze=False)
            out[rec.name] = {
                "dispatches": rec.dispatches,
                "compiles": rec.compiles,
                "variants": len(rec.variants),
                "compile_wall_s": round(rec.compile_wall_s, 4),
                "flops_per_dispatch": (
                    flops if isinstance(flops, float) else UNAVAILABLE
                ),
            }
        return out

    def table(self) -> str:
        """Human-readable ledger table (demo ``--programs`` output)."""
        snap = self.snapshot()
        rows = [(
            "program", "disp", "compiles", "flops/disp", "bytes/disp",
            "AI", "compile_s", "wall_p50_s", "mfu_p50",
        )]

        def fmt(v, nd=3):
            if isinstance(v, float):
                return f"{v:.{nd}g}"
            return str(v)

        by = snap["by_program"]
        order = sorted(
            by, key=lambda n: (-(by[n]["dispatches"]), n)
        )
        for name in order:
            e = by[name]
            wall = e.get("wall", {})
            rows.append((
                name, str(e["dispatches"]), str(e["compiles"]),
                fmt(e["flops_per_dispatch"], 4),
                fmt(e["bytes_per_dispatch"], 4),
                fmt(e["arithmetic_intensity"]),
                fmt(e.get("compile_wall_s", 0.0)),
                fmt(wall.get("p50_s", UNAVAILABLE)),
                fmt(e.get("mfu_p50", UNAVAILABLE)),
            ))
        widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
        lines = [
            "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
            for row in rows
        ]
        dev = snap["device"]
        lines.append(
            f"device: {dev['platform']}/{dev['kind'] or '?'}  "
            f"peak_flops={fmt(dev['peak_flops'], 4)}  "
            f"peak_hbm_B/s={fmt(dev['peak_hbm_bytes_per_s'], 4)}"
        )
        return "\n".join(lines)
