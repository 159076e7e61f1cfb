"""AOT model builder + bucket-routing runtime (reference:
``trace/model_builder.py`` ``ModelBuilder:106`` and ``trace/spmd.py``
``NxDModel:71``).

The reference traces one HLO per (model-key, bucket), compiles NEFFs on a
thread pool, grafts compiler-chosen weight layouts across sibling HLOs, and
assembles a torchscript router. On TPU every one of those stages is a JAX
primitive: ``jax.jit(fn).lower(*args).compile()`` is the AOT compile (layout
assignment included), ``jax.export`` provides portable serialized executables,
and the shape router stays a small Python class. Sharded inference works by
compiling with the params' NamedShardings baked in.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)


@dataclasses.dataclass
class _Entry:
    fn: Callable
    bucket_args: List[Tuple[Any, ...]]  # example args, ascending bucket size
    bucket_dim: int  # which dim of args[route_argnum] routes buckets
    route_argnum: int
    unpad: Optional[Callable] = None


class NxDModel:
    """Shape-routed bundle of compiled executables (reference
    ``trace/spmd.py:71`` torchscript module + its input router ``:144``)."""

    def __init__(self):
        self._compiled: Dict[str, List[Tuple[int, Callable]]] = {}
        self._route: Dict[str, Tuple[int, int]] = {}
        self._unpad: Dict[str, Optional[Callable]] = {}

    def add_compiled(self, key, bucket_size, call, bucket_dim, route_argnum,
                     unpad: Optional[Callable] = None):
        self._compiled.setdefault(key, []).append((bucket_size, call))
        self._compiled[key].sort(key=lambda t: t[0])
        self._route[key] = (bucket_dim, route_argnum)
        self._unpad[key] = unpad

    def buckets(self, key) -> List[int]:
        return [b for b, _ in self._compiled[key]]

    def __call__(self, key: str, *args):
        """Route to the smallest bucket that fits, right-padding the routed
        dim. With an ``unpad`` callback registered for the key (ModelBuilder
        ``add(..., unpad=...)``), outputs are mapped back to the caller's
        original size: ``unpad(outputs, original_size)``; without one,
        outputs keep the bucket shape (the reference's raw bucketed
        semantics — round-2 weak #8 flagged this as a sharp edge, hence the
        explicit opt-in contract)."""
        bucket_dim, route_argnum = self._route[key]
        size = args[route_argnum].shape[bucket_dim]
        for bucket_size, call in self._compiled[key]:
            if size <= bucket_size:
                if size < bucket_size:
                    args = list(args)
                    a = args[route_argnum]
                    pad = [(0, 0)] * a.ndim
                    pad[bucket_dim] = (0, bucket_size - size)
                    args[route_argnum] = jnp.pad(a, pad)
                out = call(*args)
                unpad = self._unpad.get(key)
                if unpad is not None and size < bucket_size:
                    out = unpad(out, size)
                return out
        raise ValueError(
            f"input size {size} exceeds largest bucket "
            f"{self._compiled[key][-1][0]} for model key {key!r}"
        )


class ModelBuilder:
    """Collect named sub-models with bucketed example inputs, AOT-compile
    them, and assemble the routed :class:`NxDModel` (reference
    ``ModelBuilder.add:158`` / ``trace:189``)."""

    def __init__(self):
        self._entries: Dict[str, _Entry] = {}

    def add(
        self,
        key: str,
        fn: Callable,
        bucket_args: Sequence[Tuple[Any, ...]],
        bucket_dim: int = -1,
        route_argnum: int = 0,
        unpad: Optional[Callable] = None,
    ) -> "ModelBuilder":
        """Register ``fn`` with one example-args tuple per bucket (reference
        add:158 — e.g. key "context_encode" with seq buckets 128/512/2048 and
        key "token_gen" with a single decode bucket). ``unpad(outputs,
        original_size)`` maps bucket-shaped outputs back to the caller's
        size (e.g. ``lambda out, n: out[:, :n]`` for per-position logits)."""
        sizes = [a[route_argnum].shape[bucket_dim] for a in bucket_args]
        order = sorted(range(len(sizes)), key=lambda i: sizes[i])
        self._entries[key] = _Entry(
            fn=fn,
            bucket_args=[tuple(bucket_args[i]) for i in order],
            bucket_dim=bucket_dim,
            route_argnum=route_argnum,
            unpad=unpad,
        )
        return self

    def trace(self, donate_argnums: Tuple[int, ...] = (),
              programs=None, aot_cache: Optional[str] = None) -> NxDModel:
        """AOT-compile every (key, bucket) (reference trace:189; the thread
        pool + priority-NEFF layout grafting are unnecessary — XLA compiles
        each executable with its own layout assignment).

        ``programs`` (a :class:`~neuronx_distributed_tpu.observability.
        programs.ProgramLedger`) records each executable under
        ``"{key}[{bucket}]"`` — compile wall, cost analysis AND memory
        analysis captured eagerly at zero extra compile cost (the
        ``Compiled`` is already in hand on this path), with the routed
        calls dispatch-counted through ledger proxies.

        ``aot_cache`` (ISSUE 17) makes the trace restore-or-compile: the
        persistent compile cache is pointed at ``aot_cache/xla``, and each
        (key, bucket) first tries a serialized executable keyed by its
        call signature — deserialization skips XLA entirely; a miss
        compiles (a disk hit when the cache has seen the program) and
        writes the artifact for the next process. Skew falls back to
        compile, loudly, never fatally."""
        aot = None
        if aot_cache is not None:
            from neuronx_distributed_tpu.inference import aot as aot_mod

            aot = aot_mod
            aot.enable_persistent_cache(os.path.join(aot_cache, aot.XLA_SUBDIR))
        model = NxDModel()
        for key, entry in self._entries.items():
            jitted = jax.jit(entry.fn, donate_argnums=donate_argnums)
            for args in entry.bucket_args:
                size = args[entry.route_argnum].shape[entry.bucket_dim]
                name = f"{key}[{size}]"
                compiled = lowered = None
                if aot is not None:
                    sig = aot.call_signature(args)
                    try:
                        compiled = aot.load_executable(aot_cache, name, sig)
                    except aot.SkewError as e:
                        logger.warning("AOT skew on %s (%s); recompiling",
                                       name, e)
                if compiled is not None:
                    wall = 0.0
                    logger.info("restored %s bucket=%d from AOT cache",
                                key, size)
                else:
                    t0 = time.perf_counter()
                    lowered = jitted.lower(*args)
                    if aot is not None:
                        # this executable will be serialized
                        compiled = aot.compile_serializable(lowered)
                    else:
                        compiled = lowered.compile()
                    wall = time.perf_counter() - t0
                    logger.info("compiled %s bucket=%d", key, size)
                    if aot is not None:
                        try:
                            aot.save_executable(aot_cache, name, sig, compiled)
                        except Exception as e:
                            logger.warning(
                                "AOT serialize failed for %s: %s", name, e
                            )
                call = compiled
                if programs is not None:
                    if lowered is not None:
                        programs.note_aot(name, lowered, compiled, wall)
                    # a restored program records NO compile — that is the
                    # point — but its dispatches still count via the proxy
                    call = programs.wrap(name, compiled)
                model.add_compiled(
                    key, size, call, entry.bucket_dim, entry.route_argnum,
                    unpad=entry.unpad,
                )
        return model

    # --- serialized executables (reference parallel_model_save/load,
    # trace/trace.py:375,400) -------------------------------------------------

    def save(self, path: str) -> None:
        """Serialize every (key, bucket) via ``jax.export`` so serving hosts
        skip retracing (reference saves per-rank torchscript+NEFF)."""
        from jax import export as jax_export

        os.makedirs(path, exist_ok=True)
        manifest = {}
        for key, entry in self._entries.items():
            for args in entry.bucket_args:
                size = args[entry.route_argnum].shape[entry.bucket_dim]
                exp = jax_export.export(jax.jit(entry.fn))(*args)
                fname = f"{key}.{size}.bin"
                with open(os.path.join(path, fname), "wb") as f:
                    f.write(exp.serialize())
                manifest.setdefault(key, []).append(
                    {
                        "bucket": int(size),
                        "file": fname,
                        "bucket_dim": entry.bucket_dim,
                        "route_argnum": entry.route_argnum,
                    }
                )
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)

    @staticmethod
    def load(path: str) -> NxDModel:
        from jax import export as jax_export

        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        model = NxDModel()
        for key, buckets in manifest.items():
            for info in buckets:
                with open(os.path.join(path, info["file"]), "rb") as f:
                    exp = jax_export.deserialize(f.read())
                model.add_compiled(
                    key,
                    info["bucket"],
                    exp.call,
                    info["bucket_dim"],
                    info["route_argnum"],
                )
        return model
