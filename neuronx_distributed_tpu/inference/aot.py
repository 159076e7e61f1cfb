"""AOT serving: persistent compile cache, serialized executables, and
ledger-driven prewarm (ISSUE 17 / ROADMAP "AOT serving" item).

Engine construction traces and compiles every program on first dispatch —
fine for one long-lived process, fatal for elastic scale-up (a spawned
replica pays the full compile bill before it can adopt work) and for the
tier-1 budget. The pjit/TPUv4 scaling work (PAPERS.md, arXiv 2204.06514)
treats ahead-of-time compilation and a persistent compile cache as table
stakes; the :class:`~..observability.programs.ProgramLedger` already
records every hot program's name, abstract signature, and donation map.
This module is the consumer that was missing — three layers, each a
rung of the fallback ladder:

1. **Persistent compilation cache** (:func:`enable_persistent_cache`) —
   the ONE owner of ``jax_compilation_cache_dir`` wiring, used by the
   engine, builder, trainer, examples, and the test suite. Keyed by
   XLA on the optimized HLO. A directory placed from outside
   (``JAX_COMPILATION_CACHE_DIR``) is left alone; the default one is
   namespaced per host-CPU fingerprint on the CPU backend only
   (utils/platform.py — a foreign XLA:CPU entry can SIGILL). Makes every
   RE-compile of a known program a disk hit.
2. **Serialized executables** (:func:`save_executable` /
   :func:`load_executable`) — ``jax.experimental.serialize_executable``
   payloads keyed by ``(program name, ledger signature)``, written next
   to the manifest. A deserialize skips XLA entirely
   (``decode_compilations == 0``); ANY header mismatch (jax/jaxlib
   version, platform, device kind, host fingerprint) or unpicklable blob
   raises :class:`SkewError` and the caller drops one rung.
3. **Trace-level prewarm** (:func:`prewarm_programs`) — replay-dispatch
   every manifest entry with pedigree-faithful dummy arguments BEFORE the
   first request, so compiles (disk hits, given rung 1) happen at warmup,
   not inside the first request's TTFT. This is the fail-soft floor: it
   needs only the live function and the manifest.

The replay trick is load-bearing: jit's DISPATCH cache and the AOT
``lower().compile()`` cache do not share (``fn.lower(...).compile()``
leaves ``fn._cache_size() == 0`` — measured on this jax), so a classic
AOT warmup would still pay a dispatch-cache miss on the first real call.
Replaying through the ledger proxy with arguments that land in the same
dispatch-cache ENTRY (same abstract signature AND same argument pedigree
— committed/uncommitted/numpy/static, recorded per leaf at compile time)
makes the first real dispatch a pure cache hit: zero new compiles,
pinned by ``_cache_size`` deltas in tests/serving/test_aot.py.
"""

from __future__ import annotations

import contextlib
import json
import os
import pickle
import re
from typing import Any, Callable, Dict, List, Optional

import numpy as np

__all__ = [
    "AOTProgram",
    "MANIFEST_NAME",
    "ProgramManifest",
    "SkewError",
    "UnportableError",
    "XLA_SUBDIR",
    "call_signature",
    "compile_serializable",
    "enable_persistent_cache",
    "load_executable",
    "materialize_call",
    "persistent_cache_dir",
    "prewarm_programs",
    "save_executable",
]

MANIFEST_NAME = "manifest.json"
XLA_SUBDIR = "xla"  # persistent-compile-cache subdir inside an AOT dir
ARTIFACT_SUFFIX = ".aotx"
DISABLE_ENV = "NXD_TPU_PERSISTENT_CACHE"  # "0"/"off"/"false" disables
PLACED_ENV = "JAX_COMPILATION_CACHE_DIR"  # set => the dir is not ours to move

_FORMAT = 1
_CACHE_DIR: Optional[str] = None


class SkewError(RuntimeError):
    """A serialized executable cannot be trusted on this host/version —
    the caller must fall back to trace-level prewarm, never crash."""


class UnportableError(RuntimeError):
    """A manifest entry cannot be encoded/replayed faithfully (opaque
    leaf, unknown sharding) — skip the entry, never guess."""


# --- persistent compilation cache (rung 1) --------------------------------


def enable_persistent_cache(
    path: str,
    *,
    min_compile_time_secs: float = 0.0,
    host_scoped: bool = True,
) -> Optional[str]:
    """Turn on jax's persistent compilation cache (the ONE owner of this
    wiring — engine, builder, trainer, examples, ``chip_smoke.py`` and
    conftest all route here). Returns the directory in use, or None when
    disabled via ``NXD_TPU_PERSISTENT_CACHE=0``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside: jax reads the variable itself, and this function neither sets
    another directory nor appends anything to that one — ``path`` is
    ignored. Otherwise the cache goes to ``path``; on the CPU backend, with
    ``host_scoped=True``, under a host-CPU fingerprint namespace
    (utils/platform.py) — a foreign XLA:CPU AOT entry can SIGILL, so a
    moved cache must go cold, not lethal. On an accelerator the path is
    used as given: the fingerprint folds in the kernel release and
    microcode, which differ on every machine, so a namespaced TPU cache
    would never hit.

    ``min_compile_time_secs`` defaults to 0 (cache everything) — right
    for small AOT bundles where the next process replays every program —
    but bulk consumers should set a floor: disk round-tripping a
    sub-second program costs more than its compile (conftest pins 0.5 off
    measurement).

    Safe to call mid-process even after compiles have run: jax memoizes
    the cache-enabled check on first use, so the cache object is reset
    when the directory actually changes. Idempotent for a repeated
    identical path."""
    global _CACHE_DIR
    if os.environ.get(DISABLE_ENV, "1").strip().lower() in (
        "0", "off", "false", "no",
    ):
        return None
    import jax

    placed = os.environ.get(PLACED_ENV)
    if placed:
        resolved = placed
    elif host_scoped and jax.default_backend() == "cpu":
        from neuronx_distributed_tpu.utils.platform import host_cache_dir

        resolved = host_cache_dir(path)
    else:
        resolved = path
        os.makedirs(resolved, exist_ok=True)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", resolved)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(min_compile_time_secs),
    )
    if _CACHE_DIR != resolved:
        # drop the memoized "is the cache in use" check so a dir set
        # AFTER the process's first compile still takes effect
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    _CACHE_DIR = resolved
    return resolved


def persistent_cache_dir() -> Optional[str]:
    """The directory :func:`enable_persistent_cache` last wired, or None."""
    return _CACHE_DIR


# --- manifest codec -------------------------------------------------------
#
# An abstract call is encoded as its pytree TREEDEF (pickled — the params
# tree contains registered custom nodes like the partitioner's boxed
# leaves, which no hand-rolled JSON walk can reconstruct) plus a flat
# leaf list in flatten order, which zips exactly with the per-leaf
# pedigree the ledger recorded at compile time. Array leaves carry
# shape/dtype plus the pedigree kind; Python scalars carry their VALUE (a
# static_argnums bucket int must replay exactly). Anything else is
# unportable — skipped loudly, never guessed. The pickled treedef shares
# the checkpoint trust boundary (a manifest lives NEXT to the weights it
# describes); loading one requires the defining classes importable, which
# is exactly the same-codebase contract prewarm already needs.


def _encode_leaf(x, ped: dict) -> dict:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        node: Dict[str, Any] = {
            "t": "aval",
            "shape": [int(s) for s in x.shape],
            "dtype": str(np.dtype(x.dtype)),
        }
        kind = ped.get("kind", "jax")
        if kind != "jax":
            node["kind"] = kind
        for key in ("committed", "spec", "weak"):
            if key in ped:
                node[key] = ped[key]
        return node
    if isinstance(x, (bool, int, float, str)):
        return {"t": "py", "v": x}
    raise UnportableError(f"opaque leaf {type(x).__name__}")


def encode_call(a_args, a_kwargs, pedigree=None) -> dict:
    """Encode one captured abstract call ``(args, kwargs)`` (ShapeDtype
    skeletons + static leaves) as treedef + flat leaves, zipping in the
    per-leaf dispatch pedigree. Raises :class:`UnportableError` on
    anything that cannot round-trip faithfully."""
    import base64

    import jax

    leaves, treedef = jax.tree_util.tree_flatten(
        (tuple(a_args), dict(a_kwargs or {}))
    )
    peds = list(pedigree or [])
    if pedigree is not None and len(peds) != len(leaves):
        raise UnportableError(
            f"pedigree mismatch: {len(peds)} pedigrees, {len(leaves)} leaves"
        )
    enc = [
        _encode_leaf(leaf, peds[i] if i < len(peds) else {"kind": "jax"})
        for i, leaf in enumerate(leaves)
    ]
    try:
        td = base64.b64encode(pickle.dumps(treedef)).decode("ascii")
    except Exception as e:
        raise UnportableError(
            f"treedef not picklable: {type(e).__name__}: {e}"
        )
    return {
        "t": "flat",
        "treedef": td,
        "leaves": enc,
        # human-readable structure hint only — replay uses the pickle
        "structure": str(treedef)[:400],
    }


def _dummy_array(node: dict, sharding_resolver=None):
    shape = tuple(int(s) for s in node.get("shape", ()))
    dtype = np.dtype(node.get("dtype", "float32"))
    kind = node.get("kind", "jax")
    if kind == "np":
        return np.zeros(shape, dtype)
    if kind == "np_scalar":
        return dtype.type(0)
    import jax
    import jax.numpy as jnp

    if node.get("weak") and shape == ():
        # weak-typed scalars (bare Python ints/floats that became jax
        # arrays) key differently from strong ones — reproduce via asarray
        if dtype.kind == "i":
            return jnp.asarray(0)
        if dtype.kind == "f":
            return jnp.asarray(0.0)
    if node.get("committed"):
        spec = node.get("spec")
        if spec is not None:
            sh = sharding_resolver(spec) if sharding_resolver else None
            if sh is None:
                raise UnportableError(
                    f"committed sharded leaf {spec} needs a resolver"
                )
            return jax.device_put(np.zeros(shape, dtype), sh)
        return jax.device_put(np.zeros(shape, dtype), jax.devices()[0])
    return jnp.zeros(shape, dtype)


def materialize_call(call_node: dict, sharding_resolver=None):
    """Build pedigree-faithful dummy ``(args, kwargs)`` for one manifest
    entry — each array leaf lands in the SAME pjit dispatch-cache entry
    the recorded runtime argument did. Values are zeros (or the recorded
    literal for static Python leaves); only shape/dtype/pedigree matter
    for the dispatch key."""
    import base64

    import jax

    if not isinstance(call_node, dict) or call_node.get("t") != "flat":
        raise UnportableError("manifest call node is not a flat encoding")
    try:
        treedef = pickle.loads(base64.b64decode(call_node["treedef"]))
    except Exception as e:
        raise UnportableError(
            f"treedef not loadable here: {type(e).__name__}: {e}"
        )
    leaves = []
    for node in call_node["leaves"]:
        t = node.get("t")
        if t == "py":
            leaves.append(node["v"])
        elif t == "aval":
            leaves.append(_dummy_array(node, sharding_resolver))
        else:
            raise UnportableError(f"unknown manifest leaf {t!r}")
    try:
        built = jax.tree_util.tree_unflatten(treedef, leaves)
    except Exception as e:
        raise UnportableError(
            f"unflatten failed: {type(e).__name__}: {e}"
        )
    if not isinstance(built, tuple) or len(built) != 2:
        raise UnportableError("manifest call node is not an (args, kwargs)")
    args, kwargs = built
    return tuple(args), dict(kwargs or {})


def call_signature(args, kwargs=None) -> str:
    """Ledger-compatible signature digest of a CONCRETE call — the
    artifact key the builder uses before any ledger record exists."""
    from neuronx_distributed_tpu.observability.programs import (
        _abstract_leaf,
        _signature,
    )

    import jax

    a_args, a_kwargs = jax.tree_util.tree_map(
        _abstract_leaf, (tuple(args), dict(kwargs or {}))
    )
    return _signature(a_args, a_kwargs)


# --- ProgramManifest ------------------------------------------------------


class ProgramManifest:
    """Serializable record of every ledger-registered program: name +
    abstract signature (avals / pedigree / donation map), persisted as
    JSON next to checkpoints and AOT artifacts. ``programs`` maps name →
    list of variant dicts ``{"signature", "call", "portable", "note",
    "donated_argnums"}``; ``call`` is the :func:`encode_call` node tree
    (None when uncapturable — the entry is then documentation, not
    replayable)."""

    def __init__(self, programs: Dict[str, List[dict]], meta=None):
        self.programs = programs
        self.meta = dict(meta or {})

    @classmethod
    def from_ledger(cls, ledger, names=None) -> "ProgramManifest":
        import jax

        programs: Dict[str, List[dict]] = {}
        for name, info in ledger.programs().items():
            if names is not None and name not in names:
                continue
            entries = []
            for var in info.variants:
                entry: Dict[str, Any] = {
                    "signature": var.signature,
                    "call": None,
                    "portable": False,
                    "note": "",
                }
                donated = getattr(var._variant, "donated_argnums", None)
                if isinstance(donated, list):
                    entry["donated_argnums"] = donated
                if not var.captured:
                    entry["note"] = "signature not captured (AOT record)"
                else:
                    try:
                        entry["call"] = encode_call(
                            var.abstract_args,
                            var.abstract_kwargs,
                            var.pedigree,
                        )
                        entry["portable"] = True
                    except UnportableError as e:
                        entry["note"] = str(e)
                entries.append(entry)
            programs[name] = entries
        try:
            dev = jax.devices()[0]
            device_kind = str(getattr(dev, "device_kind", ""))
            platform = str(getattr(dev, "platform", ""))
        except Exception:
            device_kind = platform = ""
        meta = {
            "format": _FORMAT,
            "jax": jax.__version__,
            "platform": platform,
            "device_kind": device_kind,
        }
        return cls(programs, meta)

    def names(self):
        return list(self.programs)

    def entries(self, name: str) -> List[dict]:
        return list(self.programs.get(name, ()))

    def to_json(self) -> dict:
        return {"meta": self.meta, "programs": self.programs}

    @classmethod
    def from_json(cls, obj: dict) -> "ProgramManifest":
        if not isinstance(obj, dict) or "programs" not in obj:
            raise ValueError("not a ProgramManifest JSON object")
        return cls(dict(obj["programs"]), obj.get("meta"))

    def save(self, path: str) -> str:
        """Write as JSON. ``path`` may be a directory (uses
        ``manifest.json`` inside) or a file path. Atomic replace."""
        if os.path.isdir(path):
            path = os.path.join(path, MANIFEST_NAME)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "ProgramManifest":
        if os.path.isdir(path):
            path = os.path.join(path, MANIFEST_NAME)
        with open(path) as f:
            return cls.from_json(json.load(f))


# --- serialized executables (rung 2) --------------------------------------


def _artifact_path(dirpath: str, name: str, signature: str) -> str:
    import hashlib

    h = hashlib.sha1(f"{name}@{signature}".encode()).hexdigest()[:16]
    safe = re.sub(r"[^A-Za-z0-9_.\[\]-]", "_", name)[:48]
    return os.path.join(dirpath, f"{safe}.{h}{ARTIFACT_SUFFIX}")


def _skew_header() -> dict:
    import jax
    import jaxlib

    from neuronx_distributed_tpu.utils.platform import host_fingerprint

    try:
        dev = jax.devices()[0]
        platform = str(getattr(dev, "platform", ""))
        device_kind = str(getattr(dev, "device_kind", ""))
    except Exception:
        platform = device_kind = ""
    return {
        "format": _FORMAT,
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", ""),
        "platform": platform,
        "device_kind": device_kind,
        # CPU executables embed target features; a foreign entry can
        # SIGILL (utils/platform.py) — fence per host fingerprint
        "host": host_fingerprint() if platform == "cpu" else "",
    }


def compile_serializable(lowered):
    """Compile ``lowered`` afresh for :func:`save_executable`, with the
    persistent disk cache BYPASSED and whatever this process compiled
    before. An XLA:CPU executable that was LOADED from the disk cache
    serializes WITHOUT its jitted object code — the payload round-trips
    in-process but fails in a fresh process (``Function ... not found``;
    a program with a sort does not serialize at all) — while a fresh
    compile embeds the code: one real compile per saved program, paid
    once at save time. Turning the cache off is not enough: ``jit.lower``
    hands back the lowering the dispatch built, and jax memoises that
    lowering's executable (on the lowering, and again by its module in
    ``pxla._cached_compilation``), so a plain ``lowered.compile()``
    returns the executable the traffic ran with. A compiler option is
    part of both memo keys: naming one at its default value changes
    nothing in the program and reaches the compiler."""
    import jax

    prev = bool(jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile(
            compiler_options={"xla_embed_ir_in_executable": False}
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def save_executable(dirpath: str, name: str, signature: str, compiled) -> str:
    """Serialize one ``jax.stages.Compiled`` under its ledger key.
    Atomic write; raises on serialization failure (caller decides whether
    that is fatal — for ``save_aot`` it is a per-program skip)."""
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    # the ids of the devices it was compiled for: ``deserialize_and_load``
    # loads onto every device of the backend unless told, and a one-device
    # program loaded onto eight then wants eight shards of each argument
    devices = [d.id for d in compiled.runtime_executable().local_devices()]
    header = dict(
        _skew_header(), name=name, signature=signature, devices=devices
    )
    blob = pickle.dumps(
        (header, payload, in_tree, out_tree),
        protocol=pickle.HIGHEST_PROTOCOL,
    )
    os.makedirs(dirpath, exist_ok=True)
    path = _artifact_path(dirpath, name, signature)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return path


def load_executable(dirpath: str, name: str, signature: str):
    """Deserialize the executable for ``(name, signature)``. Returns None
    when no artifact exists; raises :class:`SkewError` when one exists
    but cannot be trusted (corrupt blob, version/platform/host mismatch,
    deserialization failure) — the caller falls back to trace-level
    prewarm and records a loud flight event, never crashes."""
    path = _artifact_path(dirpath, name, signature)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            header, payload, in_tree, out_tree = pickle.loads(f.read())
    except Exception as e:
        raise SkewError(
            f"corrupt AOT artifact {os.path.basename(path)}: "
            f"{type(e).__name__}: {e}"
        )
    want = dict(_skew_header(), name=name, signature=signature)
    if not isinstance(header, dict):
        raise SkewError(f"malformed AOT header in {os.path.basename(path)}")
    for key, expect in want.items():
        got = header.get(key)
        if got != expect:
            raise SkewError(
                f"AOT skew on {key!r}: artifact has {got!r}, "
                f"host wants {expect!r}"
            )
    try:
        import jax
        from jax.experimental import serialize_executable as se

        by_id = {d.id: d for d in jax.devices()}
        return se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in header["devices"]],
        )
    except Exception as e:
        raise SkewError(
            f"deserialize failed for {name}@{signature}: "
            f"{type(e).__name__}: {e}"
        )


class AOTProgram:
    """Dispatch shim over a deserialized ``Compiled``: tries the AOT
    executable, permanently falls back to the live jitted function on the
    first signature mismatch (recording a flight event). Duck-types the
    ledger-proxy surface — ``_cache_size`` reads the FALLBACK's pjit
    cache, so ``decode_compilations`` reports 0 while the deserialized
    path serves and only counts real compiles if the fallback engages."""

    def __init__(self, name, compiled, fallback, flight=None):
        self._name = name
        self._compiled = compiled
        self._fallback = fallback
        self._flight = flight
        self.used_fallback = False

    @property
    def __wrapped__(self):
        return self._fallback

    def _cache_size(self) -> int:
        cs = getattr(self._fallback, "_cache_size", None)
        return int(cs()) if cs is not None else 0

    def lower(self, *args, **kwargs):
        return self._fallback.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fallback, name)

    def __call__(self, *args, **kwargs):
        if not self.used_fallback:
            try:
                return self._compiled(*args, **kwargs)
            except (TypeError, ValueError) as e:
                # aval/layout mismatch — the live program's real call
                # convention drifted from the artifact; engage the jit
                # fallback for good and say so loudly
                self.used_fallback = True
                if self._flight is not None:
                    try:
                        self._flight.record(
                            "aot_fallback",
                            program=self._name,
                            error=f"{type(e).__name__}: {e}"[:200],
                        )
                    except Exception:
                        pass
        return self._fallback(*args, **kwargs)


# --- prewarm (rungs 2+3) --------------------------------------------------


def prewarm_programs(
    manifest: ProgramManifest,
    resolve: Callable[[str], Any],
    *,
    ledger=None,
    artifact_dir: Optional[str] = None,
    install: Optional[Callable[[str, AOTProgram], bool]] = None,
    mode: str = "auto",
    flight=None,
    sharding_resolver=None,
) -> dict:
    """Restore or compile every manifest program up front. For each entry:
    try deserialize-install (``mode="auto"``, single-variant programs with
    an artifact and an ``install`` hook), else replay-dispatch pedigree-
    faithful dummies through the live proxy from ``resolve(name)`` so the
    first real dispatch is a pure dispatch-cache hit. ``mode="trace"``
    skips artifacts entirely. Failures degrade rung by rung — skew →
    replay, unportable/unresolvable → skip — each recorded in the report
    and on the flight recorder; nothing raises."""
    import time as _time

    report: Dict[str, Any] = {
        "deserialized": [],
        "compiled": [],
        "replayed": [],
        "skipped": {},
        "skew": [],
    }
    t0 = _time.perf_counter()

    def _flight(event, **kw):
        if flight is not None:
            try:
                flight.record(event, **kw)
            except Exception:
                pass

    scope = ledger.prewarming() if ledger is not None else contextlib.nullcontext()
    with scope:
        for name in manifest.names():
            entries = manifest.entries(name)
            fn = resolve(name)
            if fn is None:
                report["skipped"][name] = "program not constructible here"
                continue
            installed = False
            if (
                mode in ("auto", "deserialize")
                and artifact_dir is not None
                and install is not None
                and len(entries) == 1
            ):
                try:
                    compiled = load_executable(
                        artifact_dir, name, entries[0]["signature"]
                    )
                except SkewError as e:
                    compiled = None
                    report["skew"].append(name)
                    _flight("aot_skew", program=name, error=str(e)[:200])
                if compiled is not None:
                    # fall back to the RAW jit fn, not the ledger proxy —
                    # the install hook re-wraps the shim, so routing the
                    # fallback through the old proxy would double-count
                    shim = AOTProgram(
                        name, compiled,
                        getattr(fn, "__wrapped__", fn),
                        flight=flight,
                    )
                    try:
                        if install(name, shim):
                            report["deserialized"].append(name)
                            installed = True
                    except Exception as e:
                        _flight(
                            "aot_install_failed", program=name,
                            error=f"{type(e).__name__}: {e}"[:200],
                        )
            if installed:
                continue
            for entry in entries:
                key = (
                    f"{name}@{entry['signature']}"
                    if len(entries) > 1 else name
                )
                if not entry.get("portable") or entry.get("call") is None:
                    report["skipped"][key] = (
                        entry.get("note") or "not portable"
                    )
                    continue
                try:
                    args, kwargs = materialize_call(
                        entry["call"], sharding_resolver
                    )
                except UnportableError as e:
                    report["skipped"][key] = str(e)
                    continue
                try:
                    fn(*args, **kwargs)
                except Exception as e:
                    report["skipped"][key] = (
                        f"replay failed: {type(e).__name__}: {e}"[:200]
                    )
                    _flight(
                        "aot_prewarm_failed", program=name,
                        error=f"{type(e).__name__}: {e}"[:200],
                    )
                    continue
                report["replayed"].append(key)
                if getattr(fn, "last_call_compiled", False):
                    report["compiled"].append(key)
    report["wall_s"] = round(_time.perf_counter() - t0, 4)
    _flight(
        "aot_prewarm",
        deserialized=len(report["deserialized"]),
        replayed=len(report["replayed"]),
        compiled=len(report["compiled"]),
        skipped=len(report["skipped"]),
        wall_s=report["wall_s"],
    )
    return report
