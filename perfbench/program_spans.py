"""What the PROGRAM says about its own time, read from a traced run.

The runners' own spans (``engine.step``, ``generator.*``) wrap the calls into
the engine and the trainer from outside. The program emits its own: the
``nxd.step*`` spans inside ``ServingEngine.step()`` and the ``nxd.train.*``
spans inside ``Trainer.fit`` (``observability/tracing.py``), each a
``jax.profiler.TraceAnnotation`` with host scalars as stats, and named scopes
(``kv_view``, ``moe.*``, ``sample``, and flax's own module names
``moe``, ``mlp``, ``lm_head``) on the model step. This module reads both out
of the traced run's ``.xplane.pb`` for the per-layer readers beside it.

Where things are in a v5e trace (looked at on ``tests/benchmark/data/
v5e_small.xplane.pb`` before this was written):

* host spans: events of the ``/host:CPU`` plane, their keyword stats as event
  stats (ints stay ints). ``jax.profiler.ProfileData`` gives both.
* a scope on a device op: the ``XLA Ops`` events carry NO path in their own
  stats. The path is in the plane's event METADATA: stat ``tf_op`` holds
  the HLO ``op_name`` (``jit(chunk_fn)/while/body/.../kv_view/copy:``), stat
  ``program_id`` the fingerprint that the ``XLA Modules`` event's name ends in
  (``jit_chunk_fn(<program_id>)``). ``ProfileData`` does not expose metadata
  stats, so :func:`op_paths` decodes that part of the file's protobuf wire
  format itself (some forty lines; the events are still read through
  ``ProfileData``) and the join is on (program id, instruction text).
* what a scope cannot reach: ops the TPU compiler re-creates carry its own
  ``op_name``. The experts' grouped matmuls become Mosaic calls named
  ``ragged-dot-none`` with ``op_name="ragged-dot-none"``, and an expanded
  gather's pieces carry ``op_name="gather"``; neither has a path.

The runners write the trace to ``<checkout>/perfbench_out/trace`` and the run
record carries no path, so :func:`load` derives it the same way. A program
without these spans (the parent of the PR that added them), a trace without
a device plane (a CPU rehearsal) or no trace at all: the readers get ``None``
or empty lists, return ``None``, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, "perfbench_out", "trace")   # as run.py hands it to the runners

STEP = "nxd.step"
PREFILL = "nxd.step.prefill"
FIRST_TOKEN = "nxd.step.prefill.first_token"
DISPATCH = "nxd.step.decode.dispatch"
READBACK = "nxd.step.decode.readback"
EMIT = "nxd.step.decode.emit"
# idle gaps inside step(), by the phase the host was in (reduce_planes gives a
# gap to the innermost span that covers its middle). The readback goes with
# the dispatch: the host is already waiting there, so a gap under it is the
# device not yet running the chunk it was just handed (launch latency), or a
# hole inside that chunk; on the chip most of step()'s idle time lies there
# (PR 24), and without it the three would not add up to the step's share.
IDLE_GROUPS = {
    "admit": ("nxd.step.reap", "nxd.step.preempt", "nxd.step.admit", PREFILL, FIRST_TOKEN),
    "dispatch": (DISPATCH, READBACK),
    "emit": (EMIT, "nxd.step.health"),
}
STEP_SPANS = (STEP,) + tuple(n for names in IDLE_GROUPS.values() for n in names)
TRAIN_DISPATCH = "nxd.train.dispatch"
PREFIX = "nxd."

_CACHE = "_program_spans"
_MODULE_ID = re.compile(r"\((-?\d+)\)$")


# --- the protobuf wire format, as far as the event metadata needs it ---------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    ``memoryview`` for bytes; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def op_paths(serialized) -> Dict[str, Dict[Tuple[Optional[int], str], str]]:
    """Per device plane, ``{(program id, instruction text): op_name path}`` from
    the plane's event metadata (``XSpace.planes[].event_metadata[].stats``:
    ``tf_op`` and ``program_id``). Lines and events are skipped unread."""
    out: Dict[str, Dict[Tuple[Optional[int], str], str]] = {}
    for number, plane in _fields(memoryview(serialized)):
        if number != 1:
            continue
        name, stat_names, metas = "", {}, []
        for f, value in _fields(plane):
            if f == 2:
                name = _text(value)
            elif f == 4:                                   # map<int64, XEventMetadata>
                metas.extend(v for k, v in _fields(value) if k == 2)
            elif f == 5:                                   # map<int64, XStatMetadata>
                for k, v in _fields(value):
                    if k == 2:
                        d = dict(_fields(v))
                        if 1 in d and 2 in d:
                            stat_names[d[1]] = _text(d[2])
        if not xplane.DEVICE_PLANE.match(name):
            continue
        paths = out.setdefault(name, {})
        for meta in metas:
            names, path, program = [], None, None
            for f, value in _fields(meta):
                if f in (2, 4):                            # name, display_name
                    names.append(_text(value))
                elif f == 5:                               # XStat
                    stat = dict(_fields(value))
                    key = stat_names.get(stat.get(1))
                    if key == "tf_op":
                        # a string, or a reference to a stat metadata's name
                        path = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7))
                    elif key == "program_id":
                        program = stat.get(3, stat.get(4))
            if path:
                for n in names:
                    paths[(program, n)] = path
                    paths.setdefault((None, n), path)
    return out


# --- reading a run -------------------------------------------------------------------


def from_serialized(serialized) -> dict:
    """The spans and scopes of one ``.xplane.pb``'s bytes."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_serialized_xspace(bytes(serialized)).planes)
    window = None
    rows: List[Tuple[int, int, str, Dict[str, object], str]] = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for a, b, name, ev in xplane._events(line):
                if name == xplane.WINDOW_SPAN:
                    window = (a, b)
                elif name.startswith(PREFIX):
                    rows.append((a, b, name, xplane._stats(ev), line.name))
    spans: Dict[str, List[Tuple[int, int, Dict[str, object], str]]] = {}
    for a, b, name, stats, thread in rows:
        if window is not None:
            if b <= window[0] or a >= window[1]:
                continue
            a, b = max(a, window[0]), min(b, window[1])
        spans.setdefault(name, []).append((a, b, stats, thread))
    for events in spans.values():
        events.sort(key=lambda e: e[0])
    return {"planes": planes, "window": window, "spans": spans, "serialized": serialized}


def load(run: dict) -> Optional[dict]:
    """The spans of the traced run, parsed once and kept on ``run``; ``None``
    where the run left no trace."""
    if _CACHE not in run:
        path = xplane.find_xplane(TRACE_DIR) if "trace" in run else None
        if path is None:
            run[_CACHE] = None
        else:
            with open(path, "rb") as f:
                run[_CACHE] = from_serialized(f.read())
    return run[_CACHE]


def spans(run: dict, name: str) -> List[Tuple[int, int, Dict[str, object], str]]:
    """``(start ns, end ns, stats, thread)`` of every ``name`` span, clipped to
    the traced window, in time order."""
    got = load(run)
    return got["spans"].get(name, []) if got else []


def window_ns(run: dict) -> Optional[int]:
    got = load(run)
    if not got or got["window"] is None:
        return None
    return got["window"][1] - got["window"][0]


def stat_values(run: dict, name: str, stat: str) -> List[float]:
    return [float(s[stat]) for _, _, s, _ in spans(run, name) if stat in s]


def children(run: dict, parent: Tuple[int, int, Dict[str, object], str], name: str):
    """The ``name`` spans inside ``parent`` on its thread."""
    a, b, _, thread = parent
    return [c for c in spans(run, name) if c[3] == thread and a <= c[0] and c[1] <= b]


def step_idle(run: dict) -> Optional[Dict[str, float]]:
    """Seconds of device idle gaps by ``nxd.step*`` span, from the benchmark's
    own reduction; ``None`` without a device plane or without the spans."""
    got = load(run)
    if not got or not got["spans"].get(STEP):
        return None
    key = "step_idle"
    if key not in got:
        reduced = xplane.reduce_planes(got["planes"], span_names=STEP_SPANS, require_device=False)
        got[key] = {"devices": reduced["devices"], "window_s": reduced["window_s"],
                    "gaps": dict((n, s) for n, s in reduced["idle_gaps"])}
    return got[key] if got[key]["devices"] else None


def step_idle_pct(run: dict, group: str) -> Optional[float]:
    idle = step_idle(run)
    if idle is None or not idle["window_s"]:
        return None
    return 100.0 * sum(idle["gaps"].get(n, 0.0) for n in IDLE_GROUPS[group]) / idle["window_s"]


def scope_seconds(run: dict) -> Optional[dict]:
    """Self time of the device's ops by scope, traced window, averaged over
    the chips: ``{"busy_s", "named_s", "ops": {(base name, components of its
    op_name path): s}}``. ``named_s`` is the time of the ops that have a path
    at all."""
    got = load(run)
    if not got:
        return None
    key = "scopes"
    if key in got:
        return got[key]
    paths = op_paths(got["serialized"])
    devices = [p for p in got["planes"] if xplane.DEVICE_PLANE.match(p.name)]
    result = None
    if devices and not any(paths.values()):
        # the decoder is tied to XSpace's field numbers: say so, do not go quiet
        print("program_spans: a device plane and no tf_op in its event metadata: "
              "no scope metric can be read (has the xplane schema changed?)", file=sys.stderr)
    if devices and any(paths.values()):
        n = len(devices)
        busy = named = 0.0
        by_op: Dict[Tuple[str, frozenset], float] = {}
        for plane in devices:
            lines = {line.name: line for line in plane.lines}
            if xplane.OPS_LINE not in lines:
                continue
            ops = [(a, b, name) for a, b, name, _ in xplane._events(lines[xplane.OPS_LINE])]
            modules = sorted((a, b, name) for a, b, name, _ in xplane._events(lines[xplane.MODULES_LINE])) \
                if xplane.MODULES_LINE in lines else []
            if got["window"] is not None:
                ops = xplane.clip(ops, *got["window"])
            busy += xplane.union_length((a, b) for a, b, _ in ops) / n
            starts = [a for a, _, _ in modules]
            table = paths.get(plane.name, {})
            for (a, _b, name), (_, ns) in zip(ops, xplane.self_times(ops)):
                i = bisect.bisect_right(starts, a) - 1
                program = None
                if i >= 0 and a < modules[i][1]:
                    m = _MODULE_ID.search(modules[i][2])
                    program = int(m.group(1)) if m else None
                path = table.get((program, name)) or table.get((None, name)) or ""
                named += ns / n if path else 0.0
                key = (xplane.base_name(name), frozenset(path.rstrip(":").split("/")) if path else frozenset())
                by_op[key] = by_op.get(key, 0.0) + ns / n
        result = {"busy_s": busy / 1e9, "named_s": named / 1e9,
                  "ops": {k: v / 1e9 for k, v in by_op.items()}}
    got[key] = result
    return result


def scope_share_pct(run: dict, scope: str, also_ops: Sequence[str] = ()) -> Optional[float]:
    """Self time of the ops under ``scope`` (a component of their ``op_name``
    path) over the device's busy time (%); ``also_ops``: base-name prefixes
    of ops the compiler re-created without a path (module docstring), counted
    with the scope. ``None`` where the trace shows neither."""
    s = scope_seconds(run)
    if not s or not s["busy_s"]:
        return None
    also = tuple(also_ops)
    hit = sum(v for (base, parts), v in s["ops"].items()
              if scope in parts or (also and base.startswith(also)))
    return 100.0 * hit / s["busy_s"] if hit else None
