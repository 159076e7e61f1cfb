"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler`` writes one ``.xplane.pb`` per traced window; nothing else in
the repo reads it. This module does, with JAX's own ``ProfileData`` and
nothing more, and every trace-derived per-layer metric is a small reader
over the dict :func:`reduce_trace` returns. Kept with the benchmark so that
every PR computes the same number the same way.

How a TPU trace is laid out (one look at a recorded v5e trace, PR 23): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program, named ``<jit name>(<fingerprint>)``), ``XLA Ops`` (one event
per executed HLO op; a ``while`` or ``conditional`` event encloses the events
of its body; an event's name is the whole HLO instruction, ``%fusion.12 =
bf16[...] fusion(...)``; a Pallas kernel is named after the scope it was called
in, e.g. ``%attn._cached_attention.10 = ... custom_call_target="tpu_custom_call"``)
and ``Async XLA Ops``; and a plane ``/host:CPU`` with one line per thread
whose events are the host's TraceMe spans, ``jax.profiler.TraceAnnotation``
among them. All on one clock.

Definitions:

* busy: the union of the ``XLA Ops`` intervals of a device, clipped to the
  window; idle share is ``1 - busy / window``. Averaged over the chips.
* an op's time: its SELF time, its interval less the intervals of the ops it
  encloses, so a ``while`` does not count its body twice. Names lose their
  ``.<n>`` suffix, so ``fusion.12`` and ``fusion.7`` add up under ``fusion``.
* kernel time: the self time of the Pallas (Mosaic) custom calls, also split
  by the program (``XLA Modules`` event) that was running: the flash kernel of
  a prefill and the paged kernel of a decode chunk carry the same scope name.
* exposed collective time: the self time of collective ops on the op line.
  The op line runs one op at a time, so while a collective (or the ``-start``
  or ``-done`` half of an asynchronous one, ``async-collective-done`` among
  them) holds it, no compute runs on that device.
* an idle gap is attributed to the innermost host span, among the benchmark's
  own annotations, that covers its middle.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "perfbench.window"
COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
    "collective-broadcast", "async-collective",
)
MIN_GAP_NS = 20_000  # shorter holes between ops are launch overhead, not host waits

_SUFFIX = re.compile(r"(\.\d+)+$")


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``%all-reduce.3 = ...`` -> ``all-reduce``."""
    name = name.strip().lstrip("%").split(" ", 1)[0]
    return _SUFFIX.sub("", name)


def module_base(name: str) -> str:
    """``jit_chunk_fn(5243485375410626644)`` -> ``jit_chunk_fn``."""
    return name.split("(", 1)[0]


def is_collective(name: str) -> bool:
    return base_name(name).startswith(COLLECTIVE_PREFIXES)


def is_kernel(name: str) -> bool:
    """A Pallas kernel: the op line carries the whole HLO instruction, and a
    Mosaic kernel's is a custom call whose target is ``tpu_custom_call``
    (XLA's own custom calls, ``ConcatBitcast`` and the like, are not)."""
    return 'custom_call_target="tpu_custom_call"' in name


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# --- interval arithmetic ---------------------------------------------------------


def union_length(intervals: Iterable[Tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps_between(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The holes the union of ``intervals`` leaves in ``[lo, hi]``."""
    out, cursor = [], lo
    for a, b in sorted(intervals):
        if a > cursor:
            out.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


def self_times(events: Sequence[Tuple[int, int, str]]) -> List[Tuple[str, int]]:
    """``(name, self_ns)`` per event of ONE line whose events nest properly:
    an event's interval less that of the events it encloses."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    selfs = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        a, b, _ = events[i]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= min(b, events[stack[-1]][1]) - a
        stack.append(i)
    return [(events[i][2], max(selfs[i], 0)) for i in range(len(events))]


def clip(events, lo: int, hi: int):
    return [(max(a, lo), min(b, hi), n) for a, b, n in events if b > lo and a < hi]


# --- reading ------------------------------------------------------------------------


def _events(line) -> List[Tuple[int, int, str, Dict[str, object]]]:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        out.append((start, start + int(ev.duration_ns), str(ev.name), ev))
    return out


def _stats(ev) -> Dict[str, object]:
    try:
        return {str(k): v for k, v in ev.stats}
    except Exception:  # an event without stats
        return {}


def read_planes(path: str):
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(path).planes)


def _empty(window) -> dict:
    return {
        "devices": 0, "window_s": (window[1] - window[0]) / 1e9 if window else 0.0, "busy_s": 0.0,
        "device_ops": [], "idle_gaps": [], "op_s": {}, "kernel_s": {}, "kernel_calls": {},
        "kernel_s_by_module": {},
        "module_s": {}, "module_calls": {}, "collective_exposed_s": 0.0,
    }


def reduce_planes(planes, span_names: Sequence[str] = (), require_device: bool = True) -> dict:
    """The dict every trace reader works on. ``span_names``: the host spans
    idle gaps may be attributed to. ``require_device=False`` (a CPU
    rehearsal, whose trace has no device plane) returns an empty reduction
    instead of refusing."""
    devices = []
    host_spans: List[Tuple[int, int, str]] = []
    window = None
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices.append((int(m.group(1)), lines))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for a, b, name, _ in _events(line):
                    if name == WINDOW_SPAN:
                        window = (a, b)
                    elif name in span_names:
                        host_spans.append((a, b, name))
    devices.sort(key=lambda d: d[0])
    if not devices:
        if not require_device:
            return _empty(window)
        raise RuntimeError("the trace holds no /device:TPU plane: nothing ran on a device")

    per_device = []
    for _, lines in devices:
        ops = [(a, b, n) for a, b, n, _ in _events(lines[OPS_LINE])] if OPS_LINE in lines else []
        modules = [(a, b, n) for a, b, n, _ in _events(lines[MODULES_LINE])] if MODULES_LINE in lines else []
        per_device.append((ops, sorted(modules)))

    if window is None:  # no window span in the trace: the extent of the device's own events
        starts = [a for ops, _ in per_device for a, _, _ in ops]
        ends = [b for ops, _ in per_device for _, b, _ in ops]
        if not starts:
            raise RuntimeError("the trace holds no device operation")
        window = (min(starts), max(ends))
    lo, hi = window

    n = len(per_device)
    busy_ns = 0
    op_self: Dict[str, float] = {}
    kernel_self: Dict[str, float] = {}
    kernel_calls: Dict[str, float] = {}
    kernel_by_module: Dict[str, Dict[str, float]] = {}
    module_time: Dict[str, float] = {}
    module_calls: Dict[str, float] = {}
    exposed_ns = 0.0
    for ops, modules in per_device:
        ops = clip(ops, lo, hi)
        busy_ns += union_length((a, b) for a, b, _ in ops)
        module_starts = [a for a, _, _ in modules]
        for (a, _b, name), (_, ns) in zip(ops, self_times(ops)):
            key = base_name(name)
            op_self[key] = op_self.get(key, 0.0) + ns / n
            if is_kernel(name):
                kernel_self[key] = kernel_self.get(key, 0.0) + ns / n
                kernel_calls[key] = kernel_calls.get(key, 0.0) + 1.0 / n
                i = bisect.bisect_right(module_starts, a) - 1
                if i >= 0 and a < modules[i][1]:
                    owner = kernel_by_module.setdefault(module_base(modules[i][2]), {})
                    owner[key] = owner.get(key, 0.0) + ns / n
            if is_collective(name):
                exposed_ns += ns / n
        for a, b, name in clip(modules, lo, hi):
            key = module_base(name)
            module_time[key] = module_time.get(key, 0.0) + (b - a) / n
            module_calls[key] = module_calls.get(key, 0.0) + 1.0 / n

    first_ops = clip(per_device[0][0], lo, hi)
    gap_by: Dict[str, float] = {}
    for a, b in gaps_between(((x, y) for x, y, _ in first_ops), lo, hi):
        if b - a < MIN_GAP_NS:
            continue
        mid = (a + b) // 2
        covering = [(e - s, name) for s, e, name in host_spans if s <= mid <= e]
        name = min(covering)[1] if covering else "(no span)"
        gap_by[name] = gap_by.get(name, 0.0) + (b - a)

    def ranked(d: Dict[str, float]):
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "device_ops": ranked(op_self),
        "idle_gaps": ranked(gap_by),
        "op_s": {k: v / 1e9 for k, v in op_self.items()},
        "kernel_s": {k: v / 1e9 for k, v in kernel_self.items()},
        "kernel_calls": kernel_calls,
        "kernel_s_by_module": {m: {k: v / 1e9 for k, v in ks.items()} for m, ks in kernel_by_module.items()},
        "module_s": {k: v / 1e9 for k, v in module_time.items()},
        "module_calls": module_calls,
        "collective_exposed_s": exposed_ns / 1e9,
    }


def reduce_trace(trace_dir: str, span_names: Sequence[str] = (), require_device: bool = True) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return reduce_planes(read_planes(path), span_names, require_device)
