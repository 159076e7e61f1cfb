"""Who owns the device's idle time between two decode chunks, read from a traced run.

``xplane.reduce_planes`` gives a WHOLE idle gap to the span that covers its
middle, on whatever clocks the two planes came with. The one gap that matters,
between chunk N's last op and chunk N+1's first, runs across the tail of the
readback, the emit, the health sync, the benchmark's loop, the reap, the
admission, the page dealing and the dispatch: where its middle lies is chance,
and ``step_idle_pct.dispatch`` / ``.emit`` trade 5 points between two seeds of
one cell (PERF.md section 6, PR 54). This module reads what the program emits
since PR 54 (``nxd.program`` around every ledgered call,
``nxd.step.decode.pages``, ``nxd.step.close``) and does three things the
accepted reduction does not:

* **joins** every module run of the device plane to the ledgered call that
  made it, on the module's name (the span's ``module`` stat) and, for one
  name, on ORDER (:func:`_join`): the ``XLA Modules`` event carries a
  ``run_id``, the runtime's ``DoEnqueueProgram`` host event carries the same
  and gives the run its enqueue time on the host's clock, and a call is
  paired with the next run of its module enqueued after it opened. (The
  runtime enqueues from threads of its own, 1.0-1.6 ms after the span opens
  and as often after the call returned as before, so containment in time joins
  a quarter of the runs and order joins all that a span names.) The span's
  ``program`` stat is the ledger's name (``decode_chunk``, ``prefill[8192]``,
  ``paged_admit``, ...), which :func:`program_class` sorts into ``decode``,
  ``prefill`` and ``other``; a run of a module no span names (the host's eager
  ``jnp`` programs) stays unjoined and is ``other``.
* **fits one clock**: the host plane and the device plane of a v5e trace are
  NOT on one clock (host - device read +0.3..1.4 ms in PR 53's traces, and
  the two drift apart by 10-35 us a second). No run starts before its enqueue
  starts and none ends after its ``CompleteCallbacks`` starts (without
  ``run_id``: before its ``nxd.program`` opens, after the span that awaits it
  returns). Over the joined runs that bounds host - device from below,
  ``enqueue - device start``, and from above, ``completion - device end``.
  :func:`_fit_clock` takes a constant offset where one fits, else the smallest
  drift up to :data:`MAX_DRIFT_US_PER_S` that uncrosses the bounds, and shifts
  the device plane by the LOWER bound (the quickest launch of the trace then
  reads 0). Where nothing uncrosses them it takes drift 0 and the midpoint of
  the crossed pair, and the crossing is ``clock_fit_violation_ms``: an error
  bar on every split of that line, not a reason to report nothing. Where no
  run joins at all the planes keep the clocks they came with (offset 0,
  violation 0, one line in the log).
* **splits by overlap**: each idle interval of device 0 of at least
  ``xplane.MIN_GAP_NS``, on the fitted clock and clipped to
  ``perfbench.window``, is cut at every span's edge on the stepping thread's
  timeline, and each piece goes to the ONE part that owns it (:data:`PARTS`),
  so the eight parts add up to the idle time of those intervals.

**Totality** (what PR 53's readers lacked, and were refused for): on a trace
that holds a device plane, an ``nxd.step`` span and an ``nxd.program`` span,
every reader returns a ``float``. A part no gap fell into reads ``0.0``, a
class with no run reads ``0.0``, a window without an idle interval reads eight
zeros, and the stages (read, join, fit, split, classes, scopes) fail apart: one
that raises says so in the log and leaves what it would have filled at zero,
and the later stages go on with that. ``None`` keeps the meaning it has
everywhere else in ``perfbench/``: no trace, no device plane (a CPU
rehearsal), or a program without the spans (the parent of PR 54).

Imports ``xplane`` and ``program_spans`` and edits neither.
"""

from __future__ import annotations

import bisect
import re
import sys
from typing import Callable, Dict, List, Optional, Tuple

from perfbench import program_spans as ps
from perfbench import stats
from perfbench import xplane

PROGRAM = "nxd.program"
PAGES = "nxd.step.decode.pages"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"

# the parts of the device's idle time, by what the stepping thread was in
PARTS = ("completion", "launch", "dispatch", "pages", "emit", "admit", "unowned", "outside")
_PHASE_PART = {
    ps.DISPATCH: "dispatch", PAGES: "pages", ps.EMIT: "emit",
    "nxd.step.reap": "admit", "nxd.step.preempt": "admit", "nxd.step.admit": "admit",
    ps.PREFILL: "admit", "nxd.step.health": "admit", "nxd.step.close": "admit",
}
_AWAITING = (ps.READBACK, ps.FIRST_TOKEN)     # the spans in which the host waits for a run

CLASSES = ("decode", "prefill", "other")
_PREFILL = re.compile(r"^(draft_)?prefill\[\d+\]$")
_CACHE = "chunk_gaps"

Interval = Tuple[int, int]
Point = Tuple[int, int]           # (a time on the device's clock, a bound on host - device there)
MAX_DRIFT_US_PER_S = 200


def program_class(program: str) -> str:
    """The class of a ledger name: the decode chunk, what a prefill awaits, the rest."""
    if program in ("decode_chunk", "spec_decode_chunk"):
        return "decode"
    if program in ("suffix_prefill", "first_token") or _PREFILL.match(program):
        return "prefill"
    return "other"


def _log(message: str) -> None:
    print(f"perfbench: chunk_gaps: {message}", file=sys.stderr, flush=True)


# --- interval arithmetic ---------------------------------------------------------------


def _cut(pieces: List[Interval], a: int, b: int) -> Tuple[List[Interval], List[Interval]]:
    """``pieces`` (disjoint) inside ``[a, b]`` and outside it."""
    inside, outside = [], []
    for x, y in pieces:
        lo, hi = max(x, a), min(y, b)
        if lo < hi:
            inside.append((lo, hi))
            if x < lo:
                outside.append((x, lo))
            if hi < y:
                outside.append((hi, y))
        else:
            outside.append((x, y))
    return inside, outside


def _length(pieces: List[Interval]) -> int:
    return sum(b - a for a, b in pieces)


# --- reading -----------------------------------------------------------------------------


def _read(planes) -> dict:
    """The host events the join needs (unclipped) and device 0's runs and ops."""
    enqueue: Dict[int, int] = {}
    complete: Dict[int, int] = {}
    programs: List[Tuple[int, int, str, str]] = []
    phases: List[Tuple[int, int, str, str]] = []
    device = None
    for plane in planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m and (device is None or int(m.group(1)) < device[0]):
            device = (int(m.group(1)), {line.name: line for line in plane.lines})
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for a, b, name, ev in xplane._events(line):
                if name == ENQUEUE or name == COMPLETE:
                    run_id = xplane._stats(ev).get("run_id")
                    if run_id is not None:
                        (enqueue if name == ENQUEUE else complete)[int(run_id)] = a
                elif name == PROGRAM:
                    stat = xplane._stats(ev)
                    programs.append((a, b, str(stat.get("program", "")), str(stat.get("module", ""))))
                elif name.startswith(ps.STEP):
                    phases.append((a, b, name, line.name))
    runs, ops = [], []
    if device is not None:
        lines = device[1]
        if xplane.MODULES_LINE in lines:
            for a, b, name, ev in xplane._events(lines[xplane.MODULES_LINE]):
                run_id = xplane._stats(ev).get("run_id")
                runs.append({"a": a, "b": b, "name": name, "run_id": None if run_id is None else int(run_id),
                             "call": None})
        if xplane.OPS_LINE in lines:
            ops = [(a, b, name) for a, b, name, _ in xplane._events(lines[xplane.OPS_LINE])]
    runs.sort(key=lambda r: r["a"])
    programs.sort()
    return {"enqueue": enqueue, "complete": complete, "programs": programs, "phases": phases,
            "device": None if device is None else device[0], "runs": runs, "ops": ops}


def _join(read: dict) -> str:
    """Gives each run its ``nxd.program`` span (``r["call"]``, an index into
    ``read["programs"]``, or ``None``) and returns the join used.

    A call of a jitted program makes exactly one run of the module the span
    names, and calls and runs of one module keep their order. The runtime
    enqueues a run from a thread of its own, often after the call has
    returned (a decode chunk's ``DoEnqueueProgram`` lies 1.0-1.6 ms after its
    span opens, about where the span closes), so containment in time does not
    hold; the order does. The two lists are paired from the trace's END (the
    session stops after the last run has ended; a run in flight when it
    opened has no call), a call only to a run enqueued after it opened:
    ``"run_id+order"`` where the enqueue's ``run_id`` gives that time,
    ``"module+order"`` (the run's own end, on the device's clock) where not.
    A run of a module no span names (the eager programs the host's ``jnp``
    calls make: conversions, key splits) stays unjoined."""
    programs, runs, enqueue = read["programs"], read["runs"], read["enqueue"]
    by_run_id = any(r["run_id"] in enqueue for r in runs)
    calls_of: Dict[str, List[int]] = {}
    for i, p in enumerate(programs):
        calls_of.setdefault(p[3], []).append(i)
    runs_of: Dict[str, List[dict]] = {}
    for r in runs:
        r["call"] = None
        # the host's clock where the enqueue is known; else the device's, which on
        # the hosts seen reads under 2 ms behind
        r["at"] = enqueue[r["run_id"]] if r["run_id"] in enqueue else r["b"] + 2_000_000
        runs_of.setdefault(xplane.module_base(r["name"]), []).append(r)
    for module, mine in runs_of.items():
        calls = calls_of.get(module, [])
        j = len(calls) - 1
        for r in sorted(mine, key=lambda r: r["at"], reverse=True):
            while j >= 0 and programs[calls[j]][0] > r["at"]:
                j -= 1          # a call that opened after this run was enqueued made another, or none
            if j < 0:
                break
            r["call"] = calls[j]
            j -= 1
    return "run_id+order" if by_run_id else "module+order"


class Clock:
    """``host = device + offset + drift * (device - middle)``: the device
    plane's clock laid on the host plane's."""

    def __init__(self, offset: int = 0, drift: float = 0.0, middle: int = 0):
        self.offset, self.drift, self.middle = offset, drift, middle

    def __call__(self, t: int) -> int:
        return t + self.offset + int(self.drift * (t - self.middle))


def _clock_bounds(read: dict, join: str) -> Tuple[List[Point], List[Point]]:
    """What causality says of ``host clock - device clock``, a joined run
    each: at the run's start at least ``enqueue - start``, at its end at most
    ``completion - end``."""
    lowers, uppers = [], []
    if join == "run_id+order":
        for r in read["runs"]:
            if r["call"] is None:
                continue
            if r["run_id"] in read["enqueue"]:
                lowers.append((r["a"], read["enqueue"][r["run_id"]] - r["a"]))
            if r["run_id"] in read["complete"]:
                uppers.append((r["b"], read["complete"][r["run_id"]] - r["b"]))
    else:
        lowers = [(r["a"], read["programs"][r["call"]][0] - r["a"]) for r in read["runs"] if r["call"] is not None]
        for step in _steps(read, Clock()):
            uppers.extend((last, b - last) for _a, b, _first, last in step["awaited"])
    return lowers, uppers


def _fit_clock(lowers: List[Point], uppers: List[Point], middle: int) -> Tuple[int, float, int]:
    """``(offset at middle, drift, violation)``, all the trace allows one to
    say of host - device: a CONSTANT offset where one lies between every
    lower and every upper bound; where those cross (the two clocks drift
    apart by 10-35 us a second in a v5e host's traces, and the bounds lie
    0.05-0.2 ms apart), the smallest drift, in steps of 1 us/s up to
    :data:`MAX_DRIFT_US_PER_S`, under which they do not. The offset taken is
    the LOWER bound and the violation 0. Where no such drift uncrosses them:
    drift 0, the midpoint of the crossed pair, and the crossing
    (``lower - upper`` > 0) as the violation."""
    def bounds(drift: float) -> Tuple[int, Optional[int]]:
        lower = max(bound - drift * (t - middle) for t, bound in lowers)
        upper = min((bound - drift * (t - middle) for t, bound in uppers), default=None)
        return int(lower), None if upper is None else int(upper)

    for k in range(MAX_DRIFT_US_PER_S + 1):
        for drift in ((0.0,) if k == 0 else (k * 1e-6, -k * 1e-6)):
            lower, upper = bounds(drift)
            if upper is None or lower <= upper:
                return lower, drift, 0
    lower, upper = bounds(0.0)
    return (lower + upper) // 2, 0.0, lower - upper


def _steps(read: dict, clock: Clock) -> List[dict]:
    """The ``nxd.step`` spans of the stepping thread in time order, each with
    its phase spans, its ``nxd.program`` spans and, for each span in which
    the host waits, the runs it waits for on the fitted clock."""
    tops = sorted((a, b, line) for a, b, name, line in read["phases"] if name == ps.STEP)
    steps = [{"a": a, "b": b, "line": line, "phases": [], "programs": [], "awaited": []} for a, b, line in tops]
    starts = [s["a"] for s in steps]

    def owner(a: int, b: int) -> Optional[dict]:
        i = bisect.bisect_right(starts, a) - 1
        return steps[i] if i >= 0 and b <= steps[i]["b"] else None

    for a, b, name, line in read["phases"]:
        step = owner(a, b)
        if name != ps.STEP and step is not None and step["line"] == line:
            step["phases"].append((a, b, name))
    for i, (a, b, _program, _module) in enumerate(read["programs"]):
        step = owner(a, b)
        if step is not None:
            step["programs"].append((a, b, i))
    by_call: Dict[int, List[dict]] = {}
    for r in read["runs"]:
        if r["call"] is not None:
            by_call.setdefault(r["call"], []).append(r)
    for step in steps:
        step["phases"].sort()
        for a, b, name in step["phases"]:
            if name not in _AWAITING:
                continue
            # a readback waits for the chunk its step's dispatch span called,
            # a first token for what its prefill span called up to its return
            want = "decode" if name == ps.READBACK else "prefill"
            if name == ps.READBACK:
                within = [(x, y) for x, y, n in step["phases"] if n == ps.DISPATCH and y <= a]
            else:
                within = [(x, b) for x, y, n in step["phases"] if n == ps.PREFILL and x <= a and b <= y]
            mine = [r for x, y in within[-1:] for pa, pb, i in step["programs"] if x <= pa and pb <= y
                    and program_class(read["programs"][i][2]) == want for r in by_call.get(i, [])]
            if mine:
                step["awaited"].append((a, b, clock(min(r["a"] for r in mine)), clock(max(r["b"] for r in mine))))
    return steps


def _split(gap: Interval, steps: List[dict], ends: List[int], parts: Dict[str, int], extra: Dict[str, int]) -> None:
    """One idle interval over the stepping thread's timeline: each piece to its part."""
    rest = [gap]
    i = bisect.bisect_right(ends, gap[0])
    while i < len(steps) and steps[i]["a"] < gap[1]:
        step = steps[i]
        i += 1
        inside, rest = _cut(rest, step["a"], step["b"])
        if not inside:
            continue
        for a, b, _ in step["programs"]:
            got, inside = _cut(inside, a, b)
            parts["launch"] += _length(got)
        for a, b, first, last in step["awaited"]:
            got, inside = _cut(inside, a, b)
            done, running = _cut(got, last, b)
            before, between = _cut(running, a, first)
            parts["completion"] += _length(done)
            parts["launch"] += _length(before) + _length(between)
            extra["in_run_ns"] += _length(between)
        for a, b, name in step["phases"]:
            if name in _PHASE_PART:
                got, inside = _cut(inside, a, b)
                parts[_PHASE_PART[name]] += _length(got)
                if _PHASE_PART[name] == "admit" and _length(got) > extra["longest"][0]:
                    extra["longest"] = (_length(got), name)     # ``admit`` is seven spans: say which
            elif name in _AWAITING:        # waited for no run this trace holds: the host's wait all the same
                got, inside = _cut(inside, a, b)
                parts["completion"] += _length(got)
        parts["unowned"] += _length(inside)
    parts["outside"] += _length(rest)


# --- the stages, each failing alone ----------------------------------------------------------


def _stage(name: str, fn: Callable, default):
    """``fn()``, or ``default`` and a line in the log where it raises: one
    stage's fault costs what that stage fills, not the readers their values."""
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 - any fault of a stage degrades, none withholds
        _log(f"the {name} stage failed with {e!r}: what it fills reads 0")
        return default


def _fit(read: dict, join: str, window: Optional[Interval], out: dict) -> Clock:
    """The fit stage: bounds, offset, drift and violation into ``out`` and the log."""
    lowers, uppers = _clock_bounds(read, join)
    if not lowers:
        _log(f"no run of {len(read['runs'])} joined to an nxd.program span ({join}): no clock is fitted, "
             "the planes keep the clocks they came with (offset 0, violation 0)")
        return Clock()
    lower_one = max(b for _, b in lowers)                   # for ONE offset over the whole trace
    upper_one = min((b for _, b in uppers), default=None)
    if window is None:
        times = [a for a, _, _ in read["ops"]] or [r["a"] for r in read["runs"]]
        middle = (min(times) + max(times)) // 2
    else:
        middle = (window[0] + window[1]) // 2 - lower_one   # the window's middle, on the device's clock
    offset, drift, violation = _fit_clock(lowers, uppers, middle)
    out.update(shift_ns=offset, drift_us_per_s=drift * 1e6, violation_ns=violation)
    said = f"host - device clock: >= {lower_one / 1e6:.3f} ms" + (
        f", <= {upper_one / 1e6:.3f} ms" if upper_one is not None else "") + f" over {len(lowers)} runs"
    if violation:
        said += (f": the bounds cross by {violation / 1e6:.3f} ms and no drift up to {MAX_DRIFT_US_PER_S} us/s "
                 "uncrosses them; their midpoint is taken and the crossing is clock_fit_violation_ms")
    elif drift:
        said += f": they cross for one offset; a drift of {drift * 1e6:+.0f} us/s uncrosses them"
    _log(f"{said}; the device plane shifted by {offset / 1e6:.3f} ms at the window's middle")
    return Clock(offset, drift, middle)


def _idle_parts(read: dict, clock: Clock, ops, window: Interval, out: dict) -> None:
    """The split stage."""
    lo, hi = window
    gaps = xplane.gaps_between(((a, b) for a, b, _ in ops), lo, hi)
    steps = _steps(read, clock)
    ends = [s["b"] for s in steps]
    parts = {name: 0 for name in PARTS}
    extra = {"in_run_ns": 0, "longest": (0, "")}
    largest = {name: 0 for name in PARTS}          # of ONE idle interval, a part: a stall shows here
    below = 0
    for gap in gaps:
        if gap[1] - gap[0] < xplane.MIN_GAP_NS:
            below += gap[1] - gap[0]
            continue
        before = dict(parts)
        _split(gap, steps, ends, parts, extra)
        for name in PARTS:
            largest[name] = max(largest[name], parts[name] - before[name])
    out.update(parts=parts, below_floor_ns=below, in_run_ns=extra["in_run_ns"], largest=largest)
    pct = 100.0 / (hi - lo)
    _log("idle by phase, % of the window: " + ", ".join(f"{k} {v * pct:.3f}" for k, v in parts.items())
         + f"; sum {sum(parts.values()) * pct:.3f}; gaps under {xplane.MIN_GAP_NS / 1e3:g} us {below * pct:.3f}; "
         f"1 - busy {100.0 - out['busy_ns'] * pct:.3f}; of launch, holes inside or between awaited runs "
         f"{extra['in_run_ns'] * pct:.3f}; the largest single interval's, ms: "
         + ", ".join(f"{k} {v / 1e6:.2f}" for k, v in largest.items() if v)
         + f"; admit's longest piece: {extra['longest'][0] / 1e6:.2f} ms in {extra['longest'][1] or 'no span'}")


def _busy_by_class(read: dict, clock: Clock, ops, window: Interval, out: dict) -> List[Tuple[Optional[dict], int]]:
    """The classes stage: each op's self time to the class of the run it ran
    in. Returns, for each op, its run (or ``None``) and its self time, for
    the scopes stage."""
    lo, hi = window
    spans_of_runs = [(clock(r["a"]), clock(r["b"]), r) for r in read["runs"]]   # in time order
    run_starts = [a for a, _, _ in spans_of_runs]
    busy_by = {c: 0 for c in CLASSES}
    owners: List[Tuple[Optional[dict], int]] = []
    for (a, _b, _name), (_, ns) in zip(ops, xplane.self_times(ops)):
        i = bisect.bisect_right(run_starts, a) - 1
        run = spans_of_runs[i][2] if i >= 0 and a < spans_of_runs[i][1] else None
        owners.append((run, ns))
        # an op outside every run (none seen on a v5e) is nobody's chunk and nobody's prefill
        busy_by[run["class"] if run is not None else "other"] += ns
    out["busy_by_class"] = busy_by
    in_window = [r for a, b, r in spans_of_runs if b > lo and a < hi]
    loose = sorted({xplane.module_base(r["name"]) for r in in_window if r["call"] is None})
    _log(f"join by {out['join']}: {sum(r['call'] is not None for r in in_window)} of {len(in_window)} runs of the "
         "window joined" + (f" (unjoined: {loose})" if loose else "")
         + "; busy by class, % of busy: "
         + ", ".join(f"{c} {100.0 * v / max(out['busy_ns'], 1):.3f}" for c, v in busy_by.items()))
    return owners


def _unscoped(read: dict, ops, owners, serialized, out: dict) -> None:
    """The scopes stage: self time of the ops of JOINED runs whose ``op_name``
    holds no scope path. Adds up as it goes, so that a fault half way leaves
    what was counted until then."""
    out["unscoped_ns"] = 0
    paths = ps.op_paths(serialized).get(f"/device:TPU:{read['device']}", {}) if serialized is not None else {}
    if not paths:
        _log("no op_name paths in the device plane's event metadata: unscoped_dev_share_pct reads 0")
        return
    by_op: Dict[Tuple[str, str], int] = {}
    for (_a, _b, name), (run, ns) in zip(ops, owners):
        if run is None or run["call"] is None:
            continue
        m = ps._MODULE_ID.search(run["name"])
        program_id = int(m.group(1)) if m else None
        if not (paths.get((program_id, name)) or paths.get((None, name))):
            out["unscoped_ns"] += ns
            key = (run["program"], xplane.base_name(name))
            by_op[key] = by_op.get(key, 0) + ns
    if by_op:
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:8]
        _log("ops without a scope, s by (program, op): " + ", ".join(f"{p}/{o} {ns / 1e9:.4f}" for (p, o), ns in top))


def analyse_planes(planes, window: Optional[Interval], serialized=None) -> Optional[dict]:
    """Everything the readers below need, from one trace's planes: ``None``
    where the trace holds no device plane, no ``nxd.step`` span or no
    ``nxd.program`` span; else a dict whose every number is there (module
    docstring, Totality)."""
    if not any(xplane.DEVICE_PLANE.match(plane.name) for plane in planes):
        return None
    out = {"join": "none", "joined": 0, "unjoined": 0, "shift_ns": 0, "drift_us_per_s": 0.0, "violation_ns": 0,
           "window_ns": 0, "busy_ns": 0, "parts": {name: 0 for name in PARTS}, "below_floor_ns": 0, "in_run_ns": 0,
           "largest": {name: 0 for name in PARTS}, "busy_by_class": {c: 0 for c in CLASSES}, "unscoped_ns": 0}
    read = _stage("read", lambda: _read(planes), None)
    if read is None:
        return out
    if not read["programs"] or not any(name == ps.STEP for _, _, name, _ in read["phases"]):
        return None
    out["join"] = _stage("join", lambda: _join(read), "none")
    if out["join"] == "none":
        for r in read["runs"]:
            r["call"] = None
    for r in read["runs"]:
        r["program"] = read["programs"][r["call"]][2] if r["call"] is not None else None
        r["class"] = program_class(r["program"]) if r["program"] else "other"
    out["joined"] = sum(r["call"] is not None for r in read["runs"])
    out["unjoined"] = len(read["runs"]) - out["joined"]
    clock = _stage("fit", lambda: _fit(read, out["join"], window, out), None)
    if clock is None:
        clock = Clock()
        out.update(shift_ns=0, drift_us_per_s=0.0, violation_ns=0)
    # --- the device's time on the fitted clock
    ops = [(clock(a), clock(b), name) for a, b, name in read["ops"]]
    if window is None:
        if not ops:
            return out
        window = (min(a for a, _, _ in ops), max(b for _, b, _ in ops))
    lo, hi = window
    ops = xplane.clip(ops, lo, hi)
    out.update(window_ns=hi - lo, busy_ns=xplane.union_length((a, b) for a, b, _ in ops))
    _stage("split", lambda: _idle_parts(read, clock, ops, window, out), None)
    owners = _stage("classes", lambda: _busy_by_class(read, clock, ops, window, out), None)
    if owners is not None:
        _stage("scopes", lambda: _unscoped(read, ops, owners, serialized, out), None)
    return out


def analyse(run: dict) -> Optional[dict]:
    """:func:`analyse_planes` of the traced run, made once and kept beside the spans."""
    got = ps.load(run)
    if not got:
        return None
    if _CACHE not in got:
        got[_CACHE] = analyse_planes(got["planes"], got["window"], got["serialized"])
    return got[_CACHE]


# --- the readers' arithmetic -----------------------------------------------------------------


def _share_pct(part: int, whole: int) -> float:
    return 100.0 * part / whole if whole else 0.0


def idle_by_phase_pct(run: dict, part: str) -> Optional[float]:
    got = analyse(run)
    return None if got is None else _share_pct(got["parts"][part], got["window_ns"])


def other_programs_dev_share_pct(run: dict) -> Optional[float]:
    got = analyse(run)
    return None if got is None else _share_pct(got["busy_by_class"]["other"], got["busy_ns"])


def unscoped_dev_share_pct(run: dict) -> Optional[float]:
    got = analyse(run)
    return None if got is None else _share_pct(got["unscoped_ns"], got["busy_ns"])


def host_device_clock_offset_ms(run: dict) -> Optional[float]:
    got = analyse(run)
    return None if got is None else got["shift_ns"] / 1e6


def clock_fit_violation_ms(run: dict) -> Optional[float]:
    got = analyse(run)
    return None if got is None else got["violation_ns"] / 1e6


def step_pages_ms(run: dict) -> Optional[float]:
    """Median wall of ``nxd.step.decode.pages`` over the decode-only steps
    (the steps ``step_host_ms`` uses); host spans alone, so a CPU rehearsal
    reads it too. ``None`` for a program without ``nxd.program`` spans."""
    if not ps.spans(run, PROGRAM) or not ps.spans(run, ps.STEP):
        return None
    walls = []
    for step in ps.spans(run, ps.STEP):
        if ps.children(run, step, ps.READBACK) and not ps.children(run, step, ps.PREFILL):
            walls.append(sum(b - a for a, b, _, _ in ps.children(run, step, PAGES)) / 1e6)
    return float(stats.percentile(walls, 50)) if walls else 0.0


def pages_in_runs_share_pct(run: dict) -> Optional[float]:
    """``<kind>_pages_in_runs`` over ``<kind>_pages_mapped`` of the dispatch
    spans of the window, both kinds summed."""
    if not ps.spans(run, ps.STEP):
        return None
    in_runs = sum(sum(ps.stat_values(run, ps.DISPATCH, f"{kind}_pages_in_runs")) for kind in ("full", "window"))
    mapped = sum(sum(ps.stat_values(run, ps.DISPATCH, f"{kind}_pages_mapped")) for kind in ("full", "window"))
    return 100.0 * in_runs / mapped if mapped else 0.0
