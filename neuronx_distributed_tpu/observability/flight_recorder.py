"""Flight recorder: bounded ring of recent structured events + post-mortem.

PRs 3 and 5 gave serving and training HALT/emergency paths that stop an
unattended run safely — but they leave no record of *why* beyond a one-line
``halt_reason``. The flight recorder is the observability twin of that
chaos machinery: a fixed-size ring buffer of recent structured events
(state transitions, dispatch retries, anomaly skips, health changes,
checkpoints) that the engine/trainer feed as they run, auto-dumped as a
redacted JSON post-mortem the moment the run dies (serving ``HALTED``,
``TrainerHalted``, emergency checkpoint) — so the last N things that
happened before the death are on disk even when nobody was watching.

Redaction: post-mortems may leave the machine (bug reports, dashboards),
so payload CONTENT never enters the ring — only shapes of it. Strings are
truncated, sequences/arrays collapse to ``{"len": n}``, nested dicts are
redacted to a bounded depth, and anything else records its type name.
Token ids, prompts, and tensors structurally cannot appear in a dump.

The step ledger (``FlightRecorder.steps``, a :class:`StepLedger`) is the
recorder's second ring: one record per ``ServingEngine.step()``, all run
long, fed by ``tracing.span``'s ledger sink (the walls of the step's phases
on ``time.perf_counter()``), with a verdict per step from the ledger's own
history (did it overrun what steps made of the same parts take?) and a
watchdog thread that samples the stepping thread's stack while a step is
over its limit. Steps never enter the event ring (a 51 s window is 300-700
of them); a step that overran leaves ONE ``slow_step`` event there.

Hot-path contract (this module is on graftlint GL02's hot-path list):
``record()`` takes host scalars only and costs one dict build + deque
append; it never touches a device value, so feeding the recorder from the
engine/trainer inner loops adds zero device syncs.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

from neuronx_distributed_tpu.observability import tracing

try:  # Linux: what the scheduler and the pager did to the stepping thread
    import resource

    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):
    resource = _RUSAGE_THREAD = None

__all__ = ["FlightRecorder", "StackSamples", "StepLedger"]

_MAX_STR = 200
_MAX_SEQ = 8  # short numeric tuples (shapes, bucket ids) pass through
_MAX_DEPTH = 3
SCHEMA_VERSION = 1

# the step ring: a 51 s benchmark window is 300-700 steps
STEP_CAPACITY = 4096
# the last step records a post-mortem carries
POSTMORTEM_STEPS = 64
# a step OVERRAN if its wall is over its expected wall by more than the
# floor AND over the factor times it: a prefill of 1.4 s on full slots is
# its bucket's expected wall, a 1.2 s step around a 0.1 s chunk is not
OVERRUN_FLOOR_S = 0.5
OVERRUN_FACTOR = 2.0
# walls a running median is taken over, per chunk and per prefill bucket
MEDIAN_WINDOW = 64
# prefill buckets whose walls are kept (oldest dropped first)
MAX_BUCKETS = 256
# the watchdog: a check a few times a second, then a sample every 100 ms of
# a step that is over its limit; the thread ends itself once no step has
# begun for the idle time (the next step starts another)
WATCH_POLL_S = 0.2
WATCH_SAMPLE_S = 0.1
WATCH_IDLE_S = 5.0
MAX_SAMPLES = 32
MAX_FRAMES = 6
_SCHEDSTAT = "/proc/thread-self/schedstat"


class StackSamples(list):
    """The watchdog's samples of one step: ``(seconds into the step, phase,
    frames)`` each, frames innermost first as ``file:line function``. Code
    locations, not payload: :func:`redact` lets them through, bounded in
    count and length, where any other list of strings collapses."""

    def redacted(self) -> List[dict]:
        return [
            {"t_s": round(float(t), 3), "phase": str(phase)[:_MAX_STR],
             "frames": [str(f)[:_MAX_STR] for f in frames[:MAX_FRAMES]]}
            for t, phase, frames in self[:MAX_SAMPLES]
        ]


def redact(value: Any, depth: int = 0) -> Any:
    """Collapse a payload value to its redacted, JSON-safe form."""
    if value is None or isinstance(value, (bool, int, float)):
        if isinstance(value, float) and value != value:  # NaN -> JSON-safe
            return "nan"
        return value
    if isinstance(value, str):
        return value if len(value) <= _MAX_STR else value[:_MAX_STR] + "…"
    if isinstance(value, dict):
        if depth >= _MAX_DEPTH:
            return {"keys": len(value)}
        return {str(k)[:64]: redact(v, depth + 1) for k, v in value.items()}
    if isinstance(value, StackSamples):
        return value.redacted()
    if isinstance(value, (list, tuple)):
        if len(value) <= _MAX_SEQ and all(
            v is None or isinstance(v, (bool, int, float)) for v in value
        ):
            return ["nan" if isinstance(v, float) and v != v else v
                    for v in value]
        return {"len": len(value)}
    shape = getattr(value, "shape", None)
    if shape is not None:  # ndarray / jax.Array: shape is host metadata
        return {"type": type(value).__name__,
                "shape": [int(s) for s in shape]}
    return {"type": type(value).__name__}


class _Median:
    """Running median of the last :data:`MEDIAN_WINDOW` walls, kept current
    on ``add`` so that reading it on the step's path is an attribute load."""

    __slots__ = ("_walls", "value")

    def __init__(self):
        self._walls: deque = deque(maxlen=MEDIAN_WINDOW)
        self.value: Optional[float] = None

    def add(self, wall: float) -> None:
        self._walls.append(wall)
        self.value = statistics.median(self._walls)


class _OpenStep:
    """The step in flight: written by the stepping thread, read (and its
    ``samples`` appended to) by the watchdog."""

    __slots__ = ("ordinal", "ident", "t0", "cpu0", "process_cpu0", "stack",
                 "phases", "prefills", "bucket", "chunk", "chunk_expected",
                 "active", "sampled_slots", "expected", "cold", "samples")

    def __init__(self, ordinal: int, t0: float):
        self.ordinal = ordinal
        self.ident = threading.get_ident()
        self.t0 = t0
        self.cpu0 = time.thread_time_ns()
        self.process_cpu0 = time.process_time_ns()
        self.stack: list = []       # (name, start) of the open spans
        self.phases: Dict[str, float] = {}
        self.prefills: list = []    # (bucket, wall) of each prefill
        self.bucket = None          # of the prefill in flight
        self.chunk = False          # a chunk was read back
        self.chunk_expected = False  # its wall is in ``expected``
        self.active = 0             # slots the chunk read back was dispatched for
        self.sampled_slots = 0      # those of them whose request samples
        self.expected = 0.0         # grows as the step's parts are entered
        self.cold = False           # a part with no history: no verdict
        self.samples = StackSamples()


def overrun_limit(expected_s: float) -> float:
    """The wall past which a step of ``expected_s`` has overrun."""
    return max(expected_s + OVERRUN_FLOOR_S, OVERRUN_FACTOR * expected_s)


class StepLedger:
    """The account of every ``ServingEngine.step()``: the third sink of
    ``tracing.span`` (``enter`` / ``note`` / ``exit``, called with every
    ``nxd.step*`` span), a bounded ring of one record a step, a verdict per
    step, and the watchdog. Everything is on ``time.perf_counter()``, the
    clock of whoever drives the engine, so the ring can be cut by a
    caller's own marks.

    A step is made of a decode chunk (``dispatch + readback + emit``) and
    of prefills, each of a bucket (its ``padded`` length and the tokens it
    ``reused``). Its EXPECTED wall is the running median of the chunk's wall
    plus that of each prefill's bucket; a step with a part seen for the
    first time, or one in which the program ledger counted a compile, is
    COLD and gets no verdict. See :data:`OVERRUN_FLOOR_S`.

    One stepping thread at a time (an engine is stepped by one thread;
    engines do not share a recorder)."""

    def __init__(self, capacity: int = STEP_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._ring: deque = deque(maxlen=capacity)
        self._born = time.perf_counter()
        self._ordinal = 0
        self._open: Optional[_OpenStep] = None
        self._chunk = _Median()
        self._ahead_slots = (0, 0)   # of a chunk called ahead: the next step's
        self._buckets: Dict[Any, _Median] = {}
        self.overruns = 0
        self.overrun_seconds = 0.0   # excess wall of the overrun steps
        self._os_prev = None         # (thread, counters) at the last finish
        # the stepping thread's schedstat, kept open (a path lookup under
        # /proc costs more than the step's whole account): (thread, fd, closer);
        # False where the file does not exist (not Linux, a sandboxed kernel)
        self._schedstat = None if os.path.exists(_SCHEDSTAT) else False
        self._lock = threading.Lock()
        self._watchdog: Optional[threading.Thread] = None
        self._last_begin = self._born

    # --- the span sink (the stepping thread) ---------------------------------

    def enter(self, name: str, stats: dict) -> None:
        now = time.perf_counter()
        step = self._open
        if step is None:
            if name != tracing.STEP:
                return  # a phase outside step() (a drain's preemption)
            step = self._open = _OpenStep(self._ordinal, now)
            # the chunk this step reads back, if the last one called it
            step.active, step.sampled_slots = self._ahead_slots
            self._ahead_slots = (0, 0)
            self._ordinal += 1
            self._last_begin = now
            with self._lock:
                if self._watchdog is None:
                    self._start_watchdog()
        elif name in (tracing.STEP_DISPATCH, tracing.STEP_READBACK):
            # ONE chunk's wall a step, at whichever opens first: a step
            # reads back one chunk, and may have called the next before it
            # (the engine runs ahead) or have called none (it read back the
            # last one run ahead)
            self._slots(step, stats)
            if not step.chunk_expected:
                step.chunk_expected = True
                self._expect(step, self._chunk)
        step.stack.append((name, now))

    def note(self, name: str, stats: dict) -> None:
        """Stats a span learned after it opened: the slots a chunk runs for
        (the engine counts them inside its dispatch span), and a prefill's
        bucket, which raises the step's limit before the program runs."""
        step = self._open
        if step is not None and name == tracing.STEP_DISPATCH:
            self._slots(step, stats)
            return
        if (step is None or "padded" not in stats
                or name != tracing.STEP_PREFILL):
            return
        step.bucket = (int(stats["padded"]), int(stats.get("reused", 0)))
        self._expect(step, self._buckets.get(step.bucket))

    def exit(self, name: str) -> None:
        now = time.perf_counter()
        step = self._open
        if step is None or not step.stack or step.stack[-1][0] != name:
            return
        wall = now - step.stack.pop()[1]
        step.phases[name] = step.phases.get(name, 0.0) + wall
        if name == tracing.STEP_PREFILL:
            step.prefills.append((step.bucket, wall))
            step.bucket = None
        elif name == tracing.STEP_READBACK:
            step.chunk = True
        elif not step.stack:
            self._open = None  # left without finish(): an exception's way out

    def _slots(self, step: _OpenStep, stats: dict) -> None:
        """The record is of the chunk the step READS BACK: the slots of one
        the engine called AHEAD of it (stat ``ahead`` 1) go to the record
        of the step after."""
        if "active" not in stats:
            return
        slots = (int(stats["active"]), int(stats.get("sampled_slots", 0)))
        if stats.get("ahead"):
            self._ahead_slots = slots
        else:
            step.active, step.sampled_slots = slots

    @staticmethod
    def _expect(step: _OpenStep, median: Optional[_Median]) -> None:
        if median is None or median.value is None:
            step.cold = True
        else:
            step.expected += median.value

    # --- the step's close ------------------------------------------------------

    def finish(self, compiles: int) -> Optional[dict]:
        """End the step in flight, just before its ``nxd.step`` span closes:
        judge it, feed the medians, append its record to the ring and return
        it (``None`` with no step in flight). ``compiles``: what the program
        ledger counted during the step."""
        step = self._open
        if step is None:
            return None
        self._open = None
        wall = time.perf_counter() - step.t0
        thread_cpu = (time.thread_time_ns() - step.cpu0) / 1e9
        process_cpu = (time.process_time_ns() - step.process_cpu0) / 1e9
        phases = step.phases
        cold = step.cold or compiles > 0
        expected = step.expected
        overran = not cold and wall > overrun_limit(expected)
        if not compiles:  # a compiling step's walls are no step's median
            if step.chunk:
                self._chunk.add(sum(
                    phases.get(n, 0.0) for n in (
                        tracing.STEP_DISPATCH, tracing.STEP_READBACK,
                        tracing.STEP_EMIT)
                ))
            for bucket, prefill_wall in step.prefills:
                self._bucket(bucket).add(prefill_wall)
        record = {
            "step": step.ordinal, "t0": step.t0, "wall_s": wall,
            "phases": phases,
            "prefills": [b[0] for b, _ in step.prefills if b is not None],
            "chunk": step.chunk, "active": step.active,
            "sampled_slots": step.sampled_slots,
            "compiles": int(compiles),
            "thread_cpu_s": thread_cpu, "process_cpu_s": process_cpu,
            "cold": cold, "expected_s": None if cold else expected,
            "overran": overran,
        }
        record.update(self._os_deltas(step.ident))
        if overran:
            self.overruns += 1
            self.overrun_seconds += wall - expected
            record["since_start_s"] = step.t0 - self._born
            record["samples"] = step.samples
        self._ring.append(record)
        return record

    def _bucket(self, bucket) -> _Median:
        median = self._buckets.get(bucket)
        if median is None:
            if len(self._buckets) >= MAX_BUCKETS:
                del self._buckets[next(iter(self._buckets))]
            median = self._buckets[bucket] = _Median()
        return median

    def _os_deltas(self, ident: int) -> dict:
        """What the host did to the stepping thread since the previous
        step's finish: context switches it made (``voluntary_switches``) and
        suffered (``involuntary_switches``), major faults, seconds it was
        runnable and not running (``run_delay_s``). Linux only, and only
        between two finishes on one thread; empty elsewhere."""
        if _RUSAGE_THREAD is None:
            return {}
        usage = resource.getrusage(_RUSAGE_THREAD)
        now = (usage.ru_nvcsw, usage.ru_nivcsw, usage.ru_majflt,
               self._run_delay_ns(ident))
        prev, self._os_prev = self._os_prev, (ident, now)
        if prev is None or prev[0] != ident:
            return {}
        before = prev[1]
        out = {
            "voluntary_switches": now[0] - before[0],
            "involuntary_switches": now[1] - before[1],
            "major_faults": now[2] - before[2],
        }
        if now[3] is not None and before[3] is not None:
            out["run_delay_s"] = (now[3] - before[3]) / 1e9
        return out

    def _run_delay_ns(self, ident: int) -> Optional[int]:
        """Nanoseconds the stepping thread has waited on a run queue
        (``/proc/thread-self/schedstat``, second field)."""
        sched = self._schedstat
        if sched is False:
            return None
        try:
            if sched is None or sched[0] != ident:
                if sched:
                    sched[2]()  # another thread steps now: close the last one's
                fd = os.open(_SCHEDSTAT, os.O_RDONLY)
                sched = self._schedstat = (
                    ident, fd, weakref.finalize(self, os.close, fd)
                )
            return int(os.pread(sched[1], 96, 0).split()[1])
        except (OSError, IndexError, ValueError):
            if self._schedstat:
                self._schedstat[2]()
            self._schedstat = False
            return None

    # --- reading ------------------------------------------------------------------

    def records(self) -> List[dict]:
        """The ring's step records, oldest first. ``t0`` is on
        ``time.perf_counter()``; ``phases`` holds the wall of every
        ``nxd.step.*`` span the step entered (``.prefill.first_token`` lies
        inside ``.prefill``; the others are siblings and add up to the
        step's wall)."""
        return list(self._ring)

    def __len__(self) -> int:
        return len(self._ring)

    # --- the watchdog ---------------------------------------------------------------

    def _start_watchdog(self) -> None:
        self._watchdog = threading.Thread(
            target=self._watch, name="nxd-step-watchdog", daemon=True
        )
        self._watchdog.start()

    def _watch(self) -> None:
        """Asleep but for a check every :data:`WATCH_POLL_S`; while the step
        in flight is over its limit, one sample of the stepping thread's
        stack every :data:`WATCH_SAMPLE_S`. The limit grows as the step
        enters its parts, and a cold step has none."""
        while True:
            step = self._open
            if step is None:
                with self._lock:
                    idle = time.perf_counter() - self._last_begin
                    if self._open is None and idle > WATCH_IDLE_S:
                        self._watchdog = None
                        return
            elif not step.cold and len(step.samples) < MAX_SAMPLES:
                elapsed = time.perf_counter() - step.t0
                if elapsed > overrun_limit(step.expected):
                    self._sample(step, elapsed)
                    time.sleep(WATCH_SAMPLE_S)
                    continue
            time.sleep(WATCH_POLL_S)

    @staticmethod
    def _sample(step: _OpenStep, elapsed: float) -> None:
        frame = sys._current_frames().get(step.ident)
        stack = step.stack
        phase = stack[-1][0] if stack else ""
        frames = []
        while frame is not None and len(frames) < MAX_FRAMES:
            code = frame.f_code
            where = "/".join(code.co_filename.split(os.sep)[-2:])
            frames.append(f"{where}:{frame.f_lineno} {code.co_name}")
            frame = frame.f_back
        step.samples.append((elapsed, phase, frames))


class FlightRecorder:
    """Bounded ring of structured events with atomic post-mortem dumps.

    ``dump_dir=None`` keeps post-mortems in memory only
    (``last_postmortem``); with a directory set, each dump writes
    ``postmortem_<subsystem>_<seq>.json`` atomically (tmp + rename)."""

    def __init__(
        self,
        capacity: int = 512,
        dump_dir: Optional[str] = None,
        subsystem: str = "run",
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.dump_dir = dump_dir
        self.subsystem = subsystem
        self._ring: deque = deque(maxlen=capacity)
        self._seq = 0  # events ever recorded (ring position anchor)
        self._dumps = 0
        # the second ring: one record a ServingEngine.step(), with the
        # overrun verdict and the watchdog (a trainer's recorder leaves it
        # empty, and no thread starts before the first step)
        self.steps = StepLedger()
        self.last_postmortem: Optional[dict] = None
        self.last_dump_path: Optional[str] = None

    # --- recording ----------------------------------------------------------

    def record(self, kind: str, **fields) -> dict:
        """Append one structured event (host scalars only) and return it as
        the ring holds it. ``kind`` is the event class (``health``,
        ``dispatch_failure``, ``anomaly_skip``, ``halt``, ...); fields are
        redacted on entry so the ring never holds payload content."""
        self._seq += 1
        ev: Dict[str, Any] = {
            "seq": self._seq,
            "t_mono": time.monotonic(),
            "kind": kind,
        }
        if fields:
            ev.update(redact(fields))
        self._ring.append(ev)
        return ev

    def events(self) -> List[dict]:
        """Current ring contents, oldest first."""
        return list(self._ring)

    def clear(self) -> None:
        self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)

    # --- post-mortem --------------------------------------------------------

    def build_postmortem(self, reason: str,
                         extra: Optional[dict] = None) -> dict:
        payload = {
            "schema": SCHEMA_VERSION,
            "subsystem": self.subsystem,
            "reason": redact(str(reason)),
            "wall_time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "events_recorded": self._seq,
            "events_kept": len(self._ring),
            "events": list(self._ring),
        }
        if len(self.steps):
            # what the steps before the death looked like
            payload["steps"] = [
                redact(r) for r in self.steps.records()[-POSTMORTEM_STEPS:]
            ]
        if extra:
            payload["extra"] = redact(extra)
        return payload

    def dump(self, reason: str, extra: Optional[dict] = None) -> Optional[str]:
        """Build and persist the post-mortem. Returns the file path (or
        ``None`` when memory-only). Never raises: the dump runs inside
        halt paths whose primary job — stopping the run safely and
        requeueing work — must not be hijacked by a full disk."""
        payload = self.build_postmortem(reason, extra)
        self.last_postmortem = payload
        self._dumps += 1
        if self.dump_dir is None:
            return None
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
        except Exception:
            return None

        def _candidate():
            return os.path.join(
                self.dump_dir,
                f"postmortem_{self.subsystem}_{self._dumps:03d}.json",
            )

        # never clobber an earlier crash's record: a RESTARTED run (fresh
        # recorder, counter back at 0) dumping into the same directory
        # skips forward past whatever previous lives left behind
        path = _candidate()
        while os.path.exists(path):
            self._dumps += 1
            path = _candidate()
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1)
            os.replace(tmp, path)
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        self.last_dump_path = path
        return path
