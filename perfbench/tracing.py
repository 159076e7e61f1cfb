"""What both runners need around the device: a PRNG key from ``--seed``, the
traced sub-window of a ``--trace 1`` run, and a log of what JAX lowered and
compiled, and when."""

from __future__ import annotations

import time
from typing import List, Tuple

from perfbench import xplane

# a --trace 1 run traces the last seconds of its window: long enough for some
# twenty decode chunks or ten train steps, short enough that the trace reduces
# in seconds
TRACE_SECONDS = 6.0


def seed_key(seed: int, stream: int):
    """A PRNG key from a seed of any size (``--seed`` may pass 2**31)."""
    import jax

    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, int(seed) >> 31), stream)


class TraceWindow:
    """``jax.profiler`` on for part of a run, with one host span
    (``xplane.WINDOW_SPAN``) that marks exactly the traced window for the
    reduction. The Python tracer is off: it slows the host loop it would
    measure; the benchmark's own ``TraceAnnotation`` spans are enough.

    ``close()`` ends the span and costs nothing; ``stop()`` ends the profiler's
    session and writes the trace, which takes the host seconds (8-9 s after a
    6 s window on the chip's host, PR 23). A runner that still has requests to
    drain closes the span when the window closes and stops the session once
    nothing is running: the reduction clips to the span."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.on = False          # the span is open: the traced window is running
        self.session = False     # the profiler's session is open
        self._span = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._span.__enter__()
        self.on = self.session = True

    def close(self) -> None:
        if self.on:
            self._span.__exit__(None, None, None)
            self.on = False

    def stop(self) -> None:
        import jax

        self.close()
        if self.session:
            jax.profiler.stop_trace()
            self.session = False


class CompileLog:
    """Every program JAX lowers or hands to the backend's compiler in this
    process, with the time it ended: the program's own ledger counts only the
    programs it wraps, and a lowering whose executable then comes out of the
    persistent cache is a stall all the same (and a compile in the first run
    of a fresh checkout). ``jax.monitoring`` has no way to take a listener
    out again, so there is one log per process."""

    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILED = "/jax/core/compile/backend_compile_duration"
    _the_log = None

    def __init__(self):
        self.rows: List[Tuple[float, str, str, float]] = []   # (t_end, event, function, seconds)

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._the_log is None:
            from jax import monitoring

            cls._the_log = log = cls()

            def listen(event, seconds, **kw):
                if event in (cls.LOWERED, cls.COMPILED):
                    log.rows.append((time.perf_counter(), event, str(kw.get("fun_name")), float(seconds)))

            monitoring.register_event_duration_secs_listener(listen)
        return cls._the_log

    def between(self, event: str, t0: float, t1: float) -> List[Tuple[float, str, float]]:
        """``(t_end, function, seconds)`` of every ``event`` that ended in ``[t0, t1]``."""
        return [(t, fn, s) for t, e, fn, s in self.rows if e == event and t0 <= t <= t1]
