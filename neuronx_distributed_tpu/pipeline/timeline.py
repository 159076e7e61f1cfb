"""Pipeline timeline export (reference: ``pipeline/timeline.py`` ``PPTimeline``
— per-task chrome-trace events gathered over the PP gloo group, base class
``utils/timeline.py:15``).

The reference's runtime dispatches one task at a time per process, so it can
timestamp each task on the host. The TPU engines compile the ENTIRE schedule
into one XLA program — there are no host-visible per-task boundaries. The
honest equivalent, provided here, renders the engine's schedule (the exact
cycle tables the runtime asserts against) as a chrome-trace, calibrated by
the measured step time: per-rank rows, one slice per forward/backward slot
per cycle. For true device-level timing, pair it with a ``jax.profiler`` trace
(``observability.profile_window`` around ``Trainer.fit``)."""

from __future__ import annotations

import json
from typing import Optional

from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.pipeline.scheduler import (
    BackwardTask,
    ForwardTask,
    SyncTrainInterleavedSchedule,
)


def export_pipeline_timeline(
    engine,
    path: str,
    step_time_s: Optional[float] = None,
    num_stages: Optional[int] = None,
) -> dict:
    """Write a chrome-trace JSON (load in chrome://tracing / Perfetto) of the
    engine's pipeline schedule. ``step_time_s`` (e.g. measured by the
    Trainer's throughput meter) scales cycles to real microseconds; without
    it, one cycle = 1 ms of trace time. Returns the trace dict."""
    S = num_stages or mesh_lib.get_pipeline_model_parallel_size()
    M = engine.num_microbatches
    C = getattr(engine, "num_chunks", 1)
    sched0 = SyncTrainInterleavedSchedule(M, S, 0, num_chunks=C)
    cycles = sched0.num_cycles
    cycle_us = (step_time_s * 1e6 / cycles) if step_time_s else 1000.0

    events = []
    for r in range(S):
        sched = SyncTrainInterleavedSchedule(M, S, r, num_chunks=C)
        # replay the stream cycle-aligned: forward slot in the first half of
        # the cycle, backward slot in the second (the lockstep SPMD layout)
        for t in sched.steps():
            if isinstance(t, (ForwardTask, BackwardTask)):
                is_fwd = isinstance(t, ForwardTask)
                # exact cycle from the closed forms the runtime uses
                if is_fwd:
                    g, i = divmod(t.mb, S)
                    cyc = g * S * C + t.chunk * S + i + r
                else:
                    g, i = divmod(t.mb, S)
                    cyc = (
                        g * S * C + (C - 1 - t.chunk) * S + i
                        + (S * C - 1) + (S - 1 - r)
                    )
                events.append(
                    {
                        "name": f"{'fwd' if is_fwd else 'bwd'} mb{t.mb}"
                        + (f" c{t.chunk}" if C > 1 else ""),
                        "ph": "X",
                        "pid": 0,
                        "tid": r,
                        "ts": cyc * cycle_us + (0 if is_fwd else cycle_us / 2),
                        "dur": cycle_us / 2,
                        "args": {"microbatch": t.mb, "chunk": t.chunk,
                                 "cycle": cyc},
                    }
                )
    trace = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {
            "schedule": type(engine).__name__,
            "stages": S,
            "microbatches": M,
            "chunks": C,
            "cycles": cycles,
            "step_time_s": step_time_s,
        },
    }
    with open(path, "w") as f:
        json.dump(trace, f)
    return trace
