"""Share of the traced window the device stood idle (%) inside an ``nxd.program`` span (pjit's dispatch, the runtime's enqueue) plus the part of a readback or first-token span BEFORE the awaited run's first op (the call, the enqueue, the start; a hole inside or between the runs a span waits for counts here too, and the log says it apart). The device's idle
intervals of at least ``xplane.MIN_GAP_NS``, on the fitted clock, each split by
OVERLAP over the stepping thread's spans (``perfbench/chunk_gaps.py``); the
eight parts add up to the idle time of those intervals. Always a number on a
traced chip run (a part no gap fell into reads 0.0); ``None`` for a program
without ``nxd.program`` spans, a trace without a device, or no trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.idle_by_phase_pct(run, "launch")
