"""By how much the clock's bounds stay crossed after the fit (ms):
``max(0, lower - upper)``, 0.0 on a trace where a constant offset, or a drift
up to 200 us/s, lies between every ``enqueue - device start`` and every
``completion - device end``. The error bar on every ``idle_by_phase_pct.*`` of the
same line. ``None`` without the spans, a device plane or a trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.clock_fit_violation_ms(run)
