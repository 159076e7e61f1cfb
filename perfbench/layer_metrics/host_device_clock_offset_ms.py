"""The shift applied to the device plane to lay it on the host plane's clock
(ms), at the traced window's middle: the lower bound that causality over the
joined runs gives host - device (no run starts before its enqueue), or the
midpoint of a crossed pair (``clock_fit_violation_ms``). 0.0 where none could be
fitted. ``None`` without the spans, a device plane or a trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.host_device_clock_offset_ms(run)
