"""Share of the traced window the device stood idle (%) inside ``nxd.step.decode.readback`` or ``nxd.step.prefill.first_token`` AFTER the last op of the run the host waits for: the device is done and the host has not been told (the runtime's completion path; a stalled ``device_get`` is this number, and the log gives the largest single interval). The device's idle
intervals of at least ``xplane.MIN_GAP_NS``, on the fitted clock, each split by
OVERLAP over the stepping thread's spans (``perfbench/chunk_gaps.py``); the
eight parts add up to the idle time of those intervals. Always a number on a
traced chip run (a part no gap fell into reads 0.0); ``None`` for a program
without ``nxd.program`` spans, a trace without a device, or no trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.idle_by_phase_pct(run, "completion")
