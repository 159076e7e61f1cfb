"""Share of the traced window the device stood idle (%) inside ``nxd.step.reap``, ``.preempt``, ``.admit``, ``.prefill`` outside its programs and its first token, ``.health`` and ``.close``: the host's work around a chunk that is not the chunk's own. The device's idle
intervals of at least ``xplane.MIN_GAP_NS``, on the fitted clock, each split by
OVERLAP over the stepping thread's spans (``perfbench/chunk_gaps.py``); the
eight parts add up to the idle time of those intervals. Always a number on a
traced chip run (a part no gap fell into reads 0.0); ``None`` for a program
without ``nxd.program`` spans, a trace without a device, or no trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.idle_by_phase_pct(run, "admit")
