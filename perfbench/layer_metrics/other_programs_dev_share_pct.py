"""Busy time of the runs that are neither the decode chunk nor what a prefill
awaits (the admission's programs, the sampler's, the host's eager ``jnp``
programs and every run no ``nxd.program`` span names) over the device's busy
time (%), traced window; each run classed by the ledger name of the call that
made it (``perfbench/chunk_gaps.py``). ~100 where no run could be joined (the
log says so). ``None`` without the spans, a device plane or a trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.other_programs_dev_share_pct(run)
