"""90th percentile of the time a request waited in the engine's queue (ms):
from ``submit()`` to the start of ITS prefill, the ``queue_wait_us`` stat of
the program's ``nxd.step.prefill`` spans in the traced window. A request that
comes back after a preemption does not wait twice and carries no such stat."""
from perfbench import program_spans, stats


def read(run):
    waits = program_spans.stat_values(run, program_spans.PREFILL, "queue_wait_us")
    p90 = stats.percentile(waits, 90)
    return None if p90 is None else p90 / 1e3
