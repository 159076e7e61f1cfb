"""Self time of the ops of JOINED runs whose ``op_name`` holds no scope path,
over the device's busy time (%), traced window: what no ``*_dev_share_pct`` can
reach. The top eight ``(program, op)`` go to the log (cell 8: ``paged_admit/copy``).
0.0 where the scope table cannot be read (the log says so). ``None`` without
the spans, a device plane or a trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.unscoped_dev_share_pct(run)
