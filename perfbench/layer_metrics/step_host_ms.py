"""Host time of a decode-only step (ms): median over the ``nxd.step`` spans
of the traced window that hold a decode chunk and no prefill, of the step's
wall less its ``nxd.step.decode.readback`` (where the host waits for the
device). What is left is the host's own work a chunk: admission checks, the
dispatch, the unpack, the callbacks."""
from perfbench import program_spans as ps
from perfbench import stats


def read(run):
    hosts = []
    for step in ps.spans(run, ps.STEP):
        readbacks = ps.children(run, step, ps.READBACK)
        if not readbacks or ps.children(run, step, ps.PREFILL):
            continue
        hosts.append((step[1] - step[0] - sum(b - a for a, b, _, _ in readbacks)) / 1e6)
    return stats.percentile(hosts, 50)
