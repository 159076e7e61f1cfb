"""Share of the traced window the device stood idle while ``step()``
unpacked the chunk, ran the callbacks and retired (%): the device's idle gaps of at
least ``xplane.MIN_GAP_NS`` whose middle lies in ``nxd.step.decode.emit`` and ``nxd.step.health``,
by the benchmark's own reduction (``xplane.reduce_planes``)."""
from perfbench import program_spans


def read(run):
    return program_spans.step_idle_pct(run, "emit")
