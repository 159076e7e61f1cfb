"""Share of the traced window the device stood idle while ``step()``
dispatched the decode chunk and until the device ran it (%): the device's
idle gaps of at least ``xplane.MIN_GAP_NS`` whose middle lies in
``nxd.step.decode.dispatch`` or, the host already waiting, in
``nxd.step.decode.readback`` (launch latency, or a hole inside the chunk),
by the benchmark's own reduction (``xplane.reduce_planes``)."""
from perfbench import program_spans


def read(run):
    return program_spans.step_idle_pct(run, "dispatch")
