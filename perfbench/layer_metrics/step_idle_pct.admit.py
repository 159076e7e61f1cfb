"""Share of the traced window the device stood idle while ``step()``
reaped, preempted, admitted or prefilled (%): the device's idle gaps of at
least ``xplane.MIN_GAP_NS`` whose middle lies in ``nxd.step.reap``, ``.preempt``, ``.admit``, ``.prefill`` and its ``.first_token``,
by the benchmark's own reduction (``xplane.reduce_planes``)."""
from perfbench import program_spans


def read(run):
    return program_spans.step_idle_pct(run, "admit")
