"""Wall this engine's steps spent over their expected wall, up to the traced
window's close (ms): the stat ``overrun_us`` of the last traced ``nxd.step``
span, the step ledger's running total (``observability/flight_recorder.py``:
a step overran if it took over 0.5 s more than, and over twice, the median of
what it is made of; warm-up and the reference check's warm steps included,
compiling and first-of-a-kind steps not). 0 in most runs; a line that reads
1200-4400 marks a run whose other numbers were taken through a stalled step
(the ``slow_step`` line on stderr says where it sat). A program without the
stat: ``None``."""
from perfbench import program_spans


def read(run):
    values = program_spans.stat_values(run, program_spans.STEP, "overrun_us")
    return values[-1] / 1e3 if values else None
