"""Median host time of one train-step dispatch (ms): the trainer's
``nxd.train.dispatch`` spans (the jitted step's call returning) in the traced
window. The step is asynchronous, so this is the host's cost a step, not the
step's."""
from perfbench import program_spans, stats


def read(run):
    walls = [(b - a) / 1e6 for a, b, _, _ in program_spans.spans(run, program_spans.TRAIN_DISPATCH)]
    return stats.percentile(walls, 50)
