"""Tokens a decode step attends over tokens its slots hold (%), traced
window: the stats ``selected_tokens`` (``sum(min(held, topk))`` over the
decoding slots) and ``ctx_tokens`` of the program's
``nxd.step.decode.dispatch`` spans, summed over the window: whether the
traffic still works the mechanism (15% at a mean context of 13.7k and 2048
kept; 100% would be dense attention). A program without the stats (no
indexer, or the parent of the PR that added them): ``None``."""
from perfbench import program_spans


def read(run):
    held = program_spans.stat_values(run, program_spans.DISPATCH, "ctx_tokens")
    kept = program_spans.stat_values(run, program_spans.DISPATCH, "selected_tokens")
    if not held or not kept or not sum(held):
        return None
    return 100.0 * sum(kept) / sum(held)
