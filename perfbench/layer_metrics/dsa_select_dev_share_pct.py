"""Device time of the selection alone over busy time, traced window (%):
self time of the ops under the scope ``dsa.select``: ``jax.lax.top_k`` of
each slot's index scores, 2048 of 32,768 columns a slot a layer a step, a
sort on this chip. ``None`` where the trace shows no such scope."""
from perfbench import program_spans

SCOPE = "dsa.select"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE)
