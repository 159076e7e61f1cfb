"""Device time of the looped stack's passes over busy time, traced window (%):
self time of the ops under the scope ``loop.pass`` (every pass of the stack:
its layers' projections, norms, MLPs and attention, kernels included, and the
closing norm), in the decode chunk and in the prefills alike. What is left is
the embedding, the head, the sampler and the paged path's window gather.
``None`` where the trace shows no such scope: the program runs its stack once."""
from perfbench import program_spans

SCOPE = "loop.pass"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE)
