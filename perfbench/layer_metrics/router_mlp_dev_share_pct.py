"""Device time of the router over busy time, traced window (%): self time of
the ops under the scope ``moe.router``. For a router that is an MLP over a
state of its own (a down-projection, the previous layer's state mixed in, a
norm and three small matmuls, then softmax and the biased choice) this is four
matmuls of 256 a layer a step, bound by their latency; for a linear router one
matmul. ``None`` where the trace shows no such scope."""
from perfbench import program_spans

SCOPE = "moe.router"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE)
