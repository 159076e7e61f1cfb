"""Device time of the gated delta-rule RECURRENCE alone over busy time, traced
window (%): self time of the ops under the scope ``attn.kda.recur``: decode's
one-token state update (the state read and written in place) and prefill's
chunked forward, both Pallas kernels named after the scope, and the few small
ops that lay their vectors out. What the architecture adds around them (the
projections, the convolutions, the gates) is ``kda_block_dev_share_pct`` less
this. ``None`` where the trace shows no such scope."""
from perfbench import program_spans

SCOPE = "attn.kda.recur"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE, also_ops=(SCOPE,))
