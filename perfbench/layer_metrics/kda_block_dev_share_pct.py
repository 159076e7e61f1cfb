"""Device time of the linear-attention (gated delta-rule) block over busy time,
traced window (%): self time of the ops under the scope ``attn.kda`` (the six
projections, the three convolutions with the per-slot taps' read and write, the
unit heads, the decay, beta, the output gate and its norm, the recurrence's
kernels, the output projection), kernels included: each is called in a scope
under it and named after that. ``None`` where the trace shows no such scope:
the program has no such block."""
from perfbench import program_spans

SCOPE = "attn.kda"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE, also_ops=(SCOPE,))
