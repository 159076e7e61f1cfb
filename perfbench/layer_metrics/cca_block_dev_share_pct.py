"""Device time of the compressed-latent attention block over busy time, traced
window (%): self time of the ops under the scope ``attn.cca`` (the q/k/v
projections, the mean term, the two convolutions with the per-slot state's read
and write, the unit heads, rotary, the cache write, decode's walking kernel
and the window pages' copies, prefill's flash forward, the output projection),
kernels included: each is called in a scope under it and named after that.
``None`` where the trace shows no such scope: the program has no such block."""
from perfbench import program_spans

SCOPE = "attn.cca"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE, also_ops=(SCOPE,))
