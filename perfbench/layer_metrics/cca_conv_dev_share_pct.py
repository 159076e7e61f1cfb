"""Device time of what compressed-latent attention adds AROUND its kernel over
busy time, traced window (%): self time of the ops under the scope
``attn.cca.conv``: the mean term, the depthwise and the per-head convolution
(each one token back), the per-slot state's read and write, the unit heads,
the keys' temperature, rotary and the cache write: small operations, bound by
their latency in a decode step. No kernel is called in the scope. ``None``
where the trace shows no such scope."""
from perfbench import program_spans

SCOPE = "attn.cca.conv"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE)
