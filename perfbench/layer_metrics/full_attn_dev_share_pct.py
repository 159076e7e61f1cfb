"""Device time of the FULL layers' attention over busy time, traced window
(%): self time of the ops under the scope ``attn.full`` (the head norms,
the cache write (no rotary), decode's walking kernel and the window pages' copies,
prefill's causal flash forward), kernels included: each is called in the scope
and named after it. ``None`` where the trace shows no such scope: the program
has no such layers (a model of one kind of layer names its attention `attn`)."""
from perfbench import program_spans

SCOPE = "attn.full"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE, also_ops=(SCOPE,))
