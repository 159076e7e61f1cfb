"""Device time of the WINDOW layers' attention over busy time, traced window
(%): self time of the ops under the scope ``attn.window`` (the head norms,
rotary, the cache write, decode's walking kernel and the window pages' copies,
prefill's banded flash forward), kernels included: each is called in the scope
and named after it. ``None`` where the trace shows no such scope: the program
has no window layers."""
from perfbench import program_spans

SCOPE = "attn.window"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE, also_ops=(SCOPE,))
