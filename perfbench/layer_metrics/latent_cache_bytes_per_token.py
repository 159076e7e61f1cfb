"""Bytes the cache holds per token per attention layer (B), from the leaves
the engine allocated: the ``kv_bytes_per_token_layer`` stat of the program's
``nxd.step.decode.dispatch`` spans in the traced window
(``modules/attention.cache_bytes_per_token_layer``). A latent (MLA) cache in
bf16 reads ``(d_latent + d_rope) * 2`` = 1152 at DeepSeek-V2's widths; 2176
would mean the latent is stored twice, 10240 materialised keys and values. A
program without the stat (the parent of the PR that added it): ``None``."""
from perfbench import program_spans

STAT = "kv_bytes_per_token_layer"


def read(run):
    values = program_spans.stat_values(run, program_spans.DISPATCH, STAT)
    return values[-1] if values else None
