"""Bytes the cache holds per token per attention layer (B), from the leaves
the engine allocated, for a configuration with an indexer: the
``kv_bytes_per_token_layer`` stat of the program's ``nxd.step.decode.dispatch``
spans in the traced window (``modules/attention.cache_bytes_per_token_layer``):
K and V of 4 heads of 128 and ONE index key of 64 in bf16 read 2176; 2304
would mean the index key is padded to 128 lanes, 2048 that it is not cached.
``None`` for a program without an indexer (no ``selected_tokens`` stat)."""
from perfbench import program_spans

STAT = "kv_bytes_per_token_layer"


def read(run):
    if not program_spans.stat_values(run, program_spans.DISPATCH, "selected_tokens"):
        return None
    values = program_spans.stat_values(run, program_spans.DISPATCH, STAT)
    return values[-1] if values else None
