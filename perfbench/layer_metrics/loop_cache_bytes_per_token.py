"""Bytes the cache really holds per token over ALL its nodes (B), from the
leaves the engine allocated: the ``kv_cache_nodes`` stat of the program's
``nxd.step.decode.dispatch`` spans in the traced window times their
``kv_bytes_per_token_layer`` (``modules/attention.cache_token_bytes``: the
bytes a node, the nodes). A stack run four times over 24 layers with a K/V
cache a pass holds 96 nodes of 16 + 16 heads of 128 in bf16: 786,432; a pass
that shares or drops its cache shows as fewer nodes (589,824 at 72), a padded
leaf as more bytes a node. A program whose spans do not count the nodes (the
parent of the PR that added them): ``None``."""
from perfbench import program_spans

NODES, BYTES = "kv_cache_nodes", "kv_bytes_per_token_layer"


def read(run):
    values = [s[NODES] * s[BYTES] for _, _, s, _ in program_spans.spans(run, program_spans.DISPATCH)
              if NODES in s and BYTES in s]
    return float(values[-1]) if values else None
