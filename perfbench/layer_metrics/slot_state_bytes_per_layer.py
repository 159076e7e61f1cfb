"""Bytes a slot's STATE holds per layer beside its pages (B), from the leaves
the engine allocated: the ``slot_state_bytes_per_layer`` stat of the program's
``nxd.step.decode.dispatch`` spans in the traced window
(``modules/attention.slot_state_bytes_per_layer``). ZAYA1-8B's layers keep the
packed pre-convolution q/k latent of a slot's last token (1,280 values), the
first convolution's output for it (1,280) and the value half the next token's
second kv head reads (128): 5,376 B in bf16
(``cca_costs.slot_state_bytes``). A program without the stat (no per-slot
state, or the parent of the PR that added it): ``None``."""
from perfbench import program_spans

STAT = "slot_state_bytes_per_layer"


def read(run):
    values = program_spans.stat_values(run, program_spans.DISPATCH, STAT)
    return values[-1] if values else None
