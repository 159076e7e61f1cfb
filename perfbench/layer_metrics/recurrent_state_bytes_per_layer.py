"""Bytes a slot's STATE holds per RECURRENT layer (B), from the leaves the
engine allocated: the ``slot_state_bytes_per_layer`` stat of the program's
``nxd.step.decode.dispatch`` spans in the traced window, read where the span
also carries ``recurrent_layers`` (a model whose linear-attention layers keep a
float32 state and their convolutions' taps a slot and no page:
``modules/attention.RecurrentStateCache``). Solar-Open2's 64 heads of 128 x 128
in float32 and 3 x 24,576 taps in bf16: 4,341,760 B
(``kda_costs.slot_state_bytes``). The same stat as ``slot_state_bytes_per_layer``
reads for a fixed per-slot state beside pages, under a name of its own because
that entry's cells are pinned by a test this PR may not edit. A program without
the stats: ``None``."""
from perfbench import program_spans

STAT = "slot_state_bytes_per_layer"


def read(run):
    values = [s[STAT] for _, _, s, _ in program_spans.spans(run, program_spans.DISPATCH)
              if STAT in s and "recurrent_layers" in s]
    return float(values[-1]) if values else None
