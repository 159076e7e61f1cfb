"""``moe_block_dev_share_pct`` for a cell whose end-to-end metric is
``tpot_mean_ms``: device time of the whole sparse block over busy time,
traced window (%): the ops under the scope ``moe`` (router, dispatch, expert
activations, combine, and the SHARED experts' ``moe.shared``) plus the routed
experts' grouped matmuls, which the TPU compiler re-creates as ``ragged-dot``
kernels without a path."""
from perfbench import program_spans

SCOPE = "moe"
PATHLESS = ("ragged-dot",)


def read(run):
    return program_spans.scope_share_pct(run, SCOPE, also_ops=PATHLESS)
