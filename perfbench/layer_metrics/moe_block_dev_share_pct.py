"""Device time of the whole sparse block over busy time, traced window (%):
self time of the ops under the scope ``moe`` (router, dispatch, expert
activations, combine) plus the experts' grouped matmuls, which the TPU
compiler re-creates as ``ragged-dot`` kernels without a path. Stands beside
``moe_dev_share_pct``, which counts the grouped matmuls alone."""
from perfbench import program_spans

SCOPE = "moe"
PATHLESS = ("ragged-dot",)


def read(run):
    return program_spans.scope_share_pct(run, SCOPE, also_ops=PATHLESS)
