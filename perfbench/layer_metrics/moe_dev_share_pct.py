"""Device time of the expert MLPs' grouped matmuls over busy time, traced
window (%). The trace names device ops by their HLO name, not by the module
that made them; the experts' matmuls are the program's only ``ragged-dot``
ops, so that name finds them. The router, the sort by expert and the combine
are not in it: a named scope on the expert MLP (the ``tracing`` issue) would."""

OP = "ragged-dot"


def read(run):
    t = run["trace"]
    hit = sum(v for k, v in t["op_s"].items() if k.startswith(OP))
    return 100.0 * hit / t["busy_s"] if hit else None
