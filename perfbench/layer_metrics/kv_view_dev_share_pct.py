"""Device time of the paged path's K/V view over busy time, traced window (%):
self time of the ops under the named scope ``kv_view`` (the gather of the
logical slots x row view from the page pool and its write-back). Pieces the
TPU compiler re-creates under its own ``op_name`` (an expanded gather's) are
not in it: ``program_spans``' docstring."""
from perfbench import program_spans

SCOPE = "kv_view"


def read(run):
    return program_spans.scope_share_pct(run, SCOPE)
