"""Pages the block tables map that lie in runs of adjacent pool pages (one
copy a run in the block-walking decode kernels) over the pages they map (%):
stats ``<kind>_pages_in_runs`` / ``<kind>_pages_mapped`` of
``nxd.step.decode.dispatch``, both kinds and the traced window summed. 0.0 where
the window's steps carry no such stat; ``None`` without ``nxd.step`` spans or a trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.pages_in_runs_share_pct(run)
