"""Pages the window kind's block table maps over what ONE table would map for
the window layers (%), traced window: the stats ``window_pages_mapped`` and
``full_pages_mapped`` of the program's ``nxd.step.decode.dispatch`` spans
(host arithmetic from the two tables), summed over the window. Under one table
a window layer holds every page the full layer holds; with a table of its own
it holds the window's (256 pages of 16 tokens at a window of 4096, + the
chunk's write window). 100% would be no page freed; ~45% at contexts of
3k-17k. A program without the stats (one kind of layer, or the parent of the
PR that added them): ``None``."""
from perfbench import program_spans


def read(run):
    window = program_spans.stat_values(run, program_spans.DISPATCH, "window_pages_mapped")
    full = program_spans.stat_values(run, program_spans.DISPATCH, "full_pages_mapped")
    if not window or not full or not sum(full):
        return None
    return 100.0 * sum(window) / sum(full)
