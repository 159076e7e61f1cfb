"""Wall of the page dealing a decode-only step (ms): ``nxd.step.decode.pages``
(``_ensure_decode_pages``: the unmapped entries of both kinds' block tables, the
deal, the tables' upload), median over the ``nxd.step`` spans of the traced
window that hold a decode chunk and no prefill (the steps ``step_host_ms``
uses). 0.0 where the window holds such steps and none has the span, or holds
none; ``None`` for a program without ``nxd.program`` spans, or no trace."""
from perfbench import chunk_gaps


def read(run):
    return chunk_gaps.step_pages_ms(run)
