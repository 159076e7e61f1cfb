"""The program's own median time to first token (ms): ``submit()`` to the
stamp taken after the first token's readback, the ``ttft_us`` stat of the
``nxd.step.prefill`` spans in the traced window. It stands beside the
client's ``ttft_p50_ms``, which starts at the request's due time and covers
the whole window."""
from perfbench import program_spans, stats


def read(run):
    ttfts = program_spans.stat_values(run, program_spans.PREFILL, "ttft_us")
    p50 = stats.percentile(ttfts, 50)
    return None if p50 is None else p50 / 1e3
