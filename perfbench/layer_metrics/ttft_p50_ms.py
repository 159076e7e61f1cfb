"""Median time from a request's due time to its first token at the client (ms)."""
from perfbench import stats


def read(run):
    ttfts = run.get("ttfts")
    return stats.tail_with_missing(ttfts, 50) if ttfts else None
