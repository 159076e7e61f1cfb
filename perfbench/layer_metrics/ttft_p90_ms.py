"""90th percentile of the time from a request's DUE time to its first token
at the client (ms); a request without an answer counts as infinite. Not an
end-to-end metric: over the ~160 requests of a window it spread by a quarter
between seeds (PR 23), far past any bound; recorded, never judged."""
from perfbench import stats


def read(run):
    ttfts = run.get("ttfts")
    return stats.tail_with_missing(ttfts, 90) if ttfts else None
