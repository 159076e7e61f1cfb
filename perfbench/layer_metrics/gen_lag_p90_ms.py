"""How late the generator sent a request, against when it was due (ms, p90).
Entry point layer; a starved generator must not read as a fast server."""
from perfbench import stats


def read(run):
    return stats.percentile(run.get("lags_ms", []), 90)
