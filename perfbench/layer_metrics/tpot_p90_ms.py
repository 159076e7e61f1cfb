"""90th percentile over requests of each request's own time per output token (ms)."""
from perfbench import stats


def read(run):
    return stats.percentile(stats.per_request_tpot_ms(run.get("records", [])), 90)
