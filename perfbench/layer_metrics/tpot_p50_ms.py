"""Median over requests of each request's own time per output token (ms),
client stamps. Coarse for short answers (a step function of the prefills a
request sat through): a per-layer number, never a bound."""
from perfbench import stats


def read(run):
    return stats.percentile(stats.per_request_tpot_ms(run.get("records", [])), 50)
