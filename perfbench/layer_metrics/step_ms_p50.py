"""Median host-clock time between finished train steps inside the window (ms)."""
from perfbench import stats


def read(run):
    return stats.percentile(run.get("step_ms", []), 50)
