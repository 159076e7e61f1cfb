"""Median wall of an ``engine.step()`` that admitted nothing and decoded one
chunk, inside the window (ms): the benchmark's span around ``step()``."""
from perfbench import stats


def read(run):
    lo, hi = run["window"]
    walls = [1e3 * (t1 - t0) for t0, t1, admitted, decoded in run.get("steps", [])
             if admitted == 0 and decoded > 0 and lo <= t0 and t1 <= hi]
    return stats.percentile(walls, 50)
