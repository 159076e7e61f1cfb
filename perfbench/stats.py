"""Metric arithmetic of the benchmark: pure functions of client-side stamps.

Kept here, under the benchmark's own directory, so that no later PR can
change how a number is computed. Nothing here imports JAX.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks (numpy's default), ``None`` on an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo or xs[lo] == xs[hi]:
        return float(xs[lo])
    if math.isinf(xs[hi]):
        return math.inf       # between an answer and none: no limit is met
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def highest_percentile_with_ten_beyond(n: int) -> Optional[int]:
    """The highest of 50/90/95/99 that leaves at least ten samples beyond it
    in a sample of ``n`` (choosing-metrics, section 1); ``None`` under 20."""
    best = None
    for q in (50, 90, 95, 99):
        if n * (100 - q) / 100.0 >= 10:
            best = q
    return best


def tail_with_missing(latencies: Iterable[Optional[float]], q: float) -> Optional[float]:
    """Percentile of a latency sample in which ``None`` marks a request that
    failed, was refused or never answered: it counts as slower than any
    limit (``inf``), so a tail cannot improve by dropping requests."""
    xs = [math.inf if v is None else float(v) for v in latencies]
    return percentile(xs, q)


def tpot_mean_ms(requests: Iterable[dict]) -> Optional[float]:
    """Token-weighted time per output token, in ms: the sum over requests of
    ``t_last - t_first`` divided by the sum of ``n_out - 1`` gaps. A request
    of one token has no gap and adds nothing to either sum."""
    span = 0.0
    gaps = 0
    for r in requests:
        n = int(r["n_out"])
        if n < 2:
            continue
        span += float(r["t_last"]) - float(r["t_first"])
        gaps += n - 1
    if gaps == 0:
        return None
    return 1e3 * span / gaps


def per_request_tpot_ms(requests: Iterable[dict]) -> List[float]:
    """Each request's own ``(t_last - t_first) / (n_out - 1)`` in ms."""
    return [
        1e3 * (float(r["t_last"]) - float(r["t_first"])) / (int(r["n_out"]) - 1)
        for r in requests
        if int(r["n_out"]) >= 2
    ]


def ttft_ms(requests: Iterable[dict]) -> List[Optional[float]]:
    """Time from when each request was DUE (not from when the generator got
    round to sending it) to its first token at the client, in ms; ``None``
    for a request that produced no token."""
    out: List[Optional[float]] = []
    for r in requests:
        t_first = r.get("t_first")
        out.append(None if t_first is None else 1e3 * (float(t_first) - float(r["t_due"])))
    return out


def tokens_in_window(stamps: Iterable[float], t0: float, t1: float) -> int:
    """How many token stamps fall inside ``[t0, t1]``."""
    return sum(1 for t in stamps if t0 <= t <= t1)


def mean_left_queued(rows: Sequence[tuple], lo: float, hi: float) -> Optional[float]:
    """Time-weighted mean, over ``[lo, hi]``, of the requests the engine left
    queued. ``rows`` are ``(t0, t1, left)`` per engine step in time order:
    ``left`` requests were still waiting when the step ended at ``t1``, and
    that holds until the next step ends (an engine that left any takes its
    next step at once; one that left none may idle, at depth 0 all the same).
    A queue that one stall fills for a second or two barely moves this; one
    that grows all through a run is large over its whole last part. ``None``
    where there is no time to average over."""
    if not hi > lo:
        return None
    area = 0.0
    for i, (_t0, t1, left) in enumerate(rows):
        until = rows[i + 1][1] if i + 1 < len(rows) else hi
        a, b = max(t1, lo), min(until, hi)
        if b > a:
            area += left * (b - a)
    return area / (hi - lo)
