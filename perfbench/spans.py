"""The benchmark's own spans around its calls into each layer.

Each span is stamped on the host clock (always) and written into the
profiler's trace as a ``jax.profiler.TraceAnnotation`` (seen only while a
trace is on), so that the reduction can say what the host was doing during a
device idle gap. Spans stay in memory until the run ends.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple


class Spans:
    def __init__(self):
        self.rows: Dict[str, List[Tuple[float, float]]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.rows.setdefault(name, []).append((t0, time.perf_counter()))

    def add(self, name: str, t0: float, t1: float) -> None:
        self.rows.setdefault(name, []).append((t0, t1))

    def total(self, name: str) -> float:
        return sum(b - a for a, b in self.rows.get(name, []))
