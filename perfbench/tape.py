"""The one traffic generator: a stratified tape from a file of parameters.

A traffic mix is a data file (``perfbench/traffic/<name>.json``); this module
turns it and ``--seed`` into a tape of requests. The idea of a seeded tape is
the repo's own (``serving/traffic.py``), copied here with two changes: the
sizes are a deployment's, and the tape is STRATIFIED so that every seed
offers the same work.

* The multiset of (prompt length, answer length) pairs and the multiset of
  inter-arrival gaps are the distributions' own quantiles: the same numbers
  under every seed.
* Requests come in blocks of ``block`` requests. Every block holds the whole
  spread of lengths and of gaps; the seed permutes the pairs and the gaps
  inside each block (independently) and draws the token ids. So every few
  seconds of every run offer the same prefill and decode work, and no two
  seeds send the same sequence.
* No two prompts share a prefix: ids are drawn independently per request.

Pure numpy; nothing here imports JAX.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class TapeRequest:
    index: int
    t_due: float          # seconds after the window opens (0.0 in a closed loop)
    prompt: np.ndarray    # (p,) int32 token ids
    n_out: int            # answer length in tokens


def load_traffic(name: str, directory: Optional[str] = None) -> dict:
    """The parameters of traffic mix ``name``. A ``.json`` file under
    ``perfbench/traffic/``; a later PR adds a mix by adding a file."""
    path = os.path.join(directory or os.path.join(_HERE, "traffic"), f"{name}.json")
    with open(path) as f:
        return json.load(f)


def random_ids(rng, vocab_size: int, n: int) -> np.ndarray:
    """``n`` token ids, uniform over the vocabulary but for id 0 (padding)."""
    return rng.integers(1, vocab_size, size=n, dtype=np.int64).astype(np.int32)


def _quantiles(spec: dict, n: int) -> List[int]:
    """``n`` stratified integer draws of a log-normal length distribution:
    its quantiles at ``(i + 0.5) / n``, clipped to ``[min, max]``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = int(spec["min"]), int(spec["max"])
    median, sigma = float(spec["median"]), float(spec["sigma"])
    return [
        int(round(min(max(median * math.exp(sigma * NormalDist().inv_cdf((i + 0.5) / n)), lo), hi)))
        for i in range(n)
    ]


def _gap_quantiles(rate: float, n: int) -> List[float]:
    """``n`` stratified inter-arrival gaps of a Poisson process of ``rate``
    requests a second (exponential quantiles), scaled so that they add up to
    exactly ``n / rate``: a block always spans the same time."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def block_lengths(traffic: dict) -> List[tuple]:
    """The (prompt, answer) pairs of one block, before the seed orders them.
    The pairing is fixed (a stride through the answer quantiles that is
    coprime with the block), so the multiset of PAIRS is the same for every
    seed; a pair whose sum passes ``max_total`` gives up prompt tokens."""
    n = int(traffic["block"])
    prompts = _quantiles(traffic["prompt_len"], n)
    answers = _quantiles(traffic["answer_len"], n)
    stride = next(s for s in (7, 11, 13, 17, 19, 23, 1) if math.gcd(s, n) == 1)
    cap = traffic.get("max_total")
    pairs = []
    for i, p in enumerate(prompts):
        a = answers[(i * stride + 3) % n]
        if cap is not None and p + a > int(cap):
            p = int(cap) - a
        pairs.append((p, a))
    return pairs


def block_gaps(traffic: dict) -> List[float]:
    return _gap_quantiles(float(traffic["rate_rps"]), int(traffic["block"]))


def make_tape(traffic: dict, seed: int, *, vocab_size: int, seconds: float,
              max_requests: Optional[int] = None) -> List[TapeRequest]:
    """The tape of one run. Open loop (``"loop": "open"``): every request
    due inside ``[0, seconds)``, at stratified Poisson gaps. Closed loop:
    ``max_requests`` requests with no due time; the clients take them in
    order. Same seed, same bytes."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x7A9E]))
    pairs = block_lengths(traffic)
    n = len(pairs)
    open_loop = traffic["loop"] == "open"
    gaps = block_gaps(traffic) if open_loop else None
    tape: List[TapeRequest] = []
    t = 0.0
    while True:
        order = rng.permutation(n)
        gap_order = rng.permutation(n) if open_loop else None
        for j in range(n):
            if open_loop:
                t += gaps[gap_order[j]]
                if t >= seconds:
                    return tape
            elif max_requests is not None and len(tape) >= max_requests:
                return tape
            p, a = pairs[order[j]]
            tape.append(TapeRequest(len(tape), t if open_loop else 0.0, random_ids(rng, vocab_size, p), a))
