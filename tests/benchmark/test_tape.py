"""The stratified tape: the same work under every seed, never the same order."""

import collections
import json
import os

import numpy as np
import pytest

from perfbench import tape

TRAFFIC_DIR = os.path.join(os.path.dirname(tape.__file__), "traffic")
OPEN = [n[:-5] for n in sorted(os.listdir(TRAFFIC_DIR))
        if n.endswith(".json") and not n.endswith(".sweep.json")
        and json.load(open(os.path.join(TRAFFIC_DIR, n))).get("loop") == "open"]
SERVE = [n[:-5] for n in sorted(os.listdir(TRAFFIC_DIR))
         if n.endswith(".json") and not n.endswith(".sweep.json")
         and json.load(open(os.path.join(TRAFFIC_DIR, n))).get("loop") in ("open", "closed")]


def _tape(name, seed, seconds=51.0, **over):
    traffic = dict(tape.load_traffic(name), **over)
    return traffic, tape.make_tape(traffic, seed, vocab_size=32000, seconds=seconds,
                                   max_requests=4 * traffic["block"])


@pytest.mark.parametrize("name", SERVE)
def test_same_seed_same_bytes(name):
    _, a = _tape(name, 3_000_000_001)
    _, b = _tape(name, 3_000_000_001)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.t_due == y.t_due and x.n_out == y.n_out
        assert x.prompt.tobytes() == y.prompt.tobytes()


@pytest.mark.parametrize("name", SERVE)
def test_other_seed_other_order_and_ids(name):
    _, a = _tape(name, 1)
    _, b = _tape(name, 2)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt.tobytes() != b[0].prompt.tobytes()


@pytest.mark.parametrize("name", SERVE)
def test_every_block_holds_the_whole_spread_under_every_seed(name):
    traffic = tape.load_traffic(name)
    n = traffic["block"]
    want = collections.Counter(tape.block_lengths(traffic))
    for seed in (0, 7, 2**31 + 5):
        _, t = _tape(name, seed)
        whole = len(t) // n
        assert whole >= 2
        for b in range(whole):
            got = collections.Counter((len(r.prompt), r.n_out) for r in t[b * n:(b + 1) * n])
            assert got == want


@pytest.mark.parametrize("name", OPEN)
def test_gaps_are_the_same_multiset_and_a_block_spans_the_same_time(name):
    traffic = tape.load_traffic(name)
    n = traffic["block"]
    want = sorted(tape.block_gaps(traffic))
    assert sum(want) == pytest.approx(n / traffic["rate_rps"])
    for seed in (0, 11):
        _, t = _tape(name, seed)
        dues = np.array([0.0] + [r.t_due for r in t])
        for b in range(len(t) // n):
            gaps = np.diff(dues[b * n:(b + 1) * n + 1])
            assert sorted(gaps) == pytest.approx(want)
        assert all(r.t_due < 51.0 for r in t)


@pytest.mark.parametrize("name", SERVE)
def test_lengths_stay_inside_their_limits(name):
    traffic = tape.load_traffic(name)
    for p, a in tape.block_lengths(traffic):
        assert traffic["prompt_len"]["min"] <= p <= traffic["prompt_len"]["max"]
        assert traffic["answer_len"]["min"] <= a <= traffic["answer_len"]["max"]
        assert p + a <= traffic["max_total"]


def test_no_two_prompts_share_a_prefix():
    _, t = _tape(OPEN[0], 5)
    assert len({r.prompt[:8].tobytes() for r in t}) == len(t)


# what the generator and the runners read; "what" and "*_why" are prose
READ = {
    "open": {"loop", "block", "prompt_len", "answer_len", "max_total", "rate_rps", "drain_cap_s"},
    "closed": {"loop", "block", "prompt_len", "answer_len", "max_total", "ramp_s", "overload_backlog"},
    "steps": {"loop", "batch_sequences", "sequence_length", "warmup_steps", "token_zipf"},
}


@pytest.mark.parametrize("name", [n[:-5] for n in sorted(os.listdir(TRAFFIC_DIR))
                                  if n.endswith(".json") and not n.endswith(".sweep.json")])
def test_a_traffic_file_holds_exactly_what_is_read(name):
    """No parameter for a cell that does not exist, and none left to a
    default in code: a file names every number of its mix."""
    traffic = tape.load_traffic(name)
    keys = {k for k in traffic if k != "what" and not k.endswith("_why")}
    assert keys == READ[traffic["loop"]]


def test_an_unknown_distribution_is_refused():
    traffic = tape.load_traffic(OPEN[0])
    traffic["prompt_len"] = dict(traffic["prompt_len"], dist="pareto")
    with pytest.raises(ValueError):
        tape.block_lengths(traffic)
