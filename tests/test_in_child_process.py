"""``conftest.in_child_process``: what a case that runs in a child process may
die of. A helper that hid a failure would turn every case behind it green."""

import os
import sys

import pytest


def _count(path):
    runs = int(open(path).read()) + 1 if os.path.exists(path) else 1
    with open(path, "w") as f:
        f.write(str(runs))
    return runs


def _asserts_false(path):
    _count(path)
    assert 1 + 1 == 3, "the child's own assertion"


def _aborts(path, times):
    """Dies as XLA:CPU's rendezvous does (SIGABRT after "Termination timeout"
    on stderr) the first ``times`` runs."""
    if _count(path) <= times:
        print("rendezvous.cc: Termination timeout for `collective permute` exceeded", file=sys.stderr, flush=True)
        os.abort()


def test_an_assertion_in_the_child_fails_the_case_and_is_not_run_again(tmp_path, in_child_process):
    runs = str(tmp_path / "runs")
    with pytest.raises(pytest.fail.Exception, match="the child's own assertion"):
        in_child_process(__file__, "_asserts_false", runs)
    assert open(runs).read() == "1"


def test_a_rendezvous_abort_is_run_once_more(tmp_path, in_child_process):
    runs = str(tmp_path / "runs")
    with pytest.warns(UserWarning, match="running it once more"):
        in_child_process(__file__, "_aborts", runs, 1)
    assert open(runs).read() == "2"
