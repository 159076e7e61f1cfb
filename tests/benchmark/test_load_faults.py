"""What makes a closed-loop window no measurement of its cell: the guard that
turns a run into ``correct: false``, on hand-made window records. An open
loop's stalls are the engine's own and stay in its numbers."""

import pytest

from perfbench import tape
from perfbench.runners import serve

OPEN = tape.load_traffic("lines_steady")
CLOSED = tape.load_traffic("chat_closed")


def _window(open_loop, backlog=0, occupancy=100.0, compiles=0, at_close=0):
    """``backlog``: queued on average over the window's last quarter, which is
    what is judged; ``at_close``: at the one instant the window closed."""
    return {"open_loop": open_loop, "backlog_last_quarter": backlog, "backlog_end": at_close,
            "slot_occupancy_pct": occupancy, "compiles_in_window": compiles}


@pytest.mark.parametrize("traffic, window", [
    (OPEN, _window(True, backlog=0.3, occupancy=40.0)),                        # below the knee slots idle
    (CLOSED, _window(False, backlog=CLOSED["overload_backlog"], occupancy=serve.MIN_CLOSED_OCCUPANCY_PCT)),
    # the engine preempted at its cursor's wall and compiled the way back: slow, and the program's own doing
    (OPEN, _window(True, backlog=24.2, at_close=11, occupancy=68.8, compiles=4)),
    # a stall that ends as the window closes: many queued at that instant, none on average
    (CLOSED, _window(False, backlog=0.2, at_close=3 * CLOSED["overload_backlog"])),
    (CLOSED, _window(False, backlog=None)),                                    # a window too short to average
])
def test_a_sound_window_has_no_fault(traffic, window):
    assert serve.load_faults(window, traffic) == []


@pytest.mark.parametrize("traffic, window, word", [
    (CLOSED, _window(False, backlog=CLOSED["overload_backlog"] + 0.1), "OVERLOADED"),
    (CLOSED, _window(False, backlog=11.0, at_close=15), "OVERLOADED"),  # the run that hit the row's end
    (CLOSED, _window(False, occupancy=92.0), "STARVED"),             # hit it mid-window and recovered
    (CLOSED, _window(False, occupancy=None), "STARVED"),             # not one decode step in the window
    (CLOSED, _window(False, compiles=2), "COMPILED"),
])
def test_a_window_that_was_not_served_at_its_load_is_named(traffic, window, word):
    faults = serve.load_faults(window, traffic)
    assert len(faults) == 1 and faults[0].startswith(word)
