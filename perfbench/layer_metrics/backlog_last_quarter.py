"""Requests the engine left queued, a time-weighted mean over the window's
last quarter (``stats.mean_left_queued``). A closed loop above its traffic
file's ``overload_backlog`` is no measurement; an open loop's is recorded:
large where the engine stalled or the rate is above the knee."""


def read(run):
    value = run.get("backlog_last_quarter")
    return None if value is None else float(value)
