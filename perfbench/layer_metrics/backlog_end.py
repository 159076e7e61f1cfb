"""Requests still queued (not admitted) at the instant the window closed.
Recorded, not judged: see ``backlog_last_quarter``."""


def read(run):
    value = run.get("backlog_end")
    return None if value is None else float(value)
