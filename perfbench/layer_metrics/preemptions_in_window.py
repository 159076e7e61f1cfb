"""Requests the engine preempted (cursor or page wall) inside the window."""


def read(run):
    c = run.get("counters")
    return None if not c else float(c["after"]["preemptions"] - c["before"]["preemptions"])
