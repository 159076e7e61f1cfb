"""Seconds of set-up spent on the comparison with the plain reference."""


def read(run):
    total = run["spans"].total("reference_check")
    return total if total > 0 else None
