"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the window (GiB)."""


def read(run):
    peak = run["device"]["memory_peak_bytes"]
    return peak / 2**30 if peak else None
