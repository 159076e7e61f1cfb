"""Pallas kernels' device time over the device's busy time, traced window (%)."""


def read(run):
    t = run["trace"]
    return 100.0 * sum(t["kernel_s"].values()) / t["busy_s"] if t["busy_s"] else None
