"""Share of the traced window in which a collective op held a device's op
line, so that no compute ran on it (%), averaged over the chips."""


def read(run):
    t = run["trace"]
    return 100.0 * t["collective_exposed_s"] / t["window_s"] if t["devices"] else None
