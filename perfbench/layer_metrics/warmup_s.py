"""Seconds of set-up spent sending one block of the tape through the engine
(serve) or in the first, compiling steps (train): the benchmark's own span."""


def read(run):
    total = run["spans"].total("warmup")
    return total if total > 0 else None
