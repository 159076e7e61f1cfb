"""Device time of one decode step (ms): the decode chunk program's time on
the device in the traced window (``XLA Modules`` line) over the scan steps the
engine executed in it (its ``steps`` counter at the trace's two ends)."""

MODULE = "jit_chunk_fn"   # the engine's fused decode chunk (inference/generate.py)


def read(run):
    t, c = run["trace"], run.get("counters", {})
    if "start" not in c or MODULE not in t["module_s"]:
        return None
    steps = c["stop"]["steps"] - c["start"]["steps"]
    return 1e3 * t["module_s"][MODULE] / steps if steps else None
