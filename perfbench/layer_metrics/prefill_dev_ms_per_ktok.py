"""Device time of the prefill programs in the traced window per thousand
prompt tokens prefilled in it (ms). A request counts where its first token
(which the prefill yields) reached the client inside the traced window."""

MODULE = "jit_fn"   # the engine's per-bucket prefill program (serving/engine.py)


def read(run):
    t, c = run["trace"], run.get("counters", {})
    if "start" not in c or MODULE not in t["module_s"]:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    tokens = sum(r["prompt_len"] for r in run["clients"]
                 if r["t_first"] is not None and lo <= r["t_first"] <= hi)
    return 1e3 * t["module_s"][MODULE] / (tokens / 1e3) if tokens else None
