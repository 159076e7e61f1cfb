"""The one-token state update's share of its roofline (%), traced window:
``kda_costs.kda_decode_cost`` over every decode step a slot ran in the window
(each linear layer reads and writes the slot's float32 state once: 8 MiB at 64
heads of 128) against the time of the kernels named ``attn.kda.recur`` in the
decode chunk program. Bound: memory. A slot that took no token in a step is
not needed work (the kernel still passes its state through), so the share
reads under the kernel's own bandwidth where slots idle. ``None`` for a program
without such kernels or a geometry without recurrent layers."""
from perfbench import kda_costs, peaks

MODULE = "jit_chunk_fn"
KERNEL = "attn.kda.recur"


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if not g.get("recurrent_layers") or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    steps = sum(1 for r in run["clients"]
                for j, stamp in enumerate(r.get("stamps", ())) if j >= 1 and lo <= stamp <= hi)
    if not steps:
        return None
    flops, nbytes = kda_costs.kda_decode_cost(steps, heads=g["kda_heads"], head_dim=g["kda_head_dim"])
    share, _bound = peaks.roofline_share_pct(
        flops * g["recurrent_layers"], nbytes * g["recurrent_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
