"""The flash attention forward kernel's share of its roofline in prefill (%),
traced window. Needed work: one causal forward over each prompt's own length
per layer (padding to the bucket is not needed work). Bound: compute."""
from perfbench import peaks

MODULE = "jit_fn"          # the engine's prefill program
KERNEL = "attention"       # its attention kernel is flash forward


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    flops = nbytes = 0.0
    for r in run["clients"]:
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            f, b = peaks.flash_cost(1, r["prompt_len"], num_q_heads=g["num_q_heads"],
                                    num_kv_heads=g["num_kv_heads"], head_dim=g["head_dim"])
            flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
