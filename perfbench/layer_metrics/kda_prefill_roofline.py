"""The chunked delta-rule forward's share of its roofline (%), traced window:
``kda_costs.kda_prefill_cost`` of each prompt prefilled in the window (its own
length a linear layer: the recurrence's ``6 H d^2`` operations a valid token,
whatever the kernel's chunk size) against the time of the kernels named
``attn.kda.recur`` in the prefill programs. The chunked form spends MXU work on
the triangular system and VPU work on the pairs inside a sub-block to pass
over the state once a chunk, none of it needed work, so the share reads far
under the chip's peak; it is there to be moved. ``None`` for a program without
such kernels or a geometry without recurrent layers."""
from perfbench import kda_costs, peaks

MODULE = "jit_fn"          # the engine's prefill program
KERNEL = "attn.kda.recur"


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if not g.get("recurrent_layers") or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    flops = nbytes = 0.0
    for r in run["clients"]:
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            f, b = kda_costs.kda_prefill_cost(r["prompt_len"], heads=g["kda_heads"], head_dim=g["kda_head_dim"])
            flops, nbytes = flops + f * g["recurrent_layers"], nbytes + b * g["recurrent_layers"]
    if not flops:
        return None
    share, _bound = peaks.roofline_share_pct(flops, nbytes, seconds, peaks.peaks_for(run["device_kind"]))
    return share
