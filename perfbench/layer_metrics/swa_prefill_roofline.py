"""The prefill attention kernels' share of their roofline (%), traced window
(a window layer's banded flash forward, a full layer's flash forward):
``swa_costs.swa_prefill_cost`` of each prompt prefilled in the window (its
own length a layer): the operations of the pairs INSIDE the band (a window
layer ``sum_t min(t + 1, window)``, a full layer the causal triangle) against
the time of the kernels named ``attn.window`` and ``attn.full`` in the prefill
programs. The kernel multiplies whole tiles along the band's edges and the
bucket's padding, so the share reads under the MXU's own. Bound: compute.
``None`` for a program without window layers in its geometry."""
from perfbench import peaks, swa_costs

MODULE = "jit_fn"          # the engine's prefill program
KERNELS = ("attn.window", "attn.full")


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if any(n in k for n in KERNELS))
    if "window_layers" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    flops = nbytes = 0.0
    for r in run["clients"]:
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            f, b = swa_costs.layers_cost(swa_costs.swa_prefill_cost, g, r["prompt_len"])
            flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    share, _bound = peaks.roofline_share_pct(flops, nbytes, seconds, peaks.peaks_for(run["device_kind"]))
    return share
