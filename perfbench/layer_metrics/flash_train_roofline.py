"""The flash attention kernels' share of their roofline in the train step (%),
traced window: forward and backward over every sequence of every step, each
chip's share of the heads. The forward that remat runs again is in the kernel
time and not in the needed work. Bound: compute."""
from perfbench import peaks

# every Pallas kernel of the train step is a flash kernel (forward, dq, dk/dv)


def read(run):
    t, g = run["trace"], run["geometry"]
    seconds = sum(t["kernel_s"].values())
    steps = run.get("steps_in_trace")
    if not seconds or not steps:
        return None
    tp = run["chips"]
    fwd = peaks.flash_cost(run["batch"], run["seq"], num_q_heads=g["num_q_heads"] // tp,
                           num_kv_heads=max(g["num_kv_heads"] // tp, 1), head_dim=g["head_dim"])
    bwd = peaks.flash_backward_cost(run["batch"], run["seq"], num_q_heads=g["num_q_heads"] // tp,
                                    num_kv_heads=max(g["num_kv_heads"] // tp, 1), head_dim=g["head_dim"])
    flops = (fwd[0] + bwd[0]) * g["num_layers"] * steps
    nbytes = (fwd[1] + bwd[1]) * g["num_layers"] * steps
    share, _bound = peaks.roofline_share_pct(flops, nbytes, seconds, peaks.peaks_for(run["device_kind"]))
    return share
