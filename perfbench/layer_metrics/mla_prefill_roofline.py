"""The flash kernel's share of its roofline in MLA's materialised prefill
(%), traced window: one causal forward with q/k of ``d_nope + d_rope`` and v
of ``d_v`` over each prompt's own length per layer
(``mla_costs.mla_prefill_cost``; padding to the bucket is not needed work),
against the attention kernel's time in the prefill programs. Bound: compute.
Only a latent-cache configuration has the geometry."""
from perfbench import mla_costs, peaks

MODULE = "jit_fn"          # the engine's prefill program
KERNEL = "attention"       # ``attn._cached_attention``: flash forward


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if "latent_dim" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    flops = nbytes = 0.0
    for r in run["clients"]:
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            f, b = mla_costs.mla_prefill_cost(
                r["prompt_len"], num_q_heads=g["num_q_heads"], qk_dim=g["head_dim"], v_dim=g["v_head_dim"])
            flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
