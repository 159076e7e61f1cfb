"""The prefill attention's share of its roofline under the learned mask, for
latent attention in its materialised form (%), traced window:
``dsa_latent_costs.sparse_latent_prefill_cost`` of each prompt prefilled in
the window (its own length per layer): the operations of the SELECTED pairs
only (q and k of ``head_dim``, v of ``v_head_dim``, every head) plus the index
scores of all causal pairs, against the time of the kernels named
``dsa.attend`` (the byte-masked flash forward) and ``dsa.score`` (index
scores, thresholds and the byte mask) in the prefill programs. The flash
kernel multiplies every causal tile and masks, so the share is low by
construction and cannot pass 100%. Bound: compute. ``None`` for a program
whose selected rows are not latents."""
from perfbench import dsa_latent_costs, peaks

MODULE = "jit_fn"          # the engine's prefill program
KERNELS = ("dsa.attend", "dsa.score")


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if any(name in k for name in KERNELS))
    if "index_topk" not in g or "latent_dim" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    flops = nbytes = 0.0
    for r in run["clients"]:
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            f, b = dsa_latent_costs.sparse_latent_prefill_cost(
                r["prompt_len"], num_q_heads=g["num_q_heads"], qk_dim=g["head_dim"], v_dim=g["v_head_dim"],
                index_heads=g["index_heads"], index_dim=g["index_dim"], topk=g["index_topk"])
            flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
