"""The prefill attention's share of its roofline under the learned mask (%),
traced window: ``dsa_costs.sparse_prefill_cost`` of each prompt prefilled in
the window (its own length per layer): the operations of the SELECTED pairs
only plus the index scores of all causal pairs, against the time of the
kernels named ``dsa.attend`` (the byte-masked flash forward) and ``dsa.score``
(index scores, thresholds and the byte mask) in the prefill programs. The
flash kernel multiplies every causal tile and masks, so the share is low by
construction (about a third of a dense kernel's at a 12k prompt) and cannot
pass 100%. Bound: compute. Only a configuration with an indexer has the
geometry."""
from perfbench import dsa_costs, peaks

MODULE = "jit_fn"          # the engine's prefill program
KERNELS = ("dsa.attend", "dsa.score")


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if any(name in k for name in KERNELS))
    if "index_topk" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    flops = nbytes = 0.0
    for r in run["clients"]:
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            f, b = dsa_costs.sparse_prefill_cost(
                r["prompt_len"], num_q_heads=g["num_q_heads"], num_kv_heads=g["num_kv_heads"],
                head_dim=g["head_dim"], index_heads=g["index_heads"], index_dim=g["index_dim"],
                topk=g["index_topk"])
            flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
