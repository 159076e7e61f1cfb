"""The paged index-score kernel's share of its roofline (%), traced window:
``dsa_costs.index_decode_cost`` over the valid context of every decode step a
slot ran in the window (contexts from the client's record, as
``paged_decode_roofline``): each valid token's 128 B index key once a layer a
step, against the time of the kernel named ``dsa.score`` in the decode chunk
program. Bound: memory. Only a configuration with an indexer has the
geometry."""
from perfbench import dsa_costs, peaks

MODULE = "jit_chunk_fn"    # the engine's fused decode chunk
KERNEL = "dsa.score"       # the scope the kernel is called in


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if "index_dim" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    contexts = [r["prompt_len"] + j for r in run["clients"]
                for j, stamp in enumerate(r.get("stamps", ())) if j >= 1 and lo <= stamp <= hi]
    if not contexts:
        return None
    flops, nbytes = dsa_costs.index_decode_cost(
        contexts, index_heads=g["index_heads"], index_dim=g["index_dim"])
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
