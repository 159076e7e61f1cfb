"""The sparse LATENT decode kernel's share of its roofline (%), traced window:
``dsa_latent_costs.sparse_latent_decode_cost`` over every decode step a slot
ran in the window: the latent and rotated key of ``min(ctx, topk)`` tokens
(1152 B each at GLM-5's widths: what they HOLD, not the 2048-B tile they are
stored in) and nothing else of the cache, against the time of the kernel named
``dsa.attend`` in the decode chunk program. Bound: memory. The kernel fetches
a token a copy, so a low share says issue-bound, not bandwidth-bound. ``None``
for a program whose selected rows are not latents (no ``latent_dim`` beside
``index_topk`` in the geometry) or that has no such kernel."""
from perfbench import dsa_latent_costs, peaks

MODULE = "jit_chunk_fn"
KERNEL = "dsa.attend"


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if "index_topk" not in g or "latent_dim" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    contexts = [r["prompt_len"] + j for r in run["clients"]
                for j, stamp in enumerate(r.get("stamps", ())) if j >= 1 and lo <= stamp <= hi]
    if not contexts:
        return None
    flops, nbytes = dsa_latent_costs.sparse_latent_decode_cost(
        contexts, num_q_heads=g["num_q_heads"], latent_dim=g["latent_dim"], rope_dim=g["rope_dim"],
        topk=g["index_topk"])
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
