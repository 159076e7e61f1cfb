"""The paged ABSORBED-decode kernel's share of its roofline (%), traced
window: ``mla_costs.mla_decode_cost`` over the valid context of every decode
step a slot ran in the window (contexts from the client's record, as
``paged_decode_roofline``), against the kernel's time in the decode chunk
program. Bound: memory at every context a decode step has (arithmetic
intensity about ``H`` times multi-head attention's, still under the chip's
ridge at 16 heads). Only a latent-cache configuration has the geometry."""
from perfbench import mla_costs, peaks

MODULE = "jit_chunk_fn"    # the engine's fused decode chunk
KERNEL = "attention"       # ``attn._cached_attention``; the experts' ragged-dot kernels share the program


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if "latent_dim" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    contexts = [r["prompt_len"] + j for r in run["clients"]
                for j, stamp in enumerate(r.get("stamps", ())) if j >= 1 and lo <= stamp <= hi]
    flops, nbytes = mla_costs.mla_decode_cost(
        contexts, num_q_heads=g["num_q_heads"], latent_dim=g["latent_dim"], rope_dim=g["rope_dim"])
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
