"""The paged decode attention kernel's share of its roofline (%), traced window.

Needed work: every output token but a request's first is one decode step of
one slot, and that step's attention reads the slot's whole valid context once
per layer (``peaks.paged_decode_cost``). Contexts come from the client's
record (prompt length plus tokens so far), stamped per chunk, so the window's
edges are off by at most a chunk. Bound: memory (K/V bytes), at every context
length a decode step has."""
from perfbench import peaks

MODULE = "jit_chunk_fn"    # the engine's fused decode chunk
KERNEL = "attention"       # its attention kernel is the paged one (``attn._cached_attention``);
                           # an MoE model's ragged-dot kernels run in the same program


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    contexts = []
    for r in run["clients"]:
        for j, stamp in enumerate(r.get("stamps", ())):
            if j >= 1 and lo <= stamp <= hi:
                contexts.append(r["prompt_len"] + j)
    flops, nbytes = peaks.paged_decode_cost(
        contexts, num_q_heads=g["num_q_heads"], num_kv_heads=g["num_kv_heads"], head_dim=g["head_dim"])
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
