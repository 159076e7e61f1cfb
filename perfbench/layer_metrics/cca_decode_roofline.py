"""The paged GQA decode kernel's share of its roofline under compressed-latent
attention (%), traced window: ``cca_costs.cca_decode_cost`` over every decode
step a slot ran in the window (each layer reads the slot's valid context's K
and V, 1,024 B a token at ZAYA1-8B's widths) against the time of the kernels
named ``attn.cca.attend`` in the decode chunk program: the kernel that walks
the blocks a slot maps, and the copies of the write window's pages into the
pool beside it (called in the same scope: theirs is the smaller part). Bound:
memory. What a block of the walk fetches past a slot's context (up to a block
of 512 tokens, gap columns and unmapped pages inside a fetched block) is not
needed work, so the share reads under the kernel's own bandwidth. ``None`` for
a program without such kernels or a geometry without the convolutions'
channels."""
from perfbench import cca_costs, peaks

MODULE = "jit_chunk_fn"
KERNEL = "attn.cca.attend"


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    if "cca_conv_channels" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    contexts = [r["prompt_len"] + j for r in run["clients"]
                for j, stamp in enumerate(r.get("stamps", ())) if j >= 1 and lo <= stamp <= hi]
    if not contexts:
        return None
    flops, nbytes = cca_costs.cca_decode_cost(
        contexts, num_q_heads=g["num_q_heads"], num_kv_heads=g["num_kv_heads"], head_dim=g["head_dim"])
    share, _bound = peaks.roofline_share_pct(
        flops * g["num_layers"], nbytes * g["num_layers"], seconds, peaks.peaks_for(run["device_kind"]))
    return share
