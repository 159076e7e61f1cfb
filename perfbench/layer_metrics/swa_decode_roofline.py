"""The paged GQA decode kernel's share of its roofline in a stack of window
and full attention layers (%), traced window: ``swa_costs.swa_decode_cost``
over every decode step a slot ran in the window, a window layer reading
``min(ctx, window)`` tokens' K and V (4096 B each at Trinity's widths) and a
full layer ``ctx``, against the time of the kernels named ``attn.window`` and
``attn.full`` in the decode chunk program: the kernel that walks the blocks a
slot maps, and the copies of the write window's pages into the pool beside it
(called in the same scope: theirs is the smaller part). Bound: memory. What a
block of the walk fetches around the window (up to a block of 512 tokens at
its lower edge, unmapped pages inside a fetched block) is not needed work, so
the share reads under the kernel's own bandwidth. ``None`` for a program
without window layers in its geometry or without such kernels."""
from perfbench import peaks, swa_costs

MODULE = "jit_chunk_fn"
KERNELS = ("attn.window", "attn.full")


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run["geometry"]
    seconds = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if any(n in k for n in KERNELS))
    if "window_layers" not in g or "start" not in c or not seconds:
        return None
    lo, hi = c["start"]["t"], c["stop"]["t"]
    contexts = [r["prompt_len"] + j for r in run["clients"]
                for j, stamp in enumerate(r.get("stamps", ())) if j >= 1 and lo <= stamp <= hi]
    if not contexts:
        return None
    flops, nbytes = swa_costs.layers_cost(swa_costs.swa_decode_cost, g, contexts)
    share, _bound = peaks.roofline_share_pct(flops, nbytes, seconds, peaks.peaks_for(run["device_kind"]))
    return share
