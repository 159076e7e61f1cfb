"""The looped stack's weight reads against the HBM peak in a decode step (%),
traced window: ``loop_costs.loop_decode_cost`` over the decode steps the
engine executed in the window (its ``steps`` counter at the trace's two ends:
every weight of the stack once a PASS) against the decode chunk program's
device time (``XLA Modules`` line) LESS its attention kernels' (those named
``attn.full``: the walk and the window pages' copies, which have a roofline of
their own, ``swa_decode_roofline``). Bound: memory. No kernel: these are XLA's
own matmuls at two rows, and XLA streams their weights through asynchronous
slices that carry no scope (``slice-done``: a fifth of the step), so the time
is taken from the program whole and not from ``loop.pass``'s ops: the head,
the sampler, rotary and the window gather are in the time and not in the
bytes, so the share reads a little low and cannot pass 100. It says how near a
step's layer bodies come to streaming their weights. ``None`` for a geometry
without passes (no other family's has them)."""
from perfbench import loop_costs, peaks

MODULE = "jit_chunk_fn"
KERNEL = "attn.full"


def read(run):
    t, c, g = run["trace"], run.get("counters", {}), run.get("geometry", {})
    if not g.get("passes") or "start" not in c or MODULE not in t.get("module_s", {}):
        return None
    attention = sum(v for k, v in t["kernel_s_by_module"].get(MODULE, {}).items() if KERNEL in k)
    seconds = t["module_s"][MODULE] - attention
    steps = c["stop"]["steps"] - c["start"]["steps"]
    if seconds <= 0 or steps <= 0:
        return None
    flops, nbytes = loop_costs.loop_decode_cost(steps, run["num_slots"], g)
    share, _bound = peaks.roofline_share_pct(flops, nbytes, seconds, peaks.peaks_for(run["device_kind"]))
    return share
