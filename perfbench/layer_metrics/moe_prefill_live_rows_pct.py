"""Rows the routed experts' dispatch KEPT of the rows a prefill's bucket gave
it (%), traced window: the stats ``moe_live_rows`` and ``moe_rows`` of the
program's ``nxd.step.prefill.first_token`` spans, which the expert layers sum
on the device (rows whose mask is True / rows of the bucket, over a prefill's
expert layers) and which ride the first token's readback. 100 x prompt tokens /
bucket where every expert layer is handed the prompt's row mask; 100 where none
is, and 100.0 where the traced window held no prefill. Counted by the program,
not reckoned from the tape. A program whose first-token spans carry no such
stat (the parent of the PR that added them; a model with no expert layer), or
a run without a trace: ``None``."""
from perfbench import program_spans


def read(run):
    if not program_spans.load(run):
        return None
    firsts = program_spans.spans(run, program_spans.FIRST_TOKEN)
    pairs = [(s["moe_live_rows"], s["moe_rows"]) for _, _, s, _ in firsts
             if "moe_live_rows" in s and "moe_rows" in s]
    if firsts and not pairs:
        return None
    rows = sum(float(n) for _, n in pairs)
    return 100.0 * sum(float(live) for live, _ in pairs) / rows if rows else 100.0
