"""Rows the HELD experts computed a decode step an expert layer, traced
window: the stats ``held_rows`` (slots routed to an expert this chip holds,
summed on the device over the chunk's steps and expert layers) and ``steps``
of the program's ``nxd.step.decode.readback`` spans. Each decoding slot routes
to ``top_k`` of the router's outputs, of which this chip holds a share: 8
slots x 8 x 8 / 256 = 2.0 expected for GLM-5 cut to 8 of 256 experts, where a
deployment's 32 x 8 slots would feed them 64. A program whose expert layers
hold every expert has no such stat: ``None``."""
from perfbench import program_spans


def read(run):
    layers = run["geometry"].get("expert_layers")
    pairs = [(s["held_rows"], s["steps"]) for _, _, s, _ in program_spans.spans(run, program_spans.READBACK)
             if "held_rows" in s and "steps" in s]
    steps = sum(float(n) for _, n in pairs)
    if not layers or not steps:
        return None
    return sum(float(h) for h, _ in pairs) / (steps * layers)
