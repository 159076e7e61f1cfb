"""Share of the traced window in which a prefill ran while slots were
decoding (%): the summed durations of the ``nxd.step.prefill`` spans whose
``decoding_slots`` stat is above 0. Every such prefill holds every decoding
slot's next chunk back."""
from perfbench import program_spans


def read(run):
    window = program_spans.window_ns(run)
    prefills = program_spans.spans(run, program_spans.PREFILL)
    if not window or not program_spans.spans(run, program_spans.STEP):
        return None
    stalled = sum(b - a for a, b, s, _ in prefills if int(s.get("decoding_slots", 0)) > 0)
    return 100.0 * stalled / window
