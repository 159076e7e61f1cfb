"""Slots held per executed decode step over the engine's slots, inside the
window (%): the engine's own ``record_decode_chunk`` counters. A closed loop
under ``serve.MIN_CLOSED_OCCUPANCY_PCT`` is not a measurement."""


def read(run):
    return run.get("slot_occupancy_pct")
