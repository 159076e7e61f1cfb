"""Programs compiled (new jit signatures in the program ledger) inside the
window. Must be 0; anything else makes the run ``correct: false``."""


def read(run):
    value = run.get("compiles_in_window")
    return None if value is None else float(value)
