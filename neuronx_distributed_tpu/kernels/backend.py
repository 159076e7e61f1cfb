"""How the Pallas kernels are run, and how ``"auto"`` picks one.

A kernel is compiled by Mosaic for the TPU. It is interpreted only where a
test asks for that: through a kernel entry's ``interpret=`` argument, or
through :data:`INTERPRET`, which ``tests/conftest.py`` sets for the CPU test
session and nothing else sets. Off the TPU an uninterpreted kernel fails in
JAX's own lowering ("Only interpret mode is supported on CPU backend") — a
requested kernel never turns into a reference implementation silently.

``"auto"`` may still choose by platform (:func:`resolve_attention_impl`);
the engine and the trainer record what it resolved to in their program
ledger (``ProgramLedger.resolved``), so a run can assert which path it took.
"""

from __future__ import annotations

from typing import Optional

import jax

# tests/conftest.py sets this True; a process that is not a test leaves it
# False and therefore never interprets a kernel.
INTERPRET = False


def interpret_mode(interpret: Optional[bool]) -> bool:
    """A kernel entry's ``interpret=`` argument, defaulted from
    :data:`INTERPRET` (never from the platform)."""
    return INTERPRET if interpret is None else bool(interpret)


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def resolve_attention_impl(impl: str, cp: int = 1) -> str:
    """``"auto"`` → ring attention when the sequence is sharded over
    ``cp`` > 1 devices, else the Pallas flash kernel on the TPU and the XLA
    einsum elsewhere; every other name passes through. The one copy of the
    choice shared by the dense, ring and Ulysses dispatchers (the latter
    two ask with ``cp=1``: which engine runs INSIDE the ring)."""
    if impl != "auto":
        return impl
    if cp > 1:
        return "ring"
    return "flash" if on_tpu() else "xla"
