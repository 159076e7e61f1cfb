"""Operations and bytes of the gated delta-rule recurrence (KDA, Solar Open 2's
``solar_open2`` as configured), from shapes (the peaks table and
``roofline_share_pct`` are ``peaks.py``'s). ``H`` heads, a state of ``d x d``
float32 a head.

* decode, a slot a layer a step: the state once in and once out, ``2 * H * d *
  d * 4`` B (8 MiB at 64 heads of 128), + q, k, v, g and beta in and o out;
  ``6 * H * d * d`` operations: the decay and the read ``S'^T k`` (a multiply
  each, an add), the rank-one update (a multiply, an add), the query read (a
  multiply, an add).
* prefill, a prompt a layer: the same ``6 * H * d * d`` operations a VALID
  token (the recurrence's own count, whatever the chunk size: a chunked form
  does more arithmetic on the MXU to do it in fewer passes over the state, and
  none of that is needed work); q, k, v, g, beta read and o written a token,
  the state written once.

Needed work only, independent of the implementation's chunk size, of the
bucket's padding and of slots that take no token: no kernel can read over
100%. Both are bound by memory on a v5e: a decode step at 0.75 operations a
byte, a prompt at ``6 d / 12`` = 64 operations a byte of its vectors (the
chip's balance is 240).
"""

from __future__ import annotations

STATE_BYTES = 4      # the state is float32 whatever the activations are


def slot_state_bytes(*, heads: int, head_dim: int, taps: int, act_bytes: int = 2) -> int:
    """Bytes a slot's state holds a linear layer: the float32 state of every
    head and the last ``taps`` inputs of the q, k and v convolutions."""
    return heads * head_dim * head_dim * STATE_BYTES + taps * 3 * heads * head_dim * act_bytes


def token_vector_bytes(heads: int, head_dim: int, act_bytes: int = 2) -> float:
    """q, k, v in and o out in the activations' dtype, g (float32 a channel)
    and beta (float32 a head), one token a layer."""
    return heads * head_dim * (4 * act_bytes + 4) + heads * 4


def kda_decode_cost(slots: int, *, heads: int, head_dim: int, act_bytes: int = 2):
    """One decode step of ONE layer over ``slots`` slots that take a token."""
    flops = 6.0 * slots * heads * head_dim * head_dim
    nbytes = slots * (2.0 * heads * head_dim * head_dim * STATE_BYTES + token_vector_bytes(heads, head_dim, act_bytes))
    return flops, nbytes


def kda_prefill_cost(seq: int, *, heads: int, head_dim: int, act_bytes: int = 2):
    """A prompt of ``seq`` tokens through ONE layer's recurrence, from a zero
    state."""
    flops = 6.0 * int(seq) * heads * head_dim * head_dim
    nbytes = int(seq) * token_vector_bytes(heads, head_dim, act_bytes) + heads * head_dim * head_dim * STATE_BYTES
    return flops, nbytes
