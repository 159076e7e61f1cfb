"""Operations and bytes of the two multi-head latent attention (MLA) kernels,
from shapes (the peaks table and ``roofline_share_pct`` are ``peaks.py``'s).

* absorbed decode: ``H`` query rows of ``d_c + d_r`` against ONE shared row
  a token (the latent ``c`` of ``d_c`` and the rotated key of ``d_r``); the
  values are the latent rows, so the cache is read once.
* materialised prefill: flash attention with q and k of ``d_qk = d_nope +
  d_rope`` and v of ``d_v``.

Needed work only: valid contexts, not the shared cursor's columns; each
prompt's own length, not its padded bucket; no padding of v to q's size.
"""

from __future__ import annotations


def mla_decode_cost(context_lens, *, num_q_heads: int, latent_dim: int, rope_dim: int,
                    act_bytes: int = 2, q_len: int = 1):
    """One absorbed-decode attention call over slots whose valid contexts
    are ``context_lens`` tokens (one layer). Bytes: each valid token's
    ``latent_dim + rope_dim`` values ONCE, plus the absorbed query read
    (``H * (d_c + d_r)``) and the latent output written (``H * d_c``).
    FLOPs: ``2 * ctx * H * ((d_c + d_r) + d_c)``: scores over ``d_c + d_r``
    channels, values over ``d_c``."""
    flops = nbytes = 0.0
    row = latent_dim + rope_dim
    for ctx in context_lens:
        ctx = int(ctx)
        flops += 2.0 * q_len * ctx * num_q_heads * (row + latent_dim)
        nbytes += ctx * row * act_bytes
        nbytes += q_len * num_q_heads * (row + latent_dim) * act_bytes
    return flops, nbytes


def mla_prefill_cost(seq: int, *, num_q_heads: int, qk_dim: int, v_dim: int,
                     act_bytes: int = 2):
    """One materialised causal flash forward over a prompt of ``seq`` tokens
    (one layer). FLOPs: ``2 * S^2 * H * (d_qk + d_v)``, halved under the
    causal mask. Bytes: q and k (``d_qk`` a head) and v read, o (``d_v``)
    written, once."""
    flops = 2.0 * seq * seq * num_q_heads * (qk_dim + v_dim) / 2.0
    nbytes = seq * num_q_heads * (2.0 * qk_dim + 2.0 * v_dim) * act_bytes
    return flops, nbytes
