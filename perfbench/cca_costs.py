"""Operations and bytes of decode attention in a compressed latent (CCA,
ZAYA1's ``zaya`` as configured), from shapes (the peaks table and
``roofline_share_pct`` are ``peaks.py``'s).

A decode step of a slot in a layer reads the K and V of every token the slot
holds: ``2 * Hkv * D`` values a token, 1,024 B at 2 kv heads of 128 in bf16 (an
eighth of what 8 kv heads hold at the same hidden size), + q in and out;
``2 * ctx * H * (D + D)`` operations. Needed work only: valid contexts, not the
shared cursor's columns, not the rest of a fetched block of the walk, not a
tile's padding (a leaf held padded to 4,096 B a token would read at a quarter
of the kernel's own bandwidth here). So a kernel that does dense work reads
low and none can read over 100%. The convolutions and the per-slot state are
not the kernel's: their time is ``cca_conv_dev_share_pct``'s.
"""

from __future__ import annotations


def cca_decode_cost(context_lens, *, num_q_heads: int, num_kv_heads: int, head_dim: int,
                    act_bytes: int = 2):
    """One decode-attention call of ONE layer over slots whose valid contexts
    are ``context_lens`` (one query row a slot)."""
    flops = nbytes = 0.0
    for ctx in context_lens:
        flops += 2.0 * int(ctx) * num_q_heads * 2 * head_dim
        nbytes += int(ctx) * 2 * num_kv_heads * head_dim * act_bytes
        nbytes += 2 * num_q_heads * head_dim * act_bytes
    return flops, nbytes


def slot_state_bytes(*, num_q_heads: int, num_kv_heads: int, head_dim: int, act_bytes: int = 2) -> int:
    """Bytes a slot's state holds a layer: the packed pre-convolution q/k
    latent of its last token, the first convolution's output for it and the
    value half the next token's second kv head reads."""
    channels = (num_q_heads + num_kv_heads) * head_dim
    return (2 * channels + head_dim) * act_bytes
