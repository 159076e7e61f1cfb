"""Operations and bytes of attention in a stack that mixes WINDOW layers (a
query reads its last ``window`` tokens) and full layers, grouped-query heads
(Trinity's ``afmoe`` as configured), from shapes (the peaks table and
``roofline_share_pct`` are ``peaks.py``'s).

* decode, a slot a layer a step: the K and V of the ``min(ctx, window or
  ctx)`` tokens the layer's kind lets the query read (``2 * Hkv * D`` values a
  token: 4096 B at 8 kv heads of 128 in bf16) + q in and out; ``2 * tokens * H
  * (D + D)`` operations.
* prefill, a prompt a layer: the pairs INSIDE the band, ``sum_t min(t + 1,
  window or t + 1)``, times ``2 * H * (D + D)``; q, k, v read and o written
  once.

Needed work only: valid contexts, not the shared cursor's columns nor the
pages a block of the walk fetches around the window; each prompt's own
length, not its padded bucket; the band, not the causal triangle. So a kernel
that does dense work reads low and none can read over 100%.
"""

from __future__ import annotations

from typing import Optional


def attended(ctx: int, window: Optional[int]) -> int:
    """Tokens a query with ``ctx`` tokens before and at it reads in a layer
    of window ``window`` (``None``: a full layer)."""
    return int(ctx) if window is None else min(int(ctx), int(window))


def swa_decode_cost(context_lens, *, num_q_heads: int, num_kv_heads: int, head_dim: int,
                    window: Optional[int], act_bytes: int = 2):
    """One decode-attention call of ONE layer over slots whose valid contexts
    are ``context_lens`` (one query row a slot)."""
    flops = nbytes = 0.0
    for ctx in context_lens:
        tokens = attended(ctx, window)
        flops += 2.0 * tokens * num_q_heads * 2 * head_dim
        nbytes += tokens * 2 * num_kv_heads * head_dim * act_bytes
        nbytes += 2 * num_q_heads * head_dim * act_bytes
    return flops, nbytes


def band_pairs(seq: int, window: Optional[int]) -> float:
    """``sum_t min(t + 1, window or t + 1)`` over a prompt of ``seq`` tokens."""
    seq = int(seq)
    full = seq if window is None else min(seq, int(window))
    return full * (full + 1) / 2.0 + (0.0 if window is None else max(seq - int(window), 0) * float(window))


def swa_prefill_cost(seq: int, *, num_q_heads: int, num_kv_heads: int, head_dim: int,
                     window: Optional[int], act_bytes: int = 2):
    """A prompt of ``seq`` tokens through ONE layer's prefill attention."""
    flops = 2.0 * num_q_heads * 2 * head_dim * band_pairs(seq, window)
    nbytes = seq * (2.0 * num_q_heads + 2.0 * num_kv_heads) * head_dim * act_bytes
    return flops, nbytes


def layers_cost(cost, g: dict, *args):
    """``cost`` summed over a geometry's layers of both kinds (``window_layers``
    of window ``window``, ``full_layers`` of none)."""
    heads = dict(num_q_heads=g["num_q_heads"], num_kv_heads=g["num_kv_heads"], head_dim=g["head_dim"])
    flops = nbytes = 0.0
    for layers, window in ((g["window_layers"], g["window"]), (g["full_layers"], None)):
        f, b = cost(*args, window=window, **heads)
        flops, nbytes = flops + layers * f, nbytes + layers * b
    return flops, nbytes
