"""Operations and bytes of the kernels of attention under a learned
sparse-attention indexer (DeepSeek-Sparse-Attention's, as Keye-VL-2.0
configures it), from shapes (the peaks table and ``roofline_share_pct`` are
``peaks.py``'s).

* index scores, decode: ``H_i`` index queries of ``d_i`` against ONE index key
  a token, a weighted sum of their ``relu``.
* sparse attention, decode: a slot's ``min(ctx, topk)`` selected tokens, K and
  V of ``Hkv`` heads of ``D``.
* prefill: attention over the SELECTED pairs only, plus the index scores of
  every causal pair: a kernel that multiplies every causal tile and masks
  (dense work, sparse result) reads low by construction, and none can read
  over 100%.

Needed work only: valid contexts, not the shared cursor's columns; each
prompt's own length, not its padded bucket.
"""

from __future__ import annotations


def index_decode_cost(context_lens, *, index_heads: int, index_dim: int, act_bytes: int = 2):
    """One index-score call over slots whose valid contexts are
    ``context_lens`` (one layer, one query row a slot). Bytes: each valid
    token's index key (``d_i`` values) ONCE, the queries (``H_i * d_i``) and
    weights (``H_i``) read, a float32 score a token written. FLOPs: ``2 * ctx
    * H_i * d_i``."""
    flops = nbytes = 0.0
    for ctx in context_lens:
        ctx = int(ctx)
        flops += 2.0 * ctx * index_heads * index_dim
        nbytes += ctx * index_dim * act_bytes + ctx * 4
        nbytes += index_heads * (index_dim + 1) * act_bytes
    return flops, nbytes


def sparse_decode_cost(context_lens, *, num_q_heads: int, num_kv_heads: int, head_dim: int,
                       topk: int, act_bytes: int = 2):
    """One sparse decode-attention call (one layer, one query row a slot):
    each slot reads the K and V of its ``min(ctx, topk)`` selected tokens
    (``2 * Hkv * D`` values a token) and nothing else of the cache, plus q in
    and out. FLOPs: ``2 * min(ctx, topk) * H * (D + D)``."""
    flops = nbytes = 0.0
    for ctx in context_lens:
        kept = min(int(ctx), int(topk))
        flops += 2.0 * kept * num_q_heads * 2 * head_dim
        nbytes += kept * 2 * num_kv_heads * head_dim * act_bytes
        nbytes += 2 * num_q_heads * head_dim * act_bytes
    return flops, nbytes


def selected_pairs(seq: int, topk: int) -> float:
    """``sum_t min(t + 1, topk)`` over a prompt of ``seq`` tokens."""
    seq, topk = int(seq), int(topk)
    full = min(seq, topk)
    return full * (full + 1) / 2.0 + max(seq - topk, 0) * float(topk)


def sparse_prefill_cost(seq: int, *, num_q_heads: int, num_kv_heads: int, head_dim: int,
                        index_heads: int, index_dim: int, topk: int, act_bytes: int = 2):
    """A prompt of ``seq`` tokens through one layer's prefill attention:
    FLOPs of the selected pairs, ``2 * H * (D + D) * sum_t min(t + 1, topk)``,
    plus the index scores of all causal pairs, ``2 * H_i * d_i * S (S + 1) /
    2``. Bytes: q and o (``H * D``), k and v (``Hkv * D``), the index queries
    and keys, once."""
    flops = 2.0 * num_q_heads * 2 * head_dim * selected_pairs(seq, topk)
    flops += 2.0 * index_heads * index_dim * seq * (seq + 1) / 2.0
    nbytes = seq * (2.0 * num_q_heads + 2.0 * num_kv_heads) * head_dim * act_bytes
    nbytes += seq * (index_heads + 1.0) * index_dim * act_bytes
    return flops, nbytes
