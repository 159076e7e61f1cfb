"""Operations and bytes of attention under a learned sparse-attention indexer
that selects among the LATENTS of multi-head latent attention
(DeepSeek-Sparse-Attention over MLA, as GLM-5 configures it), from shapes
(the peaks table and ``roofline_share_pct`` are ``peaks.py``'s; the index
scores of a decode step are ``dsa_costs.index_decode_cost``).

* sparse latent attention, decode: a slot's ``min(ctx, topk)`` selected
  tokens, ONE latent row and one rotated key each for all ``H`` heads (``d_c
  + d_r`` values: 1152 B in bf16 at 512 + 64), the absorbed queries in and
  the latent-space output out.
* prefill: the materialised form over the SELECTED pairs only (q and k of
  ``d_qk``, v of ``d_v``, ``H`` heads each), plus the index scores of every
  causal pair.

Needed work only: valid contexts, not the shared cursor's columns; each
prompt's own length, not its bucket; the bytes a token's latent HOLDS, not
the tile it is stored in. So no kernel can read over 100%.
"""

from __future__ import annotations

from perfbench.dsa_costs import selected_pairs


def sparse_latent_decode_cost(context_lens, *, num_q_heads: int, latent_dim: int, rope_dim: int,
                              topk: int, act_bytes: int = 2):
    """One sparse latent decode-attention call (one layer, one query row a
    slot). Bytes: ``min(ctx, topk) * (d_c + d_r)`` values of cache, ``H *
    (d_c + d_r)`` of absorbed queries read, ``H * d_c`` written. FLOPs: ``2 *
    min(ctx, topk) * H * ((d_c + d_r) + d_c)`` (scores, then the values, which
    are the latent rows)."""
    flops = nbytes = 0.0
    for ctx in context_lens:
        kept = min(int(ctx), int(topk))
        flops += 2.0 * kept * num_q_heads * (2 * latent_dim + rope_dim)
        nbytes += kept * (latent_dim + rope_dim) * act_bytes
        nbytes += num_q_heads * (2 * latent_dim + rope_dim) * act_bytes
    return flops, nbytes


def sparse_latent_prefill_cost(seq: int, *, num_q_heads: int, qk_dim: int, v_dim: int,
                               index_heads: int, index_dim: int, topk: int, act_bytes: int = 2):
    """A prompt of ``seq`` tokens through one layer's prefill attention:
    FLOPs of the selected pairs, ``2 * H * (d_qk + d_v) * sum_t min(t + 1,
    topk)``, plus the index scores of all causal pairs, ``2 * H_i * d_i * S (S
    + 1) / 2``. Bytes: q and k (``H * d_qk``), v and o (``H * d_v``), the index
    queries and keys, once."""
    flops = 2.0 * num_q_heads * (qk_dim + v_dim) * selected_pairs(seq, topk)
    flops += 2.0 * index_heads * index_dim * seq * (seq + 1) / 2.0
    nbytes = seq * 2.0 * num_q_heads * (qk_dim + v_dim) * act_bytes
    nbytes += seq * (index_heads + 1.0) * index_dim * act_bytes
    return flops, nbytes
