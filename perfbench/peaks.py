"""The table of peaks and the operations/bytes of each kernel, from shapes.

Published peaks of one chip, keyed by ``device_kind``. A device that is not
in the table is an error, not a default. Source for "TPU v5 lite": Google
Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s bf16, 16 GB
HBM2e at 819 GB/s.

The FLOP count of a train step copies the arithmetic of the repo's
``bench.py`` (its device child): ``6 * (N - N_embed_table) * tokens`` for the
matmuls (the input embedding is a lookup) plus ``6 * L * B * S^2 * H`` for
causal attention's two S x S matmuls forward and backward. Recomputed
operations (remat) do not count.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.py's table; "
            "add its published peaks with their source"
        ) from None


def roofline_share_pct(flops: float, nbytes: float, seconds: float, peaks: Dict[str, float]):
    """``(share %, bound)``: the least time the chip could take (the larger
    of operations over peak FLOP/s and bytes over peak bytes/s) over the
    measured kernel time, and which of the two bounds it."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return 100.0 * least / seconds, ("compute" if t_flops >= t_bytes else "memory")


# --- paged decode attention ------------------------------------------------------


def paged_decode_cost(context_lens, *, num_q_heads: int, num_kv_heads: int,
                      head_dim: int, kv_bytes: int = 2, q_len: int = 1):
    """Operations and bytes one paged-decode attention call needs, for slots
    whose valid contexts are ``context_lens`` tokens long (one layer).

    Bytes: every valid K and V element is read once (``2 * ctx * Hkv * D``),
    plus the query read and the output write. FLOPs: ``QK^T`` and ``PV``,
    ``2 * 2 * q_len * ctx * Hq * D``. Pages past a slot's context and the
    padding inside its last page are NOT counted: they are work the
    algorithm does not need."""
    flops = 0.0
    nbytes = 0.0
    for ctx in context_lens:
        ctx = int(ctx)
        flops += 4.0 * q_len * ctx * num_q_heads * head_dim
        nbytes += 2.0 * ctx * num_kv_heads * head_dim * kv_bytes
        nbytes += 2.0 * q_len * num_q_heads * head_dim * kv_bytes
    return flops, nbytes


# --- flash attention ---------------------------------------------------------------


def flash_cost(batch: int, seq: int, *, num_q_heads: int, num_kv_heads: int,
               head_dim: int, causal: bool = True, act_bytes: int = 2):
    """Operations and bytes of one flash-attention FORWARD over ``(batch,
    seq)`` (one layer): ``QK^T`` and ``PV`` = ``4 * B * S^2 * Hq * D``, halved
    under a causal mask; Q, K, V read and O written once."""
    flops = 4.0 * batch * seq * seq * num_q_heads * head_dim
    if causal:
        flops /= 2.0
    q = batch * seq * num_q_heads * head_dim * act_bytes
    kv = batch * seq * num_kv_heads * head_dim * act_bytes
    return flops, 2.0 * q + 2.0 * kv


def flash_backward_cost(batch: int, seq: int, *, num_q_heads: int, num_kv_heads: int,
                        head_dim: int, causal: bool = True, act_bytes: int = 2):
    """Operations and bytes of one flash-attention BACKWARD: five S x S
    matmuls (the recomputed ``QK^T``, ``dV``, ``dP``, ``dQ``, ``dK``) against
    the forward's two, so 2.5 times its operations; Q, K, V, O, dO read and
    dQ, dK, dV written once."""
    fwd, _ = flash_cost(batch, seq, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
                        head_dim=head_dim, causal=causal, act_bytes=act_bytes)
    q = batch * seq * num_q_heads * head_dim * act_bytes
    kv = batch * seq * num_kv_heads * head_dim * act_bytes
    return 2.5 * fwd, (3.0 * q + 2.0 * kv) + (q + 2.0 * kv)


# --- whole train step ----------------------------------------------------------------


def train_flops_per_token(n_params: int, n_embed_table: int, *, num_layers: int,
                          seq: int, hidden: int) -> float:
    """Model FLOPs one trained token requires, forward and backward:
    ``6 * (N - N_embed_table)`` for the matmuls (the input embedding is a
    lookup; the output head counts) plus ``6 * L * S * H`` for attention —
    ``bench.py``'s ``6 * L * B * S^2 * H`` per batch over its ``B * S``
    tokens: causal QK^T and PV forward and backward, half of the unmasked
    ``12 * L * B * S^2 * H`` because the flash kernel computes only the
    lower triangle."""
    return 6.0 * (n_params - n_embed_table) + 6.0 * num_layers * seq * hidden
