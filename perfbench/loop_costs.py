"""Operations and bytes of a LOOPED stack's decode step outside its attention
(Ouro's ``ouro`` as configured: ``passes`` runs of ``num_layers`` decoder
layers over ONE set of weights), from shapes (the peaks table and
``roofline_share_pct`` are ``peaks.py``'s).

* a layer's weights: the q, k, v and o projections (``hidden x (H + 2 Hkv) D``
  and ``H D x hidden``), the SwiGLU MLP's three matrices (``3 x hidden x
  intermediate``) and its four norm gains: 51.4 M parameters at 2048 / 16
  heads of 128 / 5632, 102.8 MB in bf16.
* a decode step reads every layer's weights once a PASS (nothing keeps a
  layer's 100 MB on the chip between two passes: VMEM holds 128 MiB and the
  stack is 2.47 GB at 24 layers), so the needed bytes are ``passes x layers x
  a layer's bytes`` (+ the closing norm's gain a pass); ``2 x parameters``
  operations a row a pass.

Needed work only: the rows' activations (a few KB a layer) are left out, and
so are the embedding and the head (they lie outside the passes). Bound by
memory at any batch a chip's slots allow (two rows: 2 operations a byte
against the chip's balance of 240), so no step can read over 100%.
"""

from __future__ import annotations


def layer_weight_params(g: dict) -> int:
    """Parameters of ONE decoder layer of geometry ``g``."""
    hidden, d = int(g["hidden"]), int(g["head_dim"])
    attn = hidden * (int(g["num_q_heads"]) + 2 * int(g["num_kv_heads"])) * d + int(g["num_q_heads"]) * d * hidden
    return attn + 3 * hidden * int(g["intermediate"]) + 4 * hidden


def stack_weight_bytes(g: dict, weight_bytes: int = 2) -> int:
    """Bytes of the stack's weights: ``num_layers`` layers, held ONCE."""
    return int(g["num_layers"]) * layer_weight_params(g) * weight_bytes


def loop_decode_cost(steps: int, rows: int, g: dict, weight_bytes: int = 2):
    """``steps`` decode steps of ``rows`` slots through ``passes`` passes of
    the stack, attention left out: ``(operations, bytes)``."""
    passes = int(g["passes"])
    a_pass = int(g["num_layers"]) * layer_weight_params(g) + int(g["hidden"])     # + the closing norm's gain
    flops = 2.0 * steps * rows * passes * a_pass
    nbytes = float(steps) * passes * a_pass * weight_bytes
    return flops, nbytes
