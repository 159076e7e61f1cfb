"""Shared attention infrastructure: RoPE, the attention-impl dispatcher, and
the TP self-attention block used by the non-Llama model families (BERT/ViT
bidirectional, GPT-NeoX/CodeGen causal with partial rotary).

This module is the canonical home of the generic ops — ``rope_frequencies``,
``apply_rope``, ``attention_op`` — which the flagship Llama path re-exports
(models depend on modules, never the reverse).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.modules.qkv_linear import GQAQKVColumnParallelLinear
from neuronx_distributed_tpu.modules.remat import ATTN_QKV, MLP_UP
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.layers import RowParallelLinear
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain

Dtype = Any


# --- RoPE ---------------------------------------------------------------------

def rope_frequencies(head_dim: int, max_seq_len: int, theta: float) -> jax.Array:
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    return jnp.outer(t, inv_freq)  # (S, D/2)


def apply_rope(x: jax.Array, freqs: jax.Array, positions: Optional[jax.Array] = None) -> jax.Array:
    """x: (B, S, H, D); freqs: (max_S, D/2); positions: (B, S) int or None."""
    if positions is None:
        f = freqs[: x.shape[1]][None, :, None, :]  # (1, S, 1, D/2)
    else:
        f = freqs[positions][:, :, None, :]  # (B, S, 1, D/2)
    cos, sin = jnp.cos(f), jnp.sin(f)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- attention dispatch -------------------------------------------------------

def xla_attention(q, k, v, causal: bool = True, mask: Optional[jax.Array] = None,
                  segment_ids: Optional[jax.Array] = None,
                  kv_segment_ids: Optional[jax.Array] = None):
    """Reference einsum attention (golden path; CPU meshes; masked inputs).
    q:(B,S,H,D), k/v:(B,S,Hkv,D) with Hkv | H (GQA broadcast); ``mask``
    (B, Sk) True at VALID key positions (padding mask); ``segment_ids``
    (B, Sq) int restricts attention to equal-segment pairs (packed
    documents — the numerics golden for the flash kernel's segment path)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    sk = k.shape[1]
    if causal:
        cmask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        scores = jnp.where(cmask[None, None, None], scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    if segment_ids is not None:
        ks = kv_segment_ids if kv_segment_ids is not None else segment_ids
        smask = segment_ids[:, :, None] == ks[:, None, :]  # (B, Sq, Sk)
        scores = jnp.where(smask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[3]).astype(q.dtype)


def attention_op(q, k, v, causal: bool = True, impl: str = "auto",
                 mask: Optional[jax.Array] = None,
                 segment_ids: Optional[jax.Array] = None,
                 kv_segment_ids: Optional[jax.Array] = None):
    """Dispatch: ring when cp > 1, Pallas flash on TPU, XLA einsum golden
    elsewhere.

    ``segment_ids`` (B, S) int (packed-document isolation) and ``mask``
    (B, Sk) bool (True at valid keys — padding) both ride the flash kernel's
    segment path on TPU (padding becomes segment ``-1``); under cp > 1
    packed/masked SELF-attention rides the ring engines (key segments
    rotate with K/V). Only a kv-side mask with cross-length shapes keeps
    the fp32 einsum fallback (see PARITY.md)."""
    if kv_segment_ids is not None and segment_ids is None:
        raise ValueError(
            "kv_segment_ids requires segment_ids (query-side ids) — "
            "got only the key side, which would silently drop the mask"
        )
    q_seg = segment_ids
    k_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
    cp = (
        mesh_lib.get_context_parallel_size()
        if mesh_lib.model_parallel_is_initialized()
        else 1
    )
    if mask is not None:
        # fold the padding mask into segment ids: padding = segment -1
        if k_seg is None and q.shape[1] == k.shape[1]:
            q_seg = k_seg = jnp.where(mask, 0, -1)
        elif k_seg is not None and k_seg is q_seg and q.shape[1] == k.shape[1]:
            # self-attention: fold symmetrically into ONE shared array so the
            # packed+masked case keeps the cp ring route (masked q rows'
            # outputs are dropped by the caller's loss/valid masks anyway)
            q_seg = k_seg = jnp.where(mask, q_seg, -1)
        elif k_seg is not None:
            k_seg = jnp.where(mask, k_seg, -1)
        else:  # cross-length mask with no segments: einsum path handles it
            return xla_attention(q, k, v, causal=causal, mask=mask)
    if q_seg is not None:
        if cp > 1 and causal and q.shape[1] == k.shape[1] and (k_seg is q_seg):
            # packed documents at ring scale: key segments rotate with K/V
            # (round 5 — the S×S einsum fallback is gone). Self-attention
            # with ONE segment array only (a separate kv mask folded into
            # k_seg keeps the exact einsum fallback below)
            from neuronx_distributed_tpu.kernels.ring_attention import (
                ring_attention_sharded,
            )

            # ring_attention_sharded's engine choice is flash|xla|auto;
            # impl="ring"/"ulysses" here mean "the cp path" — let it pick
            # the engine (flash on TPU) instead of falling into the
            # einsum-block branch
            ring_impl = impl if impl in ("flash", "xla") else "auto"
            return ring_attention_sharded(
                q, k, v, causal=causal, impl=ring_impl, segment_ids=q_seg
            )
        if cp == 1 and backend.resolve_attention_impl(impl) == "flash":
            from neuronx_distributed_tpu.kernels.flash_attention import flash_attention

            return flash_attention(
                q, k, v, causal=causal,
                segment_ids=q_seg, kv_segment_ids=k_seg,
            )
        return xla_attention(
            q, k, v, causal=causal, segment_ids=q_seg, kv_segment_ids=k_seg
        )
    # "auto" with the sequence sharded over cp → ring attention (reference
    # long-seq path: CP groups + NKI ring kernel, parallel_state.py:678,
    # kernels/ring_attention_kernel.py)
    impl = backend.resolve_attention_impl(impl, cp)
    if impl == "flash":
        from neuronx_distributed_tpu.kernels.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal)
    if impl == "ring":
        from neuronx_distributed_tpu.kernels.ring_attention import ring_attention_sharded

        return ring_attention_sharded(q, k, v, causal=causal)
    if impl == "ulysses":
        # all-to-all sequence parallelism — an extra over the reference
        # (SURVEY §2.10: NxD has no Ulysses variant)
        from neuronx_distributed_tpu.kernels.ulysses import (
            ulysses_attention_sharded,
        )

        return ulysses_attention_sharded(q, k, v, causal=causal)
    return xla_attention(q, k, v, causal=causal)


# The named scope a trace reader finds device ops by (PERF.md section 3): the
# paged path's gather of the logical (slots x row) K/V view and its
# write-back into the pool (``gather`` transport), or the fused transport's
# gather of its write window and each step's window scatter. Compile-time
# metadata only; it may not enclose a Pallas call.
KV_VIEW_SCOPE = "kv_view"


# Named scopes of the sparse-attention block (an indexer beside GQA, below),
# a trace reader's contract (PERF.md section 3): the index projections,
# LayerNorm, rotary and the ``k_idx`` write; the index scores; the selection;
# attention over the selected. A Pallas kernel called inside one is named
# after it.
DSA_INDEX_SCOPE = "dsa.index"
DSA_SCORE_SCOPE = "dsa.score"
DSA_SELECT_SCOPE = "dsa.select"
DSA_ATTEND_SCOPE = "dsa.attend"
# the fused chunk's per-step write of the K and V window pages into the pool
# (a kernel, named after this scope)
DSA_WRITE_SCOPE = "dsa.write"

# Named scopes of a model whose stack mixes WINDOW and FULL attention layers
# (``models/afmoe.py``), a trace reader's contract (PERF.md section 3): each
# kind's attention from the head norms to the kernel (rotary, the cache write
# and the kernels included: a Pallas kernel called inside one is named after
# it), and the sigmoid gate on the attention output.
ATTN_WINDOW_SCOPE = "attn.window"
ATTN_FULL_SCOPE = "attn.full"
ATTN_GATE_SCOPE = "attn.gate"
# Attention in a compressed latent whose q/k pass two short causal
# convolutions (``models/zaya.py``): the whole block; inside it the
# projections, the convolutions with the per-slot state's read and write
# (everything the architecture adds around the kernel), and the attend.
ATTN_CCA_SCOPE = "attn.cca"
ATTN_CCA_PROJECT_SCOPE = "attn.cca.project"
ATTN_CCA_CONV_SCOPE = "attn.cca.conv"
ATTN_CCA_ATTEND_SCOPE = "attn.cca.attend"
# Gated delta-rule linear attention (``models/solar_open2.py``): the whole
# block; inside it the six projections, the short convolutions with their
# per-slot taps, the decay, the write strength and the output gate with its
# norm, the recurrence (``kernels/delta_rule.py``: both kernels are named
# after it) and the output projection.
ATTN_KDA_SCOPE = "attn.kda"
ATTN_KDA_PROJECT_SCOPE = "attn.kda.project"
ATTN_KDA_CONV_SCOPE = "attn.kda.conv"
ATTN_KDA_GATE_SCOPE = "attn.kda.gate"
ATTN_KDA_RECUR_SCOPE = "attn.kda.recur"
ATTN_KDA_OUT_SCOPE = "attn.kda.out"
# A stack that is RUN more than once over one set of weights
# (``models/ouro.py``): each pass of the stack, its layers' attention
# (``attn.full``) inside it and its closing norm.
LOOP_PASS_SCOPE = "loop.pass"
# ... and the name of pass ``t``'s cache node under a layer of such a stack,
# ``<LOOP_PASS_NODE><t>``: the layer's parameters exist once, its K/V once a
# pass (:func:`_execution_order` reads the pass from the name).
LOOP_PASS_NODE = "pass_"


def prefill_positions(padding_mask: jax.Array) -> jax.Array:
    """RoPE positions for a (possibly left-)padded prompt (B, S): restart at
    each row's first VALID token, so padded slots never shift the rotary
    phase. Padding positions clamp to 0 (they are attention-masked anyway)."""
    return jnp.maximum(
        jnp.cumsum(padding_mask.astype(jnp.int32), axis=1) - 1, 0
    )


def valid_count_below(kv_valid: jax.Array, cur: jax.Array) -> jax.Array:
    """Per-row count of valid cache slots strictly below write index ``cur``
    — each row's TRUE sequence length, which differs from the slot index when
    the prompt was padded. Counting only below ``cur`` keeps speculative
    cache rollbacks (which reset just the index leaf) from seeing stale
    validity entries."""
    below = jnp.arange(kv_valid.shape[1], dtype=jnp.int32)[None] < cur
    return jnp.sum((kv_valid & below).astype(jnp.int32), axis=1)


class KVCache:
    """The flax ``cache`` collection variables + validity bookkeeping shared
    by every cached-attention implementation (LlamaAttention and
    ParallelSelfAttention hold the rope/mask specifics; the cache writes,
    padding persistence, and rollback-safe position accounting live here
    exactly once).

    Variables: ``k``/``v`` (B, L, Hkv, D), ``index`` () int32 write cursor,
    ``kv_valid`` (B, L) bool — prefill records the padding mask, decode
    appends per-step validity, so padded prompt slots stay masked for the
    whole generation without the caller re-supplying the mask.

    ``leaves`` names the per-token storage leaves and their ``(heads,
    width)``; the writes take one array per leaf, in that order.
    :class:`LatentKVCache`, :class:`IndexedKVCache` and
    :class:`IndexedLatentKVCache` are the other kinds."""

    def __init__(self, module, b, max_seq_len, hkv, d, dtype, leaves=None):
        self.max_seq_len = max_seq_len
        self.b = b
        leaves = leaves or {"k": (hkv, d), "v": (hkv, d)}
        self.leaves = tuple(
            module.variable(
                "cache", name, jnp.zeros, (b, max_seq_len, h, w), dtype
            )
            for name, (h, w) in leaves.items()
        )
        for name, leaf in zip(leaves, self.leaves):
            setattr(self, name, leaf)  # .k/.v, or a latent cache's .k/.k_pe
        self.index = module.variable(
            "cache", "index", lambda: jnp.zeros((), jnp.int32)
        )
        self.valid = module.variable(
            "cache", "kv_valid", jnp.zeros, (b, max_seq_len), jnp.bool_
        )

    def prefill_write(self, k, v, padding_mask=None):
        """Write the prompt K/V at slot 0 and record its validity."""
        self._prefill_write((k, v), padding_mask)

    def decode_write(self, k, v, padding_mask=None):
        """Append a decode step's K/V at the cursor; ``padding_mask`` (B, s)
        marks the INCOMING tokens' validity (ragged batched decode: finished
        rows pass False so their filler tokens never become attendable)."""
        self._decode_write((k, v), padding_mask)

    def _prefill_write(self, news, padding_mask):
        b, s = news[0].shape[0], news[0].shape[1]
        for leaf, new in zip(self.leaves, news):
            leaf.value = jax.lax.dynamic_update_slice(leaf.value, new, (0, 0, 0, 0))
        self.index.value = jnp.asarray(s, jnp.int32)
        valid = (
            padding_mask.astype(jnp.bool_)
            if padding_mask is not None
            else jnp.ones((b, s), jnp.bool_)
        )
        self.valid.value = jax.lax.dynamic_update_slice(self.valid.value, valid, (0, 0))

    def decode_positions(self, s, positions):
        """(slot positions (s,), rope positions (B, s)) for a decode step.
        With explicit ``positions`` (tree/speculative decoding) both follow
        the caller; otherwise slots continue at the write cursor while RoPE
        continues each row's TRUE sequence (rollback-safe, see
        ``valid_count_below``)."""
        cur = self.index.value
        if positions is not None:
            pos = jnp.reshape(positions, (-1,)).astype(jnp.int32)
            return pos, jnp.broadcast_to(pos[None], (self.b, s))
        pos = cur + jnp.arange(s, dtype=jnp.int32)
        nvalid = valid_count_below(self.valid.value, cur)
        rope_pos = nvalid[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        return pos, rope_pos

    def _decode_write(self, news, padding_mask):
        b, s = news[0].shape[0], news[0].shape[1]
        cur = self.index.value
        # inside a fused paged frame the per-token leaves hold the chunk's
        # write window alone; ``index`` and ``kv_valid`` stay whole
        col = cur - _fused_window_origin() if _FUSED_PAGED_STACK else cur
        for leaf, new in zip(self.leaves, news):
            leaf.value = jax.lax.dynamic_update_slice(leaf.value, new, (0, col, 0, 0))
        self.index.value = cur + s
        if padding_mask is not None:
            if padding_mask.shape != (b, s):
                raise ValueError(
                    f"decode padding_mask must cover the incoming step "
                    f"tokens (shape {(b, s)}), got {padding_mask.shape} — "
                    "prompt padding is already persisted from prefill"
                )
            new_valid = padding_mask.astype(jnp.bool_)
        else:
            new_valid = jnp.ones((b, s), jnp.bool_)
        self.valid.value = jax.lax.dynamic_update_slice(self.valid.value, new_valid, (0, cur))


class LatentKVCache(KVCache):
    """The cache of multi-head LATENT attention (MLA): per token ONE latent
    row ``c`` of ``d_latent`` values (after its RMSNorm) and ONE rotated key
    ``k_pe`` of ``d_rope`` values, both shared by every head, and nothing
    per head: 512 + 64 = 576 values a token a layer at DeepSeek-V2's widths,
    against ``H * (192 + 128)`` for materialised keys and values.

    Leaves: ``k`` (B, L, 1, d_latent), the latent row, which in the absorbed
    form is the key's content part AND the value; ``k_pe`` (B, L, 1,
    d_rope). They are one-head leaves of the contract above, so every walker
    of the cache tree (:data:`PAGED_LEAVES`) handles them as it handles
    ``k``/``v``. Two leaves and not one of 576: see
    ``kernels/flash_decode.py`` (a bf16 leaf whose width is no multiple of
    128 gets a layout no kernel can stream pages from). The writes take
    ``(c, k_pe)`` where :class:`KVCache`'s take ``(k, v)``."""

    def __init__(self, module, b, max_seq_len, d_latent, d_rope, dtype):
        super().__init__(
            module, b, max_seq_len, 1, d_latent, dtype,
            leaves={"k": (1, d_latent), "k_pe": (1, d_rope)},
        )


class IndexedKVCache(KVCache):
    """The cache of GQA attention with a learned sparse-attention INDEXER
    beside it: per token the K and V of every kv head plus ONE index key
    ``k_idx`` of ``d_index`` values (after its LayerNorm and rotary) shared
    by all index heads: ``2 * Hkv * D + d_index`` values a token a layer
    (2048 + 128 = 2176 bytes at Keye-VL-2.0's widths in bf16). A decode step
    scores every cached index key against the step's index queries and
    attends only the ``topk`` best columns
    (:func:`indexed_decode_attention`).

    Leaves ``kv`` (B, L, 2 Hkv, D), a token's K heads then its V heads, and
    ``k_idx`` (B, L, 1, d_index): per-token leaves of the contract above
    (:data:`PAGED_LEAVES`), so every walker handles them as it handles
    ``k``/``v``. K and V are ONE leaf because the sparse decode kernel
    fetches a selected token at a time and is bound by the copies it names:
    one copy of ``(2 Hkv, D)`` a token (``(8, 128)`` in bf16 at Keye's
    widths: a whole tile, the layout XLA itself picks) where two leaves cost
    two. The writes take ``(k, v, k_idx)`` and join K and V on the head
    axis; :func:`split_kv` takes the leaf apart."""

    def __init__(self, module, b, max_seq_len, hkv, d, d_index, dtype):
        super().__init__(
            module, b, max_seq_len, hkv, d, dtype,
            leaves={"kv": (2 * hkv, d), "k_idx": (1, d_index)},
        )

    def prefill_write(self, k, v, k_idx, padding_mask=None):
        self._prefill_write((jnp.concatenate([k, v], axis=2), k_idx), padding_mask)

    def decode_write(self, k, v, k_idx, padding_mask=None):
        self._decode_write((jnp.concatenate([k, v], axis=2), k_idx), padding_mask)


def split_kv(kv):
    """``(k, v)`` of an :class:`IndexedKVCache`'s joined leaf (..., 2 Hkv, D)."""
    hkv = kv.shape[-2] // 2
    return kv[..., :hkv, :], kv[..., hkv:, :]


def latent_leaf_shape(d_latent: int, d_rope: int):
    """``(rows, lanes)`` of an :class:`IndexedLatentKVCache` token: the latent
    in ``d_latent / lanes`` rows of ``lanes`` (128 wherever the width allows),
    the rotated key at the start of the next row, and the rows up to what the
    chip holds anyway: a bf16 ``(rows, 128)`` array is tiled ``(2, 128)``,
    ``(4, 128)`` or ``(8, 128)``, so 5 rows are held as 8."""
    import math

    lanes = math.gcd(d_latent, 128)
    if d_rope > lanes:
        raise ValueError(
            f"the rotated key ({d_rope}) must fit one row of {lanes} lanes "
            f"(the latent is {d_latent})")
    need = d_latent // lanes + 1
    rows = next(r for r in (2, 4, 8) if r >= need) if need <= 8 else -(-need // 8) * 8
    return rows, lanes


def join_latent(c, k_pe, rows: int, lanes: int):
    """The joined leaf (..., rows, lanes) of a latent row ``c`` (..., 1,
    d_latent) and its rotated key ``k_pe`` (..., 1, d_rope)."""
    lead = c.shape[:-2]
    n_c = c.shape[-1] // lanes
    kr = jnp.pad(k_pe, ((0, 0),) * (k_pe.ndim - 1) + ((0, lanes - k_pe.shape[-1]),))
    spare = jnp.zeros(lead + (rows - n_c - 1, lanes), c.dtype)
    return jnp.concatenate([c.reshape(lead + (n_c, lanes)), kr, spare], axis=-2)


def split_latent(kv, d_latent: int, d_rope: int):
    """``(c (..., d_latent), k_pe (..., d_rope))`` of a joined latent leaf
    (..., rows, lanes)."""
    n_c = d_latent // kv.shape[-1]
    c = kv[..., :n_c, :].reshape(kv.shape[:-2] + (d_latent,))
    return c, kv[..., n_c, :d_rope]


class IndexedLatentKVCache(KVCache):
    """The cache of multi-head LATENT attention whose keys a learned indexer
    SELECTS (DeepSeek-Sparse-Attention over MLA): per token the latent row
    ``c`` (``d_latent`` values), the rotated key ``k_pe`` (``d_rope``) and ONE
    index key ``k_idx`` (``d_index``), all shared by every head: 512 + 64 +
    128 values at GLM-5's widths, 1408 B in bf16.

    Leaves ``kv`` (B, L, rows, lanes) and ``k_idx`` (B, L, 1, d_index), the
    names an :class:`IndexedKVCache` has, so every walker
    (:data:`PAGED_LEAVES`) and the window's page copies handle them alike.
    ``kv`` is the token's latent in its first ``d_latent / lanes`` rows and
    its rotated key at the start of the next (:func:`join_latent`): **(8,
    128) at GLM-5's widths, 2048 B a token a layer in bf16 of which 1152 are
    used, + 256 B of index key = 2304 B** (``cache_bytes_per_token_layer``
    reads that). Why one leaf of a whole tile: the sparse decode kernel
    fetches a SELECTED token at a time and is bound by the copies it names
    (PERF.md section 6, PR 31), and Mosaic copies whole HBM tiles only: five
    rows are held as eight whatever the shape says, a leaf of (4, 128) for
    the latent is exact but leaves the rotated key a second leaf of (2, 128)
    (1536 B a token) and a second copy a token. Measured at the serve cell's
    shapes (PERF.md section 6, PR 32) before this form was kept. The writes
    take ``(c, k_pe, k_idx)``."""

    def __init__(self, module, b, max_seq_len, d_latent, d_rope, d_index, dtype):
        self.leaf = latent_leaf_shape(d_latent, d_rope)
        super().__init__(
            module, b, max_seq_len, *self.leaf, dtype,
            leaves={"kv": self.leaf, "k_idx": (1, d_index)},
        )

    def prefill_write(self, c, k_pe, k_idx, padding_mask=None):
        self._prefill_write((join_latent(c, k_pe, *self.leaf), k_idx), padding_mask)

    def decode_write(self, c, k_pe, k_idx, padding_mask=None):
        self._decode_write((join_latent(c, k_pe, *self.leaf), k_idx), padding_mask)


# The leaf a WINDOW layer's cache node carries beside its storage: a bool
# array of shape ``(0, window)``. It holds no byte; its SHAPE is the layer's
# window, a static attribute every walker of a cache tree can read inside or
# outside a jitted program (:func:`cache_node_window`). A node without it is
# a full-attention layer's. Walkers that do not know it treat it as they
# treat ``index`` (a leaf with no slot axis) and write nothing: it is empty.
WINDOW_LEAF = "window"


class JoinedKVCache(KVCache):
    """The cache of GQA attention whose K and V are ONE joined leaf ``kv``
    (B, L, 2 Hkv, D), a token's K heads then its V heads (``(16, 128)`` a
    token at 8 kv heads of 128: whole tiles, one copy a page for the paged
    kernel that walks a slot's blocks), for a stack that mixes two KINDS of
    layer: ``window=None`` a full-attention layer's, ``window=W`` a layer
    that attends the last ``W`` tokens only and whose node says so
    (:data:`WINDOW_LEAF`). The paged cache manager gives the window kind a
    block table and a pool of its own and frees a window layer's pages as
    the cursor passes them (``serving/paging.py``). The writes take ``(k,
    v)``; :func:`split_kv` takes the leaf apart.

    ``state_width``: the layer also keeps ``state`` (B, state_width), what its
    NEXT token needs of the request's last one and the cache does not hold
    (:data:`SLOT_STATE_LEAVES`: a slot axis and no length axis). A prefill
    leaves the state of each row's last valid token, a decode step reads and
    replaces it."""

    def __init__(self, module, b, max_seq_len, hkv, d, dtype, window=None,
                 state_width: Optional[int] = None):
        super().__init__(
            module, b, max_seq_len, hkv, d, dtype, leaves={"kv": (2 * hkv, d)},
        )
        self.window = window
        if window is not None:
            module.variable("cache", WINDOW_LEAF, jnp.zeros, (0, int(window)), jnp.bool_)
        self.state = None
        if state_width is not None:
            self.state = module.variable(
                "cache", SLOT_STATE_LEAVES[0], jnp.zeros, (b, int(state_width)), dtype)

    def prefill_write(self, k, v, padding_mask=None):
        self._prefill_write((jnp.concatenate([k, v], axis=2),), padding_mask)

    def decode_write(self, k, v, padding_mask=None):
        self._decode_write((jnp.concatenate([k, v], axis=2),), padding_mask)


class RecurrentStateCache:
    """What a RECURRENT layer keeps a slot, and all it keeps: ``recur`` (B,
    heads, d, d) float32, the state a scan over the request's tokens has
    reached, and ``conv`` (B, taps, channels), the last ``taps`` inputs of its
    short causal convolutions (:data:`SLOT_STATE_LEAVES`). No per-token leaf,
    no ``kv_valid``, no ``index``: the layer grows no column, maps no page and
    has no block table; the stack's cursor is its attention layers'. A prefill
    leaves both at each row's last token, a decode step reads and replaces
    them, a slot that takes no token keeps them."""

    def __init__(self, module, b, heads, d, taps, channels, dtype):
        self.recur = module.variable("cache", "recur", jnp.zeros, (b, heads, d, d), jnp.float32)
        self.conv = module.variable("cache", "conv", jnp.zeros, (b, taps, channels), dtype)


def cache_node_window(node) -> Optional[int]:
    """The window of the layer whose cache node (the dict holding its leaves)
    this is; ``None`` for a full-attention layer."""
    leaf = node.get(WINDOW_LEAF) if hasattr(node, "get") else None
    return None if leaf is None else int(leaf.shape[-1])


def cache_windows(tree) -> dict:
    """``{layer path (key tuple): window}`` of the WINDOW layers of a cache
    tree (a row collection or a pool tree)."""
    from neuronx_distributed_tpu.utils.tree import path_keys

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = tuple(path_keys(path))
        if keys[-1] == WINDOW_LEAF:
            out[keys[:-1]] = int(leaf.shape[-1])
    return out


def window_floor(kv_valid, cur, window: int):
    """(B,) int32: the lowest cache column the query written at column
    ``cur`` may attend under a window of ``window`` TOKENS. A slot's tokens
    are its VALID columns (gap columns, left by another slot's admission
    moving the shared cursor, hold none), so the window is counted over
    ``kv_valid`` and not over columns: the lowest valid column ``j <= cur``
    with at most ``window`` valid columns in ``[j, cur]``. ``cur`` where the
    row holds nothing."""
    length = kv_valid.shape[1]
    cols = jnp.arange(length, dtype=jnp.int32)[None]
    v = kv_valid & (cols <= cur)
    after = jnp.cumsum(v[:, ::-1].astype(jnp.int32), axis=1)[:, ::-1]   # valid in [j, cur]
    keep = v & (after <= window)
    return jnp.where(keep.any(axis=1), jnp.argmax(keep, axis=1), cur).astype(jnp.int32)


def window_keep(kv_valid, q_pos, window: Optional[int]):
    """(B, S, L) bool: the cache columns the query rows at columns ``q_pos``
    (S,) attend: valid, at or before the row's column and, under a
    ``window``, among the row's last ``window`` tokens (counted over valid
    columns, as :func:`window_floor` counts them)."""
    length = kv_valid.shape[1]
    cols = jnp.arange(length, dtype=jnp.int32)
    keep = kv_valid[:, None, :] & (cols[None, None, :] <= q_pos[None, :, None])
    if window is not None:
        token = jnp.cumsum(kv_valid.astype(jnp.int32), axis=1) - 1        # (B, L)
        q_token = jnp.take_along_axis(
            token, jnp.broadcast_to(q_pos[None], (kv_valid.shape[0], q_pos.shape[0])), axis=1)
        keep = keep & (token[:, None, :] > q_token[:, :, None] - window)
    return keep


# --- cache-collection slot helpers (serving) ----------------------------------
#
# The continuous-batching engine (serving/) owns ONE cache collection whose
# batch rows are request SLOTS. These helpers operate on the raw collection
# tree (outside a flax apply), classified by leaf name — the same contract
# KVCache declares: k/v (..., B, L, Hkv, D), kv_valid (..., B, L), index
# scalar cursor (nn.scan stacks a leading layer axis on each); a latent
# cache's per-token leaves are k/k_pe (..., B, L, 1, d), an indexed cache's
# kv (..., B, L, 2 Hkv, D) and k_idx (..., B, L, 1, d_index), an indexed
# latent cache's kv (..., B, L, rows, lanes) and the same k_idx.

# THE names of the per-token storage leaves, (..., B, L, heads, width): what a
# page pool pages, a prefix block copies and a fingerprint hashes. Every
# walker of a cache tree classifies by this tuple, and a layer's leaves are
# handed around in ITS order: (k, v), a latent cache's (k, k_pe), an indexed
# cache's (kv, k_idx).
PAGED_LEAVES = ("k", "v", "k_pe", "kv", "k_idx")
# THE names of the per-slot STATE leaves: a slot axis and NO length axis: what
# a layer's next token needs of the request's tokens so far and no cache
# column holds. Each name has its own rank after the slot axis and its own
# dtype: ``state`` (..., B, width), the last token's values beside a paged
# cache (``JoinedKVCache(state_width=)``); ``recur`` (..., B, heads, d, d)
# float32, a recurrent layer's state at its natural rank (stored as a width it
# would be relaid a slot a step), and ``conv`` (..., B, taps, channels), the
# last inputs of its short convolutions (:class:`RecurrentStateCache`: a layer
# with these and NO per-token leaf, no ``kv_valid`` and no cursor). Slot-shaped
# like ``kv_valid``, so the paged transports hand them through as they are; an
# admission copies the prefill row's into the slot (no roll: there is no
# column), a freed slot's is left for the next admission to overwrite, and
# nothing that holds a context by its pages or columns alone (a prefix block,
# a staged or spilled context) has one: those are refused for the kind.
_SLOT_STATE_RANK = {"state": 1, "recur": 3, "conv": 2}     # axes AFTER the slot axis
SLOT_STATE_LEAVES = tuple(_SLOT_STATE_RANK)


def cache_leaf_name(path) -> str:
    """Terminal key of a cache-collection tree path (DictKey or str)."""
    last = path[-1]
    return last.key if hasattr(last, "key") else str(last)


def cache_batch_axis(name: str, ndim: int):
    """Batch(slot)-axis index of a cache leaf, or None for the shared
    ``index`` cursor. Leading layer axes from nn.scan stacking shift the
    batch axis right, so classify from the TRAILING dims."""
    if name in PAGED_LEAVES:
        return ndim - 4
    if name == "kv_valid":
        return ndim - 2
    if name in _SLOT_STATE_RANK:
        return ndim - 1 - _SLOT_STATE_RANK[name]
    return None


def cache_length_axis(name: str, ndim: int):
    """Column (cache-length) axis of a cache leaf, right after its batch
    axis, or None for a leaf without one: the ``index`` cursor and the
    per-slot state leaves (:data:`SLOT_STATE_LEAVES`)."""
    if name in PAGED_LEAVES or name == "kv_valid":
        return cache_batch_axis(name, ndim) + 1
    return None


def refuse_slot_state(name: str, what: str) -> None:
    """A walker that moves a context by its COLUMNS alone met a per-slot
    state leaf: the columns do not hold what the next token needs."""
    if name in SLOT_STATE_LEAVES:
        raise ValueError(
            f"{what} is not available for a cache with per-slot state "
            f"(leaf {name!r}): the state at a context's end is in no column")


def slot_state_bytes_per_layer(cache) -> float:
    """Bytes ONE slot's state leaves (:data:`SLOT_STATE_LEAVES`) hold in ONE
    layer of a cache tree (a row collection or a paged pytree), averaged over
    the layers that have them; 0 for a cache without."""
    import math

    tree = cache["pool"] if isinstance(cache, dict) and "pool" in cache else cache
    total, layers = 0.0, {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = cache_leaf_name(path)
        if name in SLOT_STATE_LEAVES:
            ax = cache_batch_axis(name, leaf.ndim)
            lead = math.prod(leaf.shape[:ax])
            total += lead * math.prod(leaf.shape[ax + 1:]) * leaf.dtype.itemsize
            layers[tuple(str(k) for k in path[:-1])] = lead      # a layer may hold several
    return total / sum(layers.values()) if layers else 0.0


def reset_cache_slot(cache, slot):
    """Free one batch row of a cache collection: clear its ``kv_valid`` so
    nothing in the row stays attendable (per-slot reset on request free —
    no full-cache reallocation). K/V storage is left in place; the next
    admission overwrites the whole row."""
    def fn(path, leaf):
        name = cache_leaf_name(path)
        if name != "kv_valid":
            return leaf
        ax = cache_batch_axis(name, leaf.ndim)
        zero = jnp.zeros_like(jax.lax.index_in_dim(leaf, 0, ax, keepdims=True))
        return jax.lax.dynamic_update_slice_in_dim(leaf, zero, slot, ax)

    return jax.tree_util.tree_map_with_path(fn, cache)


def cache_token_bytes(cache):
    """``(bytes, nodes)``: the bytes ONE token holds over ALL the attention
    nodes of a cache tree (a row collection or a paged ``{"pages", "pool"}``
    pytree) and how many nodes they are, from the allocated per-token storage
    leaves (:data:`PAGED_LEAVES`; a quantized pool's scale siblings count at
    their share of a page). A node is what one attention call of a model step
    writes and attends: a layer's, or, for a stack run more than once over one
    set of weights, a layer's in ONE pass (``T x L`` nodes: what the pool
    really holds a token is ``T`` times what the weight layers suggest)."""
    import math

    tree = cache["pool"] if isinstance(cache, dict) and "pool" in cache else cache
    total, layers = 0.0, set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = cache_leaf_name(path)
        base = pool_scale_base(name)
        if base is None and name not in PAGED_LEAVES:
            continue
        lead = math.prod(leaf.shape[:-4])
        per_token = math.prod(leaf.shape[-2:]) * leaf.dtype.itemsize
        if base is not None:  # (..., P, 1, Hkv, 1): one scale a page
            per_token /= cache_node_at(tree, path[:-1])[base].shape[-3]
        total += lead * per_token
        layers.add((tuple(str(k) for k in path[:-1]), lead))
    return total, sum(lead for _, lead in layers)


def cache_bytes_per_token_layer(cache) -> float:
    """Bytes ONE token holds in ONE attention node of a cache tree
    (:func:`cache_token_bytes`, averaged over the nodes): ``2 * Hkv * D *
    itemsize`` for a ``k``/``v`` cache, ``(d_latent + d_rope) * itemsize`` for
    a latent one (1152 at DeepSeek-V2's widths in bf16; twice the latent would
    read 2176), ``(2 * Hkv * D + d_index) * itemsize`` for an indexed one
    (2176 at Keye-VL-2.0's widths: 4 kv heads of 128 and one index key of 64;
    2304 would be the index key padded to 128 lanes)."""
    total, nodes = cache_token_bytes(cache)
    return total / nodes if nodes else 0.0


def cache_cursor(cache):
    """Shared write cursor of a raw cache collection as a traced int32
    scalar — the min over its ``index`` leaves (every attention module
    carries the same value; nn.scan stacking makes a leaf ``(num_layers,)``).
    Lets a jitted consumer (the serving engine's fused decode chunk) clamp
    its own step count against ``max_seq_len`` without a host round-trip."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    vals = [jnp.min(leaf) for path, leaf in flat if cache_leaf_name(path) == "index"]
    if not vals:
        raise ValueError("cache collection has no 'index' leaf")
    return jnp.stack(vals).min().astype(jnp.int32)


def reset_cache(cache):
    """Clear every slot's validity AND rewind the shared write cursor —
    the serving engine's drain/preemption reset (the storage itself is
    reused, never reallocated)."""
    def fn(path, leaf):
        name = cache_leaf_name(path)
        if name in ("kv_valid", "index"):
            return jnp.zeros_like(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(fn, cache)


def _col_window(leaf_ndim: int, axis: int, length: int, lo, hi):
    """Boolean mask over a cache leaf's column axis: True on ``[lo, hi)``.
    ``lo``/``hi`` may be traced scalars; the mask broadcasts against the
    leaf (singleton every other axis)."""
    shape = [1] * leaf_ndim
    shape[axis] = length
    cols = jnp.arange(length, dtype=jnp.int32).reshape(shape)
    return (cols >= lo) & (cols < hi)


def extract_cache_prefix(cache, start, m, bucket: int):
    """Copy the ``m`` cache columns starting at ``start`` out of a (batch-1)
    cache collection into a COMPACT prefix block of ``bucket`` columns
    (token 0 of the prefix at column 0), zero beyond ``m``.

    This is the prefix-cache STORE side: the block is a fresh copy (never a
    view of the source row, which the serving engine's donating programs may
    consume later), canonically zero-padded so identical prefixes produce
    identical blocks whatever padded bucket their donor prefill used. The
    roll-then-slice formulation keeps a window that touches the end of the
    row exact (a clamped ``dynamic_slice`` would silently shift it).
    ``start``/``m`` are traced scalars; ``bucket`` (>= m) is static — one
    compiled program per storage bucket. The ``index`` leaves carry ``m``
    (the block's token count rides the tree for fingerprinting)."""

    def fn(path, leaf):
        name = cache_leaf_name(path)
        refuse_slot_state(name, "a prefix block copied out of a row")
        ax = cache_batch_axis(name, leaf.ndim)
        if ax is None:  # index cursor → the prefix token count
            return jnp.full_like(leaf, m)
        col = ax + 1  # k/v AND kv_valid: column axis right after batch
        rolled = jnp.roll(leaf, -start, axis=col)
        sliced = jax.lax.slice_in_dim(rolled, 0, bucket, axis=col)
        window = _col_window(sliced.ndim, col, bucket, 0, m)
        if name == "kv_valid":
            return sliced & window
        return jnp.where(window, sliced, jnp.zeros_like(sliced))

    return jax.tree_util.tree_map_with_path(fn, cache)


def seed_cache_prefix(prefix, m, start, length: int):
    """Build a fresh batch-1 cache row of ``length`` columns whose columns
    ``[start, start + m)`` hold the stored prefix block's first ``m``
    tokens, with the write cursor at ``start + m`` — the explicit start
    cursor a suffix prefill continues from (its decode-path writes land at
    ``start + m``, its RoPE positions continue at the prefix's valid count
    ``m``). Everything outside the window is zero/invalid, so the row is
    indistinguishable from a full left-padded prefill of the same tokens as
    far as the attention math can see. ``m``/``start`` are traced (one
    compiled program per stored bucket); the prefix block is read, never
    aliased — the stored entry survives the call untouched."""

    def fn(path, leaf):
        name = cache_leaf_name(path)
        refuse_slot_state(name, "a row seeded from a prefix block")
        ax = cache_batch_axis(name, leaf.ndim)
        if ax is None:
            return jnp.full_like(leaf, start + m)
        col = ax + 1
        bucket = leaf.shape[col]
        pad = [(0, 0)] * leaf.ndim
        pad[col] = (0, length - bucket)
        full = jnp.pad(leaf, pad)
        rolled = jnp.roll(full, start, axis=col)
        window = _col_window(full.ndim, col, length, start, start + m)
        if name == "kv_valid":
            return rolled & window
        return jnp.where(window, rolled, jnp.zeros_like(rolled))

    return jax.tree_util.tree_map_with_path(fn, prefix)


def invalidate_cache_window(cache, start, keep):
    """Per-row post-hoc invalidation of a just-written column window — the
    speculative-decode acceptance primitive. A verify/draft pass writes
    ``width`` columns starting at ``start`` optimistically valid for every
    live row; acceptance then decides, PER ROW, how many of them belong to
    the final stream. This clears ``kv_valid`` for columns
    ``[start + keep[b], start + width)`` of each row ``b`` (``width`` is
    implied by the caller clamping ``keep``; columns at or beyond
    ``start + keep[b]`` up to the row end are ANDed against the keep
    window, which only ever narrows validity — columns outside
    ``[start, ∞)`` are untouched).

    Rejected draft columns become permanent invalid GAP columns: the
    attention math already runs off per-row validity counts
    (``valid_count_below`` positions, ``kv_valid`` masking), so a row's
    LOGICAL cursor advances by its own accepted length while the physical
    write cursor stays shared — this is what lets slots at different
    acceptance depths share one fused program with no per-slot cache
    reshaping. ``start`` is a traced scalar, ``keep`` a traced (B,) int32;
    K/V storage is untouched (masked columns are invisible)."""

    def fn(path, leaf):
        name = cache_leaf_name(path)
        if name != "kv_valid":
            return leaf
        ax = cache_batch_axis(name, leaf.ndim)
        col = ax + 1
        length = leaf.shape[col]
        cols = jnp.arange(length, dtype=jnp.int32)
        # broadcast keep over the batch axis, cols over the column axis;
        # any leading layer axis (nn.scan stacking) broadcasts for free
        kshape = [1] * leaf.ndim
        kshape[ax] = keep.shape[0]
        cshape = [1] * leaf.ndim
        cshape[col] = length
        cut = (
            cols.reshape(cshape)
            >= (start + keep.astype(jnp.int32)).reshape(kshape)
        ) & (cols.reshape(cshape) >= start)
        return leaf & jnp.logical_not(cut)

    return jax.tree_util.tree_map_with_path(fn, cache)


_SCALE_SUFFIX = "_scale"  # quantized-pool sibling leaves: k_scale / v_scale


def cache_node_at(tree, path):
    """Walk a cache tree to the node at ``path`` (tree_util DictKey path or
    plain key sequence) — the sibling-lookup primitive of the quantized
    paged transport (a ``k`` leaf's per-page scales live next door as
    ``k_scale``, which ``tree_map`` alone can never see)."""
    node = tree
    for k in path:
        node = node[k.key if hasattr(k, "key") else k]
    return node


def pool_scale_base(name: str):
    """``"k"``/``"v"`` if ``name`` is a quantized pool's scale sibling
    (``k_scale``/``v_scale``), else None — THE one copy of the sibling
    naming rule every pool walker classifies by."""
    if name.endswith(_SCALE_SUFFIX):
        base = name[: -len(_SCALE_SUFFIX)]
        if base in PAGED_LEAVES:
            return base
    return None


def pool_scale_sibling(pool, path, base: str):
    """The ``<base>_scale`` leaf next to the pool leaf at ``path``, or None
    on an unquantized pool — the one sibling lookup the quantized
    transports (gather/scatter/admit/seed/accounting) share."""
    parent = cache_node_at(pool, path[:-1])
    name = base + _SCALE_SUFFIX
    return parent[name] if name in parent else None


def _rebuild_tree(items):
    """Nested dict from ``(keys, leaf)`` pairs (the gather/seed side of the
    quantized pool, whose OUTPUT tree drops the scale siblings — the model
    must see exactly the k/v/index/kv_valid collection it always has)."""
    out: dict = {}
    for keys, leaf in items:
        node = out
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = leaf
    return out


# The key of the WINDOW kind's block table in a paged cache pytree: a model
# whose cache tree has window layers (:data:`WINDOW_LEAF`) is paged as
# ``{"pages": bt, "window_pages": bt_w, "pool": tree}``, the window layers'
# pool leaves sized for the window and mapped by ``bt_w``, whose entries
# behind the window read the null page; every other model as ``{"pages",
# "pool"}``.
WINDOW_PAGES = "window_pages"


def block_table_of(paged, path):
    """The block table that maps the pool leaf at ``path``: the window
    kind's for a leaf of a window layer's node, ``pages`` for every other."""
    if WINDOW_PAGES in paged and cache_node_window(
            cache_node_at(paged["pool"], path[:-1])) is not None:
        return paged[WINDOW_PAGES]
    return paged["pages"]


def with_pool(paged, pool):
    """``paged`` with its pool tree replaced (the block tables kept)."""
    return {**paged, "pool": pool}


def gather_cache_pages(paged, page_size: int):
    """Materialize the LOGICAL cache collection from a paged cache pytree
    ``{"pages": (B, n_log) int32 block table, "pool": tree}``: k/v pool
    leaves (..., P, page_size, Hkv, D) become logical rows (..., B, L, Hkv,
    D) via the block table; ``index``/``kv_valid`` (already logical) pass
    through. The result is bit-indistinguishable — for every VALID column —
    from the row-per-slot collection the same writes would have produced,
    so the whole decode/attention stack runs on it unchanged; unmapped
    logical pages surface null-page garbage in columns ``kv_valid`` already
    masks. Gather routes through the flash-decode module's paged transport
    (kernels/flash_decode.py), the same file the TPU decode kernel lives in.

    QUANTIZED pools (ISSUE 13) are self-describing: a ``k_scale``/
    ``v_scale`` sibling next to a k/v leaf marks int8 pages with per-page,
    per-kv-head scales, and the gather DEQUANTIZES into the scale leaf's
    (compute) dtype — the logical view the model sees is float either way,
    and the scale siblings never appear in it."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_gather_leaf,
        paged_gather_leaf_dequant,
    )
    from neuronx_distributed_tpu.utils.tree import path_keys

    pool = paged["pool"]
    items = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        keys = tuple(path_keys(path))
        name = keys[-1]
        if pool_scale_base(name) is not None:
            continue  # transport metadata — dropped from the logical view
        if name in PAGED_LEAVES:
            bt = block_table_of(paged, path)
            scale = pool_scale_sibling(pool, path, name)
            with jax.named_scope(KV_VIEW_SCOPE):
                leaf = (
                    paged_gather_leaf_dequant(leaf, scale, bt, page_size)
                    if scale is not None
                    else paged_gather_leaf(leaf, bt, page_size)
                )
        items.append((keys, leaf))
    return _rebuild_tree(items)


def scatter_cache_window(paged, logical, page_size: int, start_col,
                         width: int):
    """Fold a decode chunk's writes back into the paged pytree: the k/v
    pages overlapping columns ``[start_col, start_col + width)`` (the only
    columns a chunk may write — ``width`` static, ``start_col`` the traced
    entry cursor) are scattered through the block table; every other pool
    page is left untouched, which is exactly what keeps shared
    copy-on-write prefix pages bit-stable while their ref-holders decode.
    ``index``/``kv_valid`` (logical, per-slot) are adopted wholesale from
    ``logical``. Returns a fresh paged pytree (same treedef).

    On a QUANTIZED pool the window pages are re-quantized on the way out
    (per-page absmax → int8 pages + scale siblings; the scale recompute for
    the sibling leaf is CSE'd with the base leaf's inside the one jitted
    chunk). Pages outside the window keep their stored (int8, scale) pair
    untouched — the CoW bit-stability contract is unchanged."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_scatter_vals,
        paged_scatter_window_leaf,
        paged_window_vals,
        quantize_page_block,
    )

    pool = paged["pool"]
    n_log = paged["pages"].shape[1]
    # pages a width-column window can overlap, wherever it starts
    n_win = min((width - 1) // page_size + 2, n_log)
    page0 = jnp.asarray(start_col, jnp.int32) // page_size

    def fn(path, pool_leaf):
        name = cache_leaf_name(path)
        base = pool_scale_base(name) or name
        if base not in PAGED_LEAVES:
            # index / kv_valid: logical IS the storage
            return cache_node_at(logical, path[:-1])[name]
        bt = block_table_of(paged, path)
        lg = cache_node_at(logical, path[:-1])[base]
        if pool_scale_sibling(pool, path, base) is None:
            return paged_scatter_window_leaf(
                pool_leaf, lg, bt, page0, n_win, page_size
            )
        vals, idx = paged_window_vals(
            lg, bt, page0, n_win, page_size, lg.ndim - 4
        )
        q, s = quantize_page_block(vals)
        return paged_scatter_vals(pool_leaf, q if base == name else s, idx)

    with jax.named_scope(KV_VIEW_SCOPE):
        new_pool = jax.tree_util.tree_map_with_path(fn, pool)
    return with_pool(paged, new_pool)


# --- fused paged decode attention (ISSUE 14) ----------------------------------
#
# The ``gather`` transport materializes the whole logical K/V view before the
# model ever attends: on TPU an HBM round-trip of the full mapped cache per
# chunk that ``kernels/flash_decode.paged_flash_decode_attention`` (PR 12)
# exists to eliminate: the block table rides scalar prefetch and the kernel
# streams each slot's PHYSICAL pool pages directly. The trace-scope below is
# how the serving chunk routes attention through that kernel without
# touching the flax modules. Nothing attends a view there, so the chunk
# builds none: its cache holds, per per-token leaf, only the chunk's write
# WINDOW (:func:`fused_chunk_window`, ``n_win`` pages a slot), in which the
# model's decode write stages each new token (``KVCache.decode_write`` finds
# the column from the active frame). While a scope is active, every
# ``decode_attention`` call takes the next attention layer's (k, v) pool
# pair (layers call in execution order, the scope names them in the same
# order), scatters the window into it (the in-chunk columns the pool has
# not seen yet; the window's other columns rewrite their own bytes, so
# shared CoW pages stay bit-stable), attends straight off the pool through
# the fused kernel (compiled on the TPU, interpreted only in tests: it never
# degrades to the gather transport) and leaves the UPDATED pair in the
# scope's frame. The chunk builder enters the scope once per traced decode
# step with the pools its scan CARRIES and reads the step's pools back off
# the frame, so the pool is loop-carried state that XLA scatters into in
# place (PR 25: closed over, it was copied whole before every step's
# scatter).

_FUSED_PAGED_STACK: list = []


class fused_paged_attention_scope:
    """Trace-scope carrying the paged pool into the decode attention calls
    traced inside it. ``pools`` maps every attention layer to its
    ``(k_pool, v_pool)`` leaves (:func:`ordered_kv_pool_pairs`); ``page0``
    is the first logical page of the chunk's write window (the columns the
    pool does not hold yet), which is all the cache's per-token leaves hold
    (:func:`fused_chunk_window`). ``__enter__`` returns the frame: after the
    model apply its ``"pools"`` hold each layer's pair as that layer's
    window scatter left it. ``window_tables``: the block table of the window
    kind, for a model that has window layers."""

    def __init__(self, pools, tables, page_size: int, page0, window_tables=None):
        self.frame = {
            "pools": dict(pools), "order": _execution_order(pools),
            "tables": tables, "page_size": page_size, "page0": page0,
            "idx": 0,
            # the window kind's block table (a model with window layers);
            # ``floor``: each slot's lowest attendable column this step,
            # worked out by the step's first window layer for all of them
            "window_tables": window_tables, "floor": None,
        }

    def __enter__(self):
        _FUSED_PAGED_STACK.append(self.frame)
        return self.frame

    def __exit__(self, *exc):
        _FUSED_PAGED_STACK.pop()


def fused_paged_frame_active() -> bool:
    """Whether a :class:`fused_paged_attention_scope` is open: the decode step
    being traced is the fused paged chunk's, whose layers run their kernels."""
    return bool(_FUSED_PAGED_STACK)


def _fused_window_origin():
    """Logical column of the window leaves' column 0 in the active frame."""
    frame = _FUSED_PAGED_STACK[-1]
    return frame["page0"] * frame["page_size"]


def fused_chunk_window(paged, page_size: int, start_col, chunk_size: int):
    """``(cache, page0)`` for a fused decode chunk over the paged pytree:
    ``cache`` is the collection the model decodes on, its per-token leaves
    (:data:`PAGED_LEAVES`) holding ONLY the chunk's write window, ``(...,
    B, n_win * page_size, heads, width)``: the ``n_win = (chunk_size - 1)
    // page_size + 2`` logical pages a ``chunk_size``-column window can
    overlap wherever it starts, from page ``page0`` = that of ``start_col``
    (the traced entry cursor), held inside the row as
    :func:`scatter_cache_window` holds its own, gathered through the block
    table from those pages alone. ``index``/``kv_valid`` are the pool
    tree's, logical and whole."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_gather_window_leaf,
    )

    n_log = paged["pages"].shape[1]
    n_win = min((chunk_size - 1) // page_size + 2, n_log)
    page0 = jnp.clip(start_col // page_size, 0, n_log - n_win)

    def fn(path, leaf):
        if cache_leaf_name(path) not in PAGED_LEAVES:
            return leaf
        return paged_gather_window_leaf(leaf, block_table_of(paged, path), page0, n_win)

    with jax.named_scope(KV_VIEW_SCOPE):
        cache = jax.tree_util.tree_map_with_path(fn, paged["pool"])
    return cache, page0


def _execution_order(layers):
    """Cache-node tree paths (key tuples) in MODEL EXECUTION order: the order
    in which the attention calls of one model step take their nodes, which is
    the one ordering assumption of the fused transport. The rule, stated here
    and nowhere else:

    * a node's path names its layer with the layer's execution index, and the
      layers run in NATURAL order of their paths (``layers_10`` follows
      ``layers_9``; lexicographic flatten order would interleave them and hand
      layer 2 another layer's pages). Every sequential-layer family in this
      repo names its layers so.
    * a stack that is run more than once over one set of weights keeps, under
      each layer, one node a pass, named ``<LOOP_PASS_NODE><t>``
      (``models/ouro.py``). Such a stack runs PASS-MAJOR, pass 0's layers in
      their order and then pass 1's, so the pass is the outermost key. A path
      without such a component is in pass 0 of a stack run once: every other
      family's order is what the first rule alone gives."""
    import re

    def natural(keys):
        return tuple(
            tuple(
                int(part) if part.isdigit() else part
                for part in re.split(r"(\d+)", str(k))
                if part != ""
            )
            for k in keys
        )

    def passes(keys):
        named = (re.fullmatch(LOOP_PASS_NODE + r"(\d+)", str(k)) for k in keys)
        return tuple(int(m.group(1)) for m in named if m)

    return sorted(layers, key=lambda keys: (passes(keys), natural(keys)))


def ordered_kv_pool_pairs(pool):
    """``{layer: (k, v)}``: every attention layer's pool leaf pair under the
    layer's tree path (its key tuple), in model execution order
    (:func:`_execution_order`) — what the fused chunk carries through its
    scan. A latent-cache layer's pair is ``(k, k_pe)``: the leaves a layer
    holds, in :data:`PAGED_LEAVES` order."""
    from neuronx_distributed_tpu.utils.tree import path_keys

    nodes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        keys = tuple(path_keys(path))
        if keys[-1] in PAGED_LEAVES:
            nodes.setdefault(keys[:-1], {})[keys[-1]] = leaf
        elif pool_scale_base(keys[-1]) is not None:
            raise ValueError(
                "fused paged attention does not speak quantized pools "
                "(the in-kernel page stream is float) — use the gather "
                "transport with kv_quant"
            )
    return {
        layer: tuple(
            nodes[layer][name] for name in PAGED_LEAVES if name in nodes[layer]
        )
        for layer in _execution_order(nodes)
    }


def adopt_kv_pool_pairs(paged, logical, pairs):
    """The paged pytree (same treedef) whose k/v pool leaves are ``pairs``
    (``{layer: (k, v)}`` as :func:`ordered_kv_pool_pairs` keys it — the
    fused chunk's carried pools, current through its last executed step)
    and whose ``index``/``kv_valid`` (logical, per-slot) are adopted from
    ``logical``, as :func:`scatter_cache_window` adopts them."""
    from neuronx_distributed_tpu.utils.tree import path_keys

    def fn(path, _):
        *layer, name = path_keys(path)
        if name in PAGED_LEAVES:
            held = [n for n in PAGED_LEAVES if n in cache_node_at(paged["pool"], layer)]
            return pairs[tuple(layer)][held.index(name)]
        return cache_node_at(logical, path)

    return with_pool(paged, jax.tree_util.tree_map_with_path(fn, paged["pool"]))


def _next_fused_layer(frame):
    """The attention layer whose turn it is in the active frame (layers call
    in execution order)."""
    order = frame["order"]
    layer = order[frame["idx"] % len(order)]
    frame["idx"] += 1
    return layer


def _refuse_tree_mask(mask):
    if mask is not None:
        raise ValueError(
            "a tree mask replaces the positional mask the paged kernels "
            "implement, and a fused paged frame holds no K/V view for the "
            "einsum to attend: use the gather transport"
        )


def _fused_paged_decode(frame, q, caches, q_pos, kv_valid, latent_scale=None,
                        mask=None):
    """``caches``: the layer's window leaves as its cache kind orders them,
    ``(k, v)`` or a latent cache's ``(c, k_pe)``; for the latter ``q`` is the
    absorbed pair ``(q_c, q_r)`` and ``latent_scale`` the softmax scale. (An
    indexed cache's decode is :func:`_fused_sparse_decode`.)"""
    _refuse_tree_mask(mask)
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_flash_decode_attention,
        paged_latent_decode_attention,
        paged_scatter_window_pages,
    )

    pools = frame["pools"]
    layer = _next_fused_layer(frame)
    ps, bt = frame["page_size"], frame["tables"]
    # bring the pool current through THIS step: scatter the window (in which
    # the model just staged its token) into the carried pool. Its other
    # columns rewrite the bytes they were gathered with, so the scatter is
    # idempotent on shared pages
    # the scope ends BEFORE the kernel: a Pallas kernel is named after the
    # scope it is called in, and trace readers find it by that name
    with jax.named_scope(KV_VIEW_SCOPE):
        pair = tuple(
            paged_scatter_window_pages(pool, window, bt, frame["page0"])
            for pool, window in zip(pools[layer], caches)
        )
    pools[layer] = pair  # trace-time: the step's carry-out
    if latent_scale is not None:
        return paged_latent_decode_attention(
            *q, *pair, bt, q_pos, kv_valid=kv_valid, scale=latent_scale,
            page_size=ps,
        )
    return paged_flash_decode_attention(
        q, *pair, bt, q_pos, kv_valid=kv_valid, page_size=ps
    )


def _fused_sparse_decode(frame, q, q_idx, w_idx, caches, q_pos, kv_valid, topk,
                         latent_scale=None):
    """An indexed cache's step in the active frame: ``caches`` the layer's
    window leaves ``(kv, k_idx)``. With ``latent_scale`` (an
    :class:`IndexedLatentKVCache`) ``q`` is the absorbed pair ``(q_c, q_r)``
    and the attend kernel the latent one. The windows go into the carried pools (the
    joined K/V pool through a KERNEL, :func:`~neuronx_distributed_tpu.
    kernels.flash_decode.paged_scatter_window_pages_dma`: every user of that
    pool inside the decode scan is then a kernel of one layout; the 64-wide
    index keys as a latent cache's rotated key goes), then the three sparse
    kernels run off the pools, each called in ITS scope and named after it."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_index_scores,
        paged_scatter_window_pages,
        paged_scatter_window_pages_dma,
        paged_sparse_decode_attention,
        paged_sparse_latent_decode_attention,
    )

    pools = frame["pools"]
    layer = _next_fused_layer(frame)
    ps, bt, page0 = frame["page_size"], frame["tables"], frame["page0"]
    kv_pool, idx_pool = pools[layer]
    with jax.named_scope(KV_VIEW_SCOPE):
        idx_pool = paged_scatter_window_pages(idx_pool, caches[1], bt, page0)
    with jax.named_scope(DSA_WRITE_SCOPE):
        kv_pool = paged_scatter_window_pages_dma(kv_pool, caches[0], bt, page0)
    pools[layer] = (kv_pool, idx_pool)  # trace-time: the step's carry-out
    with jax.named_scope(DSA_SCORE_SCOPE):
        scores = paged_index_scores(
            q_idx, w_idx, idx_pool, bt, q_pos, kv_valid, page_size=ps)
    with jax.named_scope(DSA_SELECT_SCOPE):
        scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 ties as +0.0
        vals, cols = jax.lax.top_k(scores, min(topk, scores.shape[1]))
        n_sel = jnp.sum(vals > -jnp.inf, axis=1).astype(jnp.int32)
    with jax.named_scope(DSA_ATTEND_SCOPE):
        if latent_scale is not None:
            return paged_sparse_latent_decode_attention(
                *q, kv_pool, bt, cols, n_sel, scale=latent_scale, page_size=ps)
        return paged_sparse_decode_attention(
            q, kv_pool, bt, cols, n_sel, page_size=ps)


def _fused_walk_decode(frame, q, kv_window, q_pos, kv_valid, window):
    """A joined K/V cache's step in the active frame (:class:`JoinedKVCache`:
    both kinds of layer of a stack that mixes window and full attention):
    ``kv_window`` the layer's window leaf. The window's pages go into the
    carried pool through a kernel (as an indexed cache's joined leaf goes:
    every user of that pool inside the decode scan is then a kernel of one
    layout), through the block table of the layer's KIND; then the kernel
    that walks the blocks a slot maps attends straight off the pool. For a
    window layer the table maps nothing behind the window (the manager freed
    those pages), so the walk IS the window, and ``floor`` (each slot's
    lowest attendable column, counted in tokens over ``kv_valid``) trims the
    first block. The caller's scope names both kernels."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_scatter_window_pages_dma,
        paged_walk_decode_attention,
    )

    if q.shape[1] != 1:
        raise ValueError(f"one query row a slot in a fused paged frame, got {q.shape[1]}")
    pools = frame["pools"]
    layer = _next_fused_layer(frame)
    bt = frame["tables"]
    floor = None
    if window is not None:
        bt = frame["window_tables"]
        if bt is None:
            raise ValueError(
                "a window layer in a fused paged frame without the window "
                "kind's block table: the cache manager was not told the window")
        if frame["floor"] is None:
            frame["floor"] = window_floor(kv_valid, q_pos[0], window)
        floor = frame["floor"]
    (kv_pool,) = pools[layer]
    kv_pool = paged_scatter_window_pages_dma(kv_pool, kv_window, bt, frame["page0"])
    pools[layer] = (kv_pool,)  # trace-time: the step's carry-out
    return paged_walk_decode_attention(
        q, kv_pool, bt, q_pos, kv_valid=kv_valid, floor=floor,
        page_size=frame["page_size"])


def joined_decode_attention(q, kv_cache, q_pos, kv_valid, window: Optional[int] = None):
    """GQA attention of q (B, S, H, D) rows at cache columns ``q_pos`` (S,)
    against a :class:`JoinedKVCache` leaf (B, L, 2 Hkv, D), each row masked at
    its own column, by ``kv_valid`` (B, L) and, for a WINDOW layer, to its
    last ``window`` tokens (:func:`window_keep`). Inside a
    :class:`fused_paged_attention_scope` it attends the page pool through
    ``kernels/flash_decode.paged_walk_decode_attention`` (the kernel, or
    nothing); elsewhere this float32 einsum, on every platform (there is no
    row-cache kernel with a window's lower edge: the engine records
    ``decode_attention: "einsum"``)."""
    if _FUSED_PAGED_STACK:
        return _fused_walk_decode(
            _FUSED_PAGED_STACK[-1], q, kv_cache, q_pos, kv_valid, window)
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    k, v = split_kv(kv_cache)
    return _masked_gqa_attention(q, k, v, window_keep(kv_valid, q_pos, window))


def window_prefill_attention(q, k, v, window: Optional[int] = None, impl: str = "auto",
                             mask: Optional[jax.Array] = None):
    """Causal GQA attention of a prompt, q (B, S, H, D) over k/v (B, S, Hkv,
    D), each query reading its last ``window`` keys only; ``mask`` (B, S) True
    at valid (not padded) keys. ``window=None`` (a full layer) is
    :func:`attention_op`: the flash forward every other model's prefill runs.
    A window layer runs, on the TPU, the banded flash forward (``kernels/
    flash_attention.banded_flash_attention``: tiles wholly outside the band
    or the prompt are skipped, only the tiles their edges cut are masked;
    forward only), elsewhere and for ``impl="xla"``
    (training differentiates) the float32 einsum under the mask built from
    indices. A prompt's padding is on ONE side, so its valid keys are adjacent
    and a window of columns is a window of tokens."""
    if window is None:
        return attention_op(q, k, v, causal=True, impl=impl, mask=mask)
    b, s = q.shape[0], q.shape[1]
    if backend.resolve_attention_impl(impl) == "flash":
        from neuronx_distributed_tpu.kernels.flash_attention import (
            banded_flash_attention,
        )

        return banded_flash_attention(q, k, v, window=window, kv_valid=mask)
    rows = jnp.arange(s, dtype=jnp.int32)
    keep = (rows[:, None] >= rows[None, :]) & (rows[None, :] > rows[:, None] - window)
    keep = jnp.broadcast_to(keep[None], (b, s, s))
    if mask is not None:
        keep = keep & mask.astype(jnp.bool_)[:, None, :]
    return _masked_gqa_attention(q, k, v, keep)


def cache_fingerprint(cache):
    """Cheap integrity fingerprint of a cache(-prefix) tree — now owned by
    ``utils/fingerprint.py`` (one home for every integrity hash; see the
    SDC sentinel); this name stays as the historical import site for the
    serving engine's prefix validation."""
    from neuronx_distributed_tpu.utils.fingerprint import (
        cache_fingerprint as _impl,
    )

    return _impl(cache)


# cache length at which decode switches from the fused einsum to the Pallas
# flash-decode kernel on TPU: below this the (s, L) score tensor is small and
# the einsum path's simplicity wins; above it the kernel's single streaming
# pass over the cache (and its slot-bound block skipping) pays for itself
FLASH_DECODE_MIN_CONTEXT = 1024


def resolve_decode_impl(cache_len: int) -> str:
    """What :func:`decode_attention` runs for a cache of ``cache_len``
    columns outside a fused-paged scope: ``"flash_decode"`` (the Pallas
    kernel — long caches on the TPU) or ``"einsum"``. Kept as a function so
    the serving engine records the same answer the trace takes."""
    if cache_len >= FLASH_DECODE_MIN_CONTEXT and backend.on_tpu():
        return "flash_decode"
    return "einsum"


def decode_attention(q, k_cache, v_cache, q_pos, mask=None, kv_valid=None):
    """Attention of q (B, S, H, D) rows at positions ``q_pos`` (S,) against
    the full cache (B, L, Hkv, D), each row masked at its own position — the
    single-block special case of the ring kernel's block primitive.
    ``mask`` (S, L) overrides the positional mask (Medusa tree attention);
    ``kv_valid`` (B, L) bool masks per-batch padding slots in the cache
    (padded-prompt serving).

    Long caches on TPU route to the Pallas flash-decode kernel
    (kernels/flash_decode.py — the reference's flash-decoding KV groups,
    parallel_state.py:1368); Medusa tree steps keep the einsum (their
    ``mask`` replaces the positional mask the kernel implements).

    Inside a :class:`fused_paged_attention_scope` (the serving chunk's
    ``paged_attention="fused"`` transport, ISSUE 14) the call attends the
    PAGED POOL directly through ``paged_flash_decode_attention`` instead of
    the materialized view passed in."""
    if _FUSED_PAGED_STACK:
        return _fused_paged_decode(
            _FUSED_PAGED_STACK[-1], q, (k_cache, v_cache), q_pos, kv_valid,
            mask=mask,
        )
    if mask is None and resolve_decode_impl(k_cache.shape[1]) == "flash_decode":
        from neuronx_distributed_tpu.kernels.flash_decode import (
            flash_decode_attention,
        )

        return flash_decode_attention(q, k_cache, v_cache, q_pos, kv_valid)
    from neuronx_distributed_tpu.kernels.ring_attention import _block_attn

    b, s, h, d = q.shape
    hkv = k_cache.shape[2]
    qt = jnp.swapaxes(q, 1, 2).reshape(b, hkv, h // hkv, s, d)
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    k_pos = jnp.arange(k_cache.shape[1])
    num, _, l = _block_attn(
        qt, kt, vt, q_pos, k_pos, causal=True, mask=mask, kv_valid=kv_valid
    )
    out = num / jnp.maximum(l, 1e-20)[..., None]
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2).astype(q.dtype)


def latent_decode_attention(q_c, q_r, c_cache, r_cache, q_pos, scale,
                            mask=None, kv_valid=None):
    """ABSORBED multi-head latent attention of query rows at positions
    ``q_pos`` (S,) against a latent cache: ``q_c`` (B, S, H, d_c) are the
    heads' content queries with ``W_uk`` folded in, ``q_r`` (B, S, H, d_r)
    their rotated part; ``c_cache`` (B, L, 1, d_c) and ``r_cache`` (B, L, 1,
    d_r) the :class:`LatentKVCache` leaves. Scores ``(q_c . c + q_r . k_pe)
    * scale``, positional/``kv_valid``/``mask`` masking as
    :func:`decode_attention`, softmax in float32, values the latent rows:
    returns (B, S, H, d_c) for the caller's ``W_uv``.

    Inside a :class:`fused_paged_attention_scope` it attends the page pool
    through ``paged_latent_decode_attention`` (the kernel, or nothing);
    elsewhere this einsum, on every platform (there is no row-cache latent
    kernel: the engine records ``decode_attention: "einsum"``)."""
    if _FUSED_PAGED_STACK:
        return _fused_paged_decode(
            _FUSED_PAGED_STACK[-1], (q_c, q_r), (c_cache, r_cache), q_pos,
            kv_valid, latent_scale=scale, mask=mask,
        )
    c = c_cache[:, :, 0].astype(jnp.float32)           # (B, L, d_c)
    s = (
        jnp.einsum("bshd,bld->bhsl", q_c.astype(jnp.float32), c)
        + jnp.einsum("bshd,bld->bhsl", q_r.astype(jnp.float32),
                     r_cache[:, :, 0].astype(jnp.float32))
    ) * scale
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    if mask is None:
        mask = q_pos[:, None] >= jnp.arange(c.shape[1])[None]   # (S, L)
    ok = mask[None, None]
    if kv_valid is not None:
        ok = ok & kv_valid[:, None, None, :]
    s = jnp.where(ok, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    out = jnp.einsum("bhsl,bld->bshd", p, c)
    denom = jnp.swapaxes(p.sum(-1), 1, 2)[..., None]   # (B, S, H, 1)
    return (out / jnp.maximum(denom, 1e-20)).astype(q_c.dtype)


# --- learned sparse attention: an indexer beside GQA ------------------------------
#
# DeepSeek-Sparse-Attention's lightning indexer, as Keye-VL-2.0 configures it:
# per token ``H_i`` index queries of ``d_i`` and ONE index key of ``d_i``
# (cached: :class:`IndexedKVCache`), a weight a head; the score of query ``t``
# for key ``s`` is ``I[t, s] = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])``; a
# query attends the ``min(t + 1, topk)`` causal keys of largest score, ties to
# the lower position (``jax.lax.top_k``'s rule), one set for all heads.

# The TPU builds a prompt's learned mask in ONE kernel (``kernels/flash_attention
# .sparse_keep_mask_kernel``) whose key tiles are multiples of this: a shorter
# bucket is padded up to it (the engine's buckets past 512 are multiples
# already). The einsum and :func:`topk_mask`, the same set, are the CPU's form
# and the row cache's.
PREFILL_MASK_KERNEL_MULTIPLE = 512

# Query rows a tile of the prefill's index scores and of the row-cache decode
# (Keye-VL-2.0's ``q_chunk_size``): no (rows x keys) array is ever wider.
DSA_QUERY_CHUNK = 512


def index_scores(q_idx, w_idx, k_idx):
    """``I`` (B, T, S) float32 of index queries ``q_idx`` (B, T, H_i, d_i)
    with weights ``w_idx`` (B, T, H_i) against index keys ``k_idx`` (B, S,
    d_i): a head at a time, so that only one (T, S) product stands beside the
    sum. Zeros are +0.0 (``relu`` times a negative weight gives -0.0, which a
    bit-ordered selection would rank below)."""

    def head(acc, xs):
        qh, wh = xs                                            # (B, T, d_i), (B, T)
        s = jnp.einsum("btd,bsd->bts", qh, k_idx,
                       preferred_element_type=jnp.float32)
        return acc + jax.nn.relu(s) * wh.astype(jnp.float32)[..., None], None

    b, t = q_idx.shape[:2]
    acc, _ = jax.lax.scan(
        head, jnp.zeros((b, t, k_idx.shape[1]), jnp.float32),
        (jnp.moveaxis(q_idx, 2, 0), jnp.moveaxis(w_idx, 2, 0)))
    return jnp.where(acc == 0, 0.0, acc)


def topk_mask(scores, ok, topk: int):
    """Boolean mask (..., S) of each row's ``min(count(ok), topk)`` largest
    ``scores`` among the columns where ``ok``, ties to the lower position:
    exactly the set ``jax.lax.top_k`` returns, found WITHOUT a sort: the
    ``k``-th largest value by bisection over the bits of the float32 scores
    (32 counting passes), then the ties at that value in position order
    (-0.0 ties with 0.0)."""
    scores = scores.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(ok, jnp.where(scores == 0, 0.0, scores), -jnp.inf), jnp.uint32)
    # a monotone map of float32 onto uint32: negative floats reversed
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    k = jnp.minimum(jnp.sum(ok, axis=-1), topk).astype(jnp.int32)[..., None]

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(k.shape, jnp.uint32))
    above, tie = key > thr, key == thr
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    return ok & (above | (tie & (jnp.cumsum(tie, axis=-1) <= need)))


def _masked_gqa_attention(q, k, v, keep):
    """Softmax attention of q (B, T, H, D) over the keys of k/v (B, S, Hkv,
    D) where ``keep`` (B, T, S): float32 einsum, the golden path. A row that
    keeps nothing (a padded query) returns zeros."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, t, hkv, h // hkv, d).astype(jnp.float32)
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k.astype(jnp.float32)) / jnp.sqrt(
        jnp.float32(d))
    ok = keep[:, None, None]
    s = jnp.where(ok, s, -1e30)
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    out = jnp.einsum("bhgts,bshd->bthgd", p, v.astype(jnp.float32))
    den = jnp.moveaxis(p.sum(-1), (1, 2, 3), (2, 3, 1))[..., None]
    return (out / jnp.maximum(den, 1e-30)).reshape(b, t, h, d).astype(q.dtype)


def _query_chunks(fn, arrays, t: int):
    """``fn`` over chunks of :data:`DSA_QUERY_CHUNK` query rows (axis 1 of
    every array), one chunk's temporaries at a time (``lax.map``)."""
    chunk = DSA_QUERY_CHUNK
    if t <= chunk:
        return fn(*arrays)
    n = -(-t // chunk)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    split = lambda a: jnp.moveaxis(  # noqa: E731
        pad(a).reshape((a.shape[0], n, chunk) + a.shape[2:]), 1, 0)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(split(a) for a in arrays))
    return jnp.moveaxis(out, 0, 1).reshape((out.shape[1], n * chunk) + out.shape[3:])[:, :t]


def sparse_keep_mask(q_idx, w_idx, k_idx, q_pos, k_valid, topk: int,
                     dtype=jnp.bool_):
    """The learned mask (B, T, S), one byte a pair (``dtype``): query row ``t`` (at key position
    ``q_pos[b, t]``) keeps key ``s`` iff ``s`` is valid (``k_valid`` (B, S)),
    not in its future, and among its ``topk`` best by index score. Built a
    chunk of query rows at a time."""
    cols = jnp.arange(k_idx.shape[1], dtype=jnp.int32)

    def chunk(qi, wi, pos):
        ok = (pos[:, :, None] >= cols[None, None]) & k_valid[:, None, :]
        return topk_mask(index_scores(qi, wi, k_idx), ok, topk).astype(dtype)

    return _query_chunks(chunk, (q_idx, w_idx, q_pos), q_idx.shape[1])


def sparse_prefill_attention(q, k, v, q_idx, w_idx, k_idx, topk: int,
                             impl: str = "auto", mask=None):
    """Causal GQA self-attention of a prompt under the learned mask: q (B, S,
    H, D), k/v (B, S, Hkv, D), index queries/weights/keys as
    :func:`index_scores`; ``mask`` (B, S) True at valid (non-padding) tokens.
    The mask is ONE byte a (query, key) pair and nothing wider is ever S x S:
    scores and thresholds are taken a block of query rows at a time (on the
    TPU inside one kernel, the scores never leaving VMEM). On the TPU the
    flash kernel reads the byte mask tile by tile
    (``kernels/flash_attention.masked_flash_attention``: dense work over the
    prompt's own causal tiles, sparse result; a padded row reads as zeros
    there); elsewhere the float32 einsum."""
    b, s = q.shape[0], q.shape[1]
    valid = jnp.ones((b, s), jnp.bool_) if mask is None else mask.astype(jnp.bool_)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    flash = backend.resolve_attention_impl(impl) == "flash"
    with jax.named_scope(DSA_SCORE_SCOPE):
        if flash:
            # scores, thresholds and mask in one kernel: no score reaches HBM
            from neuronx_distributed_tpu.kernels.flash_attention import (
                sparse_keep_mask_kernel,
            )

            args = (q_idx, w_idx, k_idx[:, :, 0], valid)
            pad = -s % PREFILL_MASK_KERNEL_MULTIPLE
            if pad:     # padded keys are invalid; padded rows are cut off again
                args = tuple(
                    jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in args)
            keep = sparse_keep_mask_kernel(*args, topk)[:, :s, :s]
        else:
            keep = sparse_keep_mask(q_idx, w_idx, k_idx[:, :, 0], pos, valid, topk)
    with jax.named_scope(DSA_ATTEND_SCOPE):
        if flash:
            from neuronx_distributed_tpu.kernels.flash_attention import (
                masked_flash_attention,
            )

            # the prompt's extent too: the kernel does the prompt's work, not the bucket's
            return masked_flash_attention(q, k, v, keep, valid)
        return _query_chunks(
            lambda qc, kc: _masked_gqa_attention(qc, k, v, kc), (q, keep), s)


def indexed_decode_attention(q, q_idx, w_idx, kv_cache, idx_cache, q_pos,
                             topk: int, kv_valid=None):
    """Sparse GQA attention of decode rows against an :class:`IndexedKVCache`:
    q (B, S, H, D) rows at slot positions ``q_pos`` (S,), their index queries
    ``q_idx`` (B, S, H_i, d_i) and weights ``w_idx`` (B, S, H_i); the cache
    leaves ``kv`` (B, L, 2 Hkv, D) and ``k_idx`` (B, L, 1, d_i). Each row
    scores every valid column at or before its position, keeps the ``topk``
    best and attends those alone.

    Inside a :class:`fused_paged_attention_scope` the three kernels of
    ``kernels/flash_decode.py`` run off the page pool (index scores over the
    blocks a slot maps, ``top_k``, K and V of the selected tokens only);
    elsewhere (a row cache: a suffix prefill, ``generate()``) the same
    mathematics as float32 einsums under :func:`sparse_keep_mask`, on the
    leaf split into K and V."""
    if _FUSED_PAGED_STACK:
        return _fused_sparse_decode(
            _FUSED_PAGED_STACK[-1], q, q_idx, w_idx, (kv_cache, idx_cache),
            q_pos, kv_valid, topk,
        )
    b, s = q.shape[0], q.shape[1]
    k_cache, v_cache = split_kv(kv_cache)
    valid = (jnp.ones(k_cache.shape[:2], jnp.bool_) if kv_valid is None
             else kv_valid.astype(jnp.bool_))
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    pos = jnp.broadcast_to(q_pos.astype(jnp.int32)[None], (b, s))
    with jax.named_scope(DSA_SCORE_SCOPE):
        keep = sparse_keep_mask(q_idx, w_idx, idx_cache[:, :, 0], pos, valid, topk)
    with jax.named_scope(DSA_ATTEND_SCOPE):
        return _query_chunks(
            lambda qc, kc: _masked_gqa_attention(qc, k_cache, v_cache, kc),
            (q, keep), s)


def _masked_latent_attention(q_c, q_r, c, k_pe, keep, scale):
    """Absorbed latent attention of rows q_c (B, T, H, d_c) / q_r (B, T, H,
    d_r) over the columns of ``c`` (B, S, d_c) / ``k_pe`` (B, S, d_r) where
    ``keep`` (B, T, S): float32 einsums, the golden path. Returns (B, T, H,
    d_c); a row that keeps nothing returns zeros."""
    c = c.astype(jnp.float32)
    s = (
        jnp.einsum("bthd,bsd->bhts", q_c.astype(jnp.float32), c)
        + jnp.einsum("bthd,bsd->bhts", q_r.astype(jnp.float32), k_pe.astype(jnp.float32))
    ) * scale
    ok = keep[:, None]
    s = jnp.where(ok, s, -1e30)
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    out = jnp.einsum("bhts,bsd->bthd", p, c)
    den = jnp.swapaxes(p.sum(-1), 1, 2)[..., None]
    return (out / jnp.maximum(den, 1e-30)).astype(q_c.dtype)


def indexed_latent_decode_attention(q_c, q_r, q_idx, w_idx, kv_cache, idx_cache,
                                    q_pos, topk: int, scale: float, kv_valid=None):
    """Sparse ABSORBED latent attention of decode rows against an
    :class:`IndexedLatentKVCache`: ``q_c`` (B, S, H, d_c) the heads' content
    queries with ``W_uk`` folded in, ``q_r`` (B, S, H, d_r) their rotated
    part, index queries and weights as :func:`indexed_decode_attention`; the
    cache leaves ``kv`` (B, L, rows, lanes) and ``k_idx`` (B, L, 1, d_i).
    Each row scores every valid column at or before its position, keeps the
    ``topk`` best and attends those latents alone, softmax in float32 over
    them: (B, S, H, d_c) for the caller's ``W_uv``.

    Inside a :class:`fused_paged_attention_scope` the index-score kernel,
    ``top_k`` and the sparse latent kernel run off the page pool; elsewhere
    (a row cache) float32 einsums under :func:`sparse_keep_mask`."""
    if _FUSED_PAGED_STACK:
        return _fused_sparse_decode(
            _FUSED_PAGED_STACK[-1], (q_c, q_r), q_idx, w_idx,
            (kv_cache, idx_cache), q_pos, kv_valid, topk, latent_scale=scale,
        )
    b, s = q_c.shape[0], q_c.shape[1]
    c, k_pe = split_latent(kv_cache, q_c.shape[-1], q_r.shape[-1])
    valid = (jnp.ones(c.shape[:2], jnp.bool_) if kv_valid is None
             else kv_valid.astype(jnp.bool_))
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    pos = jnp.broadcast_to(q_pos.astype(jnp.int32)[None], (b, s))
    with jax.named_scope(DSA_SCORE_SCOPE):
        keep = sparse_keep_mask(q_idx, w_idx, idx_cache[:, :, 0], pos, valid, topk)
    with jax.named_scope(DSA_ATTEND_SCOPE):
        return _query_chunks(
            lambda qc, qr, kc: _masked_latent_attention(qc, qr, c, k_pe, kc, scale),
            (q_c, q_r, keep), s)


class ParallelSelfAttention(nn.Module):
    """Multi-head self-attention with TP-sharded heads.

    ``rotary_pct`` ∈ (0, 1] applies RoPE to the first ``rotary_pct`` fraction
    of each head dim (GPT-NeoX partial rotary); 0 disables RoPE (BERT/ViT use
    learned positions instead). ``mode`` selects KV-cache behaviour for
    causal LMs (train | prefill | decode — the same contract as
    LlamaAttention, reference StateInitializer cache trace/spmd.py:49).
    """

    hidden_size: int
    num_heads: int
    num_kv_heads: Optional[int] = None
    causal: bool = False
    use_bias: bool = True
    rotary_pct: float = 0.0
    rope_theta: float = 10000.0
    max_seq_len: int = 2048
    sequence_parallel_enabled: bool = False
    attention_impl: str = "auto"
    mode: str = "train"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def _rope(self, q, k, positions):
        if self.rotary_pct <= 0.0:
            return q, k
        d = self.hidden_size // self.num_heads
        rot = int(d * self.rotary_pct)
        rot -= rot % 2
        freqs = rope_frequencies(rot, self.max_seq_len, self.rope_theta)
        q = jnp.concatenate(
            [apply_rope(q[..., :rot], freqs, positions), q[..., rot:]], -1
        )
        k = jnp.concatenate(
            [apply_rope(k[..., :rot], freqs, positions), k[..., rot:]], -1
        )
        return q, k

    @nn.compact
    def __call__(self, x, positions=None, attention_mask: Optional[jax.Array] = None,
                 segment_ids: Optional[jax.Array] = None):
        """``attention_mask`` (B, S): True at valid (non-padding) positions.
        ``segment_ids`` (B, S): packed-document isolation (train mode). Both
        ride the flash kernel's segment path on TPU; in KV-cache modes the
        mask persists in the cache (``kv_valid``) so later decode steps keep
        padded slots masked."""
        h = self.num_heads
        hkv = self.num_kv_heads or h
        d = self.hidden_size // h
        q, k, v = GQAQKVColumnParallelLinear(
            hidden_size=self.hidden_size,
            num_heads=h,
            num_kv_heads=hkv,
            head_dim=d,
            use_bias=self.use_bias,
            sequence_parallel_enabled=self.sequence_parallel_enabled,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="qkv",
        )(x)
        b, s = q.shape[0], q.shape[1]
        q = q.reshape(b, s, h, d)
        k = k.reshape(b, s, hkv, d)
        v = v.reshape(b, s, hkv, d)
        q = constrain(q, P(UNC, UNC, mesh_lib.TP_AXIS))
        if self.mode == "train":
            q, k = self._rope(q, k, positions)
            # what the attention receives: a layer rematerialised under
            # "mlp_up+attn" keeps these (modules/remat.py); elsewhere the
            # name is the identity
            q, k, v = (checkpoint_name(t, ATTN_QKV) for t in (q, k, v))
            out = attention_op(
                q, k, v, causal=self.causal, impl=self.attention_impl,
                mask=attention_mask, segment_ids=segment_ids,
            )
        else:
            out = self._cached_attention(q, k, v, positions, attention_mask)
        out = out.reshape(b, s, h * d)
        return RowParallelLinear(
            h * d,
            self.hidden_size,
            use_bias=self.use_bias,
            sequence_parallel_enabled=self.sequence_parallel_enabled,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="o_proj",
        )(out)

    def _cached_attention(self, q, k, v, positions, attention_mask=None):
        if not self.causal:
            raise ValueError("KV-cache modes require causal attention")
        b, s = q.shape[0], q.shape[1]
        hkv = self.num_kv_heads or self.num_heads
        d = self.hidden_size // self.num_heads
        cache = KVCache(self, b, self.max_seq_len, hkv, d, q.dtype)
        if self.mode == "prefill":
            if positions is None and attention_mask is not None:
                positions = prefill_positions(attention_mask)
            q, k = self._rope(q, k, positions)
            cache.prefill_write(k, v, attention_mask)
            return attention_op(
                q, k, v, causal=True, impl=self.attention_impl,
                mask=attention_mask,
            )
        if self.mode != "decode":
            raise ValueError(f"unknown attention mode {self.mode!r}")
        pos, rope_pos = cache.decode_positions(s, positions)
        q, k = self._rope(q, k, rope_pos)
        cache.decode_write(k, v, attention_mask)
        return decode_attention(
            q, cache.k.value, cache.v.value, pos, kv_valid=cache.valid.value
        )


class ParallelMLP(nn.Module):
    """Plain 2-layer MLP: CPL → activation → RPL (BERT/NeoX/ViT FFN).
    ``glu`` gates it: ``down(act(gate(x)) * up(x))`` (SwiGLU with
    ``activation="silu"``: DeepSeek-V2's dense layer and shared experts).

    The up-projection's output, before the activation, carries the name
    ``modules.remat.MLP_UP`` (under ``glu`` the gate's does too): a layer
    rematerialised under the policies ``"mlp_up"`` and ``"mlp_up+attn"`` keeps
    it through the backward pass, ``B S I / tp`` values a layer a chip (twice
    that under ``glu``), and does not run the up-projection a second time; the
    activation is still recomputed from it. Anywhere else the name is the
    identity."""

    hidden_size: int
    intermediate_size: int
    activation: str = "gelu"
    use_bias: bool = True
    glu: bool = False
    sequence_parallel_enabled: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from neuronx_distributed_tpu.parallel.layers import ColumnParallelLinear

        act = {
            "gelu": lambda x: jax.nn.gelu(x, approximate=False),  # exact erf GELU
            "gelu_new": jax.nn.gelu,  # tanh approximation
            "relu": jax.nn.relu,
            "silu": jax.nn.silu,
        }[self.activation]
        common = dict(
            use_bias=self.use_bias,
            sequence_parallel_enabled=self.sequence_parallel_enabled,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        y = checkpoint_name(ColumnParallelLinear(
            self.hidden_size, self.intermediate_size, name="up", **common
        )(x), MLP_UP)
        if self.glu:
            y = act(checkpoint_name(ColumnParallelLinear(
                self.hidden_size, self.intermediate_size, name="gate", **common
            )(x), MLP_UP)) * y
        else:
            y = act(y)
        return RowParallelLinear(
            self.intermediate_size, self.hidden_size, name="down", **common
        )(y)
