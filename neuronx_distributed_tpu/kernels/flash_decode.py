"""Pallas flash-decode: cached attention for serving (reference: the
flash-decoding machinery behind KV-replication groups + ``num_cores_per_group``
— ``parallel_state.py:1368``, ``arrange_kv_groups:1500``,
``trace/model_builder.py:219``).

Decode attends a handful of query rows (1 token, a speculative verify window,
or a Medusa tree) against a LONG KV cache. The einsum path materializes the
(B, H, s, L) fp32 score tensor in HBM and walks the cache in two passes
(QK^T, then PV); at 8k-32k context that tensor and the second pass dominate
decode latency. This kernel is the decode analogue of the flash kernel: grid
``(B, Hkv, nL)`` with the cache-length dim innermost and sequential, carrying
the online-softmax state (m, l, acc) for all of a kv-head's query rows
(GQA group × s — a few dozen) in VMEM scratch, one fused pass, nothing
written to HBM but the (B, Hkv, R, D) output and its LSE.

Masking: each query row carries its cache-slot position (rows attend slots
``<= pos``), and an optional ``kv_valid`` (B, L) mask drops padded prompt
slots (the serving stack's persisted padding, modules/attention.py KVCache).
Cache blocks entirely beyond every row's position are skipped via an SMEM
bound.

TP layout (the reference's KV-group design, re-derived for GSPMD): kv heads
shard over tp when ``hkv % tp == 0``; when ``tp > hkv`` the excess factor
``tp // hkv`` SPLITS THE CACHE LENGTH instead — each rank scans its L-slice
and the partials merge with an exp-weighted psum over (max-shifted) LSE.
That is exactly ``num_cores_per_group``: more cores than kv heads cooperate
on one head's cache scan instead of idling (or replicating KV in HBM).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.kernels.backend import interpret_mode
from neuronx_distributed_tpu.kernels.flash_attention import (
    _SMEM_SPEC,
    _pick_block,
)

NEG_INF = -1e30


# --- paged KV: block-table gather/scatter -------------------------------------
#
# The serving engine's paged cache stores K/V as a POOL of fixed-size pages
# (..., num_pages, page_size, Hkv, D) plus a per-slot block table (B, n_log)
# mapping logical page j of slot b to a physical pool page. These two ops are
# the whole paged transport: gather materializes the logical (..., B, L, Hkv,
# D) view the attention math (and, on TPU, the flash-decode kernel above)
# already speaks, and the window scatter writes back ONLY the pages a decode
# chunk could have touched — shared copy-on-write prefix pages outside the
# window are never rewritten. On TPU the gather feeds ``flash_decode_attention``
# unchanged (the kernel is oblivious to where its cache slice came from);
# ``paged_flash_decode_attention`` below folds the page lookup into the
# kernel's block index map instead — the entry point the TP serving item
# routes through once attention carries the paged transport. Both transport
# ops here are pure jnp (no pallas) so they trace inside the engine's
# donated decode chunk on any backend.


def paged_gather_leaf(pool: jax.Array, block_table: jax.Array,
                      page_size: int) -> jax.Array:
    """Materialize the logical cache view of one pool leaf.

    ``pool`` (..., P, page_size, Hkv, D) — physical pages (leading axes are
    nn.scan layer stacking); ``block_table`` (B, n_log) int32. Returns
    (..., B, n_log*page_size, Hkv, D): slot b's logical columns
    ``[j*page_size, (j+1)*page_size)`` read physical page
    ``block_table[b, j]``. Unmapped logical pages point at the reserved null
    page (id 0) — their columns surface as garbage and MUST be masked
    invalid by the caller's ``kv_valid`` row (the serving contract)."""
    pax = pool.ndim - 4
    b, n_log = block_table.shape
    out = jnp.take(pool, block_table, axis=pax)
    # (..., B, n_log, page_size, Hkv, D) -> merge the page axes into L
    shape = out.shape[:pax] + (b, n_log * page_size) + out.shape[pax + 3:]
    return out.reshape(shape)


def paged_window_vals(logical: jax.Array, block_table: jax.Array,
                      page0: jax.Array, n_win: int, page_size: int,
                      lead_ndim: int):
    """Extract the ``n_win`` logical pages starting at ``page0`` of every
    slot as scatter-ready page blocks: returns ``(vals, idx)`` — ``vals``
    (lead..., B*n_win, page_size, tail...) and ``idx`` (B*n_win,) physical
    page ids from the block table. The shared half of the plain and the
    quantizing window scatters."""
    b, n_log = block_table.shape
    lead = logical.shape[:lead_ndim]
    page0 = jnp.clip(page0, 0, max(n_log - n_win, 0))
    bt_win = jax.lax.dynamic_slice(block_table, (0, page0), (b, n_win))
    idx = bt_win.reshape(-1)  # (B*n_win,)
    lg = logical.reshape(
        lead + (b, n_log, page_size) + logical.shape[lead_ndim + 2:]
    )
    win = jax.lax.dynamic_slice_in_dim(lg, page0, n_win, axis=lead_ndim + 1)
    vals = win.reshape(
        lead + (b * n_win, page_size) + win.shape[lead_ndim + 3:]
    )
    return vals, idx


def paged_scatter_vals(pool: jax.Array, vals: jax.Array,
                       idx: jax.Array) -> jax.Array:
    """Scatter page blocks ``vals`` (lead..., n, page_size, tail...) into
    the pool at physical ids ``idx`` (n,). Slots whose pages are unmapped
    (block table 0) scatter into the reserved null page; duplicate targets
    carry identical values everywhere except that null page, whose content
    is never attendable."""
    pax = pool.ndim - 4
    lead_n = pax
    pool_flat = pool.reshape((-1,) + pool.shape[pax:])
    vals_flat = vals.reshape((-1,) + vals.shape[lead_n:])
    out = jax.vmap(lambda p, v: p.at[idx].set(v))(pool_flat, vals_flat)
    return out.reshape(pool.shape)


def paged_scatter_window_leaf(pool: jax.Array, logical: jax.Array,
                              block_table: jax.Array, page0: jax.Array,
                              n_win: int, page_size: int) -> jax.Array:
    """Write the ``n_win`` logical pages starting at page ``page0`` of every
    slot back into the pool (the decode chunk's write window, statically
    sized; ``page0`` is traced). Values outside the window are discarded —
    they were read-only in the chunk, so the pool already holds them; this
    is what keeps shared (ref > 1) prefix pages bit-stable under CoW."""
    pax = pool.ndim - 4
    vals, idx = paged_window_vals(
        logical, block_table, page0, n_win, page_size, pax
    )
    return paged_scatter_vals(pool, vals, idx)


def paged_gather_window_leaf(pool: jax.Array, block_table: jax.Array,
                             page0: jax.Array, n_win: int) -> jax.Array:
    """The ``n_win`` logical pages from page ``page0`` of every slot, read
    from the pool through the block table: (..., B, n_win * page_size, Hkv,
    D). What the fused decode chunk stages its new tokens in: the columns it
    may write and no others (``page0`` traced and already within
    ``[0, n_log - n_win]``). Block-table ids are pool pages by construction,
    so the take clips instead of filling."""
    pax = pool.ndim - 4
    b = block_table.shape[0]
    ids = jax.lax.dynamic_slice(block_table, (0, page0), (b, n_win))
    out = jnp.take(pool, ids, axis=pax, mode="clip")
    # (..., B, n_win, page_size, Hkv, D) -> merge the page axes
    return out.reshape(out.shape[:pax] + (b, -1) + out.shape[pax + 3:])


def paged_scatter_window_pages(pool: jax.Array, window: jax.Array,
                               block_table: jax.Array,
                               page0: jax.Array) -> jax.Array:
    """Write a window leaf as :func:`paged_gather_window_leaf` gathered it
    (all of it) back into the pool, at the physical pages the block table
    maps its logical pages to. Columns the chunk has not written carry the
    bytes they were gathered with, so shared copy-on-write pages stay
    bit-stable."""
    pax = pool.ndim - 4
    b, page_size = block_table.shape[0], pool.shape[pax + 1]
    n_win = window.shape[pax + 1] // page_size
    ids = jax.lax.dynamic_slice(block_table, (0, page0), (b, n_win))
    vals = window.reshape(
        window.shape[:pax] + (b * n_win, page_size) + window.shape[pax + 2:]
    )
    return paged_scatter_vals(pool, vals, ids.reshape(-1))


def paged_write_pages_leaf(pool: jax.Array, pages: jax.Array,
                           page_ids: jax.Array) -> jax.Array:
    """Scatter explicit page blocks into the pool: ``pages`` (..., n,
    page_size, Hkv, D) land at physical ids ``page_ids`` (n,). The paged
    admission roll-in uses this to place a prefill row's occupied pages;
    unused tail ids point at the reserved null page 0."""
    pax = pool.ndim - 4
    lead = pool.shape[:pax]
    pool_flat = pool.reshape((-1,) + pool.shape[pax:])
    vals_flat = pages.reshape((-1,) + pages.shape[len(lead):])
    out = jax.vmap(lambda p, v: p.at[page_ids].set(v))(pool_flat, vals_flat)
    return out.reshape(pool.shape)


def paged_read_pages_leaf(pool: jax.Array, page_ids: jax.Array) -> jax.Array:
    """Read ``n`` physical pages as one contiguous block (..., n*page_size,
    Hkv, D) — the zero-allocation view a copy-on-write prefix hit gathers
    its shared pages through (compute-only; no pool page is written)."""
    pax = pool.ndim - 4
    out = jnp.take(pool, page_ids, axis=pax)
    n, ps = page_ids.shape[0], pool.shape[pax + 1]
    shape = out.shape[:pax] + (n * ps,) + out.shape[pax + 2:]
    return out.reshape(shape)


# --- quantized KV pages (ISSUE 13) --------------------------------------------
#
# With ServingEngine(quantize=QuantConfig(kv="int8")) the pool k/v leaves
# store int8 pages with per-page, per-kv-head symmetric scales as SIBLING
# leaves (k_scale/v_scale, shape (..., P, 1, Hkv, 1), dtype = the compute
# dtype so the transport is self-describing — dequantization targets the
# scale leaf's dtype). The four ops below are the quantized twins of the
# transport above: gather/read dequantize into the logical/compute view,
# the window quantizer turns a chunk's float write window back into
# (int8 pages, scales) for the scatter. Everything is pure jnp — it traces
# inside the donated decode chunk on any backend, and XLA fuses the
# dequant multiply into the attention consumer.

KV_QMAX = 127.0  # int8 symmetric clamp bound (quantization/config.py)


def quantize_page_block(pages: jax.Array):
    """Quantize float page blocks (..., n, page_size, Hkv, D) to int8 with
    per-(page, kv-head) symmetric scales (..., n, 1, Hkv, 1). The scale is
    computed in fp32 then CAST to the block dtype BEFORE quantizing, so a
    dequantize→requantize round-trip with an unchanged absmax is exact
    (chunk N+1 re-scattering a page chunk N wrote)."""
    pf = pages.astype(jnp.float32)
    amax = jnp.max(jnp.abs(pf), axis=(-3, -1), keepdims=True)
    scale = (jnp.maximum(amax, 1e-12) / KV_QMAX).astype(pages.dtype)
    sf = scale.astype(jnp.float32)
    q = jnp.clip(jnp.round(pf / sf), -KV_QMAX, KV_QMAX).astype(jnp.int8)
    return q, scale


def paged_gather_leaf_dequant(pool_q: jax.Array, pool_scale: jax.Array,
                              block_table: jax.Array,
                              page_size: int) -> jax.Array:
    """Materialize the DEQUANTIZED logical view of a quantized pool leaf:
    int8 pages and their per-page scales gather through the same block
    table, and the logical (..., B, L, Hkv, D) view comes back in the scale
    leaf's (compute) dtype — the exact view the unquantized gather would
    hold, so the whole decode/attention stack runs on it unchanged."""
    col = pool_q.ndim - 4 + 1  # logical column axis (after the B axis)
    q = paged_gather_leaf(pool_q, block_table, page_size)
    s = paged_gather_leaf(pool_scale, block_table, 1)  # (..., B, n_log, Hkv, 1)
    s = jnp.repeat(s, page_size, axis=col)
    return (q.astype(jnp.float32) * s.astype(jnp.float32)).astype(
        pool_scale.dtype
    )


def paged_read_pages_leaf_dequant(pool_q: jax.Array, pool_scale: jax.Array,
                                  page_ids: jax.Array,
                                  page_size: int) -> jax.Array:
    """Quantized twin of :func:`paged_read_pages_leaf`: read ``n`` physical
    pages as one contiguous DEQUANTIZED block (..., n*page_size, Hkv, D) in
    the scale leaf's dtype (the zero-copy CoW prefix-hit view)."""
    pax = pool_q.ndim - 4
    q = paged_read_pages_leaf(pool_q, page_ids)       # (..., n*ps, Hkv, D)
    s = paged_read_pages_leaf(pool_scale, page_ids)   # (..., n, Hkv, 1)
    s = jnp.repeat(s, page_size, axis=pax)
    return (q.astype(jnp.float32) * s.astype(jnp.float32)).astype(
        pool_scale.dtype
    )


def _decode_kernel(pos_ref, bound_ref, valid_ref, q_ref, k_ref, v_ref,
                   o_ref, lse_ref, m_scr, l_scr, acc_scr, *, block_l,
                   num_l_blocks, l_off, use_valid):
    j = pl.program_id(2)  # cache-length block (sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # skip blocks whose first slot is beyond every row's position (the SMEM
    # bound is max(pos) + 1, computed outside)
    run = l_off + j * block_l < bound_ref[0]

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (R, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (BL, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (BL, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (1.0 / (q.shape[-1] ** 0.5))               # (R, BL)
        rows = pos_ref[0, :][:, None]                  # (R, 1) slot positions
        cols = (
            jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], block_l), 1)
            + j * block_l + l_off
        )
        s = jnp.where(rows >= cols, s, NEG_INF)
        if use_valid:
            ok = valid_ref[0, 0] != 0                   # (1, BL)
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.exp(s - ref)
        alpha = jnp.exp(m_prev - ref)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new

    @pl.when(j == num_l_blocks - 1)
    def _finish():
        l = l_scr[:]
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l > 0, m_scr[:] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF
        )


def _valid_tiles(kv_valid, block, index_map):
    """``kv_valid`` (B, L) as (B, L/block, 1, block) int32 plus the BlockSpec
    that hands the kernel one (1, block) row per grid step. A (1, block) tile
    of the 2-D (B, L) array is not a block Mosaic can tile (its
    second-to-last dim is neither a multiple of 8 nor the whole axis); with
    the block index lifted into its own axis the tile's last two dims ARE
    the array's."""
    b, l = kv_valid.shape
    tiles = kv_valid.astype(jnp.int32).reshape(b, l // block, 1, block)
    return tiles, pl.BlockSpec((1, 1, 1, block), index_map)


def _flash_decode_call(q, k, v, pos, kv_valid, l_off, interpret, block_l):
    """q (B, Hkv, R, D) rows; k/v (B, Hkv, L, D) cache slice starting at
    global slot ``l_off``; pos (R,) global slot positions. Returns
    (out (B, Hkv, R, D), lse (B, Hkv, R, 1))."""
    b, hkv, r, d = q.shape
    l = k.shape[2]
    bl = _pick_block(l, block_l)
    nl = l // bl
    use_valid = kv_valid is not None
    if kv_valid is None:
        kv_valid = jnp.zeros((1, 1), jnp.int32)
        vspec = _SMEM_SPEC
    else:
        kv_valid, vspec = _valid_tiles(kv_valid, bl, lambda b_, h_, j: (b_, j, 0, 0))
    bound = jnp.max(pos) + 1 - l_off
    out, lse = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_l=bl, num_l_blocks=nl, l_off=0,
            use_valid=use_valid,
        ),
        grid=(b, hkv, nl),
        in_specs=[
            pl.BlockSpec((1, r), lambda b_, h_, j: (0, 0)),  # pos (SMEM-ish)
            _SMEM_SPEC,                                       # bound
            vspec,                                            # kv_valid
            pl.BlockSpec((1, 1, r, d), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bl, d), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bl, d), lambda b_, h_, j: (b_, h_, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, r, d), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, r, 1), lambda b_, h_, j: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, r, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, r, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        (pos - l_off).reshape(1, r).astype(jnp.int32),
        jnp.asarray(bound, jnp.int32).reshape((1,)),
        kv_valid,
        q, k, v,
    )
    return out, lse


def flash_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    q_pos: jax.Array,
    kv_valid: Optional[jax.Array] = None,
    block_l: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Cached decode attention: q (B, S, H, D) rows at slot positions
    ``q_pos`` (S,) against the cache (B, L, Hkv, D); each row attends slots
    ``<= `` its own position, minus invalid (padded) slots. Drop-in for the
    einsum ``decode_attention`` (modules/attention.py) minus the Medusa tree
    mask (tree steps keep the einsum path — their cache is short-lived).

    Sharding: batch over the data axes; kv heads over tp when divisible.
    When ``tp > hkv`` the excess splits the CACHE LENGTH across ranks and
    merges partials by exp-weighted psum over lse — the reference's
    ``num_cores_per_group`` flash-decode groups (parallel_state.py:1368)
    without replicating KV in HBM."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    b, s, h, d = q.shape
    hkv = k_cache.shape[2]
    group = h // hkv
    L = k_cache.shape[1]
    interpret = interpret_mode(interpret)

    # (B, S, H, D) → (B, Hkv, R=G·S, D): fold the GQA group into rows so one
    # kernel invocation serves every q head of a kv head
    qt = jnp.swapaxes(q, 1, 2).reshape(b, hkv, group, s, d).reshape(
        b, hkv, group * s, d
    )
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    rows_pos = jnp.tile(q_pos.astype(jnp.int32), (group,))  # (R,)

    def unfold(out):
        return jnp.swapaxes(
            out.reshape(b, hkv, group, s, d).reshape(b, h, s, d), 1, 2
        ).astype(q.dtype)

    if not mesh_lib.model_parallel_is_initialized():
        out, _ = _flash_decode_call(
            qt, kt, vt, rows_pos, kv_valid, 0, interpret, block_l
        )
        return unfold(out)

    mesh = mesh_lib.get_mesh()
    dp = mesh.shape[mesh_lib.EDP_AXIS] * mesh.shape[mesh_lib.EP_AXIS]
    tp = mesh.shape[mesh_lib.TP_AXIS]
    from jax.sharding import PartitionSpec as P

    bspec = mesh_lib.DATA_AXES if (dp > 1 and b % dp == 0) else None

    def replicated_over_tp():
        # batch over dp, heads/length replicated over tp. Also the fallback
        # for irregular geometries below: a bare _flash_decode_call on
        # global arrays under an active mesh would ask GSPMD to partition a
        # Mosaic custom call, which it cannot (ADVICE round 5) — every
        # kernel launch under a mesh must go through manual_shard_map.
        spec = P(bspec, None, None, None)
        fn = mesh_lib.manual_shard_map(
            lambda a, b_, c, p_, kv: _flash_decode_call(
                a, b_, c, p_, kv, 0, interpret, block_l
            )[0],
            in_specs=(spec, spec, spec, P(None), P(bspec, None)),
            out_specs=spec,
        )
        out = fn(qt, kt, vt, rows_pos,
                 kv_valid if kv_valid is not None else jnp.ones((b, L), jnp.int32))
        return unfold(out)

    if tp <= 1 or h % tp != 0:
        return replicated_over_tp()

    if hkv % tp == 0:
        # kv heads shard cleanly over tp
        spec = P(bspec, mesh_lib.TP_AXIS, None, None)
        fn = mesh_lib.manual_shard_map(
            lambda a, b_, c, p_, kv: _flash_decode_call(
                a, b_, c, p_, kv, 0, interpret, block_l
            )[0],
            in_specs=(spec, spec, spec, P(None),
                      P(bspec, None)),
            out_specs=spec,
        )
        out = fn(qt, kt, vt, rows_pos,
                 kv_valid if kv_valid is not None else jnp.ones((b, L), jnp.int32))
        return unfold(out)

    # tp > hkv (or hkv % tp != 0): split the cache length over tp and merge
    # the partials — every core scans L/tp slots of every kv head
    if L % tp != 0:
        # irregular: replicate over tp through the SAME manual region as the
        # tp<=1 branch (the bare kernel call would fail to compile on
        # tp-sharded inputs — Mosaic calls can't be auto-partitioned)
        return replicated_over_tp()

    def per_rank(a, k_, v_, p_, kv):
        rank = jax.lax.axis_index(mesh_lib.TP_AXIS)
        l_off = rank * (L // tp)
        o, lse = _flash_decode_call(a, k_, v_, p_, kv, l_off, interpret, block_l)
        # exp-weighted merge over the tp axis: partials with lse≈-inf (rows
        # whose slots all live on other ranks) contribute zero
        m = jax.lax.pmax(lse, mesh_lib.TP_AXIS)
        safe = jnp.where(m > NEG_INF / 2, m, 0.0)
        w = jnp.where(lse > NEG_INF / 2, jnp.exp(lse - safe), 0.0)
        num = jax.lax.psum(o.astype(jnp.float32) * w, mesh_lib.TP_AXIS)
        den = jax.lax.psum(w, mesh_lib.TP_AXIS)
        return (num / jnp.maximum(den, 1e-30)).astype(a.dtype)

    qs = P(bspec, None, None, None)
    ls = P(bspec, None, mesh_lib.TP_AXIS, None)  # cache length over tp
    fn = mesh_lib.manual_shard_map(
        per_rank,
        in_specs=(qs, ls, ls, P(None), P(bspec, mesh_lib.TP_AXIS)),
        out_specs=qs,
    )
    out = fn(qt, kt, vt, rows_pos,
             kv_valid if kv_valid is not None else jnp.ones((b, L), jnp.int32))
    return unfold(out)


# --- fused paged decode: block table IN the kernel's index map ----------------
#
# The transport above materializes the logical view (jnp.take through the
# block table) BEFORE the kernel sees it — an extra HBM round-trip of the
# whole mapped cache per chunk. This kernel folds the page lookup into the
# block index map instead: the block table rides Pallas scalar prefetch
# (SMEM), and the K/V BlockSpec index maps read it to stream each slot's
# PHYSICAL pool pages directly — page j of slot b arrives from pool page
# ``block_table[b, j]``, no logical copy ever exists. Same online-softmax
# math as `_decode_kernel`, one page per sequential grid step. The gather
# transport is the numerics golden: outputs are pinned identical in
# tests/kernels/test_flash_decode.py (interpret mode).
#
# One grid step streams a WHOLE page — every kv head of it — as one
# contiguous (page_size, Hkv, D) block, and the kernel walks the heads
# inside. A per-head block (1, page_size, 1, D) would slice one row out of
# the pool's (Hkv, D) tiles, which Mosaic cannot tile (refused at compile
# for the v5e at Llama-2-7B geometry); the whole-page block's last two dims
# are the array's, and the page arrives in a single DMA.


def _paged_decode_kernel(bt_ref, pos_ref, bound_ref, valid_ref, q_ref,
                         k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                         acc_scr, *, page_size, num_pages_log, num_kv_heads,
                         use_valid):
    j = pl.program_id(1)  # logical page (sequential; physical via bt_ref)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # skip logical pages entirely beyond every row's position
    run = j * page_size < bound_ref[0]

    @pl.when(run)
    def _body():
        rows = pos_ref[0, :][:, None]                  # (R, 1) slot positions
        cols = (
            jax.lax.broadcasted_iota(
                jnp.int32, (rows.shape[0], page_size), 1
            )
            + j * page_size
        )
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)        # (R, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # (ps, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)  # (ps, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * (1.0 / (q.shape[-1] ** 0.5))           # (R, ps)
            s = jnp.where(rows >= cols, s, NEG_INF)
            if use_valid:
                ok = valid_ref[0, 0] != 0               # (1, ps)
                s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            p = jnp.exp(s - ref)
            alpha = jnp.exp(m_prev - ref)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = m_new

    @pl.when(j == num_pages_log - 1)
    def _finish():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            l > 0, m_scr[:] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF
        )


def _paged_decode_call(qt, k_pool, v_pool, block_table, rows_pos, kv_valid,
                       page_size, interpret):
    """qt (B, Hkv, R, D) rows; pools (P, page_size, Hkv, D); block_table
    (B, n_log); rows_pos (R,) slot positions; kv_valid (B, L) or None.
    Returns out (B, Hkv, R, D)."""
    b, hkv, r, d = qt.shape
    n_log = block_table.shape[1]
    use_valid = kv_valid is not None
    if kv_valid is None:
        kv_valid = jnp.zeros((1, 1), jnp.int32)
        vspec = _SMEM_SPEC
    else:
        kv_valid, vspec = _valid_tiles(
            kv_valid, page_size, lambda b_, j, bt: (b_, j, 0, 0)
        )
    # THE fusion: logical page j of slot b_ streams straight from physical
    # pool page bt[b_, j] — no gathered copy in HBM
    page_spec = pl.BlockSpec(
        (1, page_size, hkv, d), lambda b_, j, bt: (bt[b_, j], 0, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the block table, read by the k/v index maps
        grid=(b, n_log),
        in_specs=[
            pl.BlockSpec((1, r), lambda b_, j, bt: (0, 0)),       # pos
            _SMEM_SPEC,                                            # bound
            vspec,                                                 # kv_valid
            pl.BlockSpec((1, hkv, r, d), lambda b_, j, bt: (b_, 0, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, r, d), lambda b_, j, bt: (b_, 0, 0, 0)),
            pl.BlockSpec((1, hkv, r, 1), lambda b_, j, bt: (b_, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, r, 1), jnp.float32),
            pltpu.VMEM((hkv, r, 1), jnp.float32),
            pltpu.VMEM((hkv, r, d), jnp.float32),
        ],
    )
    out, _ = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, page_size=page_size,
            num_pages_log=n_log, num_kv_heads=hkv, use_valid=use_valid,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, r, d), qt.dtype),
            jax.ShapeDtypeStruct((b, hkv, r, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        rows_pos.reshape(1, r),
        jnp.asarray(jnp.max(rows_pos) + 1, jnp.int32).reshape((1,)),
        kv_valid,
        qt, k_pool, v_pool,
    )
    return out


def paged_flash_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    q_pos: jax.Array,
    kv_valid: Optional[jax.Array] = None,
    page_size: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged cached-decode attention with the page lookup FUSED into the
    kernel's block index map: q (B, S, H, D) rows at slot positions
    ``q_pos`` (S,) attend each slot's logically-mapped cache directly from
    the physical pool — ``k_pool``/``v_pool`` (P, page_size, Hkv, D)
    single-layer pool leaves, ``block_table`` (B, n_log) int32 (0 = the
    reserved null page, whose columns MUST be masked by ``kv_valid`` —
    the serving contract). Output matches
    ``flash_decode_attention(q, gather(pool), ..., block_l=page_size)``
    BIT-FOR-BIT (same online-softmax block partition; other ``block_l``
    choices differ only in fp accumulation order, ~1e-7) — without ever
    materializing the gathered logical view in HBM.

    This IS the kernel on every backend: off the TPU it runs only
    interpreted (``interpret=`` / the tests' session switch) and otherwise
    fails to lower — it never turns into the gather reference.

    Under a mesh the call sits in a manual region (Mosaic calls cannot be
    auto-partitioned): batch over the data axes, heads over tp when both
    head counts divide it, replicated over tp otherwise."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    b, s, h, d = q.shape
    hkv = k_pool.shape[2]
    group = h // hkv
    interpret = interpret_mode(interpret)

    qt = jnp.swapaxes(q, 1, 2).reshape(b, hkv, group, s, d).reshape(
        b, hkv, group * s, d
    )
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    rows_pos = jnp.tile(q_pos.astype(jnp.int32), (group,))  # (R,)

    if not mesh_lib.model_parallel_is_initialized():
        out = _paged_decode_call(
            qt, k_pool, v_pool, block_table, rows_pos, kv_valid, page_size,
            interpret,
        )
    else:
        from jax.sharding import PartitionSpec as P

        mesh = mesh_lib.get_mesh()
        dp = mesh.shape[mesh_lib.EDP_AXIS] * mesh.shape[mesh_lib.EP_AXIS]
        tp = mesh.shape[mesh_lib.TP_AXIS]
        bspec = mesh_lib.DATA_AXES if (dp > 1 and b % dp == 0) else None
        hspec = (
            mesh_lib.TP_AXIS
            if (tp > 1 and h % tp == 0 and hkv % tp == 0) else None
        )
        if kv_valid is None:
            kv_valid = jnp.ones(
                (b, block_table.shape[1] * page_size), jnp.int32
            )
        rows = P(bspec, hspec, None, None)
        pool = P(None, None, hspec, None)
        fn = mesh_lib.manual_shard_map(
            lambda a, k_, v_, bt, p_, kv: _paged_decode_call(
                a, k_, v_, bt, p_, kv, page_size, interpret
            ),
            in_specs=(rows, pool, pool, P(bspec, None), P(None),
                      P(bspec, None)),
            out_specs=rows,
        )
        out = fn(qt, k_pool, v_pool, block_table, rows_pos, kv_valid)
    return jnp.swapaxes(
        out.reshape(b, hkv, group, s, d).reshape(b, h, s, d), 1, 2
    ).astype(q.dtype)


# --- paged LATENT decode (MLA, absorbed form) -----------------------------------
#
# Multi-head latent attention caches, per token, ONE latent row (``d_c``
# values, 512) and ONE rotated key (``d_r`` values, 64), shared by every
# head. In the absorbed form the ``H`` query heads (``W_uk`` folded into
# them) all score against that one row, ``q_c . c + q_r . k_r``, and the
# values are the latent row itself. So a page of the latent pool is read from
# HBM ONCE and serves the scores and the values of all heads: the block that
# went through ``q k^T`` goes through ``p v`` from VMEM.
#
# The two pool leaves are the ``k`` and ``k_pe`` leaves of a one-head cache,
# ``(P, page_size, 1, d)``; the kernel sees them without the unit head axis,
# so that a page block's last two dims are ``(page_size, d)``. Two leaves
# and not one of 576: a bf16 array whose minor dim is not a multiple of 128
# is laid out by XLA with another dim minor (the PAGE index, for a pool), and
# no kernel can stream pages out of that; 512 keeps the latent, eight ninths
# of the bytes, in whole tiles.
#
# The kernel fetches its pages itself. Both pool leaves stay in HBM
# (``memory_space=pl.ANY``); the grid is one step a slot, and inside it one
# loop walks the blocks of ``LATENT_BLOCK_TOKENS`` tokens that THIS slot maps:
# from the first block holding a mapped page to the last one at or before the
# cursor (``span``, computed from the block table outside the kernel), not the
# whole row. A block is one async copy a leaf for each RUN of adjacent pool
# pages its table entries read (the serving pool deals a slot's pages four at
# a time: :data:`PAGE_RUN`) and a copy a page elsewhere, issued back to back
# into one part of a VMEM buffer of :data:`BLOCKS_AHEAD` + 1 blocks; the next
# blocks' copies are in flight while block ``i`` is waited on and multiplied
# (:func:`_walk_blocks`). A block inside the span whose pages are all unmapped
# (``live`` 0) is neither fetched nor computed; a slot that maps nothing
# fetches nothing and writes zeros. The last block of a row whose pages do not
# fill it re-fetches the row's last page into the spare rows: their columns
# lie past every row position.
#
# Why not a BlockSpec a page (the form this replaced): at 8 slots of 32,768
# columns that was 1024 grid steps a call, and the pipeline's bookkeeping for
# 32 page operands on every one of them, mapped or not, took 0.97 of the
# call's 1.12 ms; this form takes 0.24 (v5e, PERF.md §6, PR 29). What held
# it then was NAMING the copies (two a page of 16 tokens: ~28 cycles of scalar
# work each, two bounds checks among them, in the multiply's own instruction
# stream): with one copy a run of four pages the same call takes 0.18 ms
# under a table the pool dealt, 0.25 under one with no run (PERF.md §6, PR 52).

# Tokens a block. Swept on the v5e at DeepSeek-V2-Lite's geometry (8 slots,
# 16 heads, contexts of 3k-21k ending at a cursor of 22,000; PERF.md §6,
# PR 29). Three blocks of 1024 x (512 + 64 padded to 128) bf16 are 3.75 MB of VMEM.
LATENT_BLOCK_TOKENS = 1024

# Pages a trip of the loop that issues a block's copies: the trip's copies
# are unrolled, the trips are not. Rolled up page by page the call takes 0.32
# ms, unrolled whole 0.25 (and 0.35 s to trace and lower in every process, not
# 0.14), 16 a trip 0.23-0.24 (v5e, PERF.md §6, PR 29). A trip is also what is
# fetched a copy a RUN or a copy a page (:func:`_trip_runs`), so it stays a
# multiple of :data:`PAGE_RUN`.
_PAGES_A_TRIP = 16

# Blocks whose copies are in flight while one is multiplied. Alone, a block
# of ZAYA1's leaf (512 KB) is fetched in 0.70 us and multiplied in 0.77; with
# one block ahead the two took 1.07 us together under a dealt table, with two
# 1.03, and Trinity's full layer 0.515 -> 0.511 ms a call (v5e, PERF.md
# section 6, PR 52): a block's last bytes land later than its multiply's
# predecessor ends. Three blocks of the widest leaf that walks (Trinity's 512
# x (16, 128) bf16) are 6 MiB of VMEM.
BLOCKS_AHEAD = 2

# Pages a RUN: adjacent pool pages under one aligned group of a block's table
# entries are fetched with one copy (:func:`_block_page_copies`), and the
# serving pool deals a slot's pages in such runs (``serving/paging.py``).
PAGE_RUN = 4


def _hbm_lanes(width: int, interpret: bool) -> Optional[int]:
    """The lanes a page copy of a ``width``-wide leaf names, where they are
    not the leaf's own. The chip lays an array out in tiles of 128 lanes, so
    a leaf of 64 is held padded to 128, and Mosaic slices an HBM operand in
    whole tiles only: it refuses ``pool.at[page]`` of a 64-wide leaf ("slice
    shape must be aligned to tiling (128), but is 64": its own view of the
    operand is 128 wide). So the copy names the tile's lanes, padding
    included, and the kernel reads the first ``width`` of the block it lands
    in. Interpreted, an array has no padding, and neither has the copy."""
    if interpret or width % 128 == 0:
        return None
    return -(-width // 128) * 128


def _issue_trip(group: int) -> int:
    """Pages a trip of the loop that issues a block's copies."""
    return math.gcd(group, _PAGES_A_TRIP)


def _trip_runs(block_table, group):
    """``(B, n_blocks * trips a block)`` int32 from the block table: 1 where
    EVERY aligned group of :data:`PAGE_RUN` entries of a trip's pages reads
    adjacent pool pages ``p, p + 1, ...`` (``p`` not the null page): the trip
    is fetched a copy a run. The last block's spare entries repeat the row's
    last page, as the kernel reads them (no run)."""
    trip = _issue_trip(group)
    b, n_log = block_table.shape
    n_blocks = pl.cdiv(n_log, group)
    if trip % PAGE_RUN:
        return jnp.zeros((b, n_blocks * (group // trip)), jnp.int32)
    padded = jnp.pad(block_table, ((0, 0), (0, n_blocks * group - n_log)), mode="edge")
    ids = padded.reshape(b, -1, PAGE_RUN)
    whole = (ids[:, :, 0] != 0) & jnp.all(ids[:, :, 1:] - ids[:, :, :-1] == 1, axis=2)
    return jnp.all(whole.reshape(b, -1, trip // PAGE_RUN), axis=2).astype(jnp.int32)


def _block_page_copies(leaves, sem, page_id, whole, group):
    """Issue one block's page copies: for each page ``g`` of the block's
    ``group`` and each of ``leaves`` (``(pool ref, block ref, lanes)``: a ``(P,
    page_size, ...)`` pool left in HBM, the VMEM block ``(group, page_size,
    ...)`` it lands in, :func:`_hbm_lanes` of its width), pool page
    ``page_id(g)`` goes to page ``g`` of the block, all signalling ``sem``.
    The pool's pages are adjacent in HBM, so a trip ``t`` whose table entries
    read runs of :data:`PAGE_RUN` adjacent pages (``whole(t)`` 1:
    :func:`_trip_runs`, from the table as the program runs) is ONE copy a run;
    any other trip is a copy a page. The bytes that land are the same for
    every table.

    Each side is a loop of one trip or none, not a conditional: Mosaic turns
    a conditional this small into predicated instructions and runs BOTH
    sides' scalar work (a described-v5e listing: 113 bundles a run, taken or
    not, where four single copies are ~75), and naming the copies is what
    the walk of a narrow leaf waits for."""
    trip = _issue_trip(group)

    def start(pid, g, n):
        for pool_ref, buf_ref, lanes in leaves:
            pages, rows = pl.ds(pid, n), pl.ds(g, n)
            if lanes is None:
                src, dst = pool_ref.at[pages], buf_ref.at[rows]
            else:
                width = pl.ds(0, lanes)
                src, dst = pool_ref.at[pages, :, width], buf_ref.at[rows, :, width]
            pltpu.make_async_copy(src, dst, sem).start()

    def some(t, carry):
        def a_copy_a(n):
            def copies(_, c):
                for k in range(trip // n):
                    g = pl.multiple_of(t * trip + k * n, n)
                    start(page_id(g), g, n)
                return c
            return copies

        runs = whole(t) if trip % PAGE_RUN == 0 else 0
        jax.lax.fori_loop(0, runs, a_copy_a(PAGE_RUN), 0)
        jax.lax.fori_loop(0, 1 - runs, a_copy_a(1), 0)
        return carry

    jax.lax.fori_loop(0, group // trip, some, 0)


def _wait_block(leaves, sem):
    """ONE wait a leaf for a block's copies: a DMA semaphore counts bytes, and
    the copies of :func:`_block_page_copies` fill each leaf's whole block
    (runs or single pages, the null page under an unmapped entry), so a
    descriptor over the block names exactly their bytes."""
    for _, buf_ref, _ in leaves:
        pltpu.make_async_copy(buf_ref, buf_ref, sem).wait()


def _block_rows(block_ref):
    """A fetched block ``(group, page_size, ...)`` as its tokens' rows
    ``(group * page_size, ...)``: the pages lie one after another in VMEM."""
    g, page_size = block_ref.shape[:2]
    return block_ref.reshape((g * page_size,) + block_ref.shape[2:])


def _walk_blocks(bt_ref, runs_ref, live_ref, span_ref, b, leaves, sems, group, multiply):
    """Walk the live blocks of slot ``b``'s row of the block table
    (``runs_ref``: its :func:`_trip_runs`; ``live_ref`` / ``span_ref``:
    :func:`_latent_block_walk`): block ``i``'s page copies land in part ``i %
    (BLOCKS_AHEAD + 1)`` of each of ``leaves`` (``(pool ref, VMEM ref of that
    many blocks, lanes)``), are waited for, and ``multiply(i, *blocks)`` is
    given the leaves' fetched blocks, while the copies of the next
    :data:`BLOCKS_AHEAD` live blocks are in flight: block ``i + BLOCKS_AHEAD``
    is issued before block ``i`` is waited on, and the trips before the
    span's first block only issue. A block with no mapped page is neither
    fetched nor multiplied. The last block of a row whose pages do not fill
    it fetches the row's last page again into the spare rows."""
    lo, hi = span_ref[b, 0], span_ref[b, 1]   # this slot's own blocks
    n_log, n_blocks = bt_ref.shape[1], live_ref.shape[1]
    trips = group // _issue_trip(group)

    def part(i):
        at = i % (BLOCKS_AHEAD + 1)
        return tuple((pool, buf.at[at], lanes) for pool, buf, lanes in leaves), sems.at[at]

    def issue(i):
        def page_id(g):
            page = i * group + g
            if n_log % group:
                page = jnp.minimum(page, n_log - 1)
            return bt_ref[b, page]

        _block_page_copies(*part(i), page_id, lambda t: runs_ref[b, i * trips + t], group)

    def step(i, carry):
        nxt = jnp.minimum(i + BLOCKS_AHEAD, n_blocks - 1)

        @pl.when((i + BLOCKS_AHEAD < hi) & (live_ref[b, nxt] != 0))
        def _prefetch():
            issue(nxt)

        @pl.when((i >= lo) & (live_ref[b, jnp.maximum(i, 0)] != 0))
        def _body():
            fetched, sem = part(i)
            _wait_block(fetched, sem)
            multiply(i, *(block for _, block, _ in fetched))

        return carry

    jax.lax.fori_loop(lo - BLOCKS_AHEAD, hi, step, 0)


def _paged_latent_kernel(bt_ref, runs_ref, live_ref, span_ref, pos_ref, valid_ref,
                         qc_ref, qr_ref, c_hbm, r_hbm, o_ref, c_buf, r_buf,
                         sems, m_scr, l_scr, acc_scr, *, page_size, group,
                         r_lanes, scale, use_valid):
    b = pl.program_id(0)
    block = group * page_size

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def multiply(i, c_block, r_block):
        # operands stay in their storage type (bf16 on the chip: the MXU's
        # own), accumulation is float32
        c = _block_rows(c_block)[...]                          # (T, d_c)
        kr = _block_rows(r_block)[:, :qr_ref.shape[2]]         # (T, d_r)
        dims = (((1,), (1,)), ((), ()))
        s = (
            jax.lax.dot_general(qc_ref[0], c, dims,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qr_ref[0], kr, dims,
                                  preferred_element_type=jnp.float32)
        ) * scale                                      # (R, T)
        rows = pos_ref[0, :][:, None]                  # (R, 1) slot positions
        cols = (
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i * block
        )
        s = jnp.where(rows >= cols, s, NEG_INF)
        if use_valid:
            s = jnp.where(valid_ref[0, pl.ds(i, 1), :] != 0, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.exp(s - ref)
        alpha = jnp.exp(m_prev - ref)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # the values are the latent block already in VMEM
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    _walk_blocks(bt_ref, runs_ref, live_ref, span_ref, b,
                 ((c_hbm, c_buf, None), (r_hbm, r_buf, r_lanes)), sems, group, multiply)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def _latent_block_walk(block_table, bound, group, page_size):
    """What the kernel walks, from the block table: ``live`` (B, n_blocks)
    int32, 1 where a block of ``group`` pages holds a mapped page that
    starts before the cursor ``bound``; ``span`` (B, 2) int32, each slot's
    first live block and one past its last (0, 0 for a slot with none)."""
    b, n_log = block_table.shape
    n_blocks = pl.cdiv(n_log, group)
    mapped = (block_table != 0) & (
        jnp.arange(n_log, dtype=jnp.int32)[None, :] * page_size < bound
    )
    mapped = jnp.pad(mapped, ((0, 0), (0, n_blocks * group - n_log)))
    live = mapped.reshape(b, n_blocks, group).any(axis=2)
    idx = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    lo = jnp.min(jnp.where(live, idx, n_blocks), axis=1)
    hi = jnp.max(jnp.where(live, idx + 1, 0), axis=1)
    span = jnp.stack([jnp.minimum(lo, hi), hi], axis=1)
    return live.astype(jnp.int32), span.astype(jnp.int32)


def _paged_latent_decode_call(qc, qr, c_pool, r_pool, block_table, rows_pos,
                              kv_valid, page_size, scale, interpret):
    """qc (B, R, d_c), qr (B, R, d_r) absorbed query rows; pools (P,
    page_size, d_c) and (P, page_size, d_r); block_table (B, n_log);
    rows_pos (R,); kv_valid (B, L) or None. Returns (B, R, d_c)."""
    b, r, d_c = qc.shape
    d_r = qr.shape[2]
    n_log = block_table.shape[1]
    group = min(LATENT_BLOCK_TOKENS // page_size, n_log)
    block = group * page_size
    block_table = block_table.astype(jnp.int32)
    live, span = _latent_block_walk(
        block_table, jnp.max(rows_pos) + 1, group, page_size
    )
    n_blocks = live.shape[1]
    r_lanes = _hbm_lanes(d_r, interpret)
    use_valid = kv_valid is not None
    if kv_valid is None:
        kv_valid = jnp.zeros((1, 1), jnp.int32)
        vspec = _SMEM_SPEC
    else:
        # a slot's whole row, one block a sublane: the loop picks row ``i``
        kv_valid = jnp.pad(
            kv_valid.astype(jnp.int32),
            ((0, 0), (0, n_blocks * block - kv_valid.shape[1])),
        ).reshape(b, n_blocks, block)
        vspec = pl.BlockSpec((1, n_blocks, block), lambda b_, *_: (b_, 0, 0))

    def rows_spec(d):
        return pl.BlockSpec((1, r, d), lambda b_, *_: (b_, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)   # a pool leaf, whole and unblocked
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # block table, its runs, live blocks, each slot's span
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, r), lambda b_, *_: (0, 0)),                 # pos
            vspec,                                                       # kv_valid
            rows_spec(d_c), rows_spec(d_r),
            hbm, hbm,
        ],
        out_specs=rows_spec(d_c),
        scratch_shapes=[
            pltpu.VMEM((BLOCKS_AHEAD + 1, group, page_size, d_c), c_pool.dtype),
            pltpu.VMEM((BLOCKS_AHEAD + 1, group, page_size, r_lanes or d_r), r_pool.dtype),
            pltpu.SemaphoreType.DMA((BLOCKS_AHEAD + 1,)),
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, d_c), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_latent_kernel, page_size=page_size, group=group,
            r_lanes=r_lanes, scale=scale, use_valid=use_valid,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, r, d_c), qc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(block_table, _trip_runs(block_table, group), live, span, rows_pos.reshape(1, r), kv_valid,
      qc, qr, c_pool, r_pool)


def paged_latent_decode_attention(
    q_c: jax.Array,
    q_r: jax.Array,
    c_pool: jax.Array,
    r_pool: jax.Array,
    block_table: jax.Array,
    q_pos: jax.Array,
    kv_valid: Optional[jax.Array] = None,
    *,
    scale: float,
    page_size: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Absorbed multi-head latent attention straight off the page pool.
    ``q_c`` (B, S, H, d_c): the query heads with ``W_uk`` folded in; ``q_r``
    (B, S, H, d_r): their rotated part; rows at slot positions ``q_pos``
    (S,). ``c_pool`` (P, page_size, 1, d_c) holds each token's ONE latent row,
    ``r_pool`` (P, page_size, 1, d_r) its one rotated key. Scores ``(q_c . c +
    q_r . k_r) * scale``, values the latent row. Returns (B, S, H, d_c), to
    be taken through ``W_uv`` by the caller. Block table and ``kv_valid`` as
    :func:`paged_flash_decode_attention`; like it, this is the kernel or
    nothing (interpreted only in tests).

    The pool leaves are not copied, gathered or blocked: the kernel reads
    each slot's pages out of HBM itself, ``LATENT_BLOCK_TOKENS`` tokens at a
    time, and only between the first and the last block in which the block
    table maps a page at or before the last row's position. A block there
    with no mapped page is skipped; a slot that maps nothing returns zeros.
    Inside a fetched block an unmapped page reads the null page 0, which
    only ``kv_valid`` keeps out of the result.

    No mesh: the latent row has no head axis to shard, and the serving
    engine refuses tensor parallelism for a latent-cache model."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    if mesh_lib.model_parallel_is_initialized():
        raise NotImplementedError(
            "paged latent decode attention has no sharded form: run a "
            "latent-cache (MLA) model without a model-parallel mesh"
        )
    b, s, h, d_c = q_c.shape
    d_r = q_r.shape[3]
    for pool, d in ((c_pool, d_c), (r_pool, d_r)):
        if pool.ndim != 4 or pool.shape[2] != 1 or pool.shape[3] != d:
            raise ValueError(
                f"latent pool leaf must be (P, page_size, 1, {d}), "
                f"got {pool.shape}"
            )
    # (B, S, H, d) -> (B, R = H*S, d), head-major like the GQA group fold
    fold = lambda q: jnp.swapaxes(q, 1, 2).reshape(b, h * s, q.shape[3])  # noqa: E731
    flat = lambda pool: pool.reshape(pool.shape[:2] + pool.shape[3:])     # noqa: E731
    q_pos = q_pos[None] if q_pos.ndim == 0 else q_pos
    rows_pos = jnp.tile(q_pos.astype(jnp.int32), (h,))  # (R,)
    out = _paged_latent_decode_call(
        fold(q_c), fold(q_r), flat(c_pool), flat(r_pool), block_table,
        rows_pos, kv_valid, page_size, scale, interpret_mode(interpret),
    )
    return jnp.swapaxes(out.reshape(b, h, s, d_c), 1, 2).astype(q_c.dtype)


# --- paged GQA decode that walks the blocks a slot maps ----------------------------
#
# The latent kernel's form (one grid step a slot, the pool left in HBM, a walk
# over the blocks of the row that THIS slot maps, one async copy a run of
# pages into a VMEM buffer of a few blocks: :func:`_latent_block_walk`,
# :func:`_walk_blocks`, :func:`_block_page_copies`) for grouped-query
# attention over a cache whose K and V are ONE joined leaf ``(2 Hkv, D)`` a token
# (``modules/attention.JoinedKVCache``): a page is ``page_size x 2 Hkv x D``
# values (64 KB at 16 tokens of 8 kv heads of 128 in bf16, 16 KB at 2), and all
# the query heads of the slot meet a block while it is in VMEM, the group of
# each kv head against that head's K and V rows (the sparse kernel's
# multiply). It serves both kinds of layer of a stack that mixes WINDOW and
# full attention: the walk runs from the first block in which the block table
# maps a page to the cursor, and a window layer's table maps nothing behind the
# window (the cache manager freed those pages), so there the walk is the
# window; ``floor`` (each slot's lowest attendable column) trims the first
# block. :func:`paged_flash_decode_attention`, one 16-token page a grid step
# up to the shared cursor, is some ten thousand grid steps a layer at eight
# rows of 32,768 columns.

# Tokens a block: a block of 512 x (16, 128) bf16 is 2 MiB of VMEM (three are
# held: :data:`BLOCKS_AHEAD`), and its copies (2 MiB: 32 of a page, or 8 of a
# run of four) last ~3,840 cycles. What the kernel issues
# for a block has to stay under that, and how a kv head's (T, D) rows are taken
# out of the fetched block decides it. Instruction bundles of one block's body
# in the final schedule of a described-v5e compile at Trinity's geometry
# (``chip_smoke.py --only walk --bundles DIR`` makes it again; PERF.md section
# 6, PR 44): ``buf[slot, :, h, :]`` a head, 8,857 (a load a (token, head) row:
# 8,192 loads and ~37,000 shuffles for 512 matrix pushes); the block read once
# and turned with ``jnp.swapaxes``, 2,579; each 32-bit word of a token read
# once with a sublane stride (:func:`_block_head_rows`), 1,696, beside ~1,200
# cycles that name the next block's 32 copies (~190 bundles where its table
# reads runs: one copy a run, PERF.md section 6, PR 52); pages held head-major in the POOL
# would need no shuffle at all, 963, at the price of a layout every
# ``PAGED_LEAVES`` walker would have to learn.
WALK_BLOCK_TOKENS = 512
# ... and the bytes a block may hold: the block is sized by a TOKEN's bytes.
# 512 tokens of (16, 128) bf16 (Trinity's and Solar Open 2's GQA layers) are
# these 2 MiB, and a narrower leaf (ZAYA1's (4, 128)) keeps its 512 tokens;
# a token of 16 + 16 heads of 128 (32 head rows, 8 KiB) fits 256, so that the
# :data:`BLOCKS_AHEAD` + 1 blocks held stay 6 MiB of VMEM where 512 tokens
# would hold 12 of the 16 a kernel may scope on a v5e beside its operands'
# double buffers.
WALK_BLOCK_BYTES = 2 << 20
# Score columns a block of the ROW kernel (:func:`_paged_walk_row_kernel`, one
# query row a kv head): its scores are ``(Hkv, T Hkv)`` float32, and 16 heads
# at 2,048 columns are 32 registers of the file's 64: Ouro's 16 heads take
# 128 tokens a block. ms a call at 2 slots of ~790 / ~1,550 / ~3,000 tokens
# each under a dealt table (v5e, PERF.md section 6, PR 56): blocks of 128
# tokens 0.062 / 0.082 / 0.112, of 256 0.072 / 0.093 / 0.122, of 512 0.076 /
# 0.101 / 0.131 (a third block ahead: no change); all three stream 11-12 ns a
# token (8 KiB: 89% of the HBM peak) and differ in what a slot's walk costs
# before its first block has landed and past its last token.
ROW_BLOCK_COLUMNS = 2048


def walk_row_heads(num_q_heads: int, num_kv_heads: int, itemsize: int) -> int:
    """``num_kv_heads`` where the walking kernel takes its ROW form
    (:func:`_paged_walk_row_kernel`), else 0: one query row a kv head, and a
    token's K head rows fill whole tiles (8 sublanes of 32 bits), so the
    fetched block is multiplied as it lies."""
    return num_kv_heads if num_q_heads == num_kv_heads and num_kv_heads * itemsize % 32 == 0 else 0


def walk_block_tokens(token_bytes: int, page_size: int, row_heads: int = 0) -> int:
    """Tokens a block of the walking kernel holds for a leaf of
    ``token_bytes`` a token: :data:`WALK_BLOCK_TOKENS`, or the whole pages
    that fit :data:`WALK_BLOCK_BYTES` where a token is wider than 4 KiB; for
    the row kernel over ``row_heads`` kv heads, no more than the whole pages
    whose scores are :data:`ROW_BLOCK_COLUMNS` columns."""
    fit = WALK_BLOCK_BYTES // max(int(token_bytes), 1) // page_size * page_size
    tokens = min(WALK_BLOCK_TOKENS, fit)
    if row_heads:
        tokens = min(tokens, ROW_BLOCK_COLUMNS // row_heads // page_size * page_size)
    return max(tokens, page_size)


def _block_head_rows(block_ref):
    """``rows_of(x)``: the ``(T, D)`` rows of head row ``x`` of a fetched block
    ``(T, R, D)`` (a token's ``R`` head rows: K's heads, then V's), read with
    a sublane STRIDE out of the block's 32-bit view. A token's rows are packed
    tiles in VMEM (16 rows of bf16 a tile: two rows a 32-bit sublane), so
    ``block[:, x, :]`` is one sublane-half out of each of ``T`` tiles, and
    Mosaic lowers it to a load a (token, head) row plus the unpacks, rotates
    and selects that assemble registers from them: 45,000 vector operations a
    block for 512 matrix pushes. Viewed as ``(T * R / 2, D)`` 32-bit words,
    word ``w`` of every token is ONE strided load (``T / 8`` registers), and
    the two heads that share it come apart with a shift and a truncating pack.
    Each word is read once, whoever asks for its heads."""
    t, r, d = block_ref.shape
    dtype = block_ref.dtype
    packed = 4 // dtype.itemsize               # head rows a 32-bit word: 2 in bf16, 1 in float32
    words = block_ref.reshape(t * r, d).bitcast(jnp.uint32)       # (T * R / packed, D)
    read = {}

    def rows_of(x):
        word, part = divmod(x, packed)
        if word not in read:
            read[word] = words[pl.ds(word, t, stride=r // packed), :]
        w = read[word]
        if packed > 1:                          # row 2w in the low half, 2w + 1 in the high
            bits = 32 // packed
            w = (w >> (bits * part)).astype(jnp.dtype(f"uint{bits}"))
        return pltpu.bitcast(w, dtype)

    return rows_of


def _paged_walk_kernel(bt_ref, runs_ref, live_ref, span_ref, edge_ref, valid_ref, q_ref,
                       kv_hbm, o_ref, buf, sems, m_scr, l_scr, acc_scr, *,
                       page_size, group, num_kv_heads, scale, use_valid):
    """One slot: walk its live blocks (:func:`_walk_blocks`: the next blocks'
    page copies in flight while one is multiplied). A waited block ``(T, 2
    Hkv, D)`` gives up
    each kv head's K and V rows through :func:`_block_head_rows` (each 32-bit
    word of a token read ONCE a block, with a sublane stride: the comment at
    :data:`WALK_BLOCK_TOKENS` has the three counts), and the head's group of
    query rows meets them: scores and probabilities in float32, the operands
    in their storage type, an online softmax over the blocks."""
    b = pl.program_id(0)
    floor, pos = edge_ref[b, 0], edge_ref[b, 1]
    block = group * page_size

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def multiply(i, kv_block):
        rows = q_ref.shape[2]
        cols = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1) + i * block
        ok = (cols <= pos) & (cols >= floor)
        if use_valid:
            ok = ok & (valid_ref[0, pl.ds(i, 1), :] != 0)
        rows_of = _block_head_rows(_block_rows(kv_block))
        for h in range(num_kv_heads):
            # operands stay in their storage type (bf16 on the chip: the
            # MXU's own), accumulation is float32
            k = rows_of(h)                                     # (T, D)
            v = rows_of(num_kv_heads + h)
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                          # (G, T)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            p = jnp.where(ok, jnp.exp(s - ref), 0.0)
            alpha = jnp.exp(m_prev - ref)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = m_new

    _walk_blocks(bt_ref, runs_ref, live_ref, span_ref, b, ((kv_hbm, buf, None),), sems, group, multiply)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def _paged_walk_row_kernel(bt_ref, runs_ref, live_ref, span_ref, edge_ref, valid_ref, q_ref,
                           kv_hbm, o_ref, buf, sems, m_scr, l_scr, acc_scr, *,
                           page_size, group, num_kv_heads, scale, use_valid):
    """:func:`_paged_walk_kernel` where every kv head has ONE query row (MHA:
    ``q_ref`` is ``(1, Hkv, D)``). Head by head, each head's ``(T, D)`` rows
    have to be taken out of the fetched block (a token's heads lie side by
    side: :func:`_block_head_rows`, four partial loads a register at 32 head
    rows a token) and a row of scores is one sublane of eight in every
    register it touches, each head's maximum, exponential and rescale waiting
    for its own product. Here the block is multiplied AS IT LIES: its K rows
    ``(T Hkv, D)``, token-major, meet all ``Hkv`` query rows in one product,
    ``(Hkv, T Hkv)``, of which row ``h`` keeps the columns of its own head
    (column ``c`` is token ``c // Hkv``, head ``c % Hkv``; the others are
    masked like a column past the cursor), the softmax runs once over whole
    registers, and the masked probabilities meet the block's V rows in one
    product: a zero where the head is another's adds nothing. The matrix
    unit does ``Hkv`` times the needed products, which it has room for beside
    a block's copies; no register is shuffled, as long as a token's ``Hkv``
    K rows are whole tiles (the caller's condition). ``valid_ref`` holds a
    token's validity once a head (the caller repeats it)."""
    b = pl.program_id(0)
    floor, pos = edge_ref[b, 0], edge_ref[b, 1]
    block = group * page_size
    hkv = num_kv_heads

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def multiply(i, kv_block):
        shape = (hkv, block * hkv)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        token = col // hkv + i * block
        ok = (col % hkv == jax.lax.broadcasted_iota(jnp.int32, shape, 0)) & (token <= pos) & (token >= floor)
        if use_valid:
            ok = ok & (valid_ref[0, pl.ds(i, 1), :] != 0)
        rows = _block_rows(kv_block)                           # (T, 2 Hkv, D)
        d = rows.shape[-1]
        k = rows[:, :hkv, :].reshape(block * hkv, d)
        v = rows[:, hkv:, :].reshape(block * hkv, d)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                              # (Hkv, T Hkv)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.where(ok, jnp.exp(s - ref), 0.0)
        alpha = jnp.exp(m_prev - ref)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    _walk_blocks(bt_ref, runs_ref, live_ref, span_ref, b, ((kv_hbm, buf, None),), sems, group, multiply)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def paged_walk_decode_attention(
    q: jax.Array,
    kv_pool: jax.Array,
    block_table: jax.Array,
    q_pos: jax.Array,
    kv_valid: Optional[jax.Array] = None,
    floor: Optional[jax.Array] = None,
    *,
    page_size: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA decode attention straight off the page pool, one query row a slot:
    ``q`` (B, 1, H, D) at cache column ``q_pos`` (1,); ``kv_pool`` (P,
    page_size, 2 Hkv, D), a token's K heads then its V heads; ``block_table``
    (B, n_log); ``kv_valid`` (B, L) bool or None; ``floor`` (B,) int32 each
    slot's lowest attendable column (a window layer's lower edge) or None.
    Softmax over the valid columns in ``[floor, q_pos]`` of ``q_h . k_g(h) /
    sqrt(D)``, times ``v``: (B, 1, H, D).

    The pool is not copied, gathered or blocked: the kernel reads each slot's
    pages out of HBM itself, :func:`walk_block_tokens` tokens at a time, and
    only between the first and the last block in which the block table maps
    a page at or before ``q_pos``. A block there with no mapped page is
    skipped; a slot that maps nothing returns zeros. Inside a fetched block
    an unmapped page reads the null page 0, which ``kv_valid`` or ``floor``
    keeps out of the result (a page the window's manager freed lies below
    ``floor``). Where every kv head has ONE query row (``H == Hkv``) and a
    token's K head rows fill whole tiles, the block is multiplied as it lies
    (:func:`_paged_walk_row_kernel`); otherwise each kv head's rows are taken
    out of it for that head's group of query rows (:func:`_paged_walk_kernel`).
    The kernel or nothing (interpreted only in tests); no mesh."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    if mesh_lib.model_parallel_is_initialized():
        raise NotImplementedError(
            "the paged walking decode kernel has no sharded form: serve a "
            "joined-cache model without a model-parallel mesh")
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"one query row a slot, got {s}")
    if kv_pool.ndim != 4 or kv_pool.shape[2] % 2 or kv_pool.shape[3] != d:
        raise ValueError(
            f"joined K/V pool leaf must be (P, page_size, 2 Hkv, {d}), got {kv_pool.shape}")
    if kv_pool.shape[1] != page_size:
        raise ValueError(f"pool pages hold {kv_pool.shape[1]} tokens, page_size is {page_size}")
    if kv_pool.shape[2] * kv_pool.dtype.itemsize % 4:
        raise ValueError(
            f"a token's {kv_pool.shape[2]} head rows of {kv_pool.dtype} do not fill whole 32-bit words: "
            "the kernel reads a fetched block a word a token")
    interpret = interpret_mode(interpret)
    hkv = kv_pool.shape[2] // 2
    g = h // hkv
    n_log = block_table.shape[1]
    token_bytes = kv_pool.shape[2] * d * kv_pool.dtype.itemsize
    row_heads = walk_row_heads(h, hkv, kv_pool.dtype.itemsize)
    row_form = row_heads > 0
    group = min(max(walk_block_tokens(token_bytes, page_size, row_heads) // page_size, 1), n_log)
    block = group * page_size
    block_table = block_table.astype(jnp.int32)
    pos = jnp.reshape(q_pos, (-1,))[0].astype(jnp.int32)
    live, span = _latent_block_walk(block_table, pos + 1, group, page_size)
    n_blocks = live.shape[1]
    lower = jnp.zeros((b,), jnp.int32) if floor is None else floor.astype(jnp.int32)
    edge = jnp.stack([lower, jnp.broadcast_to(pos, (b,))], axis=1)
    use_valid = kv_valid is not None
    if kv_valid is None:
        kv_valid = jnp.zeros((1, 1), jnp.int32)
        vspec = _SMEM_SPEC
    else:
        # a slot's whole row, one block a sublane: the loop picks row ``i``
        kv_valid = jnp.pad(
            kv_valid.astype(jnp.int32),
            ((0, 0), (0, n_blocks * block - kv_valid.shape[1])),
        ).reshape(b, n_blocks, block)
        if row_form:    # once a head, as the row kernel's columns lie: a token's heads side by side
            kv_valid = jnp.repeat(kv_valid, hkv, axis=2)
        vspec = pl.BlockSpec((1,) + kv_valid.shape[1:], lambda b_, *_: (b_, 0, 0))
    heads = (hkv,) if row_form else (hkv, g)    # the row kernel's query rows are ONE (Hkv, D) array
    rows = pl.BlockSpec((1, *heads, d), lambda b_, *_: (b_,) + (0,) * (len(heads) + 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # block table, its runs, live blocks, each slot's span, its (floor, position)
        grid=(b,),
        in_specs=[vspec, rows, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((BLOCKS_AHEAD + 1, group, page_size, 2 * hkv, d), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((BLOCKS_AHEAD + 1,)),
            pltpu.VMEM((*heads, 1), jnp.float32),
            pltpu.VMEM((*heads, 1), jnp.float32),
            pltpu.VMEM((*heads, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_walk_row_kernel if row_form else _paged_walk_kernel, page_size=page_size, group=group,
            num_kv_heads=hkv, scale=1.0 / (d ** 0.5), use_valid=use_valid,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, *heads, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(block_table, _trip_runs(block_table, group), live, span, edge, kv_valid, q.reshape(b, *heads, d), kv_pool)
    return out.reshape(b, 1, h, d)


# --- paged SPARSE decode (a learned indexer beside GQA) --------------------------
#
# A sparse-attention indexer caches, beside each token's K and V, ONE index key
# of ``d_i`` values (64) shared by its ``H_i`` index heads. A decode step scores
# every token the slot holds, ``I[s] = sum_j w_j relu(q_j . k_idx[s])``, keeps
# the ``topk`` largest and attends those alone. Three pieces:
#
# * :func:`paged_index_scores`: the score of every column of the blocks a slot
#   maps, straight off the paged ``k_idx`` leaf: the latent kernel's block walk
#   and page copies (:func:`_latent_block_walk`, :func:`_block_page_copies`)
#   over one leaf of 64 lanes (padded to 128 in HBM: :func:`_hbm_lanes`); 128
#   bytes a token. Invalid, future and unmapped columns read ``-inf``.
# * the selection, ``jax.lax.top_k`` over the row (the caller's).
# * :func:`paged_sparse_decode_attention`: the selected columns' K and V, and
#   nothing else, fetched a TOKEN at a time through the block table into a
#   two-chunk VMEM buffer: ONE async copy a token, of the cache's joined leaf
#   ``(2 Hkv, D)`` (K's heads, then V's: ``(8, 128)`` in bf16 at Keye's
#   widths, 2 KB), its ``(page, offset)`` read from two prefetched arrays (no
#   divide in the issuing loop), and ONE wait a chunk (a DMA semaphore counts
#   bytes; a chunk's copies fill the whole buffer). The kernel is bound by
#   how many copies it names (~22 ns each on a v5e whoever waits, scalar work
#   in the multiply's own instruction stream), not by their bytes. Online
#   softmax over chunks of ``SPARSE_CHUNK_TOKENS``, the GQA group's query
#   rows of each kv head against that head's K and V rows of the chunk.

# Tokens a chunk of the sparse kernel: 2 chunks x 512 x (2 Hkv, D) = 2 MiB of
# VMEM at Keye's widths. Swept on the chip at the serve cell's shapes (PERF.md
# section 6, PR 31), ms a call: 128 0.481, 256 0.480, 512 0.456 at 8 copies a
# trip; fewer, longer chunks leave less of the multiply standing alone.
SPARSE_CHUNK_TOKENS = 512
# Token copies a trip of the issuing loop, unrolled: 16 copies, what the
# two-leaf form unrolled (8 tokens x K and V). At chunks of 512: 8 0.456, 16
# 0.439, 32 0.427 (not taken: a longer body to lower in every process).
_TOKENS_A_TRIP = 16


def _paged_index_kernel(bt_ref, runs_ref, live_ref, span_ref, bound_ref, valid_ref,
                        q_ref, w_ref, k_hbm, o_ref, k_buf, sems, *, page_size,
                        group, lanes):
    b = pl.program_id(0)
    block = group * page_size
    d = q_ref.shape[2]

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def multiply(i, k_block):
        k = _block_rows(k_block)[:, :d]                        # (T, d_i)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # (H_i, T)
        row = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)
        cols = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) + i * block
        ok = (cols <= bound_ref[0]) & (valid_ref[0, pl.ds(i, 1), :] != 0)
        o_ref[0, pl.ds(i, 1), :] = jnp.where(ok, row, -jnp.inf)

    _walk_blocks(bt_ref, runs_ref, live_ref, span_ref, b, ((k_hbm, k_buf, lanes),), sems, group, multiply)


def paged_index_scores(
    q_idx: jax.Array,
    w_idx: jax.Array,
    kidx_pool: jax.Array,
    block_table: jax.Array,
    q_pos: jax.Array,
    kv_valid: jax.Array,
    page_size: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Index scores of ONE query row a slot against every column the slot
    maps: ``q_idx`` (B, 1, H_i, d_i) rotated index queries, ``w_idx`` (B, 1,
    H_i) their weights, ``kidx_pool`` (P, page_size, 1, d_i) the paged index
    keys, ``q_pos`` the row's slot position (scalar or (1,)), ``kv_valid`` (B,
    L) bool. Returns (B, L) float32: ``sum_j w_j relu(q_j . k[s])`` at valid
    columns ``s <= q_pos`` of mapped blocks, ``-inf`` elsewhere. The kernel
    or nothing (interpreted only in tests); no mesh, as the latent kernel."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    if mesh_lib.model_parallel_is_initialized():
        raise NotImplementedError(
            "the paged index-score kernel has no sharded form: serve an "
            "indexed-cache model without a model-parallel mesh"
        )
    b, s, h_i, d_i = q_idx.shape
    if s != 1:
        raise ValueError(f"one query row a slot, got {s}")
    if kidx_pool.ndim != 4 or kidx_pool.shape[2] != 1 or kidx_pool.shape[3] != d_i:
        raise ValueError(
            f"index-key pool leaf must be (P, page_size, 1, {d_i}), got {kidx_pool.shape}")
    interpret = interpret_mode(interpret)
    n_log = block_table.shape[1]
    group = min(LATENT_BLOCK_TOKENS // page_size, n_log)
    block = group * page_size
    block_table = block_table.astype(jnp.int32)
    bound = jnp.reshape(q_pos, (-1,))[:1].astype(jnp.int32)
    live, span = _latent_block_walk(block_table, bound[0] + 1, group, page_size)
    n_blocks = live.shape[1]
    lanes = _hbm_lanes(d_i, interpret)
    length = kv_valid.shape[1]
    valid = jnp.pad(
        kv_valid.astype(jnp.int32), ((0, 0), (0, n_blocks * block - length)),
    ).reshape(b, n_blocks, block)
    per_slot = lambda *shape: pl.BlockSpec((1,) + shape, lambda b_, *_: (b_, 0, 0))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # block table, its runs, live blocks, spans, the row's position
        grid=(b,),
        in_specs=[
            per_slot(n_blocks, block), per_slot(h_i, d_i), per_slot(h_i, 1),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=per_slot(n_blocks, block),
        scratch_shapes=[
            pltpu.VMEM((BLOCKS_AHEAD + 1, group, page_size, lanes or d_i), kidx_pool.dtype),
            pltpu.SemaphoreType.DMA((BLOCKS_AHEAD + 1,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_index_kernel, page_size=page_size, group=group,
                          lanes=lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_blocks, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(block_table, _trip_runs(block_table, group), live, span, bound, valid, q_idx[:, 0],
      w_idx[:, 0, :, None].astype(jnp.float32),
      kidx_pool.reshape(kidx_pool.shape[:2] + kidx_pool.shape[3:]))
    return out.reshape(b, n_blocks * block)[:, :length]


def _scatter_pages_kernel(ids_ref, window_ref, pool_hbm, out_hbm, sem):
    del pool_hbm  # aliased to ``out_hbm``: the pages not named keep their bytes
    i = pl.program_id(0)
    copy = pltpu.make_async_copy(window_ref.at[0], out_hbm.at[ids_ref[i]], sem)
    copy.start()
    copy.wait()


def paged_scatter_window_pages_dma(pool: jax.Array, window: jax.Array,
                                   block_table: jax.Array, page0: jax.Array,
                                   interpret: Optional[bool] = None) -> jax.Array:
    """:func:`paged_scatter_window_pages` as a kernel: one copy a window
    page into the pool, which is aliased to the result and otherwise left
    where it is. For the pool leaf the sparse kernel reads a token at a
    time: with every user of the carried pool inside the decode scan a
    kernel of ONE layout, no step converts it (with K and V as two leaves of
    4 heads an XLA scatter laid them out ``(page, width)`` minor and each
    step copied every layer's K and V pool whole: 256 MiB a leaf at the
    serve cell's size). Single layer ``(P, page, heads, D)``."""
    b, page_size = block_table.shape[0], pool.shape[1]
    n_win = window.shape[1] // page_size
    ids = jax.lax.dynamic_slice(block_table.astype(jnp.int32), (0, page0), (b, n_win)).reshape(-1)
    vals = window.reshape((b * n_win, page_size) + window.shape[2:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * n_win,),
        in_specs=[
            pl.BlockSpec((1,) + vals.shape[1:], lambda i, ids_: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _scatter_pages_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},   # operands count the scalar prefetch: ids, window, pool
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(interpret),
    )(ids, vals, pool)


def _selected_addresses(block_table, sel_cols, n_sel, page_size):
    """``(chunk, page, off, n_sel)`` for the sparse kernels' scalar prefetch:
    each slot's selected columns as pool rows ``(page, offset)``, (B, K
    rounded up to the chunk) int32, and their count held to ``K``."""
    k_sel = sel_cols.shape[1]
    chunk = min(SPARSE_CHUNK_TOKENS, -(-k_sel // _TOKENS_A_TRIP) * _TOKENS_A_TRIP)
    padded = -(-k_sel // chunk) * chunk
    n_sel = jnp.minimum(n_sel.astype(jnp.int32), k_sel)
    cols = sel_cols.astype(jnp.int32)
    # unselected entries (and the chunk's padding) read token 0 of the null page
    live = jnp.arange(k_sel)[None, :] < n_sel[:, None]
    pad = lambda a: jnp.pad(jnp.where(live, a, 0), ((0, 0), (0, padded - k_sel)))  # noqa: E731
    page = pad(jnp.take_along_axis(block_table.astype(jnp.int32), cols // page_size, axis=1))
    off = pad(cols % page_size)
    return chunk, page, off, n_sel


def _fetch_selected(page_ref, off_ref, kv_hbm, buf, sems, b, chunk, c):
    """Start the copies of chunk ``c`` of slot ``b``'s selected tokens into
    half ``c % 2`` of ``buf``: ONE async copy a token, whatever a token's
    leaf holds."""
    slot = c % 2

    def some(t, carry):
        for u in range(_TOKENS_A_TRIP):
            j = t * _TOKENS_A_TRIP + u
            i = c * chunk + j
            # a token's pool row: (page, offset); the pool keeps its four
            # dims (PR 30: flattening the first two was a whole-pool copy
            # on the chip), and one address read more costs nothing a
            # call can show
            pltpu.make_async_copy(
                kv_hbm.at[page_ref[b, i], off_ref[b, i]], buf.at[slot, j],
                sems.at[slot],
            ).start()
        return carry

    jax.lax.fori_loop(0, chunk // _TOKENS_A_TRIP, some, 0)


def _wait_selected(buf, sems, slot):
    """ONE wait a chunk: a DMA semaphore counts bytes, and a chunk's copies
    (its padding entries copy token 0 of the null page) fill the whole
    buffer, so a descriptor over the buffer names exactly their bytes."""
    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[slot]).wait()


def _sparse_decode_kernel(page_ref, off_ref, n_ref, q_ref, kv_hbm, o_ref, buf,
                          sems, m_scr, l_scr, acc_scr, *, chunk, num_kv_heads,
                          scale):
    b = pl.program_id(0)
    n = n_ref[b]
    n_chunks = (n + chunk - 1) // chunk

    fetch = functools.partial(_fetch_selected, page_ref, off_ref, kv_hbm, buf, sems, b, chunk)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _first():
        fetch(0)

    def step(c, carry):
        @pl.when(c + 1 < n_chunks)
        def _prefetch():
            fetch(c + 1)

        slot = c % 2
        _wait_selected(buf, sems, slot)
        rows = q_ref.shape[2]
        ok = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1) + c * chunk < n
        for h in range(num_kv_heads):
            k = buf[slot, :, h, :]                             # (T, D)
            v = buf[slot, :, num_kv_heads + h, :]
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                          # (G, T)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            p = jnp.where(ok, jnp.exp(s - ref), 0.0)
            alpha = jnp.exp(m_prev - ref)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, step, 0)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def paged_sparse_decode_attention(
    q: jax.Array,
    kv_pool: jax.Array,
    block_table: jax.Array,
    sel_cols: jax.Array,
    n_sel: jax.Array,
    page_size: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA decode attention over SELECTED columns only: ``q`` (B, 1, H, D);
    ``kv_pool`` (P, page_size, 2 Hkv, D), a token's K heads then its V heads
    (:class:`~neuronx_distributed_tpu.modules.attention.IndexedKVCache`'s
    joined leaf); ``sel_cols`` (B, K) int32 logical columns of each slot, of
    which the first ``n_sel`` (B,) count (a ``top_k``'s order: the valid ones
    first); ``block_table`` (B, n_log). Softmax over those columns of ``q_h .
    k_g(h) / sqrt(D)``, times ``v``: (B, 1, H, D). Only the selected tokens'
    K and V leave HBM: ``n_sel`` copies of ``(2 Hkv, D)`` a slot, rounded up
    to the chunk. The kernel or nothing (interpreted only in tests); no
    mesh."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    if mesh_lib.model_parallel_is_initialized():
        raise NotImplementedError(
            "the paged sparse decode kernel has no sharded form: serve an "
            "indexed-cache model without a model-parallel mesh"
        )
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"one query row a slot, got {s}")
    if kv_pool.ndim != 4 or kv_pool.shape[2] % 2 or kv_pool.shape[3] != d:
        raise ValueError(
            f"joined K/V pool leaf must be (P, page_size, 2 Hkv, {d}), got {kv_pool.shape}")
    hkv = kv_pool.shape[2] // 2
    g = h // hkv
    chunk, page, off, n_sel = _selected_addresses(block_table, sel_cols, n_sel, page_size)
    rows = pl.BlockSpec((1, hkv, g, d), lambda b_, *_: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the selected tokens' pages and offsets, their count
        grid=(b,),
        in_specs=[rows, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((2, chunk, 2 * hkv, d), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, chunk=chunk, num_kv_heads=hkv,
                          scale=1.0 / (d ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret_mode(interpret),
    )(page, off, n_sel, q.reshape(b, hkv, g, d), kv_pool)
    return out.reshape(b, 1, h, d)


# --- paged SPARSE LATENT decode (an indexer that selects among MLA's latents) ----
#
# DeepSeek-Sparse-Attention over multi-head latent attention (GLM-5): the
# indexer's ``topk`` columns are rows of the LATENT cache, one ``(c, k_pe)`` a
# token for every head, and the absorbed product runs against those rows
# alone. The cache's joined leaf holds a token as ``(rows, lanes)``: the
# latent in its first ``d_c / lanes`` rows, the rotated key at the start of
# the next (``modules/attention.IndexedLatentKVCache``; ``(8, 128)`` in bf16
# at 512 + 64: a whole HBM tile, which is the unit Mosaic copies). The fetch
# is the GQA kernel's (:func:`_fetch_selected` / :func:`_wait_selected`): ONE
# async copy a selected token, its ``(page, offset)`` from two prefetched
# arrays, one wait a chunk over the whole buffer. What differs is the multiply: all ``H`` heads score the SAME
# rows, so a chunk is ``d_c / lanes + 1`` matmuls of ``(H, lanes) x (lanes,
# chunk)`` for the scores and ``d_c / lanes`` of ``(H, chunk) x (chunk,
# lanes)`` for the values, a row of the token's tile each (no reshape of the
# buffer: a relayout in VMEM).
#
# Measured alone at GLM-5's serve shapes (8 slots x 2048 selected, 64 heads;
# v5e, PERF.md section 6, PR 32), ms a call: this form 0.526 (chunks of 256:
# 0.543; 32 copies a trip: 0.517, not taken as in PR 31); the latent as a
# leaf of (4, 128) and the rotated key as a second of (2, 128), two copies a
# token: 1.403.


def _sparse_latent_kernel(page_ref, off_ref, n_ref, qc_ref, qr_ref, kv_hbm,
                          o_ref, buf, sems, m_scr, l_scr, acc_scr, *, chunk,
                          scale):
    b = pl.program_id(0)
    n = n_ref[b]
    n_chunks = (n + chunk - 1) // chunk
    heads, d_c = qc_ref.shape[1], qc_ref.shape[2]
    d_r, lanes = qr_ref.shape[2], buf.shape[3]
    n_c = d_c // lanes

    fetch = functools.partial(_fetch_selected, page_ref, off_ref, kv_hbm, buf, sems, b, chunk)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _first():
        fetch(0)

    def step(c, carry):
        @pl.when(c + 1 < n_chunks)
        def _prefetch():
            fetch(c + 1)

        slot = c % 2
        _wait_selected(buf, sems, slot)
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            qr_ref[0], buf[slot, :, n_c, :][:, :d_r], dims,
            preferred_element_type=jnp.float32)                # (H, T)
        for r in range(n_c):
            s = s + jax.lax.dot_general(
                qc_ref[0, :, r * lanes:(r + 1) * lanes], buf[slot, :, r, :], dims,
                preferred_element_type=jnp.float32)
        s = s * scale
        ok = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 1) + c * chunk < n
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.where(ok, jnp.exp(s - ref), 0.0)
        alpha = jnp.exp(m_prev - ref)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        p = p.astype(buf.dtype)
        for r in range(n_c):     # the values are the latent rows already in VMEM
            cols = slice(r * lanes, (r + 1) * lanes)
            acc_scr[:, cols] = acc_scr[:, cols] * alpha + jax.lax.dot_general(
                p, buf[slot, :, r, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, step, 0)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def paged_sparse_latent_decode_attention(
    q_c: jax.Array,
    q_r: jax.Array,
    kv_pool: jax.Array,
    block_table: jax.Array,
    sel_cols: jax.Array,
    n_sel: jax.Array,
    *,
    scale: float,
    page_size: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Absorbed latent decode attention over SELECTED columns only: ``q_c``
    (B, 1, H, d_c) the heads' content queries with ``W_uk`` folded in,
    ``q_r`` (B, 1, H, d_r) their rotated part; ``kv_pool`` (P, page_size,
    rows, lanes), a token's latent in its first ``d_c / lanes`` rows and its
    rotated key at the start of the next
    (:class:`~neuronx_distributed_tpu.modules.attention.IndexedLatentKVCache`'s
    joined leaf); ``sel_cols`` (B, K) / ``n_sel`` (B,) / ``block_table`` as
    :func:`paged_sparse_decode_attention`. Softmax in float32 over those
    columns of ``(q_c . c + q_r . k_pe) * scale``, values the latent rows:
    (B, 1, H, d_c) for the caller's ``W_uv``. Only the selected tokens' tiles
    leave HBM. The kernel or nothing (interpreted only in tests); no mesh."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    if mesh_lib.model_parallel_is_initialized():
        raise NotImplementedError(
            "the paged sparse latent decode kernel has no sharded form: serve "
            "an indexed-latent-cache model without a model-parallel mesh"
        )
    b, s, h, d_c = q_c.shape
    d_r = q_r.shape[3]
    if s != 1:
        raise ValueError(f"one query row a slot, got {s}")
    rows, lanes = kv_pool.shape[2:] if kv_pool.ndim == 4 else (0, 1)
    if kv_pool.ndim != 4 or d_c % lanes or d_r > lanes or rows < d_c // lanes + 1:
        raise ValueError(
            f"joined latent pool leaf must be (P, page_size, rows > {d_c} / lanes, lanes >= "
            f"{d_r}), got {kv_pool.shape}")
    chunk, page, off, n_sel = _selected_addresses(block_table, sel_cols, n_sel, page_size)
    per_slot = lambda d: pl.BlockSpec((1, h, d), lambda b_, *_: (b_, 0, 0))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the selected tokens' pages and offsets, their count
        grid=(b,),
        in_specs=[per_slot(d_c), per_slot(d_r), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_slot(d_c),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, rows, lanes), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d_c), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sparse_latent_kernel, chunk=chunk, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d_c), q_c.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret_mode(interpret),
    )(page, off, n_sel, q_c[:, 0], q_r[:, 0], kv_pool)
    return out[:, None]
