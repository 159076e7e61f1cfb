"""The masked and the banded prefill forward as they stood before they took
the flash forward's form (PR 49's tree: a rectangular grid, the running max and
sum as columns, every tile of the bucket multiplied and masked twice): the
reference ``test_grouped_forward.py`` and ``chip_smoke.py`` hold the new
kernels' content rows to, bit for bit. Not collected: no test lives here."""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.kernels.backend import interpret_mode
from neuronx_distributed_tpu.kernels.flash_attention import NEG_INF, _diag_block, _pick_block


def _masked_fwd_kernel(keep_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                       acc_scr, *, scale, block_q, block_k, num_k_blocks, group):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # k block

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_k <= i * block_q + block_q - 1)   # tiles above the diagonal keep nothing
    def _body():
        keep = keep_ref[0] != 0                        # (BQ, BK)
        k = k_ref[0, 0]                                # (BK, D), storage type: the MXU's own
        v = v_ref[0, 0]
        for g in range(group):
            s = jax.lax.dot_general(
                q_ref[0, 0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # (BQ, BK)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            p = jnp.where(keep, jnp.exp(s - ref), 0.0)
            alpha = jnp.exp(m_prev - ref)
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[g] = m_new

    @pl.when(j == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def masked_flash_attention(q, k, v, keep, block_q: int = 512, block_k: int = 512,
                           interpret: Optional[bool] = None):
    """Attention of q (B, S, H, D) over k/v (B, S, Hkv, D) where ``keep`` (B,
    S, S) int8 is nonzero (rows queries, columns keys; CAUSAL: nothing above
    the diagonal may be kept, those tiles are not read). Softmax in float32
    over the kept keys of ``q . k / sqrt(D)``; a row that keeps nothing
    returns zeros. (B, S, H, Dv)."""
    b, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    group = h // hkv
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    nq, nk = s // bq, s // bk
    qt = jnp.swapaxes(q, 1, 2).reshape(b, hkv, group, s, d)
    kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)

    def last(i):  # the last k block a q block reads: later steps name it again, and fetch nothing
        return _diag_block(i, bq, bk)

    out = pl.pallas_call(
        functools.partial(
            _masked_fwd_kernel, scale=1.0 / (d ** 0.5), block_q=bq, block_k=bk,
            num_k_blocks=nk, group=group,
        ),
        grid=(b, hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, bk), lambda b_, h_, i, j: (b_, i, jnp.minimum(j, last(i)))),
            pl.BlockSpec((1, 1, group, bq, d), lambda b_, h_, i, j: (b_, h_, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_, jnp.minimum(j, last(i)), 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b_, h_, i, j: (b_, h_, jnp.minimum(j, last(i)), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, bq, dv), lambda b_, h_, i, j: (b_, h_, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, bq, 1), jnp.float32),
            pltpu.VMEM((group, bq, 1), jnp.float32),
            pltpu.VMEM((group, bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
    )(keep, qt, kt, vt)
    return jnp.swapaxes(out.reshape(b, h, s, dv), 1, 2)


def _band_first(i, block_q: int, block_k: int, window: int):
    """The first key block query block ``i`` reads: that of its first row's
    lowest visible column."""
    return jnp.maximum(i * block_q - window + 1, 0) // block_k


def _banded_fwd_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                       scale, block_q, block_k, steps, group, window, use_valid):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # step along this q block's band

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    jj = _band_first(i, block_q, block_k, window) + j       # the key block

    @pl.when(jj * block_k <= i * block_q + block_q - 1)     # past the diagonal: nothing
    def _body():
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + jj * block_k
        keep = (rows >= cols) & (cols > rows - window)
        if use_valid:
            keep = keep & (valid_ref[0] != 0)              # (1, BK)
        k = k_ref[0, 0]                                    # (BK, D), storage type
        v = v_ref[0, 0]
        for g in range(group):
            s = jax.lax.dot_general(
                q_ref[0, 0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                      # (BQ, BK)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            p = jnp.where(keep, jnp.exp(s - ref), 0.0)
            alpha = jnp.exp(m_prev - ref)
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[g] = m_new

    @pl.when(j == steps - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def banded_flash_attention(q, k, v, window: int, kv_valid=None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: Optional[bool] = None):
    """Causal attention of q (B, S, H, D) over k/v (B, S, Hkv, D) in which
    query ``i`` reads keys ``i - window < j <= i``, ``kv_valid`` (B, S)
    nonzero at keys that are not padding. Softmax in float32 over the kept
    keys of ``q . k / sqrt(D)``; (B, S, H, Dv)."""
    b, length, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    group = h // hkv
    # a length that is no multiple of a tile (the engine's buckets are; its
    # exact-length fallback at the row's end is not) is padded on the right:
    # no query reads a key after it, and the padded rows are cut off again
    s = -(-length // 128) * 128 if length > 128 else -(-length // 8) * 8
    if s != length:
        pad = lambda a: jnp.pad(a, ((0, 0), (0, s - length)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        q, k, v = pad(q), pad(k), pad(v)
        kv_valid = None if kv_valid is None else pad(kv_valid)
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    nq, nk = s // bq, s // bk
    steps = min(nk, (bq + window - 2) // bk + 2)
    qt = jnp.swapaxes(q, 1, 2).reshape(b, hkv, group, s, d)
    kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    use_valid = kv_valid is not None
    valid = (kv_valid.astype(jnp.int32) if use_valid else jnp.ones((1, bk), jnp.int32))[:, None, :]

    def key_block(i, j):
        # the band's blocks, then the last one again: a repeated block is not fetched
        return jnp.minimum(_band_first(i, bq, bk, window) + j, _diag_block(i, bq, bk))

    valid_map = (
        (lambda b_, h_, i, j: (b_, 0, key_block(i, j))) if use_valid
        else (lambda b_, h_, i, j: (0, 0, 0)))
    out = pl.pallas_call(
        functools.partial(
            _banded_fwd_kernel, scale=1.0 / (d ** 0.5), block_q=bq, block_k=bk,
            steps=steps, group=group, window=window, use_valid=use_valid,
        ),
        grid=(b, hkv, nq, steps),
        in_specs=[
            pl.BlockSpec((1, 1, bk), valid_map),
            pl.BlockSpec((1, 1, group, bq, d), lambda b_, h_, i, j: (b_, h_, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_, key_block(i, j), 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b_, h_, i, j: (b_, h_, key_block(i, j), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, bq, dv), lambda b_, h_, i, j: (b_, h_, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, bq, 1), jnp.float32),
            pltpu.VMEM((group, bq, 1), jnp.float32),
            pltpu.VMEM((group, bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
    )(valid, qt, kt, vt)
    return jnp.swapaxes(out.reshape(b, h, s, dv), 1, 2)[:, :length]
