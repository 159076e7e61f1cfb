"""The flash forward as it stood before the tiles were classed (PR 44's tree:
a rectangular grid, every pair's K and V fetched, one masked body): the
reference ``test_flash_attention.py`` holds the classed forward's content rows
to, bit for bit. Not collected: no test lives here."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.kernels.flash_attention import (
    _SMEM_SPEC,
    NEG_INF,
    _off_arr,
    _seg_operands,
    _seg_specs,
)


def _fwd_kernel(q_off_ref, k_off_ref, qseg_ref, kseg_ref, qmin_ref, qmax_ref,
                kmin_ref, kmax_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal, scale, block_q, block_k,
                num_k_blocks, dyn_offsets, segments):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # k block

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip K blocks entirely above the diagonal. With dynamic global
    # offsets (ring attention: this shard's rows start at q_off, the visiting
    # K/V shard's at k_off) the skip test moves to runtime — a fully-future
    # K shard skips every block, leaving l = 0 → lse ≈ -inf, which the ring
    # merge treats as a zero contribution.
    q_off = q_off_ref[0] if dyn_offsets else 0
    k_off = k_off_ref[0] if dyn_offsets else 0
    run = (
        (k_off + j * block_k <= q_off + i * block_q + block_q - 1)
        if causal
        else True
    )
    if segments:
        # skip block pairs whose segment-id ranges cannot intersect
        bidx = pl.program_id(0)
        overlap = (qmax_ref[bidx, i] >= kmin_ref[bidx, j]) & (
            qmin_ref[bidx, i] <= kmax_ref[bidx, j]
        )
        run = overlap if run is True else (run & overlap)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # (BQ, BK)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q + q_off
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k + k_off
            s = jnp.where(rows >= cols, s, NEG_INF)
        if segments:
            qs = qseg_ref[0]                           # (BQ, 1)
            ks = kseg_ref[0]                           # (1, BK)
            s = jnp.where(qs == ks, s, NEG_INF)
        m_prev = m_scr[:]                              # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exp-safe reference point: rows with every key masked so far keep
        # m = -inf; subtracting a finite 0 makes exp(s - ref) underflow to 0
        # instead of exp(-inf - -inf) = 1 polluting l
        ref = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.exp(s - ref)                           # (BQ, BK)
        alpha = jnp.exp(m_prev - ref)                  # (BQ, 1)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new

    @pl.when(j == num_k_blocks - 1)
    def _finish():
        l = l_scr[:]
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:] + jnp.log(jnp.maximum(l, 1e-30))


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int, interpret: bool,
               q_off=None, k_off=None, q_seg=None, k_seg=None):
    """Forward kernel call. ``q`` (B, H, S, D); ``k``/``v`` (B, Hkv, Sk, D)
    with Hkv | H — the BlockSpec head map serves GQA natively, no repeat.
    ``q_off``/``k_off`` are dynamic global position offsets for the causal
    mask (ring attention); None compiles the static zero-offset fast path.
    ``q_seg``/``k_seg`` (B, S)/(B, Sk) int32 segment ids enable the
    equal-segment mask (packed documents / padding)."""
    b, h, s, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[3]  # the value head size may differ from q/k's (MLA: 192/128)
    group = h // hkv
    nq, nk = s // block_q, sk // block_k
    scale = 1.0 / (d ** 0.5)
    dyn = q_off is not None or k_off is not None
    segments = q_seg is not None
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, num_k_blocks=nk, dyn_offsets=dyn,
        segments=segments,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            _SMEM_SPEC,
            _SMEM_SPEC,
            *_seg_specs(
                segments, block_q, block_k,
                lambda b_, h_, i, j: (b_, i),
                lambda b_, h_, i, j: (b_, j),
            ),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        _off_arr(q_off if q_off is not None else 0),
        _off_arr(k_off if k_off is not None else 0),
        *_seg_operands(q_seg, k_seg, block_q, block_k),
        q, k, v,
    )
    return out, lse
