"""Pallas gated delta-rule kernels: the recurrent state of a linear-attention
layer with a per-channel decay (KDA; ``models/solar_open2.py``), forward only.

A head keeps a float32 state ``S`` (key channel x value channel, ``d x d``).
Token ``t`` with unit key ``k``, value ``v``, query ``q``, per-channel log decay
``g <= 0`` and write strength ``beta`` in (0, 2)::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

:func:`delta_rule_scan` is that recurrence under ``lax.scan`` (training, and a
model asked for ``attention_impl="xla"``). The two kernels are what serving
runs:

:func:`kda_decode_step`: one token a slot. Grid ``(slot, head block)``; a
block's states come in, take the three equations on the VPU and go out IN
PLACE (``input_output_aliases``): the state's bytes once in and once out are
the step's traffic and nothing else of that size exists. The state has the
key channel on sublanes, so the per-key vectors (decay, ``beta k``, ``k``,
``q``) are handed in as COLUMNS, ``(d, 4 heads-of-the-block)`` a block, laid
out by XLA from a few KB a slot; the value-side vector ``beta v`` as rows.

:func:`kda_chunk_prefill`: a prompt, in chunks of ``chunk`` tokens. Grid
``(row, head, chunk)`` with the chunk axis sequential and ``S`` in a float32
VMEM scratch across it. With ``G`` the cumulative log decay inside a chunk
(``G_t = g_1 + ... + g_t``), ``A = tril(Diag(beta) (K e^G)(K e^-G)^T, -1)`` and
``P = tril((Q e^G)(K e^-G)^T)``::

    (I + A) U = beta (V - (K e^G) S)            # forward substitution
    O = (Q e^G) S + P U
    S <- Diag(e^{G_last}) S + (K e^{G_last - G})^T U

(the published W/U form with ``U - W S`` folded into the right-hand side: the
state is resident, so the solve runs once at the value's width). ``e^-G`` is
never formed: a channel may decay by hundreds of nats inside a chunk and
``e^{-G}`` overflows float32 past 88. Every exponent is a difference ``G_i -
G_j <= 0``: for a pair of sub-blocks of 16 tokens taken against the LATER
sub-block's first row (``e^{G_i - G_n}`` on its rows, ``e^{G_n - G_j}`` on the
earlier one's), inside a sub-block pair by pair on the VPU, which is also
where the forward substitution runs row by row (a product of powers of ``A``
would cancel catastrophically for repeated keys at ``beta`` near 2). Chunks
before a left-padded prompt's first token are not visited (their inputs are
not fetched either); a padding token inside a live chunk comes with ``beta =
0`` and ``g = 0`` and changes nothing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.kernels.backend import interpret_mode

CHUNK = 128          # tokens a grid step of the prefill kernel
SUB_BLOCK = 16       # tokens a forward-substitution block
DECODE_HEADS = 8     # heads a grid step of the decode kernel (a float32 sublane tile of vectors)

_HIGHEST = jax.lax.Precision.HIGHEST


def delta_rule_scan(q, k, v, g, beta, state=None):
    """The recurrence itself: ``q, k, v, g`` (B, S, H, d), ``beta`` (B, S, H),
    ``state`` (B, H, d, d) float32 (zeros when None). Returns ``(o (B, S, H,
    d) float32, the state after the last token)``. A token with ``beta = 0``
    and ``g = 0`` leaves the state as it was."""
    b, _, h, d = q.shape
    f32 = jnp.float32
    if state is None:
        state = jnp.zeros((b, h, d, d), f32)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), state


# --- decode: one token a slot ---------------------------------------------------


def _decode_kernel(s_ref, cols_ref, vb_ref, o_ref, s_out_ref, *, heads: int):
    cols = cols_ref[...]                                   # (d, 4 * heads)
    for h in range(heads):
        decay, kb, k, q = (cols[:, j * heads + h:j * heads + h + 1] for j in range(4))   # (d, 1) each
        s = s_ref[h] * decay
        u = vb_ref[h:h + 1, :] - jnp.sum(s * kb, axis=0, keepdims=True)                 # (1, d)
        s = s + k * u
        o_ref[h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)
        s_out_ref[h] = s


def kda_decode_step(state, q, k, v, g, beta, *, interpret: Optional[bool] = None):
    """One token a slot through the state: ``state`` (B, H, d, d) float32,
    ``q, k, v, g`` (B, H, d), ``beta`` (B, H). Returns ``(o (B, H, d) float32,
    the new state)``, the state updated in place. A slot that must keep its
    state comes with ``beta = 0`` and ``g = 0``."""
    b, h, d, _ = state.shape
    f32 = jnp.float32
    hb = DECODE_HEADS if h % DECODE_HEADS == 0 else h
    beta = beta.astype(f32)[..., None]
    vectors = jnp.stack([jnp.exp(g.astype(f32)), beta * k.astype(f32), k.astype(f32), q.astype(f32)], axis=1)
    # (B, 4, H, d) -> a head block's columns (d, 4 * hb): vector j of head i in lane j * hb + i
    cols = vectors.reshape(b, 4, h // hb, hb, d).transpose(0, 2, 4, 1, 3).reshape(b, h // hb, d, 4 * hb)
    vb = beta * v.astype(f32)
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((None, hb, d, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, d, 4 * hb), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, hb, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, hb, d, d), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, d), f32), jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(interpret),
    )(state.astype(f32), cols, vb)
    return o, state


# --- prefill: a prompt in chunks ------------------------------------------------


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """``a @ b.T``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _prefill_kernel(start_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, s_out_ref,
                    s_ref, q32_ref, kb32_ref, g_cum_ref, u_ref, o32_ref, *, chunk: int, sub: int):
    f32 = jnp.float32
    row, c = pl.program_id(0), pl.program_id(2)
    d = s_ref.shape[0]

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    live = c >= start_ref[row] // chunk

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        s0 = s_ref[...]
        k = k_ref[...].astype(f32)
        q32_ref[...] = q_ref[...].astype(f32)
        kb32_ref[...] = kb_ref[...].astype(f32)
        # the cumulative log decay, inclusive: a lower-triangular sum on the MXU
        tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)).astype(f32)
        g_cum = _dot(tri, g_ref[...])
        g_cum_ref[...] = g_cum
        from_start = jnp.exp(g_cum)                                    # <= 1
        # what the state the chunk was entered with gives every row: both products at once
        entered = _dot(jnp.concatenate([kb32_ref[...] * from_start, q32_ref[...] * from_start]), s0)
        u_ref[...] = vb_ref[...].astype(f32) - entered[:chunk]         # the right-hand side; solved row by row
        o32_ref[...] = entered[chunk:]
        token = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        for lo in range(0, chunk, sub):
            blk = slice(lo, lo + sub)
            g_blk, k_blk = g_cum[blk], k[blk]
            if lo:
                # against the EARLIER sub-blocks, through this block's first row: both exponents <= 0
                first = g_cum[lo:lo + 1]
                since = jnp.exp(g_blk - first)
                until = k[:lo] * jnp.exp(first - g_cum[:lo])
                both = _dot_nt(jnp.concatenate([kb32_ref[blk] * since, q32_ref[blk] * since]), until)
                both = _dot(both, u_ref[:lo])
                u_ref[blk] = u_ref[blk] - both[:sub]
                o32_ref[blk] = o32_ref[blk] + both[sub:]

            def one_row(r, _, lo=lo, g_blk=g_blk, k_blk=k_blk):
                at = pl.ds(lo + r, 1)
                # this row against each row of its sub-block, pair by pair: G_r - G_s <= 0 for s <= r
                decayed = k_blk * jnp.exp(jnp.minimum(g_cum_ref[at] - g_blk, 0.0))
                a = jnp.sum(decayed * kb32_ref[at], axis=1, keepdims=True)        # (sub, 1): A[r, s]
                p = jnp.sum(decayed * q32_ref[at], axis=1, keepdims=True)         # P[r, s]
                solved = u_ref[pl.ds(lo, sub)]
                u_r = u_ref[at] - jnp.sum(jnp.where(token < r, a, 0.0) * solved, axis=0, keepdims=True)
                u_ref[at] = u_r
                own = jnp.sum(jnp.where(token == r, p, 0.0), axis=0, keepdims=True)
                o32_ref[at] = (o32_ref[at] + own * u_r
                               + jnp.sum(jnp.where(token < r, p, 0.0) * solved, axis=0, keepdims=True))
                return _

            jax.lax.fori_loop(0, sub, one_row, None, unroll=True)
        o_ref[...] = o32_ref[...].astype(o_ref.dtype)
        last = g_cum[chunk - 1:chunk]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
        carried = jnp.where(eye, jnp.exp(last), 0.0)                   # Diag(e^{G_last})
        written = k * jnp.exp(last - g_cum)                            # exponents <= 0
        s_ref[...] = (_dot(carried, s0) + _dot(written.T, u_ref[...]))

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s_ref[...]


def kda_chunk_prefill(q, k, v, g, beta, valid=None, *, chunk: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """A prompt through the state from zero: ``q, k, v`` (B, S, H, d), ``g`` (B,
    S, H, d) float32 log decay, ``beta`` (B, S, H), ``valid`` (B, S) True at the
    row's tokens (padding on ONE side; None: every column). Returns ``(o (B,
    S, H, d) in q's dtype, the state after each row's last token (B, H, d, d)
    float32)``. Rows of ``o`` at padding columns hold nothing of use."""
    b, s, h, d = q.shape
    f32 = jnp.float32
    g, beta = g.astype(f32), beta.astype(f32)
    start = jnp.zeros((b,), jnp.int32)
    if valid is not None:
        valid = valid.astype(jnp.bool_)
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
        start = jnp.where(valid.any(axis=1), jnp.argmax(valid, axis=1), s).astype(jnp.int32)
    if chunk is None:
        chunk = min(CHUNK, -(-s // SUB_BLOCK) * SUB_BLOCK)
    if chunk % SUB_BLOCK:
        raise ValueError(f"chunk {chunk} is no multiple of the sub-block {SUB_BLOCK}")
    kb = (beta[..., None] * k.astype(f32)).astype(k.dtype)
    vb = (beta[..., None] * v.astype(f32)).astype(v.dtype)
    padded = -(-s // chunk) * chunk

    def flat(a):      # (B, S, H, d) -> (B, padded, H d): a head is a block of d lanes
        return jnp.pad(a.reshape(b, s, h * d), ((0, 0), (0, padded - s), (0, 0)))

    def tokens(i, j, c, start_ref):       # a skipped chunk names the first live one: nothing is fetched for it
        return i, jnp.maximum(c, jnp.minimum(start_ref[i] // chunk, padded // chunk - 1)), j

    block = pl.BlockSpec((None, chunk, d), tokens)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, padded // chunk),
        in_specs=[block] * 5,
        out_specs=[
            pl.BlockSpec((None, chunk, d), lambda i, j, c, start_ref: (i, c, j)),
            pl.BlockSpec((None, None, d, d), lambda i, j, c, start_ref: (i, j, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), f32)] + [pltpu.VMEM((chunk, d), f32)] * 5,
    )
    o, state = pl.pallas_call(
        functools.partial(_prefill_kernel, chunk=chunk, sub=SUB_BLOCK),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, padded, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, d, d), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(start, flat(q), flat(k), flat(kb), flat(vb), flat(g))
    return o[:, :s].reshape(b, s, h, d), state
