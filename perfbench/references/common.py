"""What the plain references share: float32 everywhere, matmul precision
``highest`` (on a TPU a float32 matmul otherwise runs in bf16 passes), no
kernels, no cache, no batching tricks. A reference is fed the system's own
weights ONE LAYER AT A TIME and upcasts them inside the layer's program, so a
model whose float32 copy would not fit beside the engine still has a
reference on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def highest(fn):
    """``jax.jit(fn)`` traced under ``default_matmul_precision('highest')``."""

    @functools.wraps(fn)
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    return jax.jit(wrapped)


def rope_half_split(x, positions, rot: int, theta: float):
    """Rotary embedding over the first ``rot`` channels of each head of ``x``
    (B, S, H, D), pairing channel ``i`` with ``i + rot/2`` — the convention
    the repo's ``apply_rope`` uses. The published GPT-J/CodeGen code pairs
    even with odd channels instead; the two differ by a fixed permutation of
    the q/k projection's columns, which random weights cannot tell apart
    (noted as a departure: loading published weights needs that permutation).
    """
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * inv       # (B, S, rot/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def causal_attention(q, k, v):
    """Softmax attention, q (B, S, H, D) against k/v (B, S, Hkv, D) with
    ``H = Hkv * group``: query head ``h`` reads kv head ``h // group``."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def cross_entropy(logits, labels):
    """Mean token cross entropy, float32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0].mean()


def emitted_token_gaps(ref, prompt, tokens, pad_to: int):
    """``(gaps, controls, margin, router_margins)`` for one served request.
    ``gaps[i]``: the reference's maximum logit at the position that produced
    emitted token ``i``, less the reference's logit of that token (0 where the
    reference agrees). ``controls[i]``: the same for the token's successor id,
    a wrong answer, so that the check is seen to be able to fail. ``margin``:
    the reference's median top-1/top-2 margin. ``router_margins[i]``: how
    close the reference's own expert choice was at that position (``None``
    for a reference without a router). Teacher-forced on the emitted tokens;
    right padding cannot reach earlier positions of a causal model."""
    p, n = len(prompt), len(tokens)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :p] = prompt
    ids[0, p:p + n] = tokens
    with_router = getattr(ref, "logits_and_router_margin", None)
    logits, router = with_router(ids) if with_router else (ref.logits(ids), None)
    rows = np.asarray(logits[0, p - 1:p - 1 + n], np.float32)   # token i <- position p-1+i
    toks = np.asarray(tokens, np.int64)
    top2 = -np.sort(-rows, axis=1)[:, :2]
    chosen = rows[np.arange(n), toks]
    wrong = rows[np.arange(n), (toks + 1) % rows.shape[1]]
    if router is not None:
        router = np.asarray(router[0, p - 1:p - 1 + n], np.float32)
    return top2[:, 0] - chosen, top2[:, 0] - wrong, float(np.median(top2[:, 0] - top2[:, 1])), router


def judge_gaps(gaps, router_margins, tolerance: float, near_tie: float):
    """``(ok, over, exempt)``. Every gap must be a finite number. Every gap
    must be within ``tolerance``, but for positions where the reference's own
    router margin is under ``near_tie``: there the system may have met another
    expert than the reference, and nowhere else. ``over`` counts the gaps that
    fail, ``exempt`` the positions excused. A reference without a router
    (``router_margins`` of ``None``) excuses nothing."""
    gaps = np.asarray(gaps, np.float64)
    near = (np.zeros(gaps.shape, bool) if router_margins is None
            else np.asarray(router_margins, np.float64) < near_tie)
    bad = ~np.isfinite(gaps) | ((gaps > tolerance) & ~near)
    return not bad.any(), int(bad.sum()), int(near.sum())
