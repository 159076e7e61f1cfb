"""On-device token sampling (reference: ``utils/sampling.py:77`` — avoids
``torch.multinomial`` host syncs with an on-device sampler; here the Gumbel
trick keeps everything inside the compiled program).

All functions take logits ``(..., V)`` and return int32 token ids ``(...,)``.
``top_k``/``top_p``/temperature compose in the standard order: temperature →
top-k filter → top-p filter → sample.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def greedy(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _filter_top_k(logits: jax.Array, k: int) -> jax.Array:
    vals, _ = jax.lax.top_k(logits, k)
    thresh = vals[..., -1:]
    return jnp.where(logits < thresh, -jnp.inf, logits)


def _filter_top_p(logits: jax.Array, p: float) -> jax.Array:
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep the smallest prefix with cumulative prob ≥ p (always ≥ 1 token)
    cutoff_mask = cum - probs < p
    thresh = jnp.where(cutoff_mask, sorted_logits, jnp.inf).min(-1, keepdims=True)
    return jnp.where(logits < thresh, -jnp.inf, logits)


def sample(
    logits: jax.Array,
    key: jax.Array,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jax.Array:
    """Temperature / top-k / top-p sampling via Gumbel-max — one fused XLA
    program, no host round-trip."""
    if temperature == 0.0:
        return greedy(logits)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None and top_k > 0:
        logits = _filter_top_k(logits, top_k)
    if top_p is not None and top_p < 1.0:
        logits = _filter_top_p(logits, top_p)
    gumbel = jax.random.gumbel(key, logits.shape, jnp.float32)
    return jnp.argmax(logits + gumbel, axis=-1).astype(jnp.int32)


# --- per-row sampling (continuous-batching serving) ---------------------------
#
# The serving engine runs ONE jitted decode step over all slots, so the
# sampling config (temperature/top-k/top-p) must be TRACED per-row data, not
# python constants. Sentinels replace None: top_k <= 0 and top_p >= 1.0
# disable the respective filter, temperature == 0.0 is greedy — exactly the
# conditions `sample` checks in python.

def _filtered_row(
    logits: jax.Array,
    key: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """The sampling side of :func:`sample_row`: the whole body of every row
    of a batch in which some kept row samples, its greedy rows included
    (their token is their ``argmax``, taken by the last line)."""
    v = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.asarray(temperature, jnp.float32)
    x = logits.astype(jnp.float32) / jnp.where(temp == 0.0, 1.0, temp)
    # top-k: threshold at the k-th largest (== lax.top_k(x, k)[0][-1])
    k = jnp.asarray(top_k, jnp.int32)
    desc = jnp.sort(x, axis=-1)[..., ::-1]
    kth = desc[jnp.clip(k, 1, v) - 1]
    x = jnp.where((k > 0) & (x < kth), -jnp.inf, x)
    # top-p: smallest prefix with cumulative prob >= p (mirrors _filter_top_p).
    # The filtered x sorted descending == the filter applied to `desc`
    # elementwise (the filter maps a down-set to -inf, preserving order), so
    # the second O(V log V) sort is free
    p = jnp.asarray(top_p, jnp.float32)
    sorted_logits = jnp.where((k > 0) & (desc < kth), -jnp.inf, desc)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_mask = cum - probs < p
    thresh = jnp.where(cutoff_mask, sorted_logits, jnp.inf).min(-1)
    x = jnp.where((p < 1.0) & (x < thresh), -jnp.inf, x)
    gumbel = jax.random.gumbel(key, x.shape, jnp.float32)
    tok = jnp.argmax(x + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temp == 0.0, greedy_tok, tok)


def sample_row(
    logits: jax.Array,
    key: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """One row's token from ``logits`` (V,) with traced scalar config.

    Numerically identical to :func:`sample` on the same (logits, key,
    config): the filters apply the same thresholds (k-th largest value /
    smallest top-p prefix) and the Gumbel draw over (V,) consumes the same
    bits as `sample`'s over (1, V), so a request served through the engine's
    per-slot path reproduces its solo `generate()` tokens bit-for-bit.

    A greedy row (``temperature == 0``) is its ``argmax`` and nothing else:
    the branch is a conditional on the traced scalar, so the sort, the
    softmax and the Gumbel draw over the vocabulary run for a sampled row
    only. Batched, call :func:`sample_per_row`: under ``vmap`` the
    conditional becomes a select that computes both sides."""
    return jax.lax.cond(
        jnp.asarray(temperature, jnp.float32) != 0.0,
        lambda: _filtered_row(logits, key, temperature, top_k, top_p),
        lambda: greedy(logits),
    )


def sample_per_row(
    logits: jax.Array,
    keys: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    kept: Optional[jax.Array] = None,
) -> jax.Array:
    """Batched :func:`sample_row`: logits (B, V), keys (B, 2), per-row
    (B,) config arrays → (B,) int32 tokens. The serving engine's shared
    decode step samples every slot with its own request's config here.
    Traced under the named scope ``sample`` (a trace reader's handle).

    It branches ONCE for the batch, before any work on the vocabulary: where
    no kept row samples, every token is its row's ``argmax`` and nothing
    else runs; one kept row that samples sends every row through the whole
    body. ``kept`` (B,) bool marks the rows whose token the caller keeps
    (all of them when left out): a row it discards (a slot that is done but
    still carries its last request's temperature) decides nothing, and in
    an otherwise greedy batch its discarded token is its ``argmax``. The
    conditional stands OUTSIDE the ``vmap``, on a scalar: on a per-row
    predicate it would lower to a select over both sides."""
    with jax.named_scope("sample"):
        samples = jnp.asarray(temperature, jnp.float32) != 0.0
        if kept is not None:
            samples &= kept
        return jax.lax.cond(
            jnp.any(samples),
            lambda: jax.vmap(_filtered_row)(
                logits, keys, temperature, top_k, top_p
            ),
            lambda: greedy(logits),
        )
