"""Fused speculative decode chunk for the serving engine — the multi-token
sibling of :func:`~neuronx_distributed_tpu.inference.generate.
chunked_decode_step` (reference: NxD's draft process groups,
``parallel_state.py:1428``; the solo round structure lives in
:mod:`~neuronx_distributed_tpu.inference.speculative`).

Each scan iteration is one speculative ROUND over all slots: the draft
model proposes ``gamma`` tokens autoregressively through its own donated KV
cache, the target model scores the whole window in ONE decode forward (the
s>1 verify path of the cache), and each slot accepts its own longest
matching prefix plus a corrected token — emitting ``1..gamma`` tokens per
slot per round. ``chunk_rounds`` rounds fuse into one jitted ``lax.scan``
with on-device EOS/budget freezing, so a consumer still pays exactly ONE
host synchronization per chunk whatever the per-slot acceptance pattern.

Per-slot VARIABLE advance on a shared physical cursor — the layout trick
that makes the fusion possible without per-slot cache reshaping:

* Both caches write every round's ``gamma``-column window at their shared
  write cursor, optimistically valid for live rows. After acceptance,
  :func:`~neuronx_distributed_tpu.modules.attention.invalidate_cache_window`
  clears each row's REJECTED suffix of the window, so rejected draft
  columns become permanent invalid gap columns. Attention masking and RoPE
  positions already run off per-row validity counts (``valid_count_below``
  — the same machinery that serves left-padded prompts), so a slot's
  LOGICAL cursor advances by its own accepted length while every slot
  shares one program and one physical cursor. The physical cost is
  ``gamma`` columns per executed round; the engine's preempt-and-rewind
  wall handles the (acceptance-dependent) early cursor exhaustion.
* The solo path's batch-min "pad-to-shortest" advance is gone: no slot
  ever re-drafts tokens another slot rejected.

Acceptance semantics match the solo greedy rule exactly (emission is the
target model's own greedy stream, independent of draft quality): a slot
accepts drafts while they equal the target's windowed argmax, then emits
the target's correction at the first mismatch — ``min(n_acc + 1, gamma)``
tokens per round. SAMPLED slots (``temperature > 0``) accept nothing and
emit exactly one token per round, sampled from the window's position-0
logits with the same per-slot key split the non-speculative chunk would
perform (one split per EMITTED token for every slot), so key evolution —
and therefore preemption/recovery resume — is bit-compatible with the
non-speculative engine path.

Returned callable::

    fn(params, draft_params, cache, draft_cache, state) ->
        (cache, draft_cache, state, toks, counts, accepts, used, keys)

``state`` is the engine's device-resident slot dict (the
``chunked_decode_step`` contract, unchanged). ``toks`` is the
``(chunk_rounds, B, gamma)`` ragged token block — slot ``b`` emitted the
first ``counts[r, b]`` tokens of round ``r`` — ``accepts`` the per-round
per-slot accepted draft lengths (the acceptance-stats readback), ``used``
the number of executed rounds (each consumes ``gamma`` physical columns in
BOTH caches), and ``keys`` a COPY of the post-chunk key rows. One
``device_get`` of the five outputs is the only host sync a consumer needs
per chunk. A caller jits with ``donate_argnums`` on both caches and the
state; nothing here reads the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def speculative_decode_chunk(
    target_decode_model,
    draft_decode_model,
    chunk_rounds: int,
    gamma: int,
    max_seq_len: int,
    page_size=None,
):
    """Build the fused speculative chunk (see module docstring).

    ``page_size`` switches BOTH cache arguments to the paged layout
    (``{"pages": block_table, "pool": tree}``, the ``chunked_decode_step``
    contract): logical views are gathered through each cache's block table
    on entry, the exact row-per-slot round math runs on them, and each
    cache's write window (``chunk_rounds * gamma`` columns from its entry
    cursor) is scattered back on exit — shared copy-on-write prefix pages
    outside the window are never rewritten. A QUANTIZED target pool (int8
    pages + scale siblings, ISSUE 13) de/re-quantizes inside the same
    transports; the draft cache stays float (the engine never quantizes
    it — drafts only steer acceptance)."""
    from neuronx_distributed_tpu.inference.generate import decode_write_mask
    from neuronx_distributed_tpu.inference.utils import unwrap_logits
    from neuronx_distributed_tpu.modules.attention import (
        cache_cursor,
        gather_cache_pages,
        invalidate_cache_window,
        scatter_cache_window,
    )
    from neuronx_distributed_tpu.utils.sampling import sample_per_row

    if chunk_rounds < 1:
        raise ValueError(f"chunk_rounds must be >= 1, got {chunk_rounds}")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")

    def chunk_fn(params, draft_params, cache, draft_cache, state):
        if page_size is not None:
            paged, draft_paged = cache, draft_cache
            width = chunk_rounds * gamma
            c0 = cache_cursor(paged)
            d0 = cache_cursor(draft_paged)
            out = _row_chunk(
                params, draft_params,
                gather_cache_pages(paged, page_size),
                gather_cache_pages(draft_paged, page_size),
                state,
            )
            return (
                scatter_cache_window(paged, out[0], page_size, c0, width),
                scatter_cache_window(
                    draft_paged, out[1], page_size, d0, width
                ),
            ) + out[2:]
        return _row_chunk(params, draft_params, cache, draft_cache, state)

    def _row_chunk(params, draft_params, cache, draft_cache, state):
        temp, topk, topp = state["temp"], state["topk"], state["topp"]
        eos = state["eos"]
        b = state["tok"].shape[0]
        greedy_m = temp == 0.0  # speculation-eligible rows
        idx = jnp.arange(gamma, dtype=jnp.int32)
        # every executed round consumes gamma write columns in BOTH caches;
        # clamp the round count so neither cursor can run past the row end
        room = jnp.minimum(
            max_seq_len - cache_cursor(cache),
            max_seq_len - cache_cursor(draft_cache),
        )
        allowed = jnp.clip(room // gamma, 0, chunk_rounds)

        def live(carry):
            cache, dcache, tok, keys, remaining, done = carry
            live_m = jnp.logical_not(done)
            wmask = decode_write_mask(done)
            c0 = cache_cursor(cache)
            d0 = cache_cursor(dcache)

            # draft proposes gamma greedy tokens through its own cache
            drafts = []
            dt = tok
            for _ in range(gamma):
                dout, dvars = draft_decode_model.apply(
                    {**draft_params, "cache": dcache}, dt[:, None],
                    padding_mask=wmask, mutable=["cache"],
                )
                dcache = dvars["cache"]
                dt = jnp.argmax(
                    unwrap_logits(dout)[:, -1], -1
                ).astype(jnp.int32)
                drafts.append(dt)
            draft = jnp.stack(drafts, 1)  # (B, gamma)

            # target scores [tok, d_0..d_{g-2}] in ONE s=gamma forward;
            # window row j predicts the token after its input, so matching
            # it against d_j is the greedy acceptance rule
            window = jnp.concatenate([tok[:, None], draft[:, :-1]], 1)
            tout, tvars = target_decode_model.apply(
                {**params, "cache": cache},
                window,
                padding_mask=jnp.broadcast_to(live_m[:, None], window.shape),
                mutable=["cache"],
            )
            cache = tvars["cache"]
            t_logits = unwrap_logits(tout)  # (B, gamma, V)
            target_pred = jnp.argmax(t_logits, -1).astype(jnp.int32)

            matches = (draft == target_pred) & greedy_m[:, None]
            n_acc = jnp.argmin(
                jnp.concatenate([matches, jnp.zeros((b, 1), bool)], 1), 1
            ).astype(jnp.int32)  # first mismatch == accepted length

            # ONE key split per emitted token (the non-speculative chunk's
            # exact evolution); the first split's sub-key samples the
            # round's position-0 token for sampled rows — at temp==0
            # sample_row IS argmax, so the same expression is the greedy
            # zero-acceptance correction
            split0 = jax.vmap(jax.random.split)(keys)
            k1, subs = split0[:, 0], split0[:, 1]
            # (a dead row emits nothing, so it does not decide whether the
            # round samples)
            tok0 = sample_per_row(
                t_logits[:, 0], subs, temp, topk, topp, kept=live_m
            )

            fix_pos = jnp.minimum(n_acc, gamma - 1)
            fix_val = jnp.where(
                n_acc < gamma,
                jnp.take_along_axis(target_pred, fix_pos[:, None], 1)[:, 0],
                draft[:, gamma - 1],
            )
            out = jnp.where(idx[None] < n_acc[:, None], draft, 0)
            out = jnp.where(idx[None] == fix_pos[:, None], fix_val[:, None], out)
            out = out.at[:, 0].set(jnp.where(n_acc == 0, tok0, out[:, 0]))

            # per-row emission: candidates up to the correction, cut at the
            # first EOS, clamped by the remaining budget
            cand_len = jnp.minimum(n_acc + 1, gamma)
            cand_mask = idx[None] < cand_len[:, None]
            is_eos = (
                (eos[:, None] >= 0) & (out == eos[:, None]) & cand_mask
            )
            has_eos = is_eos.any(1)
            eos_cut = jnp.where(
                has_eos, jnp.argmax(is_eos, 1).astype(jnp.int32) + 1, cand_len
            )
            emit_e = jnp.minimum(
                jnp.minimum(cand_len, eos_cut), jnp.maximum(remaining, 0)
            )
            emits = jnp.where(live_m, emit_e, 0)
            new_remaining = remaining - emits
            finished = live_m & (
                (has_eos & (eos_cut <= emits)) | (new_remaining <= 0)
            )

            # freeze: pending token / key / budget stop at the values the
            # non-speculative path would retire with
            last = jnp.take_along_axis(
                out, jnp.clip(emits - 1, 0, gamma - 1)[:, None], 1
            )[:, 0]
            tok = jnp.where(emits > 0, last, tok)
            keys = jnp.where((emits > 0)[:, None], k1, keys)
            for i in range(1, gamma):
                s = jax.vmap(jax.random.split)(keys)
                keys = jnp.where((i < emits)[:, None], s[:, 0], keys)

            # per-slot variable advance: keep each live row's accepted
            # prefix of the window (its fed tokens that survive into the
            # stream), reject the rest into invalid gap columns — in BOTH
            # caches (they fed the identical window)
            keep = jnp.where(live_m, cand_len, 0)
            cache = invalidate_cache_window(cache, c0, keep)
            dcache = invalidate_cache_window(dcache, d0, keep)

            accepts = jnp.where(live_m, n_acc, 0)
            return (
                (cache, dcache, tok, keys, new_remaining, done | finished),
                (out, emits, accepts),
            )

        def frozen(carry):
            z = jnp.zeros((b,), jnp.int32)
            return carry, (jnp.zeros((b, gamma), jnp.int32), z, z)

        def step(carry, i):
            done = carry[5]
            run = (i < allowed) & jnp.logical_not(jnp.all(done))
            return jax.lax.cond(run, live, frozen, carry)

        done0 = jnp.logical_not(state["active"])
        carry0 = (
            cache, draft_cache, state["tok"], state["keys"],
            state["remaining"], done0,
        )
        (cache, draft_cache, tok, keys, remaining, done), (
            toks, counts, accepts
        ) = jax.lax.scan(
            step, carry0, jnp.arange(chunk_rounds, dtype=jnp.int32)
        )
        used = jnp.sum((counts.sum(1) > 0).astype(jnp.int32))
        new_state = dict(
            state, tok=tok, keys=keys, remaining=remaining,
            active=jnp.logical_not(done),
        )
        return (
            cache, draft_cache, new_state, toks, counts, accepts, used,
            keys.copy(),
        )

    return chunk_fn
