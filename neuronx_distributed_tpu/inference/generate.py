"""Autoregressive generation with a KV cache (reference: the
``examples/inference/runner.py`` generate loop + ``trace/spmd.py``
``StateInitializer:49`` KV-cache state).

Flow: one prefill call writes the prompt K/V into the flax "cache" collection
and yields the first sampled token; then a single jitted ``lax.scan`` runs all
decode steps on device — cache, sampling keys, and the EOS done-mask stay in
the carry, so there is no host round-trip per token (the reference's async
SPMDModel forward serves the same purpose).

The building blocks (mode clones, validation, the decode write mask, the
unwrap/sample plumbing, and the fused multi-token chunk builder
:func:`chunked_decode_step`) are shared with the request-level
continuous-batching engine in :mod:`neuronx_distributed_tpu.serving` —
`generate` is the one-shot batch view (its scan runs the whole generation),
the engine the slot-based streaming view (its scan runs one
``decode_chunk_size`` chunk between admission points), over the same prefill
and decode-step math.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.utils.sampling import sample


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None


def pack_padded_prompt(tokens, padded_len: int, pad_side: str = "left"):
    """Pack a token sequence into a ``(1, padded_len)`` ids/mask pair — the
    ONE place the serving stack builds padded prompt buffers.

    ``pad_side="left"`` is the generate()/engine prefill contract: content
    right-aligned (the last real token at index -1, where the next-token
    logits are read), padding in front. ``pad_side="right"`` is the
    suffix-prefill chunk layout: content at index 0 so the decode-path RoPE
    positions (``prefix_valid_count + arange``) line up with the real
    tokens, padding behind (its K/V writes are mask-invalidated).
    Returns host ``np`` arrays (ids int32, mask bool)."""
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    p = tokens.size
    if p > padded_len:
        raise ValueError(
            f"{p} tokens do not fit a padded length of {padded_len}"
        )
    if pad_side not in ("left", "right"):
        raise ValueError(f"unknown pad_side {pad_side!r}")
    ids = np.zeros((1, padded_len), np.int32)
    mask = np.zeros((1, padded_len), bool)
    sl = slice(padded_len - p, None) if pad_side == "left" else slice(0, p)
    ids[0, sl] = tokens
    mask[0, sl] = True
    return ids, mask


def sown_sums(stats, names):
    """An int32 vector: each of ``names`` summed over the layers that sowed it
    into the ``stats`` collection (a model's ``chunk_stats`` over a decode
    step, its ``prefill_stats`` over a prefill)."""
    sown = jax.tree_util.tree_flatten_with_path(stats)[0]
    return jnp.stack([
        sum(leaf for path, leaf in sown
            if any(getattr(k, "key", None) == name for k in path))
        for name in names]).astype(jnp.int32)


def serving_clones(model):
    """``(prefill, decode)`` mode clones sharing the caller's params — the
    pair every serving loop (batch `generate`, the continuous-batching
    engine, speculative verify) builds its steps from."""
    return model.clone(mode="prefill"), model.clone(mode="decode")


def decode_write_mask(done: jax.Array) -> jax.Array:
    """Validity (B, 1) of the INCOMING decode-step token: rows that already
    finished feed filler tokens whose K/V must not become attendable context
    for the rest of their generation (KVCache.decode_write persists this via
    ``kv_valid``; ADVICE round 5)."""
    return jnp.logical_not(done)[:, None]


def chunked_decode_step(decode_model, chunk_size: int, max_seq_len: int,
                        page_size: Optional[int] = None,
                        paged_attention: str = "gather"):
    """Build the fused multi-token decode step shared by the serving engine
    (and any other slot-based consumer): ``chunk_size`` decode steps run as
    ONE jitted ``lax.scan`` — the serving analogue of ``generate``'s
    ``_decode_all`` loop, with per-slot sampling sentinels instead of one
    python-constant config.

    Returned callable::

        fn(params, cache, state) -> (cache, state, toks, counts, used, keys)

    ``state`` is the engine's device-resident per-slot dict — ``tok`` (B,)
    int32 pending input tokens, ``keys`` (B, 2) uint32 sampling keys,
    ``active`` (B,) bool, ``remaining`` (B,) int32 tokens left to emit,
    ``temp``/``topk``/``topp`` per-slot sampling sentinels
    (:func:`~neuronx_distributed_tpu.utils.sampling.sample_row` contract)
    and ``eos`` (B,) int32 (-1 = no EOS). The output ``state`` has the same
    structure/shapes, so a caller can jit with ``donate_argnums`` on both
    ``cache`` and ``state`` and XLA updates every buffer in place.

    Semantics, step by step, exactly mirroring the single-step engine path:
    per-slot key split → decode apply with the write mask
    (:func:`decode_write_mask`) hiding finished/inactive rows' K/V → per-row
    sample → on-device EOS/budget freezing (a finished slot's ``tok``,
    ``keys`` and ``remaining`` stop advancing, so the values a later
    preemption/finish pulls are exactly the single-step ones). Steps whose
    cursor would run past ``max_seq_len``, or where every slot is already
    frozen, skip the model apply entirely (``lax.cond``) so the shared
    write cursor lands at exactly ``start + used`` — bit-identical cursor
    arithmetic to running ``used`` single steps.

    ``toks`` is the (chunk_size, B) token block, ``counts`` (B,) how many of
    each slot's tokens are real (a prefix — freezing is monotone), ``used``
    the scalar number of executed steps, and ``keys`` a COPY of the
    post-chunk per-slot key rows (so slots retiring this chunk hand their
    frozen key to the host for free). One ``device_get`` of these four is
    the only host synchronization a consumer needs per chunk — and it must
    read the ``keys`` COPY, never the state leaf itself: ``device_get`` on
    the leaf caches a host value on that array and silently turns the next
    chunk's donation into a full copy.

    ``page_size`` switches the cache argument to the serving engine's PAGED
    layout (``{"pages": block_table, "pool": pool_tree}``): the chunk
    gathers the logical view through the block table on entry, runs the
    EXACT row-per-slot math above on it, and scatters back only the pages
    its write window could have touched on exit — one program either way,
    token streams bit-identical across layouts. A QUANTIZED pool (int8
    pages + ``k_scale``/``v_scale`` siblings, ISSUE 13) is self-describing:
    the gather dequantizes the logical view and the scatter re-quantizes
    the window pages inside the same program — the row math in between is
    untouched, and the stream contract becomes the engine's pinned
    logit-divergence budget instead of bit-identity.

    ``paged_attention`` (ISSUE 14) picks the paged transport's ATTENTION
    read path: ``"gather"`` (default) attends the materialized logical
    view; ``"fused"`` routes every decode-attention call through
    ``kernels/flash_decode.paged_flash_decode_attention`` — the block
    table rides the kernel's scalar prefetch and K/V stream straight from
    the physical pool pages. Nothing attends a view there, so the chunk
    builds none: its cache's per-token leaves hold only the chunk's write
    WINDOW (``modules/attention.fused_chunk_window``: ``(chunk_size - 1)
    // page_size + 2`` pages a slot from the cursor's page), in which the
    model's decode write stages each new token; ``index``/``kv_valid`` stay
    logical and whole. The pool is loop-carried state of the scan beside
    it: each executed step scatters the window into the carried pool (in
    place — a pool the scan only closed over was copied whole before every
    such scatter) and attends it, and on the chunk's exit the carried
    pool, current through the last executed step, IS the output pool;
    nothing is scattered a second time.
    It is the compiled kernel or nothing: off the TPU it runs only where a
    test interprets it. Fused mode does not speak quantized pools (the
    in-kernel page stream is float).

    A model that names ``chunk_stats`` (int32 scalars its layers sow into the
    ``stats`` collection each step: ``models/glm_moe_dsa.py``'s rows its held
    experts computed; the distinct experts a step's rows hit in the models
    whose layers hold every expert, ``modules/moe.MOE_CHUNK_STATS``) gets a
    seventh output, an int32 vector of their sums
    over the chunk's executed steps and the layers, in that order; for every
    other model the program is what it was.

    A model whose stack mixes WINDOW and full attention layers
    (``modules/attention.JoinedKVCache``) is paged as ``{"pages",
    "window_pages", "pool"}``: every walker picks a leaf's block table by its
    layer's kind, and the fused frame carries both tables."""
    from neuronx_distributed_tpu.inference.utils import unwrap_logits
    from neuronx_distributed_tpu.modules.attention import (
        WINDOW_PAGES,
        adopt_kv_pool_pairs,
        cache_cursor,
        fused_chunk_window,
        fused_paged_attention_scope,
        gather_cache_pages,
        ordered_kv_pool_pairs,
        scatter_cache_window,
    )
    from neuronx_distributed_tpu.utils.sampling import sample_per_row

    stat_names = tuple(getattr(decode_model, "chunk_stats", ()))
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    if paged_attention not in ("gather", "fused"):
        raise ValueError(
            f"unknown paged_attention mode {paged_attention!r} "
            "(expected 'gather' or 'fused')"
        )

    def stage(paged, start):
        """``(staged, page0)``: the collection a paged chunk's scan carries
        and the model decodes on, and the first page of the fused frame's
        window (None elsewhere). The ``gather`` transport decodes on the
        whole logical view; the fused one on its write window alone."""
        if paged_attention != "fused":
            return gather_cache_pages(paged, page_size), None
        return fused_chunk_window(paged, page_size, start, chunk_size)

    def chunk_fn(params, cache, state):
        if page_size is None:
            return _row_chunk(params, cache, state)[0]
        paged = cache
        start = cache_cursor(paged)
        staged, page0 = stage(paged, start)
        if paged_attention != "fused":
            out, _ = _row_chunk(params, staged, state)
            return (
                scatter_cache_window(
                    paged, out[0], page_size, start, chunk_size
                ),
            ) + out[1:]
        out, pools = _row_chunk(
            params, staged, state,
            pools=ordered_kv_pool_pairs(paged["pool"]),
            # a model with window layers: that kind's block table beside it
            window=(paged["pages"], page_size, page0, paged.get(WINDOW_PAGES)),
        )
        # the carried pool is current through the last executed step
        return (adopt_kv_pool_pairs(paged, out[0], pools),) + out[1:]

    def _row_chunk(params, cache, state, pools=(), window=None):
        """The row-per-slot chunk on a logical cache: ``(outputs, pools)``.
        Fused mode hands in the page pool (``pools``, every layer's
        ``(k, v)`` leaves) and the frame of its write ``window``, which is
        all ``cache``'s per-token leaves then hold: the pool rides the
        scan's carry beside it, and each live step scatters the window
        into it in place and attends it. Elsewhere ``pools`` is empty and
        adds no leaf to the carry."""
        temp, topk, topp = state["temp"], state["topk"], state["topp"]
        eos = state["eos"]
        allowed = jnp.clip(max_seq_len - cache_cursor(cache), 0, chunk_size)

        def apply(cache, tok, done):
            return decode_model.apply(
                {**params, "cache": cache}, tok[:, None],
                padding_mask=decode_write_mask(done),
                mutable=["cache", "stats"] if stat_names else ["cache"],
            )

        def live(carry):
            if stat_names:
                *carry, stats = carry
            cache, tok, keys, remaining, done, pools = carry
            split = jax.vmap(jax.random.split)(keys)
            carry_keys, subs = split[:, 0], split[:, 1]
            if window is None:
                out, variables = apply(cache, tok, done)
            else:
                with fused_paged_attention_scope(pools, *window) as frame:
                    out, variables = apply(cache, tok, done)
                pools = frame["pools"]
            emit = jnp.logical_not(done)
            # a done slot's token is discarded below, and a freed slot keeps
            # its last request's temperature: only the emitting rows decide
            # whether the step samples
            nxt = sample_per_row(
                unwrap_logits(out)[:, -1], subs, temp, topk, topp, kept=emit
            )
            remaining = remaining - emit.astype(jnp.int32)
            finished = emit & (
                ((eos >= 0) & (nxt == eos)) | (remaining <= 0)
            )
            # freeze finished slots: their pending token / key / budget stay
            # at the values the single-step engine would have retired with
            tok = jnp.where(emit, nxt, tok)
            keys = jnp.where(emit[:, None], carry_keys, keys)
            carry = (variables["cache"], tok, keys, remaining,
                     done | finished, pools)
            if stat_names:
                carry += (stats + sown_sums(variables["stats"], stat_names),)
            return carry, (nxt, emit)

        def frozen(carry):
            tok, done = carry[1], carry[4]
            return carry, (tok, jnp.zeros_like(done))

        def step(carry, i):
            done = carry[4]
            run = (i < allowed) & jnp.logical_not(jnp.all(done))
            return jax.lax.cond(run, live, frozen, carry)

        done0 = jnp.logical_not(state["active"])
        carry0 = (
            cache, state["tok"], state["keys"], state["remaining"], done0,
            pools,
        )
        if stat_names:
            carry0 += (jnp.zeros((len(stat_names),), jnp.int32),)
        (cache, tok, keys, remaining, done, pools, *stats), (toks, emits) = (
            jax.lax.scan(
                step, carry0, jnp.arange(chunk_size, dtype=jnp.int32)
            )
        )
        counts = emits.astype(jnp.int32).sum(0)
        new_state = dict(
            state, tok=tok, keys=keys, remaining=remaining,
            active=jnp.logical_not(done),
        )
        out = (cache, new_state, toks, counts, jnp.max(counts), keys.copy(),
               *stats)
        return out, pools

    return chunk_fn


def suffix_prefill_step(decode_model):
    """Build the SUFFIX-prefill program for the serving engine's prefix
    cache: given a batch-1 cache row already seeded with a reused prefix
    (``modules/attention.seed_cache_prefix`` — prefix K/V in place, write
    cursor at the prefix end), run ONLY the uncached tail through the
    decode-mode model in one multi-token step and hand back the row ready
    for slot admission.

    This IS the cache-write path with an explicit start cursor: the decode
    mode's ``KVCache.decode_write`` appends the chunk's K/V at the row's
    cursor, ``decode_positions`` continues RoPE at the prefix's valid count,
    and ``decode_attention`` lets each suffix token attend the prefix plus
    the suffix up to itself (causal by column position) — so a hit computes
    QKV/MLP for ``s`` suffix tokens instead of the whole prompt.

    Returned callable::

        fn(params, row_cache, ids, valid_len) -> (last_logits, row_cache)

    ``ids`` is a ``(1, chunk)`` RIGHT-padded suffix
    (:func:`pack_padded_prompt` ``pad_side="right"``: real tokens first so
    their RoPE positions are exact; the pad tail's K/V is written
    mask-invalid and overwritten by later decode steps). ``valid_len`` is
    the traced real-suffix length — ``last_logits`` reads index
    ``valid_len - 1``, the same next-token logits a full prefill reads at
    index -1. One jitted program per chunk bucket (``ids.shape[1]``);
    nothing is donated — the seeded row is consumed forward, the stored
    prefix entry the row was built from is never aliased."""
    from neuronx_distributed_tpu.inference.utils import unwrap_logits

    def fn(params, row_cache, ids, valid_len):
        chunk = ids.shape[1]
        mask = jnp.arange(chunk, dtype=jnp.int32)[None] < valid_len
        out, variables = decode_model.apply(
            {**params, "cache": row_cache}, ids,
            padding_mask=mask, mutable=["cache"],
        )
        logits = unwrap_logits(out)[0]  # (chunk, vocab)
        last = jax.lax.dynamic_index_in_dim(
            logits, valid_len - 1, axis=0, keepdims=False
        )
        return last, variables["cache"]

    return fn


def validate_generate_args(model, prompt_ids, max_new_tokens, attention_mask):
    """Host-side checks shared by `generate` and the serving engine's
    admission path: capacity (prompt + new tokens within the cache) and the
    LEFT-padding contract of ``attention_mask``. Tracer masks skip the
    padding check — it needs host values, and forcing a device sync (or a
    TracerError under jit/vmap wrapping) for validation is worse than
    trusting a caller that is already inside a traced context."""
    model_cfg = getattr(model, "config", None)
    max_len = getattr(model_cfg, "max_seq_len", None)
    if max_len is not None and prompt_ids.shape[1] + max_new_tokens > max_len:
        # past max_seq_len the cache write index and RoPE positions would
        # clamp and silently corrupt generation
        raise ValueError(
            f"prompt ({prompt_ids.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) exceeds the model's max_seq_len ({max_len})"
        )
    if attention_mask is not None:
        if attention_mask.shape != prompt_ids.shape:
            raise ValueError(
                f"attention_mask shape {attention_mask.shape} != prompt_ids "
                f"shape {prompt_ids.shape}"
            )
        if isinstance(attention_mask, jax.core.Tracer):
            return
        if not bool(np.asarray(attention_mask)[:, -1].all()):
            # right padding would make _logits[:, -1] a pad-slot query and
            # silently corrupt the whole continuation
            raise ValueError(
                "attention_mask has invalid tokens in the LAST column — "
                "generate() requires LEFT padding (every row's final prompt "
                "token at index -1)"
            )


def generate(
    model,
    params,
    prompt_ids: jax.Array,
    key: jax.Array,
    config: GenerationConfig = GenerationConfig(),
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Generate ``(B, max_new_tokens)`` token ids continuing ``prompt_ids``
    (B, S). ``model`` is a mode-capable module (e.g. ``LlamaForCausalLM``);
    clones with ``mode="prefill"`` / ``mode="decode"`` share its params.

    ``attention_mask`` (B, S), True at valid tokens, serves variable-length
    batches with LEFT padding (the continuous-batching layout: every row's
    last prompt token sits at index -1, so the first sampled token reads the
    right logits). The mask persists in the KV cache (``kv_valid``) and RoPE
    positions restart at each row's first valid token — no per-row offset
    bookkeeping in this loop."""
    cfg = config
    validate_generate_args(model, prompt_ids, cfg.max_new_tokens, attention_mask)
    prefill, decode = serving_clones(model)
    b = prompt_ids.shape[0]

    def _sample(logits, k):
        return sample(
            logits,
            k,
            temperature=cfg.temperature,
            top_k=cfg.top_k,
            top_p=cfg.top_p,
        )

    from neuronx_distributed_tpu.inference.utils import unwrap_logits as _logits

    @jax.jit
    def _prefill(params, ids, key):
        if attention_mask is not None:
            out, variables = prefill.apply(
                params, ids, padding_mask=attention_mask, mutable=["cache"]
            )
        else:
            out, variables = prefill.apply(params, ids, mutable=["cache"])
        tok = _sample(_logits(out)[:, -1], key)
        return tok, variables["cache"]

    @jax.jit
    def _decode_all(params, cache, first_tok, key):
        def step(carry, _):
            cache, tok, key, done = carry
            key, sub = jax.random.split(key)
            # post-EOS filler tokens write masked-invalid K/V: they must not
            # extend still-running rows' bookkeeping (valid_count_below) nor
            # this row's attendable context (ADVICE round 5)
            out, variables = decode.apply(
                {**params, "cache": cache}, tok[:, None],
                padding_mask=decode_write_mask(done), mutable=["cache"]
            )
            nxt = _sample(_logits(out)[:, -1], sub)
            if cfg.eos_token_id is not None:
                nxt = jnp.where(done, cfg.eos_token_id, nxt)
                done = done | (nxt == cfg.eos_token_id)
            return (variables["cache"], nxt, key, done), nxt

        done0 = (
            first_tok == cfg.eos_token_id
            if cfg.eos_token_id is not None
            else jnp.zeros((b,), bool)
        )
        (_, _, _, _), toks = jax.lax.scan(
            step,
            (cache, first_tok, key, done0),
            None,
            length=cfg.max_new_tokens - 1,
        )
        return toks  # (steps, B)

    key, k0 = jax.random.split(key)
    first_tok, cache = _prefill(params, prompt_ids, k0)
    if cfg.max_new_tokens == 1:
        return first_tok[:, None]
    toks = _decode_all(dict(params), cache, first_tok, key)
    return jnp.concatenate([first_tok[:, None], toks.T], axis=1)
