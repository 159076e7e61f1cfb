"""The three kernels of decode under a learned sparse-attention indexer
against plain ``jnp`` on a scattered page pool (interpret mode on CPU): the
index scores over the 64-wide (here 8-wide) paged leaf, walking only the
blocks a slot maps; the sparse GQA kernel that fetches the selected tokens'
joined K/V rows and nothing else (one copy a token, one wait a chunk); and
the byte-masked flash forward of the prefill."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.flash_attention import masked_flash_attention
from neuronx_distributed_tpu.kernels.flash_decode import (
    LATENT_BLOCK_TOKENS,
    _TOKENS_A_TRIP,
    SPARSE_CHUNK_TOKENS,
    paged_gather_leaf,
    paged_index_scores,
    paged_sparse_decode_attention,
)
from neuronx_distributed_tpu.modules.attention import (
    _masked_gqa_attention,
    index_scores,
    indexed_decode_attention,
    split_kv,
    topk_mask,
)
from tests.kernels import page_runs

PS, D, HKV, H, H_I, D_I = 16, 16, 2, 8, 4, 8
T = LATENT_BLOCK_TOKENS


def _pool(rng, b, n_log, lens):
    """A pool whose pages are dealt out of order; slot ``i`` maps the pages
    covering ``lens[i]`` (a number: columns from 0; a list of ``(first,
    end)`` ranges: those); the rest stay on the null page 0. ``(kv, k_idx,
    table)``: the joined leaf (a token's K heads, then its V heads) and the
    index keys."""
    pages = 1 + b * n_log
    leaf = lambda h, d: jnp.asarray(rng.standard_normal((pages, PS, h, d)), jnp.float32)  # noqa: E731
    ids = rng.permutation(np.arange(1, pages))
    table = np.zeros((b, n_log), np.int32)
    for i, spans in enumerate(lens):
        for first, end in [(0, spans)] if isinstance(spans, int) else spans:
            lo, hi = first // PS, -(-end // PS)
            table[i, lo:hi] = ids[i * n_log + lo:i * n_log + hi]
    return leaf(2 * HKV, D), leaf(1, D_I), jnp.asarray(table)


def _valid(b, n_log, lens):
    valid = np.zeros((b, n_log * PS), bool)
    for i, spans in enumerate(lens):
        for first, end in [(0, spans)] if isinstance(spans, int) else spans:
            valid[i, first:end] = True
    return valid


def _rows(kv_pool, table):
    """The logical K and V rows of the joined pool leaf."""
    return split_kv(paged_gather_leaf(kv_pool, table, PS))


def _queries(rng, b):
    return (jnp.asarray(rng.standard_normal((b, 1, H, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, 1, H_I, D_I)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, 1, H_I)), jnp.float32))


@pytest.mark.parametrize("n_log,lens", [
    (8, (100, 37)),                                    # ragged contexts in one block
    (160, (2500, 16, [(1040, 2560)])),                 # three blocks; a one-page slot; an unmapped first block
    (70, (1100, [(0, 40), (1030, 1100)])),             # a row whose last block is partial
])
def test_index_scores_match_jnp_over_the_blocks_a_slot_maps(n_log, lens):
    rng = np.random.default_rng(n_log)
    b = len(lens)
    _, idx_pool, table = _pool(rng, b, n_log, lens)
    _, q_idx, w_idx = _queries(rng, b)
    valid = _valid(b, n_log, lens)
    valid[0, 3:9] = False                              # an invalid stretch inside a mapped page
    cur = int(valid.any(0).nonzero()[0].max())         # the shared cursor: the last valid column
    got = paged_index_scores(q_idx, w_idx, idx_pool, table, jnp.asarray([cur], jnp.int32),
                             jnp.asarray(valid), page_size=PS)
    keys = paged_gather_leaf(idx_pool, table, PS)[:, :, 0]
    want = index_scores(q_idx, w_idx, keys)[:, 0]
    ok = valid & (np.arange(n_log * PS)[None] <= cur)
    assert got.shape == (b, n_log * PS) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.isneginf(np.asarray(got)), ~ok)
    np.testing.assert_allclose(np.asarray(got)[ok], np.asarray(want)[ok], atol=2e-5)


@pytest.mark.parametrize("case", page_runs.CASES)
def test_index_scores_with_runs_fetched_whole_are_those_of_a_copy_a_page(monkeypatch, case):
    """The index-key leaf over every shape of block table, blocks of eight
    pages: the scores with runs fetched whole are, bit for bit, those of a
    copy a page."""
    from neuronx_distributed_tpu.kernels import flash_decode

    b, group = 3, 8
    monkeypatch.setattr(flash_decode, "LATENT_BLOCK_TOKENS", group * PS)
    n_log = 2 * group + 6 if case == "short_last_block" else 3 * group     # the third block holds 6 pages
    cur = n_log * PS - 5
    table = page_runs.table(case, b, n_log, [(3, n_log), (group + 1, n_log), None])
    assert (page_runs.runs(table) > 0) == (case not in ("no_runs", "adjacent_off_the_grid"))
    valid = jnp.asarray(np.repeat(table != 0, PS, axis=1))
    rng = np.random.default_rng(6)
    idx_pool = jnp.asarray(rng.standard_normal((page_runs.pool_pages(b, n_log), PS, 1, D_I)), jnp.float32)
    _, q_idx, w_idx = _queries(rng, b)
    score = lambda: np.asarray(paged_index_scores(   # noqa: E731
        q_idx, w_idx, idx_pool, jnp.asarray(table), jnp.asarray([cur], jnp.int32), valid, page_size=PS))
    got = score()
    with page_runs.single_copies():
        want = score()
    np.testing.assert_array_equal(got, want)
    assert np.isfinite(got[:2]).any() and np.isneginf(got[2]).all()


def test_index_scores_stop_at_the_row_position():
    rng = np.random.default_rng(5)
    _, idx_pool, table = _pool(rng, 2, 8, (100, 90))
    _, q_idx, w_idx = _queries(rng, 2)
    valid = jnp.asarray(_valid(2, 8, (100, 90)))
    got = np.asarray(paged_index_scores(q_idx, w_idx, idx_pool, table, jnp.asarray(60), valid, page_size=PS))
    assert np.isfinite(got[:, :61]).all() and np.isneginf(got[:, 61:]).all()


def test_the_index_kernel_reads_only_mapped_blocks():
    """Columns of an unmapped block read -inf whatever ``kv_valid`` says of
    them: the block was never fetched."""
    rng = np.random.default_rng(6)
    _, idx_pool, table = _pool(rng, 1, 3 * T // PS, ([(T, 2 * T)],))
    _, q_idx, w_idx = _queries(rng, 1)
    valid = jnp.ones((1, 3 * T), bool)
    got = np.asarray(paged_index_scores(q_idx, w_idx, idx_pool, table, jnp.asarray(3 * T - 1), valid, page_size=PS))
    assert np.isneginf(got[:, :T]).all() and np.isneginf(got[:, 2 * T:]).all() and np.isfinite(got[:, T:2 * T]).all()


@pytest.mark.parametrize("k_sel,lens", [
    (16, (100, 37)),                   # fewer selected than a chunk
    (21, (100, 37)),                   # a count that is no multiple of a trip's copies: the chunk is padded
    (SPARSE_CHUNK_TOKENS + 40, (700, 300, 16)),   # two chunks, the second partial; a slot that keeps all it has
])
def test_sparse_attention_reads_the_selected_columns_only(k_sel, lens):
    rng = np.random.default_rng(k_sel)
    b, n_log = len(lens), 48
    kv_pool, _, table = _pool(rng, b, n_log, lens)
    q, _, _ = _queries(rng, b)
    cols = np.zeros((b, k_sel), np.int32)
    n_sel = np.zeros((b,), np.int32)
    keep = np.zeros((b, 1, n_log * PS), bool)
    for i, n in enumerate(lens):
        n_sel[i] = min(n, k_sel)
        picked = rng.permutation(n)[:n_sel[i]]
        cols[i, :n_sel[i]] = picked
        cols[i, n_sel[i]:] = rng.integers(0, n_log * PS, k_sel - n_sel[i])   # garbage past the count
        keep[i, 0, picked] = True
    got = paged_sparse_decode_attention(q, kv_pool, table, jnp.asarray(cols), jnp.asarray(n_sel), page_size=PS)
    want = _masked_gqa_attention(q, *_rows(kv_pool, table), jnp.asarray(keep))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # poison every unselected token of the pool: the result does not move
    flat = np.asarray(table)[np.arange(b)[:, None], cols // PS] * PS + cols % PS
    mask = np.ones((kv_pool.shape[0] * PS,), bool)
    mask[:PS] = False      # the null page: a chunk's padding reads its token 0 (finite garbage, weight 0)
    for i in range(b):
        mask[flat[i, :n_sel[i]]] = False
    poisoned = jnp.where(jnp.asarray(mask).reshape(-1, PS)[..., None, None], jnp.nan, kv_pool)
    again = paged_sparse_decode_attention(q, poisoned, table, jnp.asarray(cols), jnp.asarray(n_sel), page_size=PS)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_a_slot_that_selects_nothing_returns_zeros():
    rng = np.random.default_rng(8)
    kv_pool, _, table = _pool(rng, 2, 8, (50, 0))
    q, _, _ = _queries(rng, 2)
    cols = jnp.asarray(rng.integers(0, 50, (2, 16)), jnp.int32)
    out = paged_sparse_decode_attention(q, kv_pool, table, cols, jnp.asarray([16, 0]), page_size=PS)
    assert np.isfinite(np.asarray(out)).all() and not np.asarray(out[1]).any() and np.asarray(out[0]).any()


def test_selected_tokens_on_a_shared_page_are_each_slots_own_copy():
    """Two slots map the SAME physical pages (a shared prefix) and select
    tokens on them, in different orders and counts: each reads what a slot
    alone would."""
    rng = np.random.default_rng(10)
    kv_pool, _, table = _pool(rng, 2, 8, (96, 96))
    table = table.at[1, :3].set(table[0, :3])          # columns 0-47 of both slots: one set of pages
    q, _, _ = _queries(rng, 2)
    cols = np.stack([rng.permutation(96)[:40], rng.permutation(48)[:40]]).astype(np.int32)
    n_sel = np.asarray([40, 25], np.int32)
    keep = np.zeros((2, 1, 8 * PS), bool)
    for i in range(2):
        keep[i, 0, cols[i, :n_sel[i]]] = True
    got = paged_sparse_decode_attention(q, kv_pool, table, jnp.asarray(cols), jnp.asarray(n_sel), page_size=PS)
    want = _masked_gqa_attention(q, *_rows(kv_pool, table), jnp.asarray(keep))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    alone = paged_sparse_decode_attention(q[1:], kv_pool, table[1:], jnp.asarray(cols[1:]), jnp.asarray(n_sel[1:]),
                                          page_size=PS)
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(got[1]))


def _eqns(jaxpr, name, loops=()):
    """For each equation of primitive ``name`` at any depth of ``jaxpr``, the
    chain of loops around it: ``(primitive, length)`` of each enclosing
    ``scan`` / ``while``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield loops
        inner = loops
        if eqn.primitive.name in ("scan", "while"):
            inner += ((eqn.primitive.name, eqn.params.get("length")),)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, name, inner)


def test_a_trip_starts_one_copy_a_token_and_a_chunk_waits_once():
    """The kernel is bound by the copies it names, so its jaxpr is held to
    their number: a trip of the issuing loop starts ``_TOKENS_A_TRIP``
    copies (K and V are one leaf: not twice that), from a loop of ``chunk /
    _TOKENS_A_TRIP`` trips, written twice (the first chunk's fetch, and the
    prefetch inside the walk over chunks); and the ONE wait of the program
    stands in the walk over chunks, outside any issuing loop: once a chunk,
    over the whole buffer (Mosaic on the v5e takes the byte count of a wait
    from its descriptor: PERF.md section 6, PR 31)."""
    s = jax.ShapeDtypeStruct
    k_sel = 2 * SPARSE_CHUNK_TOKENS + 40
    jaxpr = jax.make_jaxpr(
        lambda q, kv, bt, cols, n: paged_sparse_decode_attention(q, kv, bt, cols, n, page_size=PS))(
        s((2, 1, H, D), jnp.float32), s((17, PS, 2 * HKV, D), jnp.float32), s((2, 8), jnp.int32),
        s((2, k_sel), jnp.int32), s((2,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    kernel = call.params["jaxpr"]
    trips = ("scan", SPARSE_CHUNK_TOKENS // _TOKENS_A_TRIP)
    starts = list(_eqns(kernel, "dma_start"))
    assert len(starts) == 2 * _TOKENS_A_TRIP
    assert sorted(starts) == [(trips,)] * _TOKENS_A_TRIP + [(("while", None), trips)] * _TOKENS_A_TRIP
    assert list(_eqns(kernel, "dma_wait")) == [(("while", None),)]
    # the scratch: ONE two-chunk buffer of the joined leaf
    bufs = [v.aval.shape for v in kernel.invars if len(v.aval.shape) == 4 and v.aval.shape[0] == 2]
    assert bufs == [(2, SPARSE_CHUNK_TOKENS, 2 * HKV, D)]


def test_the_fused_decode_is_the_einsum_decode(monkeypatch):
    """Score, select and attend off the pool inside a fused frame against
    ``indexed_decode_attention``'s einsum on the gathered rows: one rule, two
    implementations, the same sets (ties and all) and the same output."""
    from neuronx_distributed_tpu.modules import attention as att

    rng = np.random.default_rng(9)
    lens, n_log, topk = (100, 37, 70), 8, 24
    b = len(lens)
    kv_pool, idx_pool, table = _pool(rng, b, n_log, lens)
    q, q_idx, w_idx = _queries(rng, b)
    valid = jnp.asarray(_valid(b, n_log, lens))
    pos = jnp.asarray([99], jnp.int32)
    rows = [paged_gather_leaf(p, table, PS) for p in (kv_pool, idx_pool)]
    want = indexed_decode_attention(q, q_idx, w_idx, *rows, pos, topk, kv_valid=valid)
    scores = paged_index_scores(q_idx, w_idx, idx_pool, table, pos, valid, page_size=PS)
    vals, cols = jax.lax.top_k(scores, topk)
    got = paged_sparse_decode_attention(q, kv_pool, table, cols, (vals > -jnp.inf).sum(1), page_size=PS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert att.DSA_SCORE_SCOPE == "dsa.score" and att.DSA_ATTEND_SCOPE == "dsa.attend"


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_topk_mask_is_top_k_with_ties_to_the_lower_position(k):
    rng = np.random.default_rng(k)
    x = rng.standard_normal((3, 7, 33)).astype(np.float32)
    x[0, :, ::3] = 0.0                 # exact ties, and -0.0 among them
    x[0, :, 3::6] = -0.0
    x[1, 2] = 0.0                      # a row of nothing but ties
    ok = rng.random((3, 7, 33)) < 0.8
    ok[2, 3] = False                   # a row with nothing to keep
    got = np.asarray(topk_mask(jnp.asarray(x), jnp.asarray(ok), k))
    vals, idx = jax.lax.top_k(jnp.where(ok, jnp.where(x == 0, 0.0, x), -jnp.inf), min(k, 33))
    want = np.zeros_like(ok)
    for i in np.ndindex(3, 7):
        want[i][np.asarray(idx[i])[np.asarray(vals[i]) > -np.inf]] = True
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(ok.sum(-1), k)).all()
    # all-zero scores: the first k valid positions
    first = np.asarray(topk_mask(jnp.zeros((1, 20)), jnp.ones((1, 20), bool), 6))
    np.testing.assert_array_equal(first[0], np.arange(20) < 6)


@pytest.mark.parametrize("s,bq", [(64, 16), (96, 32)])
def test_masked_flash_forward_matches_the_einsum(s, bq):
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.standard_normal((2, s, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, HKV, D)), jnp.float32)
    keep = np.tril(rng.random((2, s, s)) < 0.3)
    keep[1, 5] = False                 # a row that keeps nothing: zeros
    got = masked_flash_attention(q, k, v, jnp.asarray(keep, jnp.int8), block_q=bq, block_k=bq)
    want = _masked_gqa_attention(q, k, v, jnp.asarray(keep))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got[1, 5]).any()


@pytest.mark.parametrize("s,topk,pad", [(512, 40, 0), (1024, 100, 0), (2560, 300, 200)])
def test_the_keep_mask_kernel_is_the_einsum_and_topk_mask(s, topk, pad):
    """Scores, thresholds, ties and the byte mask in one kernel against the
    float32 einsum and ``topk_mask``: the same sets, exact ties (zeroed index
    keys) and a left-padded prompt included."""
    from neuronx_distributed_tpu.kernels.flash_attention import sparse_keep_mask_kernel
    from neuronx_distributed_tpu.modules.attention import sparse_keep_mask

    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.standard_normal((1, s, H_I, D_I)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1, s, H_I)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, D_I)), jnp.float32).at[:, 5:400:7].set(0.0)
    valid = jnp.arange(s)[None] >= pad
    pos = jnp.broadcast_to(jnp.arange(s)[None], (1, s))
    want = np.asarray(sparse_keep_mask(q, w, k, pos, valid, topk))
    got = np.asarray(sparse_keep_mask_kernel(q, w, k, valid, topk))
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got != 0, want)
    assert (want.sum(-1) == np.minimum(np.maximum(np.arange(s) + 1 - pad, 0), topk)).all()
