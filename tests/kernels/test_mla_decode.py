"""The paged ABSORBED-decode kernel (multi-head latent attention) against
plain ``jnp`` on a scattered page pool: page boundaries, ragged contexts,
invalid columns, unmapped pages, the cursor bound, and the kernel's own walk
over the blocks a slot maps (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.flash_decode import (
    LATENT_BLOCK_TOKENS,
    paged_gather_leaf,
    paged_latent_decode_attention,
)
from neuronx_distributed_tpu.modules.attention import latent_decode_attention
from tests.kernels import page_runs

PS, D_C, D_R = 16, 32, 8
SCALE = 0.21
T = LATENT_BLOCK_TOKENS        # tokens the kernel fetches and multiplies at a time
G = T // PS                    # pages a block


def _pool(rng, b, n_log, lens):
    """A pool whose pages are dealt out of order; slot ``i`` maps the pages
    covering ``lens[i]`` columns (a number: from column 0; a list of
    ``(first, end)`` column ranges: those), the rest stay on the null page 0."""
    pages = 1 + b * n_log
    c_pool = rng.standard_normal((pages, PS, 1, D_C)).astype(np.float32)
    r_pool = rng.standard_normal((pages, PS, 1, D_R)).astype(np.float32)
    ids = rng.permutation(np.arange(1, pages))
    table = np.zeros((b, n_log), np.int32)
    for i, spans in enumerate(lens):
        for first, end in [(0, spans)] if isinstance(spans, int) else spans:
            lo, hi = first // PS, -(-end // PS)
            table[i, lo:hi] = ids[i * n_log + lo:i * n_log + hi]
    return jnp.asarray(c_pool), jnp.asarray(r_pool), jnp.asarray(table)


def _golden(q_c, q_r, c_pool, r_pool, table, pos, valid):
    c = paged_gather_leaf(c_pool, table, PS)
    r = paged_gather_leaf(r_pool, table, PS)
    s = (jnp.einsum("bshd,bld->bhsl", q_c, c[:, :, 0])
         + jnp.einsum("bshd,bld->bhsl", q_r, r[:, :, 0])) * SCALE
    ok = (pos[:, None] >= jnp.arange(c.shape[1])[None])[None, None] & valid[:, None, None, :]
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    p = jnp.where(ok.any(axis=-1, keepdims=True), p, 0.0)   # a row with nothing to attend: zeros
    return jnp.einsum("bhsl,bld->bshd", p, c[:, :, 0])


@pytest.mark.parametrize("n_log,lens,h,s", [
    (8, (100, 37), 4, 1),          # ragged contexts, one group of 8 pages
    (48, (700, 16, 333), 16, 1),   # three groups of 16 pages; a one-page slot
    (6, (90, 80), 4, 3),           # a multi-token step: each row at its own position
])
def test_matches_jnp_across_pages(n_log, lens, h, s):
    rng = np.random.default_rng(n_log)
    b, cur = len(lens), max(lens)
    c_pool, r_pool, table = _pool(rng, b, n_log, lens)
    q_c = jnp.asarray(rng.standard_normal((b, s, h, D_C)), jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((b, s, h, D_R)), jnp.float32)
    pos = jnp.asarray(cur - s + np.arange(s), jnp.int32)
    valid = np.zeros((b, n_log * PS), bool)
    for i, n in enumerate(lens):   # contexts END at the shared cursor, as the engine lays them out
        valid[i, :n] = True
    valid[0, 3:9] = False          # an invalid stretch inside a mapped page
    valid = jnp.asarray(valid)
    out = paged_latent_decode_attention(
        q_c, q_r, c_pool, r_pool, table, pos, valid, scale=SCALE, page_size=PS)
    want = _golden(q_c, q_r, c_pool, r_pool, table, pos, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # and the einsum the model runs outside the fused scope agrees with both
    einsum = latent_decode_attention(
        q_c, q_r, paged_gather_leaf(c_pool, table, PS), paged_gather_leaf(r_pool, table, PS),
        pos, SCALE, kv_valid=valid)
    np.testing.assert_allclose(np.asarray(einsum), np.asarray(want), atol=2e-5)


# The kernel's walk: one loop a slot over the blocks of ``T`` tokens between
# the first and the last that hold a page the slot maps at or before the
# cursor. ``spans``: each slot's mapped (and valid) column ranges; ``last``:
# the position of the last query row.
@pytest.mark.parametrize("n_log,spans,last,h,s,masked", [
    # contexts laid out as the engine does: they END at the cursor, behind unmapped leading pages
    pytest.param(4 * G, ([(500, 3500)], [(2800, 3500)], [(3460, 3500)]), 3499, 4, 1, True,
                 id="contexts_end_at_the_cursor"),
    pytest.param(2 * G, ([(100, 1500)], [], [(1400, 1500)]), 1499, 4, 1, True,
                 id="a_slot_that_maps_nothing"),
    pytest.param(4 * G, ([(0, 900), (2 * T + 52, 3300)], [(3000, 3300)]), 3299, 4, 1, True,
                 id="a_hole_of_unmapped_blocks"),
    pytest.param(G + 10, ([(0, T + 160)], [(1000, T + 126)]), T + 159, 4, 1, True,
                 id="row_not_a_multiple_of_the_block"),
    pytest.param(2 * G, ([(200, T)], [(T - 3, T)]), T - 1, 4, 1, True,
                 id="context_ends_on_a_block_boundary"),
    pytest.param(2 * G, ([(200, T + 1)], [(T - 3, T + 1)]), T, 4, 1, True,
                 id="context_ends_one_token_past_a_block_boundary"),
    pytest.param(3 * G, ([(0, 2500)], [(700, 2900)]), 900, 4, 1, True,
                 id="cursor_below_mapped_pages"),
    pytest.param(G, ([(0, 100)], [(60, 100)]), 99, 4, 1, True,
                 id="one_block_only"),
    pytest.param(2 * G, ([(0, 1500)], [(0, 1100)]), 1499, 4, 1, False,
                 id="no_kv_valid"),
    pytest.param(3 * G, ([(300, 2600)], [(2000, 2600)]), 2599, 4, 3, True,
                 id="three_rows_at_their_own_positions"),
])
def test_walks_the_blocks_a_slot_maps(n_log, spans, last, h, s, masked):
    rng = np.random.default_rng(last)
    b = len(spans)
    c_pool, r_pool, table = _pool(rng, b, n_log, spans)
    q_c = jnp.asarray(rng.standard_normal((b, s, h, D_C)), jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((b, s, h, D_R)), jnp.float32)
    pos = jnp.asarray(last - s + 1 + np.arange(s), jnp.int32)
    valid = np.zeros((b, n_log * PS), bool)
    for i, ranges in enumerate(spans):
        for first, end in ranges:
            valid[i, first:end] = True
    if masked:
        valid[0, spans[0][0][0] + 3:spans[0][0][0] + 9] = False   # an invalid stretch inside a mapped page
    else:
        valid[:] = True     # without a mask every column up to the row's position counts, the null page's too
    out = paged_latent_decode_attention(
        q_c, q_r, c_pool, r_pool, table, pos, jnp.asarray(valid) if masked else None,
        scale=SCALE, page_size=PS)
    want = _golden(q_c, q_r, c_pool, r_pool, table, pos, jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    if not all(spans):
        empty = [i for i, ranges in enumerate(spans) if not ranges]
        assert not np.asarray(out)[empty].any()     # a slot that maps nothing writes zeros


def test_null_pages_and_columns_past_the_cursor_contribute_nothing():
    """Poison everything the kernel must not read INTO the result: the null
    page and mapped columns past the cursor hold huge values."""
    rng = np.random.default_rng(5)
    c_pool, r_pool, table = _pool(rng, 2, 32, (200, 40))
    c_pool = c_pool.at[0].set(1e4)
    q_c = jnp.asarray(rng.standard_normal((2, 1, 4, D_C)), jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((2, 1, 4, D_R)), jnp.float32)
    valid = np.zeros((2, 32 * PS), bool)
    valid[0, :200], valid[1, :40] = True, True
    pos = jnp.asarray([150], jnp.int32)      # the cursor sits INSIDE slot 0's mapped pages
    out = paged_latent_decode_attention(
        q_c, q_r, c_pool, r_pool, table, pos, jnp.asarray(valid), scale=SCALE, page_size=PS)
    want = _golden(q_c, q_r, c_pool, r_pool, table, pos, jnp.asarray(valid))
    assert np.isfinite(np.asarray(out)).all() and np.abs(np.asarray(out)).max() < 10
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_bf16_operands_stay_bf16_and_agree_within_rounding():
    rng = np.random.default_rng(6)
    c_pool, r_pool, table = _pool(rng, 2, 16, (250, 130))
    q_c = jnp.asarray(rng.standard_normal((2, 1, 8, D_C)), jnp.bfloat16)
    q_r = jnp.asarray(rng.standard_normal((2, 1, 8, D_R)), jnp.bfloat16)
    valid = np.zeros((2, 16 * PS), bool)
    valid[0, :250], valid[1, :130] = True, True
    pos = jnp.asarray([249], jnp.int32)
    args = (table, pos, jnp.asarray(valid))
    out = paged_latent_decode_attention(
        q_c, q_r, c_pool.astype(jnp.bfloat16), r_pool.astype(jnp.bfloat16), *args,
        scale=SCALE, page_size=PS)
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 1, 8, D_C)
    want = _golden(q_c.astype(jnp.float32), q_r.astype(jnp.float32),
                   c_pool.astype(jnp.bfloat16).astype(jnp.float32),
                   r_pool.astype(jnp.bfloat16).astype(jnp.float32), *args)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), atol=3e-2)


def test_refuses_a_per_head_pool_and_a_mesh():
    rng = np.random.default_rng(7)
    c_pool, r_pool, table = _pool(rng, 1, 4, (20,))
    q_c = jnp.zeros((1, 1, 2, D_C))
    q_r = jnp.zeros((1, 1, 2, D_R))
    with pytest.raises(ValueError, match="latent pool leaf"):
        paged_latent_decode_attention(
            q_c, q_r, jnp.zeros((5, PS, 2, D_C)), r_pool, table, jnp.asarray([3]),
            scale=SCALE, page_size=PS)


@pytest.mark.parametrize("case", page_runs.CASES)
def test_a_run_of_pages_fetched_whole_lands_as_a_copy_a_page_does(monkeypatch, case):
    """Both leaves of the latent pool over every shape of block table, blocks
    of eight pages: what the kernel returns with runs fetched whole (one copy
    a leaf a run) is, bit for bit, what it returns with a copy a page."""
    from neuronx_distributed_tpu.kernels import flash_decode

    b, h, group = 3, 4, 8
    monkeypatch.setattr(flash_decode, "LATENT_BLOCK_TOKENS", group * PS)
    n_log = 2 * group + 6 if case == "short_last_block" else 3 * group     # the third block holds 6 pages
    cur = n_log * PS - 5
    spans = [(3, n_log), (group + 1, n_log), None]
    table = page_runs.table(case, b, n_log, spans)
    assert (page_runs.runs(table) > 0) == (case not in ("no_runs", "adjacent_off_the_grid"))
    valid = jnp.asarray(np.repeat(table != 0, PS, axis=1) & (np.arange(n_log * PS) <= cur))
    rng = np.random.default_rng(4)
    pages = page_runs.pool_pages(b, n_log)
    c_pool = jnp.asarray(rng.standard_normal((pages, PS, 1, D_C)), jnp.float32)
    r_pool = jnp.asarray(rng.standard_normal((pages, PS, 1, D_R)), jnp.float32)
    q_c = jnp.asarray(rng.standard_normal((b, 1, h, D_C)), jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((b, 1, h, D_R)), jnp.float32)
    attend = lambda: np.asarray(paged_latent_decode_attention(   # noqa: E731
        q_c, q_r, c_pool, r_pool, jnp.asarray(table), jnp.asarray([cur], jnp.int32), valid, scale=SCALE,
        page_size=PS))
    got = attend()
    with page_runs.single_copies():
        want = attend()
    np.testing.assert_array_equal(got, want)
    assert got[:2].any() and not got[2].any()
