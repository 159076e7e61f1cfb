"""The two kernels a stack of window and full attention layers runs on, in
interpret mode: the paged GQA decode kernel that walks the blocks a slot maps
(``kernels/flash_decode.paged_walk_decode_attention``) against the gather
transport and the float32 einsum under a mask built from indices, and a
prefill of either kind (a window layer's banded flash forward, ``kernels/
flash_attention.banded_flash_attention``; a full layer's ``flash_attention``)
against ``xla_attention``'s mathematics under an index mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels import flash_decode
from neuronx_distributed_tpu.kernels.flash_attention import banded_flash_attention
from neuronx_distributed_tpu.kernels.flash_decode import (
    paged_gather_leaf,
    paged_walk_decode_attention,
)
from neuronx_distributed_tpu.modules.attention import (
    _masked_gqa_attention,
    split_kv,
    window_floor,
    window_keep,
    window_prefill_attention,
    xla_attention,
)
from tests.kernels import page_runs

B, H, HKV, D, PS, N_LOG = 3, 4, 2, 16, 8, 32


@pytest.fixture()
def small_blocks(monkeypatch):
    monkeypatch.setattr(flash_decode, "WALK_BLOCK_TOKENS", 32)   # four pages a block


def paged_case(seed=0):
    """Three slots over a pool: one context from column 26, one from 80 with
    invalid (gap) columns inside it, one slot that maps nothing."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((1 + B * N_LOG, PS, 2 * HKV, D)), jnp.float32)
    ids = rng.permutation(np.arange(1, 1 + B * N_LOG))
    cur = 157
    bt = np.zeros((B, N_LOG), np.int32)
    bt[0, 3:20], bt[1, 10:20] = ids[:17], ids[17:27]
    valid = np.zeros((B, N_LOG * PS), bool)
    valid[0, 26:cur + 1] = valid[1, 80:cur + 1] = True
    valid[1, 100:109] = False
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    return pool, bt, valid, q, cur


@pytest.mark.parametrize("window", [None, 40, 8], ids=["full", "window_40", "window_8"])
def test_the_walking_kernel_is_the_gather_transport_under_an_index_mask(small_blocks, window):
    pool, bt, valid, q, cur = paged_case()
    q_pos = jnp.asarray([cur], jnp.int32)
    k, v = split_kv(paged_gather_leaf(pool, jnp.asarray(bt), PS))
    want = _masked_gqa_attention(q, k, v, window_keep(jnp.asarray(valid), q_pos, window))
    floor, table = None, bt
    if window is not None:
        floor = window_floor(jnp.asarray(valid), cur, window)
        table = bt.copy()
        for row, lo in enumerate(np.asarray(floor)):
            table[row, :lo // PS] = 0          # what the manager freed behind the window
    got = paged_walk_decode_attention(
        q, pool, jnp.asarray(table), q_pos, kv_valid=jnp.asarray(valid), floor=floor, page_size=PS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert not np.asarray(got[2]).any()        # the slot that maps nothing


def walk_geometry_case(hkv, g, d, dtype, n_log, cur, seed=0):
    """Four slots over a pool of ``hkv`` kv heads of ``d``, ``g`` query heads
    each: a context from column 26 that ends mid-block at ``cur``; one from 80
    whose gap columns 96-111 fill two pages, the second UNMAPPED inside a
    fetched block; a slot that maps nothing; a context of the cursor's own
    block alone. ``n_log`` pages of 8 a row, blocks of four."""
    rng = np.random.default_rng(seed)
    b = 4
    pool = jnp.asarray(rng.standard_normal((1 + b * n_log, PS, 2 * hkv, d)), dtype)
    ids = rng.permutation(np.arange(1, 1 + b * n_log)).reshape(b, n_log)
    bt = np.zeros((b, n_log), np.int32)
    valid = np.zeros((b, n_log * PS), bool)
    for row, start in ((0, 26), (1, 80), (3, cur - cur % 32 + 3)):
        valid[row, start:cur + 1] = True
        bt[row, start // PS:cur // PS + 1] = ids[row, start // PS:cur // PS + 1]
    valid[1, 96:112] = False
    bt[1, 13] = 0
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, d)), dtype)
    return pool, bt, valid, q


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("hkv,g,d", [(8, 6, 128), (8, 4, 128), (16, 1, 256)], ids=["trinity", "mixtral", "codegen2"])
@pytest.mark.parametrize("window,n_log,cur", [(None, 32, 157), (40, 32, 157), (28, 30, 237)],
                         ids=["full", "floor_mid_block", "floor_in_a_first_page_and_a_short_last_block"])
def test_the_walking_kernel_at_the_cells_head_geometries(small_blocks, hkv, g, d, dtype, window, n_log, cur):
    """The three head geometries that meet this kernel (Trinity's, Mixtral's,
    CodeGen2's), in float32 and in the cells' bf16 (two heads share a 32-bit
    word of the block there), against the float32 einsum under an index mask:
    a context that ends mid-block, a ``floor`` mid-block (157 - 39 = 118, the
    third page of its block) and in a block's first page (237 - 27 = 210 of
    208-215), unmapped pages inside a fetched block, a slot that maps nothing,
    gap columns, and 30 pages a row where a block holds four."""
    pool, bt, valid, q = walk_geometry_case(hkv, g, d, dtype, n_log, cur)
    q_pos = jnp.asarray([cur], jnp.int32)
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    k, v = split_kv(f32(paged_gather_leaf(pool, jnp.asarray(bt), PS)))
    want = _masked_gqa_attention(f32(q), k, v, window_keep(jnp.asarray(valid), q_pos, window))
    floor, table = None, bt
    if window is not None:
        floor = window_floor(jnp.asarray(valid), cur, window)
        assert int(floor[0]) == cur + 1 - window
        table = bt.copy()
        for row, lo in enumerate(np.asarray(floor)):
            table[row, :lo // PS] = 0          # what the manager freed behind the window
    got = paged_walk_decode_attention(
        q, pool, jnp.asarray(table), q_pos, kv_valid=jnp.asarray(valid), floor=floor, page_size=PS)
    assert got.dtype == dtype and got.shape == q.shape
    # bf16: the probabilities and the output are rounded to 8 bits; a head taken from another's rows reads ~1
    np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(want), atol=3e-6 if dtype == jnp.float32 else 2e-2)
    assert not np.asarray(f32(got[2])).any()   # the slot that maps nothing


@pytest.mark.parametrize("case", page_runs.CASES)
def test_the_walking_kernel_on_a_window_layers_table_is_the_same_with_runs_and_with_a_copy_a_page(monkeypatch, case):
    """A window layer's table (a ``floor`` a slot, nothing mapped below its
    page) over every shape of block table, blocks of eight pages: runs fetched
    whole give, bit for bit, what a copy a page gives."""
    monkeypatch.setattr(flash_decode, "WALK_BLOCK_TOKENS", 8 * PS)
    n_log = 30 if case == "short_last_block" else N_LOG       # the fourth block holds 6 pages
    cur = n_log * PS - 3
    spans = [(5, n_log), (n_log // 2 + 1, n_log), None]
    table = page_runs.table(case, B, n_log, spans)
    mapped = np.repeat(table != 0, PS, axis=1)
    valid = mapped & (np.arange(n_log * PS) <= cur)
    floor = np.where(mapped.any(1), mapped.argmax(1) + 3, cur + 1).astype(np.int32)   # mid-page, above the freed pages
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal((page_runs.pool_pages(B, n_log), PS, 2 * HKV, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    walk = lambda: np.asarray(paged_walk_decode_attention(   # noqa: E731
        q, pool, jnp.asarray(table), jnp.asarray([cur], jnp.int32), kv_valid=jnp.asarray(valid),
        floor=jnp.asarray(floor), page_size=PS))
    got = walk()
    with page_runs.single_copies():
        want = walk()
    np.testing.assert_array_equal(got, want)
    assert got[:2].any() and not got[2].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_walking_kernel_reads_each_word_of_a_block_once_and_never_a_head_a_row_at_a_time(dtype):
    """The traced kernel's reads of its block buffer: ``2 Hkv x itemsize / 4``
    of them a block (8 at Trinity's 16 rows of bf16 a token), each a (T, D)
    array of 32-bit words taken with a sublane stride from the buffer's 32-bit
    view. The parent's form, ``buf[slot, :, h, :]`` (2 Hkv reads in the storage
    type, which Mosaic lowers to a load a (token, head) row: 8,857 instruction
    bundles a block where this form is 1,696), cannot come back unseen."""
    hkv, g, d, page, n_log = 8, 6, 128, 16, 64
    shape = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda q, pool, bt, pos: paged_walk_decode_attention(q, pool, bt, pos, page_size=page))(
        shape((2, 1, hkv * g, d), dtype), shape((65, page, 2 * hkv, d), dtype), shape((2, n_log), jnp.int32),
        shape((1,), jnp.int32))
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    # a block is sized by a token's bytes: 512 tokens of Trinity's 16 rows in bf16, 256 in float32
    block = flash_decode.walk_block_tokens(2 * hkv * d * jnp.dtype(dtype).itemsize, page)
    assert block == (flash_decode.WALK_BLOCK_TOKENS if dtype == jnp.bfloat16 else flash_decode.WALK_BLOCK_TOKENS // 2)
    buffer = (flash_decode.BLOCKS_AHEAD + 1, block // page, page, 2 * hkv, d)   # blocks of pages: a run of them is one copy
    reads = []

    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "get" and e.invars[0].aval.shape == buffer:
                reads.append((e.outvars[0].aval, jax.tree.unflatten(e.params["tree"], e.invars[1:])))
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(call.params["jaxpr"])
    words_a_token = 2 * hkv * jnp.dtype(dtype).itemsize // 4
    assert len(reads) == words_a_token
    for aval, transforms in reads:
        assert aval.shape == (block, d) and aval.dtype == jnp.uint32
        rows = transforms[-1].indices[0]
        assert (rows.size, rows.stride) == (block, words_a_token)
    assert sorted(t[-1].indices[0].start for _, t in reads) == list(range(words_a_token))


def test_the_window_is_counted_over_valid_columns_not_columns():
    """Slot 1's nine gap columns lie inside a window of 64 tokens: its floor
    is nine columns lower than the gap-free slot's."""
    _, _, valid, _, cur = paged_case()
    floor = np.asarray(window_floor(jnp.asarray(valid), cur, 64))
    assert floor[0] == cur - 63 and floor[1] == cur - 63 - 9 and floor[2] == cur
    keep = np.asarray(window_keep(jnp.asarray(valid), jnp.asarray([cur]), 64))
    assert keep[0, 0].sum() == keep[1, 0].sum() == 64
    assert keep[1, 0].argmax() == floor[1]


def test_the_walking_kernel_refuses_what_it_does_not_do(small_blocks):
    pool, bt, valid, q, cur = paged_case()
    with pytest.raises(ValueError, match="one query row"):
        paged_walk_decode_attention(jnp.concatenate([q, q], 1), pool, jnp.asarray(bt), jnp.asarray([cur, cur + 1]))
    with pytest.raises(ValueError, match="joined K/V pool"):
        paged_walk_decode_attention(q, pool[:, :, :3], jnp.asarray(bt), jnp.asarray([cur]))
    with pytest.raises(ValueError, match="whole 32-bit words"):      # 2 x 3 rows of 8 bits
        paged_walk_decode_attention(
            jnp.zeros((B, 1, 3, D), jnp.int8), jnp.zeros((5, PS, 6, D), jnp.int8), jnp.asarray(bt), jnp.asarray([cur]),
            page_size=PS)


@pytest.mark.parametrize("window", [None, 32, 100], ids=["causal", "window_32", "window_100"])
@pytest.mark.parametrize("padded", [False, True], ids=["whole", "left_padded"])
def test_a_prefill_of_either_kind_is_attention_under_an_index_mask(window, padded):
    """A window layer's banded forward, and a full layer's route (``window=None``:
    the flash forward every other model's prefill runs, padding as segment -1)."""
    b, s = 2, 256
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (b, s, H, D))
    k = jax.random.normal(keys[1], (b, s, HKV, D))
    v = jax.random.normal(keys[2], (b, s, HKV, D))
    valid = jnp.arange(s)[None] >= jnp.asarray([[0], [37]]) if padded else None
    rows = jnp.arange(s)
    band = jnp.ones((s, s), bool) if window is None else rows[None, :] > rows[:, None] - window
    # xla_attention's segment path takes a (B, Sq, Sk) pair mask in no form: the
    # index mask goes through the same float32 einsum
    keep = jnp.broadcast_to((rows[:, None] >= rows[None, :]) & band, (b, s, s))
    if valid is not None:
        keep = keep & valid[:, None, :]
    want = _masked_gqa_attention(q, k, v, keep)
    if window is None:
        got = window_prefill_attention(q, k, v, None, impl="flash", mask=valid)
    else:
        got = banded_flash_attention(q, k, v, window=window, kv_valid=valid, block_q=64, block_k=32)
    rows_ok = np.ones((b, s), bool) if valid is None else np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[rows_ok], np.asarray(want)[rows_ok], atol=3e-6)
    if window is None and not padded:
        np.testing.assert_allclose(np.asarray(got), np.asarray(xla_attention(q, k, v)), atol=3e-6)
    if padded and window is not None:
        assert not np.asarray(got)[1, :37].any()       # a padded query keeps nothing: zeros


@pytest.mark.parametrize("length", [100, 203])
def test_a_length_that_is_no_multiple_of_a_tile_is_padded_and_cut(length):
    """The engine's exact-length bucket at the row's end: 203 tokens run as
    256, 100 as 104, and the rows that exist are what they were."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (1, length, H, D))
    k = jax.random.normal(keys[1], (1, length, HKV, D))
    v = jax.random.normal(keys[2], (1, length, HKV, D))
    rows = jnp.arange(length)
    keep = (rows[:, None] >= rows[None, :]) & (rows[None, :] > rows[:, None] - 32)
    want = _masked_gqa_attention(q, k, v, keep[None])
    got = banded_flash_attention(q, k, v, window=32)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


@pytest.mark.parametrize("length,valid_from", [(100, None), (203, None), (203, 50), (1100, 301)])
def test_a_length_that_is_no_multiple_of_a_tile_equals_the_parents(length, valid_from):
    """The exact-length fallback against the forward as it stood (``_group_fwd_
    parent.py``): the keys and rows the kernel pads on the right are one more
    case of "past the prompt's end", and the rows that exist are the parent's
    bit for bit (1,100 tokens run as 1,152: nine tiles of 128)."""
    import _group_fwd_parent

    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (1, length, 6, 32), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, length, 1, 32), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, length, 1, 32), jnp.bfloat16)
    valid = None if valid_from is None else jnp.arange(length)[None] >= valid_from
    want = _group_fwd_parent.banded_flash_attention(q, k, v, 160, valid, block_q=128, block_k=128)
    got = banded_flash_attention(q, k, v, 160, valid, block_q=128, block_k=128)
    assert got.shape == q.shape
    rows = slice(valid_from, None)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[:, rows], np.asarray(want, np.float32)[:, rows])


def test_the_band_cuts_the_grid_to_the_key_blocks_it_can_overlap():
    """16,384 tokens, a window of 4096, tiles of 512: the band's own list of
    pairs, nine key blocks a query block at most (252 steps), where the
    rectangle of ten steps a block took 320 and a causal forward's triangle
    528."""
    q = jax.ShapeDtypeStruct((1, 16384, 6, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 1, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda a, b, c: banded_flash_attention(a, b, c, window=4096))(q, kv, kv)
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert eqn.params["grid_mapping"].grid == (1, 1, 36 + 24 * 9)
