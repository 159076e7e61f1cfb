"""The walking GQA decode kernel on a joined leaf of FOUR head rows a token
(2 kv heads: ZAYA1's ``(4, 128)`` in bf16, a quarter of a tile, two 32-bit
words), interpreted, against the float32 einsum: bf16 (two rows a word: the
shift-and-pack path of ``_block_head_rows``) and float32 (one row a word),
contexts that end mid-page at a shared cursor, with gap columns inside a
context and a slot that holds nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.flash_decode import paged_gather_leaf, paged_walk_decode_attention
from neuronx_distributed_tpu.modules.attention import _masked_gqa_attention, split_kv
from tests.kernels import page_runs


@pytest.mark.parametrize("dtype,tol", [("bfloat16", 0.02), ("float32", 2e-5)])
@pytest.mark.parametrize("heads", [8, 4], ids=["group_of_4", "group_of_2"])
def test_walk_on_four_head_rows_a_token_is_the_einsum(dtype, tol, heads):
    dtype = jnp.dtype(dtype)
    hkv, d, page, n_log, cur = 2, 128, 16, 80, 1100          # three blocks of 512 tokens, the last partly
    contexts = (1037, 513, 40, 0)
    b = len(contexts)
    valid = np.zeros((b, n_log * page), bool)
    for i, n in enumerate(contexts):
        valid[i, cur + 1 - n:cur + 1] = True
    valid[0, 300:420] = False                                 # gap columns another slot's admission left
    rng = np.random.default_rng(0)
    table = np.zeros((b, n_log), np.int32)
    ids = rng.permutation(np.arange(1, b * n_log + 1))
    for i, n in enumerate(contexts):
        if n:
            lo = (cur + 1 - n) // page
            table[i, lo:cur // page + 1] = ids[i * n_log:i * n_log + cur // page + 1 - lo]
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    pool = jax.random.normal(keys[0], (b * n_log + 1, page, 2 * hkv, d), dtype)
    q = jax.random.normal(keys[1], (b, 1, heads, d), dtype)
    bt, ok, pos = jnp.asarray(table), jnp.asarray(valid), jnp.asarray([cur], jnp.int32)
    got = paged_walk_decode_attention(q, pool, bt, pos, kv_valid=ok, page_size=page, interpret=True)
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = _masked_gqa_attention(f32(q), *split_kv(f32(paged_gather_leaf(pool, bt, page))), ok[:, None])
    got, want = np.asarray(f32(got)), np.asarray(want)
    assert np.abs(got[:3] - want[:3]).max() <= tol
    assert not got[3].any()                                   # a slot that maps nothing returns zeros


@pytest.mark.parametrize("case", page_runs.CASES)
def test_a_run_of_pages_fetched_whole_lands_as_a_copy_a_page_does(monkeypatch, case):
    """The quarter-tile leaf in bf16 (a page is 16 KB: the leaf whose walk the
    NUMBER of copies bounds) over every shape of block table, blocks of eight
    pages: what the kernel returns with runs fetched whole is, bit for bit,
    what it returns with a copy a page."""
    from neuronx_distributed_tpu.kernels import flash_decode

    hkv, d, page, heads, b = 2, 128, 16, 8, 3
    monkeypatch.setattr(flash_decode, "WALK_BLOCK_TOKENS", 8 * page)
    n_log = 22 if case == "short_last_block" else 24         # the third block holds 6 pages
    cur = n_log * page - 5
    spans = [(3, n_log), (n_log // 2 + 1, n_log), None]
    table = page_runs.table(case, b, n_log, spans)
    assert (page_runs.runs(table) > 0) == (case not in ("no_runs", "adjacent_off_the_grid"))
    valid = np.repeat(table != 0, page, axis=1) & (np.arange(n_log * page) <= cur)
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    pool = jax.random.normal(keys[0], (page_runs.pool_pages(b, n_log), page, 2 * hkv, d), jnp.bfloat16)
    q = jax.random.normal(keys[1], (b, 1, heads, d), jnp.bfloat16)
    walk = lambda: np.asarray(paged_walk_decode_attention(   # noqa: E731
        q, pool, jnp.asarray(table), jnp.asarray([cur], jnp.int32), kv_valid=jnp.asarray(valid), page_size=page,
        interpret=True).astype(jnp.float32))
    got = walk()
    with page_runs.single_copies():
        want = walk()
    np.testing.assert_array_equal(got, want)
    assert got[:2].any() and not got[2].any()
