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
