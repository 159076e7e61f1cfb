"""The sparse LATENT decode kernel (interpreted) against float32 ``jnp``: the
absorbed product of every head over a slot's SELECTED rows of the joined
latent leaf, fetched a token a copy through the block table: ragged
``n_sel``, a slot that holds fewer than ``topk`` tokens, a slot that selects
nothing, selections that cross pages in any order, the chunk's padding, and
the whole fused step (index scores, ``top_k``, attend) against the einsum
path of the row cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.flash_decode import paged_sparse_latent_decode_attention
from neuronx_distributed_tpu.modules.attention import (
    indexed_latent_decode_attention,
    join_latent,
    latent_leaf_shape,
    split_latent,
)

PAGE = 16


def _pool(key, pages, d_c, d_r, dtype=jnp.float32):
    rows, lanes = latent_leaf_shape(d_c, d_r)
    kc, kr = jax.random.split(key)
    c = jax.random.normal(kc, (pages, PAGE, 1, d_c), dtype)
    k_pe = jax.random.normal(kr, (pages, PAGE, 1, d_r), dtype)
    pool = join_latent(c, k_pe, rows, lanes)
    # the spare lanes and rows hold garbage in a live pool: nothing may read them
    spare = jnp.ones_like(pool).at[:, :, :d_c // lanes].set(0).at[:, :, d_c // lanes, :d_r].set(0)
    return pool + 7.0 * spare, c[:, :, 0], k_pe[:, :, 0]


def _golden(q_c, q_r, c, k_pe, table, cols, n_sel, scale):
    """Float32 softmax over each slot's first ``n_sel`` selected columns."""
    out = np.zeros(q_c.shape, np.float32)
    for b in range(q_c.shape[0]):
        sel = np.asarray(cols[b, :int(n_sel[b])])
        if not len(sel):
            continue
        page = np.asarray(table)[b, sel // PAGE]
        cs, ks = np.asarray(c)[page, sel % PAGE], np.asarray(k_pe)[page, sel % PAGE]
        s = (np.asarray(q_c[b, 0]) @ cs.T + np.asarray(q_r[b, 0]) @ ks.T) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b, 0] = (p / p.sum(-1, keepdims=True)) @ cs
    return out


@pytest.mark.parametrize("d_c,d_r,heads,keep", [(256, 64, 8, 40), (32, 8, 4, 16), (512, 64, 16, 530)],
                         ids=["two_rows_of_128", "tiny_lanes_of_32", "glm5_tile_past_a_chunk"])
def test_kernel_matches_float32_over_the_selected_rows(d_c, d_r, heads, keep):
    slots, n_log = 4, 40
    pool, c, k_pe = _pool(jax.random.PRNGKey(0), slots * n_log + 1, d_c, d_r)
    rng = np.random.default_rng(1)
    table = jnp.asarray(1 + rng.permutation(slots * n_log).reshape(slots, n_log), jnp.int32)
    held = [n_log * PAGE, 7, 0, 300]                      # tokens each slot holds: a full row, under topk, none, some
    n_sel = jnp.asarray([min(h, keep) for h in held], jnp.int32)
    # any order, across pages; entries past n_sel name columns that must not be read
    cols = np.stack([np.r_[rng.permutation(max(h, 1))[:min(h, keep)],
                           rng.integers(0, n_log * PAGE, keep)][:keep] for h in held]).astype(np.int32)
    q_c = jax.random.normal(jax.random.PRNGKey(2), (slots, 1, heads, d_c), jnp.float32)
    q_r = jax.random.normal(jax.random.PRNGKey(3), (slots, 1, heads, d_r), jnp.float32)
    scale = (d_c // 2) ** -0.5
    got = paged_sparse_latent_decode_attention(
        q_c, q_r, pool, table, jnp.asarray(cols), n_sel, scale=scale, page_size=PAGE, interpret=True)
    want = _golden(q_c, q_r, c, k_pe, table, cols, n_sel, scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[2]).any()                   # the slot that selected nothing


def test_kernel_refuses_what_it_cannot_fetch():
    pool, _, _ = _pool(jax.random.PRNGKey(0), 3, 256, 64)
    q_c, q_r = jnp.zeros((1, 1, 4, 256)), jnp.zeros((1, 1, 4, 64))
    table, cols, n = jnp.ones((1, 2), jnp.int32), jnp.zeros((1, 4), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="one query row"):
        paged_sparse_latent_decode_attention(
            jnp.zeros((1, 2, 4, 256)), jnp.zeros((1, 2, 4, 64)), pool, table, cols, n, scale=1.0, interpret=True)
    with pytest.raises(ValueError, match="joined latent pool leaf"):
        paged_sparse_latent_decode_attention(q_c, q_r, pool[:, :, :2], table, cols, n, scale=1.0, interpret=True)


def test_the_fused_step_is_the_row_caches_einsum():
    """``indexed_latent_decode_attention`` inside a fused frame (the window
    scatter, the index-score kernel, ``top_k``, the sparse latent kernel) and
    on a row cache (einsums under the bisection mask) select and attend the
    same columns."""
    from neuronx_distributed_tpu.modules.attention import fused_paged_attention_scope

    slots, n_log, d_c, d_r, h, h_i, d_i, topk = 2, 6, 32, 8, 4, 4, 16, 16
    length = n_log * PAGE
    rows, lanes = latent_leaf_shape(d_c, d_r)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    row_kv = join_latent(jax.random.normal(keys[0], (slots, length, 1, d_c)),
                         jax.random.normal(keys[1], (slots, length, 1, d_r)), rows, lanes)
    row_idx = jax.random.normal(keys[2], (slots, length, 1, d_i))
    valid = jnp.arange(length)[None] < jnp.asarray([70, 9])[:, None]      # past topk; under it
    q_c, q_r = jax.random.normal(keys[3], (slots, 1, h, d_c)), jax.random.normal(keys[4], (slots, 1, h, d_r))
    q_idx, w_idx = jax.random.normal(keys[5], (slots, 1, h_i, d_i)), jax.random.normal(keys[6], (slots, 1, h_i))
    pos = jnp.asarray([69], jnp.int32)
    want = indexed_latent_decode_attention(q_c, q_r, q_idx, w_idx, row_kv, row_idx, pos, topk, 0.25, kv_valid=valid)
    c, _ = split_latent(row_kv, d_c, d_r)
    assert c.shape == (slots, length, d_c)
    # the same rows as a pool: slot b's page j is physical page 1 + b * n_log + j
    table = 1 + jnp.arange(slots * n_log, dtype=jnp.int32).reshape(slots, n_log)
    to_pool = lambda a: jnp.concatenate(  # noqa: E731
        [jnp.zeros((1, PAGE) + a.shape[2:]), a.reshape((slots * n_log, PAGE) + a.shape[2:])])
    pools = {("layer",): (to_pool(row_kv), to_pool(row_idx))}
    page0 = jnp.asarray(4, jnp.int32)                                      # the window: pages 4 and 5
    window = lambda a: jax.lax.dynamic_slice_in_dim(a, 4 * PAGE, 2 * PAGE, axis=1)  # noqa: E731
    with fused_paged_attention_scope(pools, table, PAGE, page0):
        got = indexed_latent_decode_attention(
            q_c, q_r, q_idx, w_idx, window(row_kv), window(row_idx), pos, topk, 0.25, kv_valid=valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
