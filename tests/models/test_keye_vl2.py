"""Keye-VL-2.0's language model at a tiny size with every mechanism present
(GQA with head norms, M-RoPE sections, an indexer keeping 16 columns, 8
experts top-3 renormalised) against the plain reference: the full forward,
prefill then decode through the cache in LOGITS, the selected sets, ties,
``topk`` past the context, unequal M-RoPE streams."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.models.keye_vl2 import (
    KeyeVL2ForCausalLM,
    KeyeVL2Model,
    keye_vl2_30b_a3b,
    mrope_angles,
    tiny_keye_vl2,
)
from neuronx_distributed_tpu.modules.attention import (
    PAGED_LEAVES,
    index_scores,
    sparse_keep_mask,
    split_kv,
)

from perfbench.references.keye_vl2 import Reference
from tests.models.jitted import every_position, forward, through_the_cache

ATOL = 3e-5


def published_keys(cfg):
    return {
        "num_hidden_layers": cfg.num_layers, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_experts": cfg.num_experts, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob, "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab_size,
        "rope_scaling": {"mrope_section": list(cfg.mrope_section)},
        "sa_config": {"indexer_num_heads": cfg.indexer_num_heads,
                      "indexer_head_dim": cfg.indexer_head_dim, "topk": cfg.index_topk},
    }


def _weights(model, seed=0):
    """Seeded weights with the norms' scales and the LayerNorm's bias moved
    off their initial 1 and 0, so that each matters."""
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.3 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1 else leaf
        for leaf, k in zip(leaves, keys)])


def prefill_logits(model, params, ids, **kw):
    """``(logits at EVERY position, cache)`` of a prefill: the served model
    applies its head to the last position alone, so the backbone and the
    head's kernel."""
    backbone = KeyeVL2Model(model.config, model.attention_impl, mode="prefill")
    return every_position(backbone, params, ids, **kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_keye_vl2()
    model = KeyeVL2ForCausalLM(cfg, attention_impl="xla")
    params = _weights(model)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 1, cfg.vocab_size)
    return cfg, model, params, ids, Reference(published_keys(cfg), params)


@pytest.fixture(scope="module")
def want(tiny):
    """The reference's logits of ``ids``, its full forward, once."""
    *_, ids, ref = tiny
    return ref.logits(np.asarray(ids))


@pytest.fixture(scope="module")
def prefilled(tiny):
    """``(the prefill's last logits, its cache)`` of the first 40 tokens."""
    _, model, params, ids, _ = tiny
    (last, _), cache = through_the_cache(model.clone(mode="prefill"), params, ids[:, :40])
    return last, cache


def test_the_tiny_preset_holds_every_mechanism():
    cfg = tiny_keye_vl2()
    assert cfg.num_heads // cfg.num_kv_heads == 2 and cfg.top_k == 3 and cfg.num_experts == 8
    assert sum(cfg.mrope_section) == cfg.head_dim // 2 and len(set(cfg.mrope_section)) > 1
    assert cfg.index_topk == 16 < 96 and cfg.norm_topk_prob and cfg.kv_cache_kind == "indexed"


def test_param_tree_has_the_published_parts(tiny):
    _, _, params, _, _ = tiny
    layer = params["params"]["model"]["layers_1"]
    assert set(layer) == {"attn", "input_norm", "post_attn_norm", "moe"}
    assert set(layer["moe"]) == {"router", "experts"}                  # no shared expert
    attn = layer["attn"]
    assert set(attn) == {"qkv", "q_norm", "k_norm", "o_proj", "idx_q_proj", "idx_k_proj", "idx_k_norm", "idx_w_proj"}
    assert attn["idx_q_proj"]["kernel"].shape == (64, 4 * 8) and attn["idx_k_proj"]["kernel"].shape == (64, 8)
    assert attn["idx_w_proj"]["kernel"].shape == (64, 4) and attn["q_norm"]["weight"].shape == (16,)


def test_published_widths_count_30b_parameters():
    model = KeyeVL2ForCausalLM(keye_vl2_30b_a3b(), attention_impl="xla")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sizes = {jax.tree_util.keystr(p): int(np.prod(a.shape))
             for p, a in jax.tree_util.tree_flatten_with_path(meta.unbox(shapes))[0]}
    total = sum(sizes.values())
    layer = sum(n for k, n in sizes.items() if "layers_0'" in k)
    assert 30.0e9 < total < 31.5e9
    assert abs(layer - 625.4e6) < 0.2e6                                 # ISSUE's reckoning of a layer
    assert sum(n for k, n in sizes.items() if "layers_0'" in k and "idx_" in k) == 2048 * (16 * 64 + 64 + 16) + 2 * 64


def test_full_forward_matches_the_reference_with_selection_at_work(tiny):
    cfg, model, params, ids, ref = tiny
    logits, _ = forward(model, params, ids)
    want, router, index = ref.logits_and_margins(np.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)
    assert router.shape == index.shape == (2, 96) and np.isinf(index[:, :cfg.index_topk]).all()
    assert np.isfinite(index[:, cfg.index_topk:]).all() and (index >= 0).all()


def test_prefill_matches_the_references_full_forward(tiny, want, prefilled):
    """Logits, not tokens: the prompt's at every position, and what a caller
    of a prefill reads of them."""
    cfg, model, params, ids, _ = tiny
    logits, _ = prefill_logits(model, params, ids[:, :40])
    np.testing.assert_allclose(np.asarray(logits), want[:, :40], atol=ATOL)
    last, _ = prefilled
    assert last.shape == (2, 1, cfg.vocab_size)          # all a caller of a prefill reads
    np.testing.assert_allclose(np.asarray(last[:, 0]), want[:, 39], atol=ATOL)


def test_decode_through_the_cache_matches_the_references_full_forward(tiny, want, prefilled):
    """After the prefill of 40 tokens, 56 decode steps against the cache,
    each row keeping 16 of up to 96 columns: every step's logits."""
    _, model, params, ids, _ = tiny
    decode, (_, cache) = model.clone(mode="decode"), prefilled
    for t in range(40, 96):
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, ids[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, t], atol=ATOL)


def test_a_many_row_decode_step_is_the_suffix_prefill(tiny, want):
    """The decode path with many query rows at once (how the engine resumes a
    context): each row selects for itself."""
    cfg, model, params, ids, ref = tiny
    _, cache = through_the_cache(model.clone(mode="prefill"), params, ids[:, :24])
    (logits, _), _ = through_the_cache(model.clone(mode="decode"), {**params, "cache": cache}, ids[:, 24:96])
    np.testing.assert_allclose(np.asarray(logits), want[:, 24:], atol=ATOL)


def test_the_selected_sets_are_the_references(tiny):
    """Recompute layer 0's mask from the model's own projections and hold it
    against the set the reference kept at every row."""
    cfg, model, params, ids, ref = tiny
    sets = ref.selected(np.asarray(ids))
    assert len(sets) == cfg.num_layers and sets[0].shape == (2, 96, 96)
    assert (sets[0].sum(-1) == np.minimum(np.arange(96) + 1, cfg.index_topk)).all()
    _, inter = model.apply(params, ids, capture_intermediates=lambda mdl, _: mdl.name in (
        "idx_q_proj", "idx_k_norm", "idx_w_proj") and "layers_0" in "/".join(mdl.path))
    got = inter["intermediates"]["model"]["layers_0"]["attn"]
    from neuronx_distributed_tpu.models.keye_vl2 import rotate

    pos = jnp.broadcast_to(jnp.arange(96)[None], (2, 96))
    ang = mrope_angles(pos, cfg.indexer_head_dim, cfg.rope_theta)
    q_idx = rotate(got["idx_q_proj"]["__call__"][0].reshape(2, 96, 4, 8), ang)
    k_idx = rotate(got["idx_k_norm"]["__call__"][0][:, :, None, :], ang)[:, :, 0]
    keep = sparse_keep_mask(q_idx, got["idx_w_proj"]["__call__"][0], k_idx, pos,
                            jnp.ones((2, 96), bool), cfg.index_topk)
    np.testing.assert_array_equal(np.asarray(keep), sets[0])


def test_exact_ties_resolve_to_the_lower_position(tiny):
    """Zero the index weights: every score is 0.0 and every row keeps its
    FIRST 16 positions, in the model as in the reference."""
    cfg, model, params, ids, _ = tiny
    zeroed = jax.tree_util.tree_map_with_path(
        lambda p, a: jnp.zeros_like(a) if "idx_w_proj" in jax.tree_util.keystr(p) else a, params)
    ref = Reference(published_keys(cfg), zeroed)
    sets = ref.selected(np.asarray(ids))
    want = np.tril(np.ones((96, 96), bool)) & (np.arange(96)[None] < cfg.index_topk)
    for layer in sets:
        np.testing.assert_array_equal(layer[0], want)
    logits, _ = forward(model, zeroed, ids)
    np.testing.assert_allclose(np.asarray(logits), ref.logits(np.asarray(ids)), atol=ATOL)
    assert float(index_scores(jnp.ones((1, 2, 4, 8)), -jnp.ones((1, 2, 4)), -jnp.ones((1, 3, 8)))[0, 0, 0]) == 0.0


def test_topk_at_or_past_the_context_is_dense_gqa(tiny):
    cfg, model, params, ids, _ = tiny
    dense = KeyeVL2ForCausalLM(dataclasses.replace(cfg, index_topk=96), attention_impl="xla")
    wide = KeyeVL2ForCausalLM(dataclasses.replace(cfg, index_topk=4096), attention_impl="xla")
    a, _ = forward(dense, params, ids)
    b, _ = forward(wide, params, ids)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # and it is the reference with its selection turned off
    ref = Reference(published_keys(dense.config), params)
    np.testing.assert_allclose(np.asarray(a), ref.logits(np.asarray(ids)), atol=ATOL)
    sparse, _ = forward(model, params, ids)
    assert float(jnp.abs(sparse - a)[:, cfg.index_topk:].max()) > 1e-3      # selection changes the result
    np.testing.assert_allclose(np.asarray(sparse[:, :cfg.index_topk]), np.asarray(a[:, :cfg.index_topk]), atol=ATOL)


def test_unequal_mrope_streams_match_the_reference(tiny):
    cfg, model, params, ids, ref = tiny
    t = jnp.arange(96)
    pos = jnp.stack([jnp.broadcast_to(p[None], (2, 96)) for p in (t, t // 4, t % 7)])
    logits, _ = forward(model, params, ids, positions=pos)
    want = ref.logits(np.asarray(ids), np.asarray(pos))
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)
    plain, _ = forward(model, params, ids)
    assert float(jnp.abs(plain - logits).max()) > 1e-2
    # sections [2, 3, 3]: pair 0-1 temporal, 2-4 height, 5-7 width
    ang = np.asarray(mrope_angles(pos, 16, 1e7, (2, 3, 3)))
    inv = 1.0 / (1e7 ** (np.arange(0, 16, 2) / 16))
    np.testing.assert_allclose(ang[0, 9], np.r_[9 * inv[:2], 2 * inv[2:5], 2 * inv[5:]], rtol=1e-6)


def test_left_padded_prefill_equals_the_unpadded_one(tiny):
    cfg, model, params, ids, _ = tiny
    want, _ = prefill_logits(model, params, ids[:1, :40])
    padded = jnp.concatenate([jnp.zeros((1, 8), ids.dtype), ids[:1, :40]], axis=1)
    mask = jnp.arange(48)[None] >= 8
    got, _ = prefill_logits(model, params, padded, padding_mask=mask)
    np.testing.assert_allclose(np.asarray(got[:, 8:]), np.asarray(want), atol=ATOL)


def test_cache_holds_k_and_v_joined_and_one_index_key_a_token(tiny):
    """``kv``: a token's K heads, then its V heads, in ONE leaf (what the
    sparse decode kernel fetches with one copy); ``k_idx``: the index key."""
    cfg, model, params, ids, _ = tiny
    _, cache = through_the_cache(model.clone(mode="prefill"), params, ids[:, :16])
    for i in range(cfg.num_layers):
        leaves = cache["model"][f"layers_{i}"]["attn"]
        assert set(leaves) == {"kv", "k_idx", "index", "kv_valid"}
        assert leaves["kv"].shape == (2, cfg.max_seq_len, 2 * 2, 16)
        assert leaves["k_idx"].shape == (2, cfg.max_seq_len, 1, 8)
        k, v = split_kv(leaves["kv"])
        assert k.shape == v.shape == (2, cfg.max_seq_len, 2, 16)
        assert float(jnp.abs(k[:, :16]).min()) > 0 and float(jnp.abs(v[:, :16]).min()) > 0 and not k[:, 16:].any()
    assert PAGED_LEAVES[:3] == ("k", "v", "k_pe") and {"kv", "k_idx"} <= set(PAGED_LEAVES)


def test_masked_flash_prefill_is_the_einsum_prefill(tiny):
    """The byte-masked flash kernel (interpreted) under the same learned mask."""
    cfg, model, params, ids, _ = tiny
    want, _ = prefill_logits(model, params, ids[:, :64])
    got, _ = prefill_logits(model.clone(attention_impl="flash"), params, ids[:, :64])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_loss_and_gradients_are_finite(tiny):
    cfg, model, params, ids, _ = tiny
    loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, ids[:, :-1], ids[:, 1:])))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["params"]["model"]["layers_1"]["attn"]["qkv"]["q_proj"]["kernel"]).max()) > 0
