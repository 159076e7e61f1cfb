"""GLM-5's language model at a tiny size with every mechanism present (a
query latent, MLA, an indexer fed from the query latent keeping 16 columns,
sigmoid routing under a drawn selection bias, a shared expert, a dense layer
first) against the plain reference: the full forward, prefill then decode
through the cache in LOGITS, the selected sets, unequal prompts in one batch,
and the same with only a share of the experts held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.models.glm_moe_dsa import (
    GlmMoeDsaForCausalLM,
    GlmMoeDsaModel,
    glm5,
    tiny_glm_moe_dsa,
)
from neuronx_distributed_tpu.modules.attention import (
    PAGED_LEAVES,
    latent_leaf_shape,
    split_latent,
)

from perfbench.references.glm_moe_dsa import Reference
from tests.models.jitted import every_position, forward, through_the_cache

ATOL = 3e-5


def published_keys(cfg):
    first, held = cfg.held_experts or (0, cfg.num_experts)
    return {
        "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.num_heads, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "index_n_heads": cfg.index_n_heads, "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk, "num_experts_per_tok": cfg.top_k,
        "n_routed_experts": held, "n_routed_experts_published": cfg.num_experts,
        "first_held_expert": first, "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor, "rms_norm_eps": cfg.rms_eps,
        "rope_parameters": {"rope_theta": cfg.rope_theta}, "vocab_size": cfg.vocab_size,
    }


def _weights(model, seed=0):
    """Seeded weights with every vector (the norms' scales, the LayerNorm's
    bias, the selection bias) moved off its initial value, so that each
    matters."""
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.3 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1 else leaf
        for leaf, k in zip(leaves, keys)])


def prefill_logits(model, params, ids, **kw):
    """``(logits at EVERY position, cache)`` of a prefill: the served model
    applies its head to the last position alone."""
    backbone = GlmMoeDsaModel(model.config, model.attention_impl, mode="prefill")
    return every_position(backbone, params, ids, **kw)


def _decode_step(decode):
    """One compiled decode step: ``(params, cache, tokens) -> (logits, cache)``."""

    def step(params, cache, tok):
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, tok)
        return logits, cache

    return step


def _share(params, first, count):
    """The parameters a device holding experts ``[first, first + count)`` has."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a: a[first:first + count] if "'experts'" in jax.tree_util.keystr(p) else a, params)


@pytest.fixture(scope="module", params=[None, (4, 8)], ids=["all_experts", "held_4_to_12"])
def tiny(request):
    cfg = tiny_glm_moe_dsa()
    whole = GlmMoeDsaForCausalLM(cfg, attention_impl="xla")
    params = _weights(whole)
    if request.param is not None:
        cfg = dataclasses.replace(cfg, held_experts=request.param)
        params = _share(params, *request.param)
    model = GlmMoeDsaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 1, cfg.vocab_size)
    return cfg, model, params, ids, Reference(published_keys(cfg), params)


@pytest.fixture(scope="module")
def want(tiny):
    """The reference's logits of ``ids``, its full forward, once a share."""
    *_, ids, ref = tiny
    return ref.logits(np.asarray(ids))


def test_the_tiny_preset_holds_every_mechanism():
    cfg = tiny_glm_moe_dsa()
    assert cfg.first_k_dense == 1 < cfg.num_layers and cfg.n_shared_experts == 1
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == cfg.v_head_dim
    assert cfg.index_topk == 16 < 96 and cfg.norm_topk_prob and cfg.router_bias_init_std > 0
    assert cfg.index_head_dim > cfg.qk_rope_head_dim          # rotary on a PART of an index head
    assert cfg.kv_cache_kind == "indexed_latent" and cfg.q_lora_rank != cfg.hidden_size


def test_param_tree_has_the_published_parts(tiny):
    cfg, _, params, _, _ = tiny
    assert set(params["params"]["model"]["layers_0"]) == {"attn", "input_norm", "post_attn_norm", "mlp"}
    layer = params["params"]["model"]["layers_1"]
    assert set(layer["moe"]) == {"router", "experts", "shared"}
    assert set(layer["moe"]["router"]) == {"weight", "e_score_correction_bias"}
    assert layer["moe"]["router"]["weight"].shape == (64, 16)             # the router keeps its width
    held = (cfg.held_experts or (0, 16))[1]
    assert layer["moe"]["experts"]["up_proj"].shape == (held, 64, 48)
    attn = layer["attn"]
    assert set(attn) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj",
                         "idx_q_proj", "idx_k_proj", "idx_k_norm", "idx_w_proj"}
    assert attn["idx_q_proj"]["kernel"].shape == (48, 4 * 16)             # fed from the QUERY latent
    assert attn["idx_k_proj"]["kernel"].shape == (64, 16) and attn["idx_w_proj"]["kernel"].shape == (64, 4)


def test_published_widths_count_the_issues_parameters():
    """One sparse layer at the published widths: attention 165.0 M, indexer
    9.4 M, router 1.6 M, the shared expert and each routed one 37.7 M."""
    model = GlmMoeDsaForCausalLM(glm5(num_layers=4, held_experts=(0, 8)), attention_impl="xla")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sizes = {jax.tree_util.keystr(p): int(np.prod(a.shape))
             for p, a in jax.tree_util.tree_flatten_with_path(meta.unbox(shapes))[0]}
    layer = {k: n for k, n in sizes.items() if "layers_3'" in k}
    attn = sum(n for k, n in layer.items() if "'attn'" in k)
    idx = sum(n for k, n in layer.items() if "idx_" in k)
    assert abs(attn - idx - 165.0e6) < 0.3e6 and abs(idx - 9.4e6) < 0.1e6
    assert sum(n for k, n in layer.items() if "'experts'" in k) == 8 * 3 * 6144 * 2048
    assert sum(n for k, n in layer.items() if "'shared'" in k) == 3 * 6144 * 2048
    assert sum(n for k, n in layer.items() if "'router'" in k) == 6144 * 256 + 256
    assert sum(n for k, n in sizes.items() if "layers_0'" in k and "'mlp'" in k) == 3 * 6144 * 12288


def test_full_forward_matches_the_reference_with_selection_at_work(tiny):
    cfg, model, params, ids, ref = tiny
    logits, _ = forward(model, params, ids)
    want, margin = ref.logits_and_router_margin(np.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)
    assert margin.shape == (2, 96) and (margin >= 0).all()
    # with every expert held each position has a finite margin; with a share
    # only where the 4th or 5th expert is one held here
    assert np.isfinite(margin).all() == (cfg.held_experts is None) and np.isfinite(margin).any()


def test_prefill_then_decode_through_the_cache_matches_the_references_full_forward(tiny, want):
    """Logits, not tokens: the prompt's at every position, then 56 decode
    steps against the cache, each row keeping 16 of up to 96 latents."""
    cfg, model, params, ids, ref = tiny
    prefill, decode = model.clone(mode="prefill"), model.clone(mode="decode")
    logits, _ = prefill_logits(model, params, ids[:, :40])
    np.testing.assert_allclose(np.asarray(logits), want[:, :40], atol=ATOL)
    (last, _), cache = through_the_cache(prefill, params, ids[:, :40])
    assert last.shape == (2, 1, cfg.vocab_size)          # all a caller of a prefill reads
    np.testing.assert_allclose(np.asarray(last[:, 0]), want[:, 39], atol=ATOL)
    step = _decode_step(decode)
    for t in range(40, 96):
        logits, cache = step(params, cache, ids[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, t], atol=ATOL)


def test_a_many_row_decode_step_is_the_suffix_prefill(tiny, want):
    cfg, model, params, ids, ref = tiny
    _, cache = through_the_cache(model.clone(mode="prefill"), params, ids[:, :24])
    (logits, _), _ = through_the_cache(model.clone(mode="decode"), {**params, "cache": cache}, ids[:, 24:96])
    np.testing.assert_allclose(np.asarray(logits), want[:, 24:], atol=ATOL)


def test_unequal_prompts_in_one_batch_decode_as_each_alone(tiny):
    """Two left-padded prompts of 40 and 25 tokens prefilled together, then
    decoded together past ``index_topk``: each row's logits are those of its
    own sequence in the reference."""
    cfg, model, params, ids, ref = tiny
    lens, width = (40, 25), 40
    rows = [np.asarray(ids[i, :n]) for i, n in enumerate(lens)]
    padded = np.stack([np.r_[np.zeros(width - n, rows[i].dtype), rows[i]] for i, n in enumerate(lens)])
    mask = np.stack([np.arange(width) >= width - n for n in lens])
    _, cache = through_the_cache(
        model.clone(mode="prefill"), params, jnp.asarray(padded), padding_mask=jnp.asarray(mask))
    decode = _decode_step(model.clone(mode="decode"))
    wants = [ref.logits(np.asarray(ids[i:i + 1]))[0] for i in range(2)]
    for step in range(30):
        tok = jnp.stack([ids[i, n + step] for i, n in enumerate(lens)])[:, None]
        logits, cache = decode(params, cache, tok)
        for i, n in enumerate(lens):
            np.testing.assert_allclose(np.asarray(logits[i, 0]), wants[i][n + step], atol=ATOL)


def test_the_selected_sets_past_topk_are_sparse_and_causal(tiny):
    cfg, _, _, ids, ref = tiny
    sets = ref.selected(np.asarray(ids))
    assert len(sets) == cfg.num_layers and sets[0].shape == (2, 96, 96)
    for layer in sets:
        assert (layer.sum(-1) == np.minimum(np.arange(96) + 1, cfg.index_topk)).all()
        assert not np.triu(layer[0], 1).any()
    assert (sets[0] != sets[1]).any()                                     # each layer selects for itself


def test_topk_at_or_past_the_context_is_dense_mla(tiny):
    cfg, model, params, ids, _ = tiny
    dense = GlmMoeDsaForCausalLM(dataclasses.replace(cfg, index_topk=96), attention_impl="xla")
    a, _ = forward(dense, params, ids)
    ref = Reference(published_keys(dense.config), params)
    np.testing.assert_allclose(np.asarray(a), ref.logits(np.asarray(ids)), atol=ATOL)
    sparse, _ = forward(model, params, ids)
    assert float(jnp.abs(sparse - a)[:, cfg.index_topk:].max()) > 1e-3      # selection changes the result
    np.testing.assert_allclose(np.asarray(sparse[:, :cfg.index_topk]), np.asarray(a[:, :cfg.index_topk]), atol=ATOL)


@pytest.mark.parametrize("control", ["topk", "bias_in_weights", "latent_dtype"])
def test_each_control_of_the_reference_moves_its_logits(tiny, control):
    """What ``chip_smoke.py --only glm`` and the cell's check hold the system
    to: a reference keeping half the columns, weighing with the selection
    bias, or rounding the latent to float8 is NOT the model."""
    cfg, model, params, ids, ref = tiny
    kw = {"topk": {"topk": cfg.index_topk // 2}, "bias_in_weights": {"bias_in_weights": True},
          "latent_dtype": {"latent_dtype": jnp.float8_e4m3fn}}[control]
    wrong = Reference(published_keys(cfg), params, **kw).logits(np.asarray(ids))
    logits, _ = forward(model, params, ids)
    assert float(np.abs(np.asarray(logits) - wrong).max()) > 100 * ATOL


def test_left_padded_prefill_equals_the_unpadded_one(tiny):
    cfg, model, params, ids, _ = tiny
    want, _ = prefill_logits(model, params, ids[:1, :40])
    padded = jnp.concatenate([jnp.zeros((1, 8), ids.dtype), ids[:1, :40]], axis=1)
    mask = jnp.arange(48)[None] >= 8
    got, _ = prefill_logits(model, params, padded, padding_mask=mask)
    np.testing.assert_allclose(np.asarray(got[:, 8:]), np.asarray(want), atol=ATOL)


def test_cache_holds_the_latent_and_rotated_key_joined_and_one_index_key_a_token(tiny):
    """``kv``: the latent in its first rows, the rotated key at the start of
    the next, ONE leaf (what the sparse latent kernel fetches with one
    copy); ``k_idx``: the index key."""
    cfg, model, params, ids, _ = tiny
    _, cache = through_the_cache(model.clone(mode="prefill"), params, ids[:, :16])
    rows, lanes = latent_leaf_shape(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    assert (rows, lanes) == (2, 32) and latent_leaf_shape(512, 64) == (8, 128)
    for i in range(cfg.num_layers):
        leaves = cache["model"][f"layers_{i}"]["attn"]
        assert set(leaves) == {"kv", "k_idx", "index", "kv_valid"} <= set(PAGED_LEAVES) | {"index", "kv_valid"}
        assert leaves["kv"].shape == (2, cfg.max_seq_len, rows, lanes)
        assert leaves["k_idx"].shape == (2, cfg.max_seq_len, 1, cfg.index_head_dim)
        c, k_pe = split_latent(leaves["kv"], cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        assert c.shape == (2, cfg.max_seq_len, 32) and k_pe.shape == (2, cfg.max_seq_len, 8)
        assert float(jnp.abs(c[:, :16]).min()) > 0 and float(jnp.abs(k_pe[:, :16]).min()) > 0
        assert not c[:, 16:].any() and not leaves["kv"][:, :, 1, 8:].any()     # the row's spare lanes


def test_masked_flash_prefill_is_the_einsum_prefill(tiny):
    """The byte-masked flash kernel (interpreted) under the same learned mask."""
    cfg, model, params, ids, _ = tiny
    want, _ = prefill_logits(model, params, ids[:, :64])
    got, _ = prefill_logits(model.clone(attention_impl="flash"), params, ids[:, :64])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
