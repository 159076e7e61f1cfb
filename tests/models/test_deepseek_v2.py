"""DeepSeek-V2 (multi-head latent attention, shared + routed experts) at a
tiny size with EVERY mechanism present: a dense layer and two sparse ones, 8
experts top-3 + 2 shared, latent 32, rope 8, nope 16, v 16 (so q.k and v
differ in size), YaRN on with positions past the original ones."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.models.deepseek_v2 import (
    DeepseekV2ForCausalLM,
    DeepseekV2Model,
    YarnScaling,
    deepseek_v2_lite,
    tiny_deepseek_v2,
    yarn_frequencies,
)
from tests.models.jitted import every_position, forward, through_the_cache


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_deepseek_v2()
    model = DeepseekV2ForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 72), 1, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    return cfg, model, params, ids


def prefill_logits(model, params, ids, **kw):
    """Logits at EVERY position of a prefill: ``DeepseekV2ForCausalLM`` applies
    its head to the last position alone."""
    backbone = DeepseekV2Model(model.config, model.attention_impl, mode="prefill")
    return every_position(backbone, params, ids, **kw)[0]


def test_the_tiny_preset_holds_every_mechanism():
    cfg = tiny_deepseek_v2()
    assert cfg.first_k_dense == 1 and cfg.num_layers == 3
    assert cfg.n_shared_experts == 2 and cfg.top_k == 3 and cfg.num_experts == 8
    assert cfg.qk_head_dim == 24 != cfg.v_head_dim
    assert cfg.max_seq_len > cfg.rope_scaling.original_max_position_embeddings


def test_param_tree_has_the_published_parts(tiny):
    _, _, params, _ = tiny
    layers = meta.unbox(params)["params"]["model"]
    assert set(layers["layers_0"]) == {"attn", "input_norm", "post_attn_norm", "mlp"}
    assert set(layers["layers_1"]["moe"]) == {"router", "experts", "shared"}
    attn = layers["layers_1"]["attn"]
    assert attn["kv_a_proj"]["kernel"].shape == (64, 32 + 8)
    assert attn["kv_b_proj"].shape == (32, 4, 16 + 16)
    assert attn["q_proj"]["kernel"].shape == (64, 4 * 24)
    assert attn["o_proj"]["kernel"].shape == (4 * 16, 64)
    # shared experts: ONE gated MLP of n_shared * moe_intermediate
    assert layers["layers_1"]["moe"]["shared"]["up"]["kernel"].shape == (64, 96)


def test_published_widths_count_15_7b_parameters():
    """``deepseek_v2_lite()`` is the published model: 15.7 B parameters."""
    model = DeepseekV2ForCausalLM(deepseek_v2_lite(), attention_impl="xla")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    n = sum(np.prod(a.shape) for a in jax.tree.leaves(meta.unbox(shapes)))
    assert 15.6e9 < n < 15.8e9


def test_softmax_scale_and_yarn_numbers_of_v2_lite():
    cfg = deepseek_v2_lite()
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    angles, ratio = yarn_frequencies(64, 8, 10000.0, cfg.rope_scaling)
    assert ratio == 1.0 and angles.shape == (8, 32)
    inv = np.asarray(angles[1])
    plain = 1.0 / (10000.0 ** (np.arange(0, 64, 2) / 64))
    # low = floor(dim(32)) = 10, high = ceil(dim(1)) = 23: fast channels keep
    # their frequency, slow ones are divided by the factor, a ramp between
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-6)
    assert ((inv[11:23] < plain[11:23]) & (inv[11:23] > plain[11:23] / 40.0)).all()


def test_yarn_off_is_plain_rope():
    angles, ratio = yarn_frequencies(8, 16, 10000.0, None)
    plain = 1.0 / (10000.0 ** (np.arange(0, 8, 2) / 8))
    assert ratio == 1.0
    np.testing.assert_allclose(np.asarray(angles[3]), 3 * plain, rtol=1e-6)


def test_absorbed_decode_matches_the_materialised_forward(tiny):
    """Prefill writes the latent cache; every decode step runs the ABSORBED
    form against it and must give the full (materialised, cache-free)
    forward's logits, past the original rope positions too."""
    cfg, model, params, ids = tiny
    full, _ = forward(model, params, ids)
    prefill, decode = model.clone(mode="prefill"), model.clone(mode="decode")
    (logits, _), cache = through_the_cache(prefill, params, ids[:, :40])
    np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, 39]), atol=2e-5)
    # every position of the prefill: the headless model's hidden states through the head
    np.testing.assert_allclose(np.asarray(prefill_logits(model, params, ids[:, :40])), np.asarray(full[:, :40]), atol=2e-5)
    for t in range(40, 72):   # crosses original_max_position_embeddings = 32 .. 72
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, ids[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]), atol=2e-5)


def test_cache_holds_one_latent_row_and_one_rope_key_a_token(tiny):
    cfg, model, params, ids = tiny
    _, cache = through_the_cache(model.clone(mode="prefill"), params, ids[:, :16])
    for i in range(cfg.num_layers):
        leaves = cache["model"][f"layers_{i}"]["attn"]
        assert set(leaves) == {"k", "k_pe", "index", "kv_valid"}      # no per-head K or V
        assert leaves["k"].shape == (2, cfg.max_seq_len, 1, cfg.kv_lora_rank)
        assert leaves["k_pe"].shape == (2, cfg.max_seq_len, 1, cfg.qk_rope_head_dim)


def test_left_padded_prefill_equals_the_unpadded_one(tiny):
    cfg, model, params, ids = tiny
    want = prefill_logits(model, params, ids[:1, :24])
    padded = jnp.concatenate([jnp.zeros((1, 8), ids.dtype), ids[:1, :24]], axis=1)
    mask = jnp.arange(32)[None] >= 8
    got = prefill_logits(model, params, padded, padding_mask=mask)
    np.testing.assert_allclose(np.asarray(got[:, 8:]), np.asarray(want), atol=2e-5)


def test_routing_weights_are_not_renormalised_and_shared_experts_add(tiny):
    """Zero the shared experts' output projection: the logits must change
    (the branch is live); renormalising the top-k weights must change them
    too (V2's ``norm_topk_prob`` is false)."""
    cfg, model, params, ids = tiny
    base, _ = forward(model, params, ids[:, :16])
    p = meta.unbox(params)
    no_shared = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if "shared" in str(path) and "down" in str(path) else a, p)
    out, _ = forward(model, no_shared, ids[:, :16])
    assert float(jnp.abs(out - base).max()) > 1e-3
    renorm = DeepseekV2ForCausalLM(dataclasses.replace(cfg, norm_topk_prob=True), attention_impl="xla")
    out, _ = forward(renorm, params, ids[:, :16])
    assert float(jnp.abs(out - base).max()) > 1e-3


def test_flash_prefill_runs_with_a_value_head_smaller_than_the_keys(tiny):
    """The materialised form through the flash kernel (interpreted): q/k of
    24 channels, v of 16."""
    cfg, model, params, ids = tiny
    want, _ = forward(model, params, ids[:, :64])
    got, _ = forward(model.clone(attention_impl="flash"), params, ids[:, :64])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_loss_and_gradients_are_finite(tiny):
    cfg, model, params, ids = tiny
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, ids[:, :-1], ids[:, 1:])))(meta.unbox(params))
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["params"]["model"]["layers_1"]["attn"]["kv_b_proj"]).max()) > 0
