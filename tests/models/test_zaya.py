"""ZAYA1's language model (``zaya``) at a tiny size with every mechanism
present (the two convolutions and the value shift one token back, the keys'
temperature, partial rotary, the router's MLP over a state passed through
three layers, top-1 under a drawn selection bias, the scaled residual merges,
the tied head) against the plain reference: the full forward, prefill then
decode through the row cache AND the per-slot state in LOGITS, unequal prompts
in one batch, and each of the reference's controls seen to fail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference.generate import serving_clones
from neuronx_distributed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM, tiny_zaya, zaya1_8b
from neuronx_distributed_tpu.modules.attention import SLOT_STATE_LEAVES, slot_state_bytes_per_layer

from perfbench import cca_costs
from perfbench.references.zaya import Reference
from tests.models.jitted import forward, through_the_cache

ATOL = 3e-5
STATE = SLOT_STATE_LEAVES[0]


def published_keys(cfg):
    return {
        "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "cca_time0": cfg.cca_time0, "cca_time1": cfg.cca_time1,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_parameters": {"hybrid": {"rope_theta": cfg.rope_theta, "partial_rotary_factor": cfg.partial_rotary_factor}},
        "num_experts": cfg.num_experts, "num_experts_per_tok": cfg.top_k,
        "moe_intermediate_size": cfg.moe_intermediate_size, "router_hidden_size": cfg.router_hidden_size,
        "rms_norm_eps": cfg.rms_eps, "vocab_size": cfg.vocab_size, "tie_word_embeddings": True,
    }


def weights(model, seed=0):
    """Seeded weights with every vector (the norms' and the merges' scales and
    biases, the convolutions' and the router's biases, ``gamma``, the
    selection bias) moved off its initial value, so that each matters; the
    temperatures stay where the config puts them."""
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def moved(path, leaf, k):
        name = path[-1].key
        vector = leaf.ndim == 1 or name == "conv1_bias"
        return leaf + 0.2 * jax.random.normal(k, leaf.shape) if vector and name != "temperature" else leaf

    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), [moved(p, leaf, k) for (p, leaf), k in zip(flat, keys)])


@pytest.fixture(scope="module")
def system():
    cfg = tiny_zaya()
    model = ZayaForCausalLM(cfg, attention_impl="xla")
    params = weights(model)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (2, 72), 0, cfg.vocab_size))
    return cfg, model, params, ids, Reference(published_keys(cfg), params).logits(ids)


def test_the_full_forward_is_the_references(system):
    cfg, model, params, ids, want = system
    got, _ = forward(model, params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)


def test_prefill_then_decode_through_the_state_is_the_references(system):
    """A prompt of 20 tokens, then 52 decode steps through the row cache: each
    step's convolutions and second value head read the slot's state, never a
    cache column, and leave the next step's."""
    cfg, model, params, ids, want = system
    prefill, decode = serving_clones(model)
    (logits, _), cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :20]))
    assert logits.shape[1] == 1        # the head on the LAST position alone
    np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, 19], atol=ATOL)
    node = cache["model"]["layers_1"]["attn"]
    assert set(node) == {"kv", "kv_valid", "index", STATE}
    assert node["kv"].shape == (2, cfg.max_seq_len, 2 * cfg.num_kv_heads, cfg.head_dim)
    assert node[STATE].shape == (2, cfg.slot_state_width)      # a slot axis and no length axis
    for t in range(20, 72):
        before = cache["model"]["layers_0"]["attn"][STATE]
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(ids[:, t:t + 1]))
        np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, t], atol=ATOL)
        assert not np.array_equal(before, cache["model"]["layers_0"]["attn"][STATE])


def test_unequal_prompts_in_one_batch_shift_by_tokens_not_columns(system):
    """Left-padded prompts of 60 and 41 tokens in a bucket of 64: a row's first
    token reads zero history (not the padding column before it), its state is
    its LAST token's, and the decode steps after it go on from there."""
    cfg, model, params, ids, want = system
    prefill, decode = serving_clones(model)
    lens = (60, 41)
    padded = np.full((2, 64), 3, np.int32)             # padding ids that WOULD leave a trace
    mask = np.zeros((2, 64), bool)
    for r, n in enumerate(lens):
        padded[r, 64 - n:], mask[r, 64 - n:] = ids[r, :n], True
    (logits, _), cache = through_the_cache(prefill, params, jnp.asarray(padded), padding_mask=jnp.asarray(mask))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits)[r, 0], want[r, n - 1], atol=ATOL)
    for t in range(8):
        tok = np.stack([ids[r, n + t] for r, n in enumerate(lens)])[:, None]
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(tok))
        for r, n in enumerate(lens):
            np.testing.assert_allclose(np.asarray(logits)[r, 0], want[r, n + t], atol=ATOL)


def test_a_filler_token_leaves_the_rows_state_as_it_was(system):
    """A finished row's step passes ``padding_mask`` False: nothing of it
    becomes attendable and the row's state stays."""
    cfg, model, params, ids, _ = system
    prefill, decode = serving_clones(model)
    _, cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :12]))
    mask = jnp.asarray([[True], [False]])
    _, after = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(ids[:, 12:13]), padding_mask=mask)
    for i in range(cfg.num_layers):
        was, now = (c["model"][f"layers_{i}"]["attn"][STATE] for c in (cache, after))
        assert not np.array_equal(was[0], now[0]) and np.array_equal(was[1], now[1])


@pytest.mark.parametrize("control", [
    {"conv": "none"}, {"value_shift": False}, {"eda": False}, {"residual_scaling": False},
    {"bias_in_weights": True}, {"dtype": jnp.float8_e4m3fn}, {"kv_dtype": jnp.float8_e4m3fn},
], ids=lambda c: next(iter(c)))
def test_each_control_moves_the_logits_past_the_tolerance(system, control):
    cfg, model, params, ids, want = system
    got = Reference(published_keys(cfg), params, **control).logits(ids)
    assert np.abs(got - want).max() > 100 * ATOL


def test_the_published_widths_hold_a_kib_a_token_and_five_and_a_quarter_a_slot():
    """ZAYA1-8B as published, in bf16: the joined leaf a token, the state a
    slot, the parameter count of a layer (the configuration's arithmetic)."""
    cfg = zaya1_8b(num_layers=2, param_dtype=jnp.bfloat16)
    model = ZayaForCausalLM(cfg, attention_impl="xla")
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    cache = jax.eval_shape(
        lambda p, i: model.clone(mode="prefill").apply(p, i, mutable=["cache"])[1]["cache"], shapes, ids)
    node = cache["model"]["layers_0"]["attn"]
    assert node["kv"].shape[-2:] == (4, 128) and node[STATE].shape == (1, 2688)
    widths = dict(num_q_heads=8, num_kv_heads=2, head_dim=128)
    assert slot_state_bytes_per_layer(cache) == cca_costs.slot_state_bytes(**widths) == 5376
    layer = meta.unbox(shapes)["params"]["model"]["layers_0"]
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))    # noqa: E731
    assert count(layer["moe"]["experts"]) == 16 * 3 * 2048 * 2048
    # 5.24 M of projections; the convolutions' 0.33 M (two taps a channel, two biases, a 128 x 128 matrix a head and tap); tau
    assert count(layer["attn"]) == 2048 * (1024 + 256 + 256) + 1024 * 2048 + 4 * 1280 + 2 * 10 * 128 * 128 + 2
    # the router's 0.66 M: down-projection and bias, the norm, two biased matrices of 256, the last, the selection bias
    router = 2048 * 256 + 256 + 256 + 2 * (256 * 256 + 256) + 256 * 16 + 16
    assert count(layer["moe"]["router"]) == router                  # layer 0: no state comes in, no gamma
    assert count(meta.unbox(shapes)["params"]["model"]["layers_1"]["moe"]["router"]) == router + 256


def test_what_the_model_is_not_written_for_is_refused():
    with pytest.raises(ValueError, match="2 kv heads"):
        ZayaConfig(num_kv_heads=4)
    with pytest.raises(ValueError, match="cca_time0"):
        ZayaConfig(cca_time0=4)
    with pytest.raises(ValueError, match="one expert"):
        ZayaConfig(top_k=2)


def test_the_expert_branch_scale_starts_where_the_config_says_and_the_reference_follows():
    """``moe_branch_scale_init`` starts the expert sublayer's ``s_f`` alone
    (the attention's stays 1), and the reference reads it from the weights:
    the same logits, and ``residual_scaling=False`` now differs without any
    vector moved."""
    cfg = tiny_zaya(moe_branch_scale_init=0.3)
    model = ZayaForCausalLM(cfg, attention_impl="xla")
    ids = jnp.asarray(np.random.default_rng(3).integers(1, cfg.vocab_size, size=(2, 24)), jnp.int32)
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(1), ids))
    layer = params["params"]["model"]["layers_1"]
    assert np.all(np.asarray(layer["moe_merge"]["branch_scale"]) == np.float32(0.3))
    assert np.all(np.asarray(layer["attn_merge"]["branch_scale"]) == 1.0)
    want = Reference(published_keys(cfg), params).logits(np.asarray(ids))
    np.testing.assert_allclose(np.asarray(forward(model, params, ids)[0]), want, atol=ATOL)
    plain = Reference(published_keys(cfg), params, residual_scaling=False).logits(np.asarray(ids))
    assert np.abs(plain - want).max() > 100 * ATOL
