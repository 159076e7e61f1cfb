"""Trinity's language model (``afmoe``) at a tiny size with every mechanism
present (a dense layer, then window, window, full: a window of 32; gated GQA
with head norms, rotary on the window layers only, four norms a block,
sigmoid routing under a drawn selection bias, a shared expert) against the
plain reference: the full forward, prefill then decode through the row cache
in LOGITS, unequal prompts in one batch, the same with a share of the experts
held, and each of the reference's controls seen to fail."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference.generate import serving_clones
from neuronx_distributed_tpu.models.afmoe import (
    FULL,
    SLIDING,
    AfmoeConfig,
    AfmoeForCausalLM,
    tiny_afmoe,
    trinity_large,
)
from neuronx_distributed_tpu.modules.attention import WINDOW_LEAF, cache_windows

from perfbench.references.afmoe import Reference
from tests.models.jitted import forward, through_the_cache

ATOL = 3e-5


def published_keys(cfg):
    first, held = cfg.held_experts or (0, cfg.num_experts)
    return {
        "num_hidden_layers": cfg.num_layers, "num_dense_layers": cfg.num_dense_layers,
        "layer_types": list(cfg.layer_types), "sliding_window": cfg.sliding_window,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "hidden_size": cfg.hidden_size,
        "num_experts_per_tok": cfg.top_k, "num_experts": held,
        "num_experts_published": cfg.num_experts, "first_held_expert": first,
        "route_norm": cfg.route_norm, "route_scale": cfg.route_scale,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
        "mup_enabled": cfg.mup_enabled, "vocab_size": cfg.vocab_size,
    }


def weights(model, seed=0):
    """Seeded weights with every vector (the norms' scales, the selection
    bias) moved off its initial value, so that each matters."""
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.3 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1 else leaf
        for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module", params=[None, (4, 4)], ids=["all_experts", "share_4_of_16"])
def system(request):
    cfg = tiny_afmoe(held_experts=request.param)
    model = AfmoeForCausalLM(cfg, attention_impl="xla")
    params = weights(model)
    if request.param is not None:   # the share's experts alone are stored
        first, held = request.param
        for i in range(cfg.num_dense_layers, cfg.num_layers):
            ex = params["params"]["model"][f"layers_{i}"]["moe"]["experts"]
            assert all(v.shape[0] == held for v in ex.values())
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 100), 0, cfg.vocab_size)
    ref = Reference(published_keys(cfg), params)
    return cfg, model, params, np.asarray(ids), ref.logits(np.asarray(ids))


def test_the_full_forward_is_the_references(system):
    cfg, model, params, ids, want = system
    got, _ = forward(model, params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)


def test_prefill_then_decode_past_three_windows_is_the_references(system):
    """A prompt of 20 tokens, then 80 decode steps through the row cache: the
    window (32) passes three times, the full layer reads everything."""
    cfg, model, params, ids, want = system
    prefill, decode = serving_clones(model)
    (logits, _), cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :20]))
    assert logits.shape[1] == 1        # the head on the LAST position alone
    np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, 19], atol=ATOL)
    assert cache_windows(cache) == {
        ("model", f"layers_{i}", "attn"): 32 for i in range(3)}     # the full layer's node has none
    assert cache["model"]["layers_0"]["attn"][WINDOW_LEAF].size == 0
    for t in range(20, 100):
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(ids[:, t:t + 1]))
        np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, t], atol=ATOL)


def test_unequal_prompts_in_one_batch_count_the_window_in_tokens(system):
    """Left-padded prompts of 60 and 41 tokens in a bucket of 64: rotary and
    the window follow each row's own tokens, in prefill and in the decode
    steps after it."""
    cfg, model, params, ids, want = system
    prefill, decode = serving_clones(model)
    lens = (60, 41)
    padded = np.zeros((2, 64), np.int32)
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


@pytest.mark.parametrize("control", [
    {"window": "none"}, {"window": 16}, {"rope_full": True}, {"gate": False},
    {"bias_in_weights": True}, {"dtype": jnp.float8_e4m3fn}, {"kv_dtype": jnp.float8_e4m3fn},
], ids=lambda c: next(iter(c)) + "=" + str(next(iter(c.values()))).split(".")[-1].strip("'>"))
def test_each_control_of_the_reference_moves_the_logits(system, control):
    """A comparison against the reference can fail for each mechanism: the
    window's width (or none), rotary on the full layer, the gate, the bias in
    the weights, a lower precision."""
    cfg, model, params, ids, want = system
    got = Reference(published_keys(cfg), params, **control).logits(ids)
    assert np.abs(got - want).max() > 100 * ATOL


def test_the_config_names_each_layers_kind():
    cfg = trinity_large()
    assert cfg.layer_types.count(FULL) == 15 and cfg.layer_types.count(SLIDING) == 45
    assert [cfg.layer_window(i) for i in (0, 2, 3, 59)] == [4096, 4096, None, None]
    assert cfg.kv_cache_window == 4096 and cfg.kv_cache_kind == "joined"
    assert dataclasses.replace(cfg, num_layers=2, layer_types=(FULL, FULL)).kv_cache_window is None
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig(num_layers=3, layer_types=(SLIDING, FULL))
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig(num_layers=1, layer_types=("chunked",))


@pytest.mark.parametrize("qk, post", [(1.0, 1.0), (1.5, 4.0)], ids=["ones", "as_the_benchmark_draws_them"])
def test_the_norm_gains_start_where_the_config_says(qk, post):
    """The head norms' and the post-attention norm's gains are initial VALUES
    (a trained checkpoint carries its own): every other norm starts at one."""
    cfg = tiny_afmoe(qk_norm_init=qk, post_attn_norm_init=post)
    params = meta.unbox(jax.jit(AfmoeForCausalLM(cfg, attention_impl="xla").init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]["model"]
    for i in range(cfg.num_layers):
        layer = params[f"layers_{i}"]
        want = {"q_norm": qk, "k_norm": qk, "post_attn_norm": post, "input_norm": 1.0, "pre_mlp_norm": 1.0,
                "post_mlp_norm": 1.0}
        got = {**{n: layer["attn"][n]["weight"] for n in ("q_norm", "k_norm")},
               **{n: layer[n]["weight"] for n in ("post_attn_norm", "input_norm", "pre_mlp_norm", "post_mlp_norm")}}
        for name, value in want.items():
            np.testing.assert_array_equal(np.asarray(got[name]), np.full(got[name].shape, value, np.float32), name)
    np.testing.assert_array_equal(np.asarray(params["final_norm"]["weight"]), 1.0)


def test_a_share_names_its_chunk_stats():
    assert AfmoeForCausalLM(tiny_afmoe()).chunk_stats == ()
    assert AfmoeForCausalLM(tiny_afmoe(held_experts=(0, 4))).chunk_stats == ("held_rows", "routed_rows")
