"""Ouro's looped language model at a tiny size (2 layers x 3 passes: passes !=
layers, so a walker that confuses the two fails) against the plain reference:
the full forward and the passes' exit distribution, prefill then decode through
the row cache in LOGITS, unequal prompts in one batch; parameters counted once
a layer and cache nodes once a layer a pass; each of the reference's controls
seen to fail; what is not served refused by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference.generate import serving_clones
from neuronx_distributed_tpu.models import OuroConfig, OuroForCausalLM, OuroModel, ouro_2_6b, tiny_ouro
from neuronx_distributed_tpu.models.ouro import exit_distribution
from neuronx_distributed_tpu.modules.attention import LOOP_PASS_NODE, cache_token_bytes

from perfbench.references.ouro import Reference
from tests.models.jitted import forward, through_the_cache

ATOL = 3e-5


def published_keys(cfg):
    return {
        "num_hidden_layers": cfg.num_layers, "layer_types": ["full_attention"] * cfg.num_layers,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
        "hidden_act": "silu", "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta, "rope_scaling": None,
        "total_ut_steps": cfg.total_ut_steps, "early_exit_threshold": cfg.early_exit_threshold,
        "tie_word_embeddings": False, "use_sliding_window": False, "sliding_window": None,
        "vocab_size": cfg.vocab_size,
    }


def weights(model, seed=0):
    """Seeded weights with every vector (the norms' gains, the gate's bias)
    moved off its initial value, so that each matters."""
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.3 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1 else leaf
        for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def system():
    cfg = tiny_ouro()
    model = OuroForCausalLM(cfg, attention_impl="xla")
    params = weights(model)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (2, 60), 0, cfg.vocab_size))
    ref = Reference(published_keys(cfg), params)
    return cfg, model, params, ids, ref, ref.logits(ids)


def test_the_full_forward_and_the_exit_distribution_are_the_references(system):
    cfg, model, params, ids, ref, want = system
    assert (cfg.num_layers, cfg.total_ut_steps) == (2, 3)
    logits, aux = forward(model, params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)
    p = np.asarray(aux["exit_distribution"])
    assert p.shape == (2, 60, 3) and p.dtype == np.float32
    np.testing.assert_allclose(p, ref.exit_distribution(ids), atol=1e-6)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    assert (p > 1e-4).all() and p.max() < 0.99       # every pass takes a share: the gate is at work
    # lambda = (0.5, 0.5, anything): half exit after pass 1, a quarter after 2, the rest after 3
    np.testing.assert_allclose(np.asarray(exit_distribution(jnp.array([0.0, 0.0, 9.0]))), [0.5, 0.25, 0.25], atol=1e-7)


def test_parameters_exist_once_a_layer_and_cache_nodes_once_a_layer_a_pass(system):
    cfg, model, params, ids, _, _ = system
    layers = [k for k in params["params"]["model"] if k.startswith("layers_")]
    assert sorted(layers) == ["layers_0", "layers_1"]
    assert not any(LOOP_PASS_NODE in jax.tree_util.keystr(path)     # no parameter belongs to a pass
                   for path, _ in jax.tree_util.tree_flatten_with_path(params)[0])
    # the published widths, abstractly: 48 layers of 51.4 M + embedding, head, final norm and the gate
    big = OuroForCausalLM(ouro_2_6b(), attention_impl="xla")
    shapes = jax.eval_shape(big.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 8), jnp.int32))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(meta.unbox(shapes)))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert count == 48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    prefill, _ = serving_clones(model)
    _, cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :20]))
    nodes = {(layer, node) for layer in cache["model"] for node in cache["model"][layer]["attn"]}
    assert nodes == {(f"layers_{i}", f"{LOOP_PASS_NODE}{t}") for i in range(2) for t in range(3)}
    assert cfg.kv_cache_nodes == 6
    assert cache_token_bytes(cache) == (6 * 2 * 4 * 16 * 4, 6)      # K and V of 4 heads of 16, float32, 6 nodes
    assert set(cache["model"]["layers_0"]["attn"]["pass_0"]) == {"kv", "index", "kv_valid"}   # no window, no state


def test_the_passes_nodes_differ_after_a_prefill(system):
    """Each pass writes the keys and values IT computed: no two nodes of a
    layer hold the same, and pass ``t``'s are what the reference's pass ``t``
    attends."""
    cfg, model, params, ids, _, _ = system
    prefill, _ = serving_clones(model)
    _, cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :20]))
    for i in range(cfg.num_layers):
        node = cache["model"][f"layers_{i}"]["attn"]
        held = [np.asarray(node[f"{LOOP_PASS_NODE}{t}"]["kv"][:, :20]) for t in range(cfg.total_ut_steps)]
        for a in range(len(held)):
            assert np.abs(held[a]).max() > 0.1
            for b in range(a + 1, len(held)):
                assert np.abs(held[a] - held[b]).max() > 0.05, (i, a, b)


def test_prefill_then_decode_through_the_cache_is_the_references(system):
    cfg, model, params, ids, _, want = system
    prefill, decode = serving_clones(model)
    logits, cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :20]))
    assert logits.shape[1] == 1        # the head on the LAST position alone
    np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, 19], atol=ATOL)
    for t in range(20, 60):
        logits, cache = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(ids[:, t:t + 1]))
        np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, t], atol=ATOL)


def test_unequal_prompts_in_one_batch_keep_their_own_positions(system):
    cfg, model, params, ids, _, want = system
    prefill, decode = serving_clones(model)
    lens = (30, 17)
    padded, mask = np.zeros((2, 32), np.int32), np.zeros((2, 32), bool)
    for r, n in enumerate(lens):
        padded[r, 32 - n:], mask[r, 32 - n:] = ids[r, :n], True
    logits, cache = through_the_cache(prefill, params, jnp.asarray(padded), padding_mask=jnp.asarray(mask))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits)[r, 0], want[r, n - 1], atol=ATOL)
    for t in range(6):
        tok = np.stack([ids[r, n + t] for r, n in enumerate(lens)])[:, None]
        logits, cache = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(tok))
        for r, n in enumerate(lens):
            np.testing.assert_allclose(np.asarray(logits)[r, 0], want[r, n + t], atol=ATOL)


def test_the_backbone_gives_every_pass_in_train_and_the_last_when_served(system):
    cfg, model, params, ids, ref, _ = system
    backbone = {"params": params["params"]["model"]}
    every = forward(OuroModel(cfg, "xla", "train"), backbone, jnp.asarray(ids))
    assert every.shape == (3, 2, 60, cfg.hidden_size)
    for got, h in zip(np.asarray(every), ref.pass_outputs(ids)):
        np.testing.assert_allclose(got, np.asarray(h), atol=ATOL)
    last, _ = through_the_cache(OuroModel(cfg, "xla", "prefill"), backbone, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(last), np.asarray(every[-1]), atol=ATOL)


@pytest.mark.parametrize("control", [
    {"shared_cache": True}, {"passes": 2}, {"passes": 4},
    {"dtype": jnp.float8_e4m3fn}, {"kv_dtype": jnp.float8_e4m3fn},
], ids=lambda c: "-".join(f"{k}={getattr(v, '__name__', v)}" for k, v in c.items()))
def test_each_control_of_the_reference_is_seen_to_fail(system, control):
    cfg, _, params, ids, _, want = system
    other = Reference(published_keys(cfg), params, **control).logits(ids)
    assert np.isfinite(other).all() and np.abs(other - want).max() > 100 * ATOL


def test_what_is_not_served_is_refused_by_name():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        OuroConfig(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="total_ut_steps"):
        tiny_ouro(total_ut_steps=0)
    with pytest.raises(NotImplementedError, match="packed documents"):
        model = OuroForCausalLM(tiny_ouro(), attention_impl="xla")
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), segment_ids=jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="early_exit_threshold"):
        Reference({**published_keys(tiny_ouro()), "early_exit_threshold": 0.5}, {"params": {}})


def test_served_weights_are_drawn_in_float32_and_rounded():
    """``param_dtype=bfloat16`` rounds the float32 draw: the same values, and
    a mean no bf16 draw's bias has moved."""
    wide = OuroForCausalLM(tiny_ouro(), attention_impl="xla")
    served = OuroForCausalLM(tiny_ouro(param_dtype=jnp.bfloat16), attention_impl="xla")
    ids = jnp.zeros((1, 8), jnp.int32)
    a = meta.unbox(jax.jit(wide.init)(jax.random.PRNGKey(5), ids))["params"]["lm_head"]["kernel"]
    b = meta.unbox(jax.jit(served.init)(jax.random.PRNGKey(5), ids))["params"]["lm_head"]["kernel"]
    assert b.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(a.astype(jnp.bfloat16), np.float32), np.asarray(b, np.float32))
