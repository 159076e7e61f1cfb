"""Solar Open 2's language model (``solar_open2``) at a tiny size with every
mechanism present (the three convolutions, unit q and k, the per-channel decay
from a low-rank pair, ``beta`` up to 2, the float32 state, the gated head norm;
gated GQA with no positional term; softmax routing with a shared expert)
against the plain reference, whose linear layer is the token-by-token
recurrence: the full forward, prefill then decode THROUGH THE STATE in logits
(the jnp path and both Pallas kernels, interpreted), unequal prompts in one
bucket, each of the reference's controls seen to fail, and the shares'
routed parts summing to the uncut layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference.generate import serving_clones
from neuronx_distributed_tpu.models.solar_open2 import (
    SolarOpen2Config,
    SolarOpen2ForCausalLM,
    solar_open2_250b,
    tiny_solar_open2,
)
from neuronx_distributed_tpu.modules.attention import slot_state_bytes_per_layer

from perfbench import kda_costs
from perfbench.references.solar_open2 import Reference
from tests.models.jitted import forward, through_the_cache

ATOL = 3e-5


def published_keys(cfg):
    held = cfg.held_experts or (0, cfg.num_experts)
    return {
        "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "gqa_layers": list(cfg.gqa_layers), "gqa_interval": cfg.gqa_interval,
        "linear_attn_config": {"short_conv_kernel_size": cfg.conv_kernel, "head_dim": cfg.linear_head_dim,
                               "num_heads": cfg.linear_num_heads, "num_kv_heads": None},
        "n_routed_experts": held[1], "n_routed_experts_published": cfg.num_experts, "first_held_expert": held[0],
        "n_shared_experts": cfg.num_shared_experts, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scaling_factor,
        "moe_intermediate_size": cfg.moe_intermediate_size, "rms_norm_eps": cfg.rms_eps,
        "vocab_size": cfg.vocab_size, "tie_word_embeddings": False, "use_rope": False, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
    }


def weights(model, seed=0):
    """Seeded weights with every vector (the norms' gains, the output gate's
    bias) moved off its initial value, so that each matters; ``A_log`` and
    ``dt_bias`` stay where the config draws them."""
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def moved(path, leaf, k):
        vector = leaf.ndim == 1 and path[-1].key not in ("A_log", "dt_bias")
        return leaf + 0.2 * jax.random.normal(k, leaf.shape) if vector else leaf

    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), [moved(p, leaf, k) for (p, leaf), k in zip(flat, keys)])


@pytest.fixture(scope="module")
def system():
    cfg = tiny_solar_open2()
    model = SolarOpen2ForCausalLM(cfg, attention_impl="xla")
    params = weights(model)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (2, 72), 0, cfg.vocab_size))
    return cfg, model, params, ids, Reference(published_keys(cfg), params).logits(ids)


def test_the_full_forward_is_the_references(system):
    cfg, model, params, ids, want = system
    got, _ = forward(model, params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_prefill_then_decode_through_the_state_is_the_references(system, impl):
    """A prompt of 20 tokens, then decode steps through the state: a linear
    layer keeps ``recur`` and ``conv`` a slot and NOTHING else (no column, no
    cursor), a GQA layer its joined leaf. ``flash``: the chunked prefill kernel
    and the in-place decode kernel, interpreted."""
    cfg, _, params, ids, want = system
    steps = 72 if impl == "xla" else 28
    prefill, decode = serving_clones(SolarOpen2ForCausalLM(cfg, attention_impl=impl))
    (logits, _), cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :20]))
    assert logits.shape[1] == 1        # the head on the LAST position alone
    np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, 19], atol=ATOL)
    node = cache["model"]["layers_1"]["linear_attn"]
    assert set(node) == {"recur", "conv"}
    assert node["recur"].shape == (2, cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_head_dim)
    assert node["recur"].dtype == jnp.float32 and node["conv"].shape == (2, cfg.conv_kernel - 1, cfg.conv_channels)
    assert set(cache["model"]["layers_0"]["attn"]) == {"kv", "kv_valid", "index"}
    for t in range(20, steps):
        before = cache["model"]["layers_4"]["linear_attn"]["recur"]
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(ids[:, t:t + 1]))
        np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, t], atol=ATOL)
        assert not np.array_equal(before, cache["model"]["layers_4"]["linear_attn"]["recur"])


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_unequal_prompts_in_one_bucket_leave_the_state_untouched_through_their_padding(system, impl):
    """Left-padded prompts of 60 and 41 tokens in a bucket of 64 (the second
    row's first chunk of 16 is padding alone): a row's first token reads zero
    history, its state is its own tokens', and decode goes on from there."""
    cfg, _, params, ids, want = system
    prefill, decode = serving_clones(SolarOpen2ForCausalLM(cfg, attention_impl=impl))
    lens = (60, 41)
    padded = np.full((2, 64), 3, np.int32)             # padding ids that WOULD leave a trace
    mask = np.zeros((2, 64), bool)
    for r, n in enumerate(lens):
        padded[r, 64 - n:], mask[r, 64 - n:] = ids[r, :n], True
    (logits, _), cache = through_the_cache(prefill, params, jnp.asarray(padded), padding_mask=jnp.asarray(mask))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits)[r, 0], want[r, n - 1], atol=ATOL)
    for t in range(6):
        tok = np.stack([ids[r, n + t] for r, n in enumerate(lens)])[:, None]
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(tok))
        for r, n in enumerate(lens):
            np.testing.assert_allclose(np.asarray(logits)[r, 0], want[r, n + t], atol=ATOL)


def test_a_filler_token_leaves_the_rows_state_as_it_was(system):
    """A finished row's step passes ``padding_mask`` False: its state and its
    convolutions' taps stay, bit for bit."""
    cfg, model, params, ids, _ = system
    prefill, decode = serving_clones(model)
    _, cache = through_the_cache(prefill, params, jnp.asarray(ids[:, :12]))
    mask = jnp.asarray([[True], [False]])
    _, after = through_the_cache(decode, {**params, "cache": cache}, jnp.asarray(ids[:, 12:13]), padding_mask=mask)
    for i in (1, 2, 4):
        for leaf in ("recur", "conv"):
            was, now = (c["model"][f"layers_{i}"]["linear_attn"][leaf] for c in (cache, after))
            assert not np.array_equal(was[0], now[0]) and np.array_equal(was[1], now[1])


@pytest.mark.parametrize("control", [
    {"decay": False}, {"beta_factor": 1.0}, {"conv": False}, {"gate": False}, {"rope_full": True},
    {"state_dtype": jnp.float8_e4m3fn}, {"dtype": jnp.float8_e4m3fn},
], ids=lambda c: next(iter(c)))
def test_each_control_moves_the_logits_past_the_tolerance(system, control):
    cfg, model, params, ids, want = system
    got = Reference(published_keys(cfg), params, **control).logits(ids)
    assert np.abs(got - want).max() > 100 * ATOL


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Two shares of 8 of 16 experts, one set of weights: each share's model
    computes its held experts' part of the routed sum + the shared expert; the
    shares' routed parts added, + the shared expert ONCE, are the uncut
    reference layer's output (and the system's with every expert held)."""
    whole = tiny_solar_open2(num_layers=1, gqa_layers=(0,))
    model = SolarOpen2ForCausalLM(whole, attention_impl="xla")
    params = weights(model, seed=3)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (1, 40), 0, whole.vocab_size))
    ref = Reference(published_keys(whole), params)
    layer = params["params"]["model"]["layers_0"]
    x = ref.embed(ids)
    h = x + ref.mixer_part(0, x)
    normed = h * jax.lax.rsqrt((h * h).mean(-1, keepdims=True) + whole.rms_eps) * layer["pre_moe_norm"]["weight"]
    routed_all, shared = ref.moe_part(0, normed)
    total = jnp.zeros_like(routed_all)
    for first in range(0, 16, 8):
        cut = dataclasses.replace(whole, held_experts=(first, 8))
        mine = jax.tree.map(lambda a: a, params)
        ex = layer["moe"]["experts"]
        mine["params"]["model"]["layers_0"] = {**layer, "moe": {**layer["moe"], "experts": {
            k: v[first:first + 8] for k, v in ex.items()}}}
        routed, shared_again = Reference(published_keys(cut), mine).moe_part(0, normed)
        np.testing.assert_allclose(np.asarray(shared_again), np.asarray(shared), atol=1e-6)
        total = total + routed
        # the share's own model: its logits are the share's reference's
        got, _ = forward(SolarOpen2ForCausalLM(cut, attention_impl="xla"), mine, jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(got), Reference(published_keys(cut), mine).logits(ids), atol=ATOL)
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed_all), atol=2e-6)
    got, _ = forward(model, params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), ref.logits(ids), atol=ATOL)


def test_each_devices_run_of_router_outputs_starts_summing_to_zero():
    """``router_zero_sum_group``: the logits of every run of that many
    consecutive experts sum to zero for any input, at lecun's size a column;
    without it the router is ``lecun_normal`` as every other model's."""
    cfg = tiny_solar_open2(num_layers=1, gqa_layers=(0,), router_zero_sum_group=4)
    ids = jnp.zeros((1, 8), jnp.int32)

    def router(c):
        model = SolarOpen2ForCausalLM(c, attention_impl="xla")
        return np.asarray(meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(2), ids))[
            "params"]["model"]["layers_0"]["moe"]["router"]["weight"], np.float64)

    w, plain = router(cfg), router(dataclasses.replace(cfg, router_zero_sum_group=0))
    x = np.random.default_rng(0).normal(size=(5, cfg.hidden_size)) + 3.0      # a common part and a token's own
    np.testing.assert_allclose((x @ w).reshape(5, 4, 4).sum(-1), 0.0, atol=1e-5)
    assert np.abs((x @ plain).reshape(5, 4, 4).sum(-1)).min() > 1e-3
    assert 0.8 < w.std() / plain.std() < 1.25
    with pytest.raises(ValueError, match="do not divide into runs"):
        router(dataclasses.replace(cfg, router_zero_sum_group=5))


def test_bf16_weights_are_the_float32_draw_rounded():
    """A normal drawn IN bfloat16 has a mean 72 standard errors off zero over a
    4096 x 4096 matrix (and 128 distinct values); the model's ``init`` draws
    in float32 and rounds, whatever ``param_dtype`` asks, and casts nothing
    but the parameters."""
    ids = jnp.zeros((1, 8), jnp.int32)
    cfg = tiny_solar_open2(num_layers=2, gqa_layers=(0,), param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    model = SolarOpen2ForCausalLM(cfg, attention_impl="xla")
    got = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(4), ids))
    wide = SolarOpen2ForCausalLM(dataclasses.replace(cfg, param_dtype=jnp.float32), attention_impl="xla")
    want = meta.unbox(jax.jit(wide.init)(jax.random.PRNGKey(4), ids))
    assert set(got) == {"params"} and jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.bfloat16 and b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b.astype(jnp.bfloat16), np.float32))
    drawn_in_bf16 = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (1024, 1024), jnp.bfloat16), np.float64)
    assert abs(drawn_in_bf16.mean()) * 1024 > 10        # what the override is for
    table = np.asarray(got["params"]["model"]["embed"]["embedding"], np.float64)
    assert abs(table.mean()) * np.sqrt(table.size) < 4 * table.std()


def test_the_published_widths_hold_four_mib_of_state_a_slot_a_layer_and_four_kib_a_token():
    """Solar-Open2-250B as published, in bf16: a linear layer's state and taps
    a slot, a GQA layer's joined leaf a token, the parameter count of each kind
    of layer (the configuration's arithmetic)."""
    cfg = solar_open2_250b(num_layers=2, held_experts=(0, 10), vocab_size=24576, param_dtype=jnp.bfloat16)
    assert cfg.layer_types == ("full_attention", "linear_attention")
    assert solar_open2_250b().layer_types.count("full_attention") == 12
    model = SolarOpen2ForCausalLM(cfg, attention_impl="xla")
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    cache = jax.eval_shape(
        lambda p, i: model.clone(mode="prefill").apply(p, i, mutable=["cache"])[1]["cache"], shapes, ids)
    assert cache["model"]["layers_0"]["attn"]["kv"].shape[-2:] == (16, 128)
    node = cache["model"]["layers_1"]["linear_attn"]
    assert node["recur"].shape == (1, 64, 128, 128) and node["conv"].shape == (1, 3, 24576)
    assert slot_state_bytes_per_layer(cache) == kda_costs.slot_state_bytes(heads=64, head_dim=128, taps=3) == 4341760
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))    # noqa: E731
    layers = meta.unbox(shapes)["params"]["model"]
    h, hd = 4096, 8192
    # q, k, v, o; the two low-rank pairs; beta; the convolutions; A_log, dt_bias, the gate's bias, the head norm
    assert count(layers["layers_1"]["linear_attn"]) == (
        4 * h * hd + 2 * (h * 128 + 128 * hd) + h * 64 + 4 * 3 * hd + 64 + hd + hd + 128)
    assert count(layers["layers_0"]["attn"]) == 3 * h * hd + 2 * h * 1024          # q, gate, o; k, v
    assert count(layers["layers_0"]["moe"]["experts"]) == 10 * 3 * h * 1280
    assert count(layers["layers_0"]["moe"]["router"]) == h * 320
    assert count(layers["layers_0"]["moe"]["shared"]) == 3 * h * 1280


def test_what_the_model_is_not_written_for_is_refused(system):
    cfg, model, params, ids, _ = system
    with pytest.raises(ValueError, match="at least one"):
        SolarOpen2Config(num_layers=3, gqa_layers=())
    with pytest.raises(NotImplementedError, match="positions"):
        model.apply(params, jnp.asarray(ids[:, :8]), positions=jnp.arange(8)[None])
    _, decode = serving_clones(model)
    with pytest.raises(ValueError, match="one token a slot"):
        decode.apply(params, jnp.asarray(ids[:, :2]), mutable=["cache"])
