"""The plain DeepSeek-V2 reference against the program's model class, tiny,
on the CPU, and what the reference itself must be able to tell apart."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench.references import common
from perfbench.references.deepseek_v2 import Reference, yarn_inv_freq

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "deepseek-v2-lite-serve"


def _config(directory):
    with open(os.path.join(ROOT, directory, f"{NAME}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    config = _config("tests/benchmark/data/configs")
    family = importlib.import_module(f"perfbench.families.{config['family']}")
    model = family.build(config["model"], runner="train", max_seq_len=512)
    model = model.clone(config=model.config.__class__(**{**model.config.__dict__, "dtype": jnp.float32}),
                        attention_impl="xla")
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(3), jnp.zeros((2, 16), jnp.int32)))
    return config["model"], model, params


def test_reference_logits_match_the_model_with_every_mechanism_present(tiny):
    """Dense layer + two sparse ones, 8 experts top-3 + 2 shared, latent 32,
    rope 8, nope 16, v 16, YaRN on with positions past the original 256."""
    cfg, model, params = tiny
    assert cfg["first_k_dense_replace"] == 1 and cfg["num_hidden_layers"] == 3
    assert cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] != cfg["v_head_dim"]
    ids = np.random.default_rng(0).integers(1, 256, (2, 300)).astype(np.int32)
    assert ids.shape[1] > cfg["rope_scaling"]["original_max_position_embeddings"]
    logits, _ = model.apply(params, jnp.asarray(ids))
    got, margin = Reference(cfg, params).logits_and_router_margin(ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(logits, np.float32), atol=2e-4, rtol=2e-4)
    assert margin.shape == (2, 300) and float(margin.min()) >= 0.0 and np.isfinite(np.asarray(margin)).all()


def test_query_blocks_do_not_change_the_attention(tiny, monkeypatch):
    """Attention runs a block of queries at a time (a 24k context's scores do
    not fit at once): a block of 64 over 300 positions gives a block of 512's
    logits."""
    from perfbench.references import deepseek_v2 as module

    cfg, _, params = tiny
    ids = np.random.default_rng(1).integers(1, 256, (1, 300)).astype(np.int32)
    whole = Reference(cfg, params).logits(ids)
    monkeypatch.setattr(module, "QUERY_BLOCK", 64)
    blocked = module.Reference(cfg, params).logits(ids)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), atol=1e-5)


def test_logits_at_chosen_positions_are_rows_of_the_whole(tiny):
    cfg, _, params = tiny
    ref = Reference(cfg, params)
    ids = np.random.default_rng(2).integers(1, 256, (1, 96)).astype(np.int32)
    whole, margin = ref.logits_and_router_margin(ids)
    pick = np.arange(64, 96)
    rows, m = ref.logits_and_router_margin(ids, pick)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(whole[:, 64:]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(margin[:, 64:]))


@pytest.mark.parametrize("part", ["rope channel", "shared experts", "yarn", "latent norm"])
def test_a_dropped_mechanism_moves_the_logits_far_past_the_tolerance(tiny, part):
    """What the chip's check must be able to see: take one mechanism out of
    the REFERENCE and its logits leave the configuration's tolerance."""
    cfg, _, params = tiny
    tolerance = _config("perfbench/configs")["reference_check"]["logit_tolerance"]
    ids = np.random.default_rng(4).integers(1, 256, (1, 300)).astype(np.int32)
    want = np.asarray(Reference(cfg, params).logits(ids))
    broken_cfg, broken = dict(cfg), jax.tree.map(lambda a: a, params)
    layer = broken["params"]["model"]["layers_1"]
    if part == "rope channel":      # zero one rotated channel of the shared key's projection
        kernel = layer["attn"]["kv_a_proj"]["kernel"]
        layer["attn"]["kv_a_proj"]["kernel"] = kernel.at[:, -1].set(0.0)
    elif part == "shared experts":
        del layer["moe"]["shared"]
    elif part == "yarn":
        broken_cfg["rope_scaling"] = None
    else:
        layer["attn"]["kv_a_norm"]["weight"] = 2.0 * layer["attn"]["kv_a_norm"]["weight"]
    got = np.asarray(Reference(broken_cfg, broken).logits(ids))
    assert np.abs(got - want).max() > 3 * tolerance


def test_yarn_inverse_frequencies_of_the_published_config():
    cfg = _config("perfbench/configs")["model"]
    inv, ratio = yarn_inv_freq(64, float(cfg["rope_theta"]), cfg["rope_scaling"])
    plain = 1.0 / (10000.0 ** (np.arange(0, 64, 2) / 64))
    assert ratio == 1.0
    np.testing.assert_allclose(np.asarray(inv[:11]), plain[:11], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[23:]), plain[23:] / 40.0, rtol=1e-6)


def test_the_router_near_tie_excuses_only_what_it_says(tiny):
    cfg, _, params = tiny
    ref = Reference(cfg, params)
    prompt = np.arange(1, 41, dtype=np.int32)
    ids = np.zeros((1, 64), np.int32)
    ids[0, :40] = prompt
    greedy = []
    for i in range(4):
        greedy.append(int(np.argmax(np.asarray(ref.logits(ids)[0, 39 + i]))))
        ids[0, 40 + i] = greedy[-1]
    gaps, controls, margin, router = common.emitted_token_gaps(ref, prompt, greedy, 64)
    assert (gaps == 0.0).all() and (controls > 0.0).all() and router.shape == (4,)
    ok, over, exempt = common.judge_gaps(controls, router, 0.1, 0.0)
    assert not ok and over == 4 and exempt == 0


def test_the_configuration_file_is_the_published_config_but_for_its_depth():
    """Every published key as in the source, at the file's top level and in
    the ``model`` block the harness reads; only the depth is cut."""
    config = _config("perfbench/configs")
    assert config["reduced"] == ["num_hidden_layers"]
    model = config["model"]
    assert all(config[k] == v for k, v in model.items())
    published = {
        "hidden_size": 2048, "intermediate_size": 10944, "moe_intermediate_size": 1408, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "num_attention_heads": 16,
        "n_routed_experts": 64, "num_experts_per_tok": 6, "n_shared_experts": 2, "first_k_dense_replace": 1,
        "vocab_size": 102400, "max_position_embeddings": 163840, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "routed_scaling_factor": 1, "norm_topk_prob": False, "q_lora_rank": None,
    }
    assert {k: model[k] for k in published} == published
    assert model["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    assert model["num_hidden_layers"] >= 1 + 4
