"""The plain Trinity reference against the program's model class and against
hand-written layer equations, tiny, on the CPU; the configuration's file
against the catalog; and the cell's traffic."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench import tape
from perfbench.references.afmoe import Reference, held_experts, layers_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "trinity-large-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [     # every vector off its initial value
        leaf + 0.3 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1 else leaf for leaf, k in zip(leaves, keys)])
    return config["model"], model, params


def test_reference_logits_match_the_model_with_every_mechanism_present(tiny):
    """A dense window layer, two window layers, a full layer; a window of 32
    under contexts of 150; gated GQA 4 / 2 heads of 16; 16 router outputs top-2
    under a bias with experts 4-7 held, a shared expert."""
    cfg, model, params = tiny
    assert layers_run(cfg) == (["sliding_attention"] * 3 + ["full_attention"], 1)
    assert held_experts(cfg) == (16, 4, 4) and cfg["sliding_window"] == 32
    assert model.config.held_experts == (4, 4) and model.config.num_dense_layers == 1
    assert model.config.layer_types == ("sliding_attention",) * 3 + ("full_attention",)
    ids = np.random.default_rng(0).integers(1, 256, (2, 150)).astype(np.int32)
    logits, _ = model.apply(params, jnp.asarray(ids))
    got, margin = Reference(cfg, params).logits_and_router_margin(ids)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32       # the head in blocks, on the host
    np.testing.assert_allclose(got, np.asarray(logits, np.float32), atol=2e-4, rtol=2e-4)
    assert margin.shape == (2, 150) and float(margin.min()) >= 0.0


@pytest.mark.parametrize("layer", [1, 3], ids=["window_layer", "full_layer"])
def test_one_position_of_a_sparse_layer_is_the_hand_written_equations(tiny, layer):
    """The module docstring's equations written out in numpy float64 with
    loops, for the LAST position of a 48-token context (past the window of
    32), and held against the reference's block."""
    cfg, _, params = tiny
    ref = Reference(cfg, params)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(1, 48, cfg["hidden_size"])).astype(np.float32)
    tree = params["params"]["model"][f"layers_{layer}"]
    kind = ref.kinds[layer]
    got = np.asarray(ref._sparse[kind](tree, jnp.asarray(x))[0])[0, -1]

    p = jax.tree.map(lambda a: np.asarray(a, np.float64), tree)
    a = p["attn"]
    eps, heads, kv_heads, d = cfg["rms_norm_eps"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    rms = lambda v, w: v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * w   # noqa: E731

    def rope(v, t):
        """All ``d`` channels of a head's vector at position ``t``: channel i with i + d / 2."""
        if window is None:
            return v                       # a full layer carries no positions
        ang = t / cfg["rope_theta"] ** (np.arange(0, d, 2) / d)
        v1, v2 = v[:d // 2], v[d // 2:]
        return np.concatenate([v1 * np.cos(ang) - v2 * np.sin(ang), v2 * np.cos(ang) + v1 * np.sin(ang)])

    xs = x[0].astype(np.float64)
    us = rms(xs, p["input_norm"]["weight"])
    t = 47
    q = rms((us[t] @ a["qkv"]["q_proj"]["kernel"]).reshape(heads, d), a["q_norm"]["weight"])
    first = 0 if window is None else t - window + 1
    out = np.zeros((heads, d))
    for h in range(heads):
        g = h // (heads // kv_heads)
        logit, values = [], []
        for s in range(first, t + 1):
            k = rms((us[s] @ a["qkv"]["k_proj"]["kernel"]).reshape(kv_heads, d), a["k_norm"]["weight"])[g]
            logit.append(rope(q[h], t) @ rope(k, s) / np.sqrt(d))
            values.append((us[s] @ a["qkv"]["v_proj"]["kernel"]).reshape(kv_heads, d)[g])
        prob = np.exp(np.array(logit) - max(logit))
        prob /= prob.sum()
        out[h] = sum(pr * v for pr, v in zip(prob, values))
    assert len(logit) == (32 if window else 48)
    gate = 1.0 / (1.0 + np.exp(-(us[t] @ a["gate_proj"]["kernel"])))
    y = xs[t] + rms((out.reshape(-1) * gate) @ a["o_proj"]["kernel"], p["post_attn_norm"]["weight"])
    hm = rms(y, p["pre_mlp_norm"]["weight"])
    moe = p["moe"]
    s_all = 1.0 / (1.0 + np.exp(-(hm @ moe["router"]["weight"])))
    chosen = np.argsort(-(s_all + moe["router"]["e_score_correction_bias"]), kind="stable")[:cfg["num_experts_per_tok"]]
    weights = s_all[chosen] / (s_all[chosen].sum() + 1e-20) * cfg["route_scale"]     # the bias selects, does not weigh
    silu = lambda v: v / (1.0 + np.exp(-v))   # noqa: E731
    _, lo, held = held_experts(cfg)
    total = np.zeros(cfg["hidden_size"])
    for e, w in zip(chosen, weights):
        if lo <= e < lo + held:                # what the absent experts would add is left out
            ex = moe["experts"]
            total += w * ((silu(hm @ ex["gate_proj"][e - lo]) * (hm @ ex["up_proj"][e - lo])) @ ex["down_proj"][e - lo])
    sh = moe["shared"]
    total += (silu(hm @ sh["gate"]["kernel"]) * (hm @ sh["up"]["kernel"])) @ sh["down"]["kernel"]
    want = y + rms(total, p["post_mlp_norm"]["weight"])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_the_configuration_holds_every_published_key_but_the_three_it_names():
    config = _config("perfbench/configs")
    with open(CATALOG) as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "Trinity-Large-Preview")
    assert config["source"] == published["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for group in (config, config["model"]):
        for key, value in published["config"].items():
            if key not in config["reduced"]:
                assert group[key] == value, key
    m = config["model"]
    assert (m["num_hidden_layers"], m["num_experts"], m["vocab_size"]) == (5, 32, 25024)
    assert (m["num_hidden_layers_published"], m["num_experts_published"], m["vocab_size_published"]) == (60, 256, 200192)
    assert m["layers_run"] == [0, 8, 9, 10, 11] and m["dense_layers_run"] == 1 and m["first_held_expert"] == 0
    kinds, dense = layers_run(m)
    assert kinds == ["sliding_attention"] * 4 + ["full_attention"] and dense == 1      # one whole period after a dense layer
    assert m["vocab_size"] * 8 == m["vocab_size_published"] and m["num_experts"] * 8 == m["num_experts_published"]
    assert {"slot_length", "e_score_correction_bias", "embedding_scale", "rotary", "weights", "norm_gains"} <= set(config["assumed"])
    # the gains the random weights start at are keys of the file's own, and reach the model
    assert (m["qk_norm_gain_init"], m["post_attention_norm_gain_init"]) == (1.5, 4.0)
    built = importlib.import_module("perfbench.families.afmoe").build(dict(m, num_experts=4, num_experts_published=16, vocab_size=256), runner="serve",
                               max_seq_len=64).config
    assert (built.qk_norm_init, built.post_attn_norm_init) == (1.5, 4.0)
    assert "8 chips" in config["deployment"] or "eight chips" in config["deployment"]
    assert config["serving"] == {**config["serving"], "num_slots": 8, "max_seq_len": 32768, "kv_page_size": 16}
    assert config["reference_check"]["sample_quantiles"] == [0.57]


def test_the_cells_traffic_is_the_issues_block():
    traffic = tape.load_traffic("mixedctx_closed")
    pairs = sorted(tape.block_lengths(traffic))
    assert [p for p, _ in pairs] == [2799, 4402, 5818, 7338, 9146, 11534, 15244, 16384]
    assert min(a for _, a in pairs) == 323 and max(a for _, a in pairs) == 811
    assert all(p + a <= traffic["max_total"] for p, a in pairs)
    check = _config("perfbench/configs")["reference_check"]
    picked = pairs[int(round(check["sample_quantiles"][0] * (len(pairs) - 1)))]
    assert picked[0] == 9146 and picked[0] > 2 * 4096          # over two windows: freed pages and the lower edge are checked
