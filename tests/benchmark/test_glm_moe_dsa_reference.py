"""The plain GLM-5 reference against the program's model class and against
hand-written layer equations, tiny, on the CPU; what the reference itself must
be able to tell apart; the configuration's file and the cell's traffic."""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench.references.glm_moe_dsa import Reference, held_experts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "glm-5-serve"
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
    """A query latent, MLA 4 heads of 8 + 8 / 16, an indexer of 4 x 16 fed
    from the query latent keeping 32 of up to 300 columns, 16 router outputs
    top-8 under a bias with experts 4-11 held, a shared expert, a dense layer
    first."""
    cfg, model, params = tiny
    assert cfg["index_topk"] == 32 and held_experts(cfg) == (16, 4, 8) and cfg["dense_layers_run"] == 1
    assert model.config.held_experts == (4, 8) and model.config.first_k_dense == 1
    ids = np.random.default_rng(0).integers(1, 256, (2, 300)).astype(np.int32)
    logits, _ = model.apply(params, jnp.asarray(ids))
    got, margin = Reference(cfg, params).logits_and_router_margin(ids)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32       # the head in blocks, on the host
    np.testing.assert_allclose(got, np.asarray(logits, np.float32), atol=2e-4, rtol=2e-4)
    assert margin.shape == (2, 300) and float(margin.min()) >= 0.0


def _np(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def test_one_position_of_a_sparse_layer_is_the_hand_written_equations(tiny):
    """ISSUE section 1 written out in numpy float64 with loops, for the LAST
    position of a 48-token context (past ``index_topk`` 32) in layer 1, and
    held against the reference's block."""
    cfg, _, params = tiny
    ref = Reference(cfg, params)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(1, 48, cfg["hidden_size"])).astype(np.float32)
    layer = params["params"]["model"]["layers_1"]
    got = np.asarray(ref._sparse[False](layer, jnp.asarray(x))[0])[0, -1]

    p, a = _np(layer), _np(layer["attn"])
    eps, heads = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    d_c, d_n, d_r, d_v = (cfg[k] for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    h_i, d_i, keep = cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"]
    theta = cfg["rope_parameters"]["rope_theta"]
    rms = lambda v, w: v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * w   # noqa: E731

    def rope(v, t):
        """The first ``d_r`` channels of a vector at position ``t``: channel i with i + d_r / 2."""
        half = d_r // 2
        ang = t / theta ** (np.arange(0, d_r, 2) / d_r)
        v1, v2 = v[:half], v[half:d_r]
        return np.concatenate([v1 * np.cos(ang) - v2 * np.sin(ang), v2 * np.cos(ang) + v1 * np.sin(ang), v[d_r:]])

    xs = x[0].astype(np.float64)
    hs = rms(xs, p["input_norm"]["weight"])
    t = 47
    c_q = rms(hs[t] @ a["q_a_proj"]["kernel"], a["q_a_norm"]["weight"])
    q = (c_q @ a["q_b_proj"]["kernel"]).reshape(heads, d_n + d_r)
    q_i = (c_q @ a["idx_q_proj"]["kernel"]).reshape(h_i, d_i)
    w_i = hs[t] @ a["idx_w_proj"]["kernel"]
    w_kv_b = a["kv_b_proj"].reshape(d_c, heads, d_n + d_v)
    score, keys, values = np.zeros(48), [], []
    for s in range(48):
        kv_a = hs[s] @ a["kv_a_proj"]["kernel"]
        c = rms(kv_a[:d_c], a["kv_a_norm"]["weight"])
        k_pe = rope(kv_a[d_c:], s)
        kv = np.einsum("c,chd->hd", c, w_kv_b)
        keys.append(np.concatenate([kv[:, :d_n], np.tile(k_pe, (heads, 1))], axis=1))
        values.append(kv[:, d_n:])
        raw = hs[s] @ a["idx_k_proj"]["kernel"]
        k_i = (raw - raw.mean()) / np.sqrt(raw.var() + 1e-6) * a["idx_k_norm"]["scale"] + a["idx_k_norm"]["bias"]
        k_i = rope(k_i, s)
        score[s] = sum(w_i[j] * max(rope(q_i[j], t) @ k_i, 0.0) for j in range(h_i))
    chosen = np.argsort(-score, kind="stable")[:keep]
    assert len(chosen) == 32 < 48
    out = np.zeros((heads, d_v))
    for h in range(heads):
        qh = np.concatenate([q[h, :d_n], rope(q[h, d_n:], t)])
        logit = np.array([qh @ keys[s][h] for s in chosen]) * (d_n + d_r) ** -0.5
        prob = np.exp(logit - logit.max())
        prob /= prob.sum()
        out[h] = sum(pr * values[s][h] for pr, s in zip(prob, chosen))
    y = xs[t] + out.reshape(-1) @ a["o_proj"]["kernel"]
    hm = rms(y, p["post_attn_norm"]["weight"])
    moe = p["moe"]
    s_all = 1.0 / (1.0 + np.exp(-(hm @ moe["router"]["weight"])))
    top = np.argsort(-(s_all + moe["router"]["e_score_correction_bias"]), kind="stable")[:cfg["num_experts_per_tok"]]
    weights = s_all[top] / (s_all[top].sum() + 1e-20) * cfg["routed_scaling_factor"]
    silu = lambda v: v / (1.0 + np.exp(-v))   # noqa: E731
    n_out, first, held = held_experts(cfg)
    ffn = (silu(hm @ moe["shared"]["gate"]["kernel"]) * (hm @ moe["shared"]["up"]["kernel"])) @ moe["shared"]["down"]["kernel"]
    computed = 0
    for e, w in zip(top, weights):
        if first <= e < first + held:           # the experts held here; the others are left out
            ex = {k: v[e - first] for k, v in moe["experts"].items()}
            ffn = ffn + w * ((silu(hm @ ex["gate_proj"]) * (hm @ ex["up_proj"])) @ ex["down_proj"])
            computed += 1
    assert 0 < computed < len(top)               # some chosen experts are held, some are not
    np.testing.assert_allclose(got, y + ffn, atol=2e-4, rtol=2e-4)


def test_the_margin_counts_only_where_a_held_expert_is_at_the_edge(tiny):
    cfg, _, params = tiny
    ids = np.random.default_rng(5).integers(1, 256, (1, 96)).astype(np.int32)
    share = Reference(cfg, params).logits_and_router_margin(ids)[1]
    assert np.isinf(share).any() and np.isfinite(share).any()
    # the same router over experts ALL held here (the same 8 weights reused): every position counts
    whole = dict(cfg, n_routed_experts=16, n_routed_experts_published=16, first_held_expert=0)
    doubled = jax.tree_util.tree_map_with_path(
        lambda p, a: jnp.concatenate([a, a]) if "'experts'" in jax.tree_util.keystr(p) else a, params)
    every = Reference(whole, doubled).logits_and_router_margin(ids)[1]
    assert np.isfinite(every).all()


@pytest.mark.parametrize("control", ["topk", "index_dtype", "latent_dtype", "bias_in_weights", "dtype"])
def test_each_control_moves_the_logits_or_the_selection(tiny, control):
    cfg, _, params = tiny
    ids = np.random.default_rng(2).integers(1, 256, (1, 160)).astype(np.int32)
    ref = Reference(cfg, params)
    kw = {"topk": {"topk": 16}, "index_dtype": {"index_dtype": jnp.float8_e4m3fn},
          "latent_dtype": {"latent_dtype": jnp.float8_e4m3fn}, "bias_in_weights": {"bias_in_weights": True},
          "dtype": {"dtype": jnp.float8_e4m3fn}}[control]
    other = Reference(cfg, params, **kw)
    moved = float(np.abs(other.logits(ids) - ref.logits(ids)).max())
    differ = float((other.selected(ids)[0] != ref.selected(ids)[0]).mean())
    assert moved > 0.02, (control, moved)
    assert (differ > 0) == (control in ("topk", "index_dtype", "dtype")), (control, differ)


def test_the_configuration_file_is_the_published_config_but_for_depth_experts_held_and_vocabulary():
    """Every key of the catalog's ``config`` as published at the file's top
    level and in the ``model`` block the harness reads; ``reduced`` names the
    three cuts, the published counts and the deployment stand beside them."""
    config = _config("perfbench/configs")
    with open(CATALOG) as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "GLM-5")
    assert config["source"] == published["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    model = config["model"]
    assert all(config[k] == v for k, v in model.items())
    differing = {k for k, v in published["config"].items() if model[k] != v}
    assert differing == set(config["reduced"])
    # no width among them: the guide's floors
    assert model["n_routed_experts"] == 8 and model["n_routed_experts_published"] == 256 and model["first_held_expert"] == 0
    assert model["vocab_size"] == 19360 == model["vocab_size_published"] // 8
    assert model["dense_layers_run"] == 1 and model["num_hidden_layers"] - model["dense_layers_run"] >= 4
    assert model["num_hidden_layers_published"] == 78 and model["first_k_dense_replace"] == 3
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "32 chips" in config["deployment"] and "8 of the 256" in config["deployment"]
    assert {"slot_length", "index_key_dtype", "e_score_correction_bias", "multi_token_prediction", "head_dim",
            "weights"} <= set(config["assumed"])
    assert config["serving"] == {"num_slots": 8, "max_seq_len": 32768, "kv_page_size": 16,
                                 "engine": "ServingEngine defaults, as codegen2-7b-serve"}
    assert config["reference_check"]["sample_quantiles"] == [0.0]


def test_the_family_refuses_what_it_does_not_model():
    from perfbench.families import glm_moe_dsa as family

    model = _config("tests/benchmark/data/configs")["model"]
    for change, match in (({"n_group": 2}, "group-limited"),
                          ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "YaRN"),
                          ({"scoring_func": "softmax"}, "sigmoid")):
        with pytest.raises(ValueError, match=match):
            family.build({**model, **change}, runner="serve", max_seq_len=128)
    with pytest.raises(ValueError, match="sequence-parallel"):
        family.build(model, runner="train", max_seq_len=128, sequence_parallel=True)
    g = family.geometry(_config("perfbench/configs")["model"])
    assert (g["num_q_heads"], g["head_dim"], g["v_head_dim"], g["latent_dim"], g["rope_dim"]) == (64, 256, 256, 512, 64)
    assert (g["index_heads"], g["index_dim"], g["index_topk"], g["vocab_size"]) == (32, 128, 2048, 19360)
    assert g["expert_layers"] == g["num_layers"] - 1


def test_the_cells_traffic_is_the_issues():
    from perfbench import tape

    traffic = tape.load_traffic("agentdocs_closed")
    assert traffic["loop"] == "closed" and traffic["block"] == 8 and traffic["ramp_s"] == 8
    assert traffic["overload_backlog"] == 4
    assert traffic["prompt_len"]["dist"] == "lognormal" and traffic["prompt_len"]["median"] == 8192
    assert traffic["prompt_len"]["sigma"] == 0.5 and traffic["prompt_len"]["min"] == 4096
    assert traffic["prompt_len"]["max"] in (16384, 12288)              # the issue's, or its one permitted fallback
    assert traffic["max_total"] == traffic["prompt_len"]["max"] + 1024
    assert traffic["answer_len"] == {"dist": "lognormal", "median": 640, "sigma": 0.3, "min": 384, "max": 1024}
    pairs = sorted(tape.block_lengths(traffic))
    assert len(pairs) == 8 and all(p >= 2 * 2048 for p, _ in pairs)     # selection decides every step
    assert max(p + a for p, a in pairs) <= traffic["max_total"]
    assert 0.17 < sum(min(p, 2048) for p, _ in pairs) / sum(p + a / 2 for p, a in pairs) < 0.27


def _patched_router(monkeypatch):
    """A SYSTEM whose router weighs with the selection bias too."""
    from neuronx_distributed_tpu.modules.moe import routing

    plain = routing.RouterTopK.__call__

    def biased(self, x, deterministic=True):
        out = plain(self, x, deterministic)
        bias = meta.unbox(self.get_variable("params", "e_score_correction_bias"))
        w = jnp.take_along_axis(out.probs + bias, out.top_e, axis=-1)
        return out._replace(top_w=w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9))

    monkeypatch.setattr(routing.RouterTopK, "__call__", biased)


@pytest.mark.parametrize("system, correct", [("as configured", True), ("keeps half the columns", False),
                                             ("weighs with the bias", False)])
def test_the_harness_own_comparison_tells_each_wrong_system(monkeypatch, system, correct):
    """``serve._reference_check`` itself, at the stand-in's size: the system
    as configured is ``correct``; the same weights and prompt served by a
    system built to keep half the columns, or by one whose router adds the
    selection bias to the weights, are not. On the chip:
    ``reference_check.why`` and PERF.md."""
    from perfbench import tape
    from perfbench.families import glm_moe_dsa as family
    from perfbench.runners import serve
    from perfbench.spans import Spans

    config = _config("tests/benchmark/data/configs")
    config["reference_check"]["router_near_tie"] = 0.0      # float32 on the CPU: nothing needs an excuse
    config["reference_check"]["logit_tolerance"] = 1e-3
    traffic = tape.load_traffic("agentdocs_closed", os.path.join(ROOT, "tests/benchmark/data/traffic"))
    plain = family.build

    def build(cfg, **kw):
        cfg = copy.deepcopy(cfg)
        # a bias as wide as the sigmoid's range, so that 15 greedy tokens of a tiny model show it
        cfg["e_score_correction_bias_std"] = 1.0
        if system == "keeps half the columns":
            cfg["index_topk"] //= 2
        model = plain(cfg, **kw)
        return model.clone(config=model.config.__class__(**{**model.config.__dict__, "dtype": jnp.float32}))

    monkeypatch.setattr(family, "build", build)
    if system == "weighs with the bias":
        _patched_router(monkeypatch)
    said = []
    engine, fam, params, vocab = serve.build(config, 7, Spans(), said.append)
    ok, sampled, failed = serve._reference_check(engine, fam, config, params, traffic, vocab, 7, said.append)
    assert (ok, sampled, failed) == (correct, 1, 0), said
