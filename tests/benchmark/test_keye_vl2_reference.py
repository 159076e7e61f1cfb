"""The plain Keye-VL-2.0 reference against the program's model class, tiny,
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
from perfbench.references.keye_vl2 import Reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "keye-vl2-30b-a3b-serve"


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
    params = jax.tree_util.tree_unflatten(treedef, [     # norms' scales and the LayerNorm's bias off 1 and 0
        leaf + 0.3 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1 else leaf for leaf, k in zip(leaves, keys)])
    return config["model"], model, params


def test_reference_logits_match_the_model_with_every_mechanism_present(tiny):
    """GQA 4Q/2KV with head norms, M-RoPE sections [2, 3, 3], an indexer
    keeping 32 of up to 300 columns, 8 experts top-3 renormalised."""
    cfg, model, params = tiny
    assert cfg["sa_config"]["topk"] == 32 and cfg["num_experts_per_tok"] == 3 and cfg["norm_topk_prob"]
    ids = np.random.default_rng(0).integers(1, 256, (2, 300)).astype(np.int32)
    logits, _ = model.apply(params, jnp.asarray(ids))
    got, margin = Reference(cfg, params).logits_and_router_margin(ids)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32       # the head in blocks, on the host
    np.testing.assert_allclose(got, np.asarray(logits, np.float32), atol=2e-4, rtol=2e-4)
    assert margin.shape == (2, 300) and float(margin.min()) >= 0.0 and np.isfinite(margin).all()


def test_only_the_routers_margin_excuses_a_token(tiny):
    """``judge_gaps`` reads the router's margin alone: two adjacent order
    statistics among a row's index scores are always near, and folded in
    they would excuse every position."""
    cfg, _, params = tiny
    ids = np.random.default_rng(5).integers(1, 256, (1, 96)).astype(np.int32)
    ref = Reference(cfg, params)
    logits, router, index = ref.logits_and_margins(ids)
    same, excuse = ref.logits_and_router_margin(ids)
    np.testing.assert_array_equal(excuse, router)
    np.testing.assert_array_equal(same, logits)
    assert np.isinf(index[:, :32]).all() and np.isfinite(index[:, 32:]).all()    # up to topk every key is kept
    assert np.isfinite(router).all() and (router >= 0).all() and (index >= 0).all()
    assert float(np.median(index[:, 32:])) < float(np.median(router))            # why it is not folded in


def test_query_and_head_blocks_do_not_change_the_result(tiny, monkeypatch):
    """Attention and index scores run a block of query rows at a time, the
    head a block of positions at a time: other block sizes, the same logits
    and the same selected sets."""
    from perfbench.references import keye_vl2 as module

    cfg, _, params = tiny
    ids = np.random.default_rng(1).integers(1, 256, (1, 300)).astype(np.int32)
    whole = Reference(cfg, params)
    logits, sets = whole.logits(ids), whole.selected(ids)
    monkeypatch.setattr(module, "QUERY_BLOCK", 64)
    monkeypatch.setattr(module, "HEAD_BLOCK", 100)
    blocked = module.Reference(cfg, params)
    np.testing.assert_allclose(blocked.logits(ids), logits, atol=1e-5)
    for a, b in zip(blocked.selected(ids), sets):
        np.testing.assert_array_equal(a, b)


def test_each_row_keeps_min_t_plus_1_topk_columns_of_its_past(tiny):
    cfg, _, params = tiny
    ids = np.random.default_rng(2).integers(1, 256, (2, 96)).astype(np.int32)
    sets = Reference(cfg, params).selected_and_scores(ids)
    assert len(sets) == cfg["num_hidden_layers"]
    for keep, scores in sets:
        assert keep.shape == scores.shape == (2, 96, 96)
        assert (keep.sum(-1) == np.minimum(np.arange(96) + 1, 32)).all()
        assert not np.triu(keep[0], 1).any() and np.isneginf(np.triu(scores[0], 1)[np.triu_indices(96, 1)]).all()
        kept_min = np.where(keep, scores, np.inf).min(-1)
        dropped_max = np.where(~keep & np.tril(np.ones((96, 96), bool)), scores, -np.inf).max(-1)
        assert (kept_min >= dropped_max).all()                               # a top-k by score


@pytest.mark.parametrize("part", ["indexer weights", "index key norm", "head norm", "mrope section", "renormalisation",
                                  "half the columns"])
def test_a_changed_mechanism_moves_the_logits_far_past_the_tolerance(tiny, part):
    """What the chip's check must be able to see: change one mechanism of the
    REFERENCE and its logits leave the configuration's tolerance."""
    cfg, _, params = tiny
    tolerance = _config("perfbench/configs")["reference_check"]["logit_tolerance"]
    ids = np.random.default_rng(4).integers(1, 256, (1, 300)).astype(np.int32)
    want = Reference(cfg, params).logits(ids)
    broken_cfg, broken, kw = json.loads(json.dumps(cfg)), jax.tree.map(lambda a: a, params), {}
    attn = broken["params"]["model"]["layers_0"]["attn"]
    if part == "indexer weights":        # another selection
        attn["idx_w_proj"]["kernel"] = -attn["idx_w_proj"]["kernel"]
    elif part == "index key norm":
        attn["idx_k_norm"]["scale"] = jnp.flip(attn["idx_k_norm"]["scale"])
    elif part == "head norm":
        attn["q_norm"]["weight"] = 2.0 * attn["q_norm"]["weight"]
    elif part == "mrope section":        # plain rope is what equal streams give: change the frequencies' base
        broken_cfg["rope_theta"] = 10000
    elif part == "renormalisation":
        broken_cfg["norm_topk_prob"] = False
    else:
        kw["topk"] = 16
    got = Reference(broken_cfg, broken, **kw).logits(ids)
    assert np.abs(got - want)[:, 64:].max() > 3 * tolerance


def test_lower_precision_index_keys_move_the_selection_before_the_logits(tiny):
    """``index_dtype`` rounds the index keys a cache would hold: float8 keys
    swap columns at the threshold (what ``chip_smoke.py``'s selection limit
    holds), bfloat16 ones fewer."""
    cfg, _, params = tiny
    ids = np.random.default_rng(6).integers(1, 256, (1, 300)).astype(np.int32)
    plain = Reference(cfg, params).selected(ids)[0]
    rows = np.arange(300) >= 32
    share = lambda other: float((other[0][rows] != plain[0][rows]).sum() / (2.0 * 32 * rows.sum()))  # noqa: E731
    bf16 = share(Reference(cfg, params, jnp.bfloat16).selected(ids)[0])
    fp8 = share(Reference(cfg, params, jnp.float8_e4m3fn).selected(ids)[0])
    assert 0.0 <= bf16 < fp8 and fp8 > 0.01


def test_a_lower_precision_key_value_cache_moves_the_logits(tiny):
    """``kv_dtype`` rounds the rotated keys and the values a cache would
    hold: the further below float32, the further the logits."""
    cfg, _, params = tiny
    ids = np.random.default_rng(7).integers(1, 256, (1, 96)).astype(np.int32)
    want = Reference(cfg, params).logits(ids)
    far = lambda dtype: float(np.abs(Reference(cfg, params, kv_dtype=dtype).logits(ids) - want).max())  # noqa: E731
    assert 0.0 < far(jnp.bfloat16) < far(jnp.float8_e4m3fn)


def test_the_near_tie_excuses_only_what_it_says(tiny):
    cfg, _, params = tiny
    ref = Reference(cfg, params)
    prompt = np.arange(1, 41, dtype=np.int32)
    ids = np.zeros((1, 64), np.int32)
    ids[0, :40] = prompt
    greedy = []
    for i in range(4):
        greedy.append(int(np.argmax(ref.logits(ids)[0, 39 + i])))
        ids[0, 40 + i] = greedy[-1]
    gaps, controls, margin, router = common.emitted_token_gaps(ref, prompt, greedy, 64)
    assert (gaps == 0.0).all() and (controls > 0.0).all() and router.shape == (4,)
    ok, over, exempt = common.judge_gaps(controls, router, 0.1, 0.0)
    assert not ok and over == 4 and exempt == 0


def test_the_configuration_file_is_the_published_config_but_for_its_depth():
    """Every key of the catalog's ``config`` as published, ``sa_config``
    included, at the file's top level and in the ``model`` block the harness
    reads; only the depth is cut."""
    config = _config("perfbench/configs")
    assert config["reduced"] == ["num_hidden_layers"]
    model = config["model"]
    assert all(config[k] == v for k, v in model.items())
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936,
    }
    assert {k: model[k] for k in published} == published
    assert model["rope_scaling"] == {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"}
    assert model["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert model["num_hidden_layers"] >= 4
    assert {"qk_head_norm", "indexer", "indexer_rotary", "index_key_dtype", "slot_length", "weights"} <= set(config["assumed"])
    assert config["serving"] == {"num_slots": 8, "max_seq_len": 32768, "kv_page_size": 16,
                                 "engine": "ServingEngine defaults, as codegen2-7b-serve"}
    assert config["reference_check"]["sample_quantiles"] == [0.0] and config["reference_check"]["max_answer_tokens"] == 366


def test_the_cells_traffic_is_the_issues():
    from perfbench import tape

    traffic = tape.load_traffic("longdocs_closed")
    assert traffic["loop"] == "closed" and traffic["block"] == 8 and traffic["ramp_s"] == 8
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 12288, "sigma": 0.5, "min": 4096, "max": 24576}
    assert traffic["answer_len"] == {"dist": "lognormal", "median": 384, "sigma": 0.3, "min": 192, "max": 640}
    pairs = sorted(tape.block_lengths(traffic))
    assert [p for p, _ in pairs] == [5706, 7886, 9624, 11359, 13294, 15690, 19148, 24576]
    assert max(p + a for p, a in pairs) <= traffic["max_total"] == 25216
    held = sum(p + a / 2 for p, a in pairs)
    assert 100_000 < held < 115_000                              # ~107k tokens in the slots
    assert 0.12 < sum(min(p, 2048) for p, _ in pairs) / sum(p for p, _ in pairs) < 0.20


@pytest.mark.parametrize("kept, correct", [("the configuration's columns", True), ("half of them", False)])
def test_the_harness_own_comparison_tells_a_system_that_keeps_half_the_columns(monkeypatch, kept, correct):
    """``serve._reference_check`` itself, at the stand-in's size: the system
    as configured is ``correct``; the same weights and prompt served by a
    system built to keep half the columns are not (the reference keeps the
    configuration's). On the chip: ``reference_check.why`` and PERF.md."""
    import copy

    from perfbench import tape
    from perfbench.families import keye_vl2 as family
    from perfbench.runners import serve
    from perfbench.spans import Spans

    config = _config("tests/benchmark/data/configs")
    traffic = tape.load_traffic("longdocs_closed", os.path.join(ROOT, "tests/benchmark/data/traffic"))
    if not correct:
        plain = family.build

        def half(cfg, **kw):
            cfg = copy.deepcopy(cfg)
            cfg["sa_config"]["topk"] //= 2
            return plain(cfg, **kw)

        monkeypatch.setattr(family, "build", half)
    said = []
    engine, fam, params, vocab = serve.build(config, 7, Spans(), said.append)
    ok, sampled, failed = serve._reference_check(engine, fam, config, params, traffic, vocab, 7, said.append)
    assert (ok, sampled, failed) == (correct, 1, 0), said
