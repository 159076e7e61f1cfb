"""The plain ZAYA1 reference against the program's model class, tiny, on the
CPU; the configuration's file against the catalog; the cell's traffic and its
entries in ``BENCHMARK.json``; ``cca_costs`` on hand-made shapes; the cell's
readers on a hand-made trace."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench import cca_costs, peaks, tape
from perfbench import program_spans as ps
from perfbench import run as harness
from perfbench.references.zaya import Reference, rope_theta
from tests.benchmark.test_program_spans import _device, _host, _run_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME, CELL = "zaya1-8b-serve", "zaya1_reasoning_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HEADS = dict(num_q_heads=8, num_kv_heads=2, head_dim=128)
READERS = ("cca_block_dev_share_pct", "cca_conv_dev_share_pct", "router_mlp_dev_share_pct",
           "cca_decode_roofline", "cca_cache_bytes_per_token", "slot_state_bytes_per_layer")


def _config(directory):
    with open(os.path.join(ROOT, directory, f"{NAME}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    config = _config("tests/benchmark/data/configs")
    family = importlib.import_module(f"perfbench.families.{config['family']}")
    model = family.build(config["model"], runner="train", max_seq_len=256)
    model = model.clone(config=model.config.__class__(**{**model.config.__dict__, "dtype": jnp.float32}),
                        attention_impl="xla")
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(3), jnp.zeros((2, 16), jnp.int32)))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [     # every vector off its initial value
        leaf + 0.2 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1 else leaf for leaf, k in zip(leaves, keys)])
    return config["model"], model, params


def test_reference_logits_match_the_model_with_every_mechanism_present(tiny):
    """Three layers (the router's state passes twice), 4 / 2 heads of 16, the
    convolutions and the value shift, 8 experts top-1 under a bias, the scaled
    merges off their initial values, the tied head over the vocabulary in
    blocks; the reference never shifts anything but the whole sequence."""
    cfg, model, params = tiny
    assert model.config.num_layers == 3 and model.config.slot_state_width == 2 * 96 + 16
    assert rope_theta(cfg) == 5e6 == model.config.rope_theta
    ids = np.random.default_rng(0).integers(1, 256, (2, 90)).astype(np.int32)
    logits, _ = model.apply(params, jnp.asarray(ids))
    got, margin = Reference(cfg, params).logits_and_router_margin(ids)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32       # the head in blocks, on the host
    np.testing.assert_allclose(got, np.asarray(logits, np.float32), atol=2e-4, rtol=2e-4)
    assert margin.shape == (2, 90) and float(margin.min()) >= 0.0
    # one block alone, as ``chip_smoke.py --only zaya`` compares it: the first takes no state, a later one does
    ref = Reference(cfg, params)
    x0, r0, m0, e0 = ref.block(0, ref.embed(ids))
    x1, r1, _, _ = ref.block(1, x0, r0)
    assert x1.shape == x0.shape == (2, 90, 64) and r1.shape == r0.shape == (2, 90, 32) and e0.shape == (2, 90)
    assert not np.allclose(np.asarray(Reference(cfg, params, eda=False).block(1, x0, r0)[1]), np.asarray(r1))


def test_the_configuration_holds_every_published_key_but_the_depth():
    config = _config("perfbench/configs")
    with open(CATALOG) as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "ZAYA1-8B")
    assert config["source"] == published["source_url"]
    assert config["reduced"] == ["num_hidden_layers"]
    for group in (config, config["model"]):
        for key, value in published["config"].items():
            if key not in config["reduced"]:
                assert group[key] == value, key
    m = config["model"]
    assert (m["num_hidden_layers"], m["num_hidden_layers_published"]) == (10, 40)
    assert (m["num_experts"], m["vocab_size"], m["moe_intermediate_size"]) == (16, 262272, 2048)     # every width whole
    assert {"slot_length", "cca", "router", "mod_not_run", "rotary", "initialisation", "weights"} <= set(config["assumed"])
    assert all("as remembered" in config["assumed"][k] for k in ("cca", "router"))
    # what the random weights start the learned pieces at are keys of the file's own, and reach the model
    built = importlib.import_module("perfbench.families.zaya").build(
        dict(m, vocab_size=256, num_hidden_layers=2), runner="serve", max_seq_len=64).config
    assert (built.temperature_init, built.router_bias_init_std, built.moe_branch_scale_init, built.embed_init_std) == (
        m["temperature_init"], m["router_bias_std"], m["moe_branch_scale_init"], m["embed_init_std"]) == (2.25, 0.005, 0.3, 0.02)
    assert "expert_strategy" not in m and built.expert_strategy == "auto"      # 'auto' resolves to the same programs
    assert "four pipeline stages" in config["deployment"]
    assert config["serving"] == {**config["serving"], "num_slots": 32, "max_seq_len": 16384, "kv_page_size": 16}
    check = config["reference_check"]
    assert {"sample_quantiles", "max_answer_tokens", "logit_tolerance", "router_near_tie", "why"} <= set(check)


def test_the_cells_traffic_and_entries_are_the_issues():
    traffic = tape.load_traffic("reasoning_closed")
    assert (traffic["loop"], traffic["block"], traffic["ramp_s"], traffic["overload_backlog"], traffic["max_total"]) == (
        "closed", 32, 8, 4, 8192)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.7, "min": 256, "max": 4096}
    assert traffic["answer_len"] == {"dist": "lognormal", "median": 2048, "sigma": 0.4, "min": 1024, "max": 4096}
    pairs = sorted(tape.block_lengths(traffic))
    assert len(pairs) == 32 and all(256 <= p <= 4096 and 1024 <= a <= 4096 and p + a <= 8192 for p, a in pairs)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reasoning_closed", 1)
    assert {m["name"] for m in harness.metrics_of_cell(bench, "end_to_end", CELL)} == {"tpot_mean_ms", "setup_s"}
    mine = {m["name"]: m for m in harness.metrics_of_cell(bench, "per_layer", CELL)}
    for name in READERS:
        assert mine[name]["workloads"] == [CELL] and mine[name]["moves"] == "tpot_mean_ms"
    assert {"moe_block_dev_share_pct.tpot", "kv_view_dev_share_pct", "cursor_high_water_pct", "decode_step_dev_ms",
            "device_idle_pct.serve", "prefill_stall_pct"} <= set(mine)


def test_decode_reads_a_kib_a_token_and_is_bound_by_memory():
    flops, nbytes = cca_costs.cca_decode_cost([1000, 3000], **HEADS)
    q_io = 2 * 8 * 128 * 2
    assert nbytes == 4000 * 1024 + 2 * q_io            # 2 kv heads x (K + V) x 128 x 2 B = 1,024 B a token
    assert flops == 2.0 * 4000 * 8 * 256
    share, bound = peaks.roofline_share_pct(flops, nbytes, 1e-3, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and 0 < share < 100       # 4 operations a byte
    assert cca_costs.slot_state_bytes(**HEADS) == (1280 + 1280 + 128) * 2 == 5376
    g = importlib.import_module("perfbench.families.zaya").geometry(_config("perfbench/configs")["model"])
    assert (g["num_layers"], g["expert_layers"], g["cca_conv_channels"], g["vocab_size"]) == (10, 10, 1280, 262272)
    assert {k: g[k] for k in HEADS} == HEADS


# op metadata: id -> (name, op_name path, program id); program 5 is the decode chunk, 7 a prefill
OPS = {
    1: ("fusion.1", "jit(chunk_fn)/while/body/model/layers_0/attn.cca/attn/attn.cca.project/dot_general:", 5),
    2: ("fusion.2", "jit(chunk_fn)/while/body/model/layers_0/attn.cca/attn/attn.cca.conv/mul:", 5),
    3: ("attn.cca.attend.3", "jit(chunk_fn)/while/body/model/layers_0/attn.cca/attn/attn.cca.attend/pallas_call:", 5),
    4: ("fusion.4", "jit(chunk_fn)/while/body/model/layers_0/moe/moe.router/dot_general:", 5),
    5: ("fusion.5", "jit(chunk_fn)/while/body/lm_head/dot_general:", 5),
    6: ("attn.cca.attend.6", "jit(fn)/model/layers_0/attn.cca/attn/attn.cca.attend/pallas_call:", 7),
    20: ("jit_chunk_fn(5)", None, None), 21: ("jit_fn(7)", None, None),
}


def test_the_cells_readers_on_a_hand_made_trace():
    """Projection 100 us, convolutions 50, the decode kernel 200, the router
    50, the head 100 in the chunk; a prefill's kernel 300: busy 800 us."""
    ops = [(1, 0, 100), (2, 100, 50), (3, 150, 200), (4, 350, 50), (5, 400, 100), (6, 500, 300)]
    dispatch = ("nxd.step.decode.dispatch", 0, 10, {"active": 2, "kv_bytes_per_token_layer": 1024,
                                                    "slot_state_bytes_per_layer": 5376})
    run = _run_of(_device(ops, [(20, 0, 500), (21, 500, 500)], OPS), _host([dispatch]))
    read = lambda name: harness.load_reader(name)(run)    # noqa: E731
    assert read("cca_block_dev_share_pct") == pytest.approx(100.0 * (100 + 50 + 200 + 300) / 800)
    assert read("cca_conv_dev_share_pct") == pytest.approx(100.0 * 50 / 800)
    assert read("router_mlp_dev_share_pct") == pytest.approx(100.0 * 50 / 800)
    assert read("cca_cache_bytes_per_token") == 1024 and read("slot_state_bytes_per_layer") == 5376
    # the roofline reader: the chunk's kernel alone (200 us), two slots one step each at contexts 1001 and 3001
    run.update(
        trace={"kernel_s_by_module": {"jit_chunk_fn": {"attn.cca.attend.3": 200e-6}, "jit_fn": {"attn.cca.attend.6": 300e-6}}},
        counters={"start": {"t": 10.0}, "stop": {"t": 20.0}}, device_kind="TPU v5 lite",
        geometry={**HEADS, "num_layers": 10, "cca_conv_channels": 1280},
        clients=[{"prompt_len": 1000, "stamps": [9.0, 11.0]}, {"prompt_len": 3000, "stamps": [9.5, 12.0, 25.0]}])
    _, nbytes = cca_costs.cca_decode_cost([1001, 3001], **HEADS)
    assert read("cca_decode_roofline") == pytest.approx(100.0 * (10 * nbytes / 819e9) / 200e-6, rel=1e-3)


def test_a_program_without_the_scopes_or_the_stats_leaves_the_metrics_out():
    """The parent of this PR under its benchmark files, another cell's model,
    and a run that left no trace: ``None`` from every reader, nothing raised."""
    other = {1: ("fusion.1", "jit(chunk_fn)/while/body/model/layers_0/attn/dot_general:", 5), 20: ("jit_chunk_fn(5)", None, None)}
    bare = _run_of(_device([(1, 0, 50)], [(20, 0, 100)], other),
                   _host([("nxd.step.decode.dispatch", 0, 10, {"active": 2})]))
    bare.update(trace={"kernel_s_by_module": {"jit_chunk_fn": {"attention.1": 1e-4}}}, geometry={"num_layers": 2},
                counters={}, clients=[])
    for run in (bare, {"trace": {"kernel_s_by_module": {}}, "geometry": {}, ps._CACHE: None}):
        for name in READERS:
            assert harness.load_reader(name)(run) is None, name
