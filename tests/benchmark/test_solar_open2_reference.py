"""The plain Solar Open 2 reference against the program's model class, tiny, on
the CPU; the configuration's file against the catalog; the cell's traffic and
its entries in ``BENCHMARK.json``; the cell's readers on a hand-made trace."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench import kda_costs, tape
from perfbench import program_spans as ps
from perfbench import run as harness
from perfbench.references.solar_open2 import Reference, layer_kinds
from tests.benchmark.test_program_spans import _device, _host, _run_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME, CELL = "solar-open2-250b-serve", "solar2_analysis_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("kda_block_dev_share_pct", "kda_recur_dev_share_pct", "kda_decode_roofline", "kda_prefill_roofline",
           "recurrent_state_bytes_per_layer")


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
    return config["model"], model, params


def test_reference_logits_match_the_model_with_every_mechanism_present(tiny):
    """Four layers (GQA, linear, linear, GQA), 4 / 2 heads of 16, 4 of 16
    experts held top-2 with a shared expert, a vocabulary slice; the
    reference's linear layer is the token-by-token recurrence."""
    cfg, model, params = tiny
    assert layer_kinds(cfg) == ["full", "linear", "linear", "full"] and model.config.held_experts == (0, 4)
    ids = np.random.default_rng(0).integers(1, 256, (2, 90)).astype(np.int32)
    logits, _ = model.apply(params, jnp.asarray(ids))
    ref = Reference(cfg, params)
    got, margin = ref.logits_and_router_margin(ids)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32       # the head in blocks, on the host
    np.testing.assert_allclose(got, np.asarray(logits, np.float32), atol=2e-4, rtol=2e-4)
    # the margin is a difference of router LOGITS, infinite where neither the 2nd nor the 3rd choice is held
    assert margin.shape == (2, 90) and float(margin.min()) >= 0.0 and np.isinf(margin).any() and np.isfinite(margin).any()
    # one mixer alone, as ``chip_smoke.py --only solar`` compares it
    x = ref.embed(ids)
    assert ref.mixer_part(0, x).shape == ref.mixer_part(1, x).shape == (2, 90, 64)
    for control in ({"decay": False}, {"beta_factor": 1.0}, {"conv": False}, {"state_dtype": jnp.bfloat16}):
        other = np.asarray(Reference(cfg, params, **control).mixer_part(1, x))
        assert np.isfinite(other).all() and not np.allclose(other, np.asarray(ref.mixer_part(1, x)), atol=1e-5)


def test_the_configuration_holds_every_published_key_but_the_three_cuts():
    config = _config("perfbench/configs")
    with open(CATALOG) as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "Solar-Open2-250B")
    assert config["source"] == published["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for group in (config, config["model"]):
        for key, value in published["config"].items():
            if key not in config["reduced"]:
                assert group[key] == value, key
    m = config["model"]
    assert (m["num_hidden_layers"], m["num_hidden_layers_published"]) == (8, 48)
    assert (m["n_routed_experts"], m["n_routed_experts_published"], m["first_held_expert"]) == (10, 320, 0)
    assert (m["vocab_size"], m["vocab_size_published"]) == (24576, 196608)
    assert (m["hidden_size"], m["moe_intermediate_size"], m["head_dim"], m["kda_low_rank"]) == (4096, 1280, 128, 128)
    assert {"state_dtype", "conv_silu_norm", "low_rank", "beta", "decay_init", "gates", "routing", "no_qk_norm",
            "slot_length", "intermediate_size", "qk_init_gain", "weights"} <= set(config["assumed"])
    assert "as remembered" in config["assumed"]["conv_silu_norm"]
    assert "32 chips" in config["deployment"] and "6" in config["deployment"]
    built = importlib.import_module("perfbench.families.solar_open2").build(
        dict(m, vocab_size=256, num_hidden_layers=5), runner="serve", max_seq_len=64).config
    assert built.gqa_layers == (0, 4) and built.held_experts == (0, 10) and built.num_experts == 320
    assert (built.dt_range, built.qk_init_gain) == (tuple(m["dt_range"]), m["qk_init_gain"])
    assert config["serving"] == {**config["serving"], "num_slots": 16, "max_seq_len": 32768, "kv_page_size": 16}
    check = config["reference_check"]
    assert {"sample_quantiles", "max_answer_tokens", "logit_tolerance", "router_near_tie", "why"} <= set(check)
    assert check["max_answer_tokens"] == 512


def test_the_cells_traffic_and_entries_are_the_issues():
    traffic = tape.load_traffic("analysis_closed")
    assert (traffic["loop"], traffic["block"], traffic["ramp_s"], traffic["overload_backlog"], traffic["max_total"]) == (
        "closed", 16, 8, 4, 11264)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 3072, "sigma": 0.6, "min": 1024, "max": 8192}
    pairs = sorted(tape.block_lengths(traffic))
    assert len(pairs) == 16 and all(1024 <= p <= 8192 and p + a <= 11264 for p, a in pairs)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "analysis_closed", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1]["name"] == NAME      # appended, nothing moved
    assert {m["name"] for m in harness.metrics_of_cell(bench, "end_to_end", CELL)} == {"tpot_mean_ms", "setup_s"}
    mine = {m["name"]: m for m in harness.metrics_of_cell(bench, "per_layer", CELL)}
    for name in READERS:
        assert mine[name]["workloads"] == [CELL] and mine[name]["moves"] == "tpot_mean_ms"
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(READERS)
    assert {"moe_block_dev_share_pct.tpot", "moe_held_rows_per_step", "full_attn_dev_share_pct", "swa_decode_roofline", "swa_prefill_roofline", "cursor_high_water_pct",
            "decode_step_dev_ms", "device_idle_pct.serve", "prefill_stall_pct"} <= set(mine)


# op metadata: id -> (name, op_name path, program id); program 5 is the decode chunk, 7 a prefill
_L = "jit(chunk_fn)/while/body/model/layers_1/attn.kda/linear_attn/"
OPS = {
    1: ("fusion.1", _L + "attn.kda.project/dot_general:", 5),
    2: ("fusion.2", _L + "attn.kda.conv/mul:", 5),
    3: ("attn.kda.recur.3", _L + "attn.kda.recur/pallas_call:", 5),
    4: ("fusion.4", "jit(chunk_fn)/while/body/model/layers_1/moe/moe.router/dot_general:", 5),
    5: ("attn.full.5", "jit(chunk_fn)/while/body/model/layers_0/attn/attn.full/pallas_call:", 5),
    6: ("attn.kda.recur.6", "jit(fn)/model/layers_1/attn.kda/linear_attn/attn.kda.recur/pallas_call:", 7),
    20: ("jit_chunk_fn(5)", None, None), 21: ("jit_fn(7)", None, None),
}
GEOMETRY = {"recurrent_layers": 6, "kda_heads": 64, "kda_head_dim": 128}


def test_the_cells_readers_on_a_hand_made_trace():
    """Projections 100 us, convolutions 50, the state update 200, the router
    50, the GQA walk 100 in the chunk; a prefill's chunked forward 300: busy 800 us."""
    ops = [(1, 0, 100), (2, 100, 50), (3, 150, 200), (4, 350, 50), (5, 400, 100), (6, 500, 300)]
    dispatch = ("nxd.step.decode.dispatch", 0, 10, {"active": 2, "slot_state_bytes_per_layer": 4341760,
                                                    "recurrent_layers": 6, "paged_layers": 2})
    run = _run_of(_device(ops, [(20, 0, 500), (21, 500, 500)], OPS), _host([dispatch]))
    read = lambda name: harness.load_reader(name)(run)    # noqa: E731
    assert read("kda_block_dev_share_pct") == pytest.approx(100.0 * (100 + 50 + 200 + 300) / 800)
    assert read("kda_recur_dev_share_pct") == pytest.approx(100.0 * (200 + 300) / 800)
    assert read("full_attn_dev_share_pct") == pytest.approx(100.0 * 100 / 800)
    assert read("recurrent_state_bytes_per_layer") == 4341760
    # a fixed per-slot state beside pages (no recurrent layer) is the other entry's, not this one's
    fixed = _run_of(_device(ops, [(20, 0, 500)], OPS), _host([("nxd.step.decode.dispatch", 0, 10, {"slot_state_bytes_per_layer": 5376})]))
    assert harness.load_reader("recurrent_state_bytes_per_layer")(fixed) is None
    # the rooflines: the chunk's kernel alone (200 us) over two slots' three steps; the prefill's (300 us) over one prompt of 3,000
    run.update(
        trace={"kernel_s_by_module": {"jit_chunk_fn": {"attn.kda.recur.3": 200e-6, "attn.full.5": 100e-6},
                                      "jit_fn": {"attn.kda.recur.6": 300e-6}}},
        counters={"start": {"t": 10.0}, "stop": {"t": 20.0}}, device_kind="TPU v5 lite", geometry=GEOMETRY,
        clients=[{"prompt_len": 1000, "stamps": [9.0, 11.0], "t_first": 9.0},
                 {"prompt_len": 3000, "stamps": [10.5, 12.0, 13.0, 25.0], "t_first": 10.5}])
    _, nbytes = kda_costs.kda_decode_cost(3, heads=64, head_dim=128)
    assert read("kda_decode_roofline") == pytest.approx(100.0 * (6 * nbytes / 819e9) / 200e-6, rel=1e-3)
    _, nbytes = kda_costs.kda_prefill_cost(3000, heads=64, head_dim=128)      # bound by its vectors' bytes too
    assert read("kda_prefill_roofline") == pytest.approx(100.0 * (6 * nbytes / 819e9) / 300e-6, rel=1e-3)


def test_a_program_without_the_scopes_leaves_the_metrics_out():
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
