"""The plain Ouro reference against the program's model class, tiny, on the
CPU; the configuration's file against the catalog; ``loop_costs``; the cell's
traffic and its entries in ``BENCHMARK.json``, every one found by NAME; the
cell's readers on a hand-made trace. (The cell itself is rehearsed on the CPU
by ``test_rehearsal.py``, which takes every entry of ``workloads``.)"""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench import loop_costs, swa_costs, tape
from perfbench import program_spans as ps
from perfbench import run as harness
from perfbench.references.ouro import Reference
from tests.benchmark.test_program_spans import _device, _host, _run_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME, CELL = "ouro-2.6b-serve", "ouro_worked_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("loop_cache_bytes_per_token", "loop_pass_dev_share_pct", "loop_weight_stream_roofline")


def _config(directory):
    with open(os.path.join(ROOT, directory, f"{NAME}.json")) as f:
        return json.load(f)


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def tiny():
    config = _config("tests/benchmark/data/configs")
    family = importlib.import_module(f"perfbench.families.{config['family']}")
    model = family.build(config["model"], runner="train", max_seq_len=256)
    model = model.clone(config=model.config.__class__(**{**model.config.__dict__, "dtype": jnp.float32}),
                        attention_impl="xla")
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(3), jnp.zeros((2, 16), jnp.int32)))
    return config["model"], model, params


def test_reference_logits_and_exit_distribution_match_the_model(tiny):
    """Two layers run three times (passes != layers), MHA, the gate: the
    reference's full forward of every pass, fed the model's weights a layer at
    a time; its controls are seen to differ."""
    cfg, model, params = tiny
    assert (model.config.num_layers, model.config.total_ut_steps, model.config.kv_cache_nodes) == (2, 3, 6)
    ids = np.random.default_rng(0).integers(1, 256, (2, 70)).astype(np.int32)
    logits, aux = model.apply(params, jnp.asarray(ids))
    ref = Reference(cfg, params)
    got = ref.logits(ids)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32       # the head in blocks, on the host
    np.testing.assert_allclose(got, np.asarray(logits, np.float32), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(ref.exit_distribution(ids), np.asarray(aux["exit_distribution"]), atol=1e-5)
    assert len(ref.pass_outputs(ids)) == 3
    for control in ({"shared_cache": True}, {"passes": 2}, {"dtype": jnp.float8_e4m3fn}, {"kv_dtype": jnp.float8_e4m3fn}):
        other = Reference(cfg, params, **control).logits(ids)
        assert np.isfinite(other).all() and not np.allclose(other, got, atol=1e-3)


def test_the_configuration_holds_every_published_key_but_the_depth():
    config = _config("perfbench/configs")
    with open(CATALOG) as f:
        published = next(e for e in map(json.loads, f) if e["name"] == "Ouro-2.6B")
    assert config["source"] == published["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    for group in (config, config["model"]):
        for key, value in published["config"].items():
            if key not in config["reduced"]:
                assert group[key] == value, key
    m = config["model"]
    depth = m["num_hidden_layers"]
    assert depth in (16, 24) and m["num_hidden_layers_published"] == 48
    assert m["layer_types"] == ["full_attention"] * depth
    assert (m["total_ut_steps"], m["early_exit_threshold"]) == (4, 1)       # every pass is run: the loop is never cut
    assert (m["hidden_size"], m["intermediate_size"], m["head_dim"], m["num_attention_heads"],
            m["num_key_value_heads"], m["vocab_size"]) == (2048, 5632, 128, 16, 16, 49152)
    assert {"sandwich_norm", "final_norm_every_pass", "exit_gate", "no_bias_no_head_norm", "rotary",
            "slot_length", "cache_leaf", "weights"} <= set(config["assumed"])
    assert "as remembered" in config["assumed"]["sandwich_norm"]
    assert "four passes" in config["deployment"] and str(depth) in config["deployment"]
    assert set(config["reduced_why"]) == {"num_hidden_layers", "layer_types"}
    family = importlib.import_module("perfbench.families.ouro")
    built = family.build(dict(m, vocab_size=256), runner="serve", max_seq_len=64).config
    assert (built.num_layers, built.total_ut_steps, built.kv_cache_nodes) == (depth, 4, 4 * depth)
    assert built.param_dtype == jnp.bfloat16 and built.kv_cache_kind == "joined"
    g = family.geometry(m)
    assert (g["full_layers"], g["window_layers"], g["window"], g["passes"]) == (4 * depth, 0, None, 4)
    # the needed bytes of a decode step's attention: 8 KiB a token a NODE
    _, nbytes = swa_costs.layers_cost(swa_costs.swa_decode_cost, g, [1000])
    assert nbytes == 4 * depth * (1000 * 8192 + 2 * 16 * 128 * 2)
    serving = config["serving"]
    assert (serving["num_slots"], serving["kv_page_size"]) == (2, 16) and serving["max_seq_len"] % 128 == 0
    check = config["reference_check"]
    assert {"sample_quantiles", "max_answer_tokens", "logit_tolerance", "why"} <= set(check)
    assert "float8" in check["why"] and "share" in check["why"]
    for refused in ({"rope_scaling": {"type": "yarn"}}, {"use_sliding_window": True}, {"tie_word_embeddings": True},
                    {"early_exit_threshold": 0.5}, {"layer_types": ["full_attention"]}):
        with pytest.raises(ValueError):
            family.build({**m, "vocab_size": 256, **refused}, runner="serve", max_seq_len=64)


def test_loop_costs_count_every_weight_once_a_pass():
    g = {"num_layers": 24, "passes": 4, "hidden": 2048, "intermediate": 5632, "num_q_heads": 16, "num_kv_heads": 16,
         "head_dim": 128}
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert loop_costs.layer_weight_params(g) == layer == 51_388_416
    assert loop_costs.stack_weight_bytes(g) == 24 * layer * 2                 # held ONCE: 2.47 GB
    flops, nbytes = loop_costs.loop_decode_cost(10, 2, g)
    assert nbytes == 10 * 4 * (24 * layer + 2048) * 2                          # read once a PASS: 9.9 GB a step
    assert flops == 2.0 * 10 * 2 * 4 * (24 * layer + 2048)
    # grouped-query heads: k and v are Hkv wide
    gqa = dict(g, num_kv_heads=4)
    assert loop_costs.layer_weight_params(gqa) == layer - 2 * 2048 * 12 * 128
    # bound by memory at two rows: 2 operations a byte
    assert flops / nbytes == 2.0


def test_the_cells_traffic_and_entries_are_the_issues():
    traffic = tape.load_traffic("worked_closed")
    assert (traffic["loop"], traffic["block"], traffic["ramp_s"], traffic["overload_backlog"], traffic["max_total"]) == (
        "closed", 32, 8, 4, 3072)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64, "max": 1024}
    assert traffic["answer_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.3, "min": 512, "max": 2048}
    pairs = sorted(tape.block_lengths(traffic))
    prompts, answers = [p for p, _ in pairs], sorted(a for _, a in pairs)
    assert len(pairs) == 32 and (prompts[0], prompts[-1]) == (87, 752) and (answers[0], answers[-1]) == (537, 1954)
    assert all(p + a <= 3072 for p, a in pairs) and {1 << max(p - 1, 0).bit_length() for p in prompts} == {128, 256, 512, 1024}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = _named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "worked_closed", 1)
    entry = _named(bench["configs"], NAME)
    assert entry["file"] == f"perfbench/configs/{NAME}.json" and entry["reduced"] == _config("perfbench/configs")["reduced"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert {m["name"] for m in harness.metrics_of_cell(bench, "end_to_end", CELL)} == {"tpot_mean_ms", "setup_s"}
    mine = {m["name"]: m for m in harness.metrics_of_cell(bench, "per_layer", CELL)}
    for name in READERS:
        assert mine[name]["workloads"] == [CELL] and mine[name]["moves"] == "tpot_mean_ms"
        assert os.path.exists(os.path.join(ROOT, "perfbench", "layer_metrics", f"{name}.py"))
    assert (mine["loop_cache_bytes_per_token"]["source"], mine["loop_pass_dev_share_pct"]["source"],
            mine["loop_weight_stream_roofline"]["source"]) == ("program_counter", "device_trace", "device_trace")
    # every per-layer metric that all the other serving cells report, and the attention's of the joined kind
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL and w["chips"] == 1]
    for m in bench["per_layer"]:
        if "workloads" in m and set(others) <= set(m["workloads"]):
            assert m["name"] in mine, m["name"]
    assert {"full_attn_dev_share_pct", "swa_decode_roofline", "cursor_high_water_pct", "decode_step_dev_ms",
            "device_idle_pct.serve", "prefill_stall_pct", "kv_view_dev_share_pct", "pages_in_runs_share_pct"} <= set(mine)
    # NOT the four that read a PREFILL of the traced 6 s: two slots answer a request every ~6.7 s, so a traced run may
    # hold none (call 2's held none), and a listed metric that a traced run's line lacks refuses the PR (ledger, PR 53)
    assert not {"prefill_dev_ms_per_ktok", "queue_wait_p90_ms", "engine_ttft_p50_ms", "swa_prefill_roofline"} & set(mine)
    assert not {n for n in mine if n.startswith(("moe_", "kda_", "cca_", "dsa_", "mla_", "window_"))}   # no expert, no other kind


# op metadata: id -> (name, op_name path, program id); program 5 is the decode chunk, 7 a prefill
_P = "jit(chunk_fn)/while/body/model/loop.pass/layers_1/"
OPS = {
    1: ("fusion.1", _P + "attn/qkv/dot_general:", 5),
    2: ("fusion.2", _P + "mlp/down/dot_general:", 5),
    3: ("attn.full.3", _P + "attn/pass_2/attn.full/pallas_call:", 5),
    4: ("fusion.4", "jit(chunk_fn)/while/body/lm_head/dot_general:", 5),
    5: ("fusion.5", "jit(fn)/model/loop.pass/layers_0/mlp/up/dot_general:", 7),
    6: ("attn.full.6", "jit(fn)/model/loop.pass/layers_0/attn/pass_0/attn.full/pallas_call:", 7),
    20: ("jit_chunk_fn(5)", None, None), 21: ("jit_fn(7)", None, None),
}
GEOMETRY = {"num_layers": 24, "passes": 4, "hidden": 2048, "intermediate": 5632, "num_q_heads": 16,
            "num_kv_heads": 16, "head_dim": 128, "full_layers": 96, "window_layers": 0, "window": None}


def test_the_cells_readers_on_a_hand_made_trace():
    """In the chunk: projections 100 us, the MLP 200, the walk 100, the head
    50; a prefill's MLP 250 and its flash forward 100: busy 800 us."""
    ops = [(1, 0, 100), (2, 100, 200), (3, 300, 100), (4, 400, 50), (5, 450, 250), (6, 700, 100)]
    dispatch = ("nxd.step.decode.dispatch", 0, 10, {"active": 2, "kv_bytes_per_token_layer": 8192,
                                                    "kv_cache_nodes": 96})
    run = _run_of(_device(ops, [(20, 0, 450), (21, 450, 350)], OPS), _host([dispatch]))
    read = lambda name: harness.load_reader(name)(run)    # noqa: E731
    assert read("loop_cache_bytes_per_token") == 786432
    assert read("loop_pass_dev_share_pct") == pytest.approx(100.0 * (100 + 200 + 100 + 250 + 100) / 800)
    assert read("full_attn_dev_share_pct") == pytest.approx(100.0 * (100 + 100) / 800)
    # the weight stream: the chunk's device time (450 us) less its attention kernel's (100 us) over three steps of two slots
    run.update(trace={"module_s": {"jit_chunk_fn": 450e-6, "jit_fn": 350e-6},
                      "kernel_s_by_module": {"jit_chunk_fn": {"attn.full.3": 100e-6}, "jit_fn": {"attn.full.6": 100e-6}}},
               counters={"start": {"t": 10.0, "steps": 40}, "stop": {"t": 20.0, "steps": 43}},
               device_kind="TPU v5 lite", geometry=GEOMETRY, num_slots=2)
    _, nbytes = loop_costs.loop_decode_cost(3, 2, GEOMETRY)
    assert read("loop_weight_stream_roofline") == pytest.approx(100.0 * (nbytes / 819e9) / 350e-6, rel=1e-3)
    # a span that counts no nodes (another program's) is not this metric's
    other = _run_of(_device(ops, [(20, 0, 450)], OPS), _host([("nxd.step.decode.dispatch", 0, 10, {"kv_bytes_per_token_layer": 8192})]))
    assert harness.load_reader("loop_cache_bytes_per_token")(other) is None


def test_a_program_without_the_scope_leaves_the_metrics_out():
    """The parent of this PR under its benchmark files, another cell's model,
    and a run that left no trace: ``None`` from every reader, nothing raised."""
    plain = {1: ("fusion.1", "jit(chunk_fn)/while/body/model/layers_0/attn/dot_general:", 5), 20: ("jit_chunk_fn(5)", None, None)}
    bare = _run_of(_device([(1, 0, 50)], [(20, 0, 100)], plain),
                   _host([("nxd.step.decode.dispatch", 0, 10, {"active": 2, "kv_bytes_per_token_layer": 4096})]))
    bare.update(trace={"module_s": {"jit_chunk_fn": 1e-4}, "kernel_s_by_module": {}},
                counters={"start": {"t": 0.0, "steps": 1}, "stop": {"t": 1.0, "steps": 9}}, device_kind="TPU v5 lite",
                geometry={"num_layers": 2}, num_slots=2, clients=[])
    for run in (bare, {"trace": {"kernel_s_by_module": {}}, "geometry": {}, ps._CACHE: None}):
        for name in READERS:
            assert harness.load_reader(name)(run) is None, name
