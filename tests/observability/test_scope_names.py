"""The names a trace reader finds things by (ISSUE 24), pinned on the
programs of the benchmark's rehearsal configurations: the jitted programs,
the scope a Pallas kernel is named after, and the named scopes on the model
step. A refactor that renames one silently empties a per-layer metric
(``perfbench/layer_metrics``: ``decode_step_dev_ms`` finds ``jit_chunk_fn``,
``paged_decode_roofline`` the kernel ``attn._cached_attention``,
``kv_view_dev_share_pct`` the scope ``kv_view``, ...)."""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.observability.programs import module_name
from neuronx_distributed_tpu.serving import ServingEngine
from tests.serving.span_spy import overhear

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "benchmark", "data", "configs")
OP_NAME = re.compile(r'op_name="([^"]+)"')
LOCATION = re.compile(r'loc\("([^"]+)"')
KERNEL_CALL = re.compile(r'loc\("([^"]*)/pallas_call"')


def _programs(config_name):
    """``{"decode_chunk" | "prefill": (lowered text with locations, optimized
    HLO text)}`` of a rehearsal configuration's engine, on the fused paged
    path the chip runs (its kernel interpreted, as everywhere in this suite)."""
    with open(os.path.join(TINY, config_name + ".json")) as f:
        config = json.load(f)
    family = importlib.import_module(f"perfbench.families.{config['family']}")
    serving = config["serving"]
    model = family.build(config["model"], runner="serve", max_seq_len=int(serving["max_seq_len"]))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = ServingEngine(model, params, num_slots=int(serving["num_slots"]),
                           kv_page_size=int(serving["kv_page_size"]), paged_attention="fused")
    engine.submit(np.arange(1, 20, dtype=np.int32), GenerationConfig(max_new_tokens=3, temperature=0.0))
    engine.run()
    out = {}
    for name, info in engine.programs.programs().items():
        key = name.split("[")[0]                   # prefill[<bucket>]
        if key in ("decode_chunk", "prefill"):
            lowered = info.variants[0].lower()
            out[key] = (lowered.as_text(debug_info=True), lowered.compile().as_text())
    # what the ``nxd.program`` span around each call says its module is
    out["modules"] = {"decode_chunk": module_name(engine._decode_chunk.__wrapped__),
                      "prefill": module_name(next(iter(engine._prefill_fns.values())).__wrapped__)}
    return out


def _traced(program):
    """Scope components in the program as traced (its locations)."""
    return {part for path in LOCATION.findall(program[0]) for part in path.split("/")}


def _optimized(program):
    """Scope components that survive into the optimized HLO's ``op_name``s
    (a fusion keeps one instruction's, so fewer than were traced)."""
    return {part for path in OP_NAME.findall(program[1]) for part in path.split("/")}


@pytest.fixture(scope="module")
def codegen():
    return _programs("codegen2-7b-serve")


@pytest.fixture(scope="module")
def mixtral():
    return _programs("mixtral-8x7b-serve")


def test_programs_keep_their_jit_names(codegen, mixtral):
    for programs in (codegen, mixtral):
        assert re.search(r"HloModule (\S+?),", programs["decode_chunk"][1]).group(1) == "jit_chunk_fn"
        assert re.search(r"HloModule (\S+?),", programs["prefill"][1]).group(1) == "jit_fn"
        # and the ``module`` stat of the call's ``nxd.program`` span says the same:
        # ``perfbench/chunk_gaps.py`` joins the device's runs to their calls by it
        assert programs["modules"] == {"decode_chunk": "jit_chunk_fn", "prefill": "jit_fn"}


def test_a_kernel_is_still_called_in_the_attention_scope(codegen, mixtral):
    """XLA names a Pallas call after the innermost scope it is traced in:
    no new scope may come between ``attn._cached_attention`` and the call."""
    for programs in (codegen, mixtral):
        callers = {path.rsplit("/", 1)[-1] for path in KERNEL_CALL.findall(programs["decode_chunk"][0])}
        assert callers == {"attn._cached_attention"}


def test_named_scopes_reach_the_optimized_hlo(codegen, mixtral):
    for programs in (codegen, mixtral):
        assert {"kv_view", "sample", "lm_head", "attn"} <= _traced(programs["decode_chunk"])
        assert {"kv_view", "sample", "lm_head"} <= _optimized(programs["decode_chunk"])
    assert "mlp" in _optimized(codegen["decode_chunk"])
    moe = {"moe", "moe.router", "moe.dispatch", "moe.experts", "moe.combine"}
    assert moe <= _optimized(mixtral["decode_chunk"])
    # the rehearsal's four experts prefill through the dense strategy, whose
    # einsums hold dispatch and combine: one scope
    assert {"moe", "moe.router", "moe.experts"} <= _optimized(mixtral["prefill"])


@pytest.fixture(scope="module")
def deepseek():
    return _programs("deepseek-v2-lite-serve")


def test_the_latent_attention_programs_carry_their_names(deepseek):
    """``mla_block_dev_share_pct`` finds the scopes ``mla.compress``,
    ``mla.absorb`` (decode) and ``mla.expand`` (prefill), ``moe_block_dev_share_pct``
    the shared experts under ``moe``; both MLA kernels (the paged latent one
    in decode, flash in prefill on the chip) are called from
    ``attn._cached_attention`` with no scope between."""
    decode, prefill = deepseek["decode_chunk"], deepseek["prefill"]
    assert re.search(r"HloModule (\S+?),", decode[1]).group(1) == "jit_chunk_fn"
    callers = {path.rsplit("/", 1)[-1] for path in KERNEL_CALL.findall(decode[0])}
    assert callers == {"attn._cached_attention"}
    assert {"mla.compress", "mla.absorb", "attn", "kv_view", "moe", "moe.shared"} <= _traced(decode)
    assert {"mla.compress", "mla.absorb", "moe", "moe.shared", "moe.experts"} <= _optimized(decode)
    assert "mla.expand" not in _traced(decode)             # decode never expands W_kv_b over the cache
    assert {"mla.compress", "mla.expand", "moe.shared"} <= _optimized(prefill)
    assert "mla.absorb" not in _traced(prefill)
    # the attention call itself sits outside the mla.* scopes, in the method
    assert any(path.endswith("attn._cached_attention/pallas_call") or "attn._cached_attention" in path
               for path in LOCATION.findall(prefill[0]))


@pytest.fixture(scope="module")
def keye():
    return _programs("keye-vl2-30b-a3b-serve")


def test_the_sparse_attention_programs_carry_their_names(keye):
    """``dsa_block_dev_share_pct`` finds the scopes ``dsa.index`` (the index
    projections, LayerNorm, rotary, the ``k_idx`` write), ``dsa.score``,
    ``dsa.select`` and ``dsa.attend``; each decode kernel is called in ITS
    scope and so carries its name (``dsa_index_roofline`` reads ``dsa.score``,
    ``dsa_attend_roofline`` ``dsa.attend``; ``dsa.write`` is the window's page
    copies into the K and V pools)."""
    decode, prefill = keye["decode_chunk"], keye["prefill"]
    assert re.search(r"HloModule (\S+?),", decode[1]).group(1) == "jit_chunk_fn"
    assert re.search(r"HloModule (\S+?),", prefill[1]).group(1) == "jit_fn"
    callers = {path.rsplit("/", 1)[-1] for path in KERNEL_CALL.findall(decode[0])}
    assert callers == {"dsa.score", "dsa.attend", "dsa.write"}
    scopes = {"dsa.index", "dsa.score", "dsa.select", "dsa.attend"}
    assert scopes | {"attn", "kv_view", "moe", "sample", "lm_head"} <= _traced(decode)
    assert scopes | {"moe", "moe.experts"} <= _optimized(decode)
    # prefill: the scores and the mask under dsa.score, attention under dsa.attend
    assert {"dsa.index", "dsa.score", "dsa.attend", "moe"} <= _traced(prefill)
    assert {"dsa.index", "dsa.score"} <= _optimized(prefill)
    assert "dsa.select" not in _traced(prefill)            # a threshold, not a top_k


def test_the_dispatch_span_carries_the_selection_stats():
    """``dsa_selected_share_pct`` reads ``selected_tokens`` / ``ctx_tokens`` off
    ``nxd.step.decode.dispatch``: host arithmetic from the slots' lengths; a
    model without an indexer has neither."""
    from neuronx_distributed_tpu.models.keye_vl2 import KeyeVL2ForCausalLM, tiny_keye_vl2
    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM, tiny_mixtral

    def stats(model, prompts):
        params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=16)
        for p in prompts:
            engine.submit(np.arange(1, 1 + p, dtype=np.int32), GenerationConfig(max_new_tokens=2, temperature=0.0))
        seen = overhear(engine, "nxd.step.decode.dispatch")
        engine.run()
        # the first chunk: each slot holds its prompt and the prefill's token
        return {k: v for k, v in seen[0].items() if k in ("ctx_tokens", "selected_tokens")}

    got = stats(KeyeVL2ForCausalLM(tiny_keye_vl2(), attention_impl="xla"), (40, 9))
    assert got == {"ctx_tokens": 41 + 10, "selected_tokens": 16 + 10}
    assert stats(MixtralForCausalLM(tiny_mixtral(), attention_impl="xla"), (12,)) == {}


@pytest.fixture(scope="module")
def glm():
    return _programs("glm-5-serve")


def test_the_sparse_latent_attention_programs_carry_both_blocks_names(glm):
    """GLM-5's programs answer to BOTH sets of readers: ``mla_block_dev_share_pct``
    finds ``mla.compress``, ``mla.absorb`` (decode) and ``mla.expand``
    (prefill); ``dsa_block_dev_share_pct`` finds ``dsa.index``, ``dsa.score``,
    ``dsa.select``, ``dsa.attend`` and ``dsa.write``, each decode kernel
    called in ITS scope (``dsa_index_roofline`` reads ``dsa.score``,
    ``dsa_latent_attend_roofline`` ``dsa.attend``, no ``attn._cached_attention``
    between); ``moe_block_dev_share_pct.tpot`` the held experts' path and the
    shared expert under ``moe``."""
    decode, prefill = glm["decode_chunk"], glm["prefill"]
    assert re.search(r"HloModule (\S+?),", decode[1]).group(1) == "jit_chunk_fn"
    assert re.search(r"HloModule (\S+?),", prefill[1]).group(1) == "jit_fn"
    callers = {path.rsplit("/", 1)[-1] for path in KERNEL_CALL.findall(decode[0])}
    assert callers == {"dsa.score", "dsa.attend", "dsa.write"}
    dsa = {"dsa.index", "dsa.score", "dsa.select", "dsa.attend"}
    assert dsa | {"mla.compress", "mla.absorb", "attn", "kv_view", "moe", "moe.router", "moe.dispatch",
                  "moe.experts", "moe.shared", "sample", "lm_head"} <= _traced(decode)
    assert dsa | {"mla.compress", "mla.absorb", "moe", "moe.shared", "moe.experts"} <= _optimized(decode)
    assert "mla.expand" not in _traced(decode) and "attn._cached_attention" not in _traced(decode)
    assert {"dsa.index", "dsa.score", "dsa.attend", "mla.compress", "mla.expand", "moe", "moe.router",
            "moe.dispatch", "moe.experts", "moe.combine", "moe.shared"} <= _traced(prefill)
    assert {"dsa.index", "dsa.score", "mla.compress", "mla.expand", "moe.shared"} <= _optimized(prefill)
    assert "dsa.select" not in _traced(prefill) and "mla.absorb" not in _traced(prefill)


def test_the_readback_span_carries_the_rows_the_held_experts_computed(tmp_path):
    """``moe_held_rows_per_step`` reads ``held_rows`` / ``routed_rows`` off
    ``nxd.step.decode.readback`` beside ``steps``: summed on the device over
    the chunk's steps and sparse layers, read back with its tokens; a model
    whose expert layers hold every expert has neither."""
    import dataclasses

    from neuronx_distributed_tpu.models.glm_moe_dsa import GlmMoeDsaForCausalLM, tiny_glm_moe_dsa
    from neuronx_distributed_tpu.utils.timeline import Timeline

    def stats(cfg, tmp):
        model = GlmMoeDsaForCausalLM(cfg, attention_impl="xla")
        params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        timeline = Timeline(str(tmp))
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=16, decode_chunk_size=4, timeline=timeline)
        for p in (20, 9):
            engine.submit(np.arange(1, 1 + p, dtype=np.int32), GenerationConfig(max_new_tokens=6, temperature=0.0))
        engine.run()
        return [e["args"] for e in timeline._events if e.get("name") == "nxd.step.decode.readback" and e.get("args")]

    cfg = tiny_glm_moe_dsa()
    held = stats(dataclasses.replace(cfg, held_experts=(4, 8)), tmp_path / "held.json")
    assert held and all(set(a) == {"steps", "held_rows", "routed_rows"} for a in held)
    sparse_layers = cfg.num_layers - cfg.first_k_dense
    for a in held:
        assert a["routed_rows"] == a["steps"] * sparse_layers * 2 * cfg.top_k
        assert 0 <= a["held_rows"] <= a["routed_rows"]
    assert sum(a["held_rows"] for a in held) > 0
    assert all(set(a) == {"steps"} for a in stats(cfg, tmp_path / "whole.json"))


@pytest.fixture(scope="module")
def trinity():
    return _programs("trinity-large-serve")


def test_the_window_and_full_attention_programs_carry_their_kinds_names(trinity):
    """``window_attn_dev_share_pct`` finds the scope ``attn.window``,
    ``full_attn_dev_share_pct`` ``attn.full``; each kind's decode kernels (the
    walk over the blocks a slot maps, the write window's page copies) are
    called in ITS scope and so carry its name (``swa_decode_roofline`` reads
    both), no ``attn._cached_attention`` and no ``kv_view`` between; the gate's
    projection and product lie under ``attn.gate``; the held experts' path and
    the shared expert under ``moe``."""
    decode, prefill = trinity["decode_chunk"], trinity["prefill"]
    assert re.search(r"HloModule (\S+?),", decode[1]).group(1) == "jit_chunk_fn"
    assert re.search(r"HloModule (\S+?),", prefill[1]).group(1) == "jit_fn"
    callers = {path.rsplit("/", 1)[-1] for path in KERNEL_CALL.findall(decode[0])}
    assert callers == {"attn.window", "attn.full"}
    kinds = {"attn.window", "attn.full", "attn.gate"}
    assert kinds | {"attn", "kv_view", "moe", "moe.router", "moe.experts", "moe.shared",
                    "sample", "lm_head"} <= _traced(decode)
    assert kinds | {"moe", "moe.shared"} <= _optimized(decode)
    assert kinds | {"moe", "moe.shared"} <= _traced(prefill)
    assert {"attn.window", "attn.full"} <= _optimized(prefill)


@pytest.fixture(scope="module")
def zaya():
    return _programs("zaya1-8b-serve")


def test_the_compressed_latent_attention_programs_carry_their_names(zaya):
    """``cca_block_dev_share_pct`` finds the scope ``attn.cca``,
    ``cca_conv_dev_share_pct`` ``attn.cca.conv`` (the convolutions and the
    per-slot state's read and write: no kernel is called there),
    ``cca_decode_roofline`` the decode kernels called in ``attn.cca.attend``
    (the walk over the blocks a slot maps and the write window's page copies),
    no ``attn._cached_attention`` between; ``router_mlp_dev_share_pct`` the
    router's MLP under ``moe.router``; the tied head under ``lm_head``."""
    decode, prefill = zaya["decode_chunk"], zaya["prefill"]
    assert re.search(r"HloModule (\S+?),", decode[1]).group(1) == "jit_chunk_fn"
    assert re.search(r"HloModule (\S+?),", prefill[1]).group(1) == "jit_fn"
    assert {path.rsplit("/", 1)[-1] for path in KERNEL_CALL.findall(decode[0])} == {"attn.cca.attend"}
    cca = {"attn.cca", "attn.cca.project", "attn.cca.conv", "attn.cca.attend"}
    assert cca | {"attn", "kv_view", "moe", "moe.router", "moe.experts", "sample", "lm_head"} <= _traced(decode)
    assert cca | {"moe", "moe.router", "lm_head"} <= _optimized(decode)
    assert cca | {"moe", "moe.router"} <= _traced(prefill)
    assert {"attn.cca", "attn.cca.conv"} <= _optimized(prefill)


@pytest.fixture(scope="module")
def solar():
    return _programs("solar-open2-250b-serve")


def test_the_linear_attention_programs_carry_their_names(solar):
    """``kda_block_dev_share_pct`` finds the scope ``attn.kda``,
    ``kda_recur_dev_share_pct`` and both rooflines ``attn.kda.recur``: the
    decode chunk's one-token state update is a Pallas kernel called there and
    named after it (the prefill's chunked forward likewise on the chip; off it
    this rehearsal's prefill runs the recurrence under ``lax.scan`` in the same
    scope); the GQA layers' walking kernel is called in ``attn.full``, their
    gate under ``attn.gate``, as Trinity's full layers'."""
    decode, prefill = solar["decode_chunk"], solar["prefill"]
    assert re.search(r"HloModule (\S+?),", decode[1]).group(1) == "jit_chunk_fn"
    assert re.search(r"HloModule (\S+?),", prefill[1]).group(1) == "jit_fn"
    assert {path.rsplit("/", 1)[-1] for path in KERNEL_CALL.findall(decode[0])} == {"attn.kda.recur", "attn.full"}
    kda = {"attn.kda", "attn.kda.project", "attn.kda.conv", "attn.kda.gate", "attn.kda.recur", "attn.kda.out"}
    assert kda | {"attn.full", "attn.gate", "kv_view", "moe", "moe.router", "moe.experts", "sample", "lm_head"} <= _traced(decode)
    assert kda | {"attn.gate", "moe", "moe.router", "lm_head"} <= _optimized(decode)
    assert kda | {"attn.full", "attn.gate", "moe", "moe.router"} <= _traced(prefill)
    assert {"attn.kda", "attn.kda.project", "attn.kda.recur", "attn.kda.out"} <= _optimized(prefill)


@pytest.fixture(scope="module")
def ouro():
    return _programs("ouro-2.6b-serve")


def test_the_looped_stacks_programs_carry_their_names(ouro):
    """``loop_pass_dev_share_pct`` and ``loop_weight_stream_roofline`` find the
    scope ``loop.pass`` around every pass of the stack (the constants are
    ``modules/attention.py``'s, beside the other ``*_SCOPE`` names), and
    ``attn.full`` INSIDE it, innermost: the walking kernel and the window
    pages' copies are called there and named after it, under the pass's cache
    node ``pass_<t>`` (``full_attn_dev_share_pct``, ``swa_decode_roofline``);
    what lies under ``loop.pass`` and outside ``attn.full`` is the weight
    stream."""
    from neuronx_distributed_tpu.modules.attention import ATTN_FULL_SCOPE, LOOP_PASS_NODE, LOOP_PASS_SCOPE

    assert (LOOP_PASS_SCOPE, ATTN_FULL_SCOPE, LOOP_PASS_NODE) == ("loop.pass", "attn.full", "pass_")
    decode, prefill = ouro["decode_chunk"], ouro["prefill"]
    assert re.search(r"HloModule (\S+?),", decode[1]).group(1) == "jit_chunk_fn"
    assert re.search(r"HloModule (\S+?),", prefill[1]).group(1) == "jit_fn"
    calls = KERNEL_CALL.findall(decode[0])
    assert {path.rsplit("/", 1)[-1] for path in calls} == {"attn.full"}
    # 2 layers x 3 passes: every node's kernels under loop.pass and its own pass node
    assert len(calls) >= 6 and all("loop.pass" in path.split("/") for path in calls)
    assert {part for path in calls for part in path.split("/") if part.startswith("pass_")} == {"pass_0", "pass_1", "pass_2"}
    names = {"loop.pass", "attn.full", "kv_view", "mlp", "lm_head", "sample"}
    assert names <= _traced(decode) and {"loop.pass", "mlp", "lm_head"} <= _optimized(decode)
    assert {"loop.pass", "attn.full", "mlp"} <= _traced(prefill) and {"loop.pass", "mlp"} <= _optimized(prefill)
    # the head and the sampler lie outside the passes
    outside = [path for path in LOCATION.findall(decode[0]) if "lm_head" in path.split("/")]
    assert outside and not any("loop.pass" in path.split("/") for path in outside)
