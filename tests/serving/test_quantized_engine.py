"""Quantized serving (ISSUE 13): ``ServingEngine(quantize=QuantConfig(...))``.

The correctness contract under quantization shifts from bit-identity to a
pinned LOGIT-DIVERGENCE budget: the quantized decode's per-step logits must
stay within a max-KL / top-1-agreement budget of the fp32 stream, and the
greedy short-prompt smoke stays token-identical on the tiny model wherever
fp32's own choice is not a tie that a divergence inside that budget flips.
The serving invariants do NOT shift: one decode program
(``decode_compilations == 1``), the pinned host-sync budgets (re-pinned
with quantization ON in test_host_sync.py), page-pool accounting/CoW, and
the preemption/recovery machinery all hold with quantization enabled.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, generate
from neuronx_distributed_tpu.inference.generate import serving_clones
from neuronx_distributed_tpu.inference.utils import unwrap_logits
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.quantization import (
    QuantConfig,
    quantize_param_tree,
)
from neuronx_distributed_tpu.serving import RequestState, ServingEngine

# the pinned divergence budget: int8 weight quantization of the tiny model
# reads a max KL of 6e-5 to 9e-5 on this file's prompts — the budget leaves
# an order of magnitude of headroom while still catching a broken dequant
# path (which lands orders of magnitude above it)
MAX_KL_BUDGET = 5e-3
TOP1_AGREEMENT_FLOOR = 0.98

PAGE = 16


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _serve(model, params, prompts, gcfg, **kw):
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4, **kw
    )
    reqs = [
        engine.submit(p, gcfg, key=jax.random.PRNGKey(100 + i))
        for i, p in enumerate(prompts)
    ]
    engine.run()
    for r in reqs:
        assert r.state is RequestState.DONE
    return engine, [r.tokens for r in reqs]


def _flips(setup, prompt, ref, toks, gcfg, **kw):
    """The steps at which the quantized engine's greedy token differs from
    fp32's GIVEN fp32's prefix, as the contract counts agreement: after a
    differing step the engine is served ``prompt + ref[:step + 1]`` and
    compared from there, so one flip is one flip and not every token
    after it. Each flip carries the least ``KL(fp32 || q)`` that any
    distribution ``q`` needs to rank the engine's token at or above fp32's
    (``q`` = fp32's with the two tokens' mass split evenly): what the
    pinned budget must cover for the flip to be inside the contract."""
    cfg, model, params = setup
    flips, base = [], 0
    while True:
        d = next((i for i, (a, b) in enumerate(zip(ref[base:], toks))
                  if a != b), None)
        if d is None:
            return flips
        step = base + d
        prefix = np.concatenate([prompt, np.asarray(ref[:step], np.int32)])
        logits = unwrap_logits(model.apply(params, jnp.asarray(prefix)[None]))
        p = np.asarray(jax.nn.softmax(logits[0, -1].astype(jnp.float32)),
                       np.float64)
        p_r, p_q = p[ref[step]], p[toks[d]]
        half = (p_r + p_q) / 2
        flips.append(
            (step, p_r * np.log(p_r / half) + p_q * np.log(p_q / half))
        )
        base = step + 1
        if base == len(ref):
            return flips
        anchored = np.concatenate([prompt, np.asarray(ref[:base], np.int32)])
        _, (toks,) = _serve(
            model, params, [anchored],
            dataclasses.replace(gcfg, max_new_tokens=len(ref) - base), **kw
        )


def test_greedy_smoke_token_identical(setup):
    """Greedy short-prompt smoke on the tiny model: int8 weights, paged
    int8 weights, and int8 weights + int8 KV pages all reproduce the fp32
    stream token for token, but for a step where fp32's top two tokens are
    so close that a divergence inside the pinned KL budget ranks them the
    other way (a random tiny model has such steps; which ones flip is the
    CPU's arithmetic)."""
    cfg, model, params = setup
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(3, 10, dtype=np.int32)]
    gcfg = GenerationConfig(max_new_tokens=12, temperature=0.0)
    _, ref = _serve(model, params, prompts, gcfg)
    for kw in (
        dict(quantize=QuantConfig(weights="int8")),
        dict(quantize=QuantConfig(weights="int8"), kv_page_size=PAGE),
        dict(quantize=QuantConfig(weights="int8", kv="int8"),
             kv_page_size=PAGE),
    ):
        engine, toks = _serve(model, params, prompts, gcfg, **kw)
        assert engine.decode_compilations == 1
        for prompt, r, t in zip(prompts, ref, toks):
            for step, flip_kl in _flips(setup, prompt, r, t, gcfg, **kw):
                assert flip_kl < MAX_KL_BUDGET, (kw, step, flip_kl, r, t)


def test_logit_divergence_budget(setup):
    """THE pinned quantization-quality contract: teacher-force the fp32
    greedy continuation through the fp32 and the int8-weight decode stacks
    and bound the per-step next-token divergence (max KL + top-1
    agreement). A broken dequant path (wrong scale axis, stale scales)
    lands orders of magnitude outside the budget."""
    cfg, model, params = setup
    prompt = jnp.arange(1, 9, dtype=jnp.int32)
    steps = 16
    ref_stream = np.asarray(generate(
        model, params, prompt[None], jax.random.PRNGKey(0),
        GenerationConfig(max_new_tokens=steps, temperature=0.0),
    ))[0]

    qcfg = QuantConfig(weights="int8").weight_qconfig()
    qmodel = LlamaForCausalLM(
        dataclasses.replace(cfg, quantization=qcfg), attention_impl="xla"
    )
    qparams = quantize_param_tree(params, qcfg)
    cont = jnp.asarray(ref_stream[:-1], jnp.int32)

    def teacher_forced(m, p):
        prefill, decode = serving_clones(m)

        @jax.jit
        def fn(p, prompt_ids, cont_ids):
            out, v = prefill.apply(p, prompt_ids[None], mutable=["cache"])
            first = unwrap_logits(out)[0, -1]

            def step(cache, tok):
                o, vv = decode.apply(
                    {**p, "cache": cache}, tok[None, None],
                    mutable=["cache"],
                )
                return vv["cache"], unwrap_logits(o)[0, -1]

            _, rest = jax.lax.scan(step, v["cache"], cont_ids)
            return jnp.concatenate([first[None], rest], 0)

        return np.asarray(fn(dict(p), prompt, cont))

    ref_logits = teacher_forced(model, params)
    q_logits = teacher_forced(qmodel, qparams)
    pr = jax.nn.softmax(jnp.asarray(ref_logits), -1)
    kl = np.asarray(jnp.sum(
        pr * (jax.nn.log_softmax(jnp.asarray(ref_logits), -1)
              - jax.nn.log_softmax(jnp.asarray(q_logits), -1)), -1
    ))
    top1 = (ref_logits.argmax(-1) == q_logits.argmax(-1)).mean()
    assert kl.max() < MAX_KL_BUDGET, f"max KL {kl.max()} over budget"
    assert top1 >= TOP1_AGREEMENT_FLOOR, f"top-1 agreement {top1}"


def test_kv_quant_stream_within_budget(setup):
    """int8 KV pages on top of int8 weights: the engine still agrees
    with fp32 on the overwhelming majority of greedy choices (the
    per-page-quantized cache adds error each chunk; the budget is
    agreement, not bit-identity), each choice taken given fp32's prefix
    as the contract's top-1 agreement is, and where it does not agree
    fp32's own choice is a tie inside the pinned KL budget's reach."""
    cfg, model, params = setup
    prompts = [np.arange(1, 9, dtype=np.int32)]
    gcfg = GenerationConfig(max_new_tokens=24, temperature=0.0)
    _, ref = _serve(model, params, prompts, gcfg)
    kw = dict(
        quantize=QuantConfig(weights="int8", kv="int8"), kv_page_size=PAGE
    )
    _, toks = _serve(model, params, prompts, gcfg, **kw)
    flips = _flips(setup, prompts[0], ref[0], toks[0], gcfg, **kw)
    agree = 1 - len(flips) / len(ref[0])
    assert agree >= 0.9, (flips, ref[0], toks[0])
    assert all(kl < MAX_KL_BUDGET for _, kl in flips), flips


@pytest.mark.slow  # heavy dtype variant (tier-1 budget, PR 5/13
# lean-core policy): the int8 serve leg stays tier-1 via
# test_greedy_smoke_token_identical; fp8 numerics via the
# tests/quantization roundtrip + quantized-model units
def test_fp8_weights_serve(setup):
    """fp8 (e4m3) weight quantization serves end to end — coarser grid, so
    only sanity (vocab-range tokens, full generation) is pinned."""
    cfg, model, params = setup
    prompts = [np.arange(1, 9, dtype=np.int32)]
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    engine, toks = _serve(
        model, params, prompts, gcfg, quantize=QuantConfig(weights="fp8")
    )
    assert len(toks[0]) == 8
    assert all(0 <= t < cfg.vocab_size for t in toks[0])
    assert engine.decode_compilations == 1


def test_quantized_params_bytes_shrink(setup):
    """The HBM ledger sees the win: int8 params are a fraction of the
    fp32 residents, and the int8-KV page unit is a fraction of the fp32
    page — plan() at a fixed budget fits >= 1.8x the pages (the
    acceptance criterion's capacity axis, here as ledger arithmetic)."""
    cfg, model, params = setup
    prompts = [np.arange(1, 9, dtype=np.int32)]
    gcfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    fp_engine, _ = _serve(model, params, prompts, gcfg, kv_page_size=PAGE)
    q_engine, _ = _serve(
        model, params, prompts, gcfg,
        quantize=QuantConfig(weights="int8", kv="int8"), kv_page_size=PAGE,
    )
    fp_res = fp_engine.hbm.snapshot()["residents"]
    q_res = q_engine.hbm.snapshot()["residents"]
    assert q_res["params"]["bytes"] < 0.5 * fp_res["params"]["bytes"]
    fp_page = fp_engine.cache.page_nbytes
    q_page = q_engine.cache.page_nbytes
    assert fp_page / q_page >= 1.8, (fp_page, q_page)
    budget = 10 * fp_page
    assert (budget // q_page) >= 1.8 * (budget // fp_page)


@pytest.mark.slow  # heavy quant x paged composition (tier-1 budget,
# PR 5/13 lean-core policy): each leg stays tier-1 via
# test_greedy_smoke_token_identical and
# test_paged_cache.py::test_prefix_hit_is_zero_copy_and_bit_identical
def test_quantized_paged_prefix_sharing_zero_copy(setup):
    """CoW prefix sharing works unchanged on half-size quantized pages:
    shared-system-prompt traffic maps pool pages (scales ride along as
    sibling leaves under the same page ids), copy_bytes stays 0, and the
    allocator's leak invariant holds."""
    cfg, model, params = setup
    shared = np.arange(1, 1 + 2 * PAGE, dtype=np.int32)  # 2 whole pages
    prompts = [
        np.concatenate([shared, np.asarray([40 + i], np.int32)])
        for i in range(3)
    ]
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    engine, toks = _serve(
        model, params, prompts, gcfg,
        quantize=QuantConfig(weights="int8", kv="int8"), kv_page_size=PAGE,
    )
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] >= 1
    assert snap["prefix_pages_shared"] >= 2
    assert engine.cache.alloc.copy_bytes == 0
    engine.cache.check()
    # all requests share the context: identical continuations except the
    # divergent last prompt token — just pin full generations
    assert all(len(t) == 6 for t in toks)


def test_weight_swap_requantizes(setup):
    """engine.params = <float tree> on a quantized engine converts ONCE on
    assignment; a PRE-quantized tree binds as-is."""
    cfg, model, params = setup
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        quantize=QuantConfig(weights="int8"),
    )
    flat = jax.tree_util.tree_leaves(engine._params)
    assert any(leaf.dtype == jnp.int8 for leaf in flat)
    engine.params = params  # float swap → requantized
    flat = jax.tree_util.tree_leaves(engine._params)
    assert any(leaf.dtype == jnp.int8 for leaf in flat)
    pre = quantize_param_tree(params, engine._weight_qcfg)
    engine.params = pre  # pre-quantized swap → bound as-is
    req = engine.submit(
        np.arange(1, 7, dtype=np.int32),
        GenerationConfig(max_new_tokens=4, temperature=0.0),
        key=jax.random.PRNGKey(0),
    )
    engine.run()
    assert req.state is RequestState.DONE


@pytest.mark.slow  # heavy quant x spec composition (tier-1 budget,
# PR 5/13 lean-core policy): each leg stays tier-1 via
# test_greedy_smoke_token_identical and
# test_spec_decode.py::test_spec_engine_equals_solo_speculative_generate
def test_speculative_quantized_serving(setup):
    """quantize= composes with speculative decoding: the fused draft-verify
    chunk runs the QUANTIZED target verify (draft stays float), still one
    decode program, greedy stream identical to the quantized spec-off
    engine."""
    cfg, model, params = setup
    draft_cfg = tiny_llama(num_layers=2)
    draft = LlamaForCausalLM(draft_cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    d_params = draft.init(jax.random.PRNGKey(7), ids)
    prompts = [np.arange(1, 7, dtype=np.int32)]
    gcfg = GenerationConfig(max_new_tokens=12, temperature=0.0)
    _, ref = _serve(
        model, params, prompts, gcfg, quantize=QuantConfig(weights="int8")
    )
    engine, toks = _serve(
        model, params, prompts, gcfg,
        quantize=QuantConfig(weights="int8"),
        draft_model=draft, draft_params=d_params, gamma=3,
    )
    assert toks == ref
    assert engine.decode_compilations == 1


def test_validation_errors(setup):
    import types

    cfg, model, params = setup
    # a model whose config is not even a dataclass gets the explanatory
    # ValueError, not a bare dataclasses TypeError
    dummy = types.SimpleNamespace(
        config=types.SimpleNamespace(max_seq_len=128, vocab_size=8)
    )
    with pytest.raises(ValueError, match="'quantization' field"):
        ServingEngine(
            dummy, {"params": {}}, num_slots=1,
            quantize=QuantConfig(weights="int8"),
        )
    with pytest.raises(ValueError, match="kv_page_size"):
        ServingEngine(
            model, params, num_slots=2,
            quantize=QuantConfig(weights="int8", kv="int8"),
        )
    with pytest.raises(ValueError, match="weight quantization"):
        QuantConfig(weights="int4")
    with pytest.raises(ValueError, match="KV quantization"):
        QuantConfig(kv="fp8")
    with pytest.raises(ValueError, match="quantizes nothing"):
        QuantConfig(weights=None, kv=None)
    qmodel = LlamaForCausalLM(
        dataclasses.replace(
            cfg, quantization=QuantConfig(weights="int8").weight_qconfig()
        ),
        attention_impl="xla",
    )
    with pytest.raises(ValueError, match="already carries"):
        ServingEngine(
            qmodel, params, num_slots=2, quantize=QuantConfig(weights="int8")
        )


@pytest.mark.slow  # heavy quant x preemption composition (tier-1
# budget, PR 5/13 lean-core policy): each leg stays tier-1 via
# test_greedy_smoke_token_identical and
# test_engine.py::test_preemption_resumes_token_identical
def test_quantized_eager_admission_and_preemption(setup):
    """The preempt-and-rewind machinery is quantization-blind: eager
    admission over a small quantized pool preempts and resumes, streams
    complete, pool accounting clean."""
    cfg, model, params = setup
    prompts = [
        np.arange(1 + i, 12 + i, dtype=np.int32) for i in range(4)
    ]
    gcfg = GenerationConfig(max_new_tokens=20, temperature=0.0)
    engine = ServingEngine(
        model, params, num_slots=4, decode_chunk_size=4, admission="eager",
        quantize=QuantConfig(weights="int8", kv="int8"), kv_page_size=PAGE,
        kv_num_pages=3 * (cfg.max_seq_len // PAGE) + 1,
    )
    reqs = [
        engine.submit(p, gcfg, key=jax.random.PRNGKey(i))
        for i, p in enumerate(prompts)
    ]
    engine.run()
    for r in reqs:
        assert r.state is RequestState.DONE and len(r.tokens) == 20
    engine.cache.check()
