"""The latent (MLA) cache kind on the serving path, tiny, on the CPU: the
engine's row cache, the ``gather`` transport and the fused paged path (kernel
interpreted) against the plain reference's FULL forward in logits; what the
cache leaves hold; prefix sharing, preemption and resume on the latent pool;
tensor parallelism refused; the other models' programs unchanged."""

import dataclasses
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.inference.generate import chunked_decode_step, serving_clones
from neuronx_distributed_tpu.models.deepseek_v2 import (
    DeepseekV2ForCausalLM,
    deepseek_v2_lite,
    tiny_deepseek_v2,
)
from neuronx_distributed_tpu.modules.attention import (
    PAGED_LEAVES,
    cache_bytes_per_token_layer,
    ordered_kv_pool_pairs,
)
from neuronx_distributed_tpu.quantization import QuantConfig
from neuronx_distributed_tpu.serving import PagedCacheManager, PrefixCache, ServingEngine
from perfbench.references import common
from perfbench.references.deepseek_v2 import Reference

PS = 16
# float32 model against the float32 reference: the largest gap seen is 3e-6
# (summation order); 1e-4 is thirty of those and a hundredth of what an int8
# latent costs (test_the_check_fails_on_a_lower_precision_latent)
TOLERANCE = 1e-4
PATHS = {
    "row": {},
    "gather": {"kv_page_size": PS, "paged_attention": "gather"},
    "fused": {"kv_page_size": PS, "paged_attention": "fused"},
}


def _published_keys(cfg):
    rs = cfg.rope_scaling
    return {
        "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.num_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "num_experts_per_tok": cfg.top_k,
        "n_routed_experts": cfg.num_experts, "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta, "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "rope_scaling": {**dataclasses.asdict(rs), "type": "yarn"},
    }


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_deepseek_v2(max_seq_len=256)
    model = DeepseekV2ForCausalLM(cfg, attention_impl="xla")
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32) for n in (20, 37, 9, 50)]
    return cfg, model, params, prompts, Reference(_published_keys(cfg), meta.unbox(params))


def _serve(model, params, prompts, new_tokens=12, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefix_cache", None)
    eng = ServingEngine(model, params, decode_chunk_size=4, **kw)
    gcfg = GenerationConfig(max_new_tokens=new_tokens, temperature=0.0)
    reqs = [eng.submit(p, gcfg, key=jax.random.PRNGKey(i)) for i, p in enumerate(prompts)]
    eng.run()
    return eng, [list(r.tokens) for r in reqs]


def _largest_gap(ref, prompts, streams):
    worst = 0.0
    for prompt, toks in zip(prompts, streams):
        gaps, controls, _, _ = common.emitted_token_gaps(ref, prompt, toks, 128)
        assert controls.min() > 100 * TOLERANCE      # the check is able to fail
        worst = max(worst, float(gaps.max()))
    return worst


@pytest.fixture(scope="module")
def streams(setup):
    _, model, params, prompts, _ = setup
    return {name: _serve(model, params, prompts, **kw) for name, kw in PATHS.items()}


@pytest.mark.parametrize("path", list(PATHS))
def test_prefill_then_decode_matches_the_references_full_forward(setup, streams, path):
    """Prefill (materialised) then decode (absorbed) through the cache: every
    emitted token is the reference's largest logit at its position, within
    ``TOLERANCE``, the reference never having seen a cache."""
    *_, prompts, ref = setup
    eng, toks = streams[path]
    assert all(len(t) == 12 for t in toks)
    assert _largest_gap(ref, prompts, toks) <= TOLERANCE
    want = "paged_latent_fused" if path == "fused" else "einsum"
    assert eng.programs.resolved["decode_attention"] == want


def test_the_three_transports_emit_one_stream(streams):
    assert streams["row"][1] == streams["gather"][1] == streams["fused"][1]


def _decode_logits(model, params, ids, split, latent_dtype=None):
    """Prefill ``ids[:, :split]``, then decode the rest a token at a time
    through the row cache; ``latent_dtype`` rounds the cached latent and
    rotated key to that type first (a lower-precision cache)."""
    prefill, decode = serving_clones(model)
    _, state = prefill.apply(params, ids[:, :split], mutable=["cache"])
    cache = state["cache"]
    if latent_dtype is not None:
        cache = jax.tree_util.tree_map_with_path(
            lambda path, a: a.astype(latent_dtype).astype(a.dtype) if path[-1].key in PAGED_LEAVES else a,
            cache)
    rows = []
    for t in range(split, ids.shape[1]):
        (logits, _), state = decode.apply({**params, "cache": cache}, ids[:, t:t + 1], mutable=["cache"])
        cache = state["cache"]
        rows.append(logits[:, 0])
    return jnp.stack(rows, axis=1)


def test_the_logit_check_fails_on_a_lower_precision_latent(setup):
    """Decode logits against the reference's full forward: within the
    tolerance as computed, far outside it when the cached latent is first
    rounded to bfloat16, the nearest precision below this configuration's
    float32 (and further with an fp8 latent)."""
    cfg, model, params, _, ref = setup
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 48), 1, cfg.vocab_size)
    want = np.asarray(ref.logits(np.asarray(ids)))[:, 40:]
    gap = lambda dtype: float(np.abs(np.asarray(_decode_logits(model, params, ids, 40, dtype)) - want).max())  # noqa: E731
    assert gap(None) <= TOLERANCE
    assert gap(jnp.bfloat16) > 10 * TOLERANCE
    assert gap(jnp.float8_e4m3fn) > gap(jnp.bfloat16)


def test_an_int8_latent_pool_serves_through_the_gather_transport(setup):
    """The quantized pool's walkers handle the latent leaves as they handle
    k/v (a scale sibling each); its stream stays the reference's at these
    margins, which is why the precision check above is made in logits."""
    _, model, params, prompts, ref = setup
    eng, toks = _serve(model, params, prompts, kv_page_size=PS,
                       quantize=QuantConfig(weights=None, kv="int8"))
    names = {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(eng.cache.cache["pool"])[0]}
    assert {"k", "k_scale", "k_pe", "k_pe_scale"} <= names and "v" not in names
    assert all(len(t) == 12 for t in toks)
    assert cache_bytes_per_token_layer(eng.cache.cache) == (32 + 8) * 1 + 2 * 4 / PS


def test_cache_leaves_hold_576_values_a_token_and_nothing_per_head(streams):
    eng, _ = streams["fused"]
    pool = eng.cache.cache["pool"]
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        name = path[-1].key
        if name in PAGED_LEAVES:
            assert name in ("k", "k_pe") and leaf.shape[-2] == 1, (name, leaf.shape)
    # tiny widths in float32: (32 + 8) values of 4 bytes, in all three layouts
    for name in PATHS:
        assert cache_bytes_per_token_layer(streams[name][0].cache.cache) == (32 + 8) * 4
        assert streams[name][0].metrics.snapshot()["kv_bytes_per_token_layer"] == (32 + 8) * 4
    # the published widths in bf16: 576 values, 1152 bytes; K and V per head
    # would be 16 * (192 + 128) * 2 = 10240
    model = DeepseekV2ForCausalLM(
        deepseek_v2_lite(num_layers=2, param_dtype=jnp.bfloat16), attention_impl="xla")
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    row = jax.eval_shape(
        lambda p, i: model.clone(mode="prefill").apply(p, i, mutable=["cache"])[1]["cache"], shapes, ids)
    assert cache_bytes_per_token_layer(row) == 1152
    values = sum(np.prod(a.shape[-2:]) for p, a in jax.tree_util.tree_flatten_with_path(row)[0]
                 if p[-1].key in PAGED_LEAVES) / 2
    assert values == 576


def test_a_kv_cache_reads_its_own_bytes():
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama

    cfg = tiny_llama(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    _, state = model.clone(mode="prefill").apply(params, ids, mutable=["cache"])
    assert cache_bytes_per_token_layer(state["cache"]) == 2 * 2 * 8 * 4   # k and v, 2 heads of 8, f32


def test_fused_chunk_carries_both_latent_leaves(setup):
    """PR 25's contract on the latent pool: each layer's ``(k, k_pe)`` pair
    rides the scan's carry, paired with its layer in execution order."""
    cfg, model, params, _, _ = setup
    prefill, decode = serving_clones(model)
    ids = jnp.zeros((1, 16), jnp.int32)
    row = jax.eval_shape(lambda p, i: prefill.apply(p, i, mutable=["cache"])[1]["cache"], params, ids)

    def pool_of(row):
        mgr = PagedCacheManager(2, cfg.max_seq_len, PS)
        mgr.allocate_from(row)
        return mgr.cache

    paged = jax.eval_shape(pool_of, row)
    pairs = ordered_kv_pool_pairs(paged["pool"])
    assert [layer[-2] for layer in pairs] == ["layers_0", "layers_1", "layers_2"]
    assert all([leaf.shape[-1] for leaf in pair] == [32, 8] for pair in pairs.values())
    state = jax.eval_shape(ServingEngine(model, params, num_slots=2, kv_page_size=PS)._fresh_slot_state)
    jaxpr = jax.make_jaxpr(chunked_decode_step(decode, 4, cfg.max_seq_len, page_size=PS,
                                               paged_attention="fused"))(params, paged, state)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    carried = [v.aval.shape for v in scans[0].invars[scans[0].params["num_consts"]:]]
    for pair in pairs.values():
        for leaf in pair:
            assert leaf.shape in carried


def test_prefix_sharing_on_the_latent_pool_is_zero_copy_and_stream_identical(setup):
    cfg, model, params, _, ref = setup
    rng = np.random.default_rng(3)
    system = rng.integers(1, cfg.vocab_size, size=35).astype(np.int32)    # two whole pages
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, size=5 + i).astype(np.int32)])
               for i in range(4)]
    _, plain = _serve(model, params, prompts, new_tokens=8, kv_page_size=PS)
    for attention in ("gather", "fused"):
        eng, shared = _serve(model, params, prompts, new_tokens=8, kv_page_size=PS,
                             paged_attention=attention, prefix_cache=PrefixCache(min_match=8))
        assert shared == plain
        snap = eng.metrics.snapshot()
        assert snap["prefix_hits"] >= 3 and snap["prefix_pages_shared"] >= 2 * snap["prefix_hits"]
        assert eng.cache.alloc.copy_bytes == 0
        eng.cache.check()
    assert _largest_gap(ref, prompts, plain) <= TOLERANCE


def test_preemption_and_resume_on_the_latent_pool_give_the_undisturbed_stream(setup):
    """A short row: the shared cursor reaches its end, every request is
    preempted and resumed from its context; the streams are those of an
    engine that never hit the wall."""
    cfg, model, params, prompts, _ = setup
    short = DeepseekV2ForCausalLM(dataclasses.replace(cfg, max_seq_len=64), attention_impl="xla")
    picks = [prompts[0][:12], prompts[1][:17], prompts[2]]
    _, want = _serve(model, params, picks, new_tokens=24, num_slots=3)
    eng, got = _serve(short, params, picks, new_tokens=24, num_slots=2, kv_page_size=PS,
                      admission="eager")
    assert eng.metrics.snapshot()["preemptions"] > 0
    assert got == want
    eng.cache.check()


def test_tensor_parallel_serving_refuses_a_latent_cache_model(setup):
    _, model, params, _, _ = setup
    with pytest.raises(ValueError, match="latent-cache"):
        ServingEngine(model, params, num_slots=2, tp=2)


# --- the other models' programs ------------------------------------------------------

# sha256 of the jaxpr text (addresses masked) of tiny Mixtral's and CodeGen's
# prefill and paged decode chunk, both transports, taken on the PARENT commit
# of the PR that added the latent cache (4823593): what that PR generalised
# (KVCache's leaves, the pool pairing, the fused decode, the flash kernel's
# value head size) must leave these programs exactly as they were. The two
# ``decode.fused`` digests were taken anew by the PR that made the fused chunk
# gather its write window instead of the logical view (PR 27); that PR left
# the other four, prefill and the ``gather`` chunk, as they are. Mixtral's two
# ``decode`` digests were taken anew by the PR that made the expert layers sow
# ``hit_experts`` / ``routed_rows`` into a decode chunk (PR 33: the model names
# ``chunk_stats``, so the chunk sums them and has a seventh output); that PR
# left ``mixtral.prefill`` and CodeGen's three as they are. All four ``decode``
# digests were taken anew by the PR that put the chunk's sampler behind one
# conditional on "some emitting row samples" (PR 36: ``sample_per_row`` takes
# ``kept=~done`` and branches round the sort); that PR left both ``prefill``
# digests as they are (a prefill samples nothing).
PARENT_PROGRAMS = {
    "mixtral.prefill": "e0d71476f6d38b847454df722dd626fe5ecbef1e0635893022a29220a2e62e69",
    "mixtral.decode.gather": "3dd48b7bb2d393f00deb7568a1c77dc997d3743775d913a32f7f90d29d1f5f83",
    "mixtral.decode.fused": "3d166644826da274dc5f22bb35072967bd480d62c1aeb3903b898c56b6e9feb0",
    "codegen.prefill": "2ea437e83f3d502ceb35b1052f326594d3425b7c623598af85f683630941d54a",
    "codegen.decode.gather": "26031da023b7a3167fd7da7206124f5efad09ea86c03cde92890e6da20ec99dd",
    "codegen.decode.fused": "414b5c2ac9b6016838e6863ceecf99ba70debfc0aefabf9d6c1270a2152ce969",
}


def _model_program_texts(name, model):
    """``{name.prefill, name.decode.gather, name.decode.fused}``: the jaxpr
    text of a tiny model's prefill and paged decode chunk, both transports.

    Taken with ``checkpoint_name`` as the identity it lowers to: PR 40 named
    ``ParallelMLP``'s up-projection output for the trainer's remat policy, a
    ``name`` equation in every jaxpr that holds a dense MLP and nothing in the
    lowered program (``tests/models/test_remat_policy.py`` holds the lowered
    texts equal), so the digests below still say "everything else is the
    parent's"."""
    from unittest import mock

    from neuronx_distributed_tpu.modules import attention

    with mock.patch.object(attention, "checkpoint_name", lambda x, name: x):
        return _program_texts_of(name, model)


def _program_texts_of(name, model):
    out = {}
    length = model.config.max_seq_len
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    pre, dec = serving_clones(model)
    ids, mask = jnp.zeros((1, 32), jnp.int32), jnp.ones((1, 32), bool)

    def prefill(p, i, m):
        return pre.apply(p, i, padding_mask=m, mutable=["cache"])

    out[f"{name}.prefill"] = str(jax.make_jaxpr(prefill)(params, ids, mask))
    row = jax.eval_shape(lambda p, i, m: prefill(p, i, m)[1]["cache"], params, ids, mask)

    def pool_of(row):
        mgr = PagedCacheManager(4, length, 16)
        mgr.allocate_from(row)
        return mgr.cache

    paged = jax.eval_shape(pool_of, row)
    state = dict(
        tok=jnp.zeros((4,), jnp.int32), keys=jnp.zeros((4, 2), jnp.uint32),
        active=jnp.ones((4,), bool), remaining=jnp.full((4,), 5, jnp.int32),
        temp=jnp.zeros((4,), jnp.float32), topk=jnp.zeros((4,), jnp.int32),
        topp=jnp.ones((4,), jnp.float32), eos=jnp.full((4,), -1, jnp.int32))
    for mode in ("gather", "fused"):
        fn = chunked_decode_step(dec, 4, length, page_size=16, paged_attention=mode)
        out[f"{name}.decode.{mode}"] = str(jax.make_jaxpr(fn)(params, paged, state))
    return out


def _digest(text):
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()


def _program_texts():
    from neuronx_distributed_tpu.models.codegen import CodeGenForCausalLM, tiny_codegen
    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM, tiny_mixtral

    return {
        **_model_program_texts("mixtral", MixtralForCausalLM(tiny_mixtral(), attention_impl="xla")),
        **_model_program_texts("codegen", CodeGenForCausalLM(tiny_codegen())),
    }


@pytest.mark.parametrize("program", list(PARENT_PROGRAMS))
def test_mixtral_and_codegen_programs_are_the_parents(program):
    if not hasattr(test_mixtral_and_codegen_programs_are_the_parents, "texts"):
        test_mixtral_and_codegen_programs_are_the_parents.texts = _program_texts()
    text = test_mixtral_and_codegen_programs_are_the_parents.texts[program]
    digest = hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()
    assert digest == PARENT_PROGRAMS[program], json.dumps({program: digest})


# sha256 of the same three programs of tiny DeepSeek-V2 (this file's model),
# taken on the PARENT commit (07a904c) of the PR that joined K and V into one
# leaf of the INDEXED cache (PR 31): a new name in ``PAGED_LEAVES`` and a new
# sparse decode kernel must leave the programs of a model with no such leaf
# exactly as they were. The two ``decode`` digests: anew with PR 33's counters,
# as Mixtral's above, and anew again with PR 36's sampler branch;
# ``deepseek.prefill`` is the parent's.
DEEPSEEK_PARENT_PROGRAMS = {
    "deepseek.prefill": "fead3bfa519cb976a59ab0e77801e17c93cdee964d75836884e38e694fa96c18",
    "deepseek.decode.gather": "277cce6135272958e7f21a7520375984ab6fcf96160e46688f9604f6fdadcc09",
    "deepseek.decode.fused": "821885426fa3755ddf462db3230e800b98f18b5000050c355d391f46039854fe",
}


@pytest.fixture(scope="module")
def deepseek_program_texts():
    return _model_program_texts("deepseek", DeepseekV2ForCausalLM(tiny_deepseek_v2(), attention_impl="xla"))


@pytest.mark.parametrize("program", list(DEEPSEEK_PARENT_PROGRAMS))
def test_deepseek_programs_are_the_parents(deepseek_program_texts, program):
    digest = _digest(deepseek_program_texts[program])
    assert digest == DEEPSEEK_PARENT_PROGRAMS[program], json.dumps({program: digest})
