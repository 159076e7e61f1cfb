"""The indexed LATENT cache kind (a token's latent and rotated key joined in
one leaf, and one index key) on the serving path, tiny, on the CPU: the
engine's row cache, the ``gather`` transport and the fused paged path (the
index-score, window-write and sparse latent kernels interpreted) against the
plain reference's FULL forward in logits, with contexts past ``topk``, a
share of the experts held and the rows they computed read back with each
chunk; what the cache leaves hold; prefix sharing, per-page fingerprints,
preemption and resume, ``reset_cache_slot``; tensor parallelism refused; and
the programs of the model with the OTHER indexed cache unchanged."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.inference.generate import chunked_decode_step, serving_clones
from neuronx_distributed_tpu.models.glm_moe_dsa import (
    GlmMoeDsaForCausalLM,
    glm5,
    tiny_glm_moe_dsa,
)
from neuronx_distributed_tpu.modules.attention import (
    PAGED_LEAVES,
    cache_bytes_per_token_layer,
    ordered_kv_pool_pairs,
    reset_cache_slot,
)
from neuronx_distributed_tpu.serving import PagedCacheManager, PrefixCache, ServingEngine
from perfbench.references import common
from perfbench.references.glm_moe_dsa import Reference
from tests.models.test_glm_moe_dsa import published_keys
from tests.serving.test_latent_cache import _digest, _model_program_texts

PS = 16
# float32 model against the float32 reference: summation order only
TOLERANCE = 1e-4
PATHS = {
    "row": {},
    "gather": {"kv_page_size": PS, "paged_attention": "gather"},
    "fused": {"kv_page_size": PS, "paged_attention": "fused"},
}
HELD = (4, 8)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_glm_moe_dsa(max_seq_len=256, held_experts=HELD)
    model = GlmMoeDsaForCausalLM(cfg, attention_impl="xla")
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(0)
    # 37 and 50 are past topk = 16 at once; 20 and 9 pass it while decoding
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32) for n in (20, 37, 9, 50)]
    return cfg, model, params, prompts, Reference(published_keys(cfg), meta.unbox(params))


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
    """Prefill under the learned mask, then decode (score, select, the
    absorbed form over the selected latents) through the cache: every emitted
    token is the reference's largest logit at its position within
    ``TOLERANCE``, the reference never having seen a cache or the absorbed
    form. Contexts run to 62 tokens with 16 kept; 8 of 16 experts held."""
    cfg, *_, prompts, ref = setup
    eng, toks = streams[path]
    assert all(len(t) == 12 for t in toks)
    assert max(len(p) for p in prompts) + 12 > 3 * cfg.index_topk
    assert _largest_gap(ref, prompts, toks) <= TOLERANCE
    want = "paged_sparse_latent_fused" if path == "fused" else "einsum"
    assert eng.programs.resolved["decode_attention"] == want


def test_the_three_transports_emit_one_stream(streams):
    assert streams["row"][1] == streams["gather"][1] == streams["fused"][1]


def test_the_chunk_reads_back_the_rows_its_held_experts_computed(setup):
    """``held_rows`` / ``routed_rows`` on ``nxd.step.decode.readback``: one
    pair of int32 a chunk, summed on the device over the chunk's steps and
    the two sparse layers; the served model and no other has them."""
    cfg, model, params, prompts, _ = setup
    eng = ServingEngine(model, params, decode_chunk_size=4, num_slots=2, kv_page_size=PS, prefix_cache=None)
    fn = eng._nonspec_chunk()
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    for i, p in enumerate(prompts[:2]):
        eng.submit(p, gcfg, key=jax.random.PRNGKey(i))
    while eng.has_work and not any(eng._active):
        eng.step()
    out = fn(eng._params, eng.cache.take(), eng._state)
    assert len(out) == 7                                              # six, and the model's own counters
    held, routed = (int(v) for v in out[6])
    steps = int(out[4])
    sparse_layers = cfg.num_layers - cfg.first_k_dense
    assert routed == steps * sparse_layers * 2 * cfg.top_k            # slots x top-k a layer a step
    assert 0 < held < routed
    assert eng._decode_model.chunk_stats == ("held_rows", "routed_rows")
    whole = GlmMoeDsaForCausalLM(dataclasses.replace(cfg, held_experts=None), attention_impl="xla")
    assert serving_clones(whole)[1].chunk_stats == ()


def test_cache_leaves_hold_the_joined_latent_and_one_index_key_a_token(streams):
    eng, _ = streams["fused"]
    names = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(eng.cache.cache["pool"])[0]:
        if path[-1].key in PAGED_LEAVES:
            names.setdefault(path[-1].key, leaf.shape[-2:])
    assert names == {"kv": (2, 32), "k_idx": (1, 16)}
    for name in PATHS:
        assert cache_bytes_per_token_layer(streams[name][0].cache.cache) == (2 * 32 + 16) * 4
        assert streams[name][0].metrics.snapshot()["kv_bytes_per_token_layer"] == (2 * 32 + 16) * 4
    # the published widths in bf16: one (8, 128) tile (1152 B used) + 128 index values = 2304 bytes
    model = GlmMoeDsaForCausalLM(
        glm5(num_layers=2, first_k_dense=1, held_experts=(0, 2), param_dtype=jnp.bfloat16), attention_impl="xla")
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    row = jax.eval_shape(
        lambda p, i: model.clone(mode="prefill").apply(p, i, mutable=["cache"])[1]["cache"], shapes, ids)
    assert cache_bytes_per_token_layer(row) == 2304
    leaves = {path[-1].key: leaf.shape[-2:] for path, leaf in jax.tree_util.tree_flatten_with_path(row)[0]
              if path[-1].key in PAGED_LEAVES}
    assert leaves == {"kv": (8, 128), "k_idx": (1, 128)}     # the joined leaf: one whole bf16 tile a token


def test_fused_chunk_carries_both_leaves(setup):
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
    assert all([leaf.shape[-2:] for leaf in pair] == [(2, 32), (1, 16)] for pair in pairs.values())
    state = jax.eval_shape(ServingEngine(model, params, num_slots=2, kv_page_size=PS)._fresh_slot_state)
    jaxpr = jax.make_jaxpr(chunked_decode_step(decode, 4, cfg.max_seq_len, page_size=PS,
                                               paged_attention="fused"))(params, paged, state)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    carried = [v.aval.shape for v in scans[0].invars[scans[0].params["num_consts"]:]]
    for pair in pairs.values():
        for leaf in pair:
            assert leaf.shape in carried
    rows = [v.aval.shape for e in jaxpr.jaxpr.eqns for v in e.outvars
            if len(v.aval.shape) == 4 and v.aval.shape[:2] == (2, cfg.max_seq_len)]
    assert not rows


def test_prefix_sharing_on_the_pool_is_zero_copy_and_stream_identical(setup):
    cfg, model, params, _, ref = setup
    rng = np.random.default_rng(3)
    system = rng.integers(1, cfg.vocab_size, size=35).astype(np.int32)    # two whole pages
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, size=5 + i).astype(np.int32)])
               for i in range(4)]
    _, plain = _serve(model, params, prompts, new_tokens=8, kv_page_size=PS)
    eng, shared = _serve(model, params, prompts, new_tokens=8, kv_page_size=PS,
                         paged_attention="fused", prefix_cache=PrefixCache(min_match=8))
    assert shared == plain
    snap = eng.metrics.snapshot()
    assert snap["prefix_hits"] >= 3 and snap["prefix_pages_shared"] >= 2 * snap["prefix_hits"]
    assert eng.cache.alloc.copy_bytes == 0
    eng.cache.check()
    assert _largest_gap(ref, prompts, plain) <= TOLERANCE


def test_preemption_and_resume_give_the_undisturbed_stream(setup):
    """A short row: the shared cursor reaches its end, every request is
    preempted and resumed from its context (``paged_seed`` and the suffix
    prefill through the decode path, many query rows at once, each selecting
    for itself)."""
    cfg, model, params, prompts, _ = setup
    short = GlmMoeDsaForCausalLM(dataclasses.replace(cfg, max_seq_len=64), attention_impl="xla")
    picks = [prompts[0][:12], prompts[1][:17], prompts[2]]
    _, want = _serve(model, params, picks, new_tokens=24, num_slots=3)
    eng, got = _serve(short, params, picks, new_tokens=24, num_slots=2, kv_page_size=PS,
                      admission="eager")
    assert eng.metrics.snapshot()["preemptions"] > 0
    assert got == want
    eng.cache.check()


def test_page_fingerprints_see_both_leaves_and_a_freed_slot_attends_nothing(setup):
    from neuronx_distributed_tpu.utils.fingerprint import cache_fingerprint

    _, model, params, prompts, _ = setup
    eng, _ = _serve(model, params, prompts[:2], new_tokens=4, kv_page_size=PS)
    pool = eng.cache.cache["pool"]
    base = np.asarray(cache_fingerprint(pool))
    for name in ("kv", "k_idx"):
        def flip(path, leaf, name=name):
            return leaf.at[1, 0, 0, 0].add(1.0) if path[-1].key == name and "layers_0" in str(path) else leaf
        changed = np.asarray(cache_fingerprint(jax.tree_util.tree_map_with_path(flip, pool)))
        assert not np.array_equal(changed, base), name
    # reset_cache_slot on a row collection of this kind: validity cleared, storage left
    _, state = model.clone(mode="prefill").apply(params, jnp.asarray(prompts[1][None, :32]), mutable=["cache"])
    row = jax.tree.map(lambda a: jnp.concatenate([a, a]) if a.ndim else a, state["cache"])
    freed = reset_cache_slot(row, 1)
    for path, leaf in jax.tree_util.tree_flatten_with_path(freed)[0]:
        if path[-1].key == "kv_valid":
            assert bool(leaf[0, :32].all()) and not bool(leaf[1].any())
        elif path[-1].key in PAGED_LEAVES:
            assert bool(jnp.abs(leaf[1, :32]).sum() > 0)


def test_tensor_parallel_serving_refuses_an_indexed_latent_cache_model(setup):
    _, model, params, _, _ = setup
    with pytest.raises(ValueError, match="indexed_latent-cache"):
        ServingEngine(model, params, num_slots=2, tp=2)


# sha256 of the jaxpr text of tiny Keye-VL-2.0's prefill and paged decode
# chunk, both transports, taken on the PARENT commit (4eea0cf) of the PR that
# added the indexed latent cache (PR 32): `_fused_sparse_decode` grew a latent
# form, `chunked_decode_step` a model's own counters and the router a
# selection bias; the model with the OTHER indexed cache must not notice.
# (Mixtral's, CodeGen's and DeepSeek-V2's nine are in test_latent_cache.py.)
# The two ``decode`` digests were taken anew by PR 33, whose expert layers sow
# ``hit_experts`` / ``routed_rows`` into a decode chunk; ``keye.prefill`` is
# the parent's, and so are GLM-5's three at the end of this file (a layer told which experts it
# holds sows what it sowed). PR 36 (the chunk's sampler behind one conditional
# on "some emitting row samples", ``utils/sampling.sample_per_row``) took the
# two ``decode`` digests anew, here and in GLM-5's below; both ``prefill``
# digests stand.
KEYE_PARENT_PROGRAMS = {
    "keye.prefill": "b6cab9cad2a08e38110d3c0b904d73989f7f803088075fe75bfdb6c9d3465882",
    "keye.decode.gather": "745243e191fa1af397416893de4bfb8335a4040b9dd4c285bd488fb3be53102b",
    "keye.decode.fused": "3250f7de95cc048c6da0b48e83f9e8a6c03e923b231f561c69cf6f8d305fceee",
}


@pytest.fixture(scope="module")
def keye_program_texts():
    from neuronx_distributed_tpu.models.keye_vl2 import KeyeVL2ForCausalLM, tiny_keye_vl2

    return _model_program_texts("keye", KeyeVL2ForCausalLM(tiny_keye_vl2(max_seq_len=128), attention_impl="xla"))


@pytest.mark.parametrize("program", list(KEYE_PARENT_PROGRAMS))
def test_keye_programs_are_the_parents(keye_program_texts, program):
    digest = _digest(keye_program_texts[program])
    assert digest == KEYE_PARENT_PROGRAMS[program], json.dumps({program: digest})


# The same three programs of tiny GLM-5 with a share of its experts held,
# taken on the PARENT commit (97eb835) of the PR that gave the other expert
# layers their counters and a streamed decode form (PR 33): a layer told which
# experts it holds sows and multiplies what it did. (The two ``decode`` digests:
# anew with PR 36's sampler branch, see Keye's above.)
GLM_PARENT_PROGRAMS = {
    "glm.prefill": "212888961eaff3339697e55a3b1de353d6252f85a2c75980d5c7e71537802798",
    "glm.decode.gather": "cff08709ba7f506ae7f3a9aab3660044fd2a0b55a54ae56da5ca2a99c10ea39b",
    "glm.decode.fused": "709276835566c6a217ceaed065c302fc9acfe5fd61e02ddc3a910f9d383dd08d",
}


@pytest.fixture(scope="module")
def glm_program_texts():
    return _model_program_texts("glm", GlmMoeDsaForCausalLM(
        tiny_glm_moe_dsa(max_seq_len=128, held_experts=(2, 4)), attention_impl="xla"))


@pytest.mark.parametrize("program", list(GLM_PARENT_PROGRAMS))
def test_glm_programs_are_the_parents(glm_program_texts, program):
    digest = _digest(glm_program_texts[program])
    assert digest == GLM_PARENT_PROGRAMS[program], json.dumps({program: digest})
