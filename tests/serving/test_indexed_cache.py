"""The indexed cache kind (K and V joined in one leaf and one index key a token) on the serving
path, tiny, on the CPU: the engine's row cache, the ``gather`` transport and
the fused paged path (the three sparse kernels interpreted) against the plain
reference's FULL forward in logits, with contexts past ``topk`` so that
selection is at work; what the cache leaves hold; prefix sharing, per-page
fingerprints, fault injection's walkers, the host tier, preemption and resume
on the two-leaf pool; tensor parallelism refused."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.inference.generate import chunked_decode_step, serving_clones
from neuronx_distributed_tpu.models.keye_vl2 import (
    KeyeVL2ForCausalLM,
    keye_vl2_30b_a3b,
    tiny_keye_vl2,
)
from neuronx_distributed_tpu.modules.attention import (
    PAGED_LEAVES,
    cache_bytes_per_token_layer,
    ordered_kv_pool_pairs,
)
from neuronx_distributed_tpu.serving import PagedCacheManager, PrefixCache, ServingEngine
from perfbench.references import common
from perfbench.references.keye_vl2 import Reference

PS = 16
# float32 model against the float32 reference: summation order only
TOLERANCE = 1e-4
PATHS = {
    "row": {},
    "gather": {"kv_page_size": PS, "paged_attention": "gather"},
    "fused": {"kv_page_size": PS, "paged_attention": "fused"},
}


def published_keys(cfg):
    return {
        "num_hidden_layers": cfg.num_layers, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_experts": cfg.num_experts, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob, "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab_size,
        "rope_scaling": {"mrope_section": list(cfg.mrope_section)},
        "sa_config": {"indexer_num_heads": cfg.indexer_num_heads,
                      "indexer_head_dim": cfg.indexer_head_dim, "topk": cfg.index_topk},
    }


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_keye_vl2(max_seq_len=256)
    model = KeyeVL2ForCausalLM(cfg, attention_impl="xla")
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
    """Prefill under the learned mask, then decode (score, select, attend)
    through the cache: every emitted token is the reference's largest logit
    at its position within ``TOLERANCE``, the reference never having seen a
    cache. Contexts run to 62 tokens with 16 kept."""
    cfg, *_, prompts, ref = setup
    eng, toks = streams[path]
    assert all(len(t) == 12 for t in toks)
    assert max(len(p) for p in prompts) + 12 > 3 * cfg.index_topk
    assert _largest_gap(ref, prompts, toks) <= TOLERANCE
    want = "paged_sparse_fused" if path == "fused" else "einsum"
    assert eng.programs.resolved["decode_attention"] == want


def test_the_three_transports_emit_one_stream(streams):
    assert streams["row"][1] == streams["gather"][1] == streams["fused"][1]


def test_cache_leaves_hold_k_and_v_joined_and_one_index_key_a_token(streams):
    """Two per-token leaves a layer: ``kv`` (a token's K heads, then its V
    heads: what the sparse decode kernel fetches with ONE copy) and
    ``k_idx``; no separate ``k`` or ``v``."""
    eng, _ = streams["fused"]
    names = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(eng.cache.cache["pool"])[0]:
        if path[-1].key in PAGED_LEAVES:
            names.setdefault(path[-1].key, leaf.shape[-2:])
    assert names == {"kv": (2 * 2, 16), "k_idx": (1, 8)}
    # tiny widths in float32: 2 x 2 heads of 16 + one key of 8, 4 bytes each
    for name in PATHS:
        assert cache_bytes_per_token_layer(streams[name][0].cache.cache) == (2 * 2 * 16 + 8) * 4
        assert streams[name][0].metrics.snapshot()["kv_bytes_per_token_layer"] == (2 * 2 * 16 + 8) * 4
    # the published widths in bf16: 2 x 4 x 128 + 64 values = 2176 bytes; an
    # index key padded to 128 lanes would read 2304
    model = KeyeVL2ForCausalLM(
        keye_vl2_30b_a3b(num_layers=2, num_experts=8, param_dtype=jnp.bfloat16), attention_impl="xla")
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    row = jax.eval_shape(
        lambda p, i: model.clone(mode="prefill").apply(p, i, mutable=["cache"])[1]["cache"], shapes, ids)
    assert cache_bytes_per_token_layer(row) == 2176
    leaves = {path[-1].key: leaf.shape[-2:] for path, leaf in jax.tree_util.tree_flatten_with_path(row)[0]
              if path[-1].key in PAGED_LEAVES}
    assert leaves == {"kv": (8, 128), "k_idx": (1, 64)}      # the joined leaf: one whole bf16 tile a token


def test_fused_chunk_carries_both_leaves(setup):
    """PR 25's contract on the indexed pool: each layer's ``(kv, k_idx)``
    rides the scan's carry, paired with its layer in execution order, and the
    chunk's cache holds the write WINDOW of each, not a row."""
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
    assert [layer[-2] for layer in pairs] == ["layers_0", "layers_1"]
    assert all([leaf.shape[-2:] for leaf in pair] == [(4, 16), (1, 8)] for pair in pairs.values())
    state = jax.eval_shape(ServingEngine(model, params, num_slots=2, kv_page_size=PS)._fresh_slot_state)
    jaxpr = jax.make_jaxpr(chunked_decode_step(decode, 4, cfg.max_seq_len, page_size=PS,
                                               paged_attention="fused"))(params, paged, state)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    carried = [v.aval.shape for v in scans[0].invars[scans[0].params["num_consts"]:]]
    for pair in pairs.values():
        for leaf in pair:
            assert leaf.shape in carried
    # no per-token leaf as long as a row anywhere in the chunk
    rows = [v.aval.shape for e in jaxpr.jaxpr.eqns for v in e.outvars
            if len(v.aval.shape) == 4 and v.aval.shape[:2] == (2, cfg.max_seq_len)]
    assert not rows


def test_prefix_sharing_on_the_indexed_pool_is_zero_copy_and_stream_identical(setup):
    """Prefix extract/seed and per-page fingerprints walk both leaves: the
    shared stream is the unshared one, no page is copied."""
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


def test_prefix_sharing_on_the_row_cache_extracts_and_seeds_both_leaves(setup):
    cfg, model, params, _, _ = setup
    rng = np.random.default_rng(4)
    system = rng.integers(1, cfg.vocab_size, size=33).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, size=4 + i).astype(np.int32)])
               for i in range(3)]
    _, plain = _serve(model, params, prompts, new_tokens=8)
    eng, shared = _serve(model, params, prompts, new_tokens=8, prefix_cache=PrefixCache(min_match=8))
    assert shared == plain and eng.metrics.snapshot()["prefix_hits"] >= 2


def test_preemption_and_resume_on_the_indexed_pool_give_the_undisturbed_stream(setup):
    """A short row: the shared cursor reaches its end, every request is
    preempted and resumed from its context (``paged_seed`` and the suffix
    prefill through the decode path, many query rows at once); the streams
    are those of an engine that never hit the wall."""
    cfg, model, params, prompts, _ = setup
    short = KeyeVL2ForCausalLM(dataclasses.replace(cfg, max_seq_len=64), attention_impl="xla")
    picks = [prompts[0][:12], prompts[1][:17], prompts[2]]
    _, want = _serve(model, params, picks, new_tokens=24, num_slots=3)
    eng, got = _serve(short, params, picks, new_tokens=24, num_slots=2, kv_page_size=PS,
                      admission="eager")
    assert eng.metrics.snapshot()["preemptions"] > 0
    assert got == want
    eng.cache.check()


def test_page_fingerprints_and_the_fault_walkers_see_the_index_key(setup):
    """A flipped bit in a ``k_idx`` page is a corruption the per-page
    fingerprint catches, as one in ``kv`` is."""
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


def test_the_host_tier_round_trips_two_leaf_pages(setup):
    """The tier's two walkers on the two-leaf pool: pages spilled to the
    host carry ``kv`` and ``k_idx`` blocks of every layer (2 x 2 heads of 16
    + one key of 8 in float32 a token), and come back, at fresh page ids, bit
    for bit."""
    _, model, params, prompts, _ = setup
    eng, _ = _serve(model, params, prompts[:2], new_tokens=4, kv_page_size=PS)
    mgr = eng.cache
    ids = mgr._alloc_pages(2)     # held, so that the pages that come back are others
    before = {jax.tree_util.keystr(p): np.asarray(leaf[jnp.asarray(ids)])
              for p, leaf in jax.tree_util.tree_flatten_with_path(mgr.cache["pool"])[0]
              if p[-1].key in PAGED_LEAVES}
    items, nbytes = mgr.spill_pages(ids)
    assert sorted({keys[-1] for keys, _ in items}) == ["k_idx", "kv"]
    assert len(items) == 2 * 2 and nbytes == sum(a.nbytes for a in before.values())
    assert nbytes == len(ids) * PS * 2 * (2 * 2 * 16 + 8) * 4      # pages x tokens x layers x bytes a token a layer
    fresh = mgr.prefetch_pages(items, len(ids))
    assert not set(fresh) & set(ids)
    for p, leaf in jax.tree_util.tree_flatten_with_path(mgr.cache["pool"])[0]:
        if p[-1].key in PAGED_LEAVES:
            np.testing.assert_array_equal(np.asarray(leaf[jnp.asarray(fresh)]), before[jax.tree_util.keystr(p)])
    mgr.unpin_pages(fresh)
    for pid in ids:
        mgr.alloc.deref(pid)
    mgr.check()


def test_tensor_parallel_serving_refuses_an_indexed_cache_model(setup):
    _, model, params, _, _ = setup
    with pytest.raises(ValueError, match="indexed-cache"):
        ServingEngine(model, params, num_slots=2, tp=2)
