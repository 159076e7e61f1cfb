"""The fused decode chunk CARRIES its page pool (PR 25).

``paged_attention="fused"`` scatters every step's write window into the page
pool and attends the pool through the paged kernel. Closed over by the
chunk's ``lax.scan``, the pool was loop-invariant and XLA copied all of it
before each of those scatters; in the scan's carry it is updated in place.
What is pinned here, on the CPU:

* structure — every pool leaf is a CARRY of the scan and none a constant
  (``gather`` mode, float or int8, the speculative chunk and the
  row-per-slot layout carry no pool);
* values — a small engine with the interpreted kernel emits gather mode's
  tokens, compiles ONE decode program, and leaves the copy-on-write prefix
  pages that two slots decode over byte for byte as they were.

That the chip's compiler then writes in place is
``tests/kernels/test_tpu_compile.py``'s guard (slow)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.inference.generate import (
    chunked_decode_step,
    serving_clones,
)
from neuronx_distributed_tpu.inference.spec_decode import (
    speculative_decode_chunk,
)
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import (
    PagedCacheManager,
    PrefixCache,
    ServingEngine,
)

SLOTS, PAGE = 2, 16


@pytest.fixture(scope="module", params=["mha", "gqa"])
def setup(request):
    kv_heads = {"mha": 4, "gqa": 2}[request.param]
    cfg = tiny_llama(num_layers=2, hidden_size=32, intermediate_size=96,
                     vocab_size=128, num_heads=4, num_kv_heads=kv_heads)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _chunk_operands(cfg, model, params, paged, kv_quant=None):
    """Abstract ``(cache, state)`` of a decode chunk: the paged pytree a
    :class:`PagedCacheManager` builds from a prefill row (int8 pages with
    scale siblings under ``kv_quant``), or the row-per-slot cache itself."""
    prefill, _ = serving_clones(model)
    ids = jax.ShapeDtypeStruct((1, 8), jnp.int32)

    def cache_of(p, ids):
        row = prefill.apply(p, ids, mutable=["cache"])[1]["cache"]
        if not paged:
            return jax.tree.map(
                lambda a: jnp.concatenate([a] * SLOTS) if a.ndim else a, row
            )
        mgr = PagedCacheManager(SLOTS, cfg.max_seq_len, PAGE, kv_quant=kv_quant)
        mgr.allocate_from(row)
        return mgr.cache

    cache = jax.eval_shape(cache_of, params, ids)
    state = {
        "tok": jnp.zeros((SLOTS,), jnp.int32),
        "keys": jnp.zeros((SLOTS, 2), jnp.uint32),
        "active": jnp.ones((SLOTS,), jnp.bool_),
        "temp": jnp.ones((SLOTS,), jnp.float32),
        "topk": jnp.zeros((SLOTS,), jnp.int32),
        "topp": jnp.ones((SLOTS,), jnp.float32),
        "remaining": jnp.full((SLOTS,), 4, jnp.int32),
        "eos": jnp.full((SLOTS,), -1, jnp.int32),
    }
    return cache, state


def _scan_of(cfg, model, params, chunk, page_size, mode, kv_quant=None,
             speculative=False):
    """``(scan equation, pool leaf avals)`` of the chunk's jaxpr; with
    ``speculative`` the draft-verify chunk's, the model drafting for itself
    (``chunk`` rounds of two columns)."""
    cache, state = _chunk_operands(
        cfg, model, params, page_size is not None, kv_quant
    )
    decode = serving_clones(model)[1]
    if speculative:
        fn = speculative_decode_chunk(
            decode, decode, chunk, 2, cfg.max_seq_len, page_size=page_size
        )
        operands = (params, params, cache, cache, state)
    else:
        fn = chunked_decode_step(
            decode, chunk, cfg.max_seq_len,
            page_size=page_size, paged_attention=mode,
        )
        operands = (params, cache, state)
    jaxpr = jax.make_jaxpr(fn)(*operands).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    pool = (
        [l for l in jax.tree.leaves(cache["pool"]) if l.ndim == 4]
        if page_size is not None else []
    )
    return scans[0], [(l.shape, l.dtype) for l in pool]


def _carry_and_consts(scan):
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    avals = [(v.aval.shape, v.aval.dtype) for v in scan.invars]
    return avals[n_consts:n_consts + n_carry], avals[:n_consts]


@pytest.mark.parametrize("chunk", [1, 8])
def test_fused_chunk_carries_every_pool_leaf(setup, chunk):
    cfg, model, params = setup
    scan, pool = _scan_of(cfg, model, params, chunk, PAGE, "fused")
    carry, consts = _carry_and_consts(scan)
    assert len(pool) == 2 * cfg.num_layers
    for leaf in set(pool):
        assert carry.count(leaf) == pool.count(leaf), (
            f"pool leaf {leaf} is not loop-carried state of the decode scan"
        )
        assert leaf not in consts, (
            f"pool leaf {leaf} is closed over by the decode scan: XLA copies "
            "the whole pool before each step's window scatter"
        )
    # the carried pool comes out of the scan and is the chunk's output pool
    outs = [(v.aval.shape, v.aval.dtype) for v in scan.outvars]
    assert all(outs.count(leaf) >= pool.count(leaf) for leaf in set(pool))


@pytest.mark.parametrize("page_size, kv_quant, speculative", [
    pytest.param(PAGE, None, False, id="paged_gather"),
    pytest.param(None, None, False, id="row_per_slot"),
    pytest.param(PAGE, "int8", False, id="paged_gather_int8"),
    pytest.param(PAGE, None, True, id="speculative"),
])
def test_other_transports_carry_no_pool(setup, page_size, kv_quant,
                                        speculative):
    """Only the fused transport changed: gather mode (a quantized pool's
    too) and the speculative chunk scatter their window once on the chunk's
    exit and the row layout has no pool, so their scans carry the logical
    cache(s) and the four per-slot leaves, as before."""
    cfg, model, params = setup
    scan, pool = _scan_of(
        cfg, model, params, 4, page_size, "gather", kv_quant, speculative
    )
    carry, consts = _carry_and_consts(scan)
    assert not set(pool) & (set(carry) | set(consts))
    logical = 4 * cfg.num_layers  # k, v, index, kv_valid per layer
    caches = 2 if speculative else 1  # the target's and the draft's
    assert len(carry) == caches * logical + 4  # + tok, keys, remaining, done


def _shared_page_run(model, params, cfg, mode):
    """Two requests behind one system prompt on two slots: the second
    prefill hits the prefix cache, so both slots map the SAME physical
    prefix pages (copy-on-write) and decode over them in the same chunks.
    Returns ``(engine, token streams, shared page ids, their bytes before
    the second request arrived)``."""
    engine = ServingEngine(
        model, params, num_slots=SLOTS, decode_chunk_size=3,
        kv_page_size=PAGE, paged_attention=mode,
        prefix_cache=PrefixCache(min_match=PAGE),
    )
    system = np.arange(1, 2 * PAGE + 2, dtype=np.int32)  # 2 whole pages + 1
    rng = np.random.RandomState(11)
    prompts = [
        np.concatenate([
            system, rng.randint(1, cfg.vocab_size, size=3 + i).astype(np.int32)
        ])
        for i in range(2)
    ]
    gcfgs = [
        GenerationConfig(max_new_tokens=12, temperature=0.0),
        GenerationConfig(max_new_tokens=9, temperature=0.8, top_k=11),
    ]
    first = engine.submit(prompts[0], gcfgs[0], key=jax.random.PRNGKey(3))
    engine.step()  # prefill, insert (the entry pins the system prompt's
    # pages) and a first chunk
    shared = list(engine.prefix.entries[0].page_ids)
    before = _page_bytes(engine, shared)
    second = engine.submit(prompts[1], gcfgs[1], key=jax.random.PRNGKey(4))
    engine.step()  # the hit: slot 1 maps the same pages, and both decode
    assert [engine.cache.alloc.refcount(p) for p in shared] == [3, 3]
    engine.run()
    return engine, [first.tokens, second.tokens], shared, before


def _page_bytes(engine, page_ids):
    pool = engine.cache.cache["pool"]
    return [
        np.asarray(leaf[np.asarray(page_ids)])
        for leaf in jax.tree.leaves(pool) if leaf.ndim == 4
    ]


def test_fused_tokens_match_gather_over_a_shared_page(setup):
    cfg, model, params = setup
    fused, toks, shared, before = _shared_page_run(model, params, cfg, "fused")
    assert fused.paged_attention == "fused"
    assert fused.decode_compilations == 1
    assert fused.metrics.snapshot()["prefix_hits"] == 1
    assert fused.cache.alloc.copy_bytes == 0
    assert len(toks[0]) == 12 and len(toks[1]) == 9
    # two ref-holders decoded over the shared pages for several chunks, each
    # step scattering its window into the carried pool: not a byte moved
    for was, now in zip(before, _page_bytes(fused, shared)):
        np.testing.assert_array_equal(was, now)
    _, gather_toks, _, _ = _shared_page_run(model, params, cfg, "gather")
    assert toks == gather_toks
