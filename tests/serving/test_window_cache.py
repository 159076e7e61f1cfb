"""Window and full attention layers in ONE paged cache (``serving/paging.py``,
a block table and a pool a layer kind): Trinity's tiny model through
``ServingEngine.submit`` / ``step`` against the plain reference's full
forward, in LOGITS, for more than three windows with pages freed on the way;
the allocator's invariants over both tables; preempt-and-rewind and a drain;
a disaggregated handoff refused. (The contract every cache kind holds, this
``joined`` kind among them, and what a windowed model cannot have yet, refused
by name at construction: ``test_cache_kinds.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.models.afmoe import AfmoeForCausalLM, tiny_afmoe
from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM, tiny_mixtral
from neuronx_distributed_tpu.modules.attention import WINDOW_PAGES
from neuronx_distributed_tpu.serving import ServingEngine
from neuronx_distributed_tpu.serving.paging import (
    WINDOW_SLACK_PAGES,
    PagedCacheManager,
    CacheKindUnsupported,
)

from perfbench.references.afmoe import Reference
from tests.models.test_afmoe import published_keys, weights
from tests.serving.span_spy import overhear

WINDOW, PAGE, CHUNK = 32, 8, 4
LOGIT_ATOL = 5e-5


@pytest.fixture(scope="module")
def system():
    cfg = tiny_afmoe(held_experts=(4, 4), max_seq_len=256)
    model = AfmoeForCausalLM(cfg, attention_impl="xla")
    params = weights(model)
    return cfg, model, params, Reference(published_keys(cfg), params)


def engine_of(system, mode="gather", slots=3, **kw):
    _, model, params, _ = system
    return ServingEngine(model, params, num_slots=slots, kv_page_size=PAGE,
                         paged_attention=mode, decode_chunk_size=CHUNK, **kw)


def submit(engine, rng, p, n):
    prompt = rng.integers(0, 256, p).astype(np.int32)
    return prompt, engine.submit(prompt, GenerationConfig(max_new_tokens=n, temperature=0.0))


def gaps(ref, prompt, req):
    """The reference's largest logit less its logit of each emitted token."""
    toks = np.asarray(req.tokens)
    rows = ref.logits(np.concatenate([prompt, toks])[None])[0, len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return rows.max(-1) - rows[np.arange(len(toks)), toks]


@pytest.mark.parametrize("mode", ["gather", "fused"])
def test_prefill_then_paged_decode_past_three_windows_is_the_references(system, mode):
    """Three requests whose contexts pass the window three times and more:
    every emitted token is the reference's best, pages are freed on the way,
    no slot ever maps more than its share, and a drained engine holds none."""
    ref = system[3]
    engine = engine_of(system, mode)
    mgr = engine.cache
    assert engine.programs.resolved["decode_attention"] == (
        "paged_walk_fused" if mode == "fused" else "einsum")
    assert mgr.window == WINDOW and engine.prefix is None
    assert mgr.window_pages_per_slot == 16     # 32 / 8 + 1 + 2 + the slack, up to a multiple of 8
    assert mgr.alloc_w.num_pages == 3 * 16 + 1
    rng = np.random.default_rng(0)
    reqs = [submit(engine, rng, p, n) for p, n in ((20, 90), (70, 60), (45, 100))]
    peak = 0
    while engine.has_work:
        engine.step()
        mgr.check()
        peak = max(peak, max(int((mgr._tables_w[s] != 0).sum()) for s in range(3)))
    assert mgr.window_pages_freed_total > 20
    assert engine.metrics.snapshot()["window_pages_freed"] == mgr.window_pages_freed_total
    assert peak <= WINDOW // PAGE + 1 + 2 + WINDOW_SLACK_PAGES
    assert engine.metrics.preemptions == 0
    for prompt, req in reqs:
        assert len(req.tokens) == req.config.max_new_tokens
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL
    assert mgr.alloc_w.free_pages == mgr.alloc_w.num_pages - 1
    assert mgr.alloc.free_pages == mgr.alloc.num_pages - 1
    assert WINDOW_PAGES in mgr.cache and set(mgr.cache) == {"pages", WINDOW_PAGES, "pool"}


def test_a_late_admissions_cursor_jump_leaves_the_window_counted_in_tokens(system):
    """A slot decodes at a cursor of ~40 when a prompt of 100 tokens (bucket
    128) moves the shared cursor to 128: the columns between are gap columns
    of the first slot, its window reaches back over them, its pages there are
    kept, and its tokens stay the reference's."""
    ref = system[3]
    engine = engine_of(system, "gather", slots=2)
    rng = np.random.default_rng(1)
    first = submit(engine, rng, 30, 70)
    for _ in range(3):
        engine.step()
    assert engine.cache.cursor < 60
    second = submit(engine, rng, 100, 40)
    engine.step()
    assert engine.cache._gaps and engine.cache._gaps[0][1] == 128
    lo = engine.cache._window_floor(first[1].slot)
    assert lo < engine.cache._gaps[0][0]          # the window reaches below the gap
    engine.run()
    for prompt, req in (first, second):
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL
    assert not engine.cache._gaps                  # cleared with the last slot


def test_preempt_and_rewind_at_the_wall_returns_by_a_prefill_of_the_whole_context(system):
    """A row of 128 columns ends under two requests: the engine preempts,
    rewinds, and each comes back by a prefill of prompt + tokens emitted
    (its window pages are gone, and no prefix cache holds them); streams
    stay the reference's and no page is left."""
    cfg, _, params, ref = system
    model = AfmoeForCausalLM(tiny_afmoe(held_experts=(4, 4), max_seq_len=128), attention_impl="xla")
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PAGE, paged_attention="gather",
                           decode_chunk_size=CHUNK, admission="eager")
    rng = np.random.default_rng(2)
    reqs = [submit(engine, rng, 40, 80), submit(engine, rng, 60, 60)]
    while engine.has_work:
        engine.step()
        engine.cache.check()
    assert engine.metrics.preemptions >= 1
    assert "paged_seed" not in {n for n, e in engine.programs.snapshot(analyze=False)["by_program"].items()
                                if e["dispatches"]}
    for prompt, req in reqs:
        assert len(req.tokens) == req.config.max_new_tokens
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL
    assert engine.cache.alloc_w.free_pages == engine.cache.alloc_w.num_pages - 1


def test_a_windowed_pool_that_runs_short_meets_the_wall_and_not_an_error(system):
    """The window kind's allocator is the page-pressure wall's second face:
    with no page to back a slot's next write window ``ensure_decode_window``
    says so, takes nothing, and the engine preempts."""
    mgr = PagedCacheManager(2, 256, PAGE, window=WINDOW, window_write_cols=CHUNK)
    engine = engine_of(system, "gather", slots=2)
    rng = np.random.default_rng(3)
    submit(engine, rng, 40, 30)
    engine.step()
    live = engine.cache
    taken = live.alloc_w.alloc(live.alloc_w.free_pages)      # somebody holds the rest
    live.cursor += 2 * PAGE                                   # past every mapped page
    before = live.alloc.free_pages
    assert live.ensure_decode_window(np.array([0]), CHUNK) is False
    assert live.alloc.free_pages == before                    # the full kind took nothing either
    for pid in taken:
        live.alloc_w.deref(pid)
    live.cursor -= 2 * PAGE
    engine.run()
    del mgr


def test_the_dispatch_span_carries_both_tables_counts(system):
    engine = engine_of(system, "gather", slots=2)
    rng = np.random.default_rng(4)
    submit(engine, rng, 70, 6)
    submit(engine, rng, 12, 6)
    seen = overhear(engine, "nxd.step.decode.dispatch")
    engine.run()
    first = seen[0]     # each slot holds its prompt and the prefill's token
    assert first["ctx_tokens"] == 71 + 13 and first["window_tokens"] == 32 + 13
    # the full table maps every page of both contexts, the window table the window's
    assert first["full_pages_mapped"] > first["window_pages_mapped"] > 0
    assert first["window_pages_mapped"] <= 2 * engine.cache.window_pages_per_slot
    # and, of each, the pages in runs of adjacent pool pages: the 70-token prompt's whole groups of four
    assert first["full_pages_mapped"] > first["full_pages_in_runs"] >= 4
    assert first["window_pages_mapped"] > first["window_pages_in_runs"] >= 4


def test_a_model_with_one_kind_of_layer_builds_todays_tree():
    model = MixtralForCausalLM(tiny_mixtral(), attention_impl="xla")
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=16)
    engine.submit(np.arange(1, 20, dtype=np.int32), GenerationConfig(max_new_tokens=3, temperature=0.0))
    engine.step()
    assert set(engine.cache.cache) == {"pages", "pool"}
    assert engine.cache.window is None and engine.cache.alloc_w is None
    assert engine._window_stats() == {} and engine.prefix is not None
    engine.run()


def test_tensor_parallel_and_a_disaggregated_handoff_are_refused(system):
    from neuronx_distributed_tpu.serving.disagg import DisaggregatedServer

    _, model, params, _ = system
    with pytest.raises(ValueError, match="joined-cache"):
        ServingEngine(model, params, num_slots=2, kv_page_size=PAGE, tp=2)
    engine = engine_of(system, "gather", slots=2)
    with pytest.raises(CacheKindUnsupported, match="disaggregation"):
        DisaggregatedServer(engine)
    mgr = engine.cache
    for call in (lambda: mgr.pin_pages([1]), lambda: mgr.seed_row([1], 8, 0),
                 lambda: mgr.stage_context(None, 8, 8), lambda: mgr.spill_pages([1])):
        with pytest.raises(CacheKindUnsupported):
            call()


def test_the_window_floor_walks_back_over_the_cursors_jumps():
    """Host arithmetic alone: a slot admitted at 40 (context from 10), the
    cursor's jumps over [52, 128) and [140, 150), the cursor at 160: its last
    32 tokens are the 10 columns from 150, the 12 from 128, and 10 below 52."""
    mgr = PagedCacheManager(2, 256, PAGE, window=WINDOW, window_write_cols=CHUNK)
    mgr._slot_start[0], mgr._slot_target[0] = 10, 40
    mgr._gaps = [(20, 30), (52, 128), (140, 150)]     # the first lies in the slot's own prompt
    mgr.cursor = 160
    assert mgr._window_floor(0) == 52 - (31 - 10 - 12)
    mgr._gaps = []
    assert mgr._window_floor(0) == 160 - 31
    mgr.cursor = 45
    assert mgr._window_floor(0) == 14
    mgr.cursor = 41
    assert mgr._window_floor(0) == 10                 # never below the context's start
    mgr._slot_start[0] = mgr._slot_target[0] = None
