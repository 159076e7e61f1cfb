"""Per-slot STATE beside the pages (``modules/attention.SLOT_STATE_LEAVES``):
ZAYA1's tiny model through ``ServingEngine.submit`` / ``step`` against the
plain reference's full forward, in LOGITS, where "the previous token" is not
the previous column: padding columns of a prefill bucket, gap columns left in
a decoding slot's row by another slot's admission, the first token's zero
history, a slot reused by a shorter request, preempt-and-rewind through the
state, and the stat the dispatch span carries. (The contract every cache kind
holds, this ``joined_state`` kind among them, and what it cannot have, refused
by name at construction: ``test_cache_kinds.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM, tiny_mixtral
from neuronx_distributed_tpu.models.zaya import ZayaForCausalLM, tiny_zaya
from neuronx_distributed_tpu.modules.attention import (
    SLOT_STATE_LEAVES,
    cache_batch_axis,
    cache_length_axis,
    extract_cache_prefix,
    reset_cache_slot,
)
from neuronx_distributed_tpu.serving import ServingEngine
from neuronx_distributed_tpu.serving.paging import CacheKindUnsupported, PagedCacheManager
from neuronx_distributed_tpu.utils.fingerprint import cache_fingerprint

from perfbench.references.zaya import Reference
from tests.models.test_zaya import published_keys, weights
from tests.serving.span_spy import overhear

PAGE, CHUNK = 8, 4
LOGIT_ATOL = 5e-5
STATE = SLOT_STATE_LEAVES[0]
MODES = ["row", "gather", "fused"]


@pytest.fixture(scope="module")
def system():
    cfg = tiny_zaya(max_seq_len=256)
    model = ZayaForCausalLM(cfg, attention_impl="xla")
    params = weights(model)
    return cfg, model, params, Reference(published_keys(cfg), params)


def engine_of(system, mode="gather", slots=2, model=None, **kw):
    paged = {} if mode == "row" else {"kv_page_size": PAGE, "paged_attention": mode}
    return ServingEngine(model or system[1], system[2], num_slots=slots, decode_chunk_size=CHUNK, **paged, **kw)


def submit(engine, rng, p, n):
    prompt = rng.integers(0, 256, p).astype(np.int32)
    return prompt, engine.submit(prompt, GenerationConfig(max_new_tokens=n, temperature=0.0))


def gaps(ref, prompt, req):
    """The reference's largest logit less its logit of each emitted token."""
    toks = np.asarray(req.tokens)
    rows = ref.logits(np.concatenate([prompt, toks])[None])[0, len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return rows.max(-1) - rows[np.arange(len(toks)), toks]


def slot_states(engine):
    tree = engine.cache.cache
    tree = tree["pool"] if isinstance(tree, dict) and "pool" in tree else tree
    return [np.asarray(leaf) for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
            if path[-1].key == STATE]


@pytest.mark.parametrize("mode", MODES)
def test_gap_columns_lie_between_a_token_and_its_predecessor(system, mode):
    """A slot decodes at a cursor of ~40 when a prompt of 100 tokens (bucket
    128) moves the shared cursor to 128: the first slot's next token is written
    ~85 columns after its predecessor, with nothing of the slot between. Its
    convolutions and its second value head read the slot's STATE, not column
    ``cursor - 1`` (the other request's token), and its stream stays the
    reference's; so does the late one's, whose 28 padding columns precede its
    first token (zero history, not the padding's projection)."""
    ref = system[3]
    engine = engine_of(system, mode)
    rng = np.random.default_rng(1)
    first = submit(engine, rng, 30, 60)
    for _ in range(3):
        engine.step()
    assert engine.cache.cursor < 60
    second = submit(engine, rng, 100, 30)
    engine.step()
    assert engine.cache.cursor >= 128 and engine.metrics.preemptions == 0
    engine.run()
    for prompt, req in (first, second):
        assert len(req.tokens) == req.config.max_new_tokens
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL


@pytest.mark.parametrize("mode", MODES)
def test_a_slot_reused_by_a_shorter_request_starts_from_its_own_prefill(system, mode):
    """One slot: a long request, then a short one in the same slot. Freeing
    leaves the old state where it is; the next admission overwrites it with
    the new prompt's last token's, and the short request's stream is the
    reference's (it would not be with anything of the first left)."""
    ref = system[3]
    engine = engine_of(system, mode, slots=1)
    rng = np.random.default_rng(2)
    long = submit(engine, rng, 70, 20)
    engine.run()
    left = slot_states(engine)
    assert all(np.abs(s).max() > 0 for s in left)          # nothing cleared it: nothing needs to
    short = submit(engine, rng, 9, 20)
    engine.step()
    assert short[1].slot == 0                                  # the only slot: the long request's
    engine.run()
    for prompt, req in (long, short):
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL


def test_a_one_token_prompt_has_no_history_at_all(system):
    """The shortest request: its only prompt token reads zeros for its
    predecessor in both convolutions and in the second value head."""
    ref = system[3]
    engine = engine_of(system, "fused")
    prompt, req = submit(engine, np.random.default_rng(3), 1, 12)
    engine.run()
    assert gaps(ref, prompt, req).max() < LOGIT_ATOL


@pytest.mark.parametrize("mode", ["gather", "fused"])
def test_preempt_and_rewind_at_the_wall_rebuilds_the_state_by_a_prefill(system, mode):
    """A row of 128 columns ends under two requests: the engine preempts,
    rewinds, and each comes back by a prefill of prompt + tokens emitted,
    which leaves the state of the context's last token (no prefix cache holds
    anything, no seeded row); streams stay the reference's and no page is
    left."""
    cfg, _, params, ref = system
    model = ZayaForCausalLM(tiny_zaya(max_seq_len=128), attention_impl="xla")
    engine = engine_of(system, mode, model=model, admission="eager")
    rng = np.random.default_rng(4)
    reqs = [submit(engine, rng, 40, 80), submit(engine, rng, 60, 60)]
    while engine.has_work:
        engine.step()
        engine.cache.check()
    assert engine.metrics.preemptions >= 1
    ran = {n for n, e in engine.programs.snapshot(analyze=False)["by_program"].items() if e["dispatches"]}
    assert not ran & {"paged_seed", "suffix_prefill"}
    for prompt, req in reqs:
        assert len(req.tokens) == req.config.max_new_tokens
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL
    assert engine.cache.alloc.free_pages == engine.cache.alloc.num_pages - 1


def test_the_dispatch_span_carries_the_states_bytes_and_other_models_carry_none(system):
    engine = engine_of(system, "gather")
    submit(engine, np.random.default_rng(5), 12, 6)
    seen = overhear(engine, "nxd.step.decode.dispatch")
    engine.run()
    cfg = system[0]
    assert seen[0]["slot_state_bytes_per_layer"] == 4 * cfg.slot_state_width       # float32 here
    assert seen[0]["kv_bytes_per_token_layer"] == 4 * 2 * cfg.num_kv_heads * cfg.head_dim
    assert engine.prefix is None and engine.cache.slot_state       # "auto" resolved to no prefix cache
    other = MixtralForCausalLM(tiny_mixtral(), attention_impl="xla")
    params = jax.jit(other.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    plain = ServingEngine(other, params, num_slots=2, kv_page_size=16)
    assert plain._slot_state_stats() == {} and not plain.cache.slot_state and plain.prefix is not None


def test_the_row_engine_shares_no_prefix_either(system):
    """Without pages too: a prefix block copied out of a row's COLUMNS has no
    state at its end, so ``"auto"`` resolves to none and asking is refused."""
    assert engine_of(system, "row").prefix is None
    with pytest.raises(CacheKindUnsupported, match="prefix_cache"):
        engine_of(system, "row", prefix_cache=4)


def test_every_walker_classifies_the_state_leaf_by_name(system):
    """A slot axis and no length axis: freeing a slot leaves it, a fingerprint
    hashes it without a column weight, and what moves a context by its columns
    or pages alone refuses it."""
    assert cache_batch_axis(STATE, 2) == 0 and cache_batch_axis(STATE, 3) == 1      # a scanned stack's
    assert cache_length_axis(STATE, 2) is None and cache_length_axis("kv", 4) == 1
    engine = engine_of(system, "row")
    submit(engine, np.random.default_rng(6), 10, 2)
    engine.step()
    cache = engine.cache.cache
    node = cache["model"]["layers_0"]["attn"]
    freed = reset_cache_slot(cache, 0)["model"]["layers_0"]["attn"]
    assert np.array_equal(freed[STATE], node[STATE]) and not np.asarray(freed["kv_valid"])[0].any()
    bumped = jax.tree_util.tree_map_with_path(
        lambda p, leaf: leaf + 1 if p[-1].key == STATE else leaf, cache)
    assert float(cache_fingerprint(bumped)) != float(cache_fingerprint(cache))
    with pytest.raises(ValueError, match="per-slot state"):
        extract_cache_prefix(cache, 0, 4, 8)
    engine.run()
    mgr = PagedCacheManager(2, 64, PAGE)
    mgr.allocate_from(cache)        # the leaves tell the manager
    for refused in (lambda: mgr.pin_pages([1]), lambda: mgr.seed_row([1], 8, 0),
                    lambda: mgr.spill_pages([1]), lambda: mgr.stage_context(None, 8, 8)):
        with pytest.raises(CacheKindUnsupported, match="per-slot state"):
            refused()
    with pytest.raises(CacheKindUnsupported, match="quantized"):
        PagedCacheManager(2, 64, PAGE, kv_quant="int8").allocate_from(cache)


# --- a RECURRENT layer's state: ``recur`` and ``conv`` and no page at all (Solar Open 2) ----------


@pytest.fixture(scope="module")
def recurrent():
    from neuronx_distributed_tpu.models.solar_open2 import SolarOpen2ForCausalLM, tiny_solar_open2
    from perfbench.references.solar_open2 import Reference as SolarReference
    from tests.models.test_solar_open2 import published_keys as solar_keys, weights as solar_weights

    cfg = tiny_solar_open2(max_seq_len=256)
    model = SolarOpen2ForCausalLM(cfg, attention_impl="xla")
    params = solar_weights(model)
    return cfg, model, params, SolarReference(solar_keys(cfg), params)


def recurrent_states(engine):
    tree = engine.cache.cache
    tree = tree["pool"] if isinstance(tree, dict) and "pool" in tree else tree
    return [np.asarray(leaf) for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
            if path[-1].key == "recur"]


@pytest.mark.parametrize("mode", ["fused"])
def test_a_preempted_recurrent_slot_comes_back_through_a_prefill_that_rebuilds_its_state(recurrent, mode):
    """A row of 128 columns ends under two requests: the engine preempts,
    rewinds, and each comes back by a FULL prefill of prompt + tokens emitted
    (no snapshot of the state, no seeded row), which leaves the state the
    recurrence reaches over that context; streams stay the reference's, and
    the GQA layers' pages all come back."""
    from neuronx_distributed_tpu.models.solar_open2 import SolarOpen2ForCausalLM, tiny_solar_open2

    _, _, params, ref = recurrent
    model = SolarOpen2ForCausalLM(tiny_solar_open2(max_seq_len=128), attention_impl="xla")
    engine = engine_of(recurrent, mode, model=model, admission="eager")
    rng = np.random.default_rng(4)
    reqs = [submit(engine, rng, 40, 80), submit(engine, rng, 60, 60)]
    while engine.has_work:
        engine.step()
        engine.cache.check()
    assert engine.metrics.preemptions >= 1
    ran = {n for n, e in engine.programs.snapshot(analyze=False)["by_program"].items() if e["dispatches"]}
    assert not ran & {"paged_seed", "suffix_prefill"}
    for prompt, req in reqs:
        assert len(req.tokens) == req.config.max_new_tokens
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL
    assert engine.cache.alloc.free_pages == engine.cache.alloc.num_pages - 1


@pytest.mark.parametrize("mode", ["row", "fused"])
def test_a_freed_recurrent_slots_state_does_not_leak_into_the_next_admission(recurrent, mode):
    """One slot: a long request, then a short one in the same slot, admitted
    while ANOTHER slot decodes (so the shared cursor has moved and the short
    prompt is left-padded in its bucket). Freeing leaves the old state where
    it is (nothing clears 4 MiB a layer); the admission overwrites it with the
    new prompt's, whose padding columns change nothing; the short request's
    stream is the reference's (it would not be with anything of the first
    left in ``recur`` or ``conv``)."""
    ref = recurrent[3]
    engine = engine_of(recurrent, mode, slots=2)
    rng = np.random.default_rng(2)
    long = submit(engine, rng, 70, 14)
    other = submit(engine, rng, 33, 60)
    engine.step()
    slot = long[1].slot
    while not long[1].finished:
        engine.step()
    left = recurrent_states(engine)
    assert all(np.abs(s[slot]).max() > 0 for s in left)      # nothing cleared it: nothing needs to
    short = submit(engine, rng, 9, 20)
    engine.step()
    assert short[1].slot == slot
    engine.run()
    for prompt, req in (long, other, short):
        assert gaps(ref, prompt, req).max() < LOGIT_ATOL


def test_the_dispatch_span_carries_the_recurrent_layers_stats(recurrent):
    """Dispatch: the bytes a slot's state holds a layer (both leaves), how many
    layers are recurrent and how many page. The gauge: all slots, all layers."""
    cfg = recurrent[0]
    engine = engine_of(recurrent, "fused")
    submit(engine, np.random.default_rng(5), 12, 6)
    seen = overhear(engine, "nxd.step.decode.dispatch")
    engine.run()
    h, d = cfg.linear_num_heads, cfg.linear_head_dim
    recur, conv = h * d * d * 4, (cfg.conv_kernel - 1) * cfg.conv_channels * 4       # float32 here
    assert seen[0]["slot_state_bytes_per_layer"] == recur + conv
    assert (seen[0]["recurrent_layers"], seen[0]["paged_layers"]) == (3, 2)
    assert engine.metrics.view.gauge("serving_slot_state_bytes").value == 2 * 3 * (recur + conv)
    assert engine.prefix is None and engine.cache.slot_state


def test_every_walker_classifies_the_recurrent_leaves_by_name():
    """Each name has its own rank after the slot axis: ``recur`` (slots,
    heads, d, d), ``conv`` (slots, taps, channels); neither has a length axis."""
    assert cache_batch_axis("recur", 4) == 0 and cache_batch_axis("recur", 5) == 1      # a scanned stack's
    assert cache_batch_axis("conv", 3) == 0 and cache_batch_axis("conv", 4) == 1
    assert cache_length_axis("recur", 4) is None and cache_length_axis("conv", 3) is None
    assert set(SLOT_STATE_LEAVES) == {"state", "recur", "conv"}
