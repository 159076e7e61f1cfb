"""The contract every cache KIND holds on the serving path, tiny, on the CPU,
one row of ``KINDS`` a kind: the engine's row cache, the ``gather`` transport
and the fused paged path (the kind's kernels interpreted) against the plain
reference's FULL forward in logits; the transports emit one stream; what the
cache leaves hold a token; the fused chunk carries every leaf; prefix sharing
is zero-copy and stream-identical; preemption and resume give the undisturbed
stream; and what the kind cannot have is refused by name.

``latent``: DeepSeek-V2's (a latent and a rotated key a token). ``indexed``:
Keye-VL-2.0's (K and V joined in one leaf and one index key; contexts past
``topk`` so that selection is at work). ``indexed_latent``: GLM-5's (the latent
and the rotated key joined, and one index key; a share of the experts held).
``joined``: Trinity's (K and V joined; window and full attention layers, a
block table and a pool a layer kind). ``joined_state``: ZAYA1's (K and V
joined, and per-slot STATE beside the pages: what a layer's next token needs of
the slot's last one, a slot axis and no length axis). ``joined_recurrent``:
Solar Open 2's (GQA layers with K and V joined; between them RECURRENT layers
that keep a float32 state and their convolutions' taps a slot and nothing
else: no per-token leaf, no page, no block table). ``looped``: Ouro's (K and V
joined, no window; the stack run three times over one set of weights, a cache
node a layer a PASS under one block table, taken pass-major).

What is particular to ONE kind stays in that kind's file
(``test_latent_cache.py``, ``test_indexed_cache.py``,
``test_indexed_latent_cache.py``, ``test_window_cache.py``,
``test_slot_state.py``), on ``built(kind)``
from here: a kind's model, parameters, reference and served streams are built
once a process. A new cache kind is a row of ``KINDS``."""

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.inference.generate import chunked_decode_step, serving_clones
from neuronx_distributed_tpu.modules.attention import (
    PAGED_LEAVES,
    SLOT_STATE_LEAVES,
    cache_bytes_per_token_layer,
    ordered_kv_pool_pairs,
    slot_state_bytes_per_layer,
)
from neuronx_distributed_tpu.quantization import QuantConfig
from neuronx_distributed_tpu.serving import PagedCacheManager, PrefixCache, ServingEngine
from neuronx_distributed_tpu.serving.paging import CacheKindUnsupported
from perfbench.references import common

# float32 model against the float32 reference: the largest gap seen is 3e-6
# (summation order); 1e-4 is thirty of those and a hundredth of what an int8
# latent costs (test_latent_cache.py::test_the_logit_check_fails_on_a_lower_precision_latent)
TOLERANCE = 1e-4
PATHS = ("row", "gather", "fused")


def _latent():
    from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM, deepseek_v2_lite, tiny_deepseek_v2
    from perfbench.references.deepseek_v2 import Reference

    def published(cfg):
        return {
            "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": cfg.first_k_dense,
            "num_attention_heads": cfg.num_heads, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "num_experts_per_tok": cfg.top_k,
            "n_routed_experts": cfg.num_experts, "rms_norm_eps": cfg.rms_eps,
            "rope_theta": cfg.rope_theta, "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rope_scaling": {**dataclasses.asdict(cfg.rope_scaling), "type": "yarn"},
        }

    return (DeepseekV2ForCausalLM, tiny_deepseek_v2(max_seq_len=256), published, Reference,
            deepseek_v2_lite(num_layers=2, param_dtype=jnp.bfloat16))


def _indexed():
    from neuronx_distributed_tpu.models.keye_vl2 import KeyeVL2ForCausalLM, keye_vl2_30b_a3b, tiny_keye_vl2
    from perfbench.references.keye_vl2 import Reference
    from tests.models.test_keye_vl2 import published_keys

    return (KeyeVL2ForCausalLM, tiny_keye_vl2(max_seq_len=256), published_keys, Reference,
            keye_vl2_30b_a3b(num_layers=2, num_experts=8, param_dtype=jnp.bfloat16))


def _indexed_latent():
    from neuronx_distributed_tpu.models.glm_moe_dsa import GlmMoeDsaForCausalLM, glm5, tiny_glm_moe_dsa
    from perfbench.references.glm_moe_dsa import Reference
    from tests.models.test_glm_moe_dsa import published_keys

    return (GlmMoeDsaForCausalLM, tiny_glm_moe_dsa(max_seq_len=256, held_experts=(4, 8)), published_keys, Reference,
            glm5(num_layers=2, first_k_dense=1, held_experts=(0, 2), param_dtype=jnp.bfloat16))


def _joined():
    from neuronx_distributed_tpu.models.afmoe import AfmoeForCausalLM, tiny_afmoe
    from perfbench.references.afmoe import Reference
    from tests.models.test_afmoe import published_keys

    return AfmoeForCausalLM, tiny_afmoe(held_experts=(4, 4), max_seq_len=256), published_keys, Reference, None


def _joined_state():
    from neuronx_distributed_tpu.models.zaya import ZayaForCausalLM, tiny_zaya, zaya1_8b
    from perfbench.references.zaya import Reference
    from tests.models.test_zaya import published_keys

    return (ZayaForCausalLM, tiny_zaya(max_seq_len=256), published_keys, Reference,
            zaya1_8b(num_layers=2, param_dtype=jnp.bfloat16))


def _joined_recurrent():
    from neuronx_distributed_tpu.models.solar_open2 import SolarOpen2ForCausalLM, solar_open2_250b, tiny_solar_open2
    from perfbench.references.solar_open2 import Reference
    from tests.models.test_solar_open2 import published_keys

    return (SolarOpen2ForCausalLM, tiny_solar_open2(max_seq_len=256, held_experts=(4, 8)), published_keys, Reference,
            solar_open2_250b(num_layers=2, held_experts=(0, 2), param_dtype=jnp.bfloat16))


def _looped():
    from neuronx_distributed_tpu.models.ouro import OuroForCausalLM, ouro_2_6b, tiny_ouro
    from perfbench.references.ouro import Reference
    from tests.models.test_ouro import published_keys

    return (OuroForCausalLM, tiny_ouro(max_seq_len=256), published_keys, Reference,
            ouro_2_6b(num_layers=2, param_dtype=jnp.bfloat16))


@dataclasses.dataclass(frozen=True)
class Kind:
    """A cache kind's row: what builds its tiny model and reference, and what
    the contract reads differently for it."""

    parts: Callable                      # -> (model class, tiny config, published keys of a config, Reference, published-width config)
    fused: str                           # what ``paged_attention="fused"`` resolves the decode attention to
    leaves: Dict[str, Tuple[int, int]]   # per-token leaf -> its (rows, lanes) a token, tiny widths
    layers: Tuple[str, ...] = ()         # the pool's nodes in execution order (``node_name``)
    published_leaves: Optional[Dict[str, Tuple[int, int]]] = None   # the same at the published widths, bf16 ...
    published_bytes: int = 0             # ... and their bytes a token a layer
    page: int = 16
    prefix_transports: Tuple[str, ...] = ("gather", "fused")   # () = it shares no prefixes
    # one pool whose pages hold a whole context. Not ``joined``: its window layers have a pool and a
    # block table of their own and free pages behind the window, so what its leaves and its chunk
    # hold, and its preempt-and-rewind at the wall, are test_window_cache.py's
    whole_pool: bool = True
    refuses: Tuple[str, ...] = ("tp",)   # see ``REFUSALS``
    # per-slot state leaf -> its shape a slot, tiny widths (a slot axis, no length axis) ...
    state: Dict[str, Tuple[int, ...]] = dataclasses.field(default_factory=dict)
    published_state_bytes: int = 0       # ... and their bytes a slot a layer at the published widths in bf16
    state_layers: Optional[int] = None   # the layers that keep them; None: the pool's layers

    @property
    def state_bytes(self) -> int:        # a slot a layer, tiny widths in float32
        return sum(math.prod(shape) for shape in self.state.values()) * 4

    @property
    def bytes(self) -> int:              # a token a layer, tiny widths in float32
        return sum(rows * lanes for rows, lanes in self.leaves.values()) * 4


KINDS = {
    # tiny widths: (32 + 8) values; the published widths in bf16: 576 values, 1152
    # bytes; K and V per head would be 16 * (192 + 128) * 2 = 10240
    "latent": Kind(_latent, "paged_latent_fused", {"k": (1, 32), "k_pe": (1, 8)},
                   ("layers_0", "layers_1", "layers_2"), {"k": (1, 512), "k_pe": (1, 64)}, 1152),
    # two per-token leaves a layer: ``kv`` (a token's K heads, then its V heads:
    # what the sparse decode kernel fetches with ONE copy) and ``k_idx``; no
    # separate ``k`` or ``v``. Tiny: 2 x 2 heads of 16 + one key of 8; published
    # in bf16: 2 x 4 x 128 + 64 values = 2176 bytes (an index key padded to 128
    # lanes would read 2304), the joined leaf one whole bf16 tile a token
    "indexed": Kind(_indexed, "paged_sparse_fused", {"kv": (2 * 2, 16), "k_idx": (1, 8)},
                    ("layers_0", "layers_1"), {"kv": (8, 128), "k_idx": (1, 64)}, 2176),
    # published in bf16: one (8, 128) tile (1152 B used) + 128 index values = 2304 bytes
    "indexed_latent": Kind(_indexed_latent, "paged_sparse_latent_fused", {"kv": (2, 32), "k_idx": (1, 16)},
                           ("layers_0", "layers_1", "layers_2"), {"kv": (8, 128), "k_idx": (1, 128)}, 2304,
                           prefix_transports=("fused",)),
    # a window of 32 under contexts of up to 62, pages of 8; what a model with window layers cannot
    # have yet (ROADMAP queue 2, C1) is refused at construction, each by name
    "joined": Kind(_joined, "paged_walk_fused", {"kv": (2 * 2, 16)}, page=8, prefix_transports=(), whole_pool=False,
                   refuses=("tp", "prefix_cache", "kv_host_pages", "draft_model", "quantize.kv")),
    # a token: 2 x 2 heads of 16 (published in bf16: (4, 128), 1024 bytes, a QUARTER of a tile). A slot: the
    # packed q/k latent of its last token, the first convolution's output for it and the shifted value
    # half, 2 x 96 + 16 (published: 2 x 1280 + 128 values, 5376 bytes). A context is not its pages alone,
    # so whatever holds one by them is refused at construction, each by name
    "joined_state": Kind(_joined_state, "paged_walk_fused", {"kv": (2 * 2, 16)}, ("layers_0", "layers_1", "layers_2"),
                         {"kv": (4, 128)}, 1024, prefix_transports=(), state={"state": (2 * 96 + 16,)},
                         published_state_bytes=5376,
                         refuses=("tp", "prefix_cache", "kv_host_pages", "draft_model", "quantize.kv", "disagg")),
    # layers 0 and 3 of 5 are GQA layers: 2 x 2 heads of 16 a token (published in bf16: (16, 128), 4096 bytes), the
    # only layers the pool pages. Layers 1, 2 and 4 keep a slot 4 heads' float32 state of 16 x 16 and the last 3
    # inputs of 3 x 4 x 16 convolution channels (published: 64 x 128 x 128 x 4 + 3 x 24,576 x 2 = 4,341,760 bytes)
    # and NO per-token leaf: they map no page and ride the chunk's carry as they are
    "joined_recurrent": Kind(_joined_recurrent, "paged_walk_fused", {"kv": (2 * 2, 16)}, ("layers_0", "layers_3"),
                             {"kv": (16, 128)}, 4096, prefix_transports=(),
                             state={"recur": (4, 16, 16), "conv": (3, 192)}, published_state_bytes=4341760,
                             state_layers=3,
                             refuses=("tp", "prefix_cache", "kv_host_pages", "draft_model", "quantize.kv", "disagg")),
    # Ouro's: K and V joined, NO window and no per-slot state, so a context is its pages and prefixes are shared;
    # 2 layers run 3 times over one set of weights, a node a layer a PASS, taken pass-major (published in bf16: 16 +
    # 16 heads of 128, (32, 128), 8192 bytes a token a node)
    "looped": Kind(_looped, "paged_walk_fused", {"kv": (2 * 4, 16)},
                   tuple(f"layers_{i}/pass_{t}" for t in range(3) for i in range(2)), {"kv": (32, 128)}, 8192),
}
WHOLE_POOL = [name for name, row in KINDS.items() if row.whole_pool]

# what a kind may refuse at construction: name -> (engine arguments, the
# exception, what its message names), given the kind's ``Built``
REFUSALS = {
    "tp": lambda b: ({"tp": 2}, ValueError, f"{b.cfg.kv_cache_kind}-cache"),
    "prefix_cache": lambda b: ({"prefix_cache": 4}, CacheKindUnsupported, "prefix_cache"),
    "kv_host_pages": lambda b: ({"kv_host_pages": 8}, CacheKindUnsupported, "kv_host_pages"),
    "draft_model": lambda b: ({"draft_model": b.model, "draft_params": b.params}, CacheKindUnsupported, "draft_model"),
    "quantize.kv": lambda b: ({"quantize": QuantConfig(weights=None, kv="int8")}, CacheKindUnsupported, r"quantize\.kv"),
    "disagg": None,   # refused where the handoff is built, from a built engine: the test's own branch
}


class Built:
    """A kind's tiny model, parameters, prompts and reference, and the streams
    it served through each layout: each built when first asked for, once a
    process (``built``)."""

    def __init__(self, name):
        self.name, self.kind = name, KINDS[name]
        self.cls, self.cfg, self.published_keys, reference, self.published_cfg = self.kind.parts()
        self.model = self.cls(self.cfg, attention_impl="xla")
        self.params = jax.jit(self.model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        rng = np.random.default_rng(0)
        # with topk = 16: 37 and 50 are past it at once; 20 and 9 pass it while decoding
        self.prompts = [rng.integers(1, self.cfg.vocab_size, size=n).astype(np.int32) for n in (20, 37, 9, 50)]
        self.ref = reference(self.published_keys(self.cfg), meta.unbox(self.params))
        self._streams = {}

    def __iter__(self):      # ``cfg, model, params, prompts, ref = built(kind)``
        return iter((self.cfg, self.model, self.params, self.prompts, self.ref))

    def stream(self, path):
        """``(engine, tokens)`` of the four prompts, 12 new tokens each, served through ``path``."""
        if path not in self._streams:
            paged = {} if path == "row" else {"kv_page_size": self.kind.page, "paged_attention": path}
            self._streams[path] = serve(self.model, self.params, self.prompts, **paged)
        return self._streams[path]


@functools.lru_cache(maxsize=None)
def built(name) -> Built:
    return Built(name)


def serve(model, params, prompts, new_tokens=12, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefix_cache", None)
    eng = ServingEngine(model, params, decode_chunk_size=4, **kw)
    gcfg = GenerationConfig(max_new_tokens=new_tokens, temperature=0.0)
    reqs = [eng.submit(p, gcfg, key=jax.random.PRNGKey(i)) for i, p in enumerate(prompts)]
    eng.run()
    return eng, [list(r.tokens) for r in reqs]


def largest_gap(ref, prompts, streams):
    worst = 0.0
    for prompt, toks in zip(prompts, streams):
        gaps, controls, _, _ = common.emitted_token_gaps(ref, prompt, toks, 128)
        assert controls.min() > 100 * TOLERANCE      # the check is able to fail
        worst = max(worst, float(gaps.max()))
    return worst


def node_name(path):
    """A cache node's name in a row's ``layers``: its layer, and its pass where the stack is run more than once."""
    return "/".join(k for k in path if k.startswith(("layers_", "pass_")))


def paged_leaves(tree):
    """``{leaf name: (rows, lanes) a token}`` over a cache tree's per-token leaves."""
    return {path[-1].key: leaf.shape[-2:] for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
            if path[-1].key in PAGED_LEAVES}


@pytest.fixture(scope="module")
def kind(request):
    """``built(<the test's kind>)``; module-scoped, so that pytest runs a kind's
    cases together, and the kind's engines go with the last of them (its
    model stays, for the kind's own file)."""
    kind = built(request.param)
    yield kind
    kind._streams.clear()


def kinds(names=tuple(KINDS)):
    return pytest.mark.parametrize("kind", list(names), indirect=True)


@pytest.mark.parametrize("path", PATHS)
@kinds()
def test_prefill_then_decode_matches_the_reference(kind, path):
    """Prefill, then decode through the cache (``latent``: materialised, then
    absorbed; the indexed kinds: the learned mask, then score, select, attend
    over the selected rows): every emitted token is the reference's largest
    logit at its position within ``TOLERANCE``, the reference never having
    seen a cache. With an indexer contexts run to 62 tokens with 16 kept."""
    eng, toks = kind.stream(path)
    assert all(len(t) == 12 for t in toks)
    if hasattr(kind.cfg, "index_topk"):
        assert max(len(p) for p in kind.prompts) + 12 > 3 * kind.cfg.index_topk
    assert largest_gap(kind.ref, kind.prompts, toks) <= TOLERANCE
    assert eng.programs.resolved["decode_attention"] == (kind.kind.fused if path == "fused" else "einsum")


@kinds()
def test_the_transports_emit_one_stream(kind):
    assert kind.stream("row")[1] == kind.stream("gather")[1] == kind.stream("fused")[1]


@kinds(WHOLE_POOL)
def test_cache_leaves_hold_the_kinds_values_a_token_and_nothing_else(kind):
    """The pool's per-token leaves are the kind's and no other (no per-head K
    or V beside a latent, no separate ``k`` or ``v`` beside a joined leaf);
    every layout reads the same bytes a token a layer; and the published
    widths in bf16 read the published bytes."""
    row = kind.kind
    assert paged_leaves(kind.stream("fused")[0].cache.cache["pool"]) == row.leaves
    for path in PATHS:
        eng = kind.stream(path)[0]
        assert cache_bytes_per_token_layer(eng.cache.cache) == row.bytes
        assert eng.metrics.snapshot()["kv_bytes_per_token_layer"] == row.bytes
    model = kind.cls(kind.published_cfg, attention_impl="xla")
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    cache = jax.eval_shape(
        lambda p, i: model.clone(mode="prefill").apply(p, i, mutable=["cache"])[1]["cache"], shapes, ids)
    assert cache_bytes_per_token_layer(cache) == row.published_bytes
    assert paged_leaves(cache) == row.published_leaves
    # per-slot state: the kind's leaves and no other, (slots, width), the same bytes in every layout
    pool = kind.stream("fused")[0].cache.cache["pool"]
    assert {path[-1].key: leaf.shape for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]
            if path[-1].key in SLOT_STATE_LEAVES} == {name: (2,) + shape for name, shape in row.state.items()}
    for path in PATHS:
        assert slot_state_bytes_per_layer(kind.stream(path)[0].cache.cache) == row.state_bytes
    assert slot_state_bytes_per_layer(cache) == row.published_state_bytes


@kinds(WHOLE_POOL)
def test_fused_chunk_carries_every_leaf(kind):
    """PR 25's contract on the kind's pool: each layer's leaves ride the
    scan's carry, paired with their layer in execution order, and the
    chunk's cache holds the write WINDOW of each, not a row."""
    cfg, model, params, _, _ = kind
    page = kind.kind.page
    prefill, decode = serving_clones(model)
    ids = jnp.zeros((1, 16), jnp.int32)
    row = jax.eval_shape(lambda p, i: prefill.apply(p, i, mutable=["cache"])[1]["cache"], params, ids)

    def pool_of(row):
        mgr = PagedCacheManager(2, cfg.max_seq_len, page)
        mgr.allocate_from(row)
        return mgr.cache

    paged = jax.eval_shape(pool_of, row)
    pairs = ordered_kv_pool_pairs(paged["pool"])
    assert [node_name(layer) for layer in pairs] == list(kind.kind.layers)
    assert all([leaf.shape[-2:] for leaf in pair] == list(kind.kind.leaves.values()) for pair in pairs.values())
    state = jax.eval_shape(ServingEngine(model, params, num_slots=2, kv_page_size=page)._fresh_slot_state)
    jaxpr = jax.make_jaxpr(chunked_decode_step(decode, 4, cfg.max_seq_len, page_size=page,
                                               paged_attention="fused"))(params, paged, state)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    carried = [v.aval.shape for v in scans[0].invars[scans[0].params["num_consts"]:]]
    for pair in pairs.values():
        for leaf in pair:
            assert leaf.shape in carried
    for shape in kind.kind.state.values():     # a layer's state leaves ride the carry too, at their own rank
        assert carried.count((2,) + shape) == (kind.kind.state_layers or len(kind.kind.layers))
    # no per-token leaf as long as a row anywhere in the chunk
    rows = [v.aval.shape for e in jaxpr.jaxpr.eqns for v in e.outvars
            if len(v.aval.shape) == 4 and v.aval.shape[:2] == (2, cfg.max_seq_len)]
    assert not rows


@kinds([name for name, row in KINDS.items() if row.prefix_transports])
def test_prefix_sharing_on_the_pool_is_zero_copy_and_stream_identical(kind):
    """Prefix extract/seed and per-page fingerprints walk every leaf: the
    shared stream is the unshared one, no page is copied."""
    cfg, model, params, _, ref = kind
    page = kind.kind.page
    rng = np.random.default_rng(3)
    system = rng.integers(1, cfg.vocab_size, size=35).astype(np.int32)    # two whole pages
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, size=5 + i).astype(np.int32)])
               for i in range(4)]
    _, plain = serve(model, params, prompts, new_tokens=8, kv_page_size=page)
    for attention in kind.kind.prefix_transports:
        eng, shared = serve(model, params, prompts, new_tokens=8, kv_page_size=page,
                            paged_attention=attention, prefix_cache=PrefixCache(min_match=8))
        assert shared == plain
        snap = eng.metrics.snapshot()
        assert snap["prefix_hits"] >= 3 and snap["prefix_pages_shared"] >= 2 * snap["prefix_hits"]
        assert eng.cache.alloc.copy_bytes == 0
        eng.cache.check()
    assert largest_gap(ref, prompts, plain) <= TOLERANCE


@kinds(WHOLE_POOL)
def test_preemption_and_resume_give_the_undisturbed_stream(kind):
    """A short row: the shared cursor reaches its end, every request is
    preempted and resumed from its context (``paged_seed`` and the suffix
    prefill through the decode path, many query rows at once, each selecting
    for itself where the kind selects); the streams are those of an engine
    that never hit the wall."""
    _, model, params, prompts, _ = kind
    picks = [prompts[0][:12], prompts[1][:17], prompts[2]]
    _, want = serve(model, params, picks, new_tokens=24, num_slots=3)
    short = kind.cls(dataclasses.replace(kind.cfg, max_seq_len=64), attention_impl="xla")
    eng, got = serve(short, params, picks, new_tokens=24, num_slots=2, kv_page_size=kind.kind.page,
                     admission="eager")
    assert eng.metrics.snapshot()["preemptions"] > 0
    assert got == want
    eng.cache.check()


@kinds()
def test_a_chunk_run_ahead_gives_the_stream_of_one_chunk_at_a_time(kind):
    """Every slot held and a queue behind them, on the fused path the cells
    run: the engine calls the next chunk before it reads the last one back
    (``serving/engine.py``, "Decode hot path"), above the cache. Whatever the
    kind keeps (window pages freed behind a PROJECTED cursor, a slot's state, a
    recurrent layer's, a node a layer a pass), every request's tokens and final
    key are those of the same engine held to one chunk at a time (the rule
    patched to say no, from here), greedy and sampled, and the pages' invariant
    holds with every page back."""
    answers = (33, 21, 40, 26)

    def run(ahead):
        eng = ServingEngine(kind.model, kind.params, decode_chunk_size=4, num_slots=2, prefix_cache=None,
                            kv_page_size=kind.kind.page, paged_attention="fused")
        if not ahead:
            eng._can_run_ahead = lambda: False
        reqs = [eng.submit(p, GenerationConfig(max_new_tokens=n, temperature=0.7 if i % 2 else 0.0,
                                               top_k=20 if i % 2 else None), key=jax.random.PRNGKey(i))
                for i, (p, n) in enumerate(zip(kind.prompts, answers))]
        eng.run()
        eng.cache.check()
        assert eng._in_flight is None and eng.cache.alloc.free_pages == eng.cache.alloc.num_pages - 1
        return eng, [(list(r.tokens), r.key.tolist()) for r in reqs]

    plain, want = run(False)
    eng, got = run(True)
    assert got == want and [len(t) for t, _ in got] == list(answers)
    m = eng.metrics
    assert plain.metrics.chunks_run_ahead == 0 < m.chunks_run_ahead < m.chunks == plain.metrics.chunks
    if kind.kind.whole_pool is False:   # the window kind: the same pages went back behind the cursor, a chunk early
        assert eng.cache.window_pages_freed_total == plain.cache.window_pages_freed_total > 0


@pytest.mark.parametrize("kind, what", [(name, what) for name, row in KINDS.items() for what in row.refuses],
                         indirect=["kind"])
def test_a_kind_refuses_by_name_what_it_cannot_have(kind, what):
    _, model, params, _, _ = kind
    if what == "disagg":
        from neuronx_distributed_tpu.serving.disagg import DisaggregatedServer

        with pytest.raises(CacheKindUnsupported, match="disaggregation"):
            DisaggregatedServer(ServingEngine(model, params, num_slots=2, kv_page_size=kind.kind.page))
        return
    kwargs, error, names = REFUSALS[what](kind)
    with pytest.raises(error, match=names):
        ServingEngine(model, params, num_slots=2, kv_page_size=kind.kind.page, **kwargs)
