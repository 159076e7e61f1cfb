"""The fused decode chunk stages its tokens in its write WINDOW (PR 27).

``paged_attention="fused"`` attends the page pool through the paged kernel,
so nothing in the chunk attends a logical K/V view; the chunk gathers, per
per-token leaf, only the ``n_win`` pages a slot its columns can fall in, the
model's decode write lands inside that window, and each step scatters the
window into the carried pool. Pinned here, on the CPU (kernel interpreted),
for a ``KVCache`` model and a ``LatentKVCache`` one:

* values: one chunk over a hand-paged cache emits the ``gather`` transport's
  tokens and leaves its pool, with the cursor mid-page, on a page boundary,
  crossing one, within ``n_win`` pages of the row's end (the clip), running
  into the row's end, over a chunk longer than a page; one slot retires
  mid-chunk, one is empty, and two slots map the same physical prefix pages,
  which stay byte for byte what they were;
* structure: no array of a logical leaf's shape anywhere in the fused
  chunk's jaxpr (the ``gather`` chunk's has them: the control)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference.generate import (
    chunked_decode_step,
    serving_clones,
)
from neuronx_distributed_tpu.models.deepseek_v2 import (
    DeepseekV2ForCausalLM,
    tiny_deepseek_v2,
)
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.modules.attention import (
    PAGED_LEAVES,
    cache_leaf_name,
    cache_node_at,
    decode_attention,
    fused_paged_attention_scope,
    gather_cache_pages,
)

SLOTS, PAGE, ROW = 4, 16, 128
N_LOG = ROW // PAGE

# name: (entry cursor, chunk size)
CASES = {
    "mid_page": (21, 4),            # columns 21-24, inside logical page 1
    "page_aligned": (32, 4),
    "crosses_a_boundary": (29, 6),  # 29-34 over column 32
    "longer_than_a_page": (40, 20),  # n_win = 3
    "clipped_at_the_rows_end": (117, 6),  # page0 = 7 of 8, held at 6
    "runs_into_the_rows_end": (125, 6),   # 3 steps are allowed
}


def _llama():
    cfg = tiny_llama(num_layers=2, hidden_size=32, intermediate_size=96,
                     vocab_size=128, num_heads=4, num_kv_heads=2,
                     max_seq_len=ROW)
    return cfg, LlamaForCausalLM(cfg, attention_impl="xla")


def _deepseek():
    cfg = tiny_deepseek_v2(max_seq_len=ROW)
    return cfg, DeepseekV2ForCausalLM(cfg, attention_impl="xla")


@pytest.fixture(scope="module", params=["kv", "latent"])
def setup(request):
    cfg, model = {"kv": _llama, "latent": _deepseek}[request.param]()
    params = jax.jit(model.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )
    return cfg, model, params


def _paged_after_prefill(cfg, model, params, start, chunk):
    """A paged pytree as the engine would hold it after prefills that left
    the shared cursor at ``start``: slots 0 and 1 hold ONE prompt and map
    the same physical pages for every page wholly below the cursor (the
    copy-on-write prefix), slot 2 a shorter left-padded prompt, slot 3
    nothing. Pages the chunk cannot reach stay on the null page. Returns
    ``(paged, shared page ids)``."""
    rng = np.random.default_rng(start)
    ids = rng.integers(1, cfg.vocab_size, size=(SLOTS, start)).astype(np.int32)
    ids[1] = ids[0]
    mask = np.ones((SLOTS, start), bool)
    mask[2, : start // 3] = False
    mask[3] = False
    prefill, _ = serving_clones(model)
    row = jax.jit(
        lambda p, i, m: prefill.apply(p, i, padding_mask=m, mutable=["cache"])
    )(params, jnp.asarray(ids), jnp.asarray(mask))[1]["cache"]

    whole = start // PAGE
    reach = min((start + chunk - 1) // PAGE, N_LOG - 1)
    n_pages = 1 + SLOTS * N_LOG
    table = np.zeros((SLOTS, N_LOG), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    table[:, : reach + 1] = perm[: SLOTS * (reach + 1)].reshape(SLOTS, -1)
    table[1, :whole] = table[0, :whole]
    table[3] = 0

    def page_out(path, leaf):
        if cache_leaf_name(path) not in PAGED_LEAVES:
            return leaf
        pages = np.asarray(leaf).reshape((SLOTS, N_LOG, PAGE) + leaf.shape[2:])
        pool = np.zeros((n_pages, PAGE) + leaf.shape[2:], pages.dtype)
        for b in range(SLOTS - 1):
            pool[table[b]] = pages[b]
        pool[0] = 0
        return jnp.asarray(pool)

    pool = jax.tree_util.tree_map_with_path(page_out, row)
    return {"pages": jnp.asarray(table), "pool": pool}, table[0, :whole]


def _state(chunk):
    return {
        "tok": jnp.asarray([5, 9, 3, 0], jnp.int32),
        "keys": jax.vmap(jax.random.PRNGKey)(jnp.arange(SLOTS)),
        "active": jnp.asarray([True, True, True, False]),
        # slot 1 samples, the others are greedy
        "temp": jnp.asarray([0.0, 0.8, 0.0, 0.0], jnp.float32),
        "topk": jnp.asarray([0, 11, 0, 0], jnp.int32),
        "topp": jnp.ones((SLOTS,), jnp.float32),
        # slot 2 retires after its second token, mid-chunk
        "remaining": jnp.asarray([chunk + 5, chunk + 5, 2, 0], jnp.int32),
        "eos": jnp.full((SLOTS,), -1, jnp.int32),
    }


def _run(cfg, model, params, paged, chunk, mode):
    decode = serving_clones(model)[1]
    fn = jax.jit(chunked_decode_step(
        decode, chunk, cfg.max_seq_len, page_size=PAGE, paged_attention=mode
    ))
    return fn(params, paged, _state(chunk))


def _per_token_leaves(tree):
    return [
        np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
        if cache_leaf_name(path) in PAGED_LEAVES
    ]


@pytest.mark.parametrize("case", list(CASES))
def test_fused_chunk_emits_gathers_stream_and_leaves_its_pool(setup, case):
    cfg, model, params = setup
    start, chunk = CASES[case]
    paged, shared = _paged_after_prefill(cfg, model, params, start, chunk)
    gather = _run(cfg, model, params, paged, chunk, "gather")
    fused = _run(cfg, model, params, paged, chunk, "fused")

    steps = min(chunk, ROW - start)
    assert int(fused[4]) == steps
    np.testing.assert_array_equal(fused[3], [steps, steps, min(2, steps), 0])
    # tokens, counts, executed steps, the keys' copy; the per-slot state
    for got, want in zip(fused[2:], gather[2:]):
        np.testing.assert_array_equal(got, want)
    for name in fused[1]:
        np.testing.assert_array_equal(fused[1][name], gather[1][name])

    table = np.asarray(paged["pages"])
    mapped = np.unique(table[table > 0])
    out, ref = fused[0], gather[0]
    np.testing.assert_array_equal(out["pages"], ref["pages"])
    for path, leaf in jax.tree_util.tree_flatten_with_path(out["pool"])[0]:
        want = cache_node_at(ref["pool"], path)
        if cache_leaf_name(path) not in PAGED_LEAVES:   # index, kv_valid
            np.testing.assert_array_equal(leaf, want)
            continue
        # the two transports attend through different arithmetic (einsum on
        # the view, the kernel on the pool): a later layer's new K/V agree
        # to rounding, every mapped page of them
        np.testing.assert_allclose(
            np.asarray(leaf)[mapped], np.asarray(want)[mapped], atol=2e-5
        )

    # what the chunk may not touch, bit for bit: every column below the
    # entry cursor, the shared prefix pages first of all (two slots scatter
    # them when the clipped window reaches back over them)
    for was, now in zip(_per_token_leaves(paged["pool"]),
                        _per_token_leaves(out["pool"])):
        np.testing.assert_array_equal(was[shared], now[shared])
        for b in range(SLOTS - 1):
            below = np.arange(ROW) < start
            rows_was = was[table[b]].reshape((ROW,) + was.shape[2:])
            rows_now = now[table[b]].reshape((ROW,) + now.shape[2:])
            np.testing.assert_array_equal(rows_was[below], rows_now[below])
    # and the new columns are there: slot 0's K/V at the cursor changed
    col = start % PAGE
    page = table[0, start // PAGE]
    assert any(
        np.abs(now[page, col]).max() > 0 and not np.array_equal(
            was[page, col], now[page, col])
        for was, now in zip(_per_token_leaves(paged["pool"]),
                            _per_token_leaves(out["pool"]))
    )


def _shapes(jaxpr):
    """Every array shape in a jaxpr, sub-jaxprs (scan, cond) included."""
    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            if hasattr(var, "aval") and hasattr(var.aval, "shape"):
                yield tuple(var.aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("chunk", [4, 20])
def test_fused_chunk_holds_no_array_of_a_logical_leafs_shape(setup, chunk):
    cfg, model, params = setup
    paged, _ = _paged_after_prefill(cfg, model, params, 40, chunk)
    decode = serving_clones(model)[1]
    view = jax.eval_shape(lambda c: gather_cache_pages(c, PAGE), paged)
    logical = {
        leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(view)[0]
        if cache_leaf_name(path) in PAGED_LEAVES
    }
    assert logical and all(s[:2] == (SLOTS, ROW) for s in logical)

    def shapes(mode):
        fn = chunked_decode_step(
            decode, chunk, cfg.max_seq_len, page_size=PAGE,
            paged_attention=mode,
        )
        return set(_shapes(jax.make_jaxpr(fn)(params, paged, _state(chunk)).jaxpr))

    assert logical <= shapes("gather")          # the control
    fused = shapes("fused")
    assert not logical & fused
    # what it holds instead: the window, (chunk - 1) // PAGE + 2 pages a slot
    n_win = (chunk - 1) // PAGE + 2
    assert {(SLOTS, n_win * PAGE) + s[2:] for s in logical} <= fused


def test_a_tree_mask_inside_a_fused_frame_is_refused():
    """The einsum a Medusa tree mask needs would attend the frame's window
    leaves as if they were the row."""
    q = jnp.zeros((1, 2, 2, 8))
    window = jnp.zeros((1, 2 * PAGE, 2, 8))
    pool = jnp.zeros((3, PAGE, 2, 8))
    table = jnp.zeros((1, 4), jnp.int32)
    with fused_paged_attention_scope({("l",): (pool, pool)}, table, PAGE, 0):
        with pytest.raises(ValueError, match="tree mask"):
            decode_attention(
                q, window, window, jnp.arange(2),
                mask=jnp.ones((2, 4 * PAGE), bool),
                kv_valid=jnp.ones((1, 4 * PAGE), bool),
            )
