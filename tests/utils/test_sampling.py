"""On-device sampling tests (reference analogue: utils/sampling.py unit use)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.utils.sampling import greedy, sample, sample_per_row, sample_row

B, V = 8, 32


def _logits(seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, V), jnp.float32)


def test_greedy_is_argmax():
    x = _logits()
    np.testing.assert_array_equal(np.asarray(greedy(x)), np.asarray(jnp.argmax(x, -1)))


def test_temperature_zero_is_greedy():
    x = _logits()
    out = sample(x, jax.random.PRNGKey(1), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(greedy(x)))


def test_top_k_restricts_support():
    x = _logits()
    topk_ids = np.asarray(jax.lax.top_k(x, 3)[1])
    for seed in range(10):
        out = np.asarray(sample(x, jax.random.PRNGKey(seed), top_k=3))
        for b in range(B):
            assert out[b] in topk_ids[b]


def test_top_p_restricts_support():
    # peaked distribution: top-1 has prob > 0.9 → top_p=0.5 must pick it
    x = jnp.zeros((B, V)).at[:, 7].set(10.0)
    for seed in range(5):
        out = np.asarray(sample(x, jax.random.PRNGKey(seed), top_p=0.5))
        assert (out == 7).all()


def test_sampling_follows_distribution():
    # two-token distribution with 3:1 odds; frequency must roughly match
    x = jnp.log(jnp.array([[3.0, 1.0] + [1e-9] * (V - 2)]))
    counts = np.zeros(V)
    for seed in range(200):
        tok = int(sample(x, jax.random.PRNGKey(seed))[0])
        counts[tok] += 1
    assert counts[0] > counts[1] > 0
    assert counts[2:].sum() == 0


# --- the traced per-row sampler (serving) -------------------------------------
#
# It branches once for the batch, on whether any KEPT row samples, before any
# work on the vocabulary (PR 36). Tokens are what they were: held here against
# the formula as it stood before the branch, and against `sample` with python
# constants (what `generate()` runs).

ROWS, VOCAB = 4, 96          # V not a power of two


def _row_before_the_branch(logits, key, temperature, top_k, top_p):
    """``sample_row`` as it stood before PR 36, line for line: the reference."""
    v = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.asarray(temperature, jnp.float32)
    x = logits.astype(jnp.float32) / jnp.where(temp == 0.0, 1.0, temp)
    k = jnp.asarray(top_k, jnp.int32)
    desc = jnp.sort(x, axis=-1)[..., ::-1]
    kth = desc[jnp.clip(k, 1, v) - 1]
    x = jnp.where((k > 0) & (x < kth), -jnp.inf, x)
    p = jnp.asarray(top_p, jnp.float32)
    sorted_logits = jnp.where((k > 0) & (desc < kth), -jnp.inf, desc)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_mask = cum - probs < p
    thresh = jnp.where(cutoff_mask, sorted_logits, jnp.inf).min(-1)
    x = jnp.where((p < 1.0) & (x < thresh), -jnp.inf, x)
    gumbel = jax.random.gumbel(key, x.shape, jnp.float32)
    tok = jnp.argmax(x + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temp == 0.0, greedy_tok, tok)


def _tied_logits(dtype, seed=3):
    """Logits on a grid of halves, so values repeat all over a row, and in
    every row the largest value twice (the first index has to win)."""
    x = jnp.round(2.0 * jax.random.normal(jax.random.PRNGKey(seed), (ROWS, VOCAB))) / 2.0
    x = x.at[:, 70].set(9.0).at[:, 11].set(9.0)
    return x.astype(dtype)


GREEDY, PLAIN = (0.0, None, None), (0.8, None, None)
BATCHES = {
    "all_greedy": [GREEDY] * ROWS,
    "mixed": [GREEDY, GREEDY, (0.7, 5, 0.9), GREEDY],
    "no_filter": [PLAIN, (1.3, None, None), PLAIN, (0.5, None, None)],
    "top_k": [(0.8, 4, None), (1.0, 1, None), (0.6, 17, None), (1.2, VOCAB, None)],
    "top_p": [(0.8, None, 0.5), (1.0, None, 0.05), (0.6, None, 0.95), (1.2, None, 0.7)],
    "both": [(0.8, 4, 0.5), (1.0, 30, 0.9), (0.6, 2, 0.99), (1.2, 50, 0.3)],
}


def _sentinels(configs):
    """Per-row config arrays with the traced sampler's sentinels for None."""
    return (jnp.array([t for t, _, _ in configs], jnp.float32),
            jnp.array([k or 0 for _, k, _ in configs], jnp.int32),
            jnp.array([1.0 if p is None else p for _, _, p in configs], jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("batch", list(BATCHES))
def test_per_row_sampler_gives_the_tokens_it_gave_before_the_branch(batch, dtype):
    """Row for row and bit for bit: (a) the formula before PR 36, vmapped;
    (b) ``sample`` with the row's python-constant config and key."""
    configs = BATCHES[batch]
    logits = _tied_logits(dtype)
    keys = jax.random.split(jax.random.PRNGKey(17), ROWS)
    temp, topk, topp = _sentinels(configs)
    got = np.asarray(jax.jit(sample_per_row)(logits, keys, temp, topk, topp))
    assert got.dtype == np.int32
    before = np.asarray(jax.jit(jax.vmap(_row_before_the_branch))(logits, keys, temp, topk, topp))
    np.testing.assert_array_equal(got, before)
    for row, (t, k, p) in enumerate(configs):
        solo = sample(logits[row][None], keys[row], temperature=t, top_k=k, top_p=p)
        assert got[row] == int(solo[0]), (batch, row)
        if t == 0.0:
            assert got[row] == 11          # the tied maximum's first index


@pytest.mark.parametrize("kept", [(True, True, False, True), (True, True, True, False),
                                  (False, False, False, False)],
                         ids=["sampled_row_dropped", "greedy_row_dropped", "none_kept"])
def test_only_kept_rows_decide_and_every_kept_token_is_what_it_was(kept):
    """``kept``: a dropped row's temperature holds nothing open, a kept row's
    token is the formula's whatever is dropped beside it, and a dropped
    sampled row in an otherwise greedy batch reads its ``argmax``."""
    logits = _tied_logits(jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(17), ROWS)
    config = _sentinels(BATCHES["mixed"])               # row 2 samples
    mask = jnp.asarray(kept)
    got = np.asarray(jax.jit(sample_per_row)(logits, keys, *config, kept=mask))
    before = np.asarray(jax.jit(jax.vmap(_row_before_the_branch))(logits, keys, *config))
    np.testing.assert_array_equal(got[np.asarray(kept)], before[np.asarray(kept)])
    if not kept[2]:
        np.testing.assert_array_equal(got, np.asarray(greedy(logits)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("config", [GREEDY, PLAIN, (0.8, 4, None), (0.8, None, 0.5), (0.8, 4, 0.5)],
                         ids=["greedy", "no_filter", "top_k", "top_p", "both"])
def test_first_token_program_gives_the_tokens_it_gave_before_the_branch(config, dtype):
    """``sample_row`` alone, unbatched, as ``ServingEngine._first_token`` jits it."""
    logits = _tied_logits(dtype)[1]
    key = jax.random.PRNGKey(23)
    temp, topk, topp = (a[0] for a in _sentinels([config]))
    got = jax.jit(sample_row)(logits, key, temp, topk, topp)
    assert got.dtype == jnp.int32 and got.shape == ()
    assert int(got) == int(jax.jit(_row_before_the_branch)(logits, key, temp, topk, topp))
    t, k, p = config
    assert int(got) == int(sample(logits[None], key, temperature=t, top_k=k, top_p=p)[0])


def _walk(jaxpr, skip=None):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its equations,
    the equation ``skip`` (wherever it is nested) and all under it left out."""
    for eqn in jaxpr.eqns:
        if eqn is skip:
            continue
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, skip)


def _primitives(jaxpr, skip=None):
    return {eqn.primitive.name for eqn in _walk(jaxpr, skip)}


VOCABULARY_WORK = {"sort", "cumsum", "random_bits"}


def _the_samplers_branch(jaxpr):
    """The ONE conditional that holds the sort: a scalar predicate, the
    vocabulary's work on its sampling side alone, an ``argmax`` (and its
    cast) and no more on the other, and none of that work anywhere else in
    the program. A ``cond`` under ``vmap`` with a per-row predicate would
    show as a ``select_n`` over both sides and no such equation."""
    sampler = [e for e in _walk(jaxpr) if e.primitive.name == "cond"
               and any(_primitives(b.jaxpr) <= {"argmax", "convert_element_type"}
                       and "argmax" in _primitives(b.jaxpr) for b in e.params["branches"])]
    assert len(sampler) == 1, "no ONE conditional with an argmax and no more on one side"
    (cond,) = sampler
    assert cond.invars[0].aval.shape == (), "a batched predicate: both sides would run"
    greedy_side, sampling_side = cond.params["branches"]    # index 0: the predicate is false
    assert not VOCABULARY_WORK & _primitives(greedy_side.jaxpr)
    assert VOCABULARY_WORK <= _primitives(sampling_side.jaxpr)
    assert not VOCABULARY_WORK & _primitives(jaxpr, skip=cond)
    return cond


@pytest.mark.parametrize("entry", ["sample_per_row", "sample_per_row_kept", "sample_row"])
def test_the_sampler_sorts_only_inside_its_sampling_branch(entry):
    logits = _tied_logits(jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(0), ROWS)
    config = _sentinels(BATCHES["both"])
    if entry == "sample_row":
        jaxpr = jax.make_jaxpr(sample_row)(logits[0], keys[0], *(a[0] for a in config))
    elif entry == "sample_per_row":
        jaxpr = jax.make_jaxpr(sample_per_row)(logits, keys, *config)
    else:
        jaxpr = jax.make_jaxpr(
            lambda *a: sample_per_row(*a[:-1], kept=a[-1]))(logits, keys, *config, jnp.ones((ROWS,), bool))
    _the_samplers_branch(jaxpr.jaxpr)


def test_the_sampling_side_is_the_formula_before_the_branch():
    """A mixed batch pays what it paid: the sampling side holds the
    primitives of the old formula under ``vmap``, each as many times."""
    import collections

    def counted(jaxpr):
        c = collections.Counter()
        for eqn in jaxpr.eqns:
            subs = list(jax.core.jaxprs_in_params(eqn.params))
            if eqn.primitive.name in ("pjit", "jit", "closed_call", "custom_jvp_call") and subs:
                for sub in subs:
                    c += counted(sub)
            else:
                c[eqn.primitive.name] += 1
        return c

    logits = _tied_logits(jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(0), ROWS)
    config = _sentinels(BATCHES["mixed"])
    cond = _the_samplers_branch(jax.make_jaxpr(sample_per_row)(logits, keys, *config).jaxpr)
    before = jax.make_jaxpr(jax.vmap(_row_before_the_branch))(logits, keys, *config).jaxpr
    assert counted(cond.params["branches"][1].jaxpr) == counted(before)


@pytest.fixture(scope="module")
def small_chunk():
    """``(chunk program, params, cache, state)``: a decode chunk of 4 steps
    over 2 row-per-slot slots of a tiny Llama, both slots greedy."""
    from neuronx_distributed_tpu.inference.generate import chunked_decode_step, serving_clones
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama

    slots = 2
    model = LlamaForCausalLM(tiny_llama(max_seq_len=32), attention_impl="xla")
    ids = jnp.arange(1, 9, dtype=jnp.int32)[None]
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    prefill, decode = serving_clones(model)
    row = jax.jit(lambda p, i: prefill.apply(p, i, mutable=["cache"])[1]["cache"])(params, ids)
    cache = jax.tree.map(lambda a: jnp.concatenate([a] * slots) if a.ndim else a, row)
    state = {
        "tok": jnp.full((slots,), 3, jnp.int32), "keys": jnp.zeros((slots, 2), jnp.uint32),
        "active": jnp.ones((slots,), jnp.bool_), "temp": jnp.zeros((slots,), jnp.float32),
        "topk": jnp.zeros((slots,), jnp.int32), "topp": jnp.ones((slots,), jnp.float32),
        "remaining": jnp.full((slots,), 4, jnp.int32), "eos": jnp.full((slots,), -1, jnp.int32),
    }
    return chunked_decode_step(decode, 4, 32), params, cache, state


def test_a_decode_chunk_holds_one_conditional_round_the_sampler(small_chunk):
    """Under the chunk's scan and its live step: the same one conditional on a
    scalar, and nothing of the vocabulary's work beside it."""
    fn, *operands = small_chunk
    _the_samplers_branch(jax.make_jaxpr(fn)(*operands).jaxpr)


def test_a_done_slot_asks_the_sampler_for_nothing(small_chunk):
    """A freed slot keeps its last request's temperature: it may not hold the
    sampling side open for the live, greedy slots. The chunk hands the sampler
    ``kept = ~done``, so the (never emitted) token of such a row is an
    ``argmax`` whatever its key, and the live slot's stream is what it was; a
    LIVE sampled row's stream follows its key as before."""
    fn, params, cache, state = small_chunk
    chunk = jax.jit(fn)

    def tokens(active, key):
        s = dict(state, temp=state["temp"].at[0].set(1.0), active=state["active"].at[0].set(active),
                 keys=state["keys"].at[0].set(jnp.asarray(key, jnp.uint32)))
        toks, counts = chunk(params, cache, s)[2:4]
        return np.asarray(toks), np.asarray(counts)

    idle_a, counts = tokens(False, (1, 2))
    idle_b, _ = tokens(False, (3, 4))
    assert list(counts) == [0, 4]
    np.testing.assert_array_equal(idle_a, idle_b)
    all_greedy = np.asarray(chunk(params, cache, state)[2])
    np.testing.assert_array_equal(idle_a[:, 1], all_greedy[:, 1])
    live_a, counts = tokens(True, (1, 2))
    live_b, _ = tokens(True, (3, 4))
    assert list(counts) == [4, 4] and (live_a[:, 0] != live_b[:, 0]).any()
    np.testing.assert_array_equal(live_a[:, 1], all_greedy[:, 1])       # a greedy row beside it: its argmax
