"""Each plain reference against the program's model class, tiny, on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

DATA = os.path.join(os.path.dirname(__file__), "data", "configs")


def _config(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def _model_and_params(config, runner="train"):
    import importlib

    family = importlib.import_module(f"perfbench.families.{config['family']}")
    model = family.build(config["model"], runner=runner, max_seq_len=64)
    # compare in float32: this test is about the mathematics, not bf16 rounding
    model = model.clone(config=model.config.__class__(**{
        **model.config.__dict__, "dtype": jnp.float32, "param_dtype": jnp.float32}))
    if hasattr(model, "attention_impl"):
        model = model.clone(attention_impl="xla")
    params = jax.jit(model.init)(jax.random.PRNGKey(3), jnp.zeros((2, 16), jnp.int32))
    return family, model, meta.unbox(params)


@pytest.mark.parametrize("name", ["codegen2-7b-serve", "mixtral-8x7b-serve"])
def test_reference_logits_match_the_model(name):
    import importlib

    config = _config(name)
    family, model, params = _model_and_params(config)
    ids = np.random.default_rng(0).integers(1, 256, (2, 24)).astype(np.int32)
    out = model.apply(params, jnp.asarray(ids))
    logits = out[0] if isinstance(out, tuple) else out
    ref = importlib.import_module(f"perfbench.references.{family.reference}").Reference(
        config["model"], params)
    np.testing.assert_allclose(np.asarray(ref.logits(ids)), np.asarray(logits, np.float32),
                               atol=2e-4, rtol=2e-4)


def test_reference_loss_matches_the_models_cross_entropy():
    from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy
    from perfbench.references.codegen import Reference

    config = _config("codegen2-7b-train-tp4")
    _, model, params = _model_and_params(config)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 256, (3, 25)).astype(np.int32)
    want = float(parallel_cross_entropy(model.apply(params, jnp.asarray(ids[:, :-1])),
                                        jnp.asarray(ids[:, 1:])).mean())
    got = Reference(config["model"], params).loss(ids[:, :-1], ids[:, 1:])
    assert got == pytest.approx(want, abs=2e-4)


def test_the_logit_check_can_fail():
    """A wrong token must cost far more than the tolerance, a right one nothing."""
    from perfbench.references import common
    from perfbench.references.codegen import Reference

    config = _config("codegen2-7b-serve")
    _, _, params = _model_and_params(config)
    ref = Reference(config["model"], params)
    prompt = np.arange(1, 13, dtype=np.int32)
    ids = np.zeros((1, 32), np.int32)
    ids[0, :12] = prompt
    greedy = []
    for i in range(4):   # the reference's own greedy continuation
        tok = int(np.argmax(np.asarray(ref.logits(ids)[0, 11 + i])))
        greedy.append(tok)
        ids[0, 12 + i] = tok
    gaps, controls, margin, router = common.emitted_token_gaps(ref, prompt, greedy, 32)
    assert (gaps == 0.0).all() and (controls > 0.0).all() and margin > 0.0
    assert router is None                      # a dense model has no routing to excuse
    assert common.judge_gaps(gaps, router, 0.1, 0.0) == (True, 0, 0)
    assert common.judge_gaps(controls, router, float(controls.min()) / 2, 0.0)[0] is False


def test_the_router_margin_is_the_references_own_and_excuses_only_near_ties():
    """The Mixtral reference reports, per position, how close its top-2 choice
    was (the smallest gap over the layers between its second and third router
    logit); the comparison excuses a gap there and nowhere else."""
    import importlib

    from perfbench.references import common

    config = _config("mixtral-8x7b-serve")
    family, model, params = _model_and_params(config)
    ref = importlib.import_module(f"perfbench.references.{family.reference}").Reference(
        config["model"], params)
    ids = np.random.default_rng(4).integers(1, 256, (1, 32)).astype(np.int32)
    logits, margin = ref.logits_and_router_margin(ids)
    margin = np.asarray(margin)
    assert margin.shape == (1, 32) and np.isfinite(margin).all() and (margin >= 0).all()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref.logits(ids)))
    # the gaps of arbitrary tokens are large everywhere: excused exactly where the margin is small
    tokens = ids[0, 12:20]
    gaps, _, _, router = common.emitted_token_gaps(ref, ids[0, :12], tokens, 32)
    np.testing.assert_allclose(router, margin[0, 11:19])
    cut = float(np.sort(router)[2:4].mean())   # three positions count as near-ties
    ok, over, exempt = common.judge_gaps(gaps, router, 1e-6, cut)
    assert exempt == 3 and over == int((gaps > 1e-6).sum()) - int(((gaps > 1e-6) & (router < cut)).sum())


@pytest.mark.parametrize("gaps, router, want", [
    ([0.0, 0.05, 0.1], None, (True, 0, 0)),
    ([0.0, 0.5, 0.0], None, (False, 1, 0)),                       # no router: nothing is excused
    ([0.0, 0.5, 0.0], [1.0, 0.001, 1.0], (True, 0, 1)),           # excused at the near-tie
    ([0.5, 0.0, 0.0], [1.0, 0.001, 1.0], (False, 1, 1)),          # and nowhere else
    ([0.0, float("nan"), 0.0], [1.0, 0.001, 1.0], (False, 1, 1)),  # never a NaN, near-tie or not
    ([0.0, float("inf"), 0.0], [1.0, 0.001, 1.0], (False, 1, 1)),
])
def test_judge_gaps(gaps, router, want):
    from perfbench.references import common

    assert common.judge_gaps(gaps, router, 0.1, 0.02) == want


def test_rotary_departure_is_a_permutation_of_channels():
    """Half-split rotary (the repo's) equals the published interleaved rotary
    after a fixed permutation of the rotated channels."""
    from perfbench.references import common

    x = np.random.default_rng(2).normal(size=(1, 5, 2, 8)).astype(np.float32)
    pos = jnp.arange(5)[None]
    half = np.asarray(common.rope_half_split(jnp.asarray(x), pos, 8, 10000.0))
    perm = [0, 2, 4, 6, 1, 3, 5, 7]                         # interleaved -> half-split layout
    inv = 1.0 / (10000.0 ** (np.arange(0, 8, 2) / 8))
    ang = np.arange(5)[:, None] * inv
    xi = x[..., perm]                                        # treat x as interleaved pairs (2i, 2i+1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos, sin = np.cos(ang)[None, :, None, :], np.sin(ang)[None, :, None, :]
    inter = np.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(
        np.asarray(common.rope_half_split(jnp.asarray(xi), pos, 8, 10000.0)), inter[..., perm], atol=1e-5)
    assert half.shape == x.shape
