"""What a prefill returns, in EVERY causal LM of ``models/`` (the contract of
``models/__init__.py``): the head applied to the last position alone, logits
``(B, 1, V)``, equal to ``mode="train"``'s last row, and no array of
``padded x vocab`` elements anywhere in the prefill's program."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu import models
from neuronx_distributed_tpu.inference.utils import unwrap_logits
from neuronx_distributed_tpu.models.medusa import MedusaForCausalLM
from tests.models.jitted import forward, through_the_cache

VOCAB = 384      # no other width of a tiny preset, so a shape that ends in it is logits
PADDED = 256     # the bucket whose program is searched for a PADDED x VOCAB array

LMS = {
    "llama": (models.LlamaForCausalLM, models.tiny_llama),
    "mixtral": (models.MixtralForCausalLM, models.tiny_mixtral),
    "codegen": (models.CodeGenForCausalLM, models.tiny_codegen),
    "gpt_neox": (models.GPTNeoXForCausalLM, models.tiny_gpt_neox),
    "dbrx": (models.DbrxForCausalLM, models.tiny_dbrx),
    "deepseek_v2": (models.DeepseekV2ForCausalLM, models.tiny_deepseek_v2),
    "keye_vl2": (models.KeyeVL2ForCausalLM, models.tiny_keye_vl2),
    "glm_moe_dsa": (models.GlmMoeDsaForCausalLM, models.tiny_glm_moe_dsa),
    "afmoe": (models.AfmoeForCausalLM, models.tiny_afmoe),
    "zaya": (models.ZayaForCausalLM, models.tiny_zaya),
    "medusa": (MedusaForCausalLM, models.tiny_llama),
}
# Medusa's call takes positions and a tree mask, no padding mask: its prompts are unpadded
TAKES_PADDING = [name for name in LMS if name != "medusa"]


def _rows(out):
    """Every array of logits a model returns: the head's, and Medusa's heads'."""
    if isinstance(out, tuple) and not isinstance(out[1], dict):
        return list(out)
    return [unwrap_logits(out)]


@functools.lru_cache(maxsize=None)
def _lm(name):
    cls, tiny = LMS[name]
    kw = {"attention_impl": "xla"} if "attention_impl" in cls.__dataclass_fields__ else {}
    model = cls(tiny(vocab_size=VOCAB, max_seq_len=PADDED + 8), **kw)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 1, VOCAB)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    return model, params, ids


@pytest.mark.parametrize("name", list(LMS))
def test_prefill_returns_the_last_row_alone(name):
    model, params, ids = _lm(name)
    out, _ = through_the_cache(model.clone(mode="prefill"), params, ids)
    want = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in _rows(forward(model, params, ids))]
    for got, every in zip(_rows(out), want):
        assert got.shape == every.shape[:1] + (1,) + every.shape[2:] and got.shape[-1] == VOCAB


@pytest.mark.parametrize("name", list(LMS))
def test_that_row_is_the_train_modes_last_row(name):
    model, params, ids = _lm(name)
    out, _ = through_the_cache(model.clone(mode="prefill"), params, ids)
    for got, every in zip(_rows(out), _rows(forward(model, params, ids))):
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(every[:, -1]), atol=1e-5)


@pytest.mark.parametrize("name", TAKES_PADDING)
def test_a_left_padded_prompts_row_is_the_train_modes_last_row(name):
    model, params, ids = _lm(name)
    every = unwrap_logits(forward(model, params, ids[:1]))
    padded = jnp.concatenate([jnp.zeros((1, 8), ids.dtype), ids[:1]], axis=1)
    out, _ = through_the_cache(model.clone(mode="prefill"), params, padded,
                               padding_mask=jnp.arange(32)[None] >= 8)
    got = unwrap_logits(out)
    assert got.shape == (1, 1, VOCAB)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(every[:, -1]), atol=1e-5)


def _avals(jaxpr):
    """Every value of a jaxpr, its sub-programs' (scans, conditionals, pjit) too."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("name", list(LMS))
def test_no_array_of_padded_by_vocab_in_the_prefill_program(name):
    model, params, _ = _lm(name)
    ids = jax.ShapeDtypeStruct((1, PADDED), jnp.int32)

    def logits_shaped(mode):
        program = jax.make_jaxpr(lambda p, i: model.clone(mode=mode).apply(p, i, mutable=["cache"]))(params, ids)
        return [a.shape for a in _avals(program.jaxpr)
                if getattr(a, "shape", ())[-1:] == (VOCAB,) and np.prod(a.shape) >= PADDED * VOCAB]

    assert logits_shaped("prefill") == []
    assert logits_shaped("train")        # the search finds them where they are
