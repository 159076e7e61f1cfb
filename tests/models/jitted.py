"""A tiny model's forward, COMPILED: the model files' cases apply the same
model to the same shapes many times over, and an eager ``apply`` dispatches
its hundreds of operations one by one each time. A flax module hashes by
value, so the model (its ``mode`` and ``attention_impl`` are fields of it) is
the static argument and a program is compiled once a model and a shape."""

import functools

import jax
from flax.core import meta


@functools.partial(jax.jit, static_argnums=0)
def forward(model, variables, *args, **kw):
    """``model.apply(variables, *args, **kw)``."""
    return model.apply(variables, *args, **kw)


@functools.partial(jax.jit, static_argnums=0)
def through_the_cache(model, variables, *args, **kw):
    """``(outputs, cache)`` of a prefill or a decode step:
    ``model.apply(..., mutable=["cache"])``."""
    out, state = model.apply(variables, *args, mutable=["cache"], **kw)
    return out, state["cache"]


def every_position(backbone, variables, *args, **kw):
    """``(logits at EVERY position, cache)`` of a prefill. A ``*ForCausalLM``
    in ``prefill`` mode applies its head to the last position alone
    (``models/__init__.py``), so every position is read from the headless
    ``backbone`` (the family's ``*Model`` in ``prefill`` mode) through the
    head's kernel; ``variables`` are the causal LM's."""
    variables = meta.unbox(variables)
    (hidden, _), cache = through_the_cache(backbone, {"params": variables["params"]["model"]}, *args, **kw)
    return hidden @ variables["params"]["lm_head"]["kernel"], {"model": cache}
