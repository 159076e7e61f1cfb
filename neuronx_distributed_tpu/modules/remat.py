"""What a rematerialised layer keeps through the backward pass: one table
for every model that wraps its layers in ``nn.remat``.

``remat=True`` re-runs a layer's forward in the backward pass from its input.
A policy says what is kept instead of computed twice; memory against time:

* ``None``: save nothing (the least memory; for whoever sits at the limit);
* ``"dots"`` / ``"dots_saveable"``: keep matmul outputs, recompute the cheap
  elementwise ops (``jax.checkpoint_policies``);
* ``"mlp_up"``: keep the tensor :class:`ParallelMLP` names ``MLP_UP``, its
  up-projection's pre-activation (and the gate's under ``glu``): ``B S I / tp``
  values a layer a chip. The MLP's first matmul, a third of a GPT-J block's
  matmul parameters, is not run a second time; the activation, the attention
  projections and the attention itself still are;
* ``"mlp_up+attn"``: with it, q, k and v as :class:`ParallelSelfAttention`
  hands them to the attention (``ATTN_QKV``, after rotary: ``3 B S H / tp``
  values) and the outputs of every Pallas kernel in the layer (the flash
  forward's ``o`` and ``lse``: ``B S H / tp`` values and a float32 a row a
  head). Then no matmul and no kernel of the layer runs twice: the backward
  pass recomputes the norm and the activation alone.

On the training cell (CodeGen2-7B's widths, ten layers, 8 x 2048 tokens,
tp=4 + SP on four v5e chips; PERF.md section 6, PR 40) a step takes 605 / 577
/ 519 ms under ``None`` / ``"mlp_up"`` / ``"mlp_up+attn"`` and the compiled
program keeps 9.25 / 10.00 / 11.59 GiB live a chip.

A name is the identity outside a policy that asks for it: a program without
remat, or under another policy, lowers to what it lowered to without the name.
What is saved is rounded to the layer's dtype where the compiler, computing it
again inside one fusion, may keep a float32: in bf16 the losses of two policies
agree to rounding, in float32 to the bit.
"""

from __future__ import annotations

from typing import Optional

import jax
from flax import linen as nn

MLP_UP = "mlp_up"
ATTN_QKV = "attn_qkv"


def _kernel_outputs(prim, *_, **__) -> bool:
    """A checkpoint policy: keep what a Pallas kernel hands back (a custom
    VJP's forward runs under the policy like any other equation, so the flash
    forward's residuals are kept without a name inside the kernel's code)."""
    return prim.name == "pallas_call"


_names = jax.checkpoint_policies.save_only_these_names

_POLICIES = {
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    MLP_UP: _names(MLP_UP),
    "mlp_up+attn": jax.checkpoint_policies.save_from_both_policies(
        _names(MLP_UP, ATTN_QKV), _kernel_outputs
    ),
}


def remat_layer_cls(layer_cls, remat: bool, policy: Optional[str] = None):
    """``layer_cls``, wrapped in ``nn.remat`` under ``policy`` when ``remat``."""
    if not remat:
        return layer_cls
    if policy is None:
        return nn.remat(layer_cls)
    return nn.remat(layer_cls, policy=_POLICIES[policy])


def saved_by_name(fn, *args) -> dict:
    """``{name: bytes}`` of the named tensors that the ``nn.remat`` policies
    inside ``fn`` keep for the backward pass: every ``checkpoint_name`` under
    a rematerialised layer whose policy answers yes to it, summed over the
    layers (a scanned layer counts ``length`` times). Global bytes, from the
    avals of ``fn``'s traced forward; ``args`` may be ``jax.ShapeDtypeStruct``s,
    nothing is computed. What a policy keeps by primitive (``"dots"``, the
    kernels' outputs) has no name and is not counted; nor is a name outside
    any remat, where autodiff keeps what it needs without being asked."""
    out: dict = {}

    def walk(jaxpr, times, policy):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name" and policy is not None:
                avals = [v.aval for v in eqn.invars]
                if policy(eqn.primitive, *avals, **eqn.params) is True:
                    nbytes = sum(a.size * a.dtype.itemsize for a in avals)
                    name = eqn.params["name"]
                    out[name] = out.get(name, 0) + times * nbytes
            # jax.checkpoint's equation is the one that carries a policy
            inner_policy = eqn.params.get("policy", policy)
            inner_times = times * eqn.params.get("length", 1)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inner_times, inner_policy)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, 1, None)
    return out
