"""Routers (reference: ``modules/moe/routing.py`` — ``RouterBase:12``,
``RouterTopK:127``, ``RouterSinkhorn:169``).

The reference computes router logits in fp64 for deterministic argmax/top-k
under XLA; on TPU fp64 is emulated and slow, so logits are computed in fp32
(exact for router-sized matmuls) — the same motivation, the TPU-appropriate
precision. Selection uses ``jax.lax.top_k`` which is deterministic.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any


def stratified_normal(std: float, group: int = 0):
    """An initializer for an ``(E,)`` vector whose values are the SAME numbers
    under every key: the ``E`` quantiles of ``N(0, std^2)`` at ``(i + 0.5) / E``.
    Every run of ``group`` consecutive entries (the experts one device of an
    expert-parallel layer holds) takes one quantile from each of ``group``
    strata of ``n = E / group`` neighbours: run ``r`` the ``(r + n // 2) % n``-th
    of each, so run 0 gets the strata's middles. The key only orders the values
    INSIDE a run. So the multiset a run holds, and with random router weights
    the rows routed to it, are the same for every key: an independent draw
    gives one device anything from half to one and a half times its share of
    the rows, and a benchmark that holds one share would then do another
    amount of work under every seed. ``group`` 0: one run of all ``E``."""

    def init(key, shape, dtype=jnp.float32):
        (num,) = shape
        g = group or num
        if num % g:
            raise ValueError(f"{num} values do not divide into runs of {g}")
        n = num // g
        strata = std * jax.scipy.special.ndtri(
            (jnp.arange(num, dtype=jnp.float32) + 0.5) / num).reshape(g, n)
        runs = strata[:, (jnp.arange(n) + n // 2) % n].T          # (n runs, g values)
        runs = jax.random.permutation(key, runs, axis=1, independent=True)
        return runs.reshape(num).astype(dtype)

    return init


def zero_sum_runs_lecun_normal(group: int):
    """``lecun_normal`` for a router's ``(hidden, E)`` weight in which every run
    of ``group`` consecutive experts (the experts one device of an
    expert-parallel layer holds) has columns that sum to zero, rescaled to
    lecun's size: the logits of a run then sum to zero for EVERY input, so
    what the inputs have in common (a mean vector over tokens, which random
    layers produce and a trained router has learned to ignore) prefers no run
    to another in first order. Independent columns give one device 0.9 to 1.2
    times its share of the rows of a top-8-of-320 softmax router, another under
    every key (the same reason as :func:`stratified_normal`'s: a benchmark that
    holds one share would do another amount of work under every seed); with
    zero-sum runs 0.95 to 1.06. A trained model gets there by its balance loss
    and a deployment by where it places its experts."""

    def init(key, shape, dtype=jnp.float32):
        hidden, num = shape
        if num % group:
            raise ValueError(f"{num} experts do not divide into runs of {group}")
        runs = nn.initializers.lecun_normal()(key, shape, jnp.float32).reshape(hidden, num // group, group)
        runs = (runs - runs.mean(axis=-1, keepdims=True)) * (group / (group - 1)) ** 0.5
        return runs.reshape(shape).astype(dtype)

    return init


class RouterOutput(NamedTuple):
    logits: jax.Array  # (T, E) fp32 pre-activation
    probs: jax.Array  # (T, E) fp32 activation output (aux-loss input)
    top_e: jax.Array  # (T, k) int32 chosen expert ids
    top_w: jax.Array  # (T, k) fp32 affinity weights


class RouterBase(nn.Module):
    """Linear router: hidden → per-expert logits.

    ``act_fn`` ∈ {"softmax", "sigmoid"} (reference RouterBase applies the
    activation in high precision, routing.py:12). ``jitter_eps`` multiplies the
    input by U[1-eps, 1+eps] noise during training (reference input jitter).
    Router weights are replicated — they are tiny and every rank needs full
    logits.
    """

    hidden_size: int
    num_experts: int
    top_k: int = 2
    act_fn: str = "softmax"
    jitter_eps: float = 0.0
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # 0: ``lecun_normal``; else :func:`zero_sum_runs_lecun_normal` over runs of
    # this many consecutive experts (one device's share)
    zero_sum_group: int = 0

    def _logits(self, x, deterministic: bool) -> jax.Array:
        init = (zero_sum_runs_lecun_normal(self.zero_sum_group) if self.zero_sum_group
                else nn.initializers.lecun_normal())
        weight = self.param(
            "weight",
            nn.with_partitioning(init, (None, None)),
            (self.hidden_size, self.num_experts),
            self.param_dtype,
        )
        if self.jitter_eps > 0.0 and not deterministic:
            noise = jax.random.uniform(
                self.make_rng("jitter"),
                x.shape,
                x.dtype,
                1.0 - self.jitter_eps,
                1.0 + self.jitter_eps,
            )
            x = x * noise
        # fp32 logits regardless of activation dtype
        return jnp.asarray(x, jnp.float32) @ jnp.asarray(weight, jnp.float32)

    def _activate(self, logits: jax.Array) -> jax.Array:
        if self.act_fn == "sigmoid":
            return jax.nn.sigmoid(logits)
        return jax.nn.softmax(logits, axis=-1)


class RouterTopK(RouterBase):
    """Top-k router (reference routing.py:127).

    Returns ``(probs, top_e, top_w)``:
      * ``probs (T, E)`` — full activation output (for the aux loss),
      * ``top_e (T, k)`` int32 — chosen expert ids,
      * ``top_w (T, k)`` fp32 — affinity weights, renormalized over the k
        chosen experts when ``normalize_top_k_affinities`` (reference option;
        Mixtral semantics), under either activation.

    ``selection_bias`` (DeepSeek-V3's ``noaux_tc``): a parameter
    ``e_score_correction_bias`` (E,) is added to the activations to CHOOSE
    the k experts and is no part of their weights, which are the plain
    activations of the chosen. ``selection_bias_init_std``: the normal it is
    drawn from at init (published checkpoints start it at zero and move it
    outside the gradient; a benchmark with random weights draws it wide
    enough to change selections), as :func:`stratified_normal` draws it: the
    normal's quantiles, every ``selection_bias_init_group`` consecutive experts
    (one device's share; 0: all) the same multiset under every key."""

    normalize_top_k_affinities: bool = True
    selection_bias: bool = False
    selection_bias_init_std: float = 0.0
    selection_bias_init_group: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> RouterOutput:
        return self._select(self._logits(x, deterministic))

    def _select(self, logits: jax.Array) -> RouterOutput:
        probs = self._activate(logits)
        if self.selection_bias:
            bias = self.param(
                "e_score_correction_bias",
                nn.with_partitioning(
                    stratified_normal(self.selection_bias_init_std,
                                      self.selection_bias_init_group), (None,)),
                (self.num_experts,), jnp.float32,
            )
            _, top_e = jax.lax.top_k(probs + jnp.asarray(bias, jnp.float32), self.top_k)
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
        else:
            top_w, top_e = jax.lax.top_k(probs, self.top_k)
        if self.normalize_top_k_affinities:
            top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        return RouterOutput(logits, probs, top_e.astype(jnp.int32), top_w)


class RouterSinkhorn(RouterBase):
    """Sinkhorn-balanced router (reference routing.py:169, ``_sinkhorn:235``).

    A FIXED number of Sinkhorn normalization iterations (static-shape friendly,
    same reason the reference fixes the iteration count for its lazy graphs)
    balances the token→expert assignment matrix; selection uses the balanced
    matrix, affinity weights use the plain activation of the original logits
    (Megatron sinkhorn-router semantics). At eval time routing falls back to
    plain top-k of the logits — Sinkhorn balance only matters for training
    load distribution.
    """

    sinkhorn_iterations: int = 4

    def _sinkhorn(self, logits: jax.Array) -> jax.Array:
        # Sinkhorn is invariant to a global scale of the cost matrix, so the
        # max-subtraction is exact and keeps exp() finite in fp32 (the
        # reference sidesteps overflow with fp64, slow on TPU).
        cost = jnp.exp(logits - jax.lax.stop_gradient(logits.max()))
        d0 = jnp.ones(cost.shape[0], jnp.float32)
        d1 = jnp.ones(cost.shape[1], jnp.float32)
        eps = 1e-8
        for _ in range(self.sinkhorn_iterations):
            d0 = 1.0 / (cost.shape[0] * ((cost * d1[None, :]).sum(1) + eps))
            d1 = 1.0 / (cost.shape[1] * ((cost * d0[:, None]).sum(0) + eps))
        return cost * d0[:, None] * d1[None, :]

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> RouterOutput:
        logits = self._logits(x, deterministic)
        probs = self._activate(logits)
        if deterministic:
            top_w, top_e = jax.lax.top_k(probs, self.top_k)
        else:
            balanced = self._sinkhorn(logits)
            _, top_e = jax.lax.top_k(balanced, self.top_k)
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
        return RouterOutput(logits, probs, top_e.astype(jnp.int32), top_w)


def zero_sum_lecun_normal(key, shape, dtype=jnp.float32):
    """``lecun_normal`` with every output's weights summing to zero over its
    inputs: what an input's common (token-independent) mean adds to an output
    is then nothing. A router logit fed by gelus otherwise starts with an
    offset of its own as large as half its spread over tokens, and a top-1
    choice among 16 experts hits 10 of them with 32 rows where an even one
    hits 14; which 10 changes with the key."""
    w = nn.initializers.lecun_normal()(key, shape, jnp.float32)
    return (w - w.mean(axis=0, keepdims=True)).astype(dtype)


class RouterMLP(RouterTopK):
    """A router that is an MLP over a narrow STATE of its own, which passes
    from layer to layer beside the residual stream (Zyphra's ZAYA1,
    ``models/zaya.py``): ``s = W_d x + b_d`` (hidden -> ``state_size``); given
    the previous layer's state ``r``: ``s <- s + gamma * r`` (``gamma`` a
    learned vector, absent where no state comes in: the first layer); the new
    state is ``s``. Logits ``W3 gelu(W2 gelu(W1 RMSNorm(s) + c1) + c2)``
    (``state_size`` -> ``state_size`` -> ``state_size`` -> E, the gelu exact;
    ``W2`` and ``W3`` start with zero column sums, :func:`zero_sum_lecun_normal`).
    Activation, selection bias and top-k as :class:`RouterTopK`. All of it in
    float32 at matmul precision ``highest`` (the matrices are small; the
    choice is one of 16 through four matmuls). Called with ``(x, state)``;
    returns ``(RouterOutput, new state)``."""

    state_size: int = 256
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array, state: Optional[jax.Array] = None,
                 deterministic: bool = True):
        r = self.state_size

        def dense(name, y, n_in, n_out, bias=True, init=nn.initializers.lecun_normal()):
            w = self.param(f"{name}_weight", nn.with_partitioning(
                init, (None, None)), (n_in, n_out), self.param_dtype)
            # float32 in fact: on a TPU a float32 matmul at the default precision
            # rounds its operands to bf16, and four chained ones move a top-1
            # choice that a linear router's single matmul leaves alone
            y = jnp.matmul(y, jnp.asarray(w, jnp.float32), precision=jax.lax.Precision.HIGHEST)
            if bias:
                y = y + jnp.asarray(self.param(f"{name}_bias", nn.with_partitioning(
                    nn.initializers.zeros_init(), (None,)), (n_out,), self.param_dtype), jnp.float32)
            return y

        s = dense("down", jnp.asarray(x, jnp.float32), self.hidden_size, r)
        if state is not None:
            gamma = self.param("state_mix", nn.with_partitioning(
                nn.initializers.ones_init(), (None,)), (r,), self.param_dtype)
            s = s + jnp.asarray(gamma, jnp.float32) * jnp.asarray(state, jnp.float32)
        norm = self.param("norm_weight", nn.with_partitioning(
            nn.initializers.ones_init(), (None,)), (r,), self.param_dtype)
        y = s * jax.lax.rsqrt(jnp.mean(jnp.square(s), axis=-1, keepdims=True) + self.eps)
        y = y * jnp.asarray(norm, jnp.float32)
        y = jax.nn.gelu(dense("fc1", y, r, r), approximate=False)
        # a gelu's output has a mean of its own; weights that sum to zero over
        # it give no unit, and no expert, a constant head start at the start
        y = jax.nn.gelu(dense("fc2", y, r, r, init=zero_sum_lecun_normal), approximate=False)
        logits = dense("fc3", y, r, self.num_experts, bias=False, init=zero_sum_lecun_normal)
        return self._select(logits), s


def make_router(
    kind: str,
    hidden_size: int,
    num_experts: int,
    top_k: int,
    name: Optional[str] = None,
    **kw,
):
    cls = {"top_k": RouterTopK, "sinkhorn": RouterSinkhorn, "mlp": RouterMLP}[kind]
    return cls(
        hidden_size=hidden_size, num_experts=num_experts, top_k=top_k, name=name, **kw
    )
