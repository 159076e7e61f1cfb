"""The gated delta-rule kernels (``kernels/delta_rule.py``), interpreted, tiny,
against the token-by-token recurrence: strong decay (5 nats a step in some
channels, where ``e^{-G}`` alone overflows float32 inside a chunk), ``beta``
near 0 and near 2, repeated keys at ``beta`` 2, left padding with whole
chunks skipped, a prompt that ends mid-chunk, and the state handed from the
prefill kernel to the decode kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.delta_rule import delta_rule_scan, kda_chunk_prefill, kda_decode_step

H, D = 2, 16


def inputs(seed, b, s, decay="strong", repeated=False):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(b, s, H, D))) * D ** -0.5
    k = unit(rng.normal(size=(b, s, H, D)))
    if repeated:                       # one key at every token: A = beta * ones below the diagonal
        k = np.broadcast_to(k[:, :1], k.shape)
    v = rng.normal(size=(b, s, H, D))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(b, s, H, D)))
    if decay == "strong":              # a quarter of the channels forget 5 nats a step: 160 nats over 32 tokens
        g = np.where(rng.random((1, 1, H, D)) < 0.25, -5.0, g)
    beta = rng.choice([0.01, 1.0, 1.99], size=(b, s, H)) if not repeated else np.full((b, s, H), 2.0)
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def masked_scan(q, k, v, g, beta, valid):
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    return delta_rule_scan(q, k, v, g, beta)


@pytest.mark.parametrize("case", ["strong_decay", "mild_decay", "repeated_keys_at_beta_2"])
def test_the_chunked_forward_is_the_recurrence(case):
    """Three chunks of 32 tokens (two sub-blocks each), the last one ending
    mid-chunk at token 80 of 96."""
    args = inputs(0, 2, 80, decay="mild" if case == "mild_decay" else "strong",
                  repeated=case == "repeated_keys_at_beta_2")
    o, state = kda_chunk_prefill(*args, chunk=32)
    want_o, want_state = delta_rule_scan(*args)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=1e-4)


def test_left_padding_skips_whole_chunks_and_changes_nothing():
    """Rows of 96 columns holding 20 and 70 tokens at their right end: the
    first row's first two chunks are never visited, its third starts with 12
    padding tokens; outputs at the tokens and the final state are those of the
    unpadded prompts."""
    q, k, v, g, beta = inputs(1, 2, 96)
    lengths = np.array([20, 70])
    valid = jnp.asarray(np.arange(96)[None] >= (96 - lengths)[:, None])
    # padding columns carry junk the mask must keep out
    junk = jnp.where(valid[..., None, None], 1.0, 1e3)
    o, state = kda_chunk_prefill(q, k * junk, v * junk, g * junk, beta, valid, chunk=32)
    for row, n in enumerate(lengths):
        alone = tuple(a[row:row + 1, 96 - n:] for a in (q, k, v, g, beta))
        want_o, want_state = delta_rule_scan(*alone)
        np.testing.assert_allclose(o[row, 96 - n:], want_o[0], atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(state[row], want_state[0], atol=2e-5, rtol=1e-4)
    assert not np.asarray(o[0, :64]).any()          # the skipped chunks' rows are zeros, not junk


def test_decode_steps_continue_the_prefills_state():
    """Prefill 40 tokens, then 6 single-token steps through the decode kernel,
    the second slot frozen at every other step (``beta`` 0 and ``g`` 0): every
    output and the final state are the recurrence's over the tokens each slot
    took."""
    q, k, v, g, beta = inputs(2, 2, 46)
    _, state = kda_chunk_prefill(*(a[:, :40] for a in (q, k, v, g, beta)), chunk=32)
    took = np.ones((2, 46), bool)
    took[1, 41::2] = False
    want_o, want_state = masked_scan(q, k, v, g, beta, jnp.asarray(took))
    for t in range(40, 46):
        live = jnp.asarray(took[:, t])
        o, state = kda_decode_step(state, q[:, t], k[:, t], v[:, t],
                                   jnp.where(live[:, None, None], g[:, t], 0.0),
                                   jnp.where(live[:, None], beta[:, t], 0.0))
        for slot in range(2):
            if took[slot, t]:
                np.testing.assert_allclose(o[slot], want_o[slot, t], atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=1e-4)


def test_the_decode_kernel_names_its_state_operand_as_its_output():
    """In place: the lowered call aliases the state operand to the state
    result, so a step moves the state's bytes once in and once out."""
    state = jnp.zeros((2, H, D, D), jnp.float32)
    vec = jnp.zeros((2, H, D), jnp.float32)
    jaxpr = jax.make_jaxpr(kda_decode_step)(state, vec, vec, vec, vec, jnp.zeros((2, H)))
    call = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert tuple(call.params["input_output_aliases"]) == ((0, 1),)
    assert call.invars[0].aval.shape == call.outvars[1].aval.shape == state.shape
