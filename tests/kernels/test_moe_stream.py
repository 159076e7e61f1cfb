"""The streamed expert MLP (``kernels/moe_stream.py``), interpreted on the
CPU: against ``ExpertMLPs._all_experts`` (the golden) and against the
grouped-matmul form it stands in for; its hit list against numpy; and
``jax.grad`` through the layer that calls it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.kernels.moe_stream import (
    combine_matrix,
    hit_experts,
    moe_stream_mlp,
    pick_block_i,
)
from neuronx_distributed_tpu.modules.moe import ExpertMLPs
from neuronx_distributed_tpu.modules.moe import expert_mlps


def _route(kind, rows, n_e, k, renormalise, key):
    """``(top_e, top_w)`` of a router's shapes, or a corner of them."""
    probs = jax.nn.softmax(jax.random.normal(key, (rows, n_e)))
    top_w, top_e = jax.lax.top_k(probs, k)
    if kind == "one_expert":      # every token's first choice is expert 2
        top_e = top_e.at[:, 0].set(2)
        top_e = top_e.at[:, 1:].set(jnp.where(top_e[:, 1:] == 2, 0, top_e[:, 1:]))
    elif kind == "nobody_chose":  # experts 0 and n_e - 1 get no row
        top_e = jnp.clip(top_e, 1, n_e - 2)
    if renormalise:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    return top_e.astype(jnp.int32), top_w


def _weights(n_e, hid, inter, glu, key):
    keys = jax.random.split(key, 3)
    gate = jax.random.normal(keys[0], (n_e, hid, inter)) * hid ** -0.5 if glu else None
    up = jax.random.normal(keys[1], (n_e, hid, inter)) * hid ** -0.5
    down = jax.random.normal(keys[2], (n_e, inter, hid)) * inter ** -0.5
    return gate, up, down


# (rows, experts, top_k, hidden, intermediate, glu, routing, renormalised)
CASES = [
    (8, 16, 4, 128, 384, True, "router", False),     # 384 = 3 x 128: no 256 divisor, as 1408 = 11 x 128
    (1, 8, 2, 128, 256, True, "router", True),
    (8, 8, 2, 128, 256, False, "router", True),
    (16, 8, 8, 128, 256, True, "router", False),     # k = E: every expert hit by every row
    (16, 16, 2, 256, 640, True, "one_expert", True),
    (64, 16, 4, 128, 256, True, "nobody_chose", False),
    (64, 8, 2, 128, 384, False, "router", False),
    (5, 8, 2, 128, 256, True, "router", True),       # rows that fill no sublane tile
]


@pytest.mark.parametrize("rows,n_e,k,hid,inter,glu,kind,renormalise", CASES)
@pytest.mark.parametrize("block_i", [None, 128], ids=["whole", "tiles_of_128"])
def test_stream_matches_the_golden_and_the_grouped_matmul(
        rows, n_e, k, hid, inter, glu, kind, renormalise, block_i):
    keys = jax.random.split(jax.random.PRNGKey(rows * 31 + n_e), 3)
    x = jax.random.normal(keys[0], (rows, hid))
    top_e, top_w = _route(kind, rows, n_e, k, renormalise, keys[1])
    gate, up, down = _weights(n_e, hid, inter, glu, keys[2])
    got = moe_stream_mlp(x, top_e, top_w, gate, up, down, block_i=block_i)
    layer = ExpertMLPs(num_experts=n_e, hidden_size=hid, intermediate_size=inter,
                       top_k=k, glu_mlp=glu)
    golden = layer._all_experts(x, top_e, top_w, gate, up, down)
    grouped = expert_mlps._ragged_routed_mlp(x, top_e, top_w, gate, up, down, "silu")
    assert got.shape == (rows, hid) and got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(golden), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(grouped), rtol=2e-5, atol=2e-5)


def test_bf16_operands_accumulate_in_float32():
    """bf16 in, bf16 out, and the sum across experts in float32: nearer the
    float32 golden than the grouped-matmul form's bf16 scatter-add."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (8, 256), jnp.bfloat16)
    top_e, top_w = _route("router", 8, 16, 6, False, keys[1])
    gate, up, down = (w.astype(jnp.bfloat16) for w in _weights(16, 256, 384, True, keys[2]))
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    golden = ExpertMLPs(num_experts=16, hidden_size=256, intermediate_size=384, top_k=6)._all_experts(
        f32(x), top_e, top_w, f32(gate), f32(up), f32(down))
    got = moe_stream_mlp(x, top_e, top_w, gate, up, down)
    grouped = expert_mlps._ragged_routed_mlp(x, top_e, top_w, gate, up, down, "silu")
    assert got.dtype == jnp.bfloat16
    err = lambda a: float(jnp.linalg.norm(f32(a) - golden) / jnp.linalg.norm(golden))  # noqa: E731
    assert err(got) < 1e-2
    assert err(got) <= err(grouped) * 1.05


@pytest.mark.parametrize("kind", ["router", "one_expert", "nobody_chose"])
@pytest.mark.parametrize("rows,n_e,k", [(1, 8, 2), (8, 64, 6), (16, 8, 8), (64, 16, 4)])
def test_hit_list_and_combine_matrix_against_numpy(rows, n_e, k, kind):
    top_e, top_w = _route(kind, rows, n_e, k, False, jax.random.PRNGKey(rows + n_e))
    size = min(n_e, rows * k)
    ids, count = hit_experts(top_e, n_e, size)
    want = np.unique(np.asarray(top_e))
    assert int(count) == len(want) and ids.shape == (size,) and ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ids)[:len(want)], want)   # ascending
    np.testing.assert_array_equal(np.asarray(ids)[len(want):], want[-1])   # the last block stands
    comb = np.zeros((rows, n_e), np.float32)
    for t in range(rows):
        for e, w in zip(np.asarray(top_e[t]), np.asarray(top_w[t])):
            comb[t, e] += w
    np.testing.assert_allclose(np.asarray(combine_matrix(top_e, top_w, n_e)), comb, rtol=1e-6)


@pytest.mark.parametrize("hid,inter,glu,want", [
    (2048, 1408, True, 1408),     # DeepSeek-V2-Lite: whole matrices, 17.3 MB a step
    (2048, 768, True, 768),       # Keye
    (4096, 14336, True, 1024),    # Mixtral: 117 MB a matrix, tiles of 8.4 MB
    (4096, 14336, False, 1024),    # 1792 = 14 x 128 would be 58.7 MB
    (6144, 2048, True, 512),       # GLM-5's experts, were they streamed
])
def test_tiles_come_from_the_shapes(hid, inter, glu, want):
    assert pick_block_i(hid, inter, 2, glu) == want
    with pytest.raises(ValueError, match="no tile"):
        pick_block_i(hid, inter, 2, glu, budget=1 << 16)


def test_a_tile_that_does_not_divide_is_refused():
    x = jnp.zeros((8, 128))
    w = jnp.zeros((4, 128, 384))
    with pytest.raises(ValueError, match="does not tile"):
        moe_stream_mlp(x, jnp.zeros((8, 2), jnp.int32), jnp.ones((8, 2)), w, w,
                       jnp.zeros((4, 384, 128)), block_i=256)


@pytest.mark.parametrize("glu", [True, False])
def test_grad_through_the_layer_is_the_grouped_matmul_forms(glu, monkeypatch):
    """The layer's streamed form is differentiated as the grouped-matmul
    form: same gradients for the rows, the affinities and the three weights."""
    layer = ExpertMLPs(num_experts=8, hidden_size=128, intermediate_size=256, top_k=2,
                       glu_mlp=glu, strategy="blockwise")
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (8, 128))
    top_e, top_w = _route("router", 8, 8, 2, True, keys[1])
    params = layer.init(keys[2], x, top_e, top_w)

    def loss(p, x_, w_):
        return jnp.sum(layer.apply(p, x_, top_e, w_) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))
    want = grads(params, x, top_w)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda p: loss(p, x, top_w))(params))
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert "pallas_call" in str(jax.make_jaxpr(lambda p: loss(p, x, top_w))(params))
    got = grads(params, x, top_w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
