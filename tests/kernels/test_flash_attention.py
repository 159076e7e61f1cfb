"""Flash attention kernel vs XLA einsum golden, interpreted on the CPU through
conftest's session switch (kernels/backend.py). That the same kernels COMPILE
for the chip is tests/kernels/test_tpu_compile.py's question; that they run
there is chip_smoke.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _group_fwd_parent
from _flash_fwd_parent import _flash_fwd as _parent_flash_fwd

from neuronx_distributed_tpu.kernels.flash_attention import (
    _flash_fwd,
    _tile_classes,
    _tile_pairs,
    _tile_plan,
    banded_flash_attention,
    flash_attention,
    flash_tile_plan,
    group_tile_plan,
    masked_flash_attention,
)
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
    # padding-masked golden, and a padded row of a forward-only call is zeros
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), 2, 128, 2, 32)
    valid = np.ones((2, 128), bool)
    valid[0, 96:] = False
    valid[1, 64:] = False
    seg = jnp.asarray(np.where(valid, 0, -1).astype(np.int32))
    out = flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=64, block_k=64
    )
    ref = _xla_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid], atol=2e-5)
    assert not np.asarray(out)[~valid].any()


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


@pytest.mark.parametrize("side", ["right", "left", "left_whole_blocks"])
def test_segments_backward_padding(side):
    """Grads flow only within valid segments; the padding (a tail; a prompt's
    left padding, its edge inside a block and on one) contributes the same as
    the masked golden (incl. the lse≈-inf guard in the backward): under
    differentiation a padded row is KEPT as a row, so every grad is finite."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(15), 1, 128, 2, 32)
    valid = np.ones((1, 128), bool)
    valid[0, {"right": slice(80, None), "left": slice(0, 48), "left_whole_blocks": slice(0, 64)}[side]] = False
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
        assert np.isfinite(np.asarray(a)).all(), f"d{name}"


# --- classed tiles: empty / interior / edge (the forward since PR 45) -----------
#
# The reference is the forward as it stood before (``_flash_fwd_parent.py``):
# a content row meets the same key blocks in the same order with the same
# arithmetic. On the chip that is EQUAL, bit for bit (``chip_smoke.py --only
# flash`` holds the interior body to the edge body there); the CPU's compiler
# contracts a multiply-add in one body and not in the other, so here it is
# equal to float32's last bit (``_LAST_BIT``, on values of magnitude < 8).

_LAST_BIT = 1e-6


def _same(got, want, err_msg=""):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=_LAST_BIT, err_msg=err_msg)


_HEADS = {"mha": (2, 2, 32, 32), "gqa": (4, 2, 32, 32), "dv_narrower": (2, 2, 48, 32)}


def _bhsd(key, b, s, h, hkv, d, dv, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, h, s, d), dtype), jax.random.normal(ks[1], (b, hkv, s, d), dtype),
            jax.random.normal(ks[2], (b, hkv, s, dv), dtype))


@pytest.mark.parametrize("heads", sorted(_HEADS))
@pytest.mark.parametrize("bucket,prompt", [(256, 100), (256, 128), (256, 256), (384, 70)],
                         ids=["edge_inside_a_block", "edge_on_a_boundary", "no_padding", "mostly_padding"])
def test_left_padded_prompt_equals_the_parents_forward(bucket, prompt, heads):
    h, hkv, d, dv = _HEADS[heads]
    q, k, v = _bhsd(jax.random.PRNGKey(20), 2, bucket, h, hkv, d, dv)
    seg = jnp.asarray(np.tile(np.where(np.arange(bucket) < bucket - prompt, -1, 0)[None], (2, 1)).astype(np.int32))
    want, want_lse = _parent_flash_fwd(q, k, v, True, 64, 64, True, q_seg=seg, k_seg=seg)
    got, none = _flash_fwd(q, k, v, True, 64, 64, True, q_seg=seg, k_seg=seg, residuals=False)
    assert none is None
    content = np.asarray(seg[0]) >= 0
    _same(np.asarray(got)[:, :, content], np.asarray(want)[:, :, content])
    assert not np.asarray(got)[:, :, ~content].any()        # nobody reads a padded row: zeros
    # where the backward will read the rows, every row is the parent's, lse too
    kept, lse = _flash_fwd(q, k, v, True, 64, 64, True, q_seg=seg, k_seg=seg)
    _same(kept, want)
    _same(lse, want_lse)


def test_left_padded_bf16_prompt_equals_the_parents_forward():
    """Storage-type operands in the interior body: bf16 x bf16 products are
    exact in the float32 accumulator, so the casts it leaves out change nothing."""
    q, k, v = _bhsd(jax.random.PRNGKey(21), 1, 256, 4, 2, 32, 32, jnp.bfloat16)
    seg = jnp.asarray(np.where(np.arange(256) < 90, -1, 0)[None].astype(np.int32))
    want, _ = _parent_flash_fwd(q, k, v, True, 64, 64, True, q_seg=seg, k_seg=seg)
    got, _ = _flash_fwd(q, k, v, True, 64, 64, True, q_seg=seg, k_seg=seg, residuals=False)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, :, 90:], np.asarray(want, np.float32)[:, :, 90:],
                               rtol=2 ** -7, atol=1e-3)     # bf16's last bit


@pytest.mark.parametrize("case", ["packed_documents", "packed_and_padded", "no_segments", "not_causal",
                                  "uneven_blocks", "cross_length"])
def test_forward_unchanged_where_no_row_is_padding(case):
    """Packed documents (ids >= 0), plain causal, non-causal, blocks of two
    sizes and a cross-length call: output AND lse equal to the parent's."""
    b, s, sk, bq, bk, causal = 2, 256, 256, 64, 64, case != "not_causal"
    if case == "uneven_blocks":
        bq, bk = 64, 32
    if case == "cross_length":
        sk, causal = 128, False
    ks = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(ks[0], (b, 4, s, 32))
    k, v = jax.random.normal(ks[1], (b, 2, sk, 32)), jax.random.normal(ks[2], (b, 2, sk, 32))
    q_seg = k_seg = None
    if case.startswith("packed"):
        q_seg = k_seg = _doc_segments([128, 96, 32], b=b)          # two documents and a third in one batch row
        if case == "packed_and_padded":
            q_seg = k_seg = jnp.where(jnp.arange(s)[None] >= 240, -1, q_seg)
    if case == "cross_length":
        q_seg, k_seg = _doc_segments([128, 128], b=b), _doc_segments([64, 64], b=b)
    want = _parent_flash_fwd(q, k, v, causal, bq, bk, True, q_seg=q_seg, k_seg=k_seg)
    got = _flash_fwd(q, k, v, causal, bq, bk, True, q_seg=q_seg, k_seg=k_seg)
    for a, b_, name in zip(got, want, ("out", "lse")):
        _same(a, b_, name)
    primal, _ = _flash_fwd(q, k, v, causal, bq, bk, True, q_seg=q_seg, k_seg=k_seg, residuals=False)
    rows = np.ones(s, bool) if q_seg is None else np.asarray(q_seg[0]) >= 0
    _same(np.asarray(primal)[:, :, rows], np.asarray(want[0])[:, :, rows])


@pytest.mark.parametrize("q_off,k_off", [(256, 0), (256, 256), (0, 256), (128, 64)],
                         ids=["past_shard", "own_shard", "future_shard", "straddling"])
def test_dynamic_offsets_unchanged(q_off, k_off):
    """Ring attention's form (the causal geometry known at run time only): the
    rectangle is walked, a fully-future shard is all empty pairs (lse ~ -inf)."""
    q, k, v = _bhsd(jax.random.PRNGKey(23), 1, 256, 2, 2, 32, 32)
    seg = _doc_segments([160, 96])
    fwd = lambda f: jax.jit(lambda qo, ko: f(q, k, v, True, 64, 64, True, q_off=qo, k_off=ko,   # noqa: E731
                                             q_seg=seg, k_seg=seg))(jnp.int32(q_off), jnp.int32(k_off))
    for a, b_, name in zip(fwd(_flash_fwd), fwd(_parent_flash_fwd), ("out", "lse")):
        _same(a, b_, name)


def _brute_force_tiles(seq, n_valid, block, parent):
    """Count a left-padded prompt's tiles element by element: the pairs a grid
    walks, those whose body runs, and those that hold a content row and a
    content key at or before it."""
    n, pad = seq // block, seq - n_valid
    seg = np.where(np.arange(seq) < pad, -1, 0).reshape(n, block)
    steps = bodies = needed = 0
    for i in range(n):
        for j in range(n):
            causal = j * block <= i * block + block - 1
            steps += parent or causal
            meet = seg[i].max() >= seg[j].min() and seg[i].min() <= seg[j].max()
            content = seg[i].max() >= 0 and seg[j].max() >= 0
            bodies += causal and meet and (parent or content)
            rows, cols = np.arange(block)[:, None] + i * block, np.arange(block)[None] + j * block
            needed += bool(((rows >= cols) & (rows >= pad) & (cols >= pad)).any())
    return steps, bodies, needed


# (bucket, prompt) of the serving cells' eight-prompt blocks: the tape's
# quantiles (perfbench/tape.py) in the buckets ``serving/engine._bucket`` gives
_CELL_BLOCKS = {
    "dsv2lite_docs_closed": [(4096, 3263), (8192, 4811), (8192, 6110), (8192, 7454), (16384, 9003),
                             (16384, 10984), (16384, 13950), (20992, 20566)],
    "trinity_mixedctx_closed": [(4096, 2799), (8192, 4402), (8192, 5818), (8192, 7338), (16384, 9146),
                                (16384, 11534), (16384, 15244), (16384, 16384)],
    "codegen2_lines_steady": [(1024, 1000), (2048, 1100), (512, 300), (2048, 2048)],
    "small_blocks": [(256, 100), (256, 128), (384, 70), (64, 1)],
}


@pytest.mark.parametrize("cell", sorted(_CELL_BLOCKS))
def test_flash_tile_plan_against_a_brute_force_count(cell):
    block = 64 if cell == "small_blocks" else 512
    plans = [flash_tile_plan(seq, n, block, block) for seq, n in _CELL_BLOCKS[cell]]
    brute = [_brute_force_tiles(seq, n, block, parent=False) for seq, n in _CELL_BLOCKS[cell]]
    was = [_brute_force_tiles(seq, n, block, parent=True) for seq, n in _CELL_BLOCKS[cell]]
    for (steps, bodies, edge, needed), (b_steps, b_bodies, b_needed) in zip(plans, brute):
        assert (steps, bodies, needed) == (b_steps, b_bodies, b_needed)
        assert bodies == needed and 0 < edge <= bodies
    assert all(new[0] <= old[0] and new[1] <= old[1] for new, old in zip(plans, was))
    if cell == "dsv2lite_docs_closed":       # ISSUE 45's count of the cell's block, a head a layer
        assert (sum(w[0] for w in was), sum(w[1] for w in was)) == (5585, 2215)
        assert (sum(p[0] for p in plans), sum(p[1] for p in plans)) == (2889, 1972)


def test_flash_tile_plan_defaults_to_the_blocks_the_kernel_picks():
    assert flash_tile_plan(16384, 9003) == flash_tile_plan(16384, 9003, 512, 512)
    steps, bodies, edge, needed = flash_tile_plan(16384, 9003)
    assert (steps, bodies, needed) == (528, 171, 171) and edge == 18 + 17   # the diagonal + the padding's edge column


# --- the grouped forwards: a learned byte mask, a window (the form since PR 50) --
#
# The reference is the two kernels as they stood before (``_group_fwd_parent.py``:
# a rectangular grid, column statistics, every tile of the bucket multiplied and
# masked twice). A content row meets the same key tiles in the same order with the
# same arithmetic, so it is the parent's BIT FOR BIT, here as on the chip; a query
# block wholly past the prompt's end is not visited and reads zeros.

# the serving cells' HEAD geometry (q heads, kv heads, d_qk, d_v, window), a KV head or two
_GROUPED = {
    "glm5": (2, 2, 256, 256, None),          # group 1, 256 / 256
    "keye": (8, 1, 128, 128, None),          # group 8
    "trinity": (6, 1, 128, 128, 384),        # group 6, a window of a tile and a half
}
# (bucket, tile, the batch rows' (first content token, one past the last))
_PROMPTS = {
    "ends_inside_a_tile": (1024, 256, [(324, 1024)]),
    "ends_on_a_tiles_edge": (1024, 256, [(256, 1024)]),
    "fills_the_bucket": (1024, 256, [(0, 1024)]),
    "two_rows_of_different_lengths": (1024, 128, [(724, 1024), (1, 1024)]),
    "a_row_that_keeps_nothing": (512, 128, [(200, 512), (512, 512)]),
    "padded_on_the_right": (1024, 256, [(0, 700)]),
}


def _grouped_case(geometry, prompt, dtype=jnp.bfloat16):
    h, hkv, d, dv, window = _GROUPED[geometry]
    s, tile, extents = _PROMPTS[prompt]
    b = len(extents)
    ks = jax.random.split(jax.random.PRNGKey(len(geometry) * 31 + len(prompt)), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k, v = jax.random.normal(ks[1], (b, s, hkv, d), dtype), jax.random.normal(ks[2], (b, s, hkv, dv), dtype)
    rows = np.arange(s)
    valid = np.stack([(rows >= lo) & (rows < hi) for lo, hi in extents])
    return (q, k, v), valid, window, tile


@pytest.mark.parametrize("prompt", sorted(_PROMPTS))
@pytest.mark.parametrize("geometry", sorted(_GROUPED))
def test_grouped_forward_equals_the_parents_on_content_rows(geometry, prompt):
    (q, k, v), valid, window, tile = _grouped_case(geometry, prompt)
    b, s = valid.shape
    blocks = dict(block_q=tile, block_k=tile)
    if window is None:
        rng = np.random.default_rng(s + tile)
        keep = (rng.random((b, s, s)) < 0.2) & np.tril(np.ones((s, s), bool)) & valid[:, None, :]
        content = np.flatnonzero(valid[0])
        keep[0, content[len(content) // 2]] = False           # a content row that keeps nothing: zeros
        keep = jnp.asarray(keep, jnp.int8)
        want = _group_fwd_parent.masked_flash_attention(q, k, v, keep, **blocks)
        got = masked_flash_attention(q, k, v, keep, jnp.asarray(valid), **blocks)
        np.testing.assert_array_equal(np.asarray(masked_flash_attention(q, k, v, keep, **blocks), np.float32),
                                      np.asarray(want, np.float32))     # no extent given: every row the parent's
    else:
        want = _group_fwd_parent.banded_flash_attention(q, k, v, window, jnp.asarray(valid), **blocks)
        got = banded_flash_attention(q, k, v, window, jnp.asarray(valid), **blocks)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(want[valid]).max() > 0.1
    np.testing.assert_array_equal(got[valid], want[valid])
    # nobody reads a row past the prompt's end: a query block wholly of padding is zeros ...
    blocks_past = ~valid.reshape(b, s // tile, tile).any(-1)
    assert not got.reshape(b, s // tile, tile, -1)[blocks_past].any()
    # ... and a padded row beside content rows is what its own mask row says: the parent's
    np.testing.assert_array_equal(got.reshape(b, s // tile, tile, -1)[~blocks_past],
                                  want.reshape(b, s // tile, tile, -1)[~blocks_past])
    if prompt == "a_row_that_keeps_nothing":
        assert not got[1].any()
        if window is None:
            assert not got[0, np.flatnonzero(valid[0])[valid[0].sum() // 2]].any()


def test_grouped_forward_in_float32_equals_the_parents_to_the_last_bit():
    """float32 operands (the tests' models): as the flash forward above, the
    CPU's compiler may contract a multiply-add in one form and not the other."""
    (q, k, v), valid, window, tile = _grouped_case("trinity", "ends_inside_a_tile", jnp.float32)
    want = _group_fwd_parent.banded_flash_attention(q, k, v, window, jnp.asarray(valid), block_q=tile, block_k=tile)
    got = banded_flash_attention(q, k, v, window, jnp.asarray(valid), block_q=tile, block_k=tile)
    _same(np.asarray(got)[valid], np.asarray(want)[valid])


def _brute_force_group_tiles(seq, n_valid, bq, bk, window):
    """A left-padded prompt's tiles element by element: the pairs of the
    triangle or band, and those that hold a content row and a content key the
    row may read."""
    pad = seq - n_valid
    steps = needed = 0
    for i in range(seq // bq):
        for j in range(seq // bk):
            rows, cols = np.arange(bq)[:, None] + i * bq, np.arange(bk)[None] + j * bk
            may = (rows >= cols) & (cols > rows - (window or seq))
            steps += bool(may.any())
            needed += bool((may & (rows >= pad) & (cols >= pad)).any())
    return steps, needed


# (bucket, prompt) of cells 5, 6 and 7's eight-prompt blocks (PERF.md section 4), and the group and window
_GROUP_CELLS = {
    "keye_longdocs_closed": (8, None, [(8192, 5700), (16384, 8862), (16384, 12765), (32768, 24600)]),
    "glm5_agentdocs_closed": (1, None, [(4096, 4096), (8192, 5793), (8192, 8192), (16384, 8862), (16384, 10460),
                                        (16384, 12765), (16384, 16384)]),
    "trinity_mixedctx_closed": (6, 4096, [(4096, 2799), (8192, 4402), (8192, 7338), (16384, 9146),
                                          (16384, 11534), (16384, 16384)]),
    "small_blocks": (2, 100, [(256, 100), (256, 128), (384, 70), (64, 1)]),
}


@pytest.mark.parametrize("cell", sorted(_GROUP_CELLS))
def test_group_tile_plan_against_a_brute_force_count(cell):
    group, window, prompts = _GROUP_CELLS[cell]
    blocks = (64, 32) if cell == "small_blocks" else (256 if group == 8 else 512, 512)
    for seq, n in prompts:
        steps, bodies, edge, needed = (group_tile_plan(seq, n, group, window, *blocks) if cell == "small_blocks"
                                       else group_tile_plan(seq, n, group, window))
        assert (steps, needed) == _brute_force_group_tiles(seq, n, *blocks, window), (seq, n)
        assert bodies == needed                              # visited = needed: nothing multiplied in vain
        assert (edge == bodies) if window is None else (0 < edge <= bodies)
    if cell == "glm5_agentdocs_closed":                      # ISSUE 50's count: 171, 231 and 325 of 528
        assert [group_tile_plan(16384, n, 1)[1] for n in (8862, 10460, 12765)] == [171, 231, 325]
        assert group_tile_plan(16384, 16384, 1)[:2] == (528, 528)
    if cell == "trinity_mixedctx_closed":                    # nine key blocks a query block at most, not ten
        assert group_tile_plan(16384, 16384, 6, 4096)[0] == 36 + 24 * 9


def test_the_kernels_table_is_the_plans_count():
    """The (batch row, pair) table the kernel prefetches, built by ``jnp`` from
    the mask, against the host's count for the same prompts."""
    seq, bq, bk, window, pads = 1024, 128, 64, 200, (0, 300, 1023)
    valid = np.stack([np.arange(seq) >= p for p in pads])
    seg = jnp.where(jnp.asarray(valid), 0, -1)
    qi, kj = _tile_pairs(seq // bq, seq // bk, bq, bk, True, window)
    ranges = tuple(r for blk in (bq, bk) for r in (seg.reshape(3, -1, blk).min(-1), seg.reshape(3, -1, blk).max(-1)))
    classes = np.asarray(_tile_classes(jnp, qi, kj, bq, bk, True, 0, 0, ranges, False, window))
    for row, pad in zip(classes, pads):
        assert (row.size, (row != 0).sum(), (row == 2).sum()) == group_tile_plan(seq, seq - pad, 1, window, bq, bk)[:3]
    plan = np.asarray(_tile_plan(jnp.asarray(classes), kj, qi))
    live = classes != 0
    assert (plan[live] >> 16 == np.broadcast_to(qi, plan.shape)[live]).all()        # a live pair fetches its own blocks
    assert ((plan[live] >> 2) & 0x3FFF == np.broadcast_to(kj, plan.shape)[live]).all()
    moves = (np.diff(plan >> 2, axis=1) != 0).sum(1)                                # an empty pair moves nothing
    assert (moves <= live.sum(1)).all()


@pytest.mark.parametrize("nq,nk,bq,bk,triangle", [(32, 32, 512, 512, True), (41, 41, 512, 512, True),
                                                  (8, 16, 128, 64, True), (4, 6, 64, 64, False)])
def test_the_flash_forwards_tables_are_the_parents(nq, nk, bq, bk, triangle):
    """``_tile_pairs`` / ``_tile_classes`` / ``_tile_plan`` learned two
    geometries; what ``_flash_fwd`` asks of them is what PR 45 wrote down."""
    qi, kj = _tile_pairs(nq, nk, bq, bk, triangle)
    last = np.minimum((np.arange(nq) * bq + bq - 1) // bk if triangle else nk - 1, nk - 1) + np.zeros(nq, int)
    assert (qi == np.repeat(np.arange(nq), last + 1)).all()
    assert (kj == np.concatenate([np.arange(n + 1) for n in last])).all()
    seg = np.where(np.arange(nq * bq) < 700, -1, np.arange(nq * bq) // 1500)[None]
    kseg = seg[:, :nk * bk] if nk * bk <= nq * bq else np.pad(seg, ((0, 0), (0, nk * bk - nq * bq)), constant_values=7)
    ranges = (seg.reshape(1, nq, bq).min(-1), seg.reshape(1, nq, bq).max(-1),
              kseg.reshape(1, nk, bk).min(-1), kseg.reshape(1, nk, bk).max(-1))
    for residuals in (False, True):
        got = _tile_classes(np, qi, kj, bq, bk, triangle, 0, 0, ranges, residuals)
        qmn, qmx, kmn, kmx = (r[:, x] for r, x in zip(ranges, (qi, qi, kj, kj)))
        live = (qmx >= kmn) & (qmn <= kmx)
        cut = ~((qmn == qmx) & (kmn == kmx) & (qmn == kmn))
        if triangle:
            live = live & (kj * bk <= qi * bq + bq - 1)
            cut = cut | (kj * bk + bk - 1 > qi * bq)
        if not residuals:
            live = live & (qmx >= 0) & (kmx >= 0)
        want = np.where(live, np.where(cut, 2, 1), 0)
        np.testing.assert_array_equal(got, want)
        plan = np.asarray(_tile_plan(jnp.asarray(got), kj))
        assert (plan & 3 == got).all() and (plan >> 2 < nk).all()
        assert ((plan >> 2)[got != 0] == np.broadcast_to(kj, got.shape)[got != 0]).all()
