"""The walking decode kernel on a WIDE joined leaf, in interpret mode: Ouro's
MHA, 16 kv heads of 128, K and V one leaf of 32 head rows a token (8 KiB in
bf16, twice Trinity's). The block is sized by a token's bytes
(``flash_decode.walk_block_tokens``): 256 tokens here, 512 for every leaf of
4 KiB a token or less, to the letter; and one query row a kv head takes the
ROW kernel, which multiplies a block as it lies (a token's heads side by
side) and keeps each head's own columns: 128 tokens a block at 16 heads."""

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels import flash_decode
from neuronx_distributed_tpu.kernels.flash_decode import paged_gather_leaf, paged_walk_decode_attention
from neuronx_distributed_tpu.modules.attention import _masked_gqa_attention, split_kv, window_floor, window_keep
from tests.kernels.test_window_attention import PS, walk_geometry_case


def test_a_block_is_sized_by_a_tokens_bytes_and_narrow_leaves_keep_512_tokens():
    tokens = flash_decode.walk_block_tokens
    assert flash_decode.WALK_BLOCK_BYTES == flash_decode.WALK_BLOCK_TOKENS * 16 * 128 * 2
    assert tokens(16 * 128 * 2, 16) == 512          # Trinity's and Solar Open 2's (16, 128) bf16
    assert tokens(4 * 128 * 2, 16) == 512           # ZAYA1's (4, 128)
    assert tokens(32 * 128 * 2, 16) == 256          # a (32, 128) leaf: three blocks are 6 MiB of VMEM, not 12
    assert tokens(32 * 128 * 2, 16, row_heads=16) == 128   # Ouro's, in the row kernel: 2,048 score columns
    assert tokens(4 * 128 * 2, 16, row_heads=2) == 512     # a narrow MHA leaf keeps the token limit
    assert tokens(32 * 128 * 2, 48, row_heads=16) == 96    # whole pages
    assert tokens(32 * 256 * 2, 16) == 128          # CodeGen2's 16 heads of 256
    assert tokens(32 * 128 * 2, 48) == 240          # whole pages
    assert tokens(1 << 30, 16) == 16                # never less than a page


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("hkv,d", [(16, 128), (4, 32)], ids=["ouro", "four_heads_of_32"])
@pytest.mark.parametrize("window,n_log,cur", [(None, 32, 157), (None, 30, 237), (40, 32, 157)],
                         ids=["mid_block", "a_short_last_block", "floor_mid_block"])
def test_the_walking_kernel_with_one_query_row_a_kv_head(monkeypatch, dtype, hkv, d, window, n_log, cur):
    """16 query heads against 16 kv heads of 128 (Ouro's): the ROW kernel,
    which multiplies a block as it lies and keeps each head's own columns; and
    four heads of 32, whose K rows do not fill a tile and which keep the
    per-head kernel. Blocks of four pages BY BYTES
    (the token limit left at 512, the byte limit set to four pages of this
    leaf), against the float32 einsum under an index mask: a context that ends
    mid-block, unmapped pages inside a fetched block, gap columns, a slot that
    maps nothing, a short last block, a ``floor`` mid-block."""
    token_bytes = 2 * hkv * d * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(flash_decode, "WALK_BLOCK_BYTES", 4 * PS * token_bytes)
    assert flash_decode.walk_block_tokens(token_bytes, PS, row_heads=hkv) == 4 * PS < flash_decode.WALK_BLOCK_TOKENS
    pool, bt, valid, q = walk_geometry_case(hkv, 1, d, dtype, n_log, cur)
    assert pool.shape[2:] == (2 * hkv, d)
    q_pos = jnp.asarray([cur], jnp.int32)
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    k, v = split_kv(f32(paged_gather_leaf(pool, jnp.asarray(bt), PS)))
    want = _masked_gqa_attention(f32(q), k, v, window_keep(jnp.asarray(valid), q_pos, window))
    floor = None if window is None else window_floor(jnp.asarray(valid), cur, window)
    got = paged_walk_decode_attention(q, pool, jnp.asarray(bt), q_pos, kv_valid=jnp.asarray(valid), floor=floor,
                                      page_size=PS)
    assert got.dtype == dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(want), atol=3e-6 if dtype == jnp.float32 else 2e-2)
    assert not np.asarray(f32(got[2])).any()   # the slot that maps nothing


def test_one_query_row_a_kv_head_takes_the_row_kernel_and_a_group_of_rows_does_not():
    """The traced call: MHA's kernel reads its block buffer WHOLE, K's head
    rows and V's (two reads a block, no strided word reads); a GQA group keeps
    the kernel that takes each head's rows out (its lowered text is the
    cells')."""
    import jax

    def buffer_reads(hkv, g):
        shape = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(lambda q, pool, bt, pos: paged_walk_decode_attention(q, pool, bt, pos, page_size=16))(
            shape((2, 1, hkv * g, 128), jnp.bfloat16), shape((65, 16, 2 * hkv, 128), jnp.bfloat16),
            shape((2, 64), jnp.int32), shape((1,), jnp.int32))
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        reads = []

        def walk(j):
            for e in j.eqns:
                if e.primitive.name == "get" and len(e.invars[0].aval.shape) == 5:
                    reads.append(e.outvars[0].aval)
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

        walk(call.params["jaxpr"])
        return reads

    row = buffer_reads(16, 1)
    assert [(a.shape, a.dtype) for a in row] == [((128, 16, 128), jnp.bfloat16)] * 2
    grouped = buffer_reads(8, 6)
    assert len(grouped) == 8 and all(a.dtype == jnp.uint32 for a in grouped)
