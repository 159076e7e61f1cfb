"""Pallas streamed expert MLP: a decode step's routed experts, each HIT
expert's weights read once (reference: the blockwise NKI grouped matmul,
``modules/moe/blockwise.py:434``, at the shapes of a decode step).

A decode step routes a few rows (``T`` slots x ``k``) over many experts, so
most hit experts hold one or two rows and the step is bound by reading their
weights. The grouped-matmul form (``jax.lax.ragged_dot`` on expert-sorted
rows) sorts the slots, gathers ``T k`` rows, walks (group, k-tile, n-tile)
three times with tiles of a few hundred KB and scatter-adds ``T k`` rows
back. This kernel does none of that: ALL ``T`` rows go through every hit
expert, and a row the router did not send there carries weight 0 in the
combine matrix. With ``T`` <= a few dozen that MXU work hides under the
weight stream, and the stream is what is left: grid ``(hit-list position,
I-tile)``; the weights' ``index_map`` reads the expert id from the
scalar-prefetched list of hit experts, so one grid step copies a
``(H, tile)`` block of ``gate`` and ``up`` and a ``(tile, H)`` block of
``down`` (whole matrices where they fit: a MB or more a copy, contiguous)
while the MXU works on the step before. Positions past the number of hit
experts repeat the last block index (nothing is fetched again) and skip
their compute. The routed sum is accumulated in float32 across experts in
VMEM and written once.

What it reads is the hit experts' three matrices and nothing else:
``hit x 3 H I`` values, the number ``modules/moe.MoE`` sows as
``hit_experts``.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.kernels.backend import interpret_mode

# what one grid step's weight blocks may hold in VMEM, both pipeline buffers
# counted (gate, up and down tiles, twice); v5e has 128 MiB
WEIGHT_VMEM_BYTES = 48 * 1024 * 1024
# rows are padded to whole sublane tiles of the activations' dtype
_ROW_TILE = 16


def hit_mask(top_e: jax.Array, num_experts: int) -> jax.Array:
    """``(E,)`` bool: the experts with at least one of ``top_e``'s slots."""
    return jnp.zeros((num_experts,), jnp.bool_).at[top_e.reshape(-1)].set(True)


def hit_experts(top_e: jax.Array, num_experts: int, size: int
                ) -> Tuple[jax.Array, jax.Array]:
    """``(ids, count)``: the experts ``top_e`` names, ascending, in the first
    ``count`` of ``size`` places; the places past them repeat the last one.
    From the routing alone and with no sort over the slots."""
    hit = hit_mask(top_e, num_experts)
    ids = jnp.nonzero(hit, size=size, fill_value=0)[0].astype(jnp.int32)
    count = jnp.sum(hit, dtype=jnp.int32)
    last = ids[jnp.maximum(count - 1, 0)]
    return jnp.where(jnp.arange(size) < count, ids, last), count


def combine_matrix(top_e: jax.Array, top_w: jax.Array, num_experts: int
                   ) -> jax.Array:
    """``C (T, E)`` float32: ``C[t, e] = sum_k [top_e[t, k] == e] top_w[t, k]``."""
    onehot = jax.nn.one_hot(top_e, num_experts, dtype=jnp.float32)
    return (onehot * top_w.astype(jnp.float32)[..., None]).sum(1)


def pick_block_i(hidden: int, inter: int, itemsize: int, glu: bool,
                 budget: int = WEIGHT_VMEM_BYTES) -> int:
    """The widest tile of the intermediate dim whose weight blocks fit
    ``budget`` twice over: the whole of ``inter`` where that fits (every copy
    contiguous), else its largest divisor that is a multiple of 128 lanes."""
    mats = 3 if glu else 2
    per_col = 2 * mats * hidden * itemsize
    if inter * per_col <= budget:
        return inter
    for lanes in range(inter // 128, 0, -1):
        tile = lanes * 128
        if inter % tile == 0 and tile * per_col <= budget:
            return tile
    raise ValueError(
        f"no tile of the intermediate dim {inter} that is a multiple of 128 "
        f"holds (hidden {hidden}) x tile weight blocks in {budget} bytes"
    )


def vmem_limit_bytes(rows: int, hidden: int, tile: int, itemsize: int,
                     glu: bool) -> int:
    """What the call asks Mosaic for: the weight blocks of one grid step in
    both pipeline buffers; a row of ``x`` and of the output twice each, of
    the float32 accumulator and product, of the step's three float32
    intermediates and the activation in the operands' dtype; and 8 MiB of
    the compiler's own."""
    rows = -(-rows // _ROW_TILE) * _ROW_TILE
    weights = 2 * (3 if glu else 2) * hidden * tile * itemsize
    per_row = 4 * hidden * itemsize + 2 * 4 * hidden + (3 * 4 + itemsize) * tile
    return weights + rows * per_row + (8 << 20)


def _kernel(ids_ref, count_ref, x_ref, c_ref, *refs, glu: bool, act: Callable):
    if glu:
        gate_ref, up_ref, down_ref, out_ref, acc_ref = refs
    else:
        up_ref, down_ref, out_ref, acc_ref = refs
    del ids_ref  # read by the index maps
    p, j = pl.program_id(0), pl.program_id(1)

    @pl.when((p == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p < count_ref[0])
    def _():
        x = x_ref[...]
        h = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
        if glu:
            g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
            h = act(g) * h
        else:
            h = act(h)
        y = jnp.dot(h.astype(x.dtype), down_ref[...],
                    preferred_element_type=jnp.float32)
        acc_ref[...] += y * c_ref[...]

    @pl.when((p == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def moe_stream_mlp(
    x: jax.Array,
    top_e: jax.Array,
    top_w: jax.Array,
    gate: Optional[jax.Array],
    up: jax.Array,
    down: jax.Array,
    *,
    act: Callable[[jax.Array], jax.Array] = jax.nn.silu,
    block_i: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``sum_k top_w[t, k] expert_{top_e[t, k]}(x[t])`` for ``x (T, H)``,
    ``top_e`` / ``top_w (T, k)`` and the experts' weights as the layer stores
    them: ``gate`` / ``up (E, H, I)`` (``gate`` None: no GLU), ``down (E, I,
    H)``, all of ``x``'s dtype; ``act`` the activation. Returns ``(T, H)`` in that dtype; products
    accumulate in float32, the sum across experts too. Forward only: the
    layer gives it the grouped-matmul form's backward."""
    T, H = x.shape
    E, _, I = up.shape
    glu = gate is not None
    n_max = min(E, T * top_e.shape[1])
    tile = block_i or pick_block_i(H, I, x.dtype.itemsize, glu)
    if I % tile or (tile != I and tile % 128):
        raise ValueError(f"block_i {tile} does not tile the intermediate dim {I}")
    n_i = I // tile

    ids, count = hit_experts(top_e, E, n_max)
    # the hit experts' columns of the combine matrix, one (T, 1) block a place
    cols = combine_matrix(top_e, top_w, E)[:, ids].T[..., None]
    rows = -(-T // _ROW_TILE) * _ROW_TILE
    if rows != T:
        x = jnp.pad(x, ((0, rows - T), (0, 0)))
        cols = jnp.pad(cols, ((0, 0), (0, rows - T), (0, 0)))

    def tile_of(p, j, count):  # past the hit experts: the block stands
        return jnp.where(p < count[0], j, n_i - 1)

    col_spec = pl.BlockSpec(
        (None, H, tile), lambda p, j, ids, count: (ids[p], 0, tile_of(p, j, count)))
    row_spec = pl.BlockSpec(
        (None, tile, H), lambda p, j, ids, count: (ids[p], tile_of(p, j, count), 0))
    whole = pl.BlockSpec((rows, H), lambda p, j, ids, count: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # the hit list and its length
        grid=(n_max, n_i),
        in_specs=[
            whole,
            pl.BlockSpec((None, rows, 1), lambda p, j, ids, count: (
                jnp.minimum(p, count[0] - 1), 0, 0)),
            *([col_spec] if glu else []), col_spec, row_spec,
        ],
        out_specs=whole,
        scratch_shapes=[pltpu.VMEM((rows, H), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, glu=glu, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(T, H, tile, x.dtype.itemsize, glu),
        ),
        interpret=interpret_mode(interpret),
    )(ids, count.reshape(1), x, cols, *([gate] if glu else []), up, down)
    return out[:T]
