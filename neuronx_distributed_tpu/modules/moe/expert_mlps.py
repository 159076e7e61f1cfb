"""Expert MLP execution strategies (reference: ``modules/moe/expert_mlps.py``
``ExpertMLPs:30`` with the strategy dispatch policy at ``forward:595``).

Reference strategies → TPU-native formulations:

* ``forward_all_experts`` (expert_mlps.py:179): every token through every
  expert, mask-combine. Exact/dropless; FLOPs = dense. Kept as the golden path
  and the EP-friendly dropless fallback (contraction over the sharded expert
  dim becomes one psum under GSPMD).
* ``forward_capacity_factor`` (expert_mlps.py:218): Megatron/GShard capacity-C
  dispatch. The reference builds cumsum positions + permutes with fp64 one-hot
  masks to keep XLA graphs static; here the same dispatch/combine masks are
  fp32 einsums (exact for these 0/1 matmuls) — the classic TPU MoE
  formulation, fully static, and the dispatch einsum is what XLA turns into
  the EP all-to-all.
* ``forward_blockwise`` (expert_mlps.py:346): dropless. The reference sorts
  tokens into fixed-size blocks and calls an NKI grouped-matmul kernel
  (blockwise.py:434); the TPU equivalent is ``jax.lax.ragged_dot`` — XLA's
  native grouped matmul, lowered by Mosaic to MXU tiles — on expert-sorted
  tokens. TP shards the intermediate dim inside an explicit ``shard_map``
  (Mosaic grouped matmuls are not auto-partitioned over the ragged group dim).
  With ep > 1 each ep rank rolls the expert-sorted rows to its own experts'
  segment, runs the grouped matmul on its E/ep local experts, and the
  combine is a psum over ep (the reference's blockwise NKI path composes
  with EP the same way, blockwise.py:434). A step's FEW rows on the TPU
  (``blockwise_form``: mesh-free, float weights, at most
  ``MOE_STREAM_MAX_TOKENS`` rows) go through ``kernels/moe_stream.py``
  instead: every row through each HIT expert, whose weights are streamed
  once, and no sort, gather or scatter of rows.
* ``forward_selective_loading`` (expert_mlps.py:319): decode path — for a
  handful of tokens, gather just the k expert weight slices each token
  routed to and run per-token matmuls; FLOPs = k/E of dense and no
  dispatch machinery. Auto-selected when T <= selective_threshold.
* HELD experts (``held_experts=(first, count)``; no reference counterpart):
  this device's share of an expert-parallel deployment run WITHOUT its
  exchange. The router's ids range over all ``num_experts``; the weights are
  those of experts ``[first, first + count)`` alone, and the result is the
  part of the routed sum those experts give: rows routed elsewhere add
  nothing (``_held``). Dropless and exact for any routing.

A ROW MASK (``ExpertMLPs.__call__(..., row_mask)``; a prefill's
``padding_mask``, handed down by ``MoE`` in ``prefill`` mode only): a row it
leaves out gets ZERO from the routed experts, whatever the strategy, so a
content row's sum is blind to what the bucket's padding routed to. The two
mesh-free grouped-matmul forms also do no WORK for such a row. Its slots
count as absent and sort last; the held experts' loop (``_held``) then takes
the trips the prompt's held slots need, not the bucket's; and a layer that
holds every expert runs ONE call (``_masked_ragged_routed_mlp``) whose
``ragged_dot`` is told of the experts' groups alone (XLA's does not visit the
rows past their sum) and whose combine gathers each content token's ``k``
rows where the scatter-add would walk the padded slots too. Every other form
(``selective``, ``all_experts``, ``capacity_factor``, the streamed kernel, the
``shard_map`` forms) zeroes the row's affinities and is otherwise what it
was. With no mask every path is what it was to the letter (a train step's and
a decode step's lowered text: ``tests/modules/test_moe.py``).
"""

from __future__ import annotations

import functools
from math import ceil
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.kernels.moe_stream import moe_stream_mlp
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain

Dtype = Any

# rows of a step at or below which the blockwise strategy streams the hit
# experts (``kernels/moe_stream.py``) where it would sort the slots for the
# grouped matmul: from a sweep of both forms on a v5e at DeepSeek-V2-Lite's,
# Keye's and Mixtral's expert shapes (``chip_smoke.py --only moe``; PERF.md §6,
# PR 33)
MOE_STREAM_MAX_TOKENS = 256


# rows of the expert-sorted slots a trip of the held experts' loop takes
# (``ExpertMLPs._held``): its gathered tokens are this many x hidden, whatever
# the prompt
HELD_BLOCK_ROWS = 2048

# the counters a dispatch that is given a row mask sows (``ExpertMLPs.__call__``):
# the rows it kept and the rows it was given
MOE_PREFILL_STATS = ("moe_live_rows", "moe_rows")
LATEST = dict(init_fn=lambda: jnp.zeros((), jnp.int32), reduce_fn=lambda _, new: new)


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def _grouped_mlp(xs_, gate_, up_, down_, sizes, *, glu: bool, act: str):
    h = jax.lax.ragged_dot(xs_, up_, sizes)
    if glu:
        g = jax.lax.ragged_dot(xs_, gate_, sizes)
        h = _act(act)(g) * h
    else:
        h = _act(act)(h)
    return jax.lax.ragged_dot(h, down_, sizes)


def blockwise_form(n_tokens: int, *, sharded: bool, quantized: bool) -> str:
    """``"stream"`` or ``"ragged_dot"``: how the blockwise strategy multiplies
    a call's ``n_tokens`` rows, from what the layer can observe. The streamed
    kernel is compiled for the TPU and takes a decode step's few rows of a
    mesh-free layer with float weights; a prefill's thousands of rows, tp or
    ep > 1 (``shard_map``) and quantized experts keep the grouped matmul."""
    if sharded or quantized or n_tokens > MOE_STREAM_MAX_TOKENS:
        return "ragged_dot"
    return "stream" if backend.on_tpu() else "ragged_dot"


def _masked_affinities(top_w, row_mask):
    """``top_w`` with the rows ``row_mask`` leaves out at zero: how a form
    that cannot skip a row's work gives it nothing."""
    return top_w if row_mask is None else jnp.where(row_mask[:, None], top_w, 0)


def _sorted_slots(top_e, top_w, num_experts: int, dtype):
    """The dropless dispatch: ``(token_idx, group_sizes, ws)`` of the
    ``T k`` slots sorted by expert."""
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)  # expert-sorted slot ids
    token_idx = order // top_e.shape[1]
    group_sizes = jnp.bincount(flat_e, length=num_experts).astype(jnp.int32)
    ws = top_w.reshape(-1)[order].astype(dtype)
    return token_idx, group_sizes, ws


def _ragged_routed_mlp(x, top_e, top_w, gate, up, down, act: str, row_mask=None):
    """The routed sum through the grouped matmul, mesh-free: sort the slots,
    gather their rows, three ``ragged_dot`` calls (two where ``gate`` is
    None: no GLU), scatter-add. With a ``row_mask`` (T,): the padded rows'
    slots sorted last, and none of that work done for them
    (``_masked_ragged_routed_mlp``)."""
    if row_mask is not None:
        return _masked_ragged_routed_mlp(x, top_e, top_w, row_mask, gate, up, down, act)
    with jax.named_scope("moe.dispatch"):
        token_idx, group_sizes, ws = _sorted_slots(
            top_e, top_w, up.shape[0], x.dtype)
        xs = x[token_idx]
    with jax.named_scope("moe.experts"):
        ys = _grouped_mlp(xs, gate, up, down, group_sizes,
                          glu=gate is not None, act=act)
    with jax.named_scope("moe.combine"):
        return jnp.zeros(x.shape, ys.dtype).at[token_idx].add(ys * ws[:, None])


def _masked_ragged_routed_mlp(x, top_e, top_w, row_mask, gate, up, down, act: str):
    """``_ragged_routed_mlp`` for a bucket whose rows outside ``row_mask``
    (T,) are padding: they get zero, and a content row the sum it always got,
    bf16 add for bf16 add. The padded rows' slots get a group of their own
    past the last expert and sort LAST; ``group_sizes`` names the experts'
    groups alone, and ``ragged_dot`` does not visit the rows past their sum
    (its time is that of a call on the content slots: ``chip_smoke.py --only
    moe``, PERF.md section 6, PR 55). The combine reads content rows only: a
    token's ``k`` weighted rows are GATHERED from their sorted positions and
    added one by one in the order the scatter-add met them (by expert), where
    a scatter-add over the ``T k`` slots would sort, gather and walk the
    padded slots too (11.5 ms of a 30.7 ms layer at Keye's shape)."""
    E = up.shape[0]
    T, k = top_e.shape
    with jax.named_scope("moe.dispatch"):
        key = jnp.where(row_mask[:, None], top_e, E).reshape(-1)
        order = jnp.argsort(key, stable=True)      # content slots first, by expert
        group_sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
        ws = top_w.reshape(-1)[order].astype(x.dtype)
        xs = x[order // k]
    with jax.named_scope("moe.experts"):
        ys = _grouped_mlp(xs, gate, up, down, group_sizes,
                          glu=gate is not None, act=act)
    with jax.named_scope("moe.combine"):
        # what the rows past the last group hold is ragged_dot's to say
        live = (jnp.arange(T * k) < jnp.sum(group_sizes))[:, None]
        weighted = jnp.where(live, ys * ws[:, None], 0)
        # each token's sorted positions, ascending: its experts in their order
        # (a padded token's all lie past the last group: it sums zeros)
        at = jnp.sort(jnp.argsort(order).reshape(T, k), axis=1)
        out = jnp.zeros(x.shape, ys.dtype)
        for j in range(k):
            out = out + weighted[at[:, j]]
        return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _streamed_routed_mlp(x, top_e, top_w, gate, up, down, act: str):
    """The same sum through ``kernels/moe_stream.py``; differentiated as the
    grouped-matmul form (the kernel has no backward of its own, and a step
    small enough to stream costs a backward little either way)."""
    with jax.named_scope("moe.experts"):
        return moe_stream_mlp(x, top_e, top_w, gate, up, down, act=_act(act))


def _streamed_fwd(x, top_e, top_w, gate, up, down, act):
    out = _streamed_routed_mlp(x, top_e, top_w, gate, up, down, act)
    return out, (x, top_e, top_w, gate, up, down)


def _streamed_bwd(act, residuals, ct):
    x, top_e, top_w, gate, up, down = residuals
    _, vjp = jax.vjp(
        lambda x_, w_, g_, u_, d_: _ragged_routed_mlp(
            x_, top_e, w_, g_, u_, d_, act),
        x, top_w, gate, up, down)
    dx, dw, dg, du, dd = vjp(ct)
    return dx, None, dw, dg, du, dd


_streamed_routed_mlp.defvjp(_streamed_fwd, _streamed_bwd)


@functools.lru_cache(maxsize=None)
def _sharded_blockwise_mlp(mesh, ep_ax, tp_ax, E_l: int, ep: int, glu: bool,
                           act: str):
    """Cached jitted shard_map for the ep/tp-sharded blockwise grouped matmul
    (jit keys on callable identity — rebuilding per call would recompile every
    eager invocation). The jit wrapper exists because the eager shard_map impl
    cannot execute partial-manual specs (its internal unmatch step builds a
    full-mesh out_spec); under an outer jit it inlines.

    EP alignment by LOCAL-OFFSET GATHER (round 4, VERDICT r3 weak #4): each
    rank's segment of the expert-sorted slot space starts at data-dependent
    row ``start``; instead of rolling a pre-gathered (N, H) token matrix
    forward and back per layer (two O(N·H) shuffles), the rank gathers its
    segment's token rows DIRECTLY — ``token_idx[(arange(N)+start) % N]`` —
    and scatter-adds its weighted outputs straight onto the (T, H) combine
    buffer. One gather + one scatter, both unavoidable in any dropless MoE;
    the rolls are gone and the stacked output shrinks from (N, H) to (T, H)
    rows (N = k·T). Pinned against the unsharded golden by
    ``tests/modules/test_moe.py::test_blockwise_ep_sharded_matches_golden``;
    not measured on the chip (no cell runs ep > 1)."""
    axes = tuple(a for a in (ep_ax, tp_ax) if a)
    wspec_col = P(ep_ax, None, tp_ax)
    wspec_row = P(ep_ax, tp_ax, None)

    def sharded_mlp(x, token_idx, ws, sizes, gate_, up_, down_):
        T = x.shape[0]
        N = token_idx.shape[0]
        ep_rank = jax.lax.axis_index(ep_ax) if ep > 1 else 0
        local_sizes = jax.lax.dynamic_slice_in_dim(sizes, ep_rank * E_l, E_l)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), sizes.dtype), jnp.cumsum(sizes)]
        )
        start = offsets[ep_rank * E_l]
        n_local = local_sizes.sum()
        rows = (jnp.arange(N) + start) % N  # this rank's slots, segment-first
        idx_r = token_idx[rows]
        y = _grouped_mlp(x[idx_r], gate_, up_, down_, local_sizes,
                         glu=glu, act=act)
        # rows past the local segment are garbage — zero their contribution;
        # the combine over ep (and the tp partial-sum reduction) happens
        # OUTSIDE the shard_map as a plain sum over the stacked rank dims:
        # transposing an in-region psum through a partial-manual shard_map is
        # not supported, a stacked output transposes cleanly
        valid = (jnp.arange(N) < n_local)[:, None]
        contrib = jnp.zeros((T, x.shape[1]), y.dtype).at[idx_r].add(
            jnp.where(valid, y * ws[rows][:, None], 0)
        )
        return contrib[None, None]

    return jax.jit(
        mesh_lib.compat_shard_map(
            sharded_mlp,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), wspec_col, wspec_col, wspec_row),
            out_specs=P(ep_ax, tp_ax, None, None),
            axis_names=set(axes),
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=None)
def _sharded_blockwise_mlp_manual(mesh, edp_ax, ep_ax, tp_ax, E: int,
                                  E_l: int, ep: int, k: int, glu: bool,
                                  act: str):
    """Fully-manual blockwise path (round 5, VERDICT r4 weak #3): the token
    dim is CLAIMED over edp and each data shard solves its own dropless
    dispatch — routing (sort/bincount) moves inside the region, every rank
    grouped-matmuls its (ep-segment × tp-slice) share of its shard's tokens,
    and the combine is an IN-REGION ``psum`` over (ep, tp) of the (T/edp, H)
    buffer. Replaces the stacked (ep, tp, T, H) output + outside sum, whose
    interconnect cost was ep·tp copies of the full combine buffer (the
    partial-manual psum-transpose limitation does not bite once edp is
    manual, because no auto-sharded operand dimension remains)."""
    axes = tuple(a for a in (edp_ax, ep_ax, tp_ax) if a)
    wspec_col = P(ep_ax, None, tp_ax)
    wspec_row = P(ep_ax, tp_ax, None)
    tok_spec = P(edp_ax, None)

    def sharded_mlp(x, top_e, top_w, gate_, up_, down_):
        T = x.shape[0]
        flat_e = top_e.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)  # expert-sorted local slots
        token_idx = order // k
        sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        ws = top_w.reshape(-1)[order].astype(x.dtype)
        N = token_idx.shape[0]
        ep_rank = jax.lax.axis_index(ep_ax) if ep > 1 else 0
        local_sizes = jax.lax.dynamic_slice_in_dim(sizes, ep_rank * E_l, E_l)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), sizes.dtype), jnp.cumsum(sizes)]
        )
        start = offsets[ep_rank * E_l]
        n_local = local_sizes.sum()
        rows = (jnp.arange(N) + start) % N  # this rank's slots, segment-first
        idx_r = token_idx[rows]
        y = _grouped_mlp(x[idx_r], gate_, up_, down_, local_sizes,
                         glu=glu, act=act)
        valid = (jnp.arange(N) < n_local)[:, None]
        contrib = jnp.zeros((T, x.shape[1]), y.dtype).at[idx_r].add(
            jnp.where(valid, y * ws[rows][:, None], 0)
        )
        red = tuple(a for a in (ep_ax, tp_ax) if a)
        if red:
            contrib = jax.lax.psum(contrib, red)
        return contrib

    return jax.jit(
        mesh_lib.compat_shard_map(
            sharded_mlp,
            mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, wspec_col, wspec_col,
                      wspec_row),
            out_specs=tok_spec,
            axis_names=set(axes),
            check_vma=False,
        )
    )


class ExpertMLPs(nn.Module):
    """3D-weight expert MLPs (weights ``(E, H, I)`` / ``(E, I, H)``, experts
    sharded over ep, intermediate over tp — reference ``experts.py:22`` +
    ``moe_parallel_layers.py`` fused layers).

    ``capacity_factor=None`` → dropless (reference semantics); otherwise
    Megatron-style capacity ``C = ceil(cf·T·k/E)`` with token dropping.
    """

    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    hidden_act: str = "silu"
    glu_mlp: bool = True
    capacity_factor: Optional[float] = None
    # auto | all_experts | capacity_factor | blockwise | selective
    strategy: str = "auto"
    # dense all-experts pays E/k times the routed FLOPs — only worth it when
    # the dispatch overhead dominates, i.e. very few experts (ADVICE round 1:
    # the old threshold of 8 made the flagship top-2-of-8 Mixtral dense)
    all_experts_threshold: int = 4
    # token count at or below which the per-token gathered-weights decode path
    # is used (reference forward_selective_loading, expert_mlps.py:319)
    selective_threshold: int = 8
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # weight-only serving quantization: expert weights stored int8/fp8 with
    # per-expert per-channel scales (reference QuantizedExpertFused* layers,
    # quantization_layers.py:867,:979 — the quantized-MoE serving case where
    # 1-byte expert weights are the HBM win)
    quantization_config: Optional[Any] = None
    # ``(first, count)``: the weights are experts ``[first, first + count)``
    # of ``num_experts`` (what one device of an expert-parallel deployment
    # holds); ``top_e`` still ranges over all of them (``_held``)
    held_experts: Optional[Tuple[int, int]] = None

    def _one_param(self, name, shape, partition, init):
        from neuronx_distributed_tpu.parallel.layers import _declare_kernel

        # (E, in, out) scales per expert per out-channel: (E, 1, out); the
        # declaration + scale-shape contract lives in ONE place
        return _declare_kernel(
            self, shape, partition, init, self.dtype,
            scale_partition=(partition[0], None, partition[2]),
            name=name, channel_dim=len(shape) - 1, batch_dim=0,
        )

    def _params(self):
        from neuronx_distributed_tpu.modules.moe.moe_parallel_layers import (
            COLUMN_KERNEL_PARTITION,
            ROW_KERNEL_PARTITION,
        )

        E, H, I = self.num_experts, self.hidden_size, self.intermediate_size
        if self.held_experts is not None:
            E = self.held_experts[1]
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        up = self._one_param("up_proj", (E, H, I), COLUMN_KERNEL_PARTITION, init)
        gate = None
        if self.glu_mlp:
            gate = self._one_param(
                "gate_proj", (E, H, I), COLUMN_KERNEL_PARTITION, init
            )
        down = self._one_param("down_proj", (E, I, H), ROW_KERNEL_PARTITION, init)
        return gate, up, down

    def _resolve_strategy(self, n_tokens: Optional[int] = None) -> str:
        if self.strategy != "auto":
            return self.strategy
        if n_tokens is not None and n_tokens <= self.selective_threshold:
            return "selective"
        if self.capacity_factor is not None:
            return "capacity_factor"
        # dropless: blockwise (ragged grouped matmul, routed FLOPs only) is
        # the default; dense all-experts only for a handful of experts
        if self.num_experts <= self.all_experts_threshold:
            return "all_experts"
        return "blockwise"

    @nn.compact
    def __call__(self, x: jax.Array, top_e: jax.Array, top_w: jax.Array,
                 row_mask: Optional[jax.Array] = None) -> jax.Array:
        """``x (T, H)`` tokens, ``top_e (T, k)`` expert ids, ``top_w (T, k)``
        affinities → ``(T, H)`` combined expert outputs. ``row_mask`` (T,)
        bool: a row it leaves out (a prefill bucket's padding) gets ZERO,
        whatever it routed to; the held experts' loop and the mesh-free
        grouped matmul do no work for it, every other form zeroes its
        affinities. ``None``: every row counts."""
        gate, up, down = self._params()
        if row_mask is not None and not self.is_initializing():
            # for whoever collects them (``MOE_PREFILL_STATS``): the rows this
            # dispatch kept, of the rows it was given
            self.sow("stats", "moe_live_rows", jnp.sum(row_mask, dtype=jnp.int32), **LATEST)
            self.sow("stats", "moe_rows", jnp.asarray(row_mask.size, jnp.int32), **LATEST)
        if self.held_experts is not None:
            x = x.astype(self.dtype)
            return self._held(
                x, top_e, top_w, None if gate is None else gate.astype(self.dtype),
                up.astype(self.dtype), down.astype(self.dtype), row_mask)
        strategy = self._resolve_strategy(n_tokens=x.shape[0])
        if self.strategy == "auto" and not self.is_initializing():
            from neuronx_distributed_tpu.utils.logger import get_logger

            flops_mult = (
                self.num_experts / self.top_k if strategy == "all_experts" else 1.0
            )
            get_logger(__name__).debug(
                "MoE auto strategy: %s (T=%d, E=%d, k=%d, FLOPs multiplier vs "
                "routed: %.1fx)",
                strategy, x.shape[0], self.num_experts, self.top_k, flops_mult,
            )
        x = x.astype(self.dtype)
        gate = None if gate is None else gate.astype(self.dtype)
        up, down = up.astype(self.dtype), down.astype(self.dtype)
        if strategy == "blockwise":
            return self._blockwise(x, top_e, top_w, gate, up, down, row_mask)
        top_w = _masked_affinities(top_w, row_mask)
        # the dense strategies fold dispatch and combine into their einsums:
        # one scope; the sparse ones below name their three phases
        if strategy == "all_experts":
            with jax.named_scope("moe.experts"):
                return self._all_experts(x, top_e, top_w, gate, up, down)
        if strategy == "capacity_factor":
            with jax.named_scope("moe.experts"):
                return self._capacity_factor(x, top_e, top_w, gate, up, down)
        if strategy == "selective":
            return self._selective(x, top_e, top_w, gate, up, down)
        raise ValueError(f"unknown expert strategy {strategy!r}")

    # --- held experts: one device's share, without the exchange ---------------

    def held_slots(self, top_e):
        """``(local, held)`` of router ids ``top_e``: the id inside this
        device's ``[first, first + count)`` and whether it lies there."""
        first, count = self.held_experts
        local = top_e - first
        return local, (local >= 0) & (local < count)

    def _held(self, x, top_e, top_w, gate, up, down, row_mask=None):
        """The held experts' part of ``sum_i w_i expert_i(x)``: ``x`` (T, H),
        ``top_e``/``top_w`` (T, k) over ALL ``num_experts``. Dropless and
        exact for any routing (every slot routed here is computed, however
        many), a decode step's few rows and a prefill's alike: the slots are
        sorted by held expert, absent ones last, and a loop takes
        ``HELD_BLOCK_ROWS`` sorted rows a trip through the grouped matmul
        (``ragged_dot``, which reads the weights of the experts that have
        rows) for as many trips as HELD rows need: none where nobody chose a
        held expert. A slot of a row that ``row_mask`` (T,) leaves out (a
        prefill bucket's padding) counts as absent too, so the trips follow
        the prompt and not its bucket. The gathered tokens are
        ``HELD_BLOCK_ROWS x H``, never ``T x k x H`` (1.6 GB at 16,384 tokens
        of 6144 top-8); with ``count / num_experts`` of the slots held a
        prompt takes ``P k count / (num_experts HELD_BLOCK_ROWS)`` trips,
        rounded up.
        """
        count = self.held_experts[1]
        if mesh_lib.model_parallel_is_initialized() and (
            mesh_lib.get_tensor_model_parallel_size() > 1
            or mesh_lib.get_expert_model_parallel_size() > 1
        ):
            raise NotImplementedError(
                "held_experts is one device's share run without a mesh: "
                "under an ep axis the layer holds every expert and "
                "_sharded_blockwise_mlp slices them")
        T, H = x.shape
        k = self.top_k
        local, held = self.held_slots(top_e)
        if row_mask is not None:
            held = held & row_mask[:, None]
        N = T * k
        block = min(HELD_BLOCK_ROWS, N)
        with jax.named_scope("moe.dispatch"):
            key = jnp.where(held, local, count).reshape(-1)
            order = jnp.argsort(key, stable=True)      # held slots first, by expert
            token_idx = order // k
            sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
            ends = jnp.cumsum(sizes)
            n_held = ends[-1]
            ws = top_w.reshape(-1)[order].astype(x.dtype)
            pad = -N % block
            token_idx = jnp.pad(token_idx, (0, pad))
            ws = jnp.pad(ws, (0, pad))

        def trip(state):
            i, out = state
            lo = i * block
            with jax.named_scope("moe.dispatch"):
                idx = jax.lax.dynamic_slice_in_dim(token_idx, lo, block)
                w = jax.lax.dynamic_slice_in_dim(ws, lo, block)
                # the rows of each held expert that fall in [lo, lo + block)
                part = jnp.clip(ends, lo, lo + block) - jnp.clip(ends - sizes, lo, lo + block)
                rows = x[idx]
            with jax.named_scope("moe.experts"):
                ys = _grouped_mlp(rows, gate if gate is not None else up, up, down,
                                  part.astype(jnp.int32), glu=self.glu_mlp, act=self.hidden_act)
            with jax.named_scope("moe.combine"):
                live = (lo + jnp.arange(block) < n_held)[:, None]
                out = out.at[idx].add(jnp.where(live, ys * w[:, None], 0))
            return i + 1, out

        _, out = jax.lax.while_loop(
            lambda state: state[0] * block < n_held, trip,
            (jnp.zeros((), jnp.int32), jnp.zeros((T, H), x.dtype)))
        return out

    # --- strategy: selective loading (reference expert_mlps.py:319) -----------

    def _selective(self, x, top_e, top_w, gate, up, down):
        """Per-token gathered expert weights — the decode path. For T tokens,
        gathers (T, k, H, I) weight slices and runs per-token einsums; memory
        is bounded by T·k weight slices, so this is gated on small T."""
        with jax.named_scope("moe.dispatch"):
            up_g = jnp.take(up, top_e, axis=0)  # (T, k, H, I)
            gate_g = jnp.take(gate, top_e, axis=0) if self.glu_mlp else None
            down_g = jnp.take(down, top_e, axis=0)
        with jax.named_scope("moe.experts"):
            h = jnp.einsum("th,tkhi->tki", x, up_g)
            if self.glu_mlp:
                g = jnp.einsum("th,tkhi->tki", x, gate_g)
                h = _act(self.hidden_act)(g) * h
            else:
                h = _act(self.hidden_act)(h)
            y = jnp.einsum("tki,tkih->tkh", h, down_g)
        with jax.named_scope("moe.combine"):
            return jnp.einsum("tkh,tk->th", y, top_w.astype(y.dtype))

    # --- strategy: all experts (reference expert_mlps.py:179) -----------------

    def _all_experts(self, x, top_e, top_w, gate, up, down):
        E = self.num_experts
        comb = (
            jax.nn.one_hot(top_e, E, dtype=jnp.float32) * top_w[..., None]
        ).sum(1)  # (T, E)
        h = jnp.einsum("th,ehi->tei", x, up)
        h = constrain(h, P(UNC, mesh_lib.EP_AXIS, mesh_lib.TP_AXIS))
        if self.glu_mlp:
            g = jnp.einsum("th,ehi->tei", x, gate)
            h = _act(self.hidden_act)(g) * h
        else:
            h = _act(self.hidden_act)(h)
        y = jnp.einsum("tei,eih->teh", h, down)
        y = constrain(y, P(UNC, mesh_lib.EP_AXIS))
        return jnp.einsum("teh,te->th", y, comb.astype(y.dtype))

    # --- strategy: capacity factor (reference expert_mlps.py:218) -------------

    def capacity(self, n_tokens: int) -> int:
        cf = self.capacity_factor if self.capacity_factor is not None else 1.0
        return min(
            n_tokens, int(ceil(cf * n_tokens * self.top_k / self.num_experts))
        )

    def _capacity_factor(self, x, top_e, top_w, gate, up, down):
        T, E, k = x.shape[0], self.num_experts, self.top_k
        C = self.capacity(T)
        flat_e = top_e.reshape(-1)  # (T·k,) token-major slot order = priority
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)  # (N, E)
        # position of each slot within its expert's queue (the reference's
        # cumsum-position trick, expert_mlps.py:218 — fp32 0/1 cumsums are
        # exact on TPU, the reference needed fp64 for torch-XLA argmax quirks)
        pos = (jnp.cumsum(oh, axis=0) - 1.0) * oh  # nonzero only at own expert
        pos = pos.sum(-1)  # (N,)
        keep = (pos < C).astype(jnp.float32)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
        # contract the k slot dim directly into the (T, E, C) masks — never
        # materializing the k-times-larger (N, E, C) intermediate
        oh3 = (oh * keep[:, None]).reshape(T, k, E)
        pos3 = pos_oh.reshape(T, k, C)
        dispatch = jnp.einsum("tke,tkc->tec", oh3, pos3)  # (T, E, C) 0/1
        combine = jnp.einsum(
            "tke,tkc,tk->tec", oh3, pos3, top_w.astype(jnp.float32)
        )
        # dispatch einsum → (E, C, H): the expert dim goes ep-sharded here,
        # which under GSPMD is exactly the enter-EP all-to-all
        # (reference mappings.py:474 enter_expert_parallel_region)
        xin = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), x)
        xin = constrain(xin, P(mesh_lib.EP_AXIS))
        h = jnp.einsum("ech,ehi->eci", xin, up)
        h = constrain(h, P(mesh_lib.EP_AXIS, None, mesh_lib.TP_AXIS))
        if self.glu_mlp:
            g = jnp.einsum("ech,ehi->eci", xin, gate)
            h = _act(self.hidden_act)(g) * h
        else:
            h = _act(self.hidden_act)(h)
        y = jnp.einsum("eci,eih->ech", h, down)
        y = constrain(y, P(mesh_lib.EP_AXIS))
        # combine einsum contracts (e, c) → the exit-EP all-to-all + weighting
        return jnp.einsum("tec,ech->th", combine.astype(y.dtype), y)

    # --- strategy: blockwise dropless (reference expert_mlps.py:346) ----------

    def _blockwise(self, x, top_e, top_w, gate, up, down, row_mask=None):
        T, H = x.shape
        k, E = self.top_k, self.num_experts

        initialized = mesh_lib.model_parallel_is_initialized()
        tp = mesh_lib.get_tensor_model_parallel_size() if initialized else 1
        ep = mesh_lib.get_expert_model_parallel_size() if initialized else 1

        if tp > 1 or ep > 1:
            # the shard_map forms keep their rows and zero what the mask leaves out
            top_w = _masked_affinities(top_w, row_mask)
            if E % max(ep, 1) != 0:
                raise ValueError(f"num_experts {E} not divisible by ep {ep}")
            mesh = mesh_lib.get_mesh()
            edp = mesh.shape[mesh_lib.EDP_AXIS]
            cp = mesh.shape[mesh_lib.CP_AXIS]
            # fully-manual in-region-psum path: needs the token dim cleanly
            # divisible over edp and no cp sequence sharding folded into it
            if cp == 1 and T % edp == 0:
                ctx_mesh = mesh_lib.ctx_abstract_mesh()
                smapped = _sharded_blockwise_mlp_manual(
                    mesh if ctx_mesh.empty else ctx_mesh,
                    mesh_lib.EDP_AXIS if edp > 1 else None,
                    mesh_lib.EP_AXIS if ep > 1 else None,
                    mesh_lib.TP_AXIS if tp > 1 else None,
                    E,
                    E // max(ep, 1),
                    ep,
                    k,
                    self.glu_mlp,
                    self.hidden_act,
                )
                return smapped(
                    x, top_e, top_w,
                    gate if gate is not None else up, up, down,
                )

        if tp > 1 or ep > 1:
            with jax.named_scope("moe.dispatch"):
                token_idx, group_sizes, ws = _sorted_slots(top_e, top_w, E, x.dtype)
            # Grouped (ragged) matmuls cannot be auto-partitioned by GSPMD, so
            # tp/ep sharding is an explicit shard_map. NOTE this is
            # deliberately PARTIAL manual ({tp, ep} only, unlike
            # mesh.manual_shard_map): the token rows stay sharded over the
            # auto data axes instead of being all-gathered.
            #
            # ep: each rank holds E/ep experts' weights and gathers ITS
            # segment of the expert-sorted slot space straight from the
            # (T, H) tokens (local-offset gather), then scatter-adds its
            # weighted outputs onto the combine buffer — every slot belongs
            # to exactly one rank's segment, so the stacked-rank sum is the
            # dropless combine (reference: the blockwise NKI path composes
            # with EP the same way, blockwise.py:434).
            if E % max(ep, 1) != 0:
                raise ValueError(f"num_experts {E} not divisible by ep {ep}")
            mesh = mesh_lib.get_mesh()
            ctx_mesh = mesh_lib.ctx_abstract_mesh()
            # only claim axes of size > 1: a claimed-but-unreduced axis breaks
            # the psum transpose rule in the backward
            smapped = _sharded_blockwise_mlp(
                mesh if ctx_mesh.empty else ctx_mesh,
                mesh_lib.EP_AXIS if ep > 1 else None,
                mesh_lib.TP_AXIS if tp > 1 else None,
                E // max(ep, 1),
                ep,
                self.glu_mlp,
                self.hidden_act,
            )
            with jax.named_scope("moe.experts"):
                contrib = smapped(
                    x, token_idx, ws, group_sizes,
                    gate if gate is not None else up, up, down,
                )
            with jax.named_scope("moe.combine"):
                return contrib.sum(axis=(0, 1))
        # a mesh of several devices with tp = ep = 1 still shards the rows
        # (GSPMD), which a kernel call is not partitioned over
        form = blockwise_form(
            T, sharded=initialized and mesh_lib.get_mesh().size > 1,
            quantized=self.quantization_config is not None)
        if form == "stream":
            return _streamed_routed_mlp(
                x, top_e, _masked_affinities(top_w, row_mask), gate, up, down, self.hidden_act)
        return _ragged_routed_mlp(x, top_e, top_w, gate, up, down, self.hidden_act, row_mask)


def decode_form(config, n_tokens: int, *, sharded: bool) -> str:
    """What a decode step's ``n_tokens`` rows run their routed experts
    through in a model built from ``config`` (its ``num_experts``,
    ``expert_strategy`` and, where it has them, ``capacity_factor``,
    ``quantization``, ``held_experts``): ``"held"``, the strategy ``auto``
    resolves to, or for ``blockwise`` its form (``blockwise_form``). The
    engine records it as ``programs.resolved["moe_decode"]``."""
    if getattr(config, "held_experts", None) is not None:
        return "held"
    strategy = ExpertMLPs(
        num_experts=config.num_experts, hidden_size=0, intermediate_size=0,
        strategy=config.expert_strategy,
        capacity_factor=getattr(config, "capacity_factor", None),
    )._resolve_strategy(n_tokens)
    if strategy != "blockwise":
        return strategy
    return blockwise_form(
        n_tokens, sharded=sharded,
        quantized=getattr(config, "quantization", None) is not None)
