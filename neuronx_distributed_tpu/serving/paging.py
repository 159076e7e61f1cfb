"""Paged KV cache for the serving engine: block-table slots over a
ref-counted page pool with zero-copy copy-on-write prefix sharing.

The row-per-slot manager (``serving/cache_manager.py``) charges every slot a
full ``max_seq_len`` row of HBM whatever its request actually uses, and the
prefix cache physically copies KV on every insert and reuse. This module
replaces that storage model at BLOCK/PAGE granularity while keeping every
byte of the decode math untouched:

* :class:`PageAllocator` owns a fixed pool of ``num_pages`` KV pages
  (``page_size`` cache columns each), free-listed and ref-counted. Page 0 is
  the reserved NULL page — never allocated, the scatter target of every
  unmapped block-table entry, never attendable.
* :class:`PagedCacheManager` is the drop-in slot manager: each slot holds a
  block table row (host-authoritative numpy mirror, uploaded as the
  ``pages`` leaf of the paged cache pytree ``{"pages": bt, "pool": tree}``
  the decode chunk donates). The jitted chunk gathers the logical
  ``(num_slots, max_seq_len)`` view through the table, runs the EXACT
  row-per-slot math (attention masking/RoPE still run off per-row
  ``kv_valid`` counts), and scatters back only its write window
  (``modules/attention.gather_cache_pages`` / ``scatter_cache_window`` over
  ``kernels/flash_decode``'s paged transport) — so token streams are
  bit-identical across layouts and XLA still compiles ONE decode program.
* Copy-on-write prefix sharing: every paged admission page-aligns its
  context start (the cursor target is bumped ``< page_size`` columns; gap
  columns stay invalid as ever), so insert-on-miss PINS the slot's
  whole context pages instead of extracting a compact copy, and a later
  hit maps those pages straight into the new slot's block table — ref-counts
  up, ZERO KV bytes copied (``PageAllocator.copy_bytes`` stays 0 by
  construction; the allocator accounting is the test surface). Decode
  writes always land beyond the aligned shared range, and the chunk's
  window scatter never rewrites pages outside the window, so shared pages
  are bit-stable while any number of holders decode off them.
* Page-granular fault domains: a poisoned page is quarantined out of the
  POOL (``PageAllocator.quarantine``) — only the requests whose tables map
  it are requeued, the slot indices stay in rotation, and capacity degrades
  by one page instead of one permanent slot row.

* Pages in runs: the pool leaf is ``(P, page_size, ...)``, so pool pages ``p
  .. p + PAGE_RUN - 1`` are adjacent HBM, and the kernels that fetch a slot's
  blocks themselves (``kernels/flash_decode._block_page_copies``) carry such
  a run with ONE copy where the table's aligned group of entries reads it.
  :meth:`PageAllocator.deal` makes the runs: the ``PAGE_RUN`` pages of an
  aligned group of a slot's logical row come from one aligned group of the
  pool, over the admissions and page crossings that ask for them. It is a
  preference among free pages and holds nothing back (a run's pages not yet
  asked for stay FREE), so the free count, the page-pressure wall and
  ``check()`` are what they were; the page stays the granule of alignment,
  sharing, pins, quarantine and spill.

* Window layers (``modules/attention.JoinedKVCache(window=)``): a model whose
  stack mixes window and full attention layers gets a block table and a pool
  a layer KIND. The full kind is everything above; the window kind's pool is
  sized for the window (``num_slots x (window / page_size + the chunk's write
  window + slack)`` + the null page) and its table's entries behind a slot's
  window go back to its allocator and read the null page, as the cursor moves
  (:meth:`PagedCacheManager._free_behind_window`). The paged pytree is then
  ``{"pages": bt, "window_pages": bt_w, "pool": tree}``; a model with one
  kind of layer builds exactly ``{"pages", "pool"}``.

Every manager instance registers in a weak set; ``check_all_live()`` runs
the leak/ref-count invariant (:meth:`PagedCacheManager.check`) over all
live managers — the serving test suite calls it after every test teardown.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.kernels.flash_decode import PAGE_RUN
from neuronx_distributed_tpu.modules.attention import (
    _SCALE_SUFFIX,
    PAGED_LEAVES,
    SLOT_STATE_LEAVES,
    WINDOW_PAGES,
    cache_batch_axis,
    cache_leaf_name,
    cache_node_at,
    cache_node_window,
    cache_windows,
    pool_scale_base,
    pool_scale_sibling,
    reset_cache,
    reset_cache_slot,
    seed_cache_prefix,
    with_pool,
)
from neuronx_distributed_tpu.observability.programs import per_instance

_LIVE_MANAGERS: "weakref.WeakSet" = weakref.WeakSet()


def check_all_live() -> int:
    """Run the page-leak/ref-count invariant over every live
    :class:`PagedCacheManager` (the serving suite's teardown fixture).
    Returns how many managers were checked; raises AssertionError on the
    first violated invariant."""
    n = 0
    for mgr in list(_LIVE_MANAGERS):
        mgr.check()
        n += 1
    return n


@dataclasses.dataclass
class StagedContext:
    """A prefilled context parked in the page pool with NO slot bound — the
    disaggregated prefill→decode handoff unit (ISSUE 14). ``page_ids`` hold
    the context's K/V page-aligned from the first page's column 0 (so a
    later ``map_staged`` can bind them at any page-aligned cursor — K/V
    content is position-relative, exactly why CoW prefix pages are
    remappable); the staging itself holds one pool reference per page
    until the handoff transfers it to a slot's block table (zero KV bytes
    move — ``PageAllocator.copy_bytes`` untouched) or ``release_staged``
    drops it."""

    page_ids: Tuple[int, ...]
    p: int        # real context tokens staged
    padded: int   # the prefill bucket the row was computed at


@dataclasses.dataclass
class ExportedContext:
    """Device-transfer form of a staged context for DISTINCT prefill and
    decode pools (different hosts/meshes): raw page blocks per pool k/v
    leaf (quantized pools export their scale sibling blocks too). Import
    is a REAL copy — ``PageAllocator.copy_bytes`` charges it, which is
    precisely how the shared-pool path proves it moved nothing."""

    items: list   # [(tree keys tuple, (..., n, page_size_or_1, ...) block)]
    n_pages: int
    p: int
    padded: int


class PageExhausted(RuntimeError):
    """The pool has fewer free pages than an allocation needs (after any
    reclaim callback ran dry). Admission accounting exists to make this
    unreachable on the conservative path; the eager path treats it as the
    page-pressure wall (preempt-and-rewind)."""


class CacheKindUnsupported(ValueError):
    """Asked of a cache KIND something it cannot give yet. A cache that has
    WINDOW layers (pages freed behind the window): a pinned or shared prefix
    lacks the freed pages, and so does a staged, exported, spilled or seeded
    context; a draft model, a quantized pool and ``tp > 1`` have no window
    kind. A cache with per-slot STATE beside its pages
    (``modules/attention.SLOT_STATE_LEAVES``): whatever holds a context by its
    pages alone has no state at the context's end, and a draft model, a
    quantized pool and ``tp > 1`` have no such kind either."""



# Pages a slot may hold on the window kind's table beyond ``window /
# page_size`` + the chunk's write window + one for the window's misalignment:
# room for the gap columns another slot's admission leaves inside a window
# (a jump of the shared cursor to a longer prompt's bucket strands at most a
# page at each of its ends). A slot that needs more meets the page-pressure
# wall, which preempts and rewinds.
WINDOW_SLACK_PAGES = 8


class PageAllocator:
    """Host-side owner of the physical page pool: free list + ref counts.

    A page is exactly one of: RESERVED (page 0, the null scatter target),
    FREE (on the free list, refcount absent), REFERENCED (mapped by >= 1
    block table and/or pinned by >= 1 prefix entry — the refcount is the
    sum), or QUARANTINED (poisoned, permanently out of circulation; a
    referenced page that gets quarantined leaves circulation when its last
    ref drops). ``copy_bytes`` counts KV bytes physically duplicated on
    prefix reuse — the zero-copy CoW contract is that it STAYS 0.

    Pages are dealt in RUNS where the pool has them (:meth:`deal`): the
    :data:`PAGE_RUN` pages of one aligned group of an owner's logical row come
    from one aligned group of the pool, ``p, p + 1, ...``, which the
    block-walking decode kernels fetch with one copy
    (``kernels/flash_decode._block_page_copies``). It is a preference among
    FREE pages and holds nothing back: a run's pages not yet asked for stay on
    the free list, remembered for their owner (``_dealt``) and passed over
    while any other page is free, so whatever number of pages is free can
    always be had."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is reserved), got {num_pages}"
            )
        self.num_pages = num_pages
        self._is_free = np.ones((num_pages,), np.bool_)
        self._is_free[0] = False
        self._n_free = num_pages - 1
        self._refs: Dict[int, int] = {}
        self._quarantined: set = set()
        # owner -> (the run of its logical row being dealt, that run's first pool page)
        self._dealt: Dict[object, Tuple[int, int]] = {}
        self.copy_bytes = 0  # CoW contract: never incremented by sharing

    @property
    def _free(self) -> List[int]:
        """The free pages, ascending."""
        return np.flatnonzero(self._is_free).tolist()

    @property
    def free_pages(self) -> int:
        return self._n_free

    @property
    def referenced_pages(self) -> int:
        return len(self._refs)

    @property
    def pages_quarantined(self) -> int:
        return len(self._quarantined)

    @property
    def capacity(self) -> int:
        """Usable pages: everything but the reserved null page and the
        quarantined set (referenced or free alike)."""
        return self.num_pages - 1 - len(self._quarantined)

    def refcount(self, pid: int) -> int:
        return self._refs.get(pid, 0)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages off the free list for nobody's row in particular
        (a staged or imported context: its pages ``0 .. n``), each born with
        refcount 1 (the caller's mapping). Raises :class:`PageExhausted` when
        short."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        return self.deal([(None, j) for j in range(n)])

    def deal(self, wanted: Sequence[Tuple[object, int]]) -> List[int]:
        """One page for each ``(owner, logical page)`` of ``wanted`` (an
        owner's pages ascending), all or none: :class:`PageExhausted` when
        fewer are free. An owner that asks for the first page of a logical run
        ``[PAGE_RUN r, PAGE_RUN (r + 1))`` is dealt the lowest aligned group of
        pool pages that is free whole, and the run's later pages, in this call
        or a later one, are that group's: ``p, p + 1, ...``. Every other page
        (a row that starts mid-run, no group free whole, a group's page taken
        meanwhile) is a single one: from a group already broken, else the
        lowest whole group is broken, else a page dealt to somebody's run."""
        n = len(wanted)
        if n > self._n_free:
            raise PageExhausted(
                f"need {n} pages, {self._n_free} free "
                f"(capacity {self.capacity})"
            )
        groups = self.num_pages // PAGE_RUN
        lists: Dict[str, List[int]] = {}    # found when first asked for; popped from the end

        def whole_groups():
            """Bool a group: free whole and dealt to nobody."""
            free = self._is_free[:groups * PAGE_RUN].reshape(groups, PAGE_RUN).all(axis=1)
            for _, first in self._dealt.values():
                free[first // PAGE_RUN] = False
            return free

        def whole() -> Optional[int]:
            """The first page of the lowest such group."""
            if "whole" not in lists:
                lists["whole"] = (np.flatnonzero(whole_groups())[::-1] * PAGE_RUN).tolist()
            while lists["whole"]:
                first = lists["whole"].pop()
                if self._is_free[first:first + PAGE_RUN].all():
                    return first
            return None

        def single() -> int:
            if "loose" not in lists:
                kept = whole_groups()
                for _, first in self._dealt.values():
                    kept[first // PAGE_RUN] = True
                loose = self._is_free.copy()
                loose[:groups * PAGE_RUN][np.repeat(kept, PAGE_RUN)] = False
                lists["loose"] = np.flatnonzero(loose)[::-1].tolist()
            while lists["loose"]:
                pid = lists["loose"].pop()
                if self._is_free[pid]:
                    return pid
            first = whole()
            if first is not None:
                lists["loose"] = [first + j for j in range(PAGE_RUN - 1, 0, -1)]
                return first
            return int(np.argmax(self._is_free))

        ids = []
        for i, (owner, page) in enumerate(wanted):
            run, off = divmod(page, PAGE_RUN)
            dealt = self._dealt.get(owner)
            if dealt is not None and dealt[0] != run:   # the owner has left that run
                del self._dealt[owner]
                dealt = None
            # nobody's row is not asked for again: a run only when this call takes it whole
            if dealt is None and off == 0 and (owner is not None or i + PAGE_RUN <= n):
                first = whole()
                if first is not None:
                    dealt = self._dealt[owner] = (run, first)
            pid = None
            if dealt is not None:
                if self._is_free[dealt[1] + off]:
                    pid = dealt[1] + off
                if pid is None or off == PAGE_RUN - 1:
                    del self._dealt[owner]
            if pid is None:
                pid = single()
            self._is_free[pid] = False
            self._n_free -= 1
            self._refs[pid] = 1
            ids.append(pid)
        self._dealt.pop(None, None)
        return ids

    def forget(self, owner) -> None:
        """``owner`` will not ask for the rest of the run it was being dealt."""
        self._dealt.pop(owner, None)

    def ref(self, pid: int) -> None:
        """One more holder of an already-live page (CoW share / prefix pin)."""
        if pid not in self._refs:
            raise ValueError(f"page {pid} is not live (cannot ref)")
        self._refs[pid] += 1

    def _to_free_list(self, pid: int) -> None:
        if pid not in self._quarantined:
            self._is_free[pid] = True
            self._n_free += 1

    def deref(self, pid: int) -> None:
        """Drop one holder; the last drop returns the page to the free list
        (or retires it for good if it was quarantined while referenced)."""
        c = self._refs.get(pid)
        if c is None:
            raise ValueError(f"page {pid} is not live (cannot deref)")
        if c > 1:
            self._refs[pid] = c - 1
            return
        del self._refs[pid]
        self._to_free_list(pid)

    def quarantine(self, pid: int) -> None:
        """Pull a page out of circulation permanently (poisoned content).
        A free page leaves the free list now; a referenced page keeps
        serving its current holders' BOOKKEEPING (they are being requeued
        by the caller) and retires when the last ref drops."""
        if pid <= 0 or pid >= self.num_pages:
            raise ValueError(f"page {pid} outside pool [1, {self.num_pages})")
        self._quarantined.add(pid)
        if self._is_free[pid]:
            self._is_free[pid] = False
            self._n_free -= 1

    def release_all(self) -> None:
        """Drop every reference (pool-loss recovery: all mappings and pins
        are void). Quarantined pages stay out of circulation."""
        for pid in list(self._refs):
            del self._refs[pid]
            self._to_free_list(pid)
        self._dealt.clear()


class PagedCacheManager:
    """Host-side owner of a paged cache collection + slot/block-table
    bookkeeping — the page-granular sibling of ``SlotCacheManager`` (same
    take/restore/recover/update_after_decode donation protocol, same shared
    write cursor semantics; the engine drives either through one code
    path). Device work is a handful of jitted programs: paged admission
    roll-in (scatter the prefill row's occupied pages through host-chosen
    ids), per-slot free / full reset (the row manager's exact programs —
    they touch only ``kv_valid``/``index`` leaves, which stay logical), and
    the non-donating seed-from-pages gather behind zero-copy prefix hits."""

    def __init__(self, num_slots: int, max_seq_len: int, page_size: int,
                 num_pages: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 window: Optional[int] = None, window_write_cols: int = 8):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_seq_len % page_size != 0:
            raise ValueError(
                f"max_seq_len ({max_seq_len}) must be a multiple of "
                f"page_size ({page_size})"
            )
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"unknown kv_quant {kv_quant!r} (expected 'int8' or None)"
            )
        # quantized pool (ISSUE 13): k/v pages stored int8 with per-page,
        # per-kv-head scale SIBLING leaves (k_scale/v_scale, dtype = the
        # compute dtype). The jitted transports (gather/scatter/admit/seed)
        # detect the siblings and de/re-quantize in-program; all HOST
        # accounting here (block tables, refs, pins, quarantine) is
        # layout-blind, so CoW sharing and the leak invariant are unchanged
        self.kv_quant = kv_quant
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len
        self.page_size = page_size
        self.pages_per_row = max_seq_len // page_size
        if num_pages is None:
            # default: the row manager's exact HBM (every slot a full row)
            # plus the reserved null page — paging is then a pure layout
            # change; smaller pools buy the packing win
            num_pages = num_slots * self.pages_per_row + 1
        self.alloc = PageAllocator(num_pages)
        # the WINDOW kind (a model with window layers; None: no such kind):
        # its own allocator, pool size and block table. ``window_write_cols``:
        # the columns a decode chunk can write (the engine's chunk size)
        self.window = window
        # whether the model's layers keep per-slot STATE beside their pages
        # (SLOT_STATE_LEAVES; ``allocate_from`` sees the leaves): copied into
        # the slot by an admission, refused wherever a context is held or
        # moved by its pages alone
        self.slot_state = False
        self.alloc_w: Optional[PageAllocator] = None
        self._tables_w = None
        if window is not None:
            if kv_quant is not None:
                raise CacheKindUnsupported(
                    "a cache with window layers has no quantized pool")
            if window < 1:
                raise ValueError(f"window must be >= 1, got {window}")
            per_slot = (
                -(-window // page_size) + 1
                + (max(window_write_cols, 1) - 1) // page_size + 2
                + WINDOW_SLACK_PAGES
            )
            self.window_pages_per_slot = min(-(-per_slot // 8) * 8, self.pages_per_row)
            self.alloc_w = PageAllocator(num_slots * self.window_pages_per_slot + 1)
            self._tables_w = np.zeros((num_slots, self.pages_per_row), np.int32)
        # a slot's admission cursor; the shared cursor's jumps over columns
        # nobody wrote (gap columns of every slot then decoding), ascending;
        # the lowest page each slot's window row may still map
        self._slot_target: List[Optional[int]] = [None] * num_slots
        self._gaps: List[Tuple[int, int]] = []
        self._w_lo = [0] * num_slots
        self.window_pages_freed_total = 0
        # ``<kind>_pages_mapped`` / ``<kind>_pages_in_runs`` of the tables as
        # last uploaded (the dispatch span's stats)
        self.page_stats: Dict[str, int] = {}
        self.cursor_jumps_total = 0   # jumps that left gap columns in a slot's context
        self.cache = None  # {"pages": bt, "pool": tree}; lazy like the row mgr
        self.cursor = 0
        self._free = list(range(num_slots))
        self._quarantined: set = set()  # slot indices (API compat; rare)
        self._tables = np.zeros((num_slots, self.pages_per_row), np.int32)
        self._slot_start: List[Optional[int]] = [None] * num_slots
        self._pins: Dict[int, int] = {}  # page -> prefix-entry pin count
        # engine-installed pressure valve: evict one unpinned prefix entry,
        # return whether anything was reclaimed
        self.reclaim: Optional[Callable[[], bool]] = None
        # TP serving (ISSUE 14): placement hook applied once at pool
        # allocation (kv-head-axis sharding over the engine's mesh)
        self.placement = None
        self.prefix_pages_shared_total = 0
        ps, n_log = page_size, self.pages_per_row

        def _paged_admit(paged, row, slot, shift, cursor, ids, lo_page,
                         ids_w=None):
            from neuronx_distributed_tpu.kernels.flash_decode import (
                paged_write_pages_leaf,
                quantize_page_block,
            )

            n_adm = ids.shape[0]
            pool_in = paged["pool"]

            def row_pages(path, base):
                """The admitted row's n_adm page blocks for pool leaf
                ``base`` (k or v) — shared by the page write and (on a
                quantized pool) the sibling scale write, which XLA CSEs
                inside the one jitted admit program."""
                row_leaf = cache_node_at(row, path[:-1])[base]
                r_ax = row_leaf.ndim - 4  # row batch axis
                col = r_ax + 1
                lead = row_leaf.shape[:r_ax]
                tail = row_leaf.shape[col + 1:]
                if row_leaf.shape[col] != n_log * ps:
                    # a SHORT row (the prefill bucket's columns, not the
                    # cache's: the engine's ``bucket_prefill_rows``): the
                    # window's columns are cut out of the row laid between
                    # zeros, and no whole-row copy exists
                    w = n_adm * ps
                    pad = [(0, 0)] * row_leaf.ndim
                    pad[col] = (w, w)
                    win = jax.lax.dynamic_slice_in_dim(
                        jnp.pad(row_leaf, pad), w + lo_page * ps - shift, w,
                        axis=col,
                    )
                    return win.reshape(lead + (n_adm, ps) + tail)
                rolled = jnp.roll(row_leaf, shift, axis=col)
                pg = rolled.reshape(lead + (1, n_log, ps) + tail)
                win = jax.lax.dynamic_slice_in_dim(
                    pg, lo_page, n_adm, axis=r_ax + 1
                )
                return win.reshape(lead + (n_adm, ps) + tail)

            def fn(path, pool_leaf):
                name = cache_leaf_name(path)
                base = pool_scale_base(name) or name
                if base in PAGED_LEAVES:
                    pages = row_pages(path, base)
                    if pool_scale_sibling(pool_in, path, base) is not None:
                        q, s = quantize_page_block(pages)
                        pages = q if base == name else s
                    # a window layer's leaf takes the pages of its own kind:
                    # those of the window's columns, the others the null page
                    windowed = ids_w is not None and cache_node_window(
                        cache_node_at(pool_in, path[:-1])) is not None
                    return paged_write_pages_leaf(
                        pool_leaf, pages, ids_w if windowed else ids)
                ax = cache_batch_axis(name, pool_leaf.ndim)
                if name == "kv_valid":
                    row_leaf = cache_node_at(row, path[:-1])[name]
                    short = n_log * ps - row_leaf.shape[ax + 1]
                    if short > 0:  # a short row: invalid past its end
                        pad = [(0, 0)] * row_leaf.ndim
                        pad[ax + 1] = (0, short)
                        row_leaf = jnp.pad(row_leaf, pad)
                    rolled = jnp.roll(row_leaf, shift, axis=ax + 1)
                    return jax.lax.dynamic_update_slice_in_dim(
                        pool_leaf, rolled, slot, axis=ax
                    )
                if name in SLOT_STATE_LEAVES:  # the row's, as it is: no column
                    return jax.lax.dynamic_update_slice_in_dim(
                        pool_leaf, cache_node_at(row, path[:-1])[name], slot, axis=ax
                    )
                return jnp.full_like(pool_leaf, cursor)

            pool = jax.tree_util.tree_map_with_path(fn, pool_in)
            return with_pool(paged, pool)

        def _seed_from_pages(pool, ids, m, start, length=max_seq_len):
            """Batch-1 row of ``length`` columns (static; the cache's, or
            a short row's: ``_paged_admit``) whose columns [start, start+m)
            hold the first
            ``m`` tokens of the shared pages ``ids`` — the zero-copy twin
            of ``seed_cache_prefix`` on a stored entry COPY. The pool is
            READ (never donated, never aliased into the result): the
            gather materializes compute-only views, no pool page moves.
            Quantized pools dequantize into the compute view here (the
            suffix prefill consumes a float row either way); the scale
            siblings never reach the row."""
            from neuronx_distributed_tpu.kernels.flash_decode import (
                paged_read_pages_leaf,
                paged_read_pages_leaf_dequant,
            )
            from neuronx_distributed_tpu.utils.tree import path_keys

            bucket = ids.shape[0] * ps
            items = []
            for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
                keys = tuple(path_keys(path))
                name = keys[-1]
                if pool_scale_base(name) is not None:
                    continue  # transport metadata — not part of a row
                if name in PAGED_LEAVES:
                    scale = pool_scale_sibling(pool, path, name)
                    block = (
                        paged_read_pages_leaf_dequant(leaf, scale, ids, ps)
                        if scale is not None
                        else paged_read_pages_leaf(leaf, ids)
                    )
                    leaf = jnp.expand_dims(block, leaf.ndim - 4)
                elif name == "kv_valid":
                    ax = cache_batch_axis(name, leaf.ndim)
                    valid = jnp.arange(bucket, dtype=jnp.int32)[None] < m
                    leaf = jnp.broadcast_to(
                        valid, leaf.shape[:ax] + (1, bucket)
                    )
                else:
                    leaf = jnp.full_like(leaf, m)
                items.append((keys, leaf))
            from neuronx_distributed_tpu.modules.attention import (
                _rebuild_tree,
            )

            return seed_cache_prefix(_rebuild_tree(items), m, start, length)

        def _stage_context(paged, row, shift, ids):
            """Disaggregated handoff, write half (ISSUE 14): scatter a
            prefill row's context pages into the pool at host-chosen ids —
            rolled so the context's first token sits at the first page's
            column 0 (position-relative K/V makes the block mappable at
            any aligned cursor later). kv_valid/index are untouched: slot
            binding is ``_map_slot_context``'s, at handoff time."""
            from neuronx_distributed_tpu.kernels.flash_decode import (
                paged_write_pages_leaf,
                quantize_page_block,
            )

            n_st = ids.shape[0]
            pool_in = paged["pool"]

            def fn(path, pool_leaf):
                name = cache_leaf_name(path)
                base = pool_scale_base(name) or name
                if base not in PAGED_LEAVES:
                    return pool_leaf
                row_leaf = cache_node_at(row, path[:-1])[base]
                r_ax = row_leaf.ndim - 4
                col = r_ax + 1
                rolled = jnp.roll(row_leaf, shift, axis=col)
                lead = row_leaf.shape[:r_ax]
                tail = row_leaf.shape[col + 1:]
                pg = rolled.reshape(lead + (1, n_log, ps) + tail)
                win = jax.lax.dynamic_slice_in_dim(
                    pg, 0, n_st, axis=r_ax + 1
                )
                pages = win.reshape(lead + (n_st, ps) + tail)
                if pool_scale_sibling(pool_in, path, base) is not None:
                    q, s = quantize_page_block(pages)
                    pages = q if base == name else s
                return paged_write_pages_leaf(pool_leaf, pages, ids)

            return {
                "pages": paged["pages"],
                "pool": jax.tree_util.tree_map_with_path(fn, pool_in),
            }

        def _map_slot_context(paged, slot, start, p, cursor):
            """Disaggregated handoff, bind half: the METADATA-only program
            — set the slot's kv_valid over its context columns and the
            shared cursor. No K/V byte moves; the block-table row (host
            side) is what carries the pages."""
            def fn(path, leaf):
                name = cache_leaf_name(path)
                base = pool_scale_base(name) or name
                if base in PAGED_LEAVES:
                    return leaf
                ax = cache_batch_axis(name, leaf.ndim)
                if name == "kv_valid":
                    length = leaf.shape[-1]
                    cols = jnp.arange(length, dtype=jnp.int32)
                    rowv = (cols >= start) & (cols < start + p)
                    rowv = jnp.broadcast_to(
                        rowv, leaf.shape[:ax] + (1, length)
                    )
                    return jax.lax.dynamic_update_slice_in_dim(
                        leaf, rowv, slot, axis=ax
                    )
                return jnp.full_like(leaf, cursor)

            return {
                "pages": paged["pages"],
                "pool": jax.tree_util.tree_map_with_path(
                    fn, paged["pool"]
                ),
            }

        def _import_blocks(paged, blocks, ids):
            """Distinct-pool handoff fallback: write exported page blocks
            (k/v and any scale siblings, already in pool storage form)
            into this pool at ``ids`` — the explicit device transfer the
            shared-pool path never pays."""
            from neuronx_distributed_tpu.kernels.flash_decode import (
                paged_write_pages_leaf,
            )

            def fn(path, pool_leaf):
                name = cache_leaf_name(path)
                base = pool_scale_base(name) or name
                if base not in PAGED_LEAVES:
                    return pool_leaf
                block = cache_node_at(blocks, path[:-1])[name]
                return paged_write_pages_leaf(pool_leaf, block, ids)

            return {
                "pages": paged["pages"],
                "pool": jax.tree_util.tree_map_with_path(
                    fn, paged["pool"]
                ),
            }

        # _paged_admit/_seed_from_pages are per-manager closures already;
        # the module-level reset helpers need per_instance for the same
        # pjit-cache-per-function-object reason as SlotCacheManager
        self._admit_fn = jax.jit(_paged_admit, donate_argnums=(0,))
        self._seed_fn = jax.jit(_seed_from_pages, static_argnums=(4,))
        self._free_fn = jax.jit(per_instance(reset_cache_slot), donate_argnums=(0,))
        self._reset_fn = jax.jit(per_instance(reset_cache), donate_argnums=(0,))
        self._stage_fn = jax.jit(_stage_context, donate_argnums=(0,))
        self._map_fn = jax.jit(_map_slot_context, donate_argnums=(0,))
        self._import_fn = jax.jit(_import_blocks, donate_argnums=(0,))
        # page -> outstanding staged-context holds (disaggregated handoff);
        # counted into the leak invariant like pins
        self._staged: Dict[int, int] = {}
        # tiered KV (ISSUE 19): pages written back by a host-tier prefetch
        # for a QUEUED request — pinned (the pin IS their reference; check()
        # reconciles them there) but additionally marked so the admission
        # fit math and the reclaim valve treat them as claimed, not
        # reclaimable: evicting a page the very next admission round is
        # about to map would be pure churn. Holds clear when the entry is
        # consumed or evicted (engine-side) and are VOID on pool recovery
        self._prefetch_hold: set = set()
        _LIVE_MANAGERS.add(self)

    def register_programs(self, programs, prefix: str = "") -> None:
        """Wrap the manager's jitted programs in a
        :class:`~neuronx_distributed_tpu.observability.programs.
        ProgramLedger` (ISSUE 12); proxies forward ``_cache_size()`` so
        ``seed_compilations`` keeps reading through."""
        self._admit_fn = programs.wrap(f"{prefix}paged_admit", self._admit_fn)
        self._seed_fn = programs.wrap(f"{prefix}paged_seed", self._seed_fn)
        self._free_fn = programs.wrap(f"{prefix}paged_free", self._free_fn)
        self._reset_fn = programs.wrap(f"{prefix}paged_reset", self._reset_fn)
        self._stage_fn = programs.wrap(f"{prefix}paged_stage", self._stage_fn)
        self._map_fn = programs.wrap(f"{prefix}paged_map", self._map_fn)
        self._import_fn = programs.wrap(
            f"{prefix}paged_import", self._import_fn
        )

    # --- HBM accounting ----------------------------------------------------

    @property
    def nbytes(self) -> int:
        """Bytes of the live pool + block tables (leaf metadata — no sync;
        0 before first allocation or while a donating consumer holds it)."""
        from neuronx_distributed_tpu.observability.hbm import tree_nbytes

        return tree_nbytes(self.cache) if self.cache is not None else 0

    @property
    def page_nbytes(self) -> int:
        """Bytes one pool page occupies across the k/v leaves — the HBM
        ledger's ``plan()`` unit for paged capacity questions."""
        if self.cache is None:
            return 0
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            self.cache["pool"]
        )[0]:
            name = cache_leaf_name(path)
            # pool k/v leaves are (..., P, page_size, Hkv, D), their
            # quantized scale siblings (..., P, 1, Hkv, 1) — the page axis
            # sits 4 from the end either way (leading axes are nn.scan
            # layer stacking); scales are real per-page HBM, so plan()
            # capacity math must charge them
            if name in PAGED_LEAVES or pool_scale_base(name) is not None:
                if cache_node_window(
                    cache_node_at(self.cache["pool"], path[:-1])
                ) is not None:
                    continue  # the window kind's pool is not the planner's unit
                pages_ax = max(int(leaf.shape[leaf.ndim - 4]), 1)
                total += int(leaf.nbytes) // pages_ax
        return total

    # --- slot accounting (SlotCacheManager surface) -------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def used_slots(self) -> int:
        return self.num_slots - len(self._free) - len(self._quarantined)

    @property
    def usable_slots(self) -> int:
        return self.num_slots - len(self._quarantined)

    @property
    def quarantined_slots(self) -> list:
        return sorted(self._quarantined)

    @property
    def pages_quarantined(self) -> int:
        return self.alloc.pages_quarantined

    @property
    def degraded(self) -> bool:
        """Capacity permanently shrunk (poisoned pages retired)."""
        return self.alloc.pages_quarantined > 0

    @property
    def pages_mapped(self) -> int:
        return int((self._tables != 0).sum())

    def _refuse_held_by_pages(self, what: str) -> None:
        """Refuse ``what``, which holds or moves a context by its pages alone,
        for a kind whose contexts are not their pages."""
        if self.window is not None:
            raise CacheKindUnsupported(
                f"{what} is not available for a cache with window layers: a "
                "window layer's pages behind the window are freed, so a "
                "context cannot be pinned, shared, staged, exported, spilled "
                "or seeded from its pages"
            )
        if self.slot_state:
            raise CacheKindUnsupported(
                f"{what} is not available for a cache with per-slot state: "
                "the state at a context's end is in no page, so a context "
                "cannot be pinned, shared, staged, exported, spilled or "
                "seeded from its pages"
            )

    @property
    def seed_compilations(self) -> int:
        """Distinct zero-copy seed programs compiled (one per shared page
        count) — bounded by ``pages_per_row``, never by hit traffic."""
        return int(self._seed_fn._cache_size())

    def acquire(self) -> int:
        return self._free.pop(0)

    def quarantine(self, slot: int) -> None:
        """Slot-poison entry point (the engine's poisoned-readback path):
        page-granular — the slot's EXCLUSIVELY-owned pages are retired from
        the pool (their content is suspect), shared/pinned pages predate
        this slot and survive, and the slot index itself stays in rotation
        (the caller's ``free`` returns it). Capacity degrades by the pages
        lost, not a permanent row."""
        row = self._tables[slot]
        for pid in {int(p) for p in row[row != 0]}:
            if self.alloc.refcount(pid) == 1 and self._pins.get(pid, 0) == 0:
                self.alloc.quarantine(pid)

    def quarantine_page(self, pid: int) -> List[int]:
        """Retire one poisoned page from the pool; returns the slots whose
        block tables currently map it (the caller requeues exactly those —
        the page-granular fault domain)."""
        self.alloc.quarantine(pid)
        return [
            s for s in range(self.num_slots)
            if (self._tables[s] == pid).any()
        ]

    # --- page math ----------------------------------------------------------

    def aligned_target(self, base: int, p: int) -> int:
        """Smallest cursor >= ``base`` placing a p-token context's first
        token on a page boundary (``(target - p) % page_size == 0``) — the
        alignment every paged admission enforces so whole context pages are
        shareable. Costs < page_size gap columns, invisible to the math. A
        cache with window layers shares no page, so its admissions are not
        aligned: every gap column of a slot then decoding lies in its window
        for ``window`` steps."""
        if self.window is not None:
            return base
        return base + (-(base - p)) % self.page_size

    def page_span(self, lo_col: int, hi_col: int) -> int:
        """Pages overlapped by columns [lo_col, hi_col)."""
        hi_col = min(hi_col, self.max_seq_len)
        if hi_col <= lo_col:
            return 0
        return -(-hi_col // self.page_size) - lo_col // self.page_size

    def active_spans(self) -> List[int]:
        """Start column of every slot currently holding a context (the
        admission projection's per-slot page-span inputs)."""
        return [s for s in self._slot_start if s is not None]

    def reclaimable_pages(self) -> int:
        """Pages an eviction (or spill-to-host) could actually free RIGHT
        NOW: pinned by prefix entries, mapped by no slot, un-quarantined —
        and not held by an in-flight prefetch (those are the opposite of
        reclaimable: a queued request is about to map them). Feeds both
        the eager-admission budget and the router's reclaimable-via-spill
        capacity term."""
        return sum(
            1 for pid, pins in self._pins.items()
            if pins > 0
            and self.alloc.refcount(pid) == pins
            and pid not in self.alloc._quarantined
            and pid not in self._prefetch_hold
        )

    def available_pages(self) -> int:
        """Free pages plus what evicting every unpinned-by-flight prefix
        entry could reclaim (pages pinned by entries and mapped by no
        slot) — the eager-admission page budget. In-flight prefetch holds
        are excluded on BOTH sides: held pages are neither free nor
        reclaimable, so the fit math counts them as claimed (ISSUE 19)."""
        return self.alloc.free_pages + self.reclaimable_pages()

    def _alloc_pages(self, n: int, slot: Optional[int] = None, at: int = 0) -> List[int]:
        """``n`` pages of the full kind, for pages ``[at, at + n)`` of
        ``slot``'s row (``None``: a context no slot holds yet)."""
        return self._deal_pages([(slot, at + j) for j in range(n)])

    def _deal_pages(self, wanted: Sequence[Tuple[Optional[int], int]]) -> List[int]:
        """:meth:`PageAllocator.deal` of the full kind, after reclaiming
        prefix entries while it is short."""
        while self.alloc.free_pages < len(wanted) and self.reclaim is not None:
            if not self.reclaim():
                break
        return self.alloc.deal(wanted)

    # --- prefix pins (CoW) --------------------------------------------------

    def pin_pages(self, ids: Sequence[int]) -> None:
        """A prefix entry takes a reference on each page (insert-on-miss:
        the slot's own context pages become shared storage, zero copies)."""
        self._refuse_held_by_pages("pinning a prefix's pages")
        for pid in ids:
            self.alloc.ref(int(pid))
            self._pins[int(pid)] = self._pins.get(int(pid), 0) + 1

    def unpin_pages(self, ids: Sequence[int]) -> None:
        for pid in ids:
            pid = int(pid)
            pins = self._pins.get(pid, 0)
            if pins <= 1:
                self._pins.pop(pid, None)
            else:
                self._pins[pid] = pins - 1
            self.alloc.deref(pid)

    def hold_prefetched(self, ids: Sequence[int]) -> None:
        """Mark freshly prefetched (already pinned) pages as claimed by a
        queued request — excluded from the reclaimable sum until released
        (consumption or entry eviction)."""
        self._prefetch_hold.update(int(pid) for pid in ids)

    def release_prefetched(self, ids: Sequence[int]) -> None:
        """Drop prefetch holds (no-op for pages that carry none)."""
        self._prefetch_hold.difference_update(int(pid) for pid in ids)

    def prefetch_held(self, ids: Sequence[int]) -> bool:
        """Whether ANY of ``ids`` is claimed by an in-flight prefetch —
        the reclaim valve skips entries whose pages are (evict-then-refetch
        churn would waste the transfer the prefetch just paid for)."""
        return any(int(pid) in self._prefetch_hold for pid in ids)

    def pages_live(self, ids: Sequence[int]) -> bool:
        """Reuse-time validation for a paged prefix entry: every page still
        allocated, pinned, and un-quarantined (host accounting only — the
        content never left the pool, so there is nothing to checksum)."""
        return all(
            self.alloc.refcount(int(pid)) > 0
            and self._pins.get(int(pid), 0) > 0
            and int(pid) not in self.alloc._quarantined
            for pid in ids
        )

    def slot_context_pages(self, slot: int, n: int) -> List[int]:
        """The first ``n`` context pages of a slot (insert-on-miss pins
        exactly these)."""
        start = self._slot_start[slot]
        if start is None:
            raise ValueError(f"slot {slot} holds no context")
        lo = start // self.page_size
        ids = [int(p) for p in self._tables[slot, lo:lo + n]]
        if any(p == 0 for p in ids):
            raise ValueError(
                f"slot {slot} pages {ids} include unmapped entries"
            )
        return ids

    def slot_pages(self, slot: int) -> List[int]:
        row = self._tables[slot]
        return [int(p) for p in row[row != 0]]

    # --- device-state transitions -------------------------------------------

    @staticmethod
    def _count_pages(kind: str, tables) -> Dict[str, int]:
        """``<kind>_pages_mapped`` and ``<kind>_pages_in_runs`` of a block
        table: the entries that map a page, and those of them whose whole
        aligned group of ``PAGE_RUN`` entries maps adjacent pool pages (what
        the block-walking kernels fetch with one copy)."""
        groups = tables[:, :tables.shape[1] // PAGE_RUN * PAGE_RUN].reshape(tables.shape[0], -1, PAGE_RUN)
        first = groups[:, :, 0]
        runs = first != 0
        for j in range(1, PAGE_RUN):
            runs &= groups[:, :, j] == first + j
        return {f"{kind}_pages_mapped": int(np.count_nonzero(tables)),
                f"{kind}_pages_in_runs": PAGE_RUN * int(np.count_nonzero(runs))}

    def _upload_tables(self) -> None:
        if self.cache is not None:
            self.page_stats.update(self._count_pages("full", self._tables))
            pages = jnp.asarray(self._tables)
            if self.placement is not None:
                # keep the uploaded table committed-replicated like the
                # allocation-time one — a layout flip between chunks would
                # recompile the decode program (decode_compilations pin)
                pages = self.placement({"pages": pages})["pages"]
            self.cache = dict(self.cache, pages=pages)
            self._upload_window_table()

    def _upload_window_table(self) -> None:
        if self.cache is not None and self._tables_w is not None:
            self.page_stats.update(self._count_pages("window", self._tables_w))
            self.cache = {**self.cache, WINDOW_PAGES: jnp.asarray(self._tables_w)}

    # --- the window kind ------------------------------------------------------

    def _window_floor(self, slot: int) -> int:
        """The lowest column the query slot ``slot`` writes at the cursor may
        attend: its window counted in TOKENS. A slot's context is adjacent
        columns up to its admission cursor, then every column but the shared
        cursor's jumps (``_gaps``: columns nobody wrote)."""
        start, target = self._slot_start[slot], self._slot_target[slot]
        left, pos = self.window - 1, self.cursor   # tokens wanted below the cursor
        for a, b in reversed(self._gaps):
            if a < target or b > pos:
                continue
            if pos - b >= left:
                break
            left -= pos - b
            pos = a
        return max(pos - left, start)

    def _free_behind_window(self) -> int:
        """Give back every window-kind page that lies wholly below its slot's
        window (its last column under :meth:`_window_floor`): the entry reads
        the null page from now on. Returns the pages freed."""
        if self.window is None:
            return 0
        freed = 0
        for slot, start in enumerate(self._slot_start):
            if start is None:
                continue
            hi = self._window_floor(slot) // self.page_size
            lo = self._w_lo[slot]
            if hi <= lo:
                continue
            row = self._tables_w[slot, lo:hi]
            for pid in row[row != 0]:
                self.alloc_w.deref(int(pid))
                freed += 1
            row[:] = 0
            self._w_lo[slot] = hi
        if freed:
            self.window_pages_freed_total += freed
            self._upload_window_table()
        return freed

    def _note_cursor_jump(self, old: int, new: int, but: int) -> None:
        """The shared cursor moved from ``old`` to ``new`` with nothing
        written between: gap columns of every slot holding a context (``but``
        the one being admitted)."""
        if self.window is not None and new > old and any(
            st is not None and s != but for s, st in enumerate(self._slot_start)
        ):
            self._gaps.append((old, new))
            self.cursor_jumps_total += 1

    def allocate_from(self, row_cache) -> None:
        """Build the page pool + block table from a batch-1 prefill row's
        structure — zeros everywhere; happens exactly once (lazily). With
        ``kv_quant`` the k/v pool leaves are int8 and each gains a
        per-page, per-kv-head scale SIBLING (``k_scale``/``v_scale``,
        dtype = the row's compute dtype — the transport dequantizes into
        it), so HBM holds ~1-byte KV: ~2x (bf16) / ~4x (fp32) pages at a
        fixed budget on top of paging's packing."""
        from neuronx_distributed_tpu.modules.attention import _rebuild_tree
        from neuronx_distributed_tpu.utils.tree import path_keys

        ps = self.page_size
        windows = cache_windows(row_cache)
        if set(windows.values()) != ({self.window} if self.window is not None else set()):
            raise ValueError(
                f"the model's cache has window layers {sorted(set(windows.values()))} "
                f"and the manager was built for window={self.window}: the "
                "engine reads it from model.config.kv_cache_window"
            )
        items = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(row_cache)[0]:
            keys = tuple(path_keys(path))
            name = keys[-1]
            ax = cache_batch_axis(name, leaf.ndim)
            # a window layer's leaves live in the window kind's pool
            num_pages = (self.alloc_w if keys[:-1] in windows else self.alloc).num_pages
            if name in PAGED_LEAVES:
                lead = leaf.shape[:ax]
                tail = leaf.shape[ax + 2:]  # (Hkv, D)
                if self.kv_quant is not None:
                    items.append(
                        (keys, jnp.zeros(lead + (num_pages, ps) + tail,
                                         jnp.int8))
                    )
                    items.append((
                        keys[:-1] + (name + _SCALE_SUFFIX,),
                        jnp.zeros(
                            lead + (num_pages, 1) + tail[:-1] + (1,),
                            leaf.dtype,
                        ),
                    ))
                else:
                    items.append(
                        (keys,
                         jnp.zeros(lead + (num_pages, ps) + tail, leaf.dtype))
                    )
            elif name == "kv_valid":
                lead = leaf.shape[:ax]
                items.append(
                    (keys, jnp.zeros(
                        lead + (self.num_slots, self.max_seq_len), jnp.bool_
                    ))
                )
            elif name in SLOT_STATE_LEAVES:
                if self.kv_quant is not None:
                    raise CacheKindUnsupported(
                        "a cache with per-slot state has no quantized pool")
                self.slot_state = True
                items.append(
                    (keys, jnp.zeros(
                        leaf.shape[:ax] + (self.num_slots,) + leaf.shape[ax + 1:],
                        leaf.dtype,
                    ))
                )
            else:
                items.append((keys, jnp.zeros_like(leaf)))
        self.cache = {
            "pages": jnp.asarray(self._tables),
            "pool": _rebuild_tree(items),
        }
        if self.placement is not None:
            self.cache = self.placement(self.cache)
        self._upload_window_table()

    def allocate_like(self, other: "PagedCacheManager") -> None:
        """Build this pool from ANOTHER manager's allocated pool structure
        (own ``num_pages``/``num_slots`` geometry) — the distinct-pool
        disaggregation path's decode-side bootstrap, where the decode
        engine may never have run a prefill of its own."""
        self._refuse_held_by_pages("a pool built from another manager's")
        if other.cache is None:
            raise RuntimeError("source manager has no allocated pool")
        if self.cache is not None:
            return

        def fn(path, leaf):
            name = cache_leaf_name(path)
            base = pool_scale_base(name) or name
            if base in PAGED_LEAVES:
                pax = leaf.ndim - 4
                shape = list(leaf.shape)
                shape[pax] = self.alloc.num_pages
                return jnp.zeros(tuple(shape), leaf.dtype)
            if name == "kv_valid":
                ax = cache_batch_axis(name, leaf.ndim)
                shape = list(leaf.shape)
                shape[ax] = self.num_slots
                return jnp.zeros(tuple(shape), jnp.bool_)
            return jnp.zeros(leaf.shape, leaf.dtype)

        self.cache = {
            "pages": jnp.asarray(self._tables),
            "pool": jax.tree_util.tree_map_with_path(
                fn, other.cache["pool"]
            ),
        }
        if self.placement is not None:
            self.cache = self.placement(self.cache)

    def admit(self, row_cache, slot: int, padded_len: int,
              cursor: Optional[int] = None, p: Optional[int] = None,
              shared_ids: Sequence[int] = (), m_shared: int = 0) -> None:
        """Roll a prefill row into ``slot`` at page granularity: map
        ``shared_ids`` (a CoW prefix hit's pages, ref-counted up — zero KV
        bytes move) over the first ``m_shared`` context columns, allocate
        own pages for the rest, scatter ONLY those own pages out of the
        rolled row, and set the shared cursor to ``cursor``. ``p`` is the
        real context length (default ``padded_len``); the context start
        ``cursor - p`` must be page-aligned (``aligned_target``)."""
        p = padded_len if p is None else p
        ps, n_log = self.page_size, self.pages_per_row
        if self.cache is None:
            if self.cursor > 0:
                raise RuntimeError(
                    "cache collection missing mid-flight (cursor "
                    f"{self.cursor}): a take() was never paired with "
                    "update_after_decode/restore"
                )
            self.allocate_from(row_cache)
        target = (
            self.aligned_target(max(self.cursor, padded_len), p)
            if cursor is None else cursor
        )
        if target < padded_len:
            raise ValueError(
                f"cursor {target} < padded prefill length {padded_len}: the "
                "prompt's last token cannot land left of its own start"
            )
        start = target - p
        if start % ps != 0 and self.window is None:
            raise ValueError(
                f"context start {start} not page-aligned (page_size {ps}) — "
                "use aligned_target for the cursor"
            )
        if m_shared % ps != 0 or m_shared > p:
            raise ValueError(
                f"m_shared ({m_shared}) must be a page multiple <= p ({p})"
            )
        n_sh = m_shared // ps
        if len(shared_ids) < n_sh:
            raise ValueError(
                f"{n_sh} shared pages needed, got {len(shared_ids)}"
            )
        if (self._tables[slot] != 0).any():
            raise ValueError(f"slot {slot} still maps pages (not freed?)")
        if m_shared:
            self._refuse_held_by_pages("mapping a shared prefix's pages")
        own_lo = (start + m_shared) // ps
        n_own = self.page_span(start + m_shared, target)
        # the window kind: the pages of the columns the next query can attend
        w_lo = max(start, target - self.window + 1) // ps if self.window is not None else 0
        own_w = (
            self.alloc_w.deal([(slot, w_lo + j) for j in range(self.page_span(w_lo * ps, target))])
            if self.window is not None else []
        )
        try:
            own = self._alloc_pages(n_own, slot, own_lo)
        except PageExhausted:
            for pid in own_w:
                self.alloc_w.deref(pid)
            if self.window is not None:
                self.alloc_w.forget(slot)
            raise
        s0 = start // ps
        for j in range(n_sh):
            pid = int(shared_ids[j])
            self.alloc.ref(pid)
            self._tables[slot, s0 + j] = pid
        self.prefix_pages_shared_total += n_sh
        for j, pid in enumerate(own):
            self._tables[slot, own_lo + j] = pid
        self._slot_start[slot] = start
        self._slot_target[slot] = target
        # device roll-in: one compiled program per (row bucket, n_adm)
        n_adm = min(padded_len // ps + 1, n_log)
        lo_c = min(own_lo, n_log - n_adm)
        ids_arr = np.zeros((n_adm,), np.int32)
        for j, pid in enumerate(own):
            ids_arr[own_lo - lo_c + j] = pid
        extra = ()
        if self.window is not None:
            ids_w = np.zeros((n_adm,), np.int32)
            for j, pid in enumerate(own_w):
                self._tables_w[slot, w_lo + j] = pid
                ids_w[w_lo - lo_c + j] = pid
            self._w_lo[slot] = w_lo
            extra = (jnp.asarray(ids_w),)
        self.cache = self._admit_fn(
            self.cache, row_cache,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(target - padded_len, jnp.int32),
            jnp.asarray(target, jnp.int32),
            jnp.asarray(ids_arr),
            jnp.asarray(lo_c, jnp.int32),
            *extra,
        )
        self._note_cursor_jump(self.cursor, target, but=slot)
        self.cursor = target
        self._upload_tables()
        # the jump may have carried the other slots' windows past pages
        self._free_behind_window()

    def seed_row(self, page_ids: Sequence[int], m: int, start: int,
                 length: Optional[int] = None):
        """Batch-1 row whose columns [start, start+m) read the shared pages
        — the zero-copy prefix hit's suffix-prefill substrate. Pool pages
        are gathered for COMPUTE only (nothing allocated, nothing written;
        ``PageAllocator.copy_bytes`` untouched). ``length``: the row's
        columns where it is shorter than the cache's (``admit`` takes it)."""
        self._refuse_held_by_pages("a row seeded from shared pages")
        if self.cache is None:
            raise RuntimeError("no cache allocated yet (nothing to seed from)")
        return self._seed_fn(
            self.cache["pool"],
            jnp.asarray(np.asarray(page_ids, np.int32)),
            jnp.asarray(m, jnp.int32),
            jnp.asarray(start, jnp.int32),
            *(() if length is None else (int(length),)),
        )

    # --- disaggregated prefill/decode handoff (ISSUE 14) --------------------

    def stage_context(self, row_cache, p: int, padded: int) -> StagedContext:
        """Park a prefill row's context in the pool with no slot bound:
        allocate ``ceil(p / page_size)`` pages, scatter the row's context
        K/V into them page-aligned from column 0, and hold one reference
        per page until a handoff maps them (``map_staged``) or the caller
        releases them. This is the prefill worker's half of the
        disaggregated handoff — the decode side then binds the pages by
        block-table mapping alone."""
        self._refuse_held_by_pages("staging a context without a slot")
        if self.cache is None:
            if self.cursor > 0:
                raise RuntimeError(
                    "cache collection missing mid-flight (cursor "
                    f"{self.cursor}): a take() was never paired with "
                    "update_after_decode/restore"
                )
            self.allocate_from(row_cache)
            # the first row is what shows a per-slot state
            self._refuse_held_by_pages("staging a context without a slot")
        if p < 1 or p > padded:
            raise ValueError(f"bad staged context length p={p} (padded "
                             f"{padded})")
        n = -(-p // self.page_size)
        ids = self._alloc_pages(n)
        for pid in ids:
            self._staged[pid] = self._staged.get(pid, 0) + 1
        self.cache = self._stage_fn(
            self.cache, row_cache,
            jnp.asarray(p - padded, jnp.int32),  # context start -> column 0
            jnp.asarray(np.asarray(ids, np.int32)),
        )
        return StagedContext(tuple(int(i) for i in ids), p, padded)

    def staged_live(self, staged: StagedContext) -> bool:
        """Whether a staged context's pages are all still held and
        un-quarantined — a salvaged-recovery or page-poison event between
        stage and handoff voids it (the caller re-prefills)."""
        return bool(staged.page_ids) and all(
            self._staged.get(int(pid), 0) > 0
            and int(pid) not in self.alloc._quarantined
            for pid in staged.page_ids
        )

    def map_staged(self, slot: int, staged: StagedContext,
                   cursor: int) -> None:
        """Bind a staged context to ``slot`` at ``cursor`` as a PAGE-TABLE
        operation: the staging holds transfer to the slot's block-table
        mappings (no refcount motion, no K/V byte moves —
        ``PageAllocator.copy_bytes`` provably untouched) and one small
        jitted program sets the slot's kv_valid/cursor metadata. The
        context start ``cursor - p`` must be page-aligned."""
        if not self.staged_live(staged):
            raise ValueError(
                "staged context is no longer live (pool recovery or page "
                "quarantine voided it) — re-prefill"
            )
        p = staged.p
        start = cursor - p
        if start < 0 or start % self.page_size != 0:
            raise ValueError(
                f"handoff cursor {cursor} puts the context start at "
                f"{start} — not page-aligned (page_size {self.page_size})"
            )
        if (self._tables[slot] != 0).any():
            raise ValueError(f"slot {slot} still maps pages (not freed?)")
        s0 = start // self.page_size
        for j, pid in enumerate(staged.page_ids):
            pid = int(pid)
            # ref TRANSFER: the staging hold becomes the table mapping
            holds = self._staged.get(pid, 0)
            if holds <= 1:
                self._staged.pop(pid, None)
            else:
                self._staged[pid] = holds - 1
            self._tables[slot, s0 + j] = pid
        self._slot_start[slot] = start
        self.cache = self._map_fn(
            self.cache,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(start, jnp.int32),
            jnp.asarray(p, jnp.int32),
            jnp.asarray(cursor, jnp.int32),
        )
        self.cursor = cursor
        self._upload_tables()
        staged.page_ids = ()

    def release_staged(self, staged: StagedContext) -> None:
        """Drop an unconsumed staged context (handoff abandoned): the
        staging holds release and unshared pages flow back to the free
        list. VOID-safe: pages whose staged hold is already gone (pool
        recovery cleared ``_staged`` and dropped every hold) are skipped —
        a deref there would raise inside the caller's fallback path, or
        worse steal a reference from a page since re-allocated to another
        request."""
        for pid in staged.page_ids:
            pid = int(pid)
            holds = self._staged.get(pid, 0)
            if holds <= 0:
                continue  # voided by recovery: nothing left to release
            if holds == 1:
                self._staged.pop(pid, None)
            else:
                self._staged[pid] = holds - 1
            self.alloc.deref(pid)
        staged.page_ids = ()

    def export_pages(self, staged: StagedContext) -> ExportedContext:
        """Read a staged context's raw page blocks out of the pool (k/v
        and any quantized scale siblings, in pool storage form) for a
        DISTINCT decode pool to import — the device-transfer fallback when
        prefill and decode do not share a pool."""
        if not self.staged_live(staged):
            raise ValueError("staged context is no longer live")
        from neuronx_distributed_tpu.utils.tree import path_keys

        ids = jnp.asarray(np.asarray(staged.page_ids, np.int32))
        items = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            self.cache["pool"]
        )[0]:
            keys = tuple(path_keys(path))
            base = pool_scale_base(keys[-1]) or keys[-1]
            if base in PAGED_LEAVES:
                pax = leaf.ndim - 4
                items.append((keys, jnp.take(leaf, ids, axis=pax)))
        return ExportedContext(
            items=items, n_pages=len(staged.page_ids),
            p=staged.p, padded=staged.padded,
        )

    def import_pages(self, exported: ExportedContext) -> StagedContext:
        """Write exported page blocks into THIS pool as a fresh staged
        context — a REAL device transfer, charged to
        ``PageAllocator.copy_bytes`` (the accounting that proves the
        shared-pool handoff moved nothing)."""
        self._refuse_held_by_pages("importing an exported context")
        if self.cache is None:
            raise RuntimeError(
                "import_pages needs an allocated pool — serve one "
                "admission first (or share the prefill worker's pool)"
            )
        from neuronx_distributed_tpu.modules.attention import _rebuild_tree

        ids = self._alloc_pages(exported.n_pages)
        for pid in ids:
            self._staged[pid] = self._staged.get(pid, 0) + 1
        blocks = _rebuild_tree(exported.items)
        self.cache = self._import_fn(
            self.cache, blocks, jnp.asarray(np.asarray(ids, np.int32))
        )
        self.alloc.copy_bytes += sum(
            int(block.nbytes) for _, block in exported.items
        )
        return StagedContext(
            tuple(int(i) for i in ids), exported.p, exported.padded
        )

    def spill_pages(self, ids: Sequence[int]):
        """Tiered KV, device->host half (ISSUE 19): pull the pinned prefix
        pages ``ids`` out of the pool as raw storage blocks — k/v pages
        plus any quantized scale siblings, exactly ``export_pages``'s
        layout — for the :class:`~neuronx_distributed_tpu.serving.tiering.
        HostPageStore`. One batched gather per pool leaf, then ONE explicit
        device->host pull of the whole batch. Returns ``(items, nbytes)``
        with host-numpy blocks. Runs only on the reclaim valve (a page-
        pressure event, never a steady chunk), so the pinned per-chunk
        budgets are untouched."""
        self._refuse_held_by_pages("spilling pages to the host tier")
        if self.cache is None:
            raise RuntimeError("spill needs an allocated pool")
        from neuronx_distributed_tpu.utils.tree import path_keys

        dev_ids = jnp.asarray(np.asarray(ids, np.int32))
        items = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            self.cache["pool"]
        )[0]:
            keys = tuple(path_keys(path))
            base = pool_scale_base(keys[-1]) or keys[-1]
            if base in PAGED_LEAVES:
                pax = leaf.ndim - 4
                items.append((keys, jnp.take(leaf, dev_ids, axis=pax)))
        # the spill's ONE sync: every leaf's gathered block rides a single
        # batched pull (the gathers above dispatched async)
        # graftlint: ok[GL02] tiered spill (ISSUE 19): one batched
        # device->host pull per reclaim event — off the steady chunk path,
        # the documented spill transfer
        host_blocks = jax.device_get([block for _, block in items])
        out = [
            (keys, np.asarray(block))
            for (keys, _), block in zip(items, host_blocks)
        ]
        return out, sum(int(b.nbytes) for _, b in out)

    def prefetch_pages(self, items, n_pages: int) -> List[int]:
        """Tiered KV, host->device half (ISSUE 19): write host-tier page
        blocks back into the pool at freshly allocated ids and adopt each
        page's born reference AS a prefix pin — the re-homed entry is then
        indistinguishable from one whose pages never left the device
        (``pages_live``/``unpin_pages``/``check()`` all reconcile
        unchanged). The write rides the existing jitted import program:
        host->device dispatch only, NO sync — it overlaps whatever decode
        chunk is in flight, which is the whole point. NOT charged to
        ``copy_bytes``: that meter proves device-side CoW sharing moved
        nothing; tier traffic has its own accounting."""
        if self.cache is None:
            raise RuntimeError(
                "prefetch needs an allocated pool — serve one admission "
                "first (a host-tier hit before any pool exists would have "
                "nothing to write into)"
            )
        from neuronx_distributed_tpu.modules.attention import _rebuild_tree

        ids = self._alloc_pages(n_pages)
        blocks = _rebuild_tree(list(items))
        self.cache = self._import_fn(
            self.cache, blocks, jnp.asarray(np.asarray(ids, np.int32))
        )
        for pid in ids:
            # alloc born the page at refcount 1; that reference IS the pin
            self._pins[int(pid)] = self._pins.get(int(pid), 0) + 1
        return [int(pid) for pid in ids]

    def ensure_decode_window(self, active_slots, width: int) -> bool:
        """Map real pages under every active slot's next write window
        (columns ``[cursor, cursor + width)``) before a chunk dispatch.
        Returns False when the pool cannot cover it even after reclaiming
        prefix entries — the page-pressure wall (the engine preempts and
        rewinds, exactly like the cursor wall)."""
        if self.cache is None or len(active_slots) == 0:
            return True
        ps, n_log = self.page_size, self.pages_per_row
        lo = self.cursor // ps
        hi = min(n_log, -(-(self.cursor + width) // ps))
        def unmapped(tables):
            return [
                (int(s), j)
                for s in active_slots
                for j in range(lo, hi)
                if tables[int(s), j] == 0
            ]

        need = unmapped(self._tables)
        need_w = unmapped(self._tables_w) if self.window is not None else []
        if not need and not need_w:
            return True
        ids_w: List[int] = []
        try:
            if need_w:
                ids_w = self.alloc_w.deal(need_w)
            ids = self._deal_pages(need)
        except PageExhausted:
            for pid in ids_w:   # either kind short is the wall: take nothing
                self.alloc_w.deref(pid)
            return False
        for (s, j), pid in zip(need, ids):
            self._tables[s, j] = pid
        for (s, j), pid in zip(need_w, ids_w):
            self._tables_w[s, j] = pid
        self._upload_tables()
        return True

    def free(self, slot: int) -> None:
        """Clear the slot's validity row, deref every page it maps (shared
        pages survive through their other holders/pins; exclusive pages
        return to the free list immediately), and return the slot to the
        rotation."""
        if self.cache is not None:
            self.cache = self._free_fn(self.cache, jnp.asarray(slot, jnp.int32))
        self._drop_slot_mappings(slot)
        self._upload_tables()
        if slot not in self._quarantined and slot not in self._free:
            self._free.append(slot)
            self._free.sort()

    def take(self):
        cache, self.cache = self.cache, None
        return cache

    def restore(self, cache) -> None:
        self.cache = cache

    def _drop_slot_mappings(self, slot: int) -> None:
        """Deref every page the slot maps, on both kinds' tables."""
        for tables, alloc in self._kinds():
            row = tables[slot]
            for pid in row[row != 0]:
                alloc.deref(int(pid))
            tables[slot] = 0
            alloc.forget(slot)
        self._slot_start[slot] = None
        self._slot_target[slot] = None
        self._w_lo[slot] = 0
        if not any(st is not None for st in self._slot_start):
            self._gaps.clear()   # nobody is left whose window they lie in

    def _kinds(self):
        """``(block table, allocator)`` of each kind this manager has."""
        kinds = [(self._tables, self.alloc)]
        if self.window is not None:
            kinds.append((self._tables_w, self.alloc_w))
        return kinds

    def _release_all_mappings(self) -> None:
        for slot in range(self.num_slots):
            self._drop_slot_mappings(slot)

    def recover(self, cache) -> bool:
        """Post-failed-dispatch salvage (the SlotCacheManager contract):
        every slot has been vacated, so all block-table mappings are
        dropped either way; surviving storage is invalidated in place,
        consumed storage falls to lazy reallocation. Prefix pins are the
        ENGINE's to resolve: on pool loss it clears the store, whose
        eviction hook releases every pin (the pinned content is gone)."""
        consumed = cache is None or any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in jax.tree_util.tree_leaves(cache)
        )
        self.cursor = 0
        self._release_all_mappings()
        # staged handoff contexts are VOID either way: their holder (the
        # disaggregation server) observes staged_live() False and
        # re-prefills — recovery must not leave holds that block the pool
        for pid, holds in list(self._staged.items()):
            for _ in range(holds):
                self.alloc.deref(pid)
        self._staged.clear()
        # prefetch holds are VOID too: on pool loss the engine clears the
        # prefix store, whose eviction hook releases the pins themselves —
        # a surviving hold would permanently shrink the reclaimable sum
        self._prefetch_hold.clear()
        if consumed:
            self.cache = None
            return False
        self.cache = self._reset_fn(cache)
        self._upload_tables()
        return True

    def release_all_slots(self) -> None:
        self._free = [
            s for s in range(self.num_slots) if s not in self._quarantined
        ]

    def update_after_decode(self, new_cache, steps: int = 1) -> None:
        self.cache = new_cache
        self.advance(steps)

    def advance(self, steps: int) -> None:
        """The cursor moves by the ``steps`` columns of a chunk whose output
        the manager already holds (the engine gives a chunk's output back at
        its call; its columns follow when read back, or PROJECTED before the
        next chunk's call with the difference, <= 0, settled at the readback:
        serving/engine.py, "Decode hot path")."""
        self.cursor += steps
        self._free_behind_window()

    def reset(self) -> None:
        """Rewind the cursor, invalidate every slot's context, and release
        every block-table mapping (drain / preemption — pages flow back to
        the free list unless a prefix pin or another holder keeps them;
        pinned page CONTENT is untouched, so entries stay servable)."""
        self.cursor = 0
        self._release_all_mappings()
        if self.cache is not None:
            self.cache = self._reset_fn(self.cache)
            self._upload_tables()

    # --- invariants ---------------------------------------------------------

    def check(self) -> None:
        """The page-leak/ref-count invariant: every page is exactly one of
        free / table-mapped / prefix-pinned / quarantined / reserved, ref
        counts reconcile with the mappers + pins, no slot double-maps a
        page, and the free list is duplicate-free. AssertionError with the
        offending page on any violation."""
        self._check_kind(self.alloc, self._tables, self._pins, self._staged)
        if self.window is not None:
            # the window kind: mapped by its own table alone (nothing pins or
            # stages a window page), a slot never past its share + the slack
            self._check_kind(self.alloc_w, self._tables_w, {}, {})
            for s in range(self.num_slots):
                held = int((self._tables_w[s] != 0).sum())
                assert held <= self.window_pages_per_slot, (
                    f"slot {s} maps {held} window pages, over "
                    f"{self.window_pages_per_slot}"
                )
                assert not self._tables_w[s, :self._w_lo[s]].any(), (
                    f"slot {s} maps a window page below its freed edge"
                )
        # tiered KV (ISSUE 19): a prefetch hold is an overlay on a PINNED
        # page, never a reference of its own — a hold on an unpinned page
        # means the release path lost track of a claimed prefetch
        for pid in self._prefetch_hold:
            assert self._pins.get(pid, 0) > 0, (
                f"page {pid} carries a prefetch hold but no prefix pin"
            )

    def _check_kind(self, a, tables, pins, staged) -> None:
        free = set(a._free)
        assert len(free) == len(a._free), "free list has duplicates"
        assert 0 not in free and 0 not in a._refs and 0 not in pins, (
            "reserved null page 0 entered circulation"
        )
        mapped: Dict[int, int] = {}
        for s in range(self.num_slots):
            row = [int(p) for p in tables[s] if p != 0]
            assert len(row) == len(set(row)), (
                f"slot {s} double-maps a page: {row}"
            )
            for pid in row:
                mapped[pid] = mapped.get(pid, 0) + 1
        for pid in range(1, a.num_pages):
            expect = (
                mapped.get(pid, 0) + pins.get(pid, 0)
                + staged.get(pid, 0)
            )
            have = a.refcount(pid)
            assert have == expect, (
                f"page {pid}: refcount {have} != mapped({mapped.get(pid, 0)})"
                f" + pinned({pins.get(pid, 0)})"
                f" + staged({staged.get(pid, 0)})"
            )
            states = [
                pid in free,
                expect > 0,
                pid in a._quarantined and expect == 0,
            ]
            assert sum(states) == 1, (
                f"page {pid} is not exactly one of free/referenced/"
                f"quarantined: free={pid in free} refs={have} "
                f"quarantined={pid in a._quarantined}"
            )
