"""Slot-level KV-cache management for the serving engine.

The engine owns ONE cache collection shaped ``(num_slots, max_seq_len)``
(per layer), allocated once and never reallocated between requests. Batch
rows are request SLOTS; the column layout is the shared-cursor scheme the
repo's KV cache already speaks for left-padded batches:

* ``index`` is a SINGLE write cursor shared by every slot (the KVCache
  contract). All active slots write their decode K/V at the same column.
* A newly admitted request's prompt is placed so its LAST token sits at
  column ``cursor - 1`` — exactly the left-padded layout, produced by
  rolling the batch-1 prefill cache row right by ``cursor - P`` (P = the
  padded prefill length whose index the row carries).
* Columns a slot does not cover are ``kv_valid=False``; per-row attention
  masking and RoPE positions already run off validity counts
  (``valid_count_below``), so gap columns and cursor jumps are invisible to
  the math. Raising the cursor past a slot's last write merely leaves
  invalid gap columns behind — which is how a LONG prompt can be admitted
  next to slots that joined earlier.
* Freeing a slot clears its ``kv_valid`` row (``reset_cache_slot``);
  draining the engine rewinds the cursor (``reset_cache``). Storage is
  reused in place.

The cursor therefore advances monotonically while any slot is active; the
engine preempts-and-rewinds when it would run off ``max_seq_len`` (see
``ServingEngine``).

This module also owns :class:`PrefixCache` — the host-managed, ref-counted,
LRU-evicted store of prompt-prefix KV blocks behind the engine's
prefix-reuse admission path (lookup → longest-match reuse → suffix prefill
→ insert-on-miss). Entries are compact COPIES extracted from prefill rows
(``modules/attention.extract_cache_prefix``), so they are immune to the
donation regime above: a donated decode consuming the big cache, a
quarantined slot, or a dropped-for-reallocation recovery never touches a
stored prefix.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_tpu.modules.attention import (
    cache_batch_axis,
    cache_length_axis,
    cache_leaf_name,
    reset_cache,
    reset_cache_slot,
)
from neuronx_distributed_tpu.observability.programs import per_instance


def _admit_row(big, row, slot, padded_len, cursor):
    """Merge a batch-1 prefill cache ``row`` into ``big`` at batch index
    ``slot``, rolled so the prompt's last token lands at column
    ``cursor - 1``. ``index`` leaves are set to ``cursor`` (the shared
    cursor may jump forward to fit a long prompt; gap columns stay
    invalid for every row)."""
    shift = cursor - padded_len

    def fn(path, b_leaf, r_leaf):
        name = cache_leaf_name(path)
        ax = cache_batch_axis(name, b_leaf.ndim)
        if ax is None:  # shared write cursor
            return jnp.full_like(b_leaf, cursor)
        # k/v (..., B, L, Hkv, D) and kv_valid (..., B, L): the cache-length
        # axis sits right after the batch axis in both layouts; a per-slot
        # state leaf (..., B, W) has none and is copied as it is
        col = cache_length_axis(name, b_leaf.ndim)
        r = r_leaf if col is None else jnp.roll(r_leaf, shift, axis=col)
        return jax.lax.dynamic_update_slice_in_dim(b_leaf, r, slot, axis=ax)

    return jax.tree_util.tree_map_with_path(fn, big, row)


class SlotCacheManager:
    """Host-side owner of the engine's cache collection + slot free list.

    All device work is three jitted programs compiled once each:
    admission roll-in, per-slot free, and full reset. Each DONATES the big
    cache pytree — a slot event updates the ``(num_slots, max_seq_len)``
    storage in place instead of materializing a copy, matching the decode
    step's donation regime (the manager's reference is replaced by the
    result, so the consumed buffer is never touched again)."""

    def __init__(self, num_slots: int):
        self.num_slots = num_slots
        self.cache = None  # allocated lazily from the first prefill row
        self.cursor = 0  # host mirror of the shared `index` cursor
        self._free = list(range(num_slots))
        self._quarantined: set = set()  # slots pulled from rotation for good
        # per_instance: a module-level helper jitted directly would share
        # its pjit cache (and _cache_size) across managers in this jax —
        # fresh function objects keep compile accounting per-manager
        self._admit_fn = jax.jit(per_instance(_admit_row), donate_argnums=(0,))
        self._free_fn = jax.jit(per_instance(reset_cache_slot), donate_argnums=(0,))
        self._reset_fn = jax.jit(per_instance(reset_cache), donate_argnums=(0,))
        # TP serving (ISSUE 14): optional placement hook applied once at
        # allocation — the engine installs the partitioner's kv-head-axis
        # placement so the donated programs inherit a committed layout
        self.placement = None

    def register_programs(self, programs, prefix: str = "") -> None:
        """Wrap the manager's jitted programs in a
        :class:`~neuronx_distributed_tpu.observability.programs.
        ProgramLedger` (ISSUE 12). Called by the engine after construction;
        the proxies forward ``_cache_size()`` so nothing else changes."""
        self._admit_fn = programs.wrap(f"{prefix}cache_admit", self._admit_fn)
        self._free_fn = programs.wrap(f"{prefix}cache_free", self._free_fn)
        self._reset_fn = programs.wrap(f"{prefix}cache_reset", self._reset_fn)

    # --- HBM accounting ----------------------------------------------------

    @property
    def nbytes(self) -> int:
        """Bytes of the live cache collection (leaf metadata — no sync;
        0 before first allocation or while a donating consumer holds it)."""
        from neuronx_distributed_tpu.observability.hbm import tree_nbytes

        return tree_nbytes(self.cache) if self.cache is not None else 0

    @property
    def slot_nbytes(self) -> int:
        """Approximate bytes one slot row occupies (the HBM ledger's
        ``plan()`` unit for row-mode capacity questions)."""
        return self.nbytes // self.num_slots if self.num_slots else 0

    # --- slot accounting ---------------------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def used_slots(self) -> int:
        return self.num_slots - len(self._free) - len(self._quarantined)

    @property
    def usable_slots(self) -> int:
        """Slots still in the rotation (not quarantined)."""
        return self.num_slots - len(self._quarantined)

    @property
    def quarantined_slots(self) -> list:
        return sorted(self._quarantined)

    def acquire(self) -> int:
        return self._free.pop(0)

    def quarantine(self, slot: int) -> None:
        """Pull ``slot`` out of the rotation permanently (poisoned readback
        — its cache row is suspect and must never host another request).
        The caller owns clearing the engine-side slot bookkeeping."""
        self._quarantined.add(slot)
        if slot in self._free:
            self._free.remove(slot)

    # --- device-state transitions ------------------------------------------

    def allocate_from(self, row_cache) -> None:
        """Build the (num_slots, …) cache collection from a batch-1 prefill
        row's structure — zeros everywhere; happens exactly once."""

        def fn(path, r_leaf):
            name = cache_leaf_name(path)
            ax = cache_batch_axis(name, r_leaf.ndim)
            if ax is None:
                return jnp.zeros_like(r_leaf)
            shape = list(r_leaf.shape)
            shape[ax] = self.num_slots
            return jnp.zeros(tuple(shape), r_leaf.dtype)

        self.cache = jax.tree_util.tree_map_with_path(fn, row_cache)
        if self.placement is not None:
            self.cache = self.placement(self.cache)

    def admit(self, row_cache, slot: int, padded_len: int,
              cursor: Optional[int] = None) -> None:
        """Roll a prefill row into ``slot``. ``cursor`` (default: keep, but
        never below ``padded_len``) becomes the new shared write cursor."""
        if self.cache is None:
            if self.cursor > 0:
                # a first-ever allocation always starts at cursor 0; a
                # missing cache with an advanced cursor means a donating
                # consumer lost it mid-flight (take() never paired with
                # update_after_decode/restore) — reallocating zeros would
                # silently corrupt every running slot's context
                raise RuntimeError(
                    "cache collection missing mid-flight (cursor "
                    f"{self.cursor}): a take() was never paired with "
                    "update_after_decode/restore"
                )
            self.allocate_from(row_cache)
        target = max(self.cursor, padded_len) if cursor is None else cursor
        if target < padded_len:
            raise ValueError(
                f"cursor {target} < padded prefill length {padded_len}: the "
                "prompt's last token cannot land left of its own start"
            )
        self.cache = self._admit_fn(
            self.cache, row_cache,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(padded_len, jnp.int32),
            jnp.asarray(target, jnp.int32),
        )
        self.cursor = target

    def free(self, slot: int) -> None:
        """Clear the slot's ``kv_valid`` row and return it to the free list
        — immediately re-admittable, no reallocation. A quarantined slot is
        cleared but never rejoins the rotation."""
        if self.cache is not None:
            self.cache = self._free_fn(self.cache, jnp.asarray(slot, jnp.int32))
        if slot not in self._quarantined:
            self._free.append(slot)
            self._free.sort()

    def take(self):
        """Hand the cache to a donating consumer (the engine's decode
        chunk). The manager's reference is dropped so nothing can touch the
        donated buffers; the caller MUST give the successor back via
        :meth:`update_after_decode`, or :meth:`restore` the original if the
        dispatch raised."""
        cache, self.cache = self.cache, None
        return cache

    def restore(self, cache) -> None:
        """Re-adopt a cache whose donating dispatch FAILED (cursor
        untouched). If the failure happened after XLA consumed the buffers,
        the next device use raises jax's deleted-buffer error — loud, which
        is the point: without the restore, admission would silently
        reallocate a zeroed cache under still-active slots."""
        self.cache = cache

    def recover(self, cache) -> bool:
        """Re-adopt storage after a FAILED donating dispatch whose requests
        are being requeued (cursor rewinds to 0 either way). When the
        failure left the buffers unconsumed, keep the allocation and
        invalidate it in place (one device program); when XLA already
        consumed them, drop to lazy reallocation — the next admission
        rebuilds zeros, which is safe precisely because every slot has been
        vacated. Returns whether the storage survived."""
        consumed = any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in jax.tree_util.tree_leaves(cache)
        )
        self.cursor = 0
        if consumed:
            self.cache = None
            return False
        self.cache = cache
        if self.cache is not None:
            self.cache = self._reset_fn(self.cache)
        return True

    def release_all_slots(self) -> None:
        """Return every non-quarantined slot to the free list — HOST
        bookkeeping only, for callers about to :meth:`reset` (which
        invalidates all rows in one device program; per-slot :meth:`free`
        dispatches would be redundant)."""
        self._free = [
            s for s in range(self.num_slots) if s not in self._quarantined
        ]

    def update_after_decode(self, new_cache, steps: int = 1) -> None:
        """Adopt the cache returned by a decode dispatch; ``steps`` is how
        many write columns the fused chunk actually consumed (its on-device
        clamp stops the cursor early when every slot froze)."""
        self.cache = new_cache
        self.advance(steps)

    def advance(self, steps: int) -> None:
        """Move the cursor by the ``steps`` columns of a chunk whose output
        the manager already holds (``PagedCacheManager.advance``)."""
        self.cursor += steps

    def reset(self) -> None:
        """Rewind the cursor and invalidate every slot's context (engine
        drain / preemption). Slot ownership is the engine's to clear."""
        self.cursor = 0
        if self.cache is not None:
            self.cache = self._reset_fn(self.cache)


# --- host-managed prefix KV cache ---------------------------------------------


@dataclasses.dataclass
class PrefixEntry:
    """One stored prompt prefix: ``tokens`` (the full token path, length
    ``m``), its compact device KV block ``tree`` (``bucket`` columns, token
    0 at column 0 — a COPY made at insert time, never a view of any
    engine-owned or donated buffer), the integrity ``fingerprint`` +
    ``shapes`` recorded at insert (validated on every reuse), and ``refs``
    — the pin count that protects an entry backing an in-flight suffix
    prefill from eviction."""

    tokens: Tuple[int, ...]
    tree: Any
    bucket: int
    # device scalar (or host float in tests): kept un-synced at insert so
    # a miss admission never blocks on it; reuse-time validation floats it
    fingerprint: Any
    shapes: Tuple[Tuple[int, ...], ...]
    refs: int = 0
    # PAGED engines (serving/paging.py): the entry holds no KV copy at all
    # (``tree`` is None) — just the ref-counted pool page ids its tokens
    # live in, mapped copy-on-write into a hitting slot's block table
    page_ids: Optional[Tuple[int, ...]] = None
    # integrity sentinel (ISSUE 20): per-page content fingerprints of
    # ``page_ids`` recorded at insert (device uint32 vector, bucketed —
    # positions past ``len(page_ids)`` are padding). Reuse recomputes the
    # used prefix and compares bit-exactly, closing the content gap that
    # host-side ``pages_live`` accounting cannot see (an HBM bit flip
    # leaves allocation/pins/quarantine perfectly healthy)
    page_fp: Any = None
    # tiered KV (ISSUE 19): a SPILLED entry's pages live in the engine's
    # HostPageStore under these ids instead (``page_ids`` is None while
    # host-resident). The entry STAYS in the trie so lookups keep matching
    # it; a prefetch re-homes it device-side (host_ids -> page_ids) before
    # any slot maps it. Exactly one of page_ids/host_ids is set for a
    # paged entry that still holds content
    host_ids: Optional[Tuple[int, ...]] = None
    # which tier the entry's NEXT hit is attributed to: "host" right after
    # a prefetch (the hit only exists because the host tier kept the
    # pages), reset to "device" once that hit is recorded
    hit_tier: str = "device"

    @property
    def m(self) -> int:
        return len(self.tokens)


class _TrieNode:
    __slots__ = ("children", "entry")

    def __init__(self):
        self.children: Dict[int, "_TrieNode"] = {}
        self.entry: Optional[PrefixEntry] = None


class PrefixCache:
    """Host-managed, ref-counted, LRU-evicted store of prompt-prefix KV
    blocks, keyed by a token trie.

    The serving engine consults it at admission: the longest stored prefix
    of the incoming context (capped at ``context - 1`` — at least one token
    must run so the admission has next-token logits to sample from) is
    copied into the slot's row and only the uncached tail is prefilled.
    A stored entry's first ``d`` columns serve ANY context sharing its
    first ``d`` tokens, so a single long entry covers every shorter shared
    prefix — lookup walks the trie to the deepest reachable node and uses
    any entry beneath it.

    Matches shorter than ``min_match`` tokens are misses (a tiny reuse
    does not pay for the extra programs), and contexts shorter than
    ``min_match`` are never stored. ``max_entries=0`` disables the cache
    entirely — the engine then runs today's exact full-prefill path.

    Eviction is LRU over entries (hits, covers and inserts refresh
    recency) and NEVER frees a pinned entry (``refs > 0`` — an in-flight
    suffix prefill holds one); if every entry is pinned the store
    temporarily overflows rather than corrupt an in-flight admission.
    The engine owns the counters (metrics) — this class just reports what
    each call did."""

    def __init__(self, max_entries: int = 32, min_match: int = 8):
        if min_match < 1:
            raise ValueError(f"min_match must be >= 1, got {min_match}")
        self.max_entries = max_entries
        self.min_match = min_match
        self._root = _TrieNode()
        self._lru: "OrderedDict[Tuple[int, ...], PrefixEntry]" = OrderedDict()
        # called with each entry as it leaves the store (LRU eviction,
        # forced eviction, clear) — the PAGED engine releases the entry's
        # pool page refs here so a dropped entry can never leak pages
        self.on_evict: Optional[Any] = None

    # --- introspection ------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.max_entries > 0

    def __len__(self) -> int:
        return len(self._lru)

    @property
    def entries(self):
        """Stored entries, least-recently-used first."""
        return list(self._lru.values())

    @property
    def tokens_stored(self) -> int:
        return sum(e.m for e in self._lru.values())

    @property
    def nbytes(self) -> int:
        """Bytes of stored entry KV copies (leaf metadata — no sync).
        Paged entries hold page ids, not copies, so they report 0 here;
        their bytes live in the page pool's accounting."""
        from neuronx_distributed_tpu.observability.hbm import tree_nbytes

        return sum(
            tree_nbytes(e.tree) for e in self._lru.values()
            if e.tree is not None
        )

    def _walk(self, tokens) -> Tuple[_TrieNode, int]:
        """Deepest trie node reachable along ``tokens`` and its depth.
        Every live node has at least one entry in its subtree (eviction
        prunes entry-less childless chains), so reaching depth ``d`` means
        some stored entry shares the first ``d`` tokens."""
        node, depth = self._root, 0
        for t in tokens:
            nxt = node.children.get(int(t))
            if nxt is None:
                break
            node, depth = nxt, depth + 1
        return node, depth

    @staticmethod
    def _subtree_entry(node: _TrieNode) -> Optional[PrefixEntry]:
        stack = [node]
        while stack:
            n = stack.pop()
            if n.entry is not None:
                return n.entry
            stack.extend(n.children.values())
        return None

    def match_len(self, tokens) -> int:
        """Read-only longest USABLE match length for ``tokens`` (0 when
        below ``min_match``) — the scheduler's effective-prefill-cost peek;
        no LRU state moves."""
        if not self.enabled:
            return 0
        _, depth = self._walk(tokens)
        use = min(depth, len(tokens) - 1)
        return use if use >= self.min_match else 0

    # --- lookup / insert ----------------------------------------------------

    def lookup(self, tokens) -> Optional[Tuple[PrefixEntry, int]]:
        """Longest-match lookup: ``(entry, m_use)`` where the entry's first
        ``m_use`` columns are the reusable prefix KV, or ``None`` on a miss
        (no match, or a match shorter than ``min_match``). Refreshes the
        matched entry's recency; the caller pins it (:meth:`pin`) for the
        duration of the suffix prefill."""
        if not self.enabled:
            return None
        node, depth = self._walk(tokens)
        m_use = min(depth, len(tokens) - 1)
        if m_use < self.min_match:
            return None
        entry = self._subtree_entry(node)
        if entry is None:  # unreachable for a live trie; be safe
            return None
        self._lru.move_to_end(entry.tokens)
        return entry, m_use

    def peek(self, tokens) -> Optional[Tuple[PrefixEntry, int]]:
        """:meth:`lookup` without the LRU refresh — the tiered-KV
        admission pre-pass (ISSUE 19) scans QUEUED requests for host-tier
        entries worth prefetching, and a scan must not reorder recency for
        requests that may never be admitted (the real lookup at admission
        time still refreshes)."""
        if not self.enabled:
            return None
        node, depth = self._walk(tokens)
        m_use = min(depth, len(tokens) - 1)
        if m_use < self.min_match:
            return None
        entry = self._subtree_entry(node)
        if entry is None:
            return None
        return entry, m_use

    def covers(self, tokens) -> bool:
        """Whether some stored entry already extends (or equals) ``tokens``
        — inserting them again would add nothing. Refreshes the covering
        entry so the hot prefix stays resident."""
        if not self.enabled:
            return False
        node, depth = self._walk(tokens)
        if depth < len(tokens):
            return False
        entry = self._subtree_entry(node)
        if entry is not None:
            self._lru.move_to_end(entry.tokens)
        return entry is not None

    def insert(self, tokens, tree, fingerprint,
               bucket: int) -> Tuple[Optional[PrefixEntry], int]:
        """Store a prefix block. Returns ``(entry, n_evicted)`` — entry is
        ``None`` when the insert was skipped (disabled, too short, or
        already covered). Evicts least-recently-used UNPINNED entries until
        the store fits ``max_entries``."""
        key = tuple(int(t) for t in tokens)
        if not self.enabled or len(key) < self.min_match:
            return None, 0
        if self.covers(key):
            return None, 0
        entry = PrefixEntry(
            tokens=key, tree=tree, bucket=bucket,
            fingerprint=fingerprint,
            shapes=tuple(
                tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree)
            ),
        )
        node = self._root
        for t in key:
            node = node.children.setdefault(t, _TrieNode())
        node.entry = entry
        self._lru[key] = entry
        evicted = 0
        while len(self._lru) > self.max_entries:
            victim = next(
                (e for e in self._lru.values() if e.refs == 0 and e is not entry),
                None,
            )
            if victim is None:  # everything pinned: overflow, never corrupt
                break
            self.evict_entry(victim)
            evicted += 1
        return entry, evicted

    # --- pins / eviction ----------------------------------------------------

    def pin(self, entry: PrefixEntry) -> None:
        entry.refs += 1

    def release(self, entry: PrefixEntry) -> None:
        entry.refs = max(0, entry.refs - 1)

    def release_all(self) -> None:
        """Drop every pin — the engine's recovery/halt paths call this so a
        failed in-flight suffix prefill can never leave a stale ref that
        blocks eviction forever (PR 3's recovery contract)."""
        for e in self._lru.values():
            e.refs = 0

    def evict_entry(self, entry: PrefixEntry) -> bool:
        """Remove one entry (LRU eviction, or forced — validation failure /
        poison), pruning the trie chain it leaves behind."""
        if self._lru.pop(entry.tokens, None) is None:
            return False
        if self.on_evict is not None:
            self.on_evict(entry)
        path = [self._root]
        for t in entry.tokens:
            nxt = path[-1].children.get(t)
            if nxt is None:
                return True  # trie already pruned past here
            path.append(nxt)
        path[-1].entry = None
        for depth in range(len(path) - 1, 0, -1):
            node = path[depth]
            if node.entry is None and not node.children:
                del path[depth - 1].children[entry.tokens[depth - 1]]
            else:
                break
        return True

    def clear(self) -> int:
        """Drop everything (the engine calls this on a weight swap — prefix
        KV computed under old params must never serve new-params traffic).
        Returns how many entries were dropped."""
        n = len(self._lru)
        if self.on_evict is not None:
            for e in self._lru.values():
                self.on_evict(e)
        self._root = _TrieNode()
        self._lru = OrderedDict()
        return n
