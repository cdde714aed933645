"""Host-side paged KV cache bookkeeping.

The device side is a flat page pool (models/llama.py); this allocator owns
which pages belong to which sequence. Free pages are a LIFO stack — O(1)
alloc/free, no fragmentation by construction (pages are fixed-size).

The occupancy numbers exported here are the load-balancing signal for the
endpoint picker (BASELINE.json north star: pick pods by KV-cache
occupancy), the role the reference's EPP plays via
``x-gateway-destination-endpoint`` (reference inferencepool.go:47).
"""

from __future__ import annotations

import hashlib
import logging
from dataclasses import dataclass, field

logger = logging.getLogger(__name__)


def page_chain_hashes(
    tokens: list[int], page_size: int, prev: bytes = b""
) -> list[bytes]:
    """Chained per-page content hashes over full prompt pages.

    key_i = H(key_{i-1} ‖ token ids of page i), so key_i identifies the
    ENTIRE token prefix through page i — the chain map is a radix tree
    flattened to one hash lookup per page-aligned depth (the vLLM
    automatic-prefix-caching construction). Shared between PrefixCache
    and the server's tokenizer pool, which computes the chain during
    encode so engine-side lookup costs no extra pass over the prompt.
    ``prev`` resumes the chain from an already-hashed prefix.
    """
    keys: list[bytes] = []
    for i in range(len(tokens) // page_size):
        chunk = tokens[i * page_size : (i + 1) * page_size]
        h = hashlib.blake2b(digest_size=16)
        h.update(prev)
        h.update(b",".join(str(t).encode() for t in chunk))
        prev = h.digest()
        keys.append(prev)
    return keys


class OutOfPagesError(Exception):
    """KV pool exhausted — request must wait in queue."""


@dataclass
class PageAllocator:
    num_pages: int
    page_size: int
    _free: list[int] = field(default_factory=list)
    _owned: dict[int, list[int]] = field(default_factory=dict)

    def __post_init__(self) -> None:
        self._free = list(range(self.num_pages - 1, -1, -1))

    # -- allocation -------------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.page_size))

    def can_allocate(self, n_tokens: int) -> bool:
        return len(self._free) >= self.pages_for(n_tokens)

    def allocate(self, seq_id: int, n_tokens: int) -> list[int]:
        need = self.pages_for(n_tokens)
        if len(self._free) < need:
            raise OutOfPagesError(
                f"need {need} pages, {len(self._free)} free"
            )
        pages = [self._free.pop() for _ in range(need)]
        self._owned.setdefault(seq_id, []).extend(pages)
        return pages

    def extend(self, seq_id: int, new_total_tokens: int) -> list[int]:
        """Grow a sequence to cover new_total_tokens; returns new pages."""
        owned = self._owned.get(seq_id, [])
        need = self.pages_for(new_total_tokens) - len(owned)
        if need <= 0:
            return []
        if len(self._free) < need:
            raise OutOfPagesError(
                f"extend needs {need} pages, {len(self._free)} free"
            )
        pages = [self._free.pop() for _ in range(need)]
        owned.extend(pages)
        self._owned[seq_id] = owned
        return pages

    def free(self, seq_id: int) -> None:
        for page in self._owned.pop(seq_id, []):
            self._free.append(page)

    def pages(self, seq_id: int) -> list[int]:
        return self._owned.get(seq_id, [])

    # -- telemetry (the picker signal) ------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.used_pages / self.num_pages if self.num_pages else 1.0


class RefcountedAllocator(PageAllocator):
    """PageAllocator with shared (refcounted) pages for prefix caching.

    Pages holding cached prompt prefixes are shared read-only between
    sequences. A page whose refcount drops to zero but whose content is
    still registered in the prefix cache parks in an LRU *evictable* pool:
    it can be revived by a later cache hit, or reclaimed (evicting the
    cache entry) when fresh allocations need pages.
    """

    def __post_init__(self) -> None:
        super().__post_init__()
        self._refs: dict[int, int] = {}
        # page id → cache key, insertion-ordered = LRU
        self._evictable: dict[int, object] = {}
        self._on_evict = None  # callback(cache_key)

    def set_evict_callback(self, cb) -> None:
        self._on_evict = cb

    @property
    def available_pages(self) -> int:
        return len(self._free) + len(self._evictable)

    def _pop_page(self) -> int:
        if self._free:
            return self._free.pop()
        if self._evictable:
            page, key = next(iter(self._evictable.items()))
            del self._evictable[page]
            if self._on_evict is not None:
                self._on_evict(key)
            return page
        raise OutOfPagesError("no free or evictable pages")

    def can_allocate(self, n_tokens: int) -> bool:
        return self.available_pages >= self.pages_for(n_tokens)

    @property
    def free_pages(self) -> int:
        # evictable pages are reclaimable on demand: report them as free so
        # the picker/telemetry don't see a phantom-full pool
        return self.available_pages

    def allocate(self, seq_id: int, n_tokens: int) -> list[int]:
        return self.allocate_extra(seq_id, self.pages_for(n_tokens))

    def allocate_extra(self, seq_id: int, n_pages: int) -> list[int]:
        """Allocate n fresh pages (suffix after shared-prefix adoption)."""
        if self.available_pages < n_pages:
            raise OutOfPagesError(
                f"need {n_pages} pages, {self.available_pages} available"
            )
        pages = [self._pop_page() for _ in range(n_pages)]
        for p in pages:
            self._refs[p] = self._refs.get(p, 0) + 1
        self._owned.setdefault(seq_id, []).extend(pages)
        return pages

    def adopt(self, seq_id: int, pages: list[int]) -> None:
        """Share existing (cached) pages with a new sequence."""
        for p in pages:
            self._refs[p] = self._refs.get(p, 0) + 1
            self._evictable.pop(p, None)  # back in active use
        self._owned.setdefault(seq_id, []).extend(pages)

    def free(self, seq_id: int) -> None:
        for page in self._owned.pop(seq_id, []):
            self._release_page(page)

    def _release_page(self, page: int) -> None:
        """Drop one reference; a last reference parks cache-registered
        pages in the LRU evictable pool (revivable by a later hit) and
        returns unregistered pages to the free stack."""
        refs = self._refs.get(page, 1) - 1
        if refs > 0:
            self._refs[page] = refs
            return
        self._refs.pop(page, None)
        key = self._cache_key_of(page)
        if key is not None:
            self._evictable[page] = key  # park, revivable
        else:
            self._free.append(page)

    def cow_page(self, seq_id: int, page: int) -> int:
        """Copy-on-write divergence: replace shared ``page`` in seq_id's
        chain with a fresh private page the sequence may write into
        (the caller copies the device-side K/V rows). The shared page
        keeps its cache registration; its refcount drops by one."""
        owned = self._owned.get(seq_id, [])
        idx = owned.index(page)  # ValueError = caller bug, fail loudly
        if self.available_pages < 1:
            raise OutOfPagesError("no free or evictable pages for CoW")
        fresh = self._pop_page()
        self._refs[fresh] = 1
        owned[idx] = fresh
        self._release_page(page)
        return fresh

    # -- migration export pins (ISSUE 8) -----------------------------------
    def begin_export(self, pages: list[int]) -> list[int]:
        """Pin ``pages`` for an in-flight migration export: each page's
        refcount is bumped so no free/evict/CoW path can hand the page
        out while its device→host copy (and the cross-replica transfer
        that follows) may still be reading it — the owning sequence can
        finish, cancel, or be cut mid-export without racing the wire.
        Returns the pin token to hand back to :meth:`end_export`."""
        for p in pages:
            self._refs[p] = self._refs.get(p, 0) + 1
            self._evictable.pop(p, None)  # pinned = not reclaimable
        return list(pages)

    def end_export(self, pin: list[int]) -> None:
        """Release an export pin: pages drop one reference and rejoin
        the normal lifecycle (registered pages park evictable, orphans
        return to the free stack)."""
        for p in pin:
            self._release_page(p)

    def truncate_to(self, seq_id: int, n_tokens: int) -> list[tuple]:
        """Un-write a sequence's tail from position ``n_tokens`` on:
        every owned page overlapping [n_tokens, ∞) must be PRIVATELY
        writable before decode/verify scatters land there — a shared or
        cache-registered page in that range would let (possibly
        rejected) draft K/V corrupt state other chains read. This is
        the speculative-path safety invariant, asserted directly at
        admission instead of the old repin-on-full-rebuild guard (the
        per-admission rebuild itself is gone).

        Healthy layouts satisfy the invariant by construction —
        generation writes land past the registered prompt pages, and
        full-prefix hits CoW their final page at adoption — so this
        normally returns []. A violating page is swapped for a fresh
        private one (its registration and other references survive on
        the original). Returns [(old_page, fresh_page, needs_copy)]:
        ``needs_copy`` is True when the page straddles the truncation
        offset — positions below ``n_tokens`` in it are live history
        the caller must clone device-side before anything writes."""
        owned = self._owned.get(seq_id, [])
        first = n_tokens // self.page_size
        swaps: list[tuple] = []
        for idx in range(first, len(owned)):
            page = owned[idx]
            shared = (self._refs.get(page, 1) > 1
                      or self._cache_key_of(page) is not None)
            if not shared:
                continue
            fresh = self._pop_page()
            self._refs[fresh] = 1
            owned[idx] = fresh
            self._release_page(page)
            swaps.append((
                page, fresh,
                idx == first and n_tokens % self.page_size != 0,
            ))
        return swaps

    # cache bookkeeping — maintained by PrefixCache
    def _cache_key_of(self, page: int):
        cache = getattr(self, "_prefix_cache", None)
        return cache.key_of_page(page) if cache is not None else None

    @property
    def used_pages(self) -> int:
        # evictable pages are reclaimable: count them as free capacity
        return self.num_pages - len(self._free) - len(self._evictable)

    @property
    def pinned_cached_pages(self) -> int:
        """Cache-registered pages currently referenced by live
        sequences — KV the prefix cache holds PINNED in HBM (the
        picker-visible ``prefix_pages_pinned`` / bytes-pinned signal;
        parked evictable pages are resident but reclaimable, not
        pinned)."""
        cache = getattr(self, "_prefix_cache", None)
        if cache is None:
            return 0
        return sum(1 for p in self._refs if cache.key_of_page(p)
                   is not None)


class StateSnapshots:
    """Which nodes of the page chain hold a SNAPSHOT of the per-slot
    state that goes with their pages, and in which row of the fixed
    device pool (models/cache.py ``CacheSpec.snapshot_rows``). Host
    bookkeeping only: the engine owns the pool and the two copy
    programs (tpuserve/engine.py).

    For a family with recurrent state a page hit without the state is
    no hit, so the prefix cache of such a family asks here how deep a
    cached chain can be RESUMED: at the deepest node that holds a
    snapshot (:meth:`longest`). A snapshot lives no longer than its
    chain's page (``PrefixCache._evicted`` drops it), the pool evicts
    least recently used first, and a snapshot held by an admission in
    flight (:meth:`hold`, from its restore until its prefill is done)
    is never the victim."""

    def __init__(self, n_rows: int) -> None:
        self.n_rows = n_rows
        #: chain key → pool row; insertion-ordered, oldest use first
        self._row_of: dict[bytes, int] = {}
        self._free = list(range(n_rows - 1, -1, -1))
        self._held: dict[bytes, int] = {}
        self.saved = 0
        self.restored = 0
        #: snapshots that made room for a newer one (monotonic; one
        #: dropped with its page is the page's eviction, not counted)
        self.evicted = 0

    def __len__(self) -> int:
        return len(self._row_of)

    def __contains__(self, key: bytes) -> bool:
        return key in self._row_of

    def longest(self, keys: list[bytes], depth: int) -> int:
        """The deepest ``d <= depth`` such that ``keys[d - 1]`` holds a
        snapshot: how many pages of a cached chain a hit can resume
        behind (0: none)."""
        for d in range(min(depth, len(keys)), 0, -1):
            if keys[d - 1] in self._row_of:
                return d
        return 0

    def restore_row(self, key: bytes) -> int:
        """The row to copy back into a slot; counts a use."""
        row = self._row_of.pop(key)
        self._row_of[key] = row  # most recently used
        self.restored += 1
        return row

    def claim(self, key: bytes) -> int | None:
        """The row a NEW snapshot under ``key`` is to be copied into —
        a free one, else the least recently used that no admission
        holds — or None: the key has one already (same chain, same
        state: it only counts as used), or every row is held."""
        row = self._row_of.pop(key, None)
        if row is not None:
            self._row_of[key] = row
            return None
        if self._free:
            row = self._free.pop()
        else:
            victim = next((k for k in self._row_of
                           if k not in self._held), None)
            if victim is None:
                return None
            row = self._row_of.pop(victim)
            self.evicted += 1
        self._row_of[key] = row
        self.saved += 1
        return row

    def drop(self, key: bytes) -> None:
        """``key``'s page is gone (evicted, or its prefill was cut):
        the snapshot goes with it."""
        row = self._row_of.pop(key, None)
        if row is not None:
            self._free.append(row)

    def hold(self, key: bytes) -> None:
        self._held[key] = self._held.get(key, 0) + 1

    def release(self, key: bytes) -> None:
        n = self._held.get(key, 0) - 1
        if n > 0:
            self._held[key] = n
        else:
            self._held.pop(key, None)


class PrefixCache:
    """Content-addressed map of full prompt pages → pool page ids.

    Keys are chain hashes: key_i = H(key_{i-1} ‖ tokens of page i), so a
    hit on page i implies the whole prefix matches (the vLLM automatic-
    prefix-caching construction, built independently for this engine).
    """

    def __init__(self, allocator: "RefcountedAllocator", page_size: int,
                 snapshots: StateSnapshots | None = None):
        self.allocator = allocator
        self.page_size = page_size
        #: a family with recurrent state: the chain nodes that hold a
        #: snapshot of it (None: pages are all there is to a prefix)
        self.snapshots = snapshots
        self._by_key: dict[bytes, int] = {}
        self._key_by_page: dict[int, bytes] = {}
        # chain key → the tokens that FOLLOWED that prefix last time it
        # was inserted (≤ one page) — the speculative continuation draft
        # source (tpuserve/speculation.py lookahead_drafts). Host memory
        # only, bounded by residency: evicted entries drop theirs.
        self._next_tokens: dict[bytes, list[int]] = {}
        #: entries reclaimed under pool pressure (monotonic counter)
        self.evictions = 0
        # KV memory hierarchy (ISSUE 11): optional spill sink called as
        # sink(chain_key, page_id) the moment a registered page is
        # reclaimed under pool pressure — BEFORE the registration drops,
        # while the page's device rows are still this chain's content.
        # The engine wires it to the device→host export + HostKVTier
        # put; eviction then demotes the chain instead of destroying it.
        # The sink runs synchronously inside the allocator's _pop_page,
        # so the page is never handed to its new owner until the spill
        # copy has resolved (the spilled-pinned invariant,
        # tests/test_kvcache_eviction.py).
        self.spill_sink = None
        allocator._prefix_cache = self
        allocator.set_evict_callback(self._evicted)

    def chain_keys(self, prompt: list[int]) -> list[bytes]:
        return page_chain_hashes(prompt, self.page_size)

    @property
    def resident_entries(self) -> int:
        """Prefixes (page-chain nodes) currently resident — pinned by
        live sequences or parked evictable."""
        return len(self._by_key)

    def probe(self, keys: list[bytes]) -> list[int]:
        """Pages of the longest cached prefix for pre-hashed chain keys.
        Probes are cheap and must be FRESH at adoption time (an earlier
        admission in the same pass may have inserted or evicted pages);
        the hashes themselves are content-derived and reusable."""
        pages: list[int] = []
        for key in keys:
            page = self._by_key.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def insert(self, keys: list[bytes], page_row: list[int],
               tokens: list[int] | None = None) -> None:
        """Register fully-written prompt pages (keys from lookup()).
        With ``tokens`` (the full prompt) also records, per chain key,
        up to one page of the tokens that followed that prefix — the
        speculative continuation draft source. Latest insertion wins:
        repeated chat traffic keeps the freshest next-turn guess."""
        for i, key in enumerate(keys):
            if i >= len(page_row):
                break
            existing = self._by_key.get(key)
            if existing is None:
                self._by_key[key] = page_row[i]
                self._key_by_page[page_row[i]] = key
        if tokens is not None:
            ps = self.page_size
            for i, key in enumerate(keys):
                nxt = tokens[(i + 1) * ps: (i + 2) * ps]
                # longest-wins, then latest-wins: a re-asked short
                # prompt's partial tail must not clobber the full-page
                # continuation a superseding (next-turn) prompt taught
                if nxt and len(nxt) >= len(self._next_tokens.get(key, ())):
                    self._next_tokens[key] = nxt

    def continuation(self, keys: list[bytes]) -> tuple[int, list[int]] | None:
        """Deepest chain key with a recorded continuation: returns
        (depth_pages, tokens), where ``tokens`` follow absolute
        position ``depth_pages * page_size``. None when no key of the
        chain has one. Only a draft HINT — verification rejects stale
        continuations, so no freshness guarantee is needed."""
        best: tuple[int, list[int]] | None = None
        for i, key in enumerate(keys):
            nxt = self._next_tokens.get(key)
            if nxt:
                best = (i + 1, nxt)
        return best

    def key_of_page(self, page: int):
        return self._key_by_page.get(page)

    def _evicted(self, key: bytes) -> None:
        page = self._by_key.pop(key, None)
        self._next_tokens.pop(key, None)
        if page is not None:
            if self.spill_sink is not None:
                try:
                    self.spill_sink(key, page)
                except Exception:  # noqa: BLE001 — a failed spill must
                    # degrade to a plain eviction, never kill admission
                    logger.exception("KV spill failed for page %d", page)
            self._key_by_page.pop(page, None)
            self.evictions += 1
            if self.snapshots is not None:
                self.snapshots.drop(key)
