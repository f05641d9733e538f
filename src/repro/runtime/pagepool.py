"""Host-side page allocator + shared-prefix cache for the paged KV cache.

The device holds the page POOLS (``k_pages``/``v_pages`` leaves, one
global pool per layer stack) and the (B, W) int32 ``page_table``; this
module owns the host bookkeeping that decides WHICH physical page a
lane's next logical block maps to:

* ``PagePool`` — free-list allocator over ``num_pages`` fixed-size
  pages with per-page refcounts.  Page 0 is the permanently reserved
  GARBAGE page: it is never handed out, and inactive lanes' zeroed
  table rows point at it so their (masked-out) decode writes land
  harmlessly instead of corrupting a reallocated page.

* Prefix cache — an LRU map from exact padded-prompt-token tuples (at
  page-aligned lengths, plus the full prompt length) to the page run
  holding that prefix's KV.  A hit lets admission map those pages
  read-only (refcount++) and prefill only the suffix; copy-on-write
  keeps cached entries pristine when a lane later writes into a shared
  page.

* ``LanePages`` — the one owner of every lane's pages: it decides
  WHICH page and returns the device page-table edits as data
  (``RowEdit``, ``EntryEdit``, ``CopyEdit``); the scheduler decides
  WHEN and applies them.

No jax imports — this is pure host Python/numpy.
"""
from __future__ import annotations

from collections import OrderedDict, namedtuple
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

GARBAGE_PAGE = 0


@dataclass
class PrefixEntry:
    """One cached prefix: ``tokens`` (the exact key), the pages holding
    its KV (the entry owns one reference per page), and its token
    ``length`` (may end mid-page — the last page is then only partially
    covered, and a lane extending past it must COW it)."""
    tokens: Tuple[int, ...]
    pages: Tuple[int, ...]
    length: int


class PagePool:
    """Refcounted free-list allocator over a fixed page pool.

    ``num_pages`` counts ALL pages including the reserved garbage page
    0, matching the device pool's leading axis.
    """

    def __init__(self, num_pages: int, page_size: int, metrics=None):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        if page_size < 1:
            raise ValueError(f"bad page_size {page_size}")
        self.num_pages = num_pages
        self.page_size = page_size
        # optional MetricsRegistry (duck-typed — still no jax here) for
        # the allocator's pressure events (pool.evictions, ...)
        self.metrics = metrics
        self.refcount = np.zeros((num_pages,), np.int32)
        self.refcount[GARBAGE_PAGE] = 1          # pinned forever
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        # LRU prefix cache: key -> PrefixEntry (key = (cut, tokens[:cut]))
        self._prefixes: "OrderedDict[tuple, PrefixEntry]" = OrderedDict()

    # -- allocation -------------------------------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.counter(name).inc(n)

    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` pages (refcount 1 each) or None if the free list
        is short — the caller decides whether to evict prefixes or
        defer admission."""
        if n > len(self._free):
            self._count("pool.alloc_failures")
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            assert self.refcount[p] == 0, (p, self.refcount[p])
            self.refcount[p] = 1
        return pages

    def ref(self, page: int) -> None:
        assert self.refcount[page] > 0, page
        self.refcount[page] += 1

    def free(self, page: int) -> None:
        """Drop one reference; the page returns to the free list when
        the count hits zero."""
        assert page != GARBAGE_PAGE, "freeing the garbage page"
        assert self.refcount[page] > 0, page
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self._free.append(page)

    # -- prefix cache -----------------------------------------------------

    @staticmethod
    def _key(tokens: Sequence[int], cut: int) -> tuple:
        return (cut, tuple(int(t) for t in tokens[:cut]))

    def prefix_lookup(self, tokens: Sequence[int]) -> Optional[PrefixEntry]:
        """Longest cached prefix of ``tokens``: the full length first,
        then page-aligned cuts descending.  A hit is moved to the LRU
        tail (most recent).  Every cut builds its own key tuple:
        ``pool.prefix_key_tokens`` counts the tokens keyed."""
        ps = self.page_size
        n = len(tokens)
        cuts = [n] + [c for c in range((n // ps) * ps, 0, -ps) if c < n]
        keyed, entry = 0, None
        for cut in cuts:
            key = self._key(tokens, cut)
            keyed += cut
            entry = self._prefixes.get(key)
            if entry is not None:
                self._prefixes.move_to_end(key)
                break
        self._count("pool.prefix_key_tokens", keyed)
        return entry

    def prefix_register(self, tokens: Sequence[int],
                        pages: Sequence[int]) -> None:
        """Publish every page-aligned prefix of ``tokens`` (and the full
        length) as cache entries over the lane's current ``pages``.
        Each NEW entry takes one reference per page it spans, so the
        pages outlive the lane that produced them."""
        ps = self.page_size
        n = len(tokens)
        cuts = list(range(ps, n, ps)) + [n]
        self._count("pool.prefix_key_tokens", sum(cuts))
        for cut in cuts:
            key = self._key(tokens, cut)
            if key in self._prefixes:
                self._prefixes.move_to_end(key)
                continue
            span = -(-cut // ps)
            entry = PrefixEntry(key[1], tuple(int(p) for p in pages[:span]),
                                cut)
            for p in entry.pages:
                self.ref(p)
            self._prefixes[key] = entry

    def evict_one(self) -> bool:
        """Drop the least-recently-used prefix entry (freeing its page
        references).  Returns False when the cache is empty."""
        if not self._prefixes:
            return False
        _, entry = self._prefixes.popitem(last=False)
        for p in entry.pages:
            self.free(p)
        self._count("pool.evictions")
        return True

    def prefix_entries(self) -> int:
        return len(self._prefixes)

    def leak_check(self) -> None:
        """Every page is either free, garbage, or reachable from a live
        reference — asserts the refcount/free-list invariant (used by
        tests after admit/retire cycles)."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate free pages"
        for p in range(self.num_pages):
            if p == GARBAGE_PAGE:
                assert self.refcount[p] >= 1
                assert p not in free
            elif p in free:
                assert self.refcount[p] == 0, (p, self.refcount[p])
            else:
                assert self.refcount[p] > 0, f"leaked page {p}"


# Device page-table edits, as data.  Each one's fields are the
# arguments, in order, of the scheduler program that applies it.
# Write lane ``slot``'s whole row (``_set_pt_row``):
RowEdit = namedtuple("RowEdit", "slot row")
# Point entry ``idx`` of lane ``slot``'s row at ``page`` (``_set_pt_entry``):
EntryEdit = namedtuple("EntryEdit", "slot idx page")
# Copy-on-write: copy page ``src`` into ``page`` in every pool and point
# entry ``idx`` of lane ``slot``'s row at the copy (``_copy_page``):
CopyEdit = namedtuple("CopyEdit", "src page slot idx")


class LanePages:
    """The pages of every lane: a :class:`PagePool` and ``table``, the
    host mirror of the (max_slots, pages_per_lane) device page table, so
    no allocation decision reads the device.  Every non-garbage entry of
    a lane's row holds one reference on behalf of that lane.
    ``refuse_alloc(site, slot, n)`` (the fault injector) is consulted
    before every allocation: True fails it with no eviction rescue."""

    def __init__(self, num_pages: Optional[int], page_size: int,
                 max_slots: int, pages_per_lane: int, *, capacity: int,
                 alloc_mode: str, prefix_sharing: bool, metrics=None,
                 refuse_alloc: Optional[Callable[[str, Optional[int], int],
                                                 bool]] = None):
        # auto pool: garbage page + a full complement per lane + one
        # lane's worth of slack for retained prefix entries
        if num_pages is None:
            num_pages = 1 + (max_slots + 1) * pages_per_lane
        if num_pages < 1 + pages_per_lane:
            raise ValueError(
                f"num_pages={num_pages} cannot hold even one "
                f"lane ({pages_per_lane} pages + garbage page)")
        self.pool = PagePool(num_pages, page_size, metrics=metrics)
        self.table = np.zeros((max_slots, pages_per_lane), np.int32)
        self.page_size = page_size
        self.pages_per_lane = pages_per_lane
        self.capacity = capacity
        self.alloc_mode = alloc_mode              # incremental | full
        self.prefix_sharing = prefix_sharing
        self._refuse = refuse_alloc or (lambda site, slot, n: False)

    def occupancy(self) -> float:
        return 1.0 - self.pool.available() / self.pool.num_pages

    def pages_for(self, plen: int) -> int:
        """Pages an admission of ``plen`` tokens takes ('full' mode: the
        whole lane's)."""
        if self.alloc_mode == "full":
            return self.pages_per_lane
        return -(-plen // self.page_size)

    def alloc(self, n: int, *, site: str = "",
              slot: Optional[int] = None) -> Optional[List[int]]:
        """Claim ``n`` pages, evicting LRU prefix-cache entries under
        pressure; None when the pool cannot supply them."""
        if self._refuse(site, slot, n):
            self.pool._count("faults.alloc_failures")
            return None
        pages = self.pool.alloc(n)
        while pages is None and self.pool.evict_one():
            pages = self.pool.alloc(n)
        return pages

    def take(self, slot: int, n: int) -> Optional[np.ndarray]:
        """Claim ``n`` fresh pages and map them from the start of lane
        ``slot``'s row.  No edit: the admission program writes the row."""
        pages = self.alloc(n, site="admission", slot=slot)
        if pages is None:
            return None
        self.table[slot] = 0
        self.table[slot, :n] = pages
        return np.asarray(pages, np.int32)

    def lookup(self, tokens: Sequence[int]) -> Optional[PrefixEntry]:
        return self.pool.prefix_lookup(tokens) if self.prefix_sharing \
            else None

    def map_prefix(self, slot: int, entry: PrefixEntry,
                   plen: int) -> Tuple[int, RowEdit]:
        """Map ``entry``'s pages read-only into lane ``slot``, reusing at
        most ``plen - 1`` tokens so one suffix step runs (its logits
        seed the first token).  Returns the reused length and the edit."""
        t = min(entry.length, plen - 1)
        shared = entry.pages[:-(-t // self.page_size)]
        for p in shared:
            self.pool.ref(p)
        self.table[slot] = 0
        self.table[slot, :len(shared)] = shared
        return t, RowEdit(slot, self.table[slot].copy())

    def writable(self, slot: int, pos: int,
                 site: str = "") -> Optional[List[tuple]]:
        """Make lane ``slot`` the sole owner of the page its write at
        ``pos`` lands in (first touch / copy-on-write).  Returns the
        edits, or None when the pool cannot supply the page."""
        idx = (pos % self.capacity) // self.page_size
        phys = int(self.table[slot, idx])
        if phys != GARBAGE_PAGE and self.pool.refcount[phys] <= 1:
            return []
        shared = phys != GARBAGE_PAGE
        got = self.alloc(1, site=site + ("cow" if shared else "first_touch"),
                         slot=slot)
        if got is None:
            return None
        self.table[slot, idx] = got[0]
        if not shared:
            return [EntryEdit(slot, idx, got[0])]
        self.pool.free(phys)                   # drop the lane's shared ref
        self.pool._count("sched.cow_copies")
        return [CopyEdit(phys, got[0], slot, idx)]

    def register(self, slot: int, tokens: Sequence[int]) -> None:
        """Publish lane ``slot``'s page-aligned prefixes of ``tokens``
        (and the whole prompt) as prefix-cache entries."""
        span = -(-len(tokens) // self.page_size)
        self.pool.prefix_register(tokens,
                                  [int(p) for p in self.table[slot, :span]])

    def release(self, slot: int) -> RowEdit:
        """Drop lane ``slot``'s reference on every page of its row and
        zero the row, on the device too: a freed lane's stale mapping
        must never alias a reallocated page."""
        for phys in self.table[slot]:
            if phys != GARBAGE_PAGE:
                self.pool.free(int(phys))
        self.table[slot] = 0
        return RowEdit(slot, self.table[slot].copy())

    def resident_bytes(self, cache) -> int:
        """Bytes of ``cache`` holding KV: each pool's referenced pages,
        the other leaves whole, and the refcounts."""
        used = self.pool.num_pages - self.pool.available()
        total = self.pool.refcount.nbytes
        for k, v in cache.items():
            nbytes = int(v.size) * v.dtype.itemsize
            total += (nbytes // self.pool.num_pages) * used \
                if k.endswith("_pages") else nbytes
        return total

    def audit(self, live: Sequence[int]) -> None:
        """Assert the refcount invariant: every page's count equals
        (1 for the garbage page) + (1 per ``live`` lane mapping it)
        + (1 per prefix-cache entry spanning it)."""
        expected = np.zeros(self.pool.num_pages, np.int64)
        expected[GARBAGE_PAGE] = 1
        rows = self.table[list(live)].ravel()
        np.add.at(expected, rows[rows != GARBAGE_PAGE], 1)
        for entry in self.pool._prefixes.values():
            np.add.at(expected, list(entry.pages), 1)
        actual = np.asarray(self.pool.refcount, np.int64)
        if not np.array_equal(expected, actual):
            bad = np.nonzero(expected != actual)[0]
            raise AssertionError(
                f"refcount leak: pages {bad.tolist()} expected "
                f"{expected[bad].tolist()} got {actual[bad].tolist()}")
