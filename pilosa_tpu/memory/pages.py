"""Paged device stacks — sub-stack residency granularity.

A tile-stack cache entry used to be ONE device array: a broad TopN's
(R, S, W) candidate stack evicting meant losing the whole thing, and
a byte-budget squeeze evicted entire hot stacks to fit one new one.
Here an entry becomes a set of fixed-size *pages*: the stack's leading
axes flatten to L lanes (one lane = one (leading-coords, W) row — a
shard-group x row-block slab), and consecutive lanes group into pages
of ``memory.page_bytes()`` each.  Pages are independent device arrays:

- the query operand is assembled by a jitted gather
  (``ops.bitmap.assemble_pages`` — concatenate + trim), so the engine
  sees the same single array it always did;
- eviction drops the COLDEST PAGES (memory/policy.py scoring), not
  whole entries — a 2x-overcommitted working set re-uploads only the
  pages a query actually lost;
- delta patching (PR 3) applies per page: a point write scatters into
  the one page holding its dirty lanes.

This is the ragged-KV-cache paging trick (Ragged Paged Attention,
PAPERS.md) applied to bitmap tiles; the roaring container (64Ki
columns) is the reference's analogous fixed residency unit.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from pilosa_tpu import memory


def page_lanes_for(width_words: int, itemsize: int = 4) -> int:
    """Lanes per page: the largest whole-lane count fitting the
    configured page size (>= 1 — a lane wider than the page still
    pages lane-by-lane)."""
    lane_bytes = max(int(width_words) * itemsize, 1)
    return max(int(memory.page_bytes()) // lane_bytes, 1)


@dataclass
class StackRecipe:
    """Everything the paged cache path needs to (re)build an entry at
    page granularity, supplied by the stack builders in
    executor/stacked.py:

    - ``logical_lead``: the stack's leading shape (lanes = prod)
    - ``width_words``:  trailing word-axis length
    - ``lane_words(lane)``: the lane's CURRENT full-width host words
      (re-read from live fragments — page rebuilds and patches share
      one source of truth with the whole-stack patcher)
    - ``build_host()``: the full host (lead..., W) array (bulk cold
      builds beat L lane_words calls).  Called for a rebuild only
      where the recipe has no page source
    - ``build_page(lane_ids, page_lanes, density_hint)``: the page
      source — one fresh page in its final form, a dense
      (page_lanes, W) host block or an ``EncodedPage`` with its
      ``lane_counts``, made straight from the fragments' storage
      (memory/encode.py encode_lanes).  Optional: ``row_stack`` over
      one view has one; with it a rebuild never calls ``build_host``
      and a lost page of a fresh entry never calls ``lane_words``
      (patches still do)
    - ``versions_fn()``: the entry's CURRENT fragment stamp tuple
      (prefetch warms against live versions, never a stale snapshot)
    - ``deltas_fn(old_versions)``: dirty lane map (lane -> [(lo, hi)]
      word runs, None value = whole lane) or None for structural
      changes; absent when delta patching is disabled
    - ``weight``: rebuild cost per byte relative to a plain row stack
      (groupcode stacks OR many rows per lane — evicting their pages
      costs more to restore, so the eviction policy holds them longer)
    - ``alive_fn()``: False once the fields this recipe captured were
      dropped/recreated — the prefetcher must not rebuild (and
      budget-reserve) stacks no live query can ever hit
    - ``lane_device``: serving-mesh owner slot per lane (int32
      (lanes,), from memory/placement.py) or None for the single-
      device layout — gives the PagedStack its device axis
    - ``shard_axis``: which leading axis of ``logical_lead`` indexes
      the group's shards (the axis ``lane_device`` varies along) —
      the mesh program needs it to rebuild per-device local leaves
      with the shard axis compressed to the device's owned shards
    """

    logical_lead: tuple
    width_words: int
    lane_words: object
    build_host: object
    versions_fn: object
    build_page: object = None
    deltas_fn: object = None
    weight: float = 1.0
    alive_fn: object = None
    lane_device: object = None
    shard_axis: int | None = None

    @property
    def lanes(self) -> int:
        n = 1
        for d in self.logical_lead:
            n *= int(d)
        return n


class PagedStack:
    """One cache entry's resident pages + recency/frequency.

    ``pages[i]`` is a device array of shape (page_lanes, W) (the last
    page zero-padded) or None when evicted.  Slots are swapped only
    under the owning cache's lock; readers snapshot the page list so
    a concurrent eviction can never yank an array mid-gather (the
    local reference keeps the buffer alive).  Recency/frequency are
    ENTRY-level scalars: an operand always needs all its pages, so
    per-page stamps would carry no signal (every access touches every
    page) at O(n_pages) bookkeeping cost — eviction concentrates on
    whole entries and drains their pages in index order.

    With ``lane_device`` (the serving mesh, memory/placement.py) the
    stack grows a DEVICE AXIS: lanes partition by owner slot (stable —
    within a device, global lane order is preserved) and each device's
    lane run pages independently, so a page never straddles two
    devices.  ``page_device[pi]`` is the page's owner slot,
    ``page_table[pi]`` its global lane ids, and ``inv[lane]`` the
    lane's row in the padded page concatenation (the permutation the
    single-array assembly fallback applies).  ``lane_device is None``
    keeps the exact legacy layout (contiguous lanes per page,
    ``inv`` identity)."""

    __slots__ = ("shape", "lanes", "page_lanes", "width_words",
                 "weight", "pages", "last_access", "hits",
                 "lane_device", "shard_axis", "page_device",
                 "page_table", "lane_page", "lane_slot")

    def __init__(self, shape: tuple, page_lanes: int,
                 weight: float = 1.0, lane_device=None,
                 shard_axis: int | None = None):
        self.shape = tuple(shape)
        self.width_words = int(shape[-1])
        n = 1
        for d in shape[:-1]:
            n *= int(d)
        self.lanes = n
        self.page_lanes = int(page_lanes)
        self.weight = float(weight)
        self.shard_axis = shard_axis
        if lane_device is None:
            self.lane_device = None
            self.page_device = None
            self.page_table = None
            self.lane_page = None
            self.lane_slot = None
            n_pages = -(-self.lanes // self.page_lanes)
        else:
            ld = np.ascontiguousarray(lane_device, dtype=np.int32)
            if ld.shape != (self.lanes,):
                raise ValueError("lane_device must be (lanes,)")
            self.lane_device = ld
            order = np.argsort(ld, kind="stable")
            self.page_table = []
            self.page_device = []
            pl = self.page_lanes
            for dev in np.unique(ld):
                run = order[ld[order] == dev]
                for k in range(0, run.size, pl):
                    self.page_table.append(run[k:k + pl])
                    self.page_device.append(int(dev))
            self.lane_page = np.empty(self.lanes, dtype=np.int32)
            self.lane_slot = np.empty(self.lanes, dtype=np.int32)
            for pi, ids in enumerate(self.page_table):
                self.lane_page[ids] = pi
                self.lane_slot[ids] = np.arange(ids.size,
                                                dtype=np.int32)
            n_pages = len(self.page_table)
        self.pages: list = [None] * n_pages
        self.last_access = time.time()
        self.hits = 0

    @property
    def n_pages(self) -> int:
        return len(self.pages)

    @property
    def page_nbytes(self) -> int:
        """DENSE byte size of one page — the fixed upper bound.
        Resident accounting uses each page's TRUE byte size instead
        (``resident_bytes``): container-encoded pages
        (memory/encode.py) are smaller, and charging the ledger their
        dense-tile estimate would waste exactly the capacity the
        sparse format buys."""
        return self.page_lanes * self.width_words * 4

    def resident_bytes(self) -> int:
        return sum(int(p.nbytes) for p in self.pages
                   if p is not None)

    def missing(self) -> list[int]:
        return [i for i, p in enumerate(self.pages) if p is None]

    def lane_range(self, pi: int) -> tuple[int, int]:
        """Legacy contiguous page extent (single-device layout only —
        device-partitioned pages hold non-contiguous lane id sets, use
        ``page_lane_ids``)."""
        if self.page_table is not None:
            raise ValueError("lane_range undefined for device-"
                             "partitioned pages")
        lo = pi * self.page_lanes
        return lo, min(lo + self.page_lanes, self.lanes)

    def page_lane_ids(self, pi: int) -> np.ndarray:
        """Global lane ids resident in page ``pi`` (<= page_lanes)."""
        if self.page_table is not None:
            return self.page_table[pi]
        lo, hi = self.lane_range(pi)
        return np.arange(lo, hi, dtype=np.int32)

    def page_of(self, lane: int) -> tuple[int, int]:
        """(page index, row inside the page) holding ``lane``."""
        if self.lane_page is not None:
            return int(self.lane_page[lane]), int(self.lane_slot[lane])
        return divmod(int(lane), self.page_lanes)

    def device_of(self, pi: int) -> int | None:
        """The page's serving-mesh owner slot (None = unplaced)."""
        return (None if self.page_device is None
                else self.page_device[pi])

    def inv_perm(self) -> "np.ndarray | None":
        """lane -> row in the padded page concatenation, or None when
        page order IS lane order (the legacy layout)."""
        if self.lane_page is None:
            return None
        return (self.lane_page.astype(np.int64) * self.page_lanes
                + self.lane_slot)

    def device_resident_bytes(self) -> dict[int, int]:
        """True resident bytes by owner slot (invariant checks +
        bench occupancy)."""
        out: dict[int, int] = {}
        for pi, p in enumerate(self.pages):
            if p is None:
                continue
            d = self.device_of(pi)
            out[-1 if d is None else d] = (
                out.get(-1 if d is None else d, 0) + int(p.nbytes))
        return out

    def build_page_host(self, pi: int, lane_words) -> np.ndarray:
        """Host words for one page (zero-padded past the last lane)."""
        block = np.zeros((self.page_lanes, self.width_words),
                         dtype=np.uint32)
        for k, lane in enumerate(self.page_lane_ids(pi)):
            block[k] = lane_words(int(lane))
        return block

    def touch(self, now: float | None = None):
        self.last_access = time.time() if now is None else now
        self.hits += 1
