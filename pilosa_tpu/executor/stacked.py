"""Stacked shard execution — the mesh-integrated query engine.

This is the TPU re-design of the reference's ``mapReduce`` shard
fan-out (executor.go:6449-6812).  The reference maps a per-shard
``mapFn`` over a worker pool and streams partial results through a
``reduceFn``; here the shard axis becomes the LEADING AXIS of every
operand: a whole PQL bitmap call tree compiles to ONE jitted XLA
program over ``(S, W)`` shard-stacked tiles, and the cross-shard
reduce happens IN the program (``jnp.sum`` over the shard axis, which
GSPMD lowers to a ``psum`` over ICI when the stacks are placed on a
``jax.sharding.Mesh`` with the shard axis sharded over the mesh's
"shards" axis, exactly the placement of ``parallel.place_shards``).
The in-program reduce is int32; above ``_REDUCE_MAX_SHARDS`` shards
the engine fetches per-shard partials and sums in exact host ints.

Pieces:

- ``PlanBuilder`` walks a ``pql.Call`` tree and emits a static IR
  (nested tuples) plus a flat list of *leaf* arrays (stacked row
  tiles, BSI plane stacks, existence rows, precomputed cross-shard
  results) and *param* arrays (BSI predicate masks / sign flags that
  change per query WITHOUT recompiling).
- ``TileStackCache`` memoizes the expensive part — stacking S host
  rows into one device-resident array — keyed by fragment versions so
  any write invalidates exactly the stacks it touched.  The cache is
  byte-bounded with LRU eviction (the HBM-residency policy the
  reference implements with its rank cache, cache.go:130).
- A per-structure jit cache: two queries with the same tree *shape*
  (e.g. ``Count(Intersect(Row(f=A), Row(g=B)))`` for any A, B) reuse
  one compiled executable; predicates ride in as runtime params.

Supported reductions: ``words`` (bitmap result), ``count``,
``bsi_sum`` (Sum over a filter tree), ``row_counts`` (the TopN/TopK
candidate-row scan, executor.go:2750 topKFilter as one fused AND +
popcount over the (R, S, W) stack).

Anything the IR cannot express raises ``Unstackable`` and the executor
falls back to the per-shard loop path (the reference's own remote/
local split has the same shape: fast path plus fallback).
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import logging
import os
import threading
import time
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from pilosa_tpu import memory
from pilosa_tpu.memory import encode, pressure
from pilosa_tpu.memory.pages import PagedStack, StackRecipe, page_lanes_for
from pilosa_tpu.models import timeq
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.obs import flight, metrics, roofline, stats
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.ops import kernels
from pilosa_tpu.pql import ast as past
from pilosa_tpu.pql.ast import Call, Condition

# In-program cross-shard reduction is exact in int32 only while
# S * 2^20 < 2^31; beyond ~2000 shards the engine falls back to
# per-shard partials summed on the host in Python ints.
_REDUCE_MAX_SHARDS = 2000


class Unstackable(Exception):
    """Raised when a call tree has no stacked-program equivalent."""


# ---------------------------------------------------------------------------
# tile-stack cache
# ---------------------------------------------------------------------------

def _patch_enabled() -> bool:
    """Incremental stack maintenance: stale device stacks are delta-
    patched in place of a full host restack + re-upload.
    PILOSA_TPU_STACK_PATCH=0 restores the rebuild-on-write behavior
    (the bench A/B switch; config.py [stacked] patch)."""
    return os.environ.get("PILOSA_TPU_STACK_PATCH", "1") != "0"


def _patch_max_frac() -> float:
    """Dirty fraction past which one dense rebuild upload beats
    scattering runs: the MEASURED patch-vs-rebuild break-even from
    the statistics catalog once both arms have real volume
    (stats.patch_break_even_frac), else the static default below —
    threshold choice only, results identical either way."""
    f = stats.patch_break_even_frac()
    return _PATCH_MAX_FRAC if f is None else f


# Dirty fraction past which patching loses to one contiguous rebuild
# upload: scattering most of a stack word-run by word-run costs more
# dispatch + scatter overhead than a single dense H2D transfer.
_PATCH_MAX_FRAC = float(os.environ.get("PILOSA_TPU_PATCH_MAX_FRAC",
                                       "0.5"))

# Admission cap: one paged entry may RETAIN at most this fraction of
# the budget; pages past the cap serve the query transiently and are
# never reserved.  This is the scan resistance that makes paging beat
# whole-stack eviction — a broad TopN's (R, S, W) block cannot evict
# the hot working set to cache itself, it just streams its tail.
_ENTRY_RESIDENT_FRAC = float(os.environ.get(
    "PILOSA_TPU_MEMORY_ENTRY_FRAC", "0.5"))


_log = logging.getLogger("pilosa_tpu.stacked")


# -- raw page views (ragged page-table dispatch) ----------------------------
# The ragged serving plane (executor/ragged.py) fuses queries over
# DIFFERENT indexes/shard subsets into one device program by taking
# the cache's PagedStack pages directly as program operands and
# gathering them through a page table INSIDE the fused program —
# skipping the per-access assemble_pages dispatch entirely.  A caller
# opts in with the raw_pages() context: stack fetches on this thread
# then return PageView handles (a safe snapshot of the entry's page
# arrays) instead of assembled arrays.  Everything else about the
# fetch — versions, single-flight, patching, ledger accounting — is
# identical, so a PageView is exactly as fresh as the assembled array
# would have been.

_RAW_TLS = threading.local()


class PageView:
    """Raw paged payload of one stack-cache entry: the page arrays a
    ragged program assembles its operand from.  ``pages`` is a
    local snapshot (references keep the buffers alive against
    concurrent eviction, the same contract as the assemble path);
    the last page is zero-padded past ``lanes``.  Entries under the
    sparse device format carry a MIX of dense arrays and
    memory/encode.py EncodedPage payloads — consumers with no packed
    arm take ``dense_pages()`` (the per-page decode-to-dense
    boundary, bit-exact by construction; a resident encoded page
    keeps the block its first expansion made and hands it out
    again).

    Under the serving mesh (memory/placement.py) the view also
    carries the entry's device layout: ``page_device[pi]`` the page's
    owner slot, ``lane_page``/``lane_slot`` the lane -> (page, row)
    map, ``shard_axis`` which leading axis the placement partitioned —
    everything the mesh ragged program needs to build per-device
    pools and local gathers.  All None on the single-device layout
    (page order IS lane order)."""

    __slots__ = ("shape", "lanes", "page_lanes", "pages",
                 "page_device", "lane_page", "lane_slot", "shard_axis")

    def __init__(self, shape: tuple, lanes: int, page_lanes: int,
                 pages: list, page_device=None, lane_page=None,
                 lane_slot=None, shard_axis=None):
        self.shape = tuple(shape)
        self.lanes = int(lanes)
        self.page_lanes = int(page_lanes)
        self.pages = list(pages)
        self.page_device = page_device
        self.lane_page = lane_page
        self.lane_slot = lane_slot
        self.shard_axis = shard_axis

    @property
    def width_words(self) -> int:
        return int(self.shape[-1])

    def encoded(self) -> bool:
        return any(encode.is_encoded(p) for p in self.pages)

    def dense_pages(self) -> list:
        """Every page as a dense (page_lanes, W) block (encoded pages
        give their kept block or expand; dense pages pass through)."""
        return [encode.to_dense(p) for p in self.pages]


def _expand_view(view: PageView):
    """Materialize a PageView into the assembled dense operand the
    non-raw fetch path would have returned — the whole-operand decode
    boundary for plans with no packed arm."""
    with flight.stage("stack.assemble"):
        pages = view.dense_pages()
        if view.lane_page is not None:
            return _assemble_permuted(pages, view.lane_page,
                                      view.lane_slot, view.page_lanes,
                                      view.shape)
        if len(pages) == 1 and view.lanes == view.page_lanes:
            return pages[0].reshape(view.shape)
        return bm.assemble_pages(tuple(pages), view.shape)


def _assemble_permuted(pages, lane_page, lane_slot, page_lanes,
                       shape):
    """Single-array assembly of DEVICE-PARTITIONED pages: pull every
    page to one device (the correct-but-slower fallback for consumers
    outside the mesh program), concatenate, and undo the placement
    permutation (lane -> page row)."""
    import jax
    d0 = jax.devices()[0]
    pulled = tuple(jax.device_put(p, d0) for p in pages)
    inv = (lane_page.astype(np.int32) * np.int32(page_lanes)
           + lane_slot.astype(np.int32))
    cat = jnp.concatenate(pulled, axis=0)
    return cat[jnp.asarray(inv)].reshape(shape)


def _note_rebuild_time(seconds: float) -> None:
    """One stack_rebuild / stack_page_rebuild, on the wall clock."""
    metrics.STACK_REBUILD_SECONDS.inc(seconds)
    metrics.STACK_REBUILD_TIMED.inc()


def _same_lane_device(a, b) -> bool:
    """Structural placement compare for PagedStack reuse (None =
    single-device layout)."""
    if a is None or b is None:
        return a is None and b is None
    return np.array_equal(a, b)


# set while a kept block's reservation runs on this thread: the stack
# caches' reclaim then sheds kept blocks only, so keeping one never
# costs a resident page
_KEEP_TLS = threading.local()


def _unseat(page) -> int:
    """A page leaving its slot (evicted, dropped or replaced): its
    resident bytes, a kept block included, which an encoded page then
    forgets together with its keeper.  Under the cache's lock."""
    nb = encode.page_nbytes(page)
    if encode.is_encoded(page):
        page.kept = None
        page.keeper = None
    return nb


def _page_mix(pages) -> dict:
    """{encoding: page count} of one entry's page list (flight
    records note the per-query packed-vs-dense mix)."""
    mix: dict[str, int] = {}
    for p in pages:
        k = encode.page_kind(p)
        mix[k] = mix.get(k, 0) + 1
    return mix


class raw_pages:
    """Context manager: stack fetches on this thread return PageView
    handles for paged entries (whole/host entries still return plain
    arrays — the ragged planner treats those as direct leaves)."""

    def __enter__(self):
        self._prev = getattr(_RAW_TLS, "on", False)
        _RAW_TLS.on = True
        return self

    def __exit__(self, *exc):
        _RAW_TLS.on = self._prev
        return False


class TileStackCache:
    """Budget-ledgered cache of device-resident shard stacks.

    An entry is keyed by (index, field, view-set, row, shards, mesh
    epoch) and guarded by the tuple of contributing fragment
    (gen, version) stamps: any host write bumps the fragment version
    (models/fragment.py).  On a version mismatch the entry is first
    offered to the incremental write path, which applies the
    fragments' delta logs ON DEVICE (O(delta) upload) and falls back
    to a full host restack only when the log can't prove coverage.
    Builds and patches are single-flight per key.

    Residency (PR 5): bytes are accounted through the process-wide
    budget ledger (pilosa_tpu/memory) instead of a private max_bytes —
    pressure here can shed cold bytes in the jit/result caches and
    vice versa.  On single-device placements entries are PAGED
    (memory/pages.py): fixed-size lane-block device pages assembled
    into the operand by a jitted gather, evicted and delta-patched per
    page with cost-aware scoring (memory/policy.py) — a broad TopN no
    longer evicts whole hot stacks, and a 2x-overcommitted working set
    re-uploads only the pages a query actually lost.  ``max_bytes``
    stays honored as a LOCAL cap when set (tests and explicit
    operator bounds); None defers entirely to the ledger.
    """

    _MAX_RECIPES = 512
    _MAX_WARNED = 1024

    def __init__(self, max_bytes: int | None = None, ledger=None):
        self.max_bytes = max_bytes
        self._ledger = memory.ledger() if ledger is None else ledger
        self._client = self._ledger.register(
            "stack_cache", reclaim=self._reclaim, cold_ts=self._cold_ts)
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._bytes = 0
        # queries are served concurrently from the threaded HTTP/gRPC
        # servers; the LRU's linked list is not safe to mutate from
        # two handler threads at once
        self._lock = threading.Lock()
        # per-key single-flight latches (key -> Event)
        self._building: dict = {}
        # prefetch recipes: key fingerprint -> (key, build, patcher,
        # recipe) so the flight-recorder-fed prefetcher can rebuild
        # evicted pages off the serving hot path (memory/policy.py)
        self._recipes: OrderedDict = OrderedDict()
        self._key_fps: dict = {}
        self._warned_big: set = set()
        self.hits = 0
        self.misses = 0          # every non-hit access
        self.patches = 0         # misses served by a delta patch
        self.full_rebuilds = 0   # misses served by a full build
        self.page_rebuilds = 0   # fresh entries with pages re-uploaded
        self.too_big = 0         # entries alone exceeding the budget
        self.patched_bytes = 0   # words uploaded via patch runs
        self.rebuilt_bytes = 0   # full stack/page bytes re-uploaded

    def get(self, key, versions: tuple, build, patcher=None,
            recipe=None):
        """Fetch-or-build with flight/span attribution: every access
        is timed and tagged with its outcome (hit / wait / patch /
        page_rebuild / rebuild) and the bytes it moved to the device,
        so a query's flight record says exactly what its stacks cost.
        `recipe` (memory/pages.py StackRecipe) opts the entry into
        paged residency and prefetch."""
        # the stage learns its name last: a hit is a sum and a count
        # with no span of its own (one query touches dozens); patch /
        # rebuild / wait are spans.  The work itself is annotated
        # where it runs (_serve_whole / _serve_paged).
        with flight.stage("stack_hit") as st:
            fp = (self._remember_recipe(key, build, patcher, recipe)
                  if recipe is not None else None)
            arr, outcome, moved = self._get(key, versions, build,
                                            patcher, recipe)
            st.name = "stack_" + outcome
            st.keep = outcome != "hit"
            if st.span is not None:
                st.span.set_tag("outcome", outcome)
                if moved:
                    st.span.set_tag("bytes", moved)
        flight.note_stack(
            outcome, moved,
            key_fp=fp if outcome not in ("hit", "wait") else None)
        return arr

    def probe(self, key, versions: tuple):
        """Lock-cheap fresh-hit fast path: serve a resident entry
        without the patcher/recipe machinery only a miss needs
        (builders call this before constructing those closures and
        fall back to ``get`` on None).  Declines — returns None —
        unless the entry is present, version-fresh, fully resident,
        and no builder is mid-flight on the key; the recipe store's
        recency is still bumped so hot entries keep their prefetch
        recipes."""
        t0 = time.perf_counter()
        ps_hit = None
        with self._lock:
            ent = self._entries.get(key)
            if (ent is None or ent[0] != versions
                    or key in self._building):
                return None
            payload = ent[1]
            if isinstance(payload, PagedStack):
                if payload.missing():
                    return None
                # snapshot page refs under the lock (same race note
                # as the _get hit path)
                ps_hit = (payload, list(payload.pages))
                self._entries.move_to_end(key)
            else:
                self._entries.move_to_end(key)
                self._entries[key] = (ent[0], payload, ent[2],
                                      time.time())
            self.hits += 1
            metrics.STACK_CACHE.inc(outcome="hit")
            fp = self._key_fps.get(key)
            if fp is not None and fp in self._recipes:
                self._recipes.move_to_end(fp)
        arr = payload if ps_hit is None else self._assemble(*ps_hit)
        flight.note_stack("hit", 0, dt=time.perf_counter() - t0)
        return arr

    def _get(self, key, versions: tuple, build, patcher=None,
             recipe=None):
        waited = False
        while True:
            ps_hit = None
            with self._lock:
                ent = self._entries.get(key)
                # a fresh-looking entry is only servable when no
                # builder is mid-flight on this key: paged maintenance
                # swaps pages in place, so a reader whose versions
                # snapshot predates a racing write could otherwise
                # assemble a half-patched stack (the whole-entry path
                # never could — its patcher swapped array + stamp
                # atomically).  Building keys take the wait path.
                if (ent is not None and ent[0] == versions
                        and key not in self._building):
                    payload = ent[1]
                    paged = isinstance(payload, PagedStack)
                    if not paged or not payload.missing():
                        self._entries.move_to_end(key)
                        self.hits += 1
                        metrics.STACK_CACHE.inc(outcome="hit")
                        if not paged:
                            # refresh the recency stamp the eviction
                            # scorer reads for whole entries
                            self._entries[key] = (
                                ent[0], payload, ent[2], time.time())
                            return (payload,
                                    ("wait" if waited else "hit"), 0)
                        # snapshot page refs under the lock so a
                        # concurrent eviction can't yank one mid-gather
                        ps_hit = (payload, list(payload.pages))
                if ps_hit is None:
                    ev = self._building.get(key)
                    if ev is None:
                        ev = self._building[key] = threading.Event()
                        stale = ent
                        self.misses += 1
                        metrics.STACK_CACHE.inc(outcome="miss")
                        break
            if ps_hit is not None:
                ps, arrs = ps_hit
                return (self._assemble(ps, arrs),
                        ("wait" if waited else "hit"), 0)
            # single-flight: another thread is building/patching this
            # key — wait for its result, then re-check (it may have
            # built an older version than this access wants)
            metrics.STACK_CACHE.inc(outcome="wait")
            waited = True
            ev.wait()
        try:
            # build/patch OUTSIDE the lock: restack + upload is slow
            if recipe is not None and memory.paged_enabled():
                return self._serve_paged(key, versions, stale, recipe)
            return self._serve_whole(key, versions, stale, build,
                                     patcher)
        finally:
            with self._lock:
                self._building.pop(key, None)
            ev.set()

    # -- whole-entry path (mesh/host placements; paging disabled) -------

    def _serve_whole(self, key, versions, stale, build, patcher):
        arr = None
        outcome, moved = "rebuild", 0
        stale_whole = (stale is not None
                       and not isinstance(stale[1], PagedStack))
        if stale_whole and patcher is not None:
            try:
                with flight.annotate("stack_patch"):
                    patched = patcher(stale[1], stale[0])
            except Exception:
                patched = None  # any patch failure → full rebuild
            if patched is not None:
                arr, pbytes = patched
                outcome, moved = "patch", pbytes
                with self._lock:  # single-flight is per-KEY only
                    self.patches += 1
                    self.patched_bytes += pbytes
                metrics.STACK_CACHE.inc(outcome="patch")
                metrics.STACK_MAINT_BYTES.inc(pbytes, kind="patched")
        if arr is None:
            t0 = time.perf_counter()
            with flight.annotate("stack_rebuild"):
                arr = build()
            _note_rebuild_time(time.perf_counter() - t0)
            nb = int(np.prod(arr.shape)) * arr.dtype.itemsize
            moved = nb
            with self._lock:
                self.full_rebuilds += 1
                self.rebuilt_bytes += nb
            metrics.STACK_CACHE.inc(outcome="rebuild")
            metrics.STACK_MAINT_BYTES.inc(nb, kind="rebuilt")
        nbytes = int(np.prod(arr.shape)) * arr.dtype.itemsize
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[2]
        if old is not None and old[2]:
            self._release_entry(old[1], old[2])
        cap = self._budget_cap()
        if nbytes > cap:
            # an entry that alone exceeds the budget is never cached
            # (it would pin the cache over budget forever); the caller
            # still gets the fresh stack — and the drop is no longer
            # silent: counted + warned once per key
            self._note_too_big(key, nbytes, cap)
            return arr, outcome, moved
        # ledger reservation OUTSIDE our lock: reclaim may call back
        # into this cache's _reclaim, which takes the lock
        if not self._client.reserve(nbytes):
            metrics.STACK_CACHE.inc(outcome="denied")
            return arr, outcome, moved
        with self._lock:
            self._entries[key] = (versions, arr, nbytes, time.time())
            self._bytes += nbytes
            shed, shed_map = self._enforce_local_cap_locked()
        if shed:
            self._release_freed(shed, shed_map)
        return arr, outcome, moved

    # -- paged path (single-device placements) --------------------------

    def _serve_paged(self, key, versions, stale, recipe: StackRecipe):
        w = recipe.width_words
        shape = tuple(recipe.logical_lead) + (w,)
        lanes = recipe.lanes
        pl = max(1, min(page_lanes_for(w), lanes))
        ps = None
        old_versions = None
        if stale is not None and isinstance(stale[1], PagedStack):
            cand = stale[1]
            if (cand.shape == shape and cand.page_lanes == pl
                    and _same_lane_device(cand.lane_device,
                                          recipe.lane_device)):
                ps, old_versions = cand, stale[0]
        if ps is None and stale is not None:
            # structural change or whole→paged transition: drop the
            # old payload entirely
            with self._lock:
                cur = self._entries.get(key)
                if cur is stale:
                    self._entries.pop(key)
                    self._bytes -= stale[2]
            if stale[2]:
                self._release_entry(stale[1], stale[2])
        patched_b = 0
        rebuilt_b = 0
        # local page map: every page array this access touches, so the
        # final assemble is immune to concurrent evictions (and pages
        # the ledger denied residency for still serve this query)
        local: dict[int, object] = {}
        if ps is not None:
            dirty = {} if old_versions == versions else (
                self._deltas_or_none(recipe, old_versions))
        # admission cap: retain at most this share of the budget per
        # entry — the tail of an oversized scan streams transiently
        # instead of evicting the hot working set
        resident_cap = max(
            int(_ENTRY_RESIDENT_FRAC * self._budget_cap()),
            pl * w * 4)
        # named for the profiler while it runs; the stage that times
        # it is the one around TileStackCache.get
        t0 = time.perf_counter()
        with flight.annotate(
                "stack_rebuild" if ps is None or dirty is None
                else "stack_patch" if old_versions != versions
                else "stack_page_rebuild"):
            if ps is None or dirty is None:
                if ps is not None:
                    self._drop_pages(key, ps)
                ps = PagedStack(shape, pl, weight=recipe.weight,
                                lane_device=recipe.lane_device,
                                shard_axis=recipe.shard_axis)
                # the whole stack as one host array, for a recipe
                # with no page source
                host = None if recipe.build_page is not None else (
                    np.asarray(recipe.build_host(),
                               dtype=np.uint32).reshape(-1, w))
                retained = 0
                for pi in range(ps.n_pages):
                    local[pi] = self._fresh_page(key, ps, pi, recipe,
                                                 host)
                    # true encoded page bytes — both for the admission
                    # cap and the maintenance-traffic attribution (a
                    # packed page uploads its coordinates, not the dense
                    # tile it stands for)
                    nb_pi = encode.page_nbytes(local[pi])
                    rebuilt_b += nb_pi
                    if (retained + nb_pi <= resident_cap
                            and self._page_install(key, ps, pi,
                                                   local[pi])):
                        retained += nb_pi
                outcome = "rebuild"
                with self._lock:
                    self.full_rebuilds += 1
                    self.rebuilt_bytes += rebuilt_b
                metrics.STACK_CACHE.inc(outcome="rebuild")
                metrics.STACK_MAINT_BYTES.inc(rebuilt_b, kind="rebuilt")
            else:
                with self._lock:
                    for pi, p in enumerate(ps.pages):
                        if p is not None:
                            local[pi] = p
                by_page: dict[int, dict] = {}
                for lane, runs in dirty.items():
                    by_page.setdefault(ps.page_of(lane)[0],
                                       {})[lane] = runs
                fresh: set[int] = set()
                retained = ps.resident_bytes()
                for pi in range(ps.n_pages):
                    if pi not in local:
                        local[pi] = self._fresh_page(key, ps, pi, recipe)
                        nb_pi = encode.page_nbytes(local[pi])
                        if (retained + nb_pi <= resident_cap
                                and self._page_install(key, ps, pi,
                                                       local[pi])):
                            retained += nb_pi
                        rebuilt_b += nb_pi
                        fresh.add(pi)
                for pi, lanes_d in by_page.items():
                    if pi in fresh:
                        continue  # rebuilt from live rows: already current
                    pb, rb = self._patch_page(key, ps, pi, lanes_d,
                                              recipe, local)
                    patched_b += pb
                    rebuilt_b += rb
                stale_entry = old_versions != versions
                if stale_entry:
                    outcome = "patch"
                    with self._lock:
                        self.patches += 1
                        self.patched_bytes += patched_b
                        self.rebuilt_bytes += rebuilt_b
                    metrics.STACK_CACHE.inc(outcome="patch")
                    if patched_b:
                        metrics.STACK_MAINT_BYTES.inc(patched_b,
                                                      kind="patched")
                    if rebuilt_b:
                        metrics.STACK_MAINT_BYTES.inc(rebuilt_b,
                                                      kind="rebuilt")
                else:
                    outcome = "page_rebuild"
                    with self._lock:
                        self.page_rebuilds += 1
                        self.rebuilt_bytes += rebuilt_b
                    metrics.STACK_CACHE.inc(outcome="page_rebuild")
                    if rebuilt_b:
                        metrics.STACK_MAINT_BYTES.inc(rebuilt_b,
                                                      kind="rebuilt")
        if outcome != "patch":
            _note_rebuild_time(time.perf_counter() - t0)
        repl = None
        with self._lock:
            old = self._entries.get(key)
            old_nb = old[2] if old is not None and old[1] is ps else 0
            if old is not None and old[1] is not ps and old[1] is not None:
                # someone else's payload can't be here (single-flight)
                # unless versions raced; replace it
                self._entries.pop(key)
                self._bytes -= old[2]
                repl = (old[1], old[2])
            nb = ps.resident_bytes()
            self._entries[key] = (versions, ps, nb, time.time())
            self._entries.move_to_end(key)
            self._bytes += nb - old_nb
            shed, shed_map = self._enforce_local_cap_locked()
        if repl is not None:
            self._release_entry(*repl)
        if shed:
            self._release_freed(shed, shed_map)
        arrs = [local[i] for i in range(ps.n_pages)]
        return (self._assemble(ps, arrs), outcome,
                patched_b + rebuilt_b)

    @staticmethod
    def _deltas_or_none(recipe: StackRecipe, old_versions):
        if recipe.deltas_fn is None:
            return None
        try:
            return recipe.deltas_fn(old_versions)
        except Exception:
            return None

    def _commit_block(self, block: np.ndarray, device=None):
        """Host page block → device, degrading to the host array when
        even a single page can't be allocated (the OOM backstop then
        re-executes on the CPU backend).  ``device`` commits the page
        to its placement owner (serving mesh)."""
        if device is not None:
            return pressure.guarded(
                lambda: jax.device_put(block, device),
                host_fallback=lambda: block)
        return pressure.guarded(lambda: jnp.asarray(block),
                                host_fallback=lambda: block)

    @staticmethod
    def _page_jdev(ps: PagedStack, pi: int):
        """The jax device a page commits to (None = default)."""
        slot = ps.device_of(pi)
        if slot is None:
            return None
        from pilosa_tpu.memory import placement
        return placement.device_of(slot)

    def _release_freed(self, freed: int, dev_map: dict):
        """Release shed bytes to the ledger with their device labels
        (dev_map: slot -> labeled bytes; the remainder was whole-entry
        / unlabeled)."""
        labeled = 0
        for slot, nb in dev_map.items():
            if nb > 0:
                self._client.release(nb, device=slot)
                labeled += nb
        rest = freed - labeled
        if rest > 0:
            self._client.release(rest)

    def _release_entry(self, payload, nbytes: int):
        """Release one replaced/dropped entry's accounted bytes,
        per-device when the payload is a device-partitioned stack."""
        if (isinstance(payload, PagedStack)
                and payload.page_device is not None):
            labeled = 0
            for slot, nb in payload.device_resident_bytes().items():
                if slot >= 0 and nb > 0:
                    self._client.release(nb, device=slot)
                    labeled += nb
            rest = nbytes - labeled
            if rest > 0:
                self._client.release(rest)
        elif nbytes:
            self._client.release(nbytes)

    @staticmethod
    def _stats_ident(key):
        """(index, field) of a stack key when it carries one — every
        pageable key shape is (kind, index, field, ...) except the
        groupcode key, whose field slot is a composite tuple."""
        if (len(key) >= 3 and isinstance(key[1], str)
                and isinstance(key[2], str)):
            return key[1], key[2]
        return None

    def _density_hint(self, key, width_words: int):
        """The stats catalog's density of the key's field (None where
        the key names none or the catalog cannot say)."""
        ident = self._stats_ident(key)
        if ident is None:
            return None
        return stats.field_density(ident[0], ident[1], width_words * 32)

    def _fresh_page(self, key, ps: PagedStack, pi: int,
                    recipe: StackRecipe, host=None):
        """One fresh page on its device: made by the recipe's page
        source where it has one, else cut out of `host` (the whole
        stack, a rebuild's) or read lane by lane (a page a fresh entry
        lost).  The one way a page that is not a patch is made."""
        device = self._page_jdev(ps, pi)
        ids = ps.page_lane_ids(pi)
        pl, w = ps.page_lanes, ps.width_words
        if recipe.build_page is not None:
            metrics.STACK_FRESH_PAGES.inc(source="direct")
            page = recipe.build_page(ids, pl, self._density_hint(key, w))
            return self._commit_made(page, key, device=device)
        metrics.STACK_FRESH_PAGES.inc(source="host")
        if host is None:
            block = ps.build_page_host(pi, recipe.lane_words)
        else:
            block = host[ids]
            if block.shape[0] < pl:
                block = np.concatenate(
                    [block, np.zeros((pl - block.shape[0], w),
                                     np.uint32)])
        return self._commit_page(block, key, device=device)

    def _commit_page(self, block: np.ndarray, key, prev=None,
                     reason: str = "build", device=None):
        """Encode-or-dense commit of one host page block
        (memory/encode.py): the container-adaptive arm of
        _commit_block.  ``prev`` is the page's current payload
        (hysteresis + encode-flip attribution); ``reason`` labels the
        pilosa_page_encode_total series (build/drift/patch)."""
        prev_kind = encode.page_kind(prev) if prev is not None else None
        enc = None
        if encode.enabled():
            enc = encode.encode_block(
                block, prev_kind=prev_kind,
                density_hint=self._density_hint(key, block.shape[1]))
        return self._commit_made(block if enc is None else enc, key,
                                 prev_kind=prev_kind, reason=reason,
                                 device=device)

    def _commit_made(self, page, key, prev_kind: str | None = None,
                     reason: str = "build", device=None):
        """A page whose form is decided — an EncodedPage or the dense
        host block — counted by its encoding and put on its device."""
        enc = page if encode.is_encoded(page) else None
        if encode.enabled():
            ident = self._stats_ident(key)
            if enc is None:
                if prev_kind not in (None, "dense"):
                    metrics.PAGE_ENCODE.inc(**{
                        "from": prev_kind, "to": "dense",
                        "reason": reason})
            else:
                metrics.PAGE_ENCODE.inc(**{
                    "from": prev_kind or "none", "to": enc.kind,
                    "reason": reason})
            if ident is not None:
                stats.note_page_encoding(ident[0], ident[1],
                                         encode.page_kind(page))
        if enc is None:
            return self._commit_block(page, device=device)
        return pressure.guarded(lambda: enc.to_device(device),
                                host_fallback=lambda: enc)

    def _page_install(self, key, ps: PagedStack, pi: int, arr) -> bool:
        """Retain one built page iff the ledger admits it (at the
        page's TRUE encoded byte size, against the owning device's
        budget share when placed); denied pages serve this access
        transiently and rebuild next time."""
        nb = encode.page_nbytes(arr)
        if not self._client.reserve(nb, device=ps.device_of(pi)):
            metrics.STACK_CACHE.inc(outcome="denied")
            return False
        with self._lock:
            ps.pages[pi] = arr
            self._seat_locked(key, pi, arr)
            ps.last_access = time.time()
            self._sync_entry_locked(key, ps)
        metrics.STACK_PAGES.inc(event="build",
                                encoding=encode.page_kind(arr))
        return True

    def _patch_page(self, key, ps: PagedStack, pi: int, lanes_d: dict,
                    recipe: StackRecipe, local: dict):
        """Apply dirty lane runs to one resident page; returns
        (patched_bytes, rebuilt_bytes).  Runs pad to pow2 widths and
        batch per width so the shared jitted scatter compiles once per
        bucket; a page dirtier than _PATCH_MAX_FRAC rebuilds wholesale
        (one dense upload beats scattering most of it).  Encoded pages
        (memory/encode.py) have no scatter arm: a write to one rebuilds
        the block and re-encodes — the drift path where a filling page
        flips back to dense."""
        dev = self._page_jdev(ps, pi)
        cur = local.get(pi)
        if cur is not None and encode.is_encoded(cur):
            block = ps.build_page_host(pi, recipe.lane_words)
            arr = self._commit_page(block, key, prev=cur,
                                    reason="patch", device=dev)
            local[pi] = arr
            self._page_replace(key, ps, pi, arr)
            metrics.STACK_PAGES.inc(event="patch",
                                    encoding=encode.page_kind(arr))
            return 0, encode.page_nbytes(arr)
        w = ps.width_words
        segs = []
        patched_words = 0
        for lane in sorted(lanes_d):
            runs = lanes_d[lane]
            runs = ([(0, w)] if runs is None
                    else _coalesce_runs(runs, w))
            li = ps.page_of(lane)[1]
            for lo, hi in runs:
                plen = min(1 << (hi - lo - 1).bit_length(), w)
                start = min(lo, w - plen)
                segs.append((li, start, plen, lane))
                patched_words += plen
        if not segs:
            return 0, 0
        if patched_words > _patch_max_frac() * ps.page_lanes * w:
            block = ps.build_page_host(pi, recipe.lane_words)
            arr = self._commit_page(block, key, prev=local.get(pi),
                                    reason="drift", device=dev)
            local[pi] = arr
            self._page_replace(key, ps, pi, arr)
            return 0, encode.page_nbytes(arr)
        lane_cache: dict[int, np.ndarray] = {}

        def words_of(lane):
            cur = lane_cache.get(lane)
            if cur is None:
                cur = lane_cache[lane] = np.asarray(
                    recipe.lane_words(lane), dtype=np.uint32)
            return cur

        arr = local[pi]
        by_len: dict[int, list] = {}
        for li, start, plen, lane in segs:
            by_len.setdefault(plen, []).append((li, start, lane))
        for plen, group in sorted(by_len.items()):
            n = len(group)
            npad = 1 << max(n - 1, 0).bit_length()
            idxs = np.zeros(npad, np.int32)
            starts = np.zeros(npad, np.int32)
            data = np.empty((npad, plen), np.uint32)
            for k in range(npad):
                li, start, lane = group[min(k, n - 1)]
                idxs[k], starts[k] = li, start
                data[k] = words_of(lane)[start:start + plen]
            arr = _patch_program(arr, idxs, starts, data)
        local[pi] = arr
        self._page_replace(key, ps, pi, arr)
        metrics.STACK_PAGES.inc(event="patch", encoding="dense")
        return patched_words * 4, 0

    def _page_replace(self, key, ps: PagedStack, pi: int, arr):
        """Swap a page's array in place (patch/rebuild of a page that
        was resident).  Same-size swaps keep the reservation; a size
        change (encode flip, drift re-encode) releases the old bytes
        and re-reserves at the new size.  If a concurrent reclaim
        evicted the slot meanwhile, this becomes an install
        (re-reserve)."""
        nb_new = encode.page_nbytes(arr)
        release = 0
        with self._lock:
            was = ps.pages[pi]
            if was is not None:
                nb_old = _unseat(was)
                if nb_old == nb_new:
                    ps.pages[pi] = arr
                    self._seat_locked(key, pi, arr)
                    ps.last_access = time.time()
                    return
                ps.pages[pi] = None
                self._sync_entry_locked(key, ps)
                release = nb_old
        if release:
            self._client.release(release, device=ps.device_of(pi))
        self._page_install(key, ps, pi, arr)

    def _seat_locked(self, key, pi: int, page):
        """A page taking slot `pi` of `key`'s stack: an encoded one
        may keep the dense block its next expansion makes."""
        if encode.is_encoded(page):
            page.keeper = functools.partial(self._keep_dense, key, pi)

    def _keep_dense(self, key, pi: int, page, block) -> bool:
        """Keep `block`, the expansion of the encoded page in slot `pi`
        of `key`'s stack, beside the page's payload while the page is
        resident.  It is charged to this client on the page's device,
        as spare bytes: never at the cost of a resident page or of
        another client's bytes (the reservation's reclaim sheds this
        cache's kept blocks only), and only while the device keeps
        room for its programs.  False: the block serves this access
        alone."""
        nb = int(block.nbytes)
        with self._lock:
            ps = self._seated_locked(key, pi, page)
        if ps is None:
            return False
        slot = ps.device_of(pi)
        _KEEP_TLS.on = True
        try:
            if not self._client.reserve(nb, trigger="keep", device=slot,
                                        spare=True):
                return False
        finally:
            _KEEP_TLS.on = False
        with self._lock:
            kept = self._seated_locked(key, pi, page) is ps
            if kept:
                page.kept = block
                self._sync_entry_locked(key, ps)
                shed, shed_map = self._enforce_local_cap_locked()
        if not kept:
            self._client.release(nb, device=slot)
        elif shed:
            self._release_freed(shed, shed_map)
        return kept

    def _seated_locked(self, key, pi: int, page):
        """`key`'s stack where `page` still holds its slot `pi` with no
        block kept, else None."""
        ent = self._entries.get(key)
        ps = None if ent is None else ent[1]
        if (isinstance(ps, PagedStack) and pi < ps.n_pages
                and ps.pages[pi] is page and page.kept is None):
            return ps
        return None

    def _assemble(self, ps: PagedStack, arrs: list):
        ps.touch()
        if flight.active_acc() is not None:
            flight.note_pages(_page_mix(arrs))
        if getattr(_RAW_TLS, "on", False):
            # ragged page-table dispatch: hand the caller the raw page
            # snapshot — the fused program assembles them itself, so the
            # per-access assemble dispatch is skipped entirely (sparse
            # pages ride along encoded; consumers expand per page or
            # take the packed fast paths)
            return PageView(ps.shape, ps.lanes, ps.page_lanes, arrs,
                            page_device=ps.page_device,
                            lane_page=ps.lane_page,
                            lane_slot=ps.lane_slot,
                            shard_axis=ps.shard_axis)
        with flight.stage("stack.assemble"):
            if any(encode.is_encoded(a) for a in arrs):
                # decode-to-dense boundary: this consumer needs the
                # full tile operand (no packed arm for arbitrary plan
                # nodes)
                arrs = [encode.to_dense(a) for a in arrs]
            if ps.page_table is not None:
                # device-partitioned pages: single-array consumers
                # pull everything to one device and undo the placement
                # permutation (correct-but-slower fallback — the mesh
                # program is the fast path)
                return _assemble_permuted(arrs, ps.lane_page,
                                          ps.lane_slot, ps.page_lanes,
                                          ps.shape)
            if len(arrs) == 1 and ps.lanes == ps.page_lanes:
                return arrs[0].reshape(ps.shape)
            return bm.assemble_pages(tuple(arrs), ps.shape)

    # -- budget / eviction ----------------------------------------------

    def _budget_cap(self) -> int:
        return (self.max_bytes if self.max_bytes is not None
                else self._ledger.budget())

    def _enforce_local_cap_locked(self) -> int:
        """Shed down to the LOCAL max_bytes cap (no-op when None —
        the ledger governs).  Returns bytes to release to the ledger
        (caller releases outside the lock)."""
        if self.max_bytes is None or self._bytes <= self.max_bytes:
            return 0, {}
        return self._shed_locked(self._bytes - self.max_bytes)

    def _shed_locked(self, need: int, pages: bool = True):
        """Evict ~need bytes, ENTRY-concentrated: order entries by
        cost-aware score (memory/policy.py — age / rebuild-weight /
        frequency).  Kept dense blocks go first, in that order (one
        device program remakes a block, where a page costs a host
        rebuild); then, where ``pages``, drain the victim's pages
        coldest-first, stopping mid-entry the moment enough is
        freed.  Concentration
        keeps sibling operands complete (spreading page evictions
        across entries would break every operand at once — measured
        pathological); the page-granular STOP is the paged win: the
        marginal entry loses only the bytes pressure demanded, and
        the next access restores just those pages.  Returns
        ``(freed_bytes, {device slot: labeled bytes})``; the caller
        releases them to the ledger (``_release_freed``) so per-device
        occupancy stays truthful under eviction."""
        from pilosa_tpu.memory import policy
        freed = 0
        dev_map: dict[int, int] = {}
        now = time.time()
        cands = []
        for k, ent in self._entries.items():
            payload = ent[1]
            if isinstance(payload, PagedStack):
                if not any(p is not None for p in payload.pages):
                    continue
                cands.append((payload.last_access, payload.weight,
                              payload.hits, ("paged", k, payload)))
            elif ent[2]:
                cands.append((ent[3], 1.0, 1, ("whole", k, None)))
        order = policy.victim_order(cands, now)
        for _la, _w, _h, (kind, k, ps) in order:
            if freed >= need:
                break
            if kind == "whole":
                continue
            dropped = False
            for pi, p in enumerate(ps.pages):
                if freed >= need:
                    break
                if not encode.is_encoded(p) or p.kept is None:
                    continue
                nb_k = int(p.kept.nbytes)
                p.kept = None
                dropped = True
                freed += nb_k
                slot = ps.device_of(pi)
                if slot is not None:
                    dev_map[slot] = dev_map.get(slot, 0) + nb_k
            if dropped:
                self._sync_entry_locked(k, ps)
        if not pages:
            return freed, dev_map
        for _la, _w, _h, (kind, k, ps) in order:
            if freed >= need:
                break
            if kind == "whole":
                ent = self._entries.pop(k, None)
                if ent is not None:
                    self._bytes -= ent[2]
                    freed += ent[2]
                continue
            for pi, p in enumerate(ps.pages):
                if freed >= need:
                    break
                if p is None:
                    continue
                ps.pages[pi] = None
                nb_p = _unseat(p)
                freed += nb_p
                slot = ps.device_of(pi)
                if slot is not None:
                    dev_map[slot] = dev_map.get(slot, 0) + nb_p
                metrics.STACK_PAGES.inc(event="evict",
                                        encoding=encode.page_kind(p))
            self._sync_entry_locked(k, ps)
            if not any(p is not None for p in ps.pages):
                # fully drained: drop the skeleton too, or distinct
                # keys accumulate zombie entries forever on a
                # long-lived server (pre-paging, byte pressure popped
                # whole entries and bounded the dict implicitly)
                self._entries.pop(k, None)
        return freed, dev_map

    def _reclaim(self, need: int) -> int:
        """Ledger reclaim callback (cross-client pressure); while a
        kept block is being reserved, it sheds kept blocks only."""
        pages = not getattr(_KEEP_TLS, "on", False)
        with self._lock:
            freed, dev_map = self._shed_locked(int(need), pages=pages)
        if freed:
            self._release_freed(freed, dev_map)
        return freed

    def _cold_ts(self) -> float:
        """Coldest resident page's timestamp (0 when whole entries —
        no stamps — are present: conservatively coldest)."""
        with self._lock:
            ts = None
            for ent in self._entries.values():
                if isinstance(ent[1], PagedStack):
                    ps = ent[1]
                    if any(p is not None for p in ps.pages) and (
                            ts is None or ps.last_access < ts):
                        ts = ps.last_access
                elif ent[2]:
                    return 0.0
            return ts or 0.0

    def _sync_entry_locked(self, key, ps: PagedStack):
        """Re-derive an entry's accounted bytes from its resident
        pages (called after any page install/evict)."""
        ent = self._entries.get(key)
        if ent is not None and ent[1] is ps:
            nb = ps.resident_bytes()
            self._bytes += nb - ent[2]
            self._entries[key] = (ent[0], ps, nb, ent[3])

    def _drop_pages(self, key, ps: PagedStack):
        freed = 0
        dev_map: dict[int, int] = {}
        with self._lock:
            for pi, p in enumerate(ps.pages):
                if p is not None:
                    ps.pages[pi] = None
                    nb_p = _unseat(p)
                    freed += nb_p
                    slot = ps.device_of(pi)
                    if slot is not None:
                        dev_map[slot] = dev_map.get(slot, 0) + nb_p
            self._sync_entry_locked(key, ps)
        if freed:
            self._release_freed(freed, dev_map)

    def _note_too_big(self, key, nbytes: int, cap: int):
        with self._lock:
            self.too_big += 1
            warn = key not in self._warned_big
            if warn:
                self._warned_big.add(key)
                while len(self._warned_big) > self._MAX_WARNED:
                    self._warned_big.pop()
        metrics.STACK_CACHE.inc(outcome="too_big")
        if warn:
            _log.warning(
                "stack %r (%d bytes) alone exceeds the device budget "
                "(%d bytes); it is rebuilt and served unretained on "
                "every access", key, nbytes, cap)

    # -- prefetch (memory/policy.py Prefetcher) -------------------------

    def _remember_recipe(self, key, build, patcher, recipe) -> str:
        with self._lock:
            fp = self._key_fps.get(key)
            if fp is None:
                fp = hashlib.blake2b(repr(key).encode(),
                                     digest_size=8).hexdigest()
                self._key_fps[key] = fp
            self._recipes[fp] = (key, build, patcher, recipe)
            self._recipes.move_to_end(fp)
            while len(self._recipes) > self._MAX_RECIPES:
                _ofp, (okey, _b, _p, _r) = self._recipes.popitem(
                    last=False)
                self._key_fps.pop(okey, None)
        return fp

    def prewarm(self, fp: str) -> bool:
        """Rebuild a key's missing pages from its recorded recipe at
        CURRENT fragment versions — the prefetcher's warm target.
        No-op (False) for unknown keys and fully-resident fresh
        entries."""
        with self._lock:
            rec = self._recipes.get(fp)
        if rec is None:
            return False
        key, build, patcher, recipe = rec
        if recipe.alive_fn is not None and not recipe.alive_fn():
            # the captured fields were dropped/recreated: no live
            # query computes these (gen, version) stamps anymore, so
            # warming would upload + budget-reserve dead data.  Drop
            # the recipe so it stops pinning the old fragments too.
            with self._lock:
                if self._recipes.get(fp) is rec:
                    self._recipes.pop(fp)
                    self._key_fps.pop(key, None)
            return False
        try:
            versions = recipe.versions_fn()
        except Exception:
            return False
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None and ent[0] == versions:
                payload = ent[1]
                if (not isinstance(payload, PagedStack)
                        or not payload.missing()):
                    return False
        self.get(key, versions, build, patcher, recipe)
        return True

    def clear(self):
        dev_map: dict[int, int] = {}
        with self._lock:
            total = self._bytes
            for ent in self._entries.values():
                ps = ent[1]
                if (isinstance(ps, PagedStack)
                        and ps.page_device is not None):
                    for slot, nb in ps.device_resident_bytes().items():
                        if slot >= 0:
                            dev_map[slot] = dev_map.get(slot, 0) + nb
            self._entries.clear()
            self._bytes = 0
        if total:
            self._release_freed(total, dev_map)

    @property
    def nbytes(self) -> int:
        return self._bytes


# ---------------------------------------------------------------------------
# per-structure jit cache
# ---------------------------------------------------------------------------

# Bounded LRU of compiled executables keyed by plan structure.  Shared
# across Executor instances (two engines over the same schema compile
# identical programs); bounded so a long-lived server that sees many
# distinct tree shapes doesn't accumulate executables forever.
# Entries are (fn, ledger_reserved_bytes): executables claim an
# ESTIMATED per-entry device footprint from the process budget ledger
# (their true HBM cost is opaque to the host), so pressure in the
# stack caches can shed cold executables and vice versa; a denied
# reservation still caches (reserved=0) — compilation reuse matters
# more than exact accounting for these small buffers.
_JIT_CACHE: OrderedDict[str, tuple] = OrderedDict()
_JIT_CACHE_MAX = 256
_JIT_LOCK = threading.Lock()
_JIT_EST_BYTES = int(os.environ.get(
    "PILOSA_TPU_JIT_ENTRY_EST_BYTES", str(64 << 10)))
_JIT_CLIENT_LOCK = threading.Lock()
_JIT_CLIENT = None


def _jit_client():
    global _JIT_CLIENT
    with _JIT_CLIENT_LOCK:
        if _JIT_CLIENT is None:
            _JIT_CLIENT = memory.ledger().register(
                "jit_cache", reclaim=_jit_reclaim)
        return _JIT_CLIENT


def _jit_reclaim(need: int) -> int:
    """Ledger reclaim callback: shed LEDGERED executables, oldest
    first, from both jit caches.  Zero-reserved entries are skipped —
    evicting them frees no device bytes, only recompilation time."""
    freed = 0
    evicted_sigs = []
    with _JIT_LOCK:
        for sig in list(_JIT_CACHE):
            if freed >= need:
                break
            if _JIT_CACHE[sig][1] <= 0:
                continue
            freed += _JIT_CACHE.pop(sig)[1]
            evicted_sigs.append(sig)
            metrics.JIT_CACHE.inc(cache="plan", event="evict")
        metrics.JIT_CACHE_ENTRIES.set(len(_JIT_CACHE), cache="plan")
    with _GB_KERNEL_LOCK:
        for key in list(_GB_KERNEL_JIT):
            if freed >= need:
                break
            if _GB_KERNEL_JIT[key][1] <= 0:
                continue
            freed += _GB_KERNEL_JIT.pop(key)[1]
            metrics.JIT_CACHE.inc(cache="groupby_kernel",
                                  event="evict")
        metrics.JIT_CACHE_ENTRIES.set(len(_GB_KERNEL_JIT),
                                      cache="groupby_kernel")
    for sig in evicted_sigs:
        _forget_dispatch_sig(sig)
    if freed and _JIT_CLIENT is not None:
        _JIT_CLIENT.release(freed)
    return freed

_NARY_OPS = {
    "union": bm.union,
    "intersect": bm.intersect,
    "difference": bm.difference,
    "xor": bm.xor,
}

# jitted wrappers around kernels.groupby_sum keyed by static shape
# facts (an un-jitted call pays one dispatch per pad/transpose
# around the pallas_call).  Bounded LRU
# like _JIT_CACHE: a long-lived server sweeping GroupBy shapes must
# not accumulate executables without limit.
_GB_KERNEL_JIT: OrderedDict = OrderedDict()
_GB_KERNEL_JIT_MAX = 128
_GB_KERNEL_LOCK = threading.Lock()


def _gb_jit_get(key):
    with _GB_KERNEL_LOCK:
        ent = _GB_KERNEL_JIT.get(key)
        if ent is None:
            return None
        _GB_KERNEL_JIT.move_to_end(key)
        return ent[0]


def _gb_jit_put(key, fn):
    client = _jit_client()
    reserved = (_JIT_EST_BYTES
                if client.reserve(_JIT_EST_BYTES) else 0)
    released = 0
    with _GB_KERNEL_LOCK:
        _GB_KERNEL_JIT[key] = (fn, reserved)
        metrics.JIT_CACHE.inc(cache="groupby_kernel", event="insert")
        while len(_GB_KERNEL_JIT) > _GB_KERNEL_JIT_MAX:
            released += _GB_KERNEL_JIT.popitem(last=False)[1][1]
            metrics.JIT_CACHE.inc(cache="groupby_kernel",
                                  event="evict")
        metrics.JIT_CACHE_ENTRIES.set(len(_GB_KERNEL_JIT),
                                      cache="groupby_kernel")
    if released:
        client.release(released)

# one-pass group-code GroupBy bounds: the dense code space is
# 2^sum(ceil(log2 R_f)) — the host/XLA histogram tolerates up to 2^20
# codes (a few MB of accumulator), the Pallas kernel's one-hot lane
# axis and unrolled payload stay within VMEM/compile budgets below
# 4096 codes x depth 16; the packed body has neither bound (its
# accumulators are planned in walks, kernels._packed_passes)
_ONEPASS_MAX_CODES = 1 << 20
_ONEPASS_KERNEL_MAX_CODES = kernels.ONEHOT_MAX_CODES
_ONEPASS_KERNEL_MAX_DEPTH = 16
# a filtered one-pass Sum or count that the kernel walks over a
# selection first counts each field's rows under the filter
# (_groupby_pruned) where its walk is at least _PRUNE_MIN_UNITS (group,
# payload row) units: a prepass then costs at most a tenth of the walk
# it may shorten where the filter prunes nothing.  Measured on a v5e
# chip (ssb-sf100 at 144 shards, 32 vregs of words a shard): the
# prepass 6.46 ms at its median, 1.4 us a vreg; the packed walk 1.0 ns
# a unit a vreg.  A prepass is worth _PRUNE_UNITS units, at any shard
# count.  taxi-1b's Q4 (4,800 groups, count-only) and able-1b's 60
# groups (a 7-bit Sum, 540 units) walk their whole product
_PRUNE_UNITS = 1400
_PRUNE_MIN_UNITS = 10 * _PRUNE_UNITS


def _code_space(fields_rows):
    """Power-of-two digit layout of the dense group-code space:
    returns (bits_per_field, shift_per_field, n_codes).  Field f's
    digit (its row-list index) occupies bits [shift_f, shift_f+bits_f)
    of the code; codes with a digit >= R_f simply never occur."""
    bits = [bm.digit_bits(len(rl)) for _, rl in fields_rows]
    shifts, acc = [], 0
    for b in bits:
        shifts.append(acc)
        acc += b
    return bits, shifts, 1 << acc


def _code_digits(fields_rows) -> tuple:
    """((bits, rows), ...) per field in _code_space order: the static
    layout kernels.groupby_fused visits only the live codes by."""
    bits, _shifts, _n_codes = _code_space(fields_rows)
    return tuple((b, len(rl)) for b, (_, rl) in zip(bits, fields_rows))


def _groupby_unit_costs(fields_rows, n_combos: int, depth: int,
                        has_agg: bool, n_shards: int,
                        width_words: int) -> tuple[float, float]:
    """(one-pass units, per-combo units) in packed-word ops: the
    one-pass-vs-per-combo cost model shared by the gate
    (_groupby_onepass_ok) and the stats-catalog rate calibration
    (stats.note_gate at the execution sites).  Per-combo pays the
    full gather + popcount chain per combo; one-pass reads each
    stream once but pays a ~4x column-domain factor for the
    unpack/histogram of each payload row.  Sparse combo selections
    (paged tails, tiny products) stay per-combo under the static
    1:1 rates."""
    bits, _shifts, _n_codes = _code_space(fields_rows)
    agg_percombo = (2 + 2 * depth) if has_agg else 0
    agg_onepass = (2 + depth) if has_agg else 0
    per_combo = n_combos * (len(fields_rows) + 1 + agg_percombo)
    one_pass = (sum(len(rl) for _, rl in fields_rows)
                + 4 * (sum(bits) + 1 + agg_onepass))
    scale = max(n_shards, 1) * max(width_words, 1)
    return float(one_pass * scale), float(per_combo * scale)


def _combo_codes(shifts, combos_arr: np.ndarray) -> np.ndarray:
    """Map combo index tuples (C, nf) -> dense group codes (C,)."""
    codes = np.zeros(combos_arr.shape[0], dtype=np.int64)
    for fi, sh in enumerate(shifts):
        codes |= combos_arr[:, fi].astype(np.int64) << sh
    return codes


def _forced_arm() -> str | None:
    """PILOSA_TPU_GROUPBY_ONEPASS_ARM=fused|xla, else None: the one
    read of the seam by which CPU tests, chip_smoke.py --rehearse-cpu
    and benchmark/run.py --rehearse-cpu run the kernel in interpret
    mode.  It stands in for the backend, and for nothing else."""
    import os
    forced = os.environ.get("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "")
    return forced if forced in ("fused", "xla") else None


def _onepass_arm(n_codes: int, depth: int, mesh_minmax: bool = False,
                 body: str | None = None) -> str:
    """Which one-pass device program serves the histogram:

    - "fused" — the single-pass kernel on packed words
      (groupby_fused; `body` is the one its shapes take,
      kernels.fused_plan's choice): on a TPU, inside
      _ONEPASS_KERNEL_MAX_*; past _ONEPASS_KERNEL_MAX_CODES where the
      packed body walks the live groups (a 10 x 8 x 60 GroupBy has
      8,192 codes and 4,800 groups: one walk, and 12 over slices of
      the 60 rows with a 9-bit Sum) — the one-hot body's lane axis is
      the code space and never goes there
    - "xla"   — the scatter-add form (groupby_codes_xla): off a TPU
      (a CPU would only interpret the kernel), past the bounds (a
      2^20-code value histogram under the kernel would build a
      million-lane one-hot), and for Min/Max on a mesh (the Pallas
      call is opaque to the partitioner and would replicate the stack;
      the scatter shards under GSPMD)

    _forced_arm() stands in for the backend and lifts no bound."""
    if mesh_minmax or (depth > _ONEPASS_KERNEL_MAX_DEPTH
                       and body != "packed"):
        return "xla"
    if n_codes > _ONEPASS_KERNEL_MAX_CODES and body != "packed":
        return "xla"
    return _forced_arm() or (
        "fused" if jax.default_backend() == "tpu" else "xla")


def _onepass_plan(n_codes: int, depth: int, digits, signed: bool,
                  minmax: bool = False, mesh_minmax: bool = False):
    """(arm, body, passes) of one one-pass dispatch, derived once from
    what the kernel would be handed: `depth` is the payload's (0 with
    no aggregate), `digits` the fields' layout (_code_digits)."""
    body, passes = kernels.fused_plan(digits, depth, signed, minmax)
    return _onepass_arm(n_codes, depth, mesh_minmax, body), body, passes


def _count_onepass(plan, path: str, depth: int, walked: int) -> None:
    """One one-pass dispatch in the counters, where its arm is known:
    pilosa_groupby_onepass_total{arm}, the groups it accumulates
    (`walked`, into the flight record too) and its payload's depth
    (0 without an aggregate) always, and for the kernel the body its
    shapes take and the walks it makes over its operands.  `plan` is
    _onepass_plan's; the host histogram counts as ("host", None, 0)."""
    from pilosa_tpu.obs.metrics import (
        GROUPBY_FUSED,
        GROUPBY_ONEPASS,
        GROUPBY_PASSES,
        GROUPBY_PAYLOAD,
        GROUPBY_WALKED,
    )
    arm, body, passes = plan
    GROUPBY_ONEPASS.inc(arm=arm)
    GROUPBY_WALKED.inc(walked)
    GROUPBY_PAYLOAD.inc(arm=arm, depth="gt16" if depth > 16 else "le16")
    flight.note_walked(walked)
    if arm == "fused":
        GROUPBY_FUSED.inc(path=path, body=body)
        GROUPBY_PASSES.inc(passes)


def _walked(arm: str, body, digits, n_codes: int) -> int:
    """Groups a one-pass dispatch accumulates: the packed body visits
    the product of the fields' rows, the other arms the code space."""
    if arm == "fused" and body == "packed":
        return int(np.prod([rows for _b, rows in digits], dtype=np.int64))
    return n_codes


def _onepass_gb(arm: str, digits=None):
    """The arm's histogram callable (shared by jit + shard_map).
    `digits` (_code_digits) tells the fused kernel which codes are
    live; the XLA form histograms the whole code space."""
    if arm == "fused":
        return functools.partial(kernels.groupby_fused, digits=digits)
    return kernels.groupby_codes_xla


def _onepass_unpack(flat, n_codes: int, depth: int, has_planes: bool,
                    minmax: bool = False):
    """Split the one-pass paths' single flat device fetch back into
    (counts, nn, pos, neg[, mm]) int64 over the dense code space."""
    flat = np.asarray(flat, dtype=np.int64)
    if not has_planes:
        return flat[:n_codes], None, None, None
    g = n_codes
    counts, nn = flat[:g], flat[g:2 * g]
    pos = flat[2 * g:2 * g + g * depth].reshape(g, depth)
    end = 2 * g + 2 * g * depth
    neg = flat[2 * g + g * depth:end].reshape(g, depth)
    if not minmax:
        return counts, nn, pos, neg
    return counts, nn, pos, neg, flat[end:].reshape(4, g)


def _groupby_onepass_jit(arm: str, has_planes: bool,
                         has_filter: bool, signed: bool, n_codes: int,
                         minmax: bool = False, digits=None):
    """Single-device jitted one-pass program: group-code stack in,
    ONE flat histogram array out (one fetch round trip)."""
    key = ("onepass", arm, has_planes, has_filter, signed,
           n_codes, minmax, digits)
    fn = _gb_jit_get(key)
    if fn is not None:
        return fn

    @bm.named("groupby_onepass")
    def run(cg, filt, planes):
        cp, valid = cg[:, :-1], cg[:, -1]
        if has_filter:
            valid = jnp.bitwise_and(valid, filt)
        gb = _onepass_gb(arm, digits)
        if minmax:
            c, n, p, g, mm = gb(cp, valid, planes, n_codes, signed,
                                minmax=True)
            return jnp.concatenate(
                [c, n, p.ravel(), g.ravel(), mm.ravel()])
        c, n, p, g = gb(cp, valid, planes, n_codes, signed)
        if not has_planes:
            return c
        return jnp.concatenate([c, n, p.ravel(), g.ravel()])

    fn = jax.jit(run)
    _gb_jit_put(key, fn)
    return fn


def product_combos(lens) -> np.ndarray:
    """Every combination of the fields' row positions, (C, nf), in
    product order (first field slowest): the matrix that maps 1:1 onto
    the dense group-code space (_combo_codes)."""
    return np.indices(lens).reshape(len(lens), -1).T.astype(np.int64)


def _prunes(fields_rows, depth: int, selects: bool) -> bool:
    """Whether a filtered one-pass GroupBy over these fields first
    drops the rows the filter leaves no column in (_groupby_pruned):
    where its dense code space is past _ONEPASS_MAX_CODES (it cannot be
    formed at all), or where the kernel walks a selection of the cached
    group-code stack (`selects`, StackedEngine._selects) and the walk
    is at least _PRUNE_MIN_UNITS.  The other arms would follow the
    prepass with a group-code stack of the rows kept, a new one for
    each filter: inside the bound they walk the whole code space."""
    _bits, _shifts, n_codes = _code_space(fields_rows)
    if n_codes > _ONEPASS_MAX_CODES:
        return True
    n_combos = int(np.prod([len(rl) for _f, rl in fields_rows],
                           dtype=np.int64))
    return selects and (n_combos * kernels._payload_rows(depth, False)
                        >= _PRUNE_MIN_UNITS)


def _pack_bits(mask: np.ndarray) -> np.ndarray:
    """A column mask (bool, one a column) as packed uint32 words."""
    return np.packbits(mask, bitorder="little").view(np.uint32)


def _bucket(n: int) -> int:
    """Rows a pruned field is walked with: its live rows rounded up to
    a power of two, so that the programs are a small family."""
    return 1 << max(n - 1, 0).bit_length()


def _groupby_marginals_jit(arm: str, digits, has_filter: bool):
    """The pruning prepass: per field, the count of each of its rows
    under `valid` (and the filter), read off the group-code stack's
    digit planes as a one-field histogram — two-level in the packed
    body (kernels.dense_digits).  One flat array out, the fields'
    rows in order."""
    key = ("marginals", arm, digits, has_filter)
    fn = _gb_jit_get(key)
    if fn is not None:
        return fn

    @bm.named("groupby_prune")
    def run(cg, filt):
        valid = cg[:, -1]
        if has_filter:
            valid = jnp.bitwise_and(valid, filt)
        out, at = [], 0
        for bits, rows in digits:
            if not bits:        # a one-row field: every valid column
                out.append(jnp.sum(bm.count(valid)).astype(jnp.int32)[None])
            else:
                planes = cg[:, at:at + bits]
                if arm == "fused":
                    c = kernels.groupby_fused(
                        planes, valid, None, 1 << bits, False,
                        digits=kernels.dense_digits(bits))[0]
                else:
                    c = kernels.groupby_codes_xla(planes, valid, None,
                                                  1 << bits, False)[0]
                out.append(c[:rows])
            at += bits
        return jnp.concatenate(out)

    fn = jax.jit(run)
    _gb_jit_put(key, fn)
    return fn


def _groupby_selected_jit(has_planes: bool, has_filter: bool,
                          signed: bool, digits):
    """The packed kernel over selected rows of the group-code stack
    (kernels.groupby_fused's `sel`): one flat histogram over the
    selection's codes out, as _groupby_onepass_jit's."""
    key = ("selected", has_planes, has_filter, signed, digits)
    fn = _gb_jit_get(key)
    if fn is not None:
        return fn
    n_codes = int(np.prod([rows for _b, rows in digits], dtype=np.int64))

    @bm.named("groupby_onepass")
    def run(cg, filt, planes, sel):
        cp, valid = cg[:, :-1], cg[:, -1]
        if has_filter:
            valid = jnp.bitwise_and(valid, filt)
        c, n, p, g = kernels.groupby_fused(cp, valid, planes, n_codes,
                                           signed, digits=digits, sel=sel)
        if not has_planes:
            return c
        return jnp.concatenate([c, n, p.ravel(), g.ravel()])

    fn = jax.jit(run)
    _gb_jit_put(key, fn)
    return fn


def _groupby_onepass_shard_map(mesh, arm: str, has_planes: bool,
                               has_filter: bool, signed: bool,
                               n_codes: int, digits=None):
    """Mesh one-pass wrapper: every device histograms its local shard
    slice of the flat-placed group-code stack, partial (K, G) tables
    psum over the whole mesh — the histogram is combo-count-free, so
    the collective payload is O(G), not O(C*S).  (Min/Max tables
    combine with max/min, not psum — mesh callers stay on Sum.)"""
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.parallel.mesh import shard_map_nocheck

    key = ("onepass_mesh", id(mesh), arm, has_planes,
           has_filter, signed, n_codes, digits)
    fn = _gb_jit_get(key)
    if fn is not None:
        return fn
    axes = ("rows", "shards")
    in_specs = [P(axes, None, None)]
    if has_filter:
        in_specs.append(P(axes, None))
    if has_planes:
        in_specs.append(P(axes, None, None))

    @bm.named("groupby_onepass_mesh")
    def body(cg, *rest):
        filt = rest[0] if has_filter else None
        planes = rest[-1] if has_planes else None
        cp, valid = cg[:, :-1], cg[:, -1]
        if filt is not None:
            valid = jnp.bitwise_and(valid, filt)
        gb = _onepass_gb(arm, digits)
        c, n, p, g = gb(cp, valid, planes, n_codes, signed)
        flat = c if not has_planes else jnp.concatenate(
            [c, n, p.ravel(), g.ravel()])
        return jax.lax.psum(flat, axes)

    fn = jax.jit(shard_map_nocheck(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=P(None)))
    _gb_jit_put(key, fn)
    return fn


def _groupby_kernel_shard_map(mesh, nf: int, has_planes: bool,
                              signed: bool):
    """shard_map wrapper: every device runs the fused kernel on its
    local shard slice, partial results psum over the whole mesh —
    the kernel analog of the stacked engine's in-program reduce."""
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.parallel.mesh import shard_map_nocheck

    key = (id(mesh), nf, has_planes, signed)
    fn = _gb_jit_get(key)
    if fn is not None:
        return fn
    axes = ("rows", "shards")
    stack_spec = tuple(P(None, axes, None) for _ in range(nf))
    if has_planes:
        in_specs = (stack_spec, P(None, None), P(axes, None, None))

        @bm.named("groupby_percombo_mesh")
        def body(stacks, sel, planes):
            c, n, p, g = kernels.groupby_sum(
                list(stacks), sel, planes, signed=signed)
            return jax.lax.psum(jnp.concatenate(
                [c, n, p.ravel(), g.ravel()]), axes)
    else:
        in_specs = (stack_spec, P(None, None))

        @bm.named("groupby_percombo_mesh")
        def body(stacks, sel):
            c, _n, _p, _g = kernels.groupby_sum(
                list(stacks), sel, None, signed=signed)
            return jax.lax.psum(c, axes)

    run = jax.jit(shard_map_nocheck(
        body, mesh=mesh, in_specs=in_specs, out_specs=P(None)))
    _gb_jit_put(key, run)
    return run


def _zero_groupby_result(n_combos: int, depth: int, agg_field,
                         agg_op: str = "sum"):
    """(counts, agg) zeros for a provably-empty filter."""
    if agg_field is None:
        zero_agg = None
    elif agg_op in ("min", "max"):
        zero_agg = (np.zeros(n_combos, dtype=np.int64),
                    np.zeros(n_combos, dtype=np.int64))
    else:
        zero_agg = (np.zeros(n_combos, dtype=np.int64),
                    np.zeros((n_combos, depth), dtype=np.int64),
                    np.zeros((n_combos, depth), dtype=np.int64))
    return np.zeros(n_combos, dtype=np.int64), zero_agg


def _groupby_kernel_jit(nf: int, has_planes: bool, signed: bool):
    key = (nf, has_planes, signed)
    fn = _gb_jit_get(key)
    if fn is None:
        @bm.named("groupby_percombo")
        def run(stacks, sel, planes):
            c, n, p, g = kernels.groupby_sum(
                list(stacks), sel, planes, signed=signed)
            if not has_planes:
                return c
            # one flat fetch: each extra device->host pull is a
            # separate synchronous transfer
            return jnp.concatenate(
                [c, n, p.ravel(), g.ravel()])
        fn = jax.jit(run)
        _gb_jit_put(key, fn)
    return fn

_BSI_CMP = {
    "eq": lambda p, pb, neg: bsi_ops.range_eq(p, pb, neg),
    "neq": lambda p, pb, neg: bsi_ops.range_neq(p, pb, neg),
    "lt": lambda p, pb, neg: bsi_ops.range_lt(p, pb, neg, allow_eq=False),
    "lte": lambda p, pb, neg: bsi_ops.range_lt(p, pb, neg, allow_eq=True),
    "gt": lambda p, pb, neg: bsi_ops.range_gt(p, pb, neg, allow_eq=False),
    "gte": lambda p, pb, neg: bsi_ops.range_gt(p, pb, neg, allow_eq=True),
}


def _eval(node, leaves, params):
    """Trace-time recursive evaluation of the static IR."""
    k = node[0]
    if k == "leaf":
        return leaves[node[1]]
    if k == "zeros":
        return jnp.uint32(0)  # broadcasts through every bitwise op
    if k == "nary":
        op = _NARY_OPS[node[1]]
        acc = _eval(node[2][0], leaves, params)
        for c in node[2][1:]:
            acc = op(acc, _eval(c, leaves, params))
        return acc
    if k == "not":
        return bm.difference(leaves[node[1]], _eval(node[2], leaves, params))
    if k == "qcover":
        # time-quantum cover: union of per-view single-view stacks
        acc = leaves[node[1][0]]
        for i in node[1][1:]:
            acc = bm.union(acc, leaves[i])
        return acc
    if k == "shift":
        return bm.shift(_eval(node[2], leaves, params), node[1])
    # ops/bsi.py indexes a plane on the leading axis and is
    # elementwise in the rest: the whole field goes through in one call
    if k == "bsi_cmp":
        planes = _planes(leaves[node[1]])
        fn = _BSI_CMP[node[2]]
        pb, neg = params[node[3]], params[node[4]]
        with jax.named_scope("bsi_compare"):
            return fn(planes, pb, neg)
    if k == "bsi_between":
        planes = _planes(leaves[node[1]])
        ab, bb = params[node[2]], params[node[3]]
        an, bn = params[node[4]], params[node[5]]
        with jax.named_scope("bsi_compare"):
            return bsi_ops.range_between(planes, ab, bb, an, bn)
    if k == "bsi_notnull":
        return _planes(leaves[node[1]])[bsi_ops.BSI_EXISTS_BIT]
    if k == "bsi_null":
        exists = _planes(leaves[node[1]])[bsi_ops.BSI_EXISTS_BIT]
        return bm.difference(leaves[node[2]], exists)
    raise AssertionError(f"bad IR node {k}")


def _planes(leaf):
    """A BSI leaf as ops/bsi.py reads it, plane first: the planes a
    ragged program gathered out of its pages (bm.concat_pages), or
    the resident (S, 2+depth, W) stack seen as (2+depth, S, W)."""
    return leaf if isinstance(leaf, tuple) else jnp.swapaxes(leaf, 0, 1)


def _plane_readers(plan, xla: set, kernel: set):
    """Into `xla` the leaves that a compare, a null test or a sum
    under `plan` reads as BSI planes, into `kernel` those a GroupBy
    kernel blocks: each names its leaf in a fixed place of its
    tuple."""
    if isinstance(plan, tuple) and plan:
        k = plan[0]
        if k in ("bsi_cmp", "bsi_between", "bsi_notnull", "bsi_null",
                 "bsi_sum"):
            xla.add(plan[1])
        elif k == "gb_hist" and plan[3] is not None:
            kernel.add(plan[3])
        for c in plan:
            _plane_readers(c, xla, kernel)


def _as_stack(out, leaves):
    """Shape guard: tree evaluation always yields an (S, W) stack.

    Zeros nodes are constant-folded away by the builder, so a scalar
    can only reach here through an IR bug — fail loudly rather than
    broadcasting to a guessed shape.
    """
    assert out.ndim >= 2, "stacked IR produced a scalar (unfolded zeros?)"
    return out


def _filter(tree, leaves, params):
    """A sub-plan's filter tree as an (S, W) stack, under the `filter`
    scope of a profile."""
    with jax.named_scope("filter"):
        return _as_stack(_eval(tree, leaves, params), leaves)


# the jax.named_scope of each sub-plan kind inside a plan program: op
# metadata for a reader of the profile (XProf, Perfetto), and the name
# of any op XLA does not fuse
_SCOPES = {"words": "filter", "count": "count", "bsi_sum": "bsi_sum",
           "gb_hist": "groupby", "groupby": "groupby",
           "row_counts": "topn"}


def _plan_run(plan):
    """Un-jitted `run(leaves, params)` for one plan (see _compiled).
    Split out so the "multi" kind — the cross-query batcher's fused
    program (executor/serving.py) — can compose several subplans into
    ONE traced function sharing the leaf/param tuples."""
    kind = plan[0]
    if kind == "multi":
        # fused batch: every subplan evaluates in one program (one
        # device dispatch for N concurrent queries).  groupby is
        # excluded — its run() reads the combo selector from
        # params[-1], which only a solo plan positions.
        assert all(p[0] != "groupby" for p in plan[1])
        runs = tuple(_plan_run(p) for p in plan[1])

        def run(leaves, params):
            return tuple(r(leaves, params) for r in runs)
        return run
    if kind == "ragged":
        # the cross-index page-table program (executor/ragged.py):
        #   ("ragged", n_pages, vmeta, subs)
        # leaves = the n_pages page arrays, then the direct leaves.
        # vmeta = ((leaf_start, n_pages, shape), ...): each virtual
        # leaf owns a static run of the page leaves — its real pages,
        # each once — and is assembled from them exactly once, in the
        # shape its consumers read (bm.concat_pages: a BSI leaf as
        # its planes for the compare and the sum, as the resident
        # stack where a GroupBy kernel blocks it).  subs evaluate
        # over the combined virtual+direct leaf space like "multi",
        # except ("segcount", ((leaf_start, n_pages), ...), sparam,
        # nseg) entries, which reduce a whole family of single-leaf
        # Counts through one popcount+segment-sum over the
        # concatenation of the members' pages without ever
        # materializing their operands.
        n_pages, vmeta, subs = plan[1], plan[2], plan[3]
        runs = tuple(None if s[0] == "segcount" else _plan_run(s)
                     for s in subs)
        # virtual leaves come first in the combined leaf space
        xla, kernel = set(), set()
        _plane_readers(subs, xla, kernel)
        gathered = xla - kernel

        def run(leaves, params):
            with jax.named_scope("page_gather"):
                vl = tuple(bm.concat_pages(leaves[start:start + n], shape,
                                           planes=i in gathered)
                           for i, (start, n, shape) in enumerate(vmeta))
            all_leaves = vl + tuple(leaves[n_pages:])
            outs = []
            for s, r in zip(subs, runs):
                if r is None:
                    _k, members, si, nseg = s
                    with jax.named_scope("count"):
                        lanes = jnp.concatenate(
                            [p for start, n in members
                             for p in leaves[start:start + n]], axis=0)
                        outs.append(bm.segment_count(
                            lanes, params[si], nseg))
                else:
                    outs.append(r(all_leaves, params))
            return tuple(outs)
        return run
    if kind == "ragged_mesh":
        # the mesh-sharded fused program (executor/ragged.py):
        #   ("ragged_mesh", ndev, placement_epoch, n_base, buckets,
        #    vmeta, subs, combines)
        # ONE shard_map program over the serving mesh: each device
        # gathers virtual leaves out of ITS page pool slice (leaves =
        # per-bucket (ndev, pool, page_lanes, W) arrays, P("dev")),
        # evaluates every sub over its owned shards, and the partials
        # combine INSIDE the program — psum trees for reduced outputs,
        # dump-row scatter-adds re-assembling per-shard outputs — so
        # no host ever merges device partials.  Padded local shard
        # positions read the pool's guaranteed-zero tail page; zero
        # shards are harmless for every reduction here (the
        # place_shards invariant).
        from jax.sharding import PartitionSpec as P

        from pilosa_tpu.memory import placement
        from pilosa_tpu.parallel.mesh import shard_map_nocheck
        ndev, _ep, n_base, buckets, vmeta, subs, combines = plan[1:8]
        smesh = placement.serving_mesh()
        assert smesh.devices.size == ndev
        nb = len(buckets)
        runs = tuple(None if s[0] == "segcount" else _plan_run(s)
                     for s in subs)

        def _combine(o, comb, prms):
            if comb[0] == "psum":
                return jax.lax.psum(o, "dev")
            if comb[0] == "scatter":
                _c, gi, s, axis = comb
                spos = prms[gi]
                if axis == 0:
                    z = jnp.zeros((s + 1,) + o.shape[1:], o.dtype)
                    return jax.lax.psum(z.at[spos].add(o), "dev")[:s]
                z = jnp.zeros(o.shape[:1] + (s + 1,) + o.shape[2:],
                              o.dtype)
                return jax.lax.psum(z.at[:, spos].add(o),
                                    "dev")[:, :s]
            _c, gi, s = comb                          # scatter3
            spos = prms[gi]

            def sc(x):
                z = jnp.zeros((s + 1,) + x.shape[1:], x.dtype)
                return jax.lax.psum(z.at[spos].add(x), "dev")[:s]
            return tuple(sc(x) for x in o)

        def body(*ops):
            pools = ops[:nb]
            # mesh params arrive (1, X) per device — strip the axis
            prms = (tuple(ops[nb:nb + n_base])
                    + tuple(m[0] for m in ops[nb + n_base:]))
            flats = [pool.reshape(p2 * pl, w)
                     for (p2, pl, w), pool in zip(buckets, pools)]
            vl = tuple(flats[b][prms[gi]].reshape(shape)
                       for b, gi, shape in vmeta)
            outs = []
            for s, r, comb in zip(subs, runs, combines):
                if r is None:
                    _k, b, gi, si, nseg = s
                    o = bm.segment_count(flats[b][prms[gi]],
                                         prms[si], nseg)
                else:
                    o = r(vl, prms)
                outs.append(_combine(o, comb, prms))
            return tuple(outs)

        def run(leaves, params):
            in_specs = ([P("dev")] * nb + [P()] * n_base
                        + [P("dev")] * (len(params) - n_base))
            out_specs = tuple((P(), P(), P()) if s[0] == "bsi_sum"
                              else P() for s in subs)
            fn = shard_map_nocheck(body, mesh=smesh,
                                   in_specs=tuple(in_specs),
                                   out_specs=out_specs)
            return fn(*leaves, *params)
        return run
    if kind == "words":
        tree = plan[1]

        def run(leaves, params):
            return _as_stack(_eval(tree, leaves, params), leaves)
    elif kind == "count":
        tree, reduce_ = plan[1], plan[2]

        def run(leaves, params):
            c = bm.count(_filter(tree, leaves, params))   # (S,)
            return jnp.sum(c) if reduce_ else c
    elif kind == "bsi_sum":
        planes_i, tree, reduce_ = plan[1], plan[2], plan[3]

        def run(leaves, params):
            filt = (None if tree is None
                    else _filter(tree, leaves, params))
            cnt, pos, neg = bsi_ops.sum_counts(
                _planes(leaves[planes_i]), filt)      # (S,), (S, P) x2
            if reduce_:
                return (jnp.sum(cnt), jnp.sum(pos, axis=0),
                        jnp.sum(neg, axis=0))         # scalar, (P,), (P,)
            return cnt, pos, neg
    elif kind == "gb_hist":
        # plan: ("gb_hist", cg_i, tree|None, planes_i|None, n_codes,
        #        signed, arm, digits) — the one-pass group-code
        #        histogram (digits: _code_digits, the live codes) as a
        #        BATCHABLE subplan (ISSUE 11): a GroupBy rider inside
        #        a fused "multi"/"ragged" program evaluates the same
        #        single-pass tile walk as the solo one-pass path (arm
        #        picks fused/onehot/xla at build time), and the demux
        #        gathers its combos out of the flat (K*G,) table.
        #        Unlike "groupby" it reads nothing from params[-1], so
        #        it composes with any other subplan.
        cg_i, tree, planes_i, n_codes, signed, arm, digits = plan[1:8]

        def run(leaves, params):
            cg = leaves[cg_i]                     # (S, CB+1, W)
            cp, valid = cg[:, :-1], cg[:, -1]
            if tree is not None:
                filt = _filter(tree, leaves, params)
                valid = jnp.bitwise_and(valid, filt)
            planes = leaves[planes_i] if planes_i is not None else None
            c, n, p, g = _onepass_gb(arm, digits)(
                cp, valid, planes, n_codes, signed)
            if planes_i is None:
                return c
            return jnp.concatenate([c, n, p.ravel(), g.ravel()])
    elif kind == "groupby":
        # plan: ("groupby", (stack_i, ...), planes_i|None, tree|None,
        #        reduce) — executeGroupByShard (executor.go:3918) as one
        # program: combo masks = gathered row-stack intersections, count
        # + optional BSI Sum partials, cross-shard reduce in-program.
        # The combo space arrives pre-chunked as (n_chunks, C, nf) and
        # a lax.scan walks the chunks INSIDE the program: one dispatch
        # per GroupBy regardless of combo count (a host-side chunk
        # loop would pay a dispatch and a fetch per chunk), while the
        # per-chunk (C, S, W) mask buffer stays bounded.  With reduce,
        # the four aggregate outputs concatenate into ONE flat array
        # so the host pays a single fetch, and `signed=False`
        # (BSI field with min >= 0) skips the sign-split masks and
        # the whole negative-plane popcount pass.
        stack_is, planes_i, tree, reduce_, signed = (
            plan[1], plan[2], plan[3], plan[4], plan[5])

        def run(leaves, params):
            sel_all = params[-1]                      # (n_chunks, C, nf)
            filt = None
            if tree is not None:
                filt = _filter(tree, leaves, params)

            def chunk_body(carry, sel):               # sel: (C, nf)
                m = leaves[stack_is[0]][sel[:, 0]]    # (C, S, W)
                for fi in range(1, len(stack_is)):
                    m = jnp.bitwise_and(m,
                                        leaves[stack_is[fi]][sel[:, fi]])
                if filt is not None:
                    m = jnp.bitwise_and(m, filt[None])
                counts = bm.count(m)                  # (C, S)
                if planes_i is None:
                    return carry, (jnp.sum(counts, axis=1)
                                   if reduce_ else counts)
                planes = leaves[planes_i]             # (S, P, W)
                exists, sign = planes[:, 0], planes[:, 1]
                em = jnp.bitwise_and(m, exists[None])
                nn = bm.count(em)                     # (C, S)
                pos = em if not signed else \
                    jnp.bitwise_and(em, ~sign[None])
                neg = None if not signed else \
                    jnp.bitwise_and(em, sign[None])
                mag_p = jnp.moveaxis(planes[:, 2:], 1, 0)  # (P, S, W)

                def body(c2, p_sw):
                    pc = bm.count(jnp.bitwise_and(pos, p_sw[None]))
                    nc = (jnp.zeros_like(pc) if neg is None else
                          bm.count(jnp.bitwise_and(neg, p_sw[None])))
                    if reduce_:
                        pc, nc = jnp.sum(pc, axis=1), jnp.sum(nc, axis=1)
                    return c2, (pc, nc)

                _, (pos_pc, neg_pc) = jax.lax.scan(body, 0, mag_p)
                c, n = counts, nn
                if reduce_:
                    c, n = jnp.sum(c, axis=1), jnp.sum(n, axis=1)
                return carry, (c, n, pos_pc, neg_pc)

            _, ys = jax.lax.scan(chunk_body, 0, sel_all)
            if planes_i is not None and reduce_:
                c, n, p, g = ys  # one flat fetch instead of four
                return jnp.concatenate(
                    [c.ravel(), n.ravel(), p.ravel(), g.ravel()])
            return ys  # leading axis = n_chunks on every output
    elif kind == "row_counts":
        rows_i, tree, reduce_ = plan[1], plan[2], plan[3]

        def run(leaves, params):
            rows = leaves[rows_i]                     # (R, S, W)
            if tree is None:
                c = bm.count(rows)                    # (R, S)
            else:
                filt = _filter(tree, leaves, params)
                c = bm.count(jnp.bitwise_and(rows, filt[None]))
            return jnp.sum(c, axis=1) if reduce_ else c
    else:
        raise AssertionError(kind)
    scope = _SCOPES[kind]

    def scoped(leaves, params):
        with jax.named_scope(scope):
            return run(leaves, params)
    return scoped


def _compiled(plan, sig: str | None = None, name: str | None = None):
    """plan: ("words", tree) | ("count", tree, reduce)
    | ("bsi_sum", planes_i, tree|None, reduce)
    | ("row_counts", rows_i, tree|None, reduce)
    | ("multi", (subplan, ...)) — the batcher's fused program.
    One jitted fn per structure.  `sig` lets a caller that already
    paid for repr(plan) — the multi-plan repr is multi-KB at high
    batch occupancy — pass it in instead of rebuilding it.  With
    reduce=True the cross-shard sum happens IN the program — under a
    mesh it lowers to a psum over ICI (the jitted analog of
    mapReduce's reduceFn); int32-exact up to _REDUCE_MAX_SHARDS
    shards, the caller's responsibility.  The program is named for
    its plan KIND (`jit_plan_count`, `jit_plan_ragged` on the
    profiler's XLA Modules line; `name` lets the ragged plane tell its
    extras program from the canonical one) — never for the plan's
    contents, so naming adds no program."""
    sig = repr(plan) if sig is None else sig
    with _JIT_LOCK:
        ent = _JIT_CACHE.get(sig)
        if ent is not None:
            _JIT_CACHE.move_to_end(sig)
            return ent[0]
    fn = jax.jit(bm.named(name or "plan_" + plan[0])(
        _plan_run(plan)))
    client = _jit_client()
    reserved = (_JIT_EST_BYTES
                if client.reserve(_JIT_EST_BYTES) else 0)
    evicted = []
    released = 0
    with _JIT_LOCK:
        _JIT_CACHE[sig] = (fn, reserved)
        metrics.JIT_CACHE.inc(cache="plan", event="insert")
        while len(_JIT_CACHE) > _JIT_CACHE_MAX:
            esig, (_efn, erb) = _JIT_CACHE.popitem(last=False)
            evicted.append(esig)
            released += erb
            metrics.JIT_CACHE.inc(cache="plan", event="evict")
        metrics.JIT_CACHE_ENTRIES.set(len(_JIT_CACHE), cache="plan")
    if released:
        client.release(released)
    for esig in evicted:
        # an evicted jit wrapper WILL re-trace + recompile on its next
        # dispatch — forget its shape keys so _dispatch_kind reports
        # that as 'compile', not a cached 'execute'
        _forget_dispatch_sig(esig)
    return fn


# -- dispatch attribution (flight recorder) ---------------------------------
# jax.jit compiles lazily per argument-shape signature, so "was this
# dispatch a recompile?" is invisible from the wrapper.  We shadow
# jit's cache key: the first time a (plan sig, arg shapes) pair is
# dispatched the call traces + XLA-compiles and is attributed to the
# "compile" phase; later dispatches of the same pair are "execute".
# Bounded LRU, kept consistent with _JIT_CACHE: when a plan sig is
# evicted there its shape keys are dropped here too (the next
# dispatch really recompiles), so an entry surviving only ever
# misclassifies a later dispatch as compile, never the other way.
_SEEN_DISPATCH: OrderedDict = OrderedDict()
_SEEN_DISPATCH_MAX = 4096
_SEEN_LOCK = threading.Lock()


def _forget_dispatch_sig(sig):
    with _SEEN_LOCK:
        for key in [k for k in _SEEN_DISPATCH if k[0] == sig]:
            del _SEEN_DISPATCH[key]


def _shape_key(arrs) -> tuple:
    return tuple((getattr(a, "shape", None), str(getattr(a, "dtype", "")))
                 for a in arrs)


def _dispatch_kind(sig, leaves, params) -> str:
    """'compile' on the first dispatch of (plan, arg shapes), else
    'execute' — the flight recorder's recompile detector."""
    key = (sig, _shape_key(leaves), _shape_key(params))
    with _SEEN_LOCK:
        if key in _SEEN_DISPATCH:
            _SEEN_DISPATCH.move_to_end(key)
            return "execute"
        _SEEN_DISPATCH[key] = True
        while len(_SEEN_DISPATCH) > _SEEN_DISPATCH_MAX:
            _SEEN_DISPATCH.popitem(last=False)
    return "compile"


def _block(out):
    """block_until_ready on any pytree of device/host arrays, so the
    timed execute phase covers the device work, not just the async
    dispatch.  A device error surfaces here, inside the OOM ladder of
    the caller that wraps the dispatch."""
    return jax.block_until_ready(out)


def dispatch_ready(fn, *args):
    """Call a jitted program and wait for its result: the call until
    it RETURNS is the `dispatch` stage (the host's share: argument
    handling, a first call's trace and compile, the enqueue), the
    rest of the enclosing `execute` / `compile` stage is the device."""
    with flight.stage("dispatch"):
        out = fn(*args)
    return _block(out)


def timed_call(kind: str, fn, *args):
    """(ready result, seconds) of one program call as the stage `kind`
    ('execute' | 'compile', from _dispatch_kind) over its `dispatch`
    child — for the engine's own jitted programs, which do not go
    through timed_dispatch."""
    with flight.stage(kind) as st:
        out = dispatch_ready(fn, *args)
    return out, st.seconds


# plan kind -> roofline op family (obs/roofline.py): the per-op
# labels behind pilosa_device_bandwidth_{gbps,fraction}{op}
_ROOF_OPS = {"count": "count", "words": "row", "row_counts": "topn",
             "bsi_sum": "sum", "groupby": "groupby", "multi": "multi",
             "ragged": "ragged", "ragged_mesh": "ragged",
             "row_counts_flat": "topn"}


def _plan_hbm_bytes(plan, leaves, params) -> int:
    """Bytes one dispatch of `plan` actually streams through HBM.

    Default: every operand leaf crosses once (true for the tree/scan
    programs XLA fuses into one pass).  The per-combo "groupby" scan
    is the exception — it gathers (C, S, W) combo masks and re-reads
    them once per payload pass, so its traffic comes from the
    schedule's model (kernels.groupby_scan_hbm_bytes), not from the
    operand sizes; without this the old arm's dispatches under-note
    and the groupby bandwidth gauge is fiction (ISSUE 11 satellite)."""
    if plan[0] == "groupby":
        stack_is, planes_i, tree = plan[1], plan[2], plan[3]
        sel_all = params[-1]                    # (n_chunks, C, nf)
        n_combos = int(sel_all.shape[0] * sel_all.shape[1])
        s0 = leaves[stack_is[0]]
        n_shards, width_words = s0.shape[1], s0.shape[2]
        depth = (leaves[planes_i].shape[1] - 2
                 if planes_i is not None else 0)
        return kernels.groupby_scan_hbm_bytes(
            n_shards, width_words, n_combos, len(stack_is), depth,
            signed=plan[5], has_filter=tree is not None)
    return sum(getattr(a, "nbytes", 0) for a in leaves)


def timed_dispatch(plan, leaves, params):
    """Run a plan's jitted program with flight/span attribution:
    recompiles are timed distinctly from cached dispatches, and the
    clock stops only when the device result is ready.  Dispatches run
    under the OOM backstop (memory/pressure.py): RESOURCE_EXHAUSTED
    triggers ledger-driven eviction + one retry, then a degraded-mode
    re-execution of the SAME plan on the host CPU backend — a slow
    answer instead of a failed query."""
    sig = repr(plan)
    fn = _compiled(plan, sig=sig)
    kind = _dispatch_kind(sig, leaves, params)
    oom0 = metrics.OOM_TOTAL.total(outcome="caught")
    with flight.stage(kind, kind=plan[0],
                      compile=kind == "compile") as st:
        out = pressure.guarded(
            lambda: dispatch_ready(fn, tuple(leaves), tuple(params)),
            host_fallback=lambda: pressure.run_host_plan(
                plan, leaves, params))
    dt = st.seconds
    if kind == "execute" and \
            metrics.OOM_TOTAL.total(outcome="caught") == oom0:
        # roofline attribution: operand bytes touched / device time,
        # per op family.  Cached-executable CLEAN dispatches only —
        # a compile dispatch's wall time is trace+XLA, and a dispatch
        # that tripped the OOM ladder (eviction sweep + retry or the
        # degraded host re-execution) measures recovery, not memory
        # traffic; either would poison the achieved-bandwidth gauge.
        roofline.note(_ROOF_OPS.get(plan[0], plan[0]),
                      _plan_hbm_bytes(plan, leaves, params), dt)
    return out


# ---------------------------------------------------------------------------
# plan builder
# ---------------------------------------------------------------------------

class PlanBuilder:
    """Walks a bitmap Call tree → IR + leaf/param arrays.

    Mirrors the dispatch set of executeBitmapCallShard
    (executor.go:1782): Row (incl. BSI conditions + time views),
    Union/Intersect/Difference/Xor/Not/All/Shift/ConstRow, and
    precomputed cross-shard leaves (Distinct/UnionRows) served from
    the per-query precompute cache.
    """

    def __init__(self, engine: "StackedEngine", idx, shards: list[int], pre):
        self.engine = engine
        self.ex = engine.executor
        self.idx = idx
        self.shards = list(shards)
        self.skey = tuple(self.shards)
        self.pre = pre or {}
        self.leaves: list = []
        self.params: list = []
        self._leaf_keys: dict = {}

    # -- leaf helpers ---------------------------------------------------

    def _add_leaf(self, arr) -> int:
        self.leaves.append(arr)
        return len(self.leaves) - 1

    def _cached_leaf(self, key, fetch) -> int:
        i = self._leaf_keys.get(key)
        if i is None:
            i = self._add_leaf(fetch())
            self._leaf_keys[key] = i
        return i

    def _param(self, arr) -> int:
        # params are tiny (predicate masks, sign flags): keep them on
        # the host and let jit move them with the call — no eager
        # device commit (host_only harnesses never touch a device)
        self.params.append(np.asarray(arr))
        return len(self.params) - 1

    def _row_leaf(self, field, views: tuple[str, ...], row_id: int) -> int:
        return self._cached_leaf(
            ("row", self.idx.name, field.name, views, row_id),
            lambda: self.engine.row_stack(self.idx, field, views, row_id,
                                          self.skey))

    def _planes_leaf(self, field) -> int:
        return self._cached_leaf(
            ("planes", self.idx.name, field.name, field.bit_depth),
            lambda: self.engine.plane_stack(self.idx, field, self.skey))

    def _groupcode_leaf(self, fields_rows) -> int:
        """(S, CB+1, W) group-code stack leaf for a batched one-pass
        GroupBy subplan ("gb_hist") — pageable like any other stack,
        so under raw_pages() it rides the ragged page-table program."""
        fkey = tuple((f.name, tuple(int(r) for r in rl))
                     for f, rl in fields_rows)
        return self._cached_leaf(
            ("groupcodes", self.idx.name, fkey),
            lambda: self.engine.groupcode_stack(self.idx, fields_rows,
                                                self.skey))

    def _existence_leaf(self) -> int:
        if not self.idx.track_existence:
            raise Unstackable("existence tracking off")
        return self._cached_leaf(
            ("exists", self.idx.name),
            lambda: self.engine.existence_stack(self.idx, self.skey))

    def _pre_leaf(self, call) -> int:
        res = self.pre.get(id(call))
        if res is None:
            raise Unstackable(f"no precomputed result for {call.name}")
        return self._cached_leaf(
            ("pre", id(call)),
            lambda: self.engine.place(np.stack(
                [res.shard_words(s) for s in self.shards])))

    # -- tree walk ------------------------------------------------------

    def build(self, call: Call):
        name = call.name
        if name in ("Row", "Range"):
            return self._build_row(call)
        if name in ("Union", "Intersect", "Difference", "Xor"):
            op = name.lower()
            if not call.children:
                if name in ("Union", "Xor"):
                    return ("zeros",)
                raise Unstackable(f"{name} requires subqueries")
            children = [self.build(c) for c in call.children]
            # constant-fold zeros so ("zeros",) never survives inside
            # a tree (its scalar broadcast is only safe at the root):
            #   union/xor: drop zero terms; intersect: any zero term
            #   zeroes the whole product; difference: zero base is
            #   zero, zero subtrahends drop out.
            zero = ("zeros",)
            if op in ("union", "xor"):
                children = [c for c in children if c != zero]
                if not children:
                    return zero
            elif op == "intersect":
                if zero in children:
                    return zero
            elif op == "difference":
                if children[0] == zero:
                    return zero
                children = [children[0]] + [c for c in children[1:]
                                            if c != zero]
            if len(children) == 1:
                return children[0]
            return ("nary", op, tuple(children))
        if name == "Not":
            child = self.ex._only_child(call)
            exist_i = self._existence_leaf()
            sub = self.build(child)
            if sub == ("zeros",):
                return ("leaf", exist_i)
            return ("not", exist_i, sub)
        if name == "All":
            return ("leaf", self._existence_leaf())
        if name == "Shift":
            child = self.ex._only_child(call)
            n = int(call.arg("n", 1))
            sub = self.build(child)
            if sub == ("zeros",):
                return sub
            return ("shift", n, sub)
        if name == "ConstRow":
            # keyed-index key translation (preTranslate analog)
            cols = self.engine.executor._constrow_cols(self.idx, call)
            width = self.idx.width
            per_shard = {}
            for c in cols:
                per_shard.setdefault(c // width, []).append(c % width)
            stack = np.stack([bm.from_columns(per_shard.get(s, []), width)
                              for s in self.shards])
            return ("leaf", self._add_leaf(self.engine.place(stack)))
        if name in ("Distinct", "UnionRows"):
            return ("leaf", self._pre_leaf(call))
        if name == "Precomputed":
            return ("leaf", self._pre_leaf(call))
        raise Unstackable(f"not a stackable bitmap call: {name}")

    def _build_row(self, call: Call):
        ex = self.ex
        fname, cond = call.condition_field()
        if cond is not None:
            return self._build_bsi(fname, cond)
        fname, row_val = call.field_arg()
        if fname is None:
            raise Unstackable("Row() without field argument")
        f = self.idx.field(fname)
        if f is None:
            raise Unstackable(f"field not found: {fname}")
        if f.options.type.is_bsi:
            return self._build_bsi(fname, Condition(past.OP_EQ, row_val))
        row_id = ex._row_id_for_value(f, row_val)
        if row_id is None:
            return ("zeros",)
        views = tuple(f.views_for_range(call.arg("from"), call.arg("to")))
        if len(views) > 1 and timeq.qcover():
            # quantum-cover op: one SINGLE-view stack leaf per cover
            # member, unioned in-program.  Each leaf caches under its
            # own view key, so a rolling window restacks only the
            # quantum that entered the cover and a live-edge write
            # dirties one leaf — the monolithic multi-view leaf would
            # restack the whole cover either way.
            metrics.TIMEQ_QCOVER_TOTAL.inc()
            return ("qcover", tuple(self._row_leaf(f, (vn,), row_id)
                                    for vn in views))
        return ("leaf", self._row_leaf(f, views, row_id))

    def _build_bsi(self, fname: str, cond: Condition):
        """BSI predicate → IR, mirroring the plan-time scaling and
        short-circuits of Executor._bsi_condition_shard."""
        ex = self.ex
        f = ex._bsi_field(self.idx, fname)
        depth = f.bit_depth
        v = f.views.get(f.bsi_view)
        if v is None or not v.fragments:
            if cond.value is None and cond.op == past.OP_EQ:
                return ("leaf", self._existence_leaf())
            return ("zeros",)
        planes_i = self._planes_leaf(f)

        if cond.value is None:
            if cond.op == past.OP_EQ:
                return ("bsi_null", planes_i, self._existence_leaf())
            if cond.op == past.OP_NEQ:
                return ("bsi_notnull", planes_i)
            raise Unstackable(f"invalid null comparison {cond.op}")

        max_mag = (1 << depth) - 1

        def masks(up):
            return self._param(bsi_ops.predicate_masks(up, depth))

        def flag(b):
            return self._param(bool(b))

        if past.is_between(cond):
            lo_raw, hi_raw = cond.value
            lo = ex._scaled_bound(f, lo_raw, round_up=True)
            hi = ex._scaled_bound(f, hi_raw, round_up=False)
            if cond.op in (past.OP_BTWN_LT_LT, past.OP_BTWN_LT_LTE):
                lo = max(lo, ex._scaled_bound(f, lo_raw, round_up=False) + 1)
            if cond.op in (past.OP_BTWN_LT_LT, past.OP_BTWN_LTE_LT):
                hi = min(hi, ex._scaled_bound(f, hi_raw, round_up=True) - 1)
            lo, hi = max(lo, -max_mag), min(hi, max_mag)
            if lo > hi:
                return ("zeros",)
            return ("bsi_between", planes_i, masks(abs(lo)), masks(abs(hi)),
                    flag(lo < 0), flag(hi < 0))

        op = cond.op
        if op in (past.OP_EQ, past.OP_NEQ):
            p_lo = ex._scaled_bound(f, cond.value, round_up=False)
            p_hi = ex._scaled_bound(f, cond.value, round_up=True)
            out_of_range = p_lo != p_hi or abs(p_lo) > max_mag
            if op == past.OP_EQ:
                if out_of_range:
                    return ("zeros",)
                return ("bsi_cmp", planes_i, "eq", masks(abs(p_lo)),
                        flag(p_lo < 0))
            if out_of_range:
                return ("bsi_notnull", planes_i)
            return ("bsi_cmp", planes_i, "neq", masks(abs(p_lo)),
                    flag(p_lo < 0))
        if op in (past.OP_LT, past.OP_LTE):
            allow_eq = op == past.OP_LTE
            p = ex._scaled_bound(f, cond.value, round_up=not allow_eq)
            if p > max_mag:
                return ("bsi_notnull", planes_i)
            if p < -max_mag:
                return ("zeros",)
            return ("bsi_cmp", planes_i, "lte" if allow_eq else "lt",
                    masks(abs(p)), flag(p < 0))
        if op in (past.OP_GT, past.OP_GTE):
            allow_eq = op == past.OP_GTE
            p = ex._scaled_bound(f, cond.value, round_up=allow_eq)
            if p < -max_mag:
                return ("bsi_notnull", planes_i)
            if p > max_mag:
                return ("zeros",)
            return ("bsi_cmp", planes_i, "gte" if allow_eq else "gt",
                    masks(abs(p)), flag(p < 0))
        raise Unstackable(f"unsupported condition op {op}")


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

from functools import partial


@partial(jax.jit, static_argnums=2)
def _decode_slice(planes, start, size):
    """Module-level (stable identity => one JAX compile per shape) BSI
    decode of a shard slice of a resident plane stack."""
    sl = jax.lax.dynamic_slice_in_dim(planes, start, size, axis=0)
    return bsi_ops.decode_device(sl)


@jax.jit
def _patch_program(stack, idxs, starts, data):
    """Module-level jit (stable identity — one compile per (stack
    shape, run shape) pair; run counts/widths are pow2-bucketed by
    the caller so the shape space stays small): scatter padded word
    runs into a resident stack of any leading shape through its
    flattened (L, W) view."""
    w = stack.shape[-1]
    out = bm.patch_rows(stack.reshape(-1, w), idxs, starts, data)
    return out.reshape(stack.shape)


def _make_delta_fn(frags, lanes, new_versions):
    """Dirty-lane derivation shared by the whole-entry patcher and
    the paged residency path: ``deltas(old_versions)`` maps logged
    fragment mutations onto stack LANES, returning {lane: [(lo, hi)
    word runs]} (None value = whole lane — the fragment's delta log
    couldn't prove coverage), {} when nothing relevant moved, or None
    for structural changes that force a rebuild."""
    def deltas(old_versions):
        if len(old_versions) != len(new_versions):
            return None  # structural change: rebuild
        dirty: dict[int, list | None] = {}
        for fr, ov, nv, lmap in zip(frags, old_versions,
                                    new_versions, lanes):
            if ov == nv:
                continue
            spans = None
            if (fr is not None and ov != -1 and nv != -1
                    and ov[0] == nv[0]):
                spans = fr.deltas_since(ov[1])
            if spans is None:
                # compaction: whole-lane slice rebuild for every
                # lane this fragment feeds
                for lns in lmap.values():
                    for ln in lns:
                        dirty[ln] = None
                continue
            for row, lo, hi in spans:
                for ln in lmap.get(row, ()):
                    cur = dirty.get(ln, False)
                    if cur is None:
                        continue  # already whole-lane
                    if cur is False:
                        dirty[ln] = cur = []
                    cur.append((lo, hi))
        return dirty
    return deltas


def _coalesce_runs(ranges, w: int):
    """Sort + merge overlapping/adjacent (lo, hi) word runs, clamped
    to [0, w)."""
    runs: list[list[int]] = []
    for lo, hi in sorted(ranges):
        lo, hi = max(0, lo), min(hi, w)
        if hi <= lo:
            continue
        if runs and lo <= runs[-1][1]:
            runs[-1][1] = max(runs[-1][1], hi)
        else:
            runs.append([lo, hi])
    return runs


class StackedEngine:
    """Executes PQL call trees as stacked-shard device programs.

    Owned by Executor; holds the tile-stack cache and the (optional)
    device mesh.  With a mesh set, every stack is placed with the
    shard axis sharded over the mesh "shards" axis (the placement of
    parallel.place_shards) and XLA inserts the ICI collectives for the
    cross-shard reduction — the jitted analog of mapReduce's reduceFn.
    """

    def __init__(self, executor, max_cache_bytes: int | None = None):
        self.executor = executor
        self.mesh = None
        # max_cache_bytes None (the default) defers byte bounds to the
        # process-wide device-memory ledger (pilosa_tpu/memory); a
        # value sets an additional LOCAL cap (tests, explicit bounds)
        self.cache = TileStackCache(max_cache_bytes)
        # host_only=True keeps leaf stacks as numpy (no eager device
        # commit); jit transfers them at call time.  Used by harnesses
        # that want the compiled program without touching a device.
        self.host_only = False
        # (field, rows, shards) -> (fragment versions, bool): whether
        # the row set is pairwise disjoint in the DATA — the gate for
        # the one-pass group-code GroupBy (a column in two rows of one
        # field belongs to two combos, which a per-column digit cannot
        # express).  Version-guarded like the tile stacks; bounded
        # FIFO so varied GroupBy row sets on a long-lived server
        # can't grow it without limit (keys carry whole row tuples).
        self._disjoint_cache: OrderedDict = OrderedDict()

    # -- mesh / placement ----------------------------------------------

    def set_mesh(self, mesh):
        """Set (or clear) the device mesh; placed stacks are mesh-
        specific so the cache restarts cold."""
        self.mesh = mesh
        self.cache.clear()

    def place(self, arr: np.ndarray):
        """Host (S, ..., W) stack → device; axis 0 sharded over the
        mesh (zero-padded to a multiple) via parallel.place_shards."""
        arr = np.ascontiguousarray(arr)
        if self.host_only:
            return arr
        if self.mesh is None:
            # OOM backstop: a failed upload degrades to the host array
            # (jit re-attempts the transfer at dispatch, where the
            # host-fallback ladder finishes the job)
            return pressure.guarded(lambda: jnp.asarray(arr),
                                    host_fallback=lambda: arr)
        from pilosa_tpu.parallel.mesh import place_shards
        return place_shards(self.mesh, arr, batch_axes=arr.ndim - 2)

    # -- stack builders (cached) ---------------------------------------

    def _frags(self, idx, field, view: str, shards):
        v = field.views.get(view)
        return [v.fragment(s) if v else None for s in shards]

    def _versions(self, frags) -> tuple:
        """Per-fragment (gen, version) stamps, -1 for absent.  The
        version detects writes; the gen detects drop/recreate — a
        recreated fragment restarts its version counter, and without
        the gen a matching count would false-hit the cache with the
        old incarnation's stack (and would let the patch path apply
        an empty delta over foreign data)."""
        return tuple(-1 if fr is None else (fr.gen, fr.version)
                     for fr in frags)

    # -- incremental stack maintenance (delta patching) -----------------
    # A cache entry's fragments each carry a bounded delta log
    # (models/fragment.py): on a stale access the patcher maps logged
    # (row, word-span) mutations onto the stack's LANES (one lane =
    # one (leading-coords, W) row of the device array), re-reads just
    # those word runs from the live fragments, and scatters them on
    # device (_patch_program) — a write costs O(delta) upload instead
    # of an O(S*W) restack.  A fragment whose log can't prove
    # coverage (pre-window snapshot, appeared/vanished, recreated
    # gen) compacts to whole-lane runs — the (shard, row) slice
    # rebuild; only a dirty fraction above _PATCH_MAX_FRAC falls all
    # the way back to build().

    def _make_patcher(self, frags, lanes, new_versions, logical_lead,
                      lane_words):
        """TileStackCache patcher closure (the WHOLE-entry write
        path; the paged path consumes ``_make_delta_fn`` directly via
        its StackRecipe).

        frags/lanes run parallel to the flat `new_versions` tuple:
        ``lanes[i]`` maps fragment i's ROW ids to the logical lane
        indices (flattened over `logical_lead`) that row feeds.
        ``lane_words(lane)`` returns the lane's CURRENT full-width
        host words.  Returns None when patching is disabled."""
        if not _patch_enabled():
            return None
        deltas = _make_delta_fn(frags, lanes, new_versions)

        def patcher(arr, old_versions):
            # chaos seam: an armed device-patch fault fails the
            # in-place patch exactly like a device-side error would —
            # the caller (_serve_whole) catches and falls back to a
            # full rebuild, so the entry can never be half-patched
            from pilosa_tpu.obs import faults
            faults.fire("device-patch")
            dirty = deltas(old_versions)
            if dirty is None:
                return None  # structural change: rebuild
            if not dirty:
                # versions moved but no logged mutation touches this
                # stack's rows: adopt the new versions as-is
                return arr, 0
            return self._apply_patch(arr, dirty, logical_lead,
                                     lane_words)
        return patcher

    def _apply_patch(self, arr, dirty, logical_lead, lane_words):
        """Apply dirty lane runs to a resident stack; (new_arr, bytes)
        or None when a full rebuild is cheaper.  Runs pad to pow2
        widths (content comes from the live rows, so widening is
        free and correct) and batch per width so the shared jitted
        scatter compiles once per bucket."""
        w = arr.shape[-1]
        lead_shape = arr.shape[:-1]   # device stacks may be mesh-padded
        total_words = int(np.prod(logical_lead)) * w
        segs = []                     # (flat padded lane, start, plen, lane)
        patched_words = 0
        for lane in sorted(dirty):
            coords = np.unravel_index(lane, logical_lead)
            flat = int(np.ravel_multi_index(coords, lead_shape))
            runs = dirty[lane]
            runs = [(0, w)] if runs is None else _coalesce_runs(runs, w)
            for lo, hi in runs:
                plen = min(1 << (hi - lo - 1).bit_length(), w)
                start = min(lo, w - plen)
                segs.append((flat, start, plen, lane))
                patched_words += plen
        if not segs:
            return arr, 0
        if patched_words > _patch_max_frac() * total_words:
            return None  # near-total patch: one dense upload wins
        lane_cache: dict[int, np.ndarray] = {}

        def words_of(lane):
            cur = lane_cache.get(lane)
            if cur is None:
                cur = lane_cache[lane] = np.asarray(
                    lane_words(lane), dtype=np.uint32)
            return cur

        by_len: dict[int, list] = {}
        for flat, start, plen, lane in segs:
            by_len.setdefault(plen, []).append((flat, start, lane))
        if isinstance(arr, np.ndarray):
            # host path: ONE fresh copy (resident host stacks are
            # shared read-only with concurrent queries), then the host
            # twin of the device scatter per width bucket
            out = arr.reshape(-1, w).copy()
            for plen, group in by_len.items():
                idxs = np.array([f for f, _s, _l in group], np.int64)
                starts = np.array([s for _f, s, _l in group], np.int64)
                data = np.stack([words_of(lane)[start:start + plen]
                                 for _f, start, lane in group])
                bm.patch_rows_np(out, idxs, starts, data, out=out)
            return out.reshape(arr.shape), patched_words * 4
        for plen, group in sorted(by_len.items()):
            n = len(group)
            npad = 1 << max(n - 1, 0).bit_length()
            idxs = np.zeros(npad, np.int32)
            starts = np.zeros(npad, np.int32)
            data = np.empty((npad, plen), np.uint32)
            for k in range(npad):
                flat, start, lane = group[min(k, n - 1)]
                idxs[k], starts[k] = flat, start
                data[k] = words_of(lane)[start:start + plen]
            arr = _patch_program(arr, idxs, starts, data)
        return arr, patched_words * 4

    def _pageable(self) -> bool:
        """Paged residency (memory/pages.py) applies to plain
        single-device placements; mesh shardings and host_only numpy
        stacks keep whole-array entries.  The SERVING mesh
        (memory/placement.py) is not ``self.mesh``: it keeps paging
        on and places pages per device."""
        return self.mesh is None and not self.host_only

    def _mesh_key(self):
        """Mesh/topology token for stack cache keys: the GSPMD mesh
        identity plus — when the serving mesh is on — its width and
        the placement epoch, so a device-count flip or rebalance can
        never false-hit a stack laid out for another topology."""
        from pilosa_tpu.memory import placement
        n = placement.mesh_devices() if self._pageable() else 1
        if n <= 1:
            return id(self.mesh)
        return (id(self.mesh), n, placement.epoch())

    def _lane_devices(self, idx, skey, lead, shard_axis: int):
        """Per-lane serving-mesh owner slots (int32 (lanes,)) for a
        pageable stack, or None when the mesh is off.  ``shard_axis``
        is the position of the shard axis inside ``lead``; every
        other leading axis repeats its shard's owner — all of a
        shard's lanes colocate on its placement device."""
        from pilosa_tpu.memory import placement
        if not self._pageable() or placement.mesh_devices() <= 1:
            return None
        owners = placement.owners(idx.name, skey)
        inner = 1
        for d in lead[shard_axis + 1:]:
            inner *= int(d)
        outer = 1
        for d in lead[:shard_axis]:
            outer *= int(d)
        return np.tile(np.repeat(owners, inner), outer)

    def _cached_stack(self, key, versions, build, *, frags, lanes,
                      logical_lead, lane_words, width_words,
                      build_host=None, build_page=None,
                      versions_fn=None,
                      weight: float = 1.0, pageable: bool = True,
                      alive_fn=None, lane_device=None,
                      shard_axis: int | None = None):
        """Shared fetch path for every stack builder: wires the
        whole-entry patcher and, on pageable placements, the paged
        StackRecipe (page-granular eviction/patching + prefetch).
        Fresh hits short-circuit through ``probe`` — on the serving
        steady state (hot pages, no writes) none of that machinery is
        needed and constructing it dominated the host fast paths."""
        hit = self.cache.probe(key, versions)
        if hit is not None:
            return hit
        patcher = self._make_patcher(frags, lanes, versions,
                                     logical_lead, lane_words)
        recipe = None
        if pageable and self._pageable() and build_host is not None:
            deltas_fn = None
            if _patch_enabled() and versions_fn is not None:
                # derive dirt against the LIVE versions at patch time,
                # not the tuple captured when this recipe was built:
                # the prefetcher replays stored recipes after later
                # writes, and a captured snapshot would stamp fresh
                # versions onto stale content (spans re-read live
                # rows, so a stamp OLDER than the content only costs
                # an extra idempotent patch — never staleness)
                def deltas_fn(old_versions):
                    # same device-patch chaos seam as the whole-entry
                    # patcher: _deltas_or_none catches and the paged
                    # path rebuilds the dirty pages from live rows
                    from pilosa_tpu.obs import faults
                    faults.fire("device-patch")
                    return _make_delta_fn(
                        frags, lanes, versions_fn())(old_versions)
            recipe = StackRecipe(
                logical_lead=tuple(logical_lead),
                width_words=int(width_words),
                lane_words=lane_words,
                build_host=build_host,
                build_page=build_page,
                versions_fn=versions_fn,
                deltas_fn=deltas_fn,
                weight=weight,
                alive_fn=alive_fn,
                lane_device=lane_device,
                shard_axis=shard_axis)
        return self.cache.get(key, versions, build, patcher, recipe)

    def row_stack(self, idx, field, views: tuple[str, ...], row_id: int,
                  skey: tuple):
        """(S, W) device stack of one row, unioned across views."""
        shards = list(skey)
        width = idx.width
        key = ("row", idx.name, field.name, views, row_id, skey,
               self._mesh_key())
        per_view = [self._frags(idx, field, vn, shards) for vn in views]

        def versions_fn():
            return tuple(v for frags in per_view
                         for v in self._versions(frags))

        versions = versions_fn()

        def build_host():
            out = np.zeros((len(shards), width // 32), dtype=np.uint32)
            for frags in per_view:
                for i, fr in enumerate(frags):
                    if fr is not None:
                        out[i] |= fr.row_words(row_id)
            return out

        def lane_words(si):
            out = np.zeros(width // 32, dtype=np.uint32)
            for frags in per_view:
                fr = frags[si]
                if fr is not None:
                    out |= fr.row_words(row_id)
            return out

        def build_page(ids, page_lanes, density_hint):
            # a lane is a shard: each page straight from what its
            # fragments hold, in its final form
            frags = per_view[0]
            return encode.encode_lanes(
                [None if frags[si] is None
                 else frags[si].row_source(row_id) for si in ids],
                row_id, page_lanes, width // 32, density_hint)

        frags_flat = [fr for frags in per_view for fr in frags]
        lanes = [{row_id: (si,)} for _ in per_view
                 for si in range(len(shards))]
        return self._cached_stack(
            key, versions, lambda: self.place(build_host()),
            frags=frags_flat, lanes=lanes,
            logical_lead=(len(shards),), lane_words=lane_words,
            width_words=width // 32, build_host=build_host,
            # several views (a time quantum's cover) are ORed lane by
            # lane: that stays build_host's
            build_page=build_page if len(per_view) == 1 else None,
            versions_fn=versions_fn,
            alive_fn=lambda: idx.fields.get(field.name) is field,
            lane_device=self._lane_devices(idx, skey,
                                           (len(shards),), 0),
            shard_axis=0)

    def _plane_lanes(self, frags, n_shards: int, depth: int, width: int):
        """(lanes, lane_words) for an (S, 2+depth, W) plane stack:
        lane = si*(2+depth) + plane-row."""
        p = 2 + depth

        def lane_words(lane):
            si, r = divmod(lane, p)
            fr = frags[si]
            return (fr.row_words(r) if fr is not None
                    else np.zeros(width // 32, dtype=np.uint32))

        lanes = [{r: (si * p + r,) for r in range(p)}
                 for si in range(n_shards)]
        return lanes, lane_words

    def plane_stack(self, idx, field, skey: tuple):
        """(S, 2+depth, W) device stack of a BSI field's planes."""
        shards = list(skey)
        depth = field.bit_depth
        width = idx.width
        key = ("planes", idx.name, field.name, depth, skey,
               self._mesh_key())
        frags = self._frags(idx, field, field.bsi_view, shards)
        versions = self._versions(frags)

        def build_host():
            out = np.zeros((len(shards), 2 + depth, width // 32),
                           dtype=np.uint32)
            for i, fr in enumerate(frags):
                if fr is not None:
                    for r in range(2 + depth):
                        out[i, r] = fr.row_words(r)
            return out

        lanes, lane_words = self._plane_lanes(frags, len(shards),
                                              depth, width)
        return self._cached_stack(
            key, versions, lambda: self.place(build_host()),
            frags=frags, lanes=lanes,
            logical_lead=(len(shards), 2 + depth),
            lane_words=lane_words, width_words=width // 32,
            build_host=build_host,
            versions_fn=lambda: self._versions(frags),
            alive_fn=lambda: idx.fields.get(field.name) is field,
            lane_device=self._lane_devices(
                idx, skey, (len(shards), 2 + depth), 0),
            shard_axis=0)

    def existence_stack(self, idx, skey: tuple):
        from pilosa_tpu.models.index import EXISTENCE_FIELD
        f = idx.fields.get(EXISTENCE_FIELD)
        if f is None:
            raise Unstackable("no existence field")
        return self.row_stack(idx, f, (VIEW_STANDARD,), 0, skey)

    # -- execution entry points ----------------------------------------

    def _run(self, plan, builder):
        return timed_dispatch(plan, builder.leaves, builder.params)

    def _build_timed(self, builder, call):
        """PlanBuilder.build as the `plan_build` stage.  The stack/
        leaf fetches inside the walk are stages of their own
        (TileStackCache.get), children of this one: the pure
        tree-walk cost is this span's self time."""
        with flight.stage("plan_build", call=call.name):
            return builder.build(call)

    def _reduce_in_program(self, shards) -> bool:
        """In-program (ICI-collective) cross-shard reduce is int32-
        exact only below _REDUCE_MAX_SHARDS (counts < 2^20 per shard);
        larger fleets fetch per-shard partials and sum in host ints."""
        return len(shards) <= _REDUCE_MAX_SHARDS

    def _sparse_fast(self) -> bool:
        """The packed fast paths apply exactly where pages can be
        container-encoded at all: single-device pageable placements
        with the sparse format enabled (memory/encode.py)."""
        return encode.enabled() and self._pageable()

    def sparse_raw(self):
        """Context for stack fetches that can serve packed pages:
        ``raw_pages()`` when the sparse fast paths apply, else a
        no-op (mesh/host placements keep assembled dense operands)."""
        return raw_pages() if self._sparse_fast() else (
            contextlib.nullcontext())

    def _count_packed_host(self, b, tree):
        """Host-exact Count of a bare stack leaf from its pages'
        encode-time popcounts — the packed arm: no device program and
        no dense expansion, bytes touched = the encoded payload.
        Returns None when the plan needs real device work."""
        if not (isinstance(tree, tuple) and len(tree) == 2
                and tree[0] == "leaf"):
            return None
        leaf = b.leaves[tree[1]]
        if not isinstance(leaf, PageView) or not leaf.encoded():
            return None
        t0 = time.perf_counter()
        total = 0
        enc_bytes = 0
        for p in leaf.pages:
            enc_bytes += encode.payload_nbytes(p)
            if encode.is_encoded(p):
                total += p.bit_count()
            else:
                total += int(np.bitwise_count(np.asarray(p)).sum())
        dt = time.perf_counter() - t0
        flight.note_phase("execute", dt)
        roofline.note("count", enc_bytes, dt)
        return int(total)

    @staticmethod
    def _leaf_positions(leaf):
        """Sorted, unique flat set-bit offsets of a PageView whose
        pages are ALL packed-encoded, with the encoded bytes streamed
        and the page-partition signature (cross-leaf offsets only
        compare when partitions match).  None disqualifies the leaf
        (dense/run/missing pages) — caller falls back to expansion."""
        if not isinstance(leaf, PageView) or not leaf.pages:
            return None
        parts, nbytes, off, sig = [], 0, 0, []
        for p in leaf.pages:
            if not (encode.is_encoded(p) and p.kind == "packed"):
                return None
            nbytes += p.payload_nbytes
            pos = p.positions()
            parts.append(pos if off == 0 else pos + off)
            bits = p.page_lanes * p.width_words * 32
            sig.append(bits)
            off += bits
        # device-partitioned pages permute lanes into page order; the
        # flat offsets are then PERMUTED coordinates — still a valid
        # bijection for set algebra, but only between leaves sharing
        # the exact same permutation, so it joins the signature
        if leaf.lane_page is not None:
            sig.append(leaf.lane_page.tobytes())
        # per-page positions are sorted and page offsets ascend, so
        # the concatenation is globally sorted unique; single-page
        # leaves hand back the cached array itself (never mutated)
        pos = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return pos, nbytes, tuple(sig)

    @staticmethod
    def _member(a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Mask over sorted-unique ``b``: which elements are in
        sorted-unique ``a`` (searchsorted membership — no re-sort)."""
        if a.size == 0:
            return np.zeros(b.size, dtype=bool)
        idx = np.searchsorted(a, b)
        return (idx < a.size) & (a[np.minimum(idx, a.size - 1)] == b)

    def _count_setop_packed_host(self, b, tree):
        """Host-exact Count of an n-ary set op over bare packed
        leaves: sorted-coordinate set algebra (union/intersect/
        difference/xor) instead of decode + device bitwise scan —
        bytes touched stay the encoded payloads.  None when any leaf
        isn't fully packed or the tree has deeper structure."""
        if not (isinstance(tree, tuple) and tree[0] == "nary"):
            return None
        op, children = tree[1], tree[2]
        if not all(isinstance(c, tuple) and len(c) == 2
                   and c[0] == "leaf" for c in children):
            return None
        t0 = time.perf_counter()
        leaves, enc_bytes, sig = [], 0, None
        for c in children:
            got = self._leaf_positions(b.leaves[c[1]])
            if got is None:
                return None
            pos, nb, s = got
            if sig is None:
                sig = s
            elif s != sig:
                return None
            enc_bytes += nb
            leaves.append(pos)
        if op not in ("union", "intersect", "difference", "xor"):
            return None
        if len(leaves) == 2:
            # binary ops reduce to one intersection size — no result
            # set materialized.  Both sides are sorted-unique, so a
            # stable sort of their concatenation is a single merge
            # pass and the intersection size is the adjacent-duplicate
            # count — ~2x faster here than per-element binary search
            # (searchsorted pays ~log(n) cache misses per probe)
            a, bb = leaves
            c = np.concatenate((a, bb))
            c.sort(kind="stable")
            both = int((c[1:] == c[:-1]).sum())
            n = {"union": a.size + bb.size - both,
                 "intersect": both,
                 "difference": a.size - both,
                 "xor": a.size + bb.size - 2 * both}[op]
        else:
            res = leaves[0]
            if op == "union":
                for p in leaves[1:]:
                    # keep res sorted-unique: merge in only p's novel
                    # elements (membership test, no full re-sort)
                    res = np.sort(np.concatenate(
                        (res, p[~self._member(res, p)])),
                        kind="mergesort")
            elif op == "intersect":
                for p in leaves[1:]:
                    res = res[self._member(p, res)]
            elif op == "difference":
                for p in leaves[1:]:
                    res = res[~self._member(p, res)]
            else:  # xor
                for p in leaves[1:]:
                    res = np.sort(np.concatenate(
                        (res[~self._member(p, res)],
                         p[~self._member(res, p)])), kind="mergesort")
            n = int(res.size)
        dt = time.perf_counter() - t0
        flight.note_phase("execute", dt)
        roofline.note("count", enc_bytes, dt)
        return n

    def count(self, idx, call: Call, shards: list[int], pre) -> int:
        """Exact Count via one device program + one host fetch — or,
        for a bare row leaf whose pages are container-encoded, a pure
        host sum of the encode-time popcounts."""
        if not shards:
            return 0
        b = PlanBuilder(self, idx, shards, pre)
        if self._sparse_fast():
            with raw_pages():
                tree = self._build_timed(b, call)
            if tree == ("zeros",):
                return 0
            fast = self._count_packed_host(b, tree)
            if fast is None:
                fast = self._count_setop_packed_host(b, tree)
            if fast is not None:
                return fast
            # composite plan: decode PageView leaves to the identical
            # dense operands the non-raw fetch would have assembled
            # (same shapes — same jit cache entries)
            b.leaves = [_expand_view(lf) if isinstance(lf, PageView)
                        else lf for lf in b.leaves]
        else:
            tree = self._build_timed(b, call)
            if tree == ("zeros",):
                return 0
        red = self._reduce_in_program(shards)
        counts = np.asarray(self._run(("count", tree, red), b),
                            dtype=np.int64)
        return int(counts) if red else int(counts.sum())

    def words(self, idx, call: Call, shards: list[int], pre):
        """(S, W) numpy result of a bitmap tree (one fetch), or None
        for a statically-empty tree."""
        if not shards:
            return None
        b = PlanBuilder(self, idx, shards, pre)
        tree = self._build_timed(b, call)
        if tree == ("zeros",):
            return None
        out = np.asarray(self._run(("words", tree), b))
        return out[: len(shards)]  # drop mesh padding shards

    @staticmethod
    def bsi_sum_host(cnt, pos, neg, red: bool) -> tuple[int, int]:
        """Combine a ("bsi_sum", ...) program's outputs into exact
        Python ints (shared by the solo path and the batcher demux)."""
        pos = np.asarray(pos, dtype=np.int64)
        neg = np.asarray(neg, dtype=np.int64)
        if not red:
            pos, neg = pos.sum(axis=0), neg.sum(axis=0)
        total = sum((int(p) - int(n)) << i
                    for i, (p, n) in enumerate(zip(pos, neg)))
        return int(total), int(np.asarray(cnt, dtype=np.int64).sum())

    def bsi_sum(self, idx, field, filter_call, shards: list[int], pre):
        """Sum over `field` under an optional filter tree.  Per-plane
        popcounts reduce across shards in-program; the plane-weighted
        total is combined on the host in exact Python ints."""
        b = PlanBuilder(self, idx, shards, pre)
        planes_i = b._planes_leaf(field)
        tree = None
        if filter_call is not None:
            tree = self._build_timed(b, filter_call)
            if tree == ("zeros",):
                return 0, 0
        red = self._reduce_in_program(shards)
        cnt, pos, neg = self._run(("bsi_sum", planes_i, tree, red), b)
        return self.bsi_sum_host(cnt, pos, neg, red)

    # value-hist depth bounds: the dense signed-value space is
    # 2^(depth+1) codes (sign rides as the top code bit) — the fused
    # kernel's one-hot axis caps at _ONEPASS_KERNEL_MAX_CODES, the
    # XLA/host histograms at _ONEPASS_MAX_CODES
    _VALUEHIST_MAX_DEPTH = 19

    def bsi_value_hist(self, idx, field, filter_call,
                       shards: list[int], pre):
        """Fused per-VALUE histogram over `field`'s BSI planes under
        an optional filter tree — the Range/Distinct byproduct of the
        single-pass GroupBy tile walk (kernels.bsi_value_hist): one
        pass over the plane stack yields counts per signed value,
        from which Distinct, Min/Max, and Range counts derive with no
        per-column decode.  Returns (pos (2^depth,), neg (2^depth,))
        int64; raises Unstackable past the dense-histogram depth
        bound (callers keep the decode-stream fallback)."""
        depth = field.bit_depth
        if depth > self._VALUEHIST_MAX_DEPTH or depth < 1:
            raise Unstackable("value histogram depth bound")
        skey = tuple(shards)
        if not skey:
            z = np.zeros(1 << depth, np.int64)
            return z, z.copy()
        filt = None
        if filter_call is not None:
            filt = self.words(idx, filter_call, list(skey), pre)
            if filt is None:            # statically-empty filter
                z = np.zeros(1 << depth, np.int64)
                return z, z.copy()
        n_codes = 1 << (depth + 1)
        multi = self._n_total_devices() > 1
        op_bytes = 4 * len(skey) * (idx.width // 32) * (
            (2 + depth) + (1 if filt is not None else 0))
        if self._onepass_host(multi) or multi:
            # host native/numpy arm (and the mesh fan-in: one pass
            # either way, partials summed in host ints).  The
            # code-plane layout mirrors kernels.bsi_value_hist — the
            # single owner of the transform — sign plane as the top
            # code bit, exists AND filter as validity.
            from pilosa_tpu.storage import native_ingest as ni
            planes = np.asarray(self.plane_stack_np(idx, field, skey))
            t0 = time.perf_counter()
            counts = np.zeros(n_codes, np.int64)
            nn_d = np.zeros(n_codes, np.int64)
            zd = np.zeros((n_codes, 0), np.int64)
            ones = np.uint32(0xFFFFFFFF)
            for si in range(planes.shape[0]):
                cp = np.concatenate([planes[si, 2:], planes[si, 1:2]])
                valid = planes[si, 0] & (
                    np.asarray(filt)[si] if filt is not None else ones)
                ni.groupcode_hist(cp, valid, None, n_codes, True,
                                  counts, nn_d, zd, zd)
            dt = time.perf_counter() - t0
            flight.note_phase("execute", dt)
            roofline.note("vhist", op_bytes, dt)
        else:
            arm = _onepass_arm(n_codes, 0)
            key = ("vhist", arm, filt is not None, depth, n_codes)
            fn = _gb_jit_get(key)
            if fn is None:
                @bm.named("vhist")
                def run(planes, filt):
                    # the planes-to-code layout lives in ONE place —
                    # kernels.bsi_value_hist; only the arm varies here
                    pos, neg = kernels.bsi_value_hist(
                        planes, filt, gb=_onepass_gb(arm))
                    return jnp.concatenate([pos, neg])
                fn = jax.jit(run)
                _gb_jit_put(key, fn)
            planes = self.plane_stack(idx, field, skey)
            fd = jnp.asarray(filt) if filt is not None else None
            kind = _dispatch_kind(key, [planes] + (
                [fd] if fd is not None else []), ())
            out, dt = timed_call(kind, fn, planes, fd)
            counts = np.asarray(out, dtype=np.int64)
            if kind == "execute":
                roofline.note("vhist", op_bytes, dt)
        pos_h, neg_h = counts[: 1 << depth], counts[1 << depth:]
        if filter_call is None and \
                set(skey) >= set(idx.available_shards):
            # data-stats harvest (obs/stats.py): an UNFILTERED value
            # histogram over the FULL shard set is the field's value
            # distribution — persist the summary for free.  A
            # filtered one describes the filter, and a shard-subset
            # one (cluster leg, shards= restriction) describes a
            # slice — neither may pose as the field
            stats.note_value_hist(idx.name, field.name, pos_h, neg_h)
        return pos_h, neg_h

    def _row_counts_packed_host(self, view: PageView):
        """(R,) counts of an UNFILTERED candidate stack straight from
        its pages' encode-time per-lane popcounts (one lane = one
        (row, shard) slab) — the TopN packed arm.  Bytes touched =
        the encoded payload; dense pages in the mix popcount on the
        host (one page, not the whole stack)."""
        if len(view.shape) != 3:
            return None
        r, s, _w = view.shape
        t0 = time.perf_counter()
        parts = []
        enc_bytes = 0
        for p in view.pages:
            enc_bytes += encode.payload_nbytes(p)
            if encode.is_encoded(p):
                parts.append(np.asarray(p.lane_counts,
                                        dtype=np.int64))
            else:
                parts.append(np.bitwise_count(np.asarray(p))
                             .sum(axis=1, dtype=np.int64))
        flat = np.concatenate(parts)
        if view.lane_page is not None:
            # undo the placement permutation: lane -> page row
            flat = flat[view.lane_page.astype(np.int64)
                        * view.page_lanes + view.lane_slot]
        out = flat[: r * s].reshape(r, s).sum(axis=1)
        dt = time.perf_counter() - t0
        flight.note_phase("execute", dt)
        roofline.note("topn", enc_bytes, dt)
        return out

    def row_counts(self, idx, rows_stack, filter_call, shards: list[int],
                   pre) -> np.ndarray:
        """(R,) exact intersection counts of candidate-row stacks
        against a filter tree — the TopN/TopK hot loop as one fused
        device pass (executor.go:2750 topKFilter).  A PageView
        candidate stack (fetched under the engine's sparse_raw()
        context) serves unfiltered scans from encode-time lane
        popcounts; filtered scans decode it to the identical dense
        operand."""
        if isinstance(rows_stack, PageView):
            if filter_call is None and rows_stack.encoded():
                fast = self._row_counts_packed_host(rows_stack)
                if fast is not None:
                    return fast
            rows_stack = _expand_view(rows_stack)
        b = PlanBuilder(self, idx, shards, pre)
        rows_i = b._add_leaf(rows_stack)
        tree = (self._build_timed(b, filter_call)
                if filter_call is not None else None)
        if tree == ("zeros",):
            return np.zeros(rows_stack.shape[0], dtype=np.int64)
        red = self._reduce_in_program(shards)
        out = np.asarray(
            self._run(("row_counts", rows_i, tree, red), b), dtype=np.int64)
        return out if red else out.sum(axis=1)

    # -- one-pass group-code GroupBy ------------------------------------
    # The histogram path reads every stack word and every BSI plane
    # word exactly ONCE regardless of combo count (O(S*W) traffic vs
    # the per-combo kernels' O(C*S*W)): columns decode to a dense
    # group code composed from packed per-field digit planes, and
    # counts + sign-split plane partials accumulate into a (K, G)
    # table (MXU matmuls on TPU, the native C histogram on host, the
    # XLA scatter reference elsewhere).  Requires each field's rows to
    # be DISJOINT in the data (mutex/bool always are; set fields are
    # checked and cached); overlapping rows fall back to the per-combo
    # paths, as do sparse combo selections where C is small enough
    # that per-combo work wins (paged tails, tiny products).

    def _rows_disjoint(self, idx, f, row_ids, skey: tuple) -> bool:
        """True iff no column is set in two of `row_ids` of f, checked
        against the data (sum of per-row popcounts == popcount of the
        union, per fragment) and cached by fragment versions."""
        from pilosa_tpu.models.schema import FieldType
        if f.options.type in (FieldType.MUTEX, FieldType.BOOL):
            return True
        row_key = tuple(int(r) for r in row_ids)
        if len(set(row_key)) != len(row_key):
            return False  # a duplicated row belongs to two combos
        key = (idx.name, f.name, row_key, skey)
        frags = self._frags(idx, f, VIEW_STANDARD, list(skey))
        versions = self._versions(frags)
        ent = self._disjoint_cache.get(key)
        if ent is not None and ent[0] == versions:
            return ent[1]
        ok = True
        for fr in frags:
            if fr is None:
                continue
            acc = None
            total = 0
            for r in row_key:
                wds = fr.row_words(r)
                total += int(np.bitwise_count(wds).sum())
                acc = wds.astype(np.uint32) if acc is None else acc | wds
            if acc is not None and total != int(
                    np.bitwise_count(acc).sum()):
                ok = False
                break
        self._disjoint_cache[key] = (versions, ok)
        while len(self._disjoint_cache) > 4096:
            self._disjoint_cache.popitem(last=False)
        return ok

    def groupcode_stack(self, idx, fields_rows, skey: tuple,
                        flat: bool = False, as_np: bool = False):
        """(S, CB+1, W) cached group-code stack: CB packed code
        bit-planes (each field's digit planes, stride-concatenated in
        _code_space layout) plus the VALID plane last (AND of the
        field unions — the columns that belong to some combo).  Built
        host-side from fragment rows in one pass; placed like any
        other leaf (flat=True: shard axis over ALL mesh devices for
        the shard_map body; as_np=True: raw numpy for the host
        histogram)."""
        shards = list(skey)
        fkey = tuple((f.name, tuple(int(r) for r in rl))
                     for f, rl in fields_rows)
        key = ("groupcodes", idx.name, fkey, skey, self._mesh_key(),
               flat, as_np)
        per_field = [self._frags(idx, f, VIEW_STANDARD, shards)
                     for f, _ in fields_rows]
        versions = tuple(v for fr in per_field
                         for v in self._versions(fr))
        bits, shifts, _n_codes = _code_space(fields_rows)
        cb = sum(bits)

        def build_host():
            w = idx.width // 32
            out = np.zeros((len(shards), cb + 1, w), dtype=np.uint32)
            out[:, cb] = 0xFFFFFFFF
            for (f, rl), frags, sh in zip(fields_rows, per_field,
                                          shifts):
                union = np.zeros((len(shards), w), np.uint32)
                nb = bm.digit_bits(len(rl))
                for si, fr in enumerate(frags):
                    if fr is None:
                        continue
                    pos = fr.row_positions(rl)
                    if pos is not None:
                        # a code-held fragment: every digit plane from
                        # one lookup a column, not a row at a time
                        has = pos >= 0
                        union[si] = _pack_bits(has)
                        for b in range(nb):
                            out[si, sh + b] = _pack_bits(
                                has & ((pos >> b) & 1 == 1))
                        continue
                    for di, r in enumerate(rl):
                        wds = fr.row_words(int(r))
                        union[si] |= wds
                        b = 0
                        while di >> b:
                            if (di >> b) & 1:
                                out[si, sh + b] |= wds
                            b += 1
                out[:, cb] &= union
            return out

        def build():
            out = build_host()
            if as_np or self.host_only:
                return out
            if self.mesh is None:
                return jnp.asarray(out)
            from pilosa_tpu.parallel.mesh import place_flat, place_shards
            if flat:
                return place_flat(self.mesh, out, shard_axis=0)
            return place_shards(self.mesh, out, batch_axes=1)

        # delta patching: a write to row rl[di] of field fi dirties
        # shard si's digit planes {sh_fi + b : bit b of di set} and
        # its VALID plane (the AND of field unions); lane = si*(cb+1)
        # + plane index
        def lane_words(lane):
            w = idx.width // 32
            si, p = divmod(lane, cb + 1)
            if p == cb:  # valid plane
                out = np.full(w, 0xFFFFFFFF, dtype=np.uint32)
                for (_f, rl), frags in zip(fields_rows, per_field):
                    union = np.zeros(w, np.uint32)
                    fr = frags[si]
                    if fr is not None:
                        for r in rl:
                            union |= fr.row_words(int(r))
                    out &= union
                return out
            for (_f, rl), frags, sh, nb in zip(fields_rows, per_field,
                                               shifts, bits):
                if sh <= p < sh + nb:
                    b = p - sh
                    out = np.zeros(w, np.uint32)
                    fr = frags[si]
                    if fr is not None:
                        for di, r in enumerate(rl):
                            if (di >> b) & 1:
                                out |= fr.row_words(int(r))
                    return out
            return np.zeros(w, np.uint32)

        frags_flat, lanes = [], []
        for (_f, rl), frags, sh, nb in zip(fields_rows, per_field,
                                           shifts, bits):
            for si, fr in enumerate(frags):
                frags_flat.append(fr)
                lmap: dict[int, tuple] = {}
                valid_lane = si * (cb + 1) + cb
                for di, r in enumerate(rl):
                    lns = tuple(si * (cb + 1) + sh + b
                                for b in range(nb) if (di >> b) & 1)
                    lmap[int(r)] = lmap.get(int(r), ()) + lns + \
                        (valid_lane,)
                lanes.append(lmap)
        # weight 4: a group-code page ORs every mapped row per lane —
        # far costlier to restack per byte than a plain row page, so
        # the cost-aware eviction policy holds its pages longer
        return self._cached_stack(
            key, versions, build,
            frags=frags_flat, lanes=lanes,
            logical_lead=(len(shards), cb + 1),
            lane_words=lane_words, width_words=idx.width // 32,
            build_host=build_host,
            versions_fn=lambda: tuple(v for fr in per_field
                                      for v in self._versions(fr)),
            weight=4.0, pageable=not (flat or as_np),
            alive_fn=lambda: all(idx.fields.get(f.name) is f
                                 for f, _ in fields_rows),
            lane_device=(None if (flat or as_np)
                         else self._lane_devices(
                             idx, skey, (len(shards), cb + 1), 0)),
            shard_axis=0)

    def plane_stack_np(self, idx, field, skey: tuple):
        """Host numpy twin of plane_stack for the native histogram
        (no device round trip on CPU backends)."""
        shards = list(skey)
        depth = field.bit_depth
        key = ("planes_np", idx.name, field.name, depth, skey)
        frags = self._frags(idx, field, field.bsi_view, shards)
        versions = self._versions(frags)

        def build():
            out = np.zeros((len(shards), 2 + depth, idx.width // 32),
                           dtype=np.uint32)
            for i, fr in enumerate(frags):
                if fr is not None:
                    for r in range(2 + depth):
                        out[i, r] = fr.row_words(r)
            return out

        lanes, lane_words = self._plane_lanes(frags, len(shards),
                                              depth, idx.width)
        # host numpy twin: never paged (pages are a DEVICE residency
        # unit), but still ledger-accounted via the whole-entry path
        return self._cached_stack(
            key, versions, build,
            frags=frags, lanes=lanes,
            logical_lead=(len(shards), 2 + depth),
            lane_words=lane_words, width_words=idx.width // 32,
            pageable=False)

    def _onepass_host(self, multi: bool) -> bool:
        """Whether the one-pass histogram runs on the host (native C /
        numpy) instead of a device program.  A forced device arm
        (_forced_arm) overrides the CPU-backend host preference but
        never host_only harnesses."""
        if self.host_only:
            return True
        if _forced_arm():
            return False
        return not multi and jax.default_backend() != "tpu"

    def _groupby_unit_model(self, idx, fields_rows, n_combos: int,
                            depth: int, has_agg: bool,
                            skey: tuple) -> tuple[float, float]:
        """(one-pass units, per-combo units) for this shape — the
        same unit model the gate compares, exposed so the execution
        sites can note measured seconds against it."""
        return _groupby_unit_costs(fields_rows, n_combos, depth,
                                   has_agg, len(skey),
                                   idx.width // 32)

    def _groupby_onepass_ok(self, idx, fields_rows, n_combos: int,
                            depth: int, has_agg: bool,
                            skey: tuple, pruned: bool = False) -> bool:
        """Gate + cost model for the one-pass histogram.
        PILOSA_TPU_GROUPBY_ONEPASS=0 disables, =1 forces (still
        requires disjoint rows — correctness, not cost).  `pruned`: the
        walk is over the rows the filter leaves (_groupby_pruned), so
        the full code space is never formed and has no bound."""
        import os
        flag = os.environ.get("PILOSA_TPU_GROUPBY_ONEPASS", "")
        if flag == "0":
            return False
        bits, _shifts, n_codes = _code_space(fields_rows)
        if n_codes > _ONEPASS_MAX_CODES and not pruned:
            return False
        # device paths accumulate the histogram in int32 in-program;
        # the host path sums in int64 and has no shard bound
        host = self._onepass_host(self._n_total_devices() > 1)
        if not host and len(skey) > _REDUCE_MAX_SHARDS:
            return False
        if not all(self._rows_disjoint(idx, f, rl, skey)
                   for f, rl in fields_rows):
            return False
        if flag == "1":
            return True
        cost_onepass, cost_percombo = _groupby_unit_costs(
            fields_rows, n_combos, depth, has_agg, len(skey),
            idx.width // 32)
        # measured seconds-per-unit per arm from the statistics
        # catalog (stats.note_gate at the execution sites below);
        # (1.0, 1.0) — the static unit model — until both arms have
        # samples or with PILOSA_TPU_STATS=0.  Plan choice only:
        # results are bit-exact on either arm by construction.
        r_one, r_combo = stats.gate_rates("groupby_onepass",
                                          "groupby_percombo")
        return cost_onepass * r_one < cost_percombo * r_combo

    def _groupby_onepass_path(self, idx, fields_rows, agg_field, skey,
                              combos, depth: int, signed: bool,
                              filter_call, pre, agg_op: str = "sum",
                              filt=None):
        """Run the one-pass histogram and gather the requested combos
        out of the dense code space.  Returns the same (counts, agg)
        shape as the per-combo paths — bit-exact partials included.
        ``agg_op`` "min"/"max" additionally pulls the per-group
        magnitude Min/Max table out of the SAME tile walk (fused
        kernel masked reduce / XLA scatter / numpy twin) and returns
        (counts, (nn, values)) instead of Sum partials.  `filt`: the
        filter's words where the caller evaluated them already."""
        minmax = agg_op in ("min", "max")
        bits, shifts, n_codes = _code_space(fields_rows)
        digits = _code_digits(fields_rows)
        combos_arr = np.asarray(combos, dtype=np.int64).reshape(
            len(combos), len(fields_rows))
        codes = _combo_codes(shifts, combos_arr)
        has_planes = agg_field is not None
        if filt is None and filter_call is not None:
            b0 = PlanBuilder(self, idx, list(skey), pre)
            tree0 = self._build_timed(b0, filter_call)
            if tree0 == ("zeros",):
                return _zero_groupby_result(len(combos), depth,
                                            agg_field, agg_op)
            filt = self._run(("words", tree0), b0)
        multi = self._n_total_devices() > 1
        host = self._onepass_host(multi)
        # roofline attribution: the one-pass histogram dispatches its
        # own jitted/native programs (not timed_dispatch), so the
        # bytes-touched x device-time join notes here per arm.
        # Bytes come from the single-pass traffic model (each tile
        # crosses VMEM once — kernels.groupby_onepass_hbm_bytes), NOT
        # from summing operand array sizes: the flat mesh placement
        # pads shards and the old per-arg sum credited that padding
        # (and any plane re-reads) as fresh traffic.  _dispatch_kind
        # keeps first-dispatch compiles out of the bandwidth gauge,
        # exactly like timed_dispatch.
        op_bytes = kernels.groupby_onepass_hbm_bytes(
            len(skey), idx.width // 32, sum(bits),
            depth if has_planes else 0, filt is not None)
        mm = None
        kdepth = depth if has_planes else 0
        if host:
            _count_onepass(("host", None, 0), "onepass", kdepth, n_codes)
            out = self._groupby_onepass_host(
                idx, fields_rows, agg_field, skey, n_codes, depth,
                signed, filt, minmax=minmax, op_bytes=op_bytes)
            counts, nn, pos, neg = out[:4]
            if minmax:
                mm = out[4]
        elif multi and not minmax:
            plan = _onepass_plan(n_codes, kdepth, digits, signed)
            arm = plan[0]
            _count_onepass(plan, "onepass_mesh", kdepth,
                           _walked(arm, plan[1], digits, n_codes))
            cg = self.groupcode_stack(idx, fields_rows, skey,
                                      flat=True)
            planes = (self.plane_stack_flat(idx, agg_field, skey)
                      if has_planes else None)
            fn = _groupby_onepass_shard_map(
                self.mesh, arm,
                has_planes, filt is not None, signed, n_codes, digits)
            args = [cg]
            if filt is not None:
                # the filter tree ran under the 1D shard placement;
                # re-pad it host-side to the flat layout's multiple
                f_np = np.asarray(filt)[:len(skey)]
                pad = cg.shape[0] - f_np.shape[0]
                if pad:
                    f_np = np.pad(f_np, ((0, pad), (0, 0)))
                args.append(f_np)
            if has_planes:
                args.append(planes)
            sig = ("onepass_mesh", arm, has_planes, filt is not None,
                   signed, n_codes, digits)
            kind = _dispatch_kind(sig, args, ())
            out, dt = timed_call(kind, fn, *args)
            if kind == "execute":
                roofline.note("groupby", op_bytes, dt)
            counts, nn, pos, neg = _onepass_unpack(
                out, n_codes, depth, has_planes)
        else:
            # single device — or a mesh Min/Max, which needs max/min
            # combination and so runs the single-jit program over the
            # whole (mesh-sharded) stack (Min/Max traffic is the same
            # single pass; fleets beyond the reduce bound were gated)
            plan = _onepass_plan(n_codes, kdepth, digits, signed, minmax,
                                 mesh_minmax=multi and minmax)
            arm = plan[0]
            _count_onepass(plan, "onepass", kdepth,
                           _walked(arm, plan[1], digits, n_codes))
            cg = self.groupcode_stack(idx, fields_rows, skey)
            planes = (self.plane_stack(idx, agg_field, skey)
                      if has_planes else None)
            fn = _groupby_onepass_jit(
                arm, has_planes,
                filt is not None, signed, n_codes, minmax=minmax,
                digits=digits)
            sig = ("onepass", arm, has_planes, filt is not None,
                   signed, n_codes, minmax, digits)
            args = [a for a in (cg, filt, planes) if a is not None]
            kind = _dispatch_kind(sig, args, ())
            out, dt = timed_call(kind, fn, cg, filt, planes)
            if kind == "execute":
                roofline.note("groupby", op_bytes, dt)
            out = _onepass_unpack(out, n_codes, depth, has_planes,
                                  minmax=minmax)
            counts, nn, pos, neg = out[:4]
            if minmax:
                mm = out[4]
        sel_counts = counts[codes]
        if not has_planes:
            return sel_counts, None
        if minmax:
            vals, _has = kernels.minmax_from_table(mm, depth, agg_op)
            return sel_counts, (nn[codes], vals[codes])
        return sel_counts, (nn[codes], pos[codes], neg[codes])

    def _selects(self) -> bool:
        """Whether a pruned GroupBy walks a selection of the cached
        group-code stack (the packed kernel's `sel`): on one device,
        where the fused arm serves."""
        return (self._n_total_devices() == 1
                and not self._onepass_host(False)
                and _onepass_arm(1, 0, body="packed") == "fused")

    def _groupby_live_rows(self, cg, filt, digits, host: bool) -> list:
        """The pruning prepass (stage ``groupby.prune``): per field,
        the positions in its row list of the rows that the filter
        leaves a column in, every other field having one too, read off
        the group-code stack `cg` (numpy where `host`).  A row outside
        this set is in no combination with a count, so the walk drops
        it."""
        if host:
            cg = np.asarray(cg)
            valid = cg[:, -1] & np.asarray(filt)[:cg.shape[0]]
            hit = bm.code_from_planes_np(valid[None]) > 0
            counts, at = [], 0
            for bits, rows in digits:
                if bits:
                    code = bm.code_from_planes_np(
                        np.moveaxis(cg[:, at:at + bits], 1, 0))
                    counts.append(np.bincount(
                        code[hit], minlength=1 << bits)[:rows])
                else:
                    counts.append(np.array([hit.sum()]))
                at += bits
            counts = np.concatenate(counts)
        else:
            # the Pallas call would replicate a mesh-placed stack
            arm = ("xla" if self._n_total_devices() > 1
                   else _onepass_arm(1, 0))
            fn = _groupby_marginals_jit(arm, digits, True)
            kind = _dispatch_kind(("marginals", arm, digits), (cg, filt), ())
            out, _dt = pressure.guarded(
                lambda: timed_call(kind, fn, cg, filt))
            counts = np.asarray(out)
        live, at = [], 0
        for _bits, rows in digits:
            live.append(np.flatnonzero(counts[at:at + rows]))
            at += rows
        return live

    def _groupby_pruned(self, idx, fields_rows, agg_field, skey,
                        depth: int, signed: bool, filter_call, pre):
        """A filtered one-pass GroupBy over the rows the filter leaves
        a column in (executor.go's groupByIterator skips a prefix
        that does not meet the filter; here each field's rows are
        dropped by their count under it, _groupby_live_rows, before
        any code space is formed).  On the packed kernel the walk
        selects the surviving rows of the cached group-code stack,
        each field's count rounded up to a power of two with dead
        slots (kernels.selection).  Where no selection is walked, a
        code space past _ONEPASS_MAX_CODES is histogrammed over a
        group-code stack of the surviving rows, and inside it this
        returns None: the caller walks the whole code space.  Returns
        (combos, counts, agg) over the product of the surviving rows
        in product order, the combos indexing the full row lists."""
        nf = len(fields_rows)
        kdepth = depth if agg_field is not None else 0
        none = np.zeros((0, nf), np.int64)
        b0 = PlanBuilder(self, idx, list(skey), pre)
        tree0 = self._build_timed(b0, filter_call)
        if tree0 == ("zeros",):
            return (none,) + _zero_groupby_result(0, depth, agg_field)
        filt = self._run(("words", tree0), b0)
        digits = _code_digits(fields_rows)
        host = self._onepass_host(self._n_total_devices() > 1)
        cg = self.groupcode_stack(idx, fields_rows, skey, as_np=host)
        with flight.stage("groupby.prune"):
            live = self._groupby_live_rows(cg, filt, digits, host)
        if any(rows.size == 0 for rows in live):
            return (none,) + _zero_groupby_result(0, depth, agg_field)
        local = product_combos([rows.size for rows in live])
        combos = np.stack([rows[local[:, i]] for i, rows in enumerate(live)],
                          axis=1)
        sel_digits = tuple((bits, _bucket(rows.size))
                           for (bits, _r), rows in zip(digits, live))
        n_sel = int(np.prod([b for _x, b in sel_digits], dtype=np.int64))
        sel = (kernels.selection(sel_digits, kdepth, signed, live)
               if self._selects() else None)
        if sel is None:
            if _code_space(fields_rows)[2] <= _ONEPASS_MAX_CODES:
                return None
            pruned = [(f, [rl[i] for i in rows])
                      for (f, rl), rows in zip(fields_rows, live)]
            counts, agg = self._groupby_onepass_path(
                idx, pruned, agg_field, skey, local, depth, signed,
                filter_call, pre, filt=filt)
            return combos, counts, agg
        has_planes = agg_field is not None
        n_pass = kernels._packed_passes(sel_digits, kdepth, signed, True)[3]
        _count_onepass(("fused", "packed", n_pass), "onepass", kdepth, n_sel)
        planes = (self.plane_stack(idx, agg_field, skey)
                  if has_planes else None)
        fn = _groupby_selected_jit(has_planes, True, signed, sel_digits)
        sel = jnp.asarray(sel)
        sig = ("selected", has_planes, signed, sel_digits)
        args = [a for a in (cg, filt, planes, sel) if a is not None]
        kind = _dispatch_kind(sig, args, ())
        # a pruned walk runs beside a stack cache that may be at its
        # budget: an OOM sheds and retries once (memory/pressure.py)
        out, dt = pressure.guarded(
            lambda: timed_call(kind, fn, cg, filt, planes, sel))
        if kind == "execute":
            roofline.note("groupby", kernels.groupby_onepass_hbm_bytes(
                len(skey), idx.width // 32, sum(b for b, _r in digits),
                kdepth, True), dt)
        counts, nn, pos, neg = _onepass_unpack(out, n_sel, depth,
                                               has_planes)
        shifts = np.cumsum([0] + [b for b, _r in
                                  kernels.selected_digits(sel_digits)])
        codes = np.zeros(len(local), np.int64)
        for i in range(nf):
            codes |= local[:, i] << shifts[i]
        agg = (nn[codes], pos[codes], neg[codes]) if has_planes else None
        return combos, counts[codes], agg

    def _groupby_onepass_host(self, idx, fields_rows, agg_field, skey,
                              n_codes: int, depth: int, signed: bool,
                              filt, minmax: bool = False,
                              op_bytes: int | None = None):
        """Host histogram: the native C kernel (numpy bincount without
        a toolchain) per shard, shards fanned over a thread pool (the
        ctypes call releases the GIL).  ``minmax`` adds the numpy
        Min/Max magnitude-table twin to the same per-shard walk."""
        import os

        from pilosa_tpu.storage import native_ingest as ni
        from pilosa_tpu.taskpool import Pool

        cg = np.asarray(self.groupcode_stack(idx, fields_rows, skey,
                                             as_np=True))
        planes = (np.asarray(self.plane_stack_np(idx, agg_field, skey))
                  if agg_field is not None else None)
        filt_np = (np.asarray(filt)[:len(skey)]
                   if filt is not None else None)
        if op_bytes is None:
            # the native hist streams these operands once — the same
            # single-pass traffic model as the device arms
            op_bytes = (cg.nbytes
                        + (planes.nbytes if planes is not None else 0)
                        + (filt_np.nbytes if filt_np is not None else 0))
        big = 1 << depth
        t0 = time.perf_counter()

        def one(_pool, si):
            c = np.zeros(n_codes, np.int64)
            n_ = np.zeros(n_codes, np.int64)
            p_ = np.zeros((n_codes, depth), np.int64)
            g_ = np.zeros((n_codes, depth), np.int64)
            valid = cg[si, -1]
            if filt_np is not None:
                valid = valid & filt_np[si]
            ni.groupcode_hist(
                cg[si, :-1], valid,
                planes[si] if planes is not None else None,
                n_codes, signed, c, n_, p_, g_)
            mm = None
            if minmax:
                mm = np.stack([
                    np.full(n_codes, -1, np.int64),
                    np.full(n_codes, big, np.int64),
                    np.full(n_codes, -1, np.int64),
                    np.full(n_codes, big, np.int64)])
                ni.groupcode_minmax(cg[si, :-1], valid, planes[si],
                                    n_codes, signed, mm)
            return c, n_, p_, g_, mm

        size = max(1, min(8, os.cpu_count() or 1, cg.shape[0]))
        parts = Pool(size=size).map(one, range(cg.shape[0]))
        dt = time.perf_counter() - t0
        flight.note_phase("execute", dt)
        roofline.note("groupby", op_bytes, dt)
        counts = sum(p[0] for p in parts)
        if agg_field is None:
            return counts, None, None, None
        out = (counts, sum(p[1] for p in parts),
               sum(p[2] for p in parts), sum(p[3] for p in parts))
        if not minmax:
            return out
        mm = parts[0][4]
        for p in parts[1:]:
            mm = np.stack([np.maximum(mm[0], p[4][0]),
                           np.minimum(mm[1], p[4][1]),
                           np.maximum(mm[2], p[4][2]),
                           np.minimum(mm[3], p[4][3])])
        return out + (mm,)

    # fused GroupBy kernel (ops/kernels.groupby_sum): default on a
    # single real TPU device (speed against the XLA scan: not
    # measured on today's code).  Filter trees, big combo
    # spaces (one-hot lane bound), multi-device meshes (needs a
    # shard_map wrap), host-only mode, and CPU (interpreter) fall back
    # to the XLA path.  PILOSA_TPU_GROUPBY_KERNEL=0 disables; =1
    # forces (tests exercise the interpreter path this way).
    _GROUPBY_KERNEL_MAX_COMBOS = 1024

    def _groupby_kernel_ok(self, n_combos: int, n_shards: int,
                           has_filter: bool = False) -> bool:
        import os
        flag = os.environ.get("PILOSA_TPU_GROUPBY_KERNEL", "")
        if flag == "0" or self.host_only:
            return False
        if self._n_total_devices() > 1:
            # the shard_map wrapper keeps the strict bounds: no
            # filter masking, int32 shard accumulation, one-hot
            # combo lanes
            if (has_filter or n_combos > self._GROUPBY_KERNEL_MAX_COMBOS
                    or n_shards > _REDUCE_MAX_SHARDS):
                return False
        # single device: combos CHUNK through the kernel, shards
        # chunk with int64 host accumulation, and filters AND into
        # the first row stack before the kernel (r04 guard lift —
        # big shapes no longer silently shed the 4x kernel win)
        if flag == "1":
            return True
        return jax.default_backend() == "tpu"

    def _groupby_kernel_path(self, idx, fields_rows, agg_field, skey,
                             combos, depth: int, signed: bool,
                             filt=None):
        from pilosa_tpu.obs.metrics import GROUPBY_KERNEL
        GROUPBY_KERNEL.inc()
        multi = self._n_total_devices() > 1
        # roofline: the per-combo kernel's schedule reads each
        # referenced stack row once PER REFERENCING COMBO and the
        # plane block once total — its own traffic model, distinct
        # from both the one-pass walk and the XLA scan (ISSUE 11)
        op_bytes = kernels.groupby_percombo_hbm_bytes(
            len(skey), idx.width // 32, len(combos),
            len(fields_rows), depth if agg_field is not None else 0)
        if multi:
            stacks = [self.rows_stack_flat(idx, f, (VIEW_STANDARD,),
                                           rl, skey)
                      for f, rl in fields_rows]
            planes = (self.plane_stack_flat(idx, agg_field, skey)
                      if agg_field is not None else None)
            fn = _groupby_kernel_shard_map(
                self.mesh, len(stacks), planes is not None, signed)
            sel = np.asarray(combos, dtype=np.int32).reshape(
                len(combos), len(fields_rows))
            sig = ("gbkernel_mesh", len(stacks), planes is not None,
                   signed)
            kind = _dispatch_kind(
                sig, stacks + ([planes] if planes is not None else []),
                (sel,))
            out, dt = timed_call(
                kind, fn, tuple(stacks), sel,
                *(() if planes is None else (planes,)))
            if kind == "execute":
                roofline.note("groupby", op_bytes, dt)
            return self._groupby_kernel_unpack(out, len(combos),
                                               depth, agg_field)
        # single device: shard-chunked (int64 host accumulation past
        # the int32-exact bound) x combo-chunked (one-hot lane bound)
        # with an optional pre-ANDed filter mask (r04 guard lift)
        fn = _groupby_kernel_jit(len(fields_rows),
                                 agg_field is not None, signed)
        k = len(combos)
        ckn = self._GROUPBY_KERNEL_MAX_COMBOS
        counts = np.zeros(k, dtype=np.int64)
        agg = (np.zeros(k, dtype=np.int64),
               np.zeros((k, depth), dtype=np.int64),
               np.zeros((k, depth), dtype=np.int64)) \
            if agg_field is not None else None
        # dispatch timing spans the whole chunk sweep; a compile on
        # ANY chunk keeps the sweep out of the bandwidth gauge
        dispatch_s = 0.0
        compiled_any = False
        for slo in range(0, len(skey), _REDUCE_MAX_SHARDS):
            sc = skey[slo:slo + _REDUCE_MAX_SHARDS]
            stacks = [self.rows_stack_for(idx, f, (VIEW_STANDARD,),
                                          rl, sc)
                      for f, rl in fields_rows]
            if filt is not None:
                fslice = filt[slo:slo + _REDUCE_MAX_SHARDS]
                stacks = ([jnp.bitwise_and(stacks[0],
                                           fslice[None, :, :])]
                          + list(stacks[1:]))
            planes = (self.plane_stack(idx, agg_field, sc)
                      if agg_field is not None else None)
            for clo in range(0, k, ckn):
                sel = np.asarray(
                    combos[clo:clo + ckn], dtype=np.int32).reshape(
                    -1, len(fields_rows))
                sig = ("gbkernel", len(fields_rows),
                       agg_field is not None, signed)
                args = list(stacks) + (
                    [planes] if planes is not None else [])
                kind = _dispatch_kind(sig, args, (sel,))
                if kind == "compile":
                    compiled_any = True
                out, dt = timed_call(kind, fn, tuple(stacks), sel,
                                     planes)
                dispatch_s += dt
                kc = sel.shape[0]
                c, a = self._groupby_kernel_unpack(out, kc, depth,
                                                   agg_field)
                counts[clo:clo + kc] += c
                if a is not None:
                    agg[0][clo:clo + kc] += a[0]
                    agg[1][clo:clo + kc] += a[1]
                    agg[2][clo:clo + kc] += a[2]
        if not compiled_any:
            roofline.note("groupby", op_bytes, dispatch_s)
        return counts, agg

    @staticmethod
    def _groupby_kernel_unpack(out, k: int, depth: int, agg_field):
        if agg_field is None:
            return np.asarray(out, dtype=np.int64), None
        flat = np.asarray(out, dtype=np.int64)
        counts, nn = flat[:k], flat[k:2 * k]
        pos = flat[2 * k:2 * k + k * depth].reshape(k, depth)
        neg = flat[2 * k + k * depth:].reshape(k, depth)
        return counts, (nn, pos, neg)

    def groupby(self, idx, fields_rows, filter_call, agg_field,
                shards: list[int], pre, combos=None,
                combo_chunk: int = 8, agg_op: str = "sum"):
        """GroupBy on the stacked engine: the given combos (index
        tuples into each field's row list — the caller pages them; None
        for every combination in product order) evaluated as chunked
        device programs over gathered (R, S, W) row stacks
        (executor.go:3918 + 8617 groupByIterator, re-expressed as
        fixed-shape gathers + one scan over the BSI planes for the Sum
        aggregate).

        fields_rows: [(field, row_ids), ...].  Returns (combos, counts
        (C,) int64, None | (nn (C,), pos (C, P), neg (C, P)) int64
        arrays), `combos` the given ones or, where none were given, the
        combinations answered: all of them, or those of the rows a
        filter leaves (_groupby_pruned) — a combination left out has
        no column.  ``agg_op`` "min"/"max" (per-group BSI Min/Max —
        served ONLY by the one-pass tile walk, whose Min/Max table
        falls out of the same single pass) gives (counts, (nn (C,),
        values (C,))) instead; shapes the one-pass gate refuses raise
        Unstackable so the caller's host loop keeps full generality."""
        skey = tuple(shards)
        n_combos = (len(combos) if combos is not None else int(np.prod(
            [len(rl) for _f, rl in fields_rows], dtype=np.int64)))
        depth = agg_field.bit_depth if agg_field is not None else 0
        # when no fragment holds any sign-plane bit (row_ids is cached
        # per fragment version, so this is a dict sweep, not a scan),
        # all paths skip the sign-split and negative popcounts
        # entirely.  Checked against the DATA, not options.min — value
        # writes are not range-enforced, so a declared min>=0 field
        # can still hold negatives.
        signed = False
        if agg_field is not None:
            frags = self._frags(idx, agg_field, agg_field.bsi_view,
                                list(skey))
            signed = any(fr is not None and 1 in fr.row_ids
                         for fr in frags)
        if (combos is None and filter_call is not None and agg_op == "sum"
                and n_combos and _prunes(fields_rows, depth, self._selects())
                and self._groupby_onepass_ok(
                    idx, fields_rows, n_combos, depth,
                    agg_field is not None, skey, pruned=True)):
            t_arm = time.perf_counter()
            out = self._groupby_pruned(idx, fields_rows, agg_field, skey,
                                       depth, signed, filter_call, pre)
            if out is not None:
                stats.note_gate(
                    "groupby_onepass",
                    self._groupby_unit_model(idx, fields_rows, len(out[0]),
                                             depth, agg_field is not None,
                                             skey)[0],
                    time.perf_counter() - t_arm)
                return out
        if combos is None:
            combos = product_combos([len(rl) for _f, rl in fields_rows])
        return (combos,) + self._groupby_combos(
            idx, fields_rows, filter_call, agg_field, skey, pre, combos,
            depth, signed, combo_chunk, agg_op)

    def _groupby_combos(self, idx, fields_rows, filter_call, agg_field,
                        skey, pre, combos, depth: int, signed: bool,
                        combo_chunk: int, agg_op: str):
        """groupby's (counts, agg) for the given combos."""
        n_combos = len(combos)
        # Min/Max aggregates only exist on the one-pass fused walk
        # (the per-combo kernels and XLA scan have no Min/Max table);
        # anything the gate refuses goes back to the caller's loop
        if agg_op in ("min", "max"):
            if (not n_combos
                    or not self._groupby_onepass_ok(
                        idx, fields_rows, n_combos, depth, True, skey)
                    or depth > _ONEPASS_KERNEL_MAX_DEPTH):
                raise Unstackable("groupby min/max needs the one-pass "
                                  "histogram gate")
            t_arm = time.perf_counter()
            out = self._groupby_onepass_path(
                idx, fields_rows, agg_field, skey, combos, depth,
                signed, filter_call, pre, agg_op=agg_op)
            stats.note_gate(
                "groupby_onepass",
                self._groupby_unit_model(idx, fields_rows, n_combos,
                                         depth, True, skey)[0],
                time.perf_counter() - t_arm)
            return out
        # one-pass group-code histogram: combo-count-independent
        # traffic, no (R, S, W) gather at all (the group-code stack is
        # (S, CB+1, W) with CB ~ log2 of the combo space)
        if n_combos and self._groupby_onepass_ok(
                idx, fields_rows, n_combos, depth,
                agg_field is not None, skey):
            # measured-rate calibration for the cost gate: note this
            # arm's wall seconds against its unit model so the next
            # gate decision compares measured ms, not constants
            t_arm = time.perf_counter()
            out = self._groupby_onepass_path(
                idx, fields_rows, agg_field, skey, combos, depth,
                signed, filter_call, pre)
            stats.note_gate(
                "groupby_onepass",
                self._groupby_unit_model(idx, fields_rows, n_combos,
                                         depth, agg_field is not None,
                                         skey)[0],
                time.perf_counter() - t_arm)
            return out
        kernel = self._groupby_kernel_ok(
            n_combos, len(skey), has_filter=filter_call is not None)
        # memory budget: the XLA path gathers (R, S, W) stacks for
        # the WHOLE shard set at once; the single-device kernel path
        # materializes only (R, min(S, _REDUCE_MAX_SHARDS), W) per
        # chunk (review r04 — the budget must not kill the very
        # fleets the shard-chunk lift exists for)
        total_rows = sum(len(rl) for _, rl in fields_rows)
        est_shards = len(skey)
        if kernel and self._n_total_devices() == 1:
            est_shards = min(est_shards, _REDUCE_MAX_SHARDS)
        est = total_rows * max(est_shards, 1) * (idx.width // 8)
        if est > (1 << 31):
            raise Unstackable(
                f"groupby row stacks ~{est >> 20} MiB exceed budget")
        if kernel:
            # gate-rate envelope opens before the filter dispatch:
            # every arm's sample must bracket the same cost scope
            t_arm = time.perf_counter()
            filt = None
            if filter_call is not None:
                # materialize the filter ONCE as an (S, W) device
                # stack (the XLA tree path), then AND it into the
                # first row stack — every kernel term includes the
                # combo intersection, so one mask filters counts and
                # aggregates alike (r04 guard lift)
                b0 = PlanBuilder(self, idx, list(skey), pre)
                tree0 = self._build_timed(b0, filter_call)
                if tree0 == ("zeros",):
                    return _zero_groupby_result(n_combos, depth,
                                                agg_field)
                filt = self._run(("words", tree0), b0)
            out = self._groupby_kernel_path(
                idx, fields_rows, agg_field, skey, combos, depth,
                signed, filt=filt)
            stats.note_gate(
                "groupby_percombo",
                self._groupby_unit_model(idx, fields_rows, n_combos,
                                         depth, agg_field is not None,
                                         skey)[1],
                time.perf_counter() - t_arm)
            return out
        # gate-rate envelope starts HERE so the XLA arm's sample
        # brackets the same cost scope as the one-pass/kernel sites
        # (stack build + plan + dispatch + unpack) — mixed envelopes
        # would systematically skew the measured gate rates
        t_arm = time.perf_counter()
        b = PlanBuilder(self, idx, list(skey), pre)
        stack_is = tuple(
            b._add_leaf(self.rows_stack_for(
                idx, f, (VIEW_STANDARD,), rl, skey))
            for f, rl in fields_rows)
        planes_i = None
        if agg_field is not None:
            planes_i = b._planes_leaf(agg_field)
        tree = None
        if filter_call is not None:
            tree = self._build_timed(b, filter_call)
            if tree == ("zeros",):
                return _zero_groupby_result(n_combos, depth, agg_field)
        red = self._reduce_in_program(skey)
        plan = ("groupby", stack_is, planes_i, tree, red, signed)
        nf = len(fields_rows)
        n_chunks = -(-n_combos // combo_chunk)
        padded = n_chunks * combo_chunk
        combo_idx = np.zeros((padded, nf), dtype=np.int32)
        combo_idx[:n_combos] = np.asarray(
            combos, dtype=np.int32).reshape(n_combos, nf)
        # pad combos re-count combo 0; their rows are dropped below
        sel_all = combo_idx.reshape(n_chunks, combo_chunk, nf)
        out = timed_dispatch(plan, b.leaves,
                             tuple(b.params) + (sel_all,))

        def note_arm():
            stats.note_gate(
                "groupby_percombo",
                self._groupby_unit_model(idx, fields_rows, n_combos,
                                         depth,
                                         agg_field is not None,
                                         skey)[1],
                time.perf_counter() - t_arm)

        if agg_field is None:
            c = np.asarray(out, dtype=np.int64)   # (n_chunks, C[, S])
            if not red:
                c = c.sum(axis=-1)
            counts = c.reshape(-1)[:n_combos]
            note_arm()
            return counts, None
        if red:
            # one flat (2*K + 2*K*P,) fetch, split by layout
            flat = np.asarray(out, dtype=np.int64)
            k = padded
            c = flat[:k]
            n_ = flat[k:2 * k]
            p_ = flat[2 * k:2 * k + k * depth].reshape(
                n_chunks, depth, combo_chunk)
            g_ = flat[2 * k + k * depth:].reshape(
                n_chunks, depth, combo_chunk)
        else:
            c, n_, p_, g_ = (np.asarray(x, dtype=np.int64) for x in out)
            # unreduced: trailing S axis summed here
            c, n_ = c.sum(axis=-1), n_.sum(axis=-1)
            p_, g_ = p_.sum(axis=-1), g_.sum(axis=-1)
        counts = c.reshape(-1)[:n_combos]
        nn = n_.reshape(-1)[:n_combos]
        # (n_chunks, P, C) -> (n_chunks*C, P)
        pos = p_.transpose(0, 2, 1).reshape(-1, depth)[:n_combos]
        neg = g_.transpose(0, 2, 1).reshape(-1, depth)[:n_combos]
        note_arm()
        return counts, (nn, pos, neg)

    # shards decoded per device call in decode_stream: bounds the
    # (4, S_chunk, 2^20)-int32 decode output to ~1 GiB at full width
    _DECODE_CHUNK = 64

    def decode_stream(self, idx, field, skey: tuple):
        """Stream decoded BSI values: yields (shard_ids, exists, values)
        with exists (S_c, width) bool and values (S_c, width) int64
        numpy arrays — ONE device program per <=_DECODE_CHUNK shards
        (ops.bsi.decode_device), never per-column host work."""
        shards = list(skey)
        if not shards:
            return
        planes = self.plane_stack(idx, field, tuple(skey))  # (S', P, W)
        if self.host_only or isinstance(planes, np.ndarray):
            pl = np.asarray(planes)
            depth = pl.shape[1] - 2
            for lo in range(0, len(shards), self._DECODE_CHUNK):
                hi = min(lo + self._DECODE_CHUNK, len(shards))
                ex = bsi_ops.unpack_bits_np(pl[lo:hi, 0])
                sign = bsi_ops.unpack_bits_np(pl[lo:hi, 1])
                vals = np.zeros(ex.shape, dtype=np.int64)
                for i in range(depth):
                    vals |= bsi_ops.unpack_bits_np(
                        pl[lo:hi, 2 + i]).astype(np.int64) << i
                vals = np.where(sign, -vals, vals)
                yield shards[lo:hi], ex, np.where(ex, vals, 0)
            return

        for lo in range(0, len(shards), self._DECODE_CHUNK):
            hi = min(lo + self._DECODE_CHUNK, len(shards))
            e, s, vlo, vhi = _decode_slice(planes, lo, hi - lo)
            ex, vals = bsi_ops.host_combine_decoded(e, s, vlo, vhi)
            yield shards[lo:hi], ex, vals

    def _rows_stack_np(self, idx, per_view, row_key, n_shards):
        """Host (R, S, W) assembly shared by the placement variants."""
        width = idx.width
        out = np.zeros((len(row_key), n_shards, width // 32),
                       dtype=np.uint32)
        for frags in per_view:
            for si, fr in enumerate(frags):
                if fr is not None:
                    for ri, r in enumerate(row_key):
                        out[ri, si] |= fr.row_words(r)
        return out

    def _rows_lanes(self, per_view, row_key, n_shards: int, width: int):
        """(frags_flat, lanes, lane_words) for an (R, S, W) candidate-
        row stack: lane = ri * S + si, shared by both placements."""
        def lane_words(lane):
            ri, si = divmod(lane, n_shards)
            out = np.zeros(width // 32, dtype=np.uint32)
            for frags in per_view:
                fr = frags[si]
                if fr is not None:
                    out |= fr.row_words(row_key[ri])
            return out

        frags_flat, lanes = [], []
        for frags in per_view:
            for si, fr in enumerate(frags):
                frags_flat.append(fr)
                lmap: dict[int, tuple] = {}
                for ri, r in enumerate(row_key):
                    lmap[r] = lmap.get(r, ()) + (ri * n_shards + si,)
                lanes.append(lmap)
        return frags_flat, lanes, lane_words

    def rows_stack_for(self, idx, field, views: tuple[str, ...],
                       row_ids, skey: tuple):
        """(R, S, W) stacked candidate rows for the TopN/TopK scan.

        Cached as ONE chunk-level entry (not R per-row entries): a
        broad TopN over thousands of rows must not flood the LRU and
        evict the hot per-query leaves, but a repeated TopN on a warm
        engine should not re-upload its candidate stacks either.
        """
        shards = list(skey)
        row_key = tuple(int(r) for r in row_ids)
        key = ("rowchunk", idx.name, field.name, views, row_key, skey,
               self._mesh_key())
        per_view = [self._frags(idx, field, vn, shards) for vn in views]
        versions = tuple(v for fr in per_view
                         for v in self._versions(fr))

        def build():
            out = self._rows_stack_np(idx, per_view, row_key,
                                      len(shards))
            if self.host_only:
                return out  # mirror place(): no device touch
            if self.mesh is None:
                return jnp.asarray(out)
            # 2D placement: candidate rows over the "rows" mesh axis,
            # shards over "shards" (the TopK/GroupBy row-block
            # parallelism named in parallel/mesh.py — zero-padded on
            # both axes; zero rows/shards are popcount-neutral)
            n = self.mesh.shape["shards"]
            s = out.shape[1]
            if s % n:
                out = np.concatenate(
                    [out, np.zeros((out.shape[0], n - s % n, out.shape[2]),
                                   dtype=out.dtype)], axis=1)
            nr = self.mesh.shape["rows"]
            r = out.shape[0]
            if r % nr:
                out = np.concatenate(
                    [out, np.zeros((nr - r % nr,) + out.shape[1:],
                                   dtype=out.dtype)], axis=0)
            from jax.sharding import NamedSharding, PartitionSpec as P
            return jax.device_put(
                out, NamedSharding(self.mesh, P("rows", "shards", None)))

        frags_flat, lanes, lane_words = self._rows_lanes(
            per_view, row_key, len(shards), idx.width)

        def build_host():
            return self._rows_stack_np(idx, per_view, row_key,
                                       len(shards))

        # the paged entry is WHY a broad TopN no longer evicts whole
        # hot stacks: its (R, S, W) candidate block pages along R*S
        # lanes, and budget pressure drops only the coldest page-sized
        # row-blocks
        return self._cached_stack(
            key, versions, build,
            frags=frags_flat, lanes=lanes,
            logical_lead=(len(row_key), len(shards)),
            lane_words=lane_words, width_words=idx.width // 32,
            build_host=build_host,
            versions_fn=lambda: tuple(v for fr in per_view
                                      for v in self._versions(fr)),
            alive_fn=lambda: idx.fields.get(field.name) is field,
            lane_device=self._lane_devices(
                idx, skey, (len(row_key), len(shards)), 1),
            shard_axis=1)

    # -- flat placements for the mesh GroupBy kernel --------------------
    # The shard_map kernel path shards the SHARD axis over every mesh
    # device (rows axis included) and replicates candidate rows — a
    # different layout from the 2D rows x shards placement above, so
    # these live under their own cache keys.

    def _n_total_devices(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None \
            else 1

    def rows_stack_flat(self, idx, field, views: tuple[str, ...],
                        row_ids, skey: tuple):
        """(R, S, W) with S sharded over ALL mesh devices, R
        replicated (the kernel gathers rows locally by sel)."""
        from pilosa_tpu.parallel.mesh import place_flat
        shards = list(skey)
        row_key = tuple(int(r) for r in row_ids)
        key = ("rowchunk_flat", idx.name, field.name, views, row_key,
               skey, id(self.mesh))
        per_view = [self._frags(idx, field, vn, shards) for vn in views]
        versions = tuple(v for fr in per_view
                         for v in self._versions(fr))

        def build():
            out = self._rows_stack_np(idx, per_view, row_key,
                                      len(shards))
            return place_flat(self.mesh, out, shard_axis=1)

        frags_flat, lanes, lane_words = self._rows_lanes(
            per_view, row_key, len(shards), idx.width)
        return self._cached_stack(
            key, versions, build,
            frags=frags_flat, lanes=lanes,
            logical_lead=(len(row_key), len(shards)),
            lane_words=lane_words, width_words=idx.width // 32,
            pageable=False)

    def plane_stack_flat(self, idx, field, skey: tuple):
        """(S, P, W) planes with S sharded over ALL mesh devices."""
        from pilosa_tpu.parallel.mesh import place_flat
        shards = list(skey)
        depth = field.bit_depth
        key = ("planes_flat", idx.name, field.name, depth, skey,
               id(self.mesh))
        frags = self._frags(idx, field, field.bsi_view, shards)
        versions = self._versions(frags)

        def build():
            width = idx.width
            out = np.zeros((len(shards), 2 + depth, width // 32),
                           dtype=np.uint32)
            for i, fr in enumerate(frags):
                if fr is not None:
                    for r in range(2 + depth):
                        out[i, r] = fr.row_words(r)
            return place_flat(self.mesh, out, shard_axis=0)

        lanes, lane_words = self._plane_lanes(frags, len(shards),
                                              depth, idx.width)
        return self._cached_stack(
            key, versions, build,
            frags=frags, lanes=lanes,
            logical_lead=(len(shards), 2 + depth),
            lane_words=lane_words, width_words=idx.width // 32,
            pageable=False)
