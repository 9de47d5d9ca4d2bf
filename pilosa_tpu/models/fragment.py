"""Fragment — the data-plane unit: one bitmap per (field, view, shard).

Mirrors fragment.go:84: a fragment is logically a single bitmap keyed
``row*SHARD_WIDTH + col``.  Host-side, each row lives in one of two
representations chosen by cardinality — the in-memory analog of the
reference's array/bitmap container split (roaring/container_stash.go:
46-85, roaring/roaring.go:232):

- **sparse**: a sorted int64 array of set column ids, for rows with
  <= ``SPARSE_MAX`` bits (64 KiB worst case vs 128 KiB dense) — so a
  shard with a million near-empty rows needs megabytes, not 128 GiB;
- **dense**: packed uint32 words, the device-tile form, once a row
  crosses the threshold (mutation promotes in place).

Dense decode happens only at device-upload / read time
(``row_words``); all mutators work on the compressed form.

A fragment that was bulk-loaded with one row a column (a single-valued
field: ``import_mutex`` into an empty fragment) may instead be
**code-held**: one array of each column's row id, 1 or 2 bytes a
column, where its rows would take more (ten or more rows over a full
shard).  Reads decode a row from the codes; the first mutation turns
the fragment back into rows (``_decode``).
Device-side, a per-row tile cache feeds the XLA kernels, invalidated
on write.  BSI views reuse the same row space: row 0 = exists, row 1 =
sign, rows 2.. = magnitude planes (fragment.go:34-66), so BSI plane
stacks are just ``rows[0..2+depth)`` stacked into one (2+depth, W)
device tensor.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from pilosa_tpu.models.cache import make_cache
from pilosa_tpu.obs import faults
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.shardwidth import (
    BSI_OFFSET_BIT,
    BSI_SIGN_BIT,
    SHARD_WIDTH,
    SPARSE_MAX,
)

# Paranoia mode (the roaring_paranoia.go build-tag asserts + rbf
# Tx.Check analog, SURVEY §5.2): PILOSA_TPU_PARANOIA=1 re-validates
# the hybrid row-store invariants after every mutation.  Off by
# default — the checks cost O(row) per touched row.
import os as _os

PARANOIA = _os.environ.get("PILOSA_TPU_PARANOIA") == "1"

# process-global monotonic fragment generation: identity that NEVER
# repeats across delete/recreate (unlike id(), whose freed addresses
# CPython reuses) — the result cache keys staleness on (gen, version)
import itertools as _it

_FRAG_GEN = _it.count(1)

# Process-global MUTATION EPOCH: bumped on every fragment version
# bump, fragment creation, gen retirement, and schema-level deletion
# (models/index.py, models/holder.py).  A single monotonic int lets a
# reader answer "did ANY data change since I built this?" in one
# load — the ragged serving plane (executor/ragged.py) caches its
# canonical fused program against it, so read-heavy steady state
# skips per-batch plan rebuilds entirely while any write anywhere
# conservatively invalidates.  Plain int += under the GIL: the bump
# rides paths that already take the fragment's locks, and a torn read
# can only ever UNDER-read (forcing a spurious rebuild, never a stale
# serve — the per-fragment (gen, version) stamps stay the precise
# staleness authority).
_MUT_EPOCH = 0


def bump_mutation_epoch():
    global _MUT_EPOCH
    _MUT_EPOCH += 1


def mutation_epoch() -> int:
    return _MUT_EPOCH

# Bounded per-fragment delta log (LSM-flavored incremental stack
# maintenance): every mutation appends a (version, row, word-span)
# entry so device-resident stacks can be PATCHED instead of rebuilt
# (executor/stacked.py).  The log is a sliding window — entries past
# DELTA_LOG_MAX drop off the front and readers snapshotted before the
# window fall back to a full slice rebuild.  Config knob:
# PILOSA_TPU_DELTA_LOG_MAX (config.py [stacked] delta-log-max).
DELTA_LOG_MAX = int(_os.environ.get("PILOSA_TPU_DELTA_LOG_MAX", "256"))

from collections import deque as _deque


class Fragment:
    """Host rows + device tile cache for one (index, field, view, shard)."""

    def __init__(self, index: str, field: str, view: str, shard: int,
                 width: int = SHARD_WIDTH, storage=None,
                 cache_type: str = "none", cache_size: int = 50000):
        self.index_name = index
        self.field_name = field
        self.view_name = view
        self.shard = shard
        self.width = width
        self._rows: dict[int, np.ndarray] = {}   # row id -> packed words
        self._sparse: dict[int, np.ndarray] = {}  # row id -> sorted cols
        # code-held (see the module docstring): each column's row id,
        # the dtype's largest value where it has none, and the rows'
        # bit counts; both stores above are empty while this is set
        self._codes: np.ndarray | None = None
        self._code_counts: np.ndarray | None = None
        self._device: dict[int, jnp.ndarray] = {}
        self._planes_cache: jnp.ndarray | None = None
        # monotonically increasing write stamp: every host mutation
        # bumps it, and device-side stack caches (executor/stacked.py
        # TileStackCache) compare stamps to detect staleness
        self.version = 0
        # unique-for-process-lifetime identity (see _FRAG_GEN)
        self.gen = next(_FRAG_GEN)
        bump_mutation_epoch()  # a new fragment changes read results
        # delta log: (version-after-mutation, row, word_lo, word_hi)
        # spans covering versions in (_delta_floor, version] — the
        # incremental-maintenance feed for device stack patching
        self._delta_log: _deque = _deque()
        self._delta_floor = 0
        # row_ids is hot on TopN/Rows scans (954 shards x R rows of
        # .any() sweeps = ~GB of host traffic per query); cache it
        # under the same version stamp the device tile cache uses
        self._row_ids_cache: tuple[int, list[int]] | None = None
        # rows changed since the last storage sync (persisted by
        # IndexStorage.write_fragments; empty when storage is None)
        self.dirty_rows: set[int] = set()
        # TopN rank cache (fragment.openCache, fragment.go:201):
        # counts refresh lazily from _cache_stale on access, so hot
        # write paths pay only a dict-insert, not a popcount.  An
        # insertion-ordered dict (not a set) so the deferred refresh
        # replays rows in write order — LRU recency survives batching.
        self._cache = make_cache(cache_type, cache_size)
        self._cache_stale: dict[int, None] = {}
        if storage is not None:
            # load_rows already compresses as it streams (peak = one
            # dense row): int64 arrays are sorted column ids, uint32
            # arrays are packed words
            for r, w in storage.load_rows(field, view, shard,
                                          width).items():
                if w.dtype == np.int64:
                    self._sparse[r] = w
                else:
                    self._rows[r] = w
            if self._cache is not None:
                self._cache_stale.update(dict.fromkeys(self._rows))
                self._cache_stale.update(dict.fromkeys(self._sparse))

    @property
    def sparse_row_count(self) -> int:
        """Rows currently held in a compressed form, not as dense
        words: the column arrays, or every row of a code-held
        fragment that has a bit."""
        if self._code_counts is not None:
            return int(np.count_nonzero(self._code_counts))
        return len(self._sparse)

    def _densify(self, row: int) -> np.ndarray:
        """Promote a sparse row to dense words (in place)."""
        cols = self._sparse.pop(row)
        w = bm.from_columns(cols, self.width)
        self._rows[row] = w
        return w

    def _store_cols(self, row: int, arr: np.ndarray) -> None:
        """Store a sorted column array, promoting past the threshold."""
        self._sparse[row] = arr
        if arr.size > SPARSE_MAX:
            self._densify(row)

    def _decode(self) -> None:
        """A code-held fragment back to rows, each in the form its
        cardinality asks for.  Every mutator but the bulk load calls
        this first: they write rows.  The contents do not change, so
        neither does the version."""
        codes = self._codes
        if codes is None:
            return
        cols = np.flatnonzero(codes != np.iinfo(codes.dtype).max)
        self._store_grouped(codes[cols].astype(np.int64), cols)
        self._codes = self._code_counts = None

    # -- host mutation ------------------------------------------------------

    def _row_mut(self, row: int, lo: int | None = None,
                 hi: int | None = None) -> np.ndarray:
        """Mutable DENSE words for a row (densifying if needed) —
        the bulk/word-level write path.  `lo`/`hi` bound the word span
        the caller is about to dirty (whole row when omitted)."""
        self._decode()
        w = self._rows.get(row)
        if w is None:
            if row in self._sparse:
                w = self._densify(row)
            else:
                w = bm.empty(self.width)
                self._rows[row] = w
        self._invalidate(row, lo, hi)
        return w

    def _invalidate(self, row: int, lo: int | None = None,
                    hi: int | None = None, record: bool = False):
        # epoch BEFORE version: a reader preempting the writer between
        # the two sees a moved epoch with the old version (spurious
        # rebuild — safe); the reverse order would let a cached fused
        # program pass its epoch check against a version that already
        # moved (stale serve).  Content safety holds because mutators
        # invalidate both BEFORE handing out the row (here) and AFTER
        # the bytes land (touch) — the post-landing bump is the one a
        # mid-write builder's stamp is compared against.
        bump_mutation_epoch()
        self.version += 1
        if record:
            self._record_delta(row, lo, hi)
        self._device.pop(row, None)
        self._planes_cache = None
        self.dirty_rows.add(row)
        if self._cache is not None:
            # re-insert at the end: most recent write is refreshed last
            self._cache_stale.pop(row, None)
            self._cache_stale[row] = None

    def _record_delta(self, row: int, lo: int | None, hi: int | None):
        """Append one (version, row, word-span) entry.  Deltas record
        only at touch() time (the post-mutation invalidation), so one
        mutation = one entry; the pre-invalidation bump is covered
        because the post entry's version exceeds any reader snapshot
        taken before it.  Entries are never merged: pulling an older
        entry's span forward under a newer version would make every
        snapshot in between re-patch that whole span (a point write
        would inherit the row's import history).  Oldest entries drop
        past DELTA_LOG_MAX, advancing the floor so pre-window readers
        rebuild instead of patching."""
        if lo is None:
            lo, hi = 0, self.width // 32
        log = self._delta_log
        log.append((self.version, row, lo, hi))
        # chaos seam (write plane): die right AFTER the delta-log
        # entry landed — the crash window between the in-memory
        # append and any downstream durability (WAL sync, offset
        # commit).  One dict lookup when nothing is armed — the
        # detail f-string only builds behind the armed() guard.
        if faults.armed("crash-post-append"):
            faults.fire("crash-post-append",
                        f"{self.index_name}/{self.field_name}/"
                        f"{self.view_name}/{self.shard}")
        while len(log) > DELTA_LOG_MAX:
            # floor rises BEFORE the pop: a concurrent deltas_since
            # that misses the popped entry re-checks the floor after
            # its copy and bails instead of under-reporting
            self._delta_floor = log[0][0]
            log.popleft()

    def deltas_since(self, version: int):
        """Dirty (row, word_lo, word_hi) spans of every mutation after
        `version`, or None when the log cannot prove coverage (the
        snapshot predates the sliding window, or names a version this
        incarnation never reached — a drop/recreate mismatch the
        caller should already have screened via ``gen``)."""
        if version < self._delta_floor or version > self.version:
            return None
        for _ in range(4):
            try:
                entries = list(self._delta_log)
                break
            except RuntimeError:  # writer mutated the deque mid-copy
                continue
        else:
            return None  # contended: let the caller rebuild
        if version < self._delta_floor:
            # the window slid during the copy; `entries` may be
            # missing dropped-but-needed spans — no coverage proof
            return None
        return [(r, lo, hi) for (v, r, lo, hi) in entries
                if v > version]

    def delta_export(self, since: int):
        """Transfer-unit export for online resharding (DELTA-CHASE):
        the CURRENT packed words of every row the delta log names
        above ``since``, or None when the log cannot prove coverage
        (the caller falls back to a block-checksum diff round).
        Returns ``(gen, version, span_count, {row: words})`` with
        ``version`` captured BEFORE the span collection so a write
        racing the export re-ships next round instead of vanishing.
        Shipping current contents (not historical patches) makes the
        replay idempotent and always-forward — exactly the property
        that lets a crashed chase resume from any round."""
        gen, version = self.gen, self.version
        spans = self.deltas_since(int(since))
        if spans is None:
            return gen, version, None, None
        rows = sorted({int(r) for r, _lo, _hi in spans})
        return gen, version, len(spans), {r: self.row_words(r)
                                          for r in rows}

    def touch(self, row: int, lo: int | None = None,
              hi: int | None = None):
        """Post-mutation invalidation.  ``_row_mut`` invalidates BEFORE
        handing out the mutable array; every mutator must also touch()
        AFTER the bytes land, or a concurrent reader that snapshots
        ``version`` between the two could cache pre-write data under
        the post-write version forever.  The delta log records HERE
        (post), one entry per mutation — the entry's version exceeds
        any snapshot taken before the bytes landed, so it covers the
        pre-invalidation bump too."""
        self._invalidate(row, lo, hi, record=True)
        if PARANOIA:
            self.check_row(row)

    def bump_gen(self, bump_epoch: bool = True):
        """Retire this fragment's cache identity: every derived
        (gen, version) stamp — tile stacks, result-cache snapshots,
        prefetch recipes — compares unequal afterwards.  Called when
        the fragment leaves the live tree without being destroyed
        (TTL view expiry, models/field.py): closures holding a direct
        reference would otherwise keep reading unchanged stamps and
        serve the expired view's data forever.

        ``bump_epoch=False`` skips the global mutation-epoch bump for
        batched sweeps (TTL expiry retiring N views): the caller bumps
        the epoch ONCE before the first gen moves — the same
        epoch-before-gen ordering, paid once instead of invalidating
        every canonical fused program N times per sweep."""
        if bump_epoch:
            bump_mutation_epoch()  # before the gen moves — see _invalidate
        self.gen = next(_FRAG_GEN)

    def check_row(self, row: int):
        """Paranoia assert for one row's representation invariants."""
        dense = self._rows.get(row)
        arr = self._sparse.get(row)
        assert not (dense is not None and arr is not None), \
            f"row {row} in BOTH dense and sparse stores"
        if arr is not None:
            assert arr.ndim == 1 and arr.dtype == np.int64, arr.dtype
            assert arr.size <= SPARSE_MAX, \
                f"sparse row {row} over threshold ({arr.size})"
            if arr.size:
                assert (np.diff(arr) > 0).all(), \
                    f"sparse row {row} not strictly sorted"
                assert 0 <= int(arr[0]) and int(arr[-1]) < self.width, \
                    f"sparse row {row} column out of range"
        if dense is not None:
            assert dense.dtype == np.uint32 and \
                dense.size == self.width // 32, \
                f"dense row {row} bad geometry"

    def check(self):
        """Full-fragment invariant sweep (rbf Tx.Check analog)."""
        for r in set(self._rows) | set(self._sparse):
            self.check_row(r)
        if self._codes is not None:
            assert not self._rows and not self._sparse, \
                "code-held fragment with rows besides"
            assert self._codes.size == self.width and np.array_equal(
                np.bincount(self._codes)[:self._code_counts.size],
                self._code_counts), "codes and their counts differ"
        assert self.version >= 0

    def set_row_words(self, row: int, words) -> None:
        """Replace a whole row (Store()/ClearRow write path); the
        result re-compresses when it lands under the threshold.  The
        old contents are fully replaced, so they are dropped without
        decoding."""
        self._decode()
        self._invalidate(row)
        self._sparse.pop(row, None)
        w = self._rows.get(row)
        if w is None:
            w = bm.empty(self.width)
        w[:] = words
        if int(np.bitwise_count(w).sum()) <= SPARSE_MAX:
            self._rows.pop(row, None)
            self._sparse[row] = bm.to_columns(w).astype(np.int64)
        else:
            self._rows[row] = w
        self.touch(row)

    def set_bit(self, row: int, col: int) -> bool:
        """Set one bit; returns True if it changed (fragment.setBit)."""
        assert 0 <= col < self.width
        self._decode()
        wi = col >> 5
        words = self._rows.get(row)
        if words is None:
            # sparse path: sorted-insert, promoting at the threshold
            # (the array-container write path, roaring/roaring.go:927)
            arr = self._sparse.get(row)
            if arr is None:
                self._invalidate(row, wi, wi + 1)
                self._sparse[row] = np.array([col], dtype=np.int64)
                self.touch(row, wi, wi + 1)
                return True
            i = int(np.searchsorted(arr, col))
            if i < arr.size and arr[i] == col:
                return False
            self._invalidate(row, wi, wi + 1)
            self._store_cols(row, np.insert(arr, i, col))
            self.touch(row, wi, wi + 1)
            return True
        b = np.uint32(1) << (col & 31)
        if words[wi] & b:
            return False
        self._invalidate(row, wi, wi + 1)
        words[wi] |= b
        self.touch(row, wi, wi + 1)
        return True

    def clear_bit(self, row: int, col: int) -> bool:
        self._decode()
        wi = col >> 5
        words = self._rows.get(row)
        if words is None:
            arr = self._sparse.get(row)
            if arr is None:
                return False
            i = int(np.searchsorted(arr, col))
            if i >= arr.size or arr[i] != col:
                return False
            self._invalidate(row, wi, wi + 1)
            self._sparse[row] = np.delete(arr, i)
            self.touch(row, wi, wi + 1)
            return True
        b = np.uint32(1) << (col & 31)
        if not (words[wi] & b):
            return False
        self._invalidate(row, wi, wi + 1)
        words[wi] &= ~b
        self.touch(row, wi, wi + 1)
        return True

    @staticmethod
    def _by_row(rows: np.ndarray, cols: np.ndarray):
        """(rows, cols) grouped by row with one stable sort (columns
        keep their order within a row).  numpy's stable sort is radix
        for <=16-bit ints (6x the int64 mergesort, measured r04) — row
        ids are usually small category ids, so cast when they fit."""
        key = rows
        if rows.size and 0 <= rows.min() and rows.max() < 32767:
            key = rows.astype(np.int16)
        order = np.argsort(key, kind="stable")
        return rows[order], cols[order]

    def import_bits(self, rows, cols, clear: bool = False,
                    presorted: bool = False):
        """Bulk set/clear: vectorized merge per distinct row
        (fragment.bulkImport semantics, minus the roaring plumbing).
        Rows stay in compressed form until they cross SPARSE_MAX.
        ``presorted`` promises rows are already grouped (the field's
        (shard,row) lexsort), skipping the per-fragment sort."""
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        assert rows.shape == cols.shape
        self._decode()
        if cols.size:
            # validate once up front: the sparse branches below bypass
            # bm.from_columns and would otherwise store bad ids whose
            # failures surface far from the import (or, for negatives,
            # silently wrap in clear_columns' word indexing)
            assert 0 <= cols.min() and cols.max() < self.width, \
                "column id out of range"
        # group columns by row with one sort (not one O(n) mask per
        # distinct row — a million-row sparse import must stay O(n log n))
        if presorted:
            rows_s, cols_s = rows, cols
        else:
            rows_s, cols_s = self._by_row(rows, cols)
        starts = np.flatnonzero(
            np.r_[True, rows_s[1:] != rows_s[:-1]]) if rows_s.size \
            else np.array([], dtype=np.int64)
        uniq = rows_s[starts]
        bounds = np.append(starts[1:], rows_s.size)
        for r, lo_i, hi_i in zip(uniq.tolist(), starts.tolist(),
                                 bounds.tolist()):
            r = int(r)
            sel = cols_s[lo_i:hi_i]
            # dirty word span of this row's columns (delta-log hint)
            wlo = int(sel.min()) >> 5
            whi = (int(sel.max()) >> 5) + 1
            dense = self._rows.get(r)
            if dense is None and not clear:
                arr = self._sparse.get(r)
                self._invalidate(r, wlo, whi)
                if arr is None and sel.size > SPARSE_MAX:
                    # straight to dense: union1d + store + densify
                    # re-sorts and re-scatters the same bits (ingest
                    # profile r04)
                    self._rows[r] = bm.from_columns(sel, self.width)
                elif arr is None:
                    self._store_cols(r, np.unique(sel))
                else:
                    self._store_cols(r, np.union1d(arr, sel))
                self.touch(r, wlo, whi)
                continue
            if dense is None and clear:
                arr = self._sparse.get(r)
                if arr is None:
                    continue
                self._invalidate(r, wlo, whi)
                self._sparse[r] = np.setdiff1d(arr, sel)
                self.touch(r, wlo, whi)
                continue
            mask = bm.from_columns(sel, self.width)
            words = self._row_mut(r, wlo, whi)
            if clear:
                words &= ~mask
            else:
                words |= mask
            self.touch(r, wlo, whi)

    def import_row_words(self, row: int, words) -> None:
        """Bulk dense-row import: OR pre-packed words into a row.

        The dense-tile analog of fragment.importRoaring
        (fragment.go:2038), which ingests pre-encoded roaring
        containers wholesale instead of per-bit ops — the restore /
        bulk-load fast path.
        """
        w = self._row_mut(row)
        np.bitwise_or(w, np.asarray(words, dtype=np.uint32), out=w)
        self.touch(row)

    def contains(self, row: int, col: int) -> bool:
        codes = self._codes
        if codes is not None:
            return self.row_count(row) > 0 and int(codes[col]) == row
        words = self._rows.get(row)
        if words is None:
            arr = self._sparse.get(row)
            if arr is None:
                return False
            i = int(np.searchsorted(arr, col))
            return i < arr.size and int(arr[i]) == col
        return bool((words[col >> 5] >> np.uint32(col & 31)) & 1)

    # -- BSI mutation (fragment.setValueBase semantics) ---------------------

    def set_value(self, col: int, depth: int, value: int) -> bool:
        """Write one sign-magnitude value across the bit-plane rows."""
        uval = abs(int(value))
        assert uval < (1 << depth), "value magnitude exceeds bit depth"
        changed = False
        for i in range(depth):
            op = self.set_bit if (uval >> i) & 1 else self.clear_bit
            changed |= op(BSI_OFFSET_BIT + i, col)
        changed |= self.set_bit(0, col)  # exists
        if value < 0:
            changed |= self.set_bit(BSI_SIGN_BIT, col)
        else:
            changed |= self.clear_bit(BSI_SIGN_BIT, col)
        return changed

    def clear_value(self, col: int, depth: int) -> bool:
        changed = False
        for r in range(2 + depth):
            changed |= self.clear_bit(r, col)
        return changed

    # mutex scratch planes are dense (128KB per distinct row): the
    # native last-write-wins path only pays off for categorical
    # cardinalities; high-cardinality mutexes take the sort path
    _MUTEX_KERNEL_MAX_ROWS = 256

    def import_mutex(self, rows, cols):
        """Mutex/bool bulk write: clear-then-set with last-write-wins
        per column in ONE native reverse pass (pt_mutex_fill) — no
        np.unique sort (the r04 mutex-import hotspot)."""
        rows = np.asarray(rows)
        if rows.dtype.kind not in "iu":
            rows = rows.astype(np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        assert rows.shape == cols.shape
        if cols.size == 0:
            return
        if self._codes is None and not self._rows and not self._sparse \
                and bool((cols[1:] > cols[:-1]).all()):
            # nothing to clear and one write a column: a bulk load
            self._load(rows, cols)
            return
        rows = rows.astype(np.int64, copy=False)
        if rows.min() >= 0 and rows.max() < 32767:
            # O(n) distinct + inverse via bincount — no sort
            cnt = np.bincount(rows)
            uniq = np.flatnonzero(cnt)
            inv_map = np.zeros(cnt.size, dtype=np.int64)
            inv_map[uniq] = np.arange(uniq.size)
            rowidx = inv_map[rows]
        else:
            uniq, rowidx = np.unique(rows, return_inverse=True)
        if uniq.size > self._MUTEX_KERNEL_MAX_ROWS:
            from pilosa_tpu.ops import bitmap as bm_
            if cols.size > 1 and not bool((np.diff(cols) > 0).all()):
                _u, first_rev = np.unique(cols[::-1],
                                          return_index=True)
                keep = cols.size - 1 - first_rev
                cols, rows = cols[keep], rows[keep]
            self.clear_columns(bm_.from_columns(cols, self.width))
            self.import_bits(rows, cols)
            return
        from pilosa_tpu.storage import native_ingest as ni
        written = bm.empty(self.width)
        scratch = np.zeros((uniq.size, self.width // 32), np.uint32)
        ni.mutex_fill(written, scratch, rowidx.astype(np.int64),
                      cols)
        self.clear_columns(written)
        wlo = int(cols.min()) >> 5
        whi = (int(cols.max()) >> 5) + 1
        for k, r in enumerate(np.asarray(uniq,
                                         dtype=np.int64).tolist()):
            self._row_mut(int(r), wlo, whi)[:] |= scratch[k]
            self.touch(int(r), wlo, whi)

    def _load(self, rows: np.ndarray, cols: np.ndarray) -> None:
        """Bulk load of an EMPTY fragment from (row, column) pairs
        whose columns strictly increase, into the smaller of the two
        forms: the codes (1 or 2 bytes a column of the shard) or the
        rows (128 KB a row past SPARSE_MAX bits, 8 bytes a bit under
        it).  Twenty single-valued fields over a full shard are 21 MB
        so, not 68-120.
        One invalidation for the whole load, not one per row: the
        version moves before and after the contents land, as _row_mut
        and touch() move it, and the delta floor rises to it, so a
        reader that snapshot an earlier version rebuilds (there is no
        row it could patch)."""
        assert 0 <= cols[0] and cols[-1] < self.width, \
            "column id out of range"
        bump_mutation_epoch()
        self.version += 1
        counts = None
        if 0 <= rows.min() and rows.max() < np.iinfo(np.uint16).max:
            counts = np.bincount(rows)
            dtype = np.uint8 if counts.size <= np.iinfo(np.uint8).max \
                else np.uint16
            by_rows = int(np.where(counts > SPARSE_MAX, self.width // 8,
                                   8 * counts).sum())
            if self.width * dtype().itemsize + counts.nbytes >= by_rows:
                counts = None
        if counts is not None:
            codes = np.full(self.width, np.iinfo(dtype).max, dtype=dtype)
            codes[cols] = rows
            self._codes, self._code_counts = codes, counts
            uniq = np.flatnonzero(counts).tolist()
        else:
            uniq = self._store_grouped(rows.astype(np.int64, copy=False),
                                       cols)
        self.dirty_rows.update(uniq)
        if self._cache is not None:
            self._cache_stale.update(dict.fromkeys(uniq))
        self._planes_cache = None
        bump_mutation_epoch()
        self.version += 1
        self._delta_log.clear()
        self._delta_floor = self.version
        if PARANOIA:
            self.check()

    def _store_grouped(self, rows: np.ndarray, cols: np.ndarray) -> list:
        """Store (row, column) pairs of distinct columns into the
        empty row stores and return the row ids: one stable sort
        groups the columns by row, and each row is stored in the form
        its cardinality asks for (sparse up to SPARSE_MAX bits: a
        10,000-row field's million bits are 8 MB, not 10,000 dense
        rows; the sparse rows are slices of one array that holds
        their columns and no dense row's)."""
        rows_s, cols_s = self._by_row(rows, cols)
        starts = np.flatnonzero(np.r_[True, rows_s[1:] != rows_s[:-1]])
        sizes = np.diff(np.append(starts, rows_s.size))
        uniq = rows_s[starts].tolist()
        thin = sizes <= SPARSE_MAX
        thin_cols = cols_s[np.repeat(thin, sizes)]
        at = 0
        for r, lo, n, sparse in zip(uniq, starts.tolist(), sizes.tolist(),
                                    thin.tolist()):
            if sparse:
                self._sparse[r] = thin_cols[at:at + n]
                at += n
            else:
                self._rows[r] = bm.from_columns(cols_s[lo:lo + n],
                                                self.width)
        return uniq

    def import_values(self, cols, values, depth: int, clear: bool = False):
        """Bulk BSI write (fragment.importValue semantics): last-write-
        wins per column, filled by the fused native scatter kernel
        (native/ingest/scatter.cc pt_bsi_fill_t) — one pass over the
        values instead of depth+2 numpy select+scatter passes."""
        cols = np.asarray(cols, dtype=np.int64)
        vals = np.asarray(values, dtype=np.int64).reshape(-1)
        assert cols.shape == vals.shape
        if cols.size == 0:
            return
        wlo = int(cols.min()) >> 5
        whi = (int(cols.max()) >> 5) + 1
        if clear:
            touched = bm.from_columns(cols, self.width)
            for r in range(2 + depth):
                self._row_mut(r, wlo, whi)[:] &= ~touched
                self.touch(r, wlo, whi)
            return
        # uint64 view so INT64_MIN's magnitude (2^63) is seen — np.abs
        # is the identity there and would let an out-of-depth value
        # reach the native kernel's out-of-bounds plane write.  An
        # unconditional raise, not an assert: this guard must survive
        # `python -O`, and the native kernel's own depth bound is a
        # last-resort backstop, not an error report.
        mags = np.where(vals < 0, np.negative(vals),
                        vals).view(np.uint64)
        max_bits = int(mags.max()).bit_length()
        if max_bits > depth:
            raise ValueError(
                f"value magnitude needs {max_bits} bits, fragment "
                f"depth is {depth}")
        from pilosa_tpu.storage import native_ingest as ni
        scratch = np.zeros((2 + depth, self.width // 32), np.uint32)
        ni.bsi_fill(scratch, cols, vals, depth)
        touched = scratch[0]  # the exists plane IS the touched mask
        self._row_mut(0, wlo, whi)[:] |= touched
        sign_words = self._row_mut(BSI_SIGN_BIT, wlo, whi)
        sign_words &= ~touched
        sign_words |= scratch[1]
        for i in range(depth):
            plane = self._row_mut(BSI_OFFSET_BIT + i, wlo, whi)
            plane &= ~touched
            plane |= scratch[2 + i]
        for r in range(2 + depth):
            self.touch(r, wlo, whi)

    def clear_columns(self, mask_words: np.ndarray) -> bool:
        """Clear every bit in the masked columns across ALL rows
        (Delete-records path).  Returns True if anything changed."""
        self._decode()
        mask = np.asarray(mask_words, dtype=np.uint32)
        inv = ~mask
        nz = np.flatnonzero(mask)
        wlo = int(nz[0]) if nz.size else 0
        whi = int(nz[-1]) + 1 if nz.size else 0
        changed = False
        for r in list(self._rows):
            row = self._rows[r]
            if (row & mask).any():
                self._row_mut(r, wlo, whi)[:] = row & inv
                self.touch(r, wlo, whi)
                changed = True
        for r in list(self._sparse):
            arr = self._sparse[r]
            hit = ((mask[arr >> 5] >> (arr & 31).astype(np.uint32))
                   & 1).astype(bool)
            if hit.any():
                self._invalidate(r, wlo, whi)
                self._sparse[r] = arr[~hit]
                self.touch(r, wlo, whi)
                changed = True
        return changed

    # -- reads --------------------------------------------------------------

    @property
    def row_ids(self) -> list[int]:
        cached = self._row_ids_cache
        if cached is not None and cached[0] == self.version:
            return list(cached[1])
        counts = self._code_counts
        if counts is not None:
            ids = np.flatnonzero(counts).tolist()
        else:
            ids = [r for r, w in self._rows.items() if w.any()]
            ids += [r for r, a in self._sparse.items() if a.size]
            ids.sort()
        self._row_ids_cache = (self.version, ids)
        return list(ids)

    def max_row_id(self) -> int:
        ids = self.row_ids
        return ids[-1] if ids else 0

    def row_words(self, row: int) -> np.ndarray:
        """Packed host words for a row (zeros if absent).  Sparse rows
        decode to a fresh dense array — the decode-at-upload boundary;
        treat the result as read-only."""
        codes = self._codes
        if codes is not None:
            if self.row_count(row) == 0:
                return bm.empty(self.width)
            return np.packbits(codes == row,
                               bitorder="little").view(np.uint32)
        w = self._rows.get(row)
        if w is not None:
            return w
        arr = self._sparse.get(row)
        if arr is not None:
            return bm.from_columns(arr, self.width)
        return bm.empty(self.width)

    def row_source(self, row: int):
        """How the row is held, for a reader that builds from the
        storage itself (memory/encode.py encode_lanes): None where it
        has no bit, else ``(kind, array, bits)`` — ``"codes"`` with
        the fragment's codes and the row's count, ``"cols"`` with its
        sorted columns, ``"words"`` with its packed words and -1 (not
        counted).  The array is the live one: read-only, and held by
        the caller while it reads (a writer or _decode replaces the
        stores' arrays, it does not free what a reader holds)."""
        # counts before codes: _decode clears them in the other order,
        # so both set means the codes still hold the fragment
        counts, codes = self._code_counts, self._codes
        if counts is not None and codes is not None:
            n = int(counts[row]) if 0 <= row < counts.size else 0
            return ("codes", codes, n) if n else None
        w = self._rows.get(row)
        if w is not None:
            return ("words", w, -1)
        arr = self._sparse.get(row)
        if arr is not None and arr.size:
            return ("cols", np.ascontiguousarray(arr, dtype=np.int64),
                    int(arr.size))
        return None

    def row_count(self, row: int) -> int:
        counts = self._code_counts
        if counts is not None:
            return int(counts[row]) if 0 <= row < counts.size else 0
        w = self._rows.get(row)
        if w is not None:
            return int(np.bitwise_count(w).sum())
        arr = self._sparse.get(row)
        return int(arr.size) if arr is not None else 0

    def row_cache(self):
        """The TopN rank/LRU cache, refreshed for rows written since
        the last access (None when the field's cache type is none)."""
        if self._cache is None:
            return None
        if self._cache_stale:
            for r in self._cache_stale:  # insertion (= write) order
                self._cache.add(r, self.row_count(r))
            self._cache_stale = {}
        return self._cache

    # -- device tiles -------------------------------------------------------

    def device_row(self, row: int) -> jnp.ndarray:
        """Row tile in HBM (cached until the row is written)."""
        t = self._device.get(row)
        if t is None:
            t = jnp.asarray(self.row_words(row))
            self._device[row] = t
        return t

    def device_rows(self, rows) -> jnp.ndarray:
        """Stacked (R, W) tile for a list of row ids."""
        return jnp.stack([self.device_row(r) for r in rows]) if len(rows) \
            else jnp.zeros((0, self.width // 32), dtype=jnp.uint32)

    def device_planes(self, depth: int) -> jnp.ndarray:
        """(2+depth, W) BSI plane stack for the kernel layer."""
        p = self._planes_cache
        if p is None or p.shape[0] != 2 + depth:
            p = jnp.asarray(
                np.stack([self.row_words(r) for r in range(2 + depth)]))
            self._planes_cache = p
        return p

    def memory_bytes(self) -> int:
        codes, counts = self._codes, self._code_counts
        if codes is not None and counts is not None:
            return codes.nbytes + counts.nbytes
        return (sum(w.nbytes for w in self._rows.values())
                + sum(a.nbytes for a in self._sparse.values()))

    # -- block checksums / replica repair -------------------------------
    # (fragment.go checksum-block machinery: merkle-style digests per
    # row-range block so replicas detect divergence and re-sync only
    # the diverged blocks)

    BLOCK_ROWS = 64

    def block_checksums(self) -> dict[int, str]:
        """Digest per row block b = rows [b*BLOCK_ROWS, (b+1)*BLOCK_ROWS).
        Only blocks with set bits appear; digests cover (row id, sorted
        set-column ids) pairs in row order — representation-independent
        AND proportional to set bits, so a million sparse rows hash
        their columns, not a million dense 128 KiB decodes."""
        import hashlib
        acc: dict[int, "hashlib._Hash"] = {}
        for r in self.row_ids:
            b = r // self.BLOCK_ROWS
            h = acc.get(b)
            if h is None:
                h = acc[b] = hashlib.blake2b(digest_size=16)
            h.update(int(r).to_bytes(8, "little"))
            if self._codes is not None:
                arr = np.flatnonzero(self._codes == r)
            else:
                arr = self._sparse.get(r)
            if arr is None:
                arr = bm.to_columns(self._rows[r]).astype(np.int64)
            h.update(np.ascontiguousarray(arr).tobytes())
        return {b: h.hexdigest() for b, h in acc.items()}

    def block_rows(self, block: int) -> dict[int, np.ndarray]:
        """Packed words of every non-empty row in one block."""
        lo, hi = block * self.BLOCK_ROWS, (block + 1) * self.BLOCK_ROWS
        return {r: self.row_words(r) for r in self.row_ids
                if lo <= r < hi}

    def set_block_rows(self, block: int, rows: dict[int, np.ndarray]):
        """Replace one block's contents with the owner's rows (repair
        write path): rows absent from the payload are cleared."""
        lo, hi = block * self.BLOCK_ROWS, (block + 1) * self.BLOCK_ROWS
        for r in [r for r in self.row_ids if lo <= r < hi]:
            if r not in rows:
                self.set_row_words(r, 0)
        for r, words in rows.items():
            assert lo <= int(r) < hi, "row outside block"
            self.set_row_words(int(r), words)
