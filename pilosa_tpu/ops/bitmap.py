"""Dense packed-bitmap kernels.

A shard-row is one bit per column, packed LSB-first into ``uint32``
words: column ``c`` lives at word ``c >> 5``, bit ``c & 31``.  A full
2^20-column shard-row is ``uint32[32768]`` (128 KiB).  All ops are pure
``jnp`` functions of arrays whose *last* axis is the word axis, so they
vmap/broadcast over arbitrary leading batch axes (rows, shards) and jit
cleanly onto the TPU VPU.

Reference semantics covered here (behavior, not code):
- pairwise set ops — roaring/roaring.go:927-1663 (intersect/union/
  difference/xor for all container-type pairs collapse to single
  bitwise ops on dense lanes);
- Count/Any — roaring popcount paths (roaring/roaring.go:542);
- CountRange / column-range masks — roaring/roaring.go:573;
- Shift — roaring shift-by-1 used by PQL Shift() (executor.go Shift).

Host-side packing helpers (numpy) mirror what the storage layer's
container decoder produces for HBM upload.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from pilosa_tpu.shardwidth import BITS_PER_WORD, SHARD_WIDTH

_WORD_DTYPE = jnp.uint32
_NP_WORD_DTYPE = np.uint32


# ---------------------------------------------------------------------------
# Host-side packing helpers (numpy — used by storage/ingest/tests)
# ---------------------------------------------------------------------------

def empty(width: int = SHARD_WIDTH) -> np.ndarray:
    """An all-zeros packed shard-row of `width` bits (width % 32 == 0)."""
    assert width % BITS_PER_WORD == 0
    return np.zeros(width // BITS_PER_WORD, dtype=_NP_WORD_DTYPE)


def from_columns(cols, width: int = SHARD_WIDTH) -> np.ndarray:
    """Pack a list/array of set column ids (< width) into words."""
    words = empty(width)
    cols = np.asarray(cols, dtype=np.int64)
    if cols.size:
        assert cols.min() >= 0 and cols.max() < width, "column id out of range"
        # native or-scatter (~20x numpy's bitwise_or.at; falls back
        # to it without a toolchain)
        from pilosa_tpu.storage import native_ingest as ni
        ni.or_bits(words, cols)
    return words


def to_columns(words) -> np.ndarray:
    """Unpack a packed row back into a sorted array of set column ids."""
    words = np.asarray(words, dtype=_NP_WORD_DTYPE)
    # uint32 little-endian byte view -> unpackbits(bitorder little) gives
    # bit i of word w at flat index w*32 + i, matching our LSB-first layout.
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint64)


def range_mask(start: int, end: int, width: int = SHARD_WIDTH) -> np.ndarray:
    """Packed mask with bits set for columns in [start, end)."""
    start = max(0, min(start, width))
    end = max(start, min(end, width))
    mask = empty(width)
    sw, sb = start >> 5, start & 31
    ew, eb = end >> 5, end & 31
    if sw == ew:
        if sb != eb:
            mask[sw] = ((_NP_WORD_DTYPE(1) << (eb - sb)) - 1) << sb
        return mask
    mask[sw] = _NP_WORD_DTYPE(0xFFFFFFFF) << sb
    mask[sw + 1 : ew] = 0xFFFFFFFF
    if eb:
        mask[ew] = (_NP_WORD_DTYPE(1) << eb) - 1
    return mask


# ---------------------------------------------------------------------------
# Device-side ops (jnp — jit/vmap/shard_map friendly)
# ---------------------------------------------------------------------------

def intersect(a, b):
    return jnp.bitwise_and(a, b)


def union(a, b):
    return jnp.bitwise_or(a, b)


def difference(a, b):
    """a AND NOT b."""
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def xor(a, b):
    return jnp.bitwise_xor(a, b)


def complement(a):
    """Bitwise NOT over the full shard width.

    PQL ``Not()`` is existence-relative (executor.go executeNotShard);
    the executor composes this with the existence row via difference().
    """
    return jnp.bitwise_not(a)


def popcount_words(words):
    """Per-word popcount (uint32 -> int32 counts 0..32)."""
    return jax.lax.population_count(words).astype(jnp.int32)


def count(words):
    """Number of set bits, reduced over the last (word) axis -> int32.

    Per-shard counts are < 2^20 so int32 is exact; cross-shard totals
    are combined in int64/Python on the host (SURVEY §7 "Exactness").
    """
    return jnp.sum(popcount_words(words), axis=-1)


def any_set(words):
    """True if any bit is set (last axis)."""
    return jnp.any(words != 0, axis=-1)


def intersection_count(a, b):
    """popcount(a & b) without materializing the intersection separately.

    Mirrors roaring.IntersectionCount (roaring/roaring.go:711); XLA fuses
    the AND into the popcount-reduce so this is one pass over HBM.
    """
    return count(jnp.bitwise_and(a, b))


def shift(words, n: int = 1):
    """Shift all bits toward higher column ids by static n (zero fill).

    Column c becomes column c+n; bits shifted past the end are dropped.
    Reference: PQL Shift() -> executor.executeShiftShard -> Row.Shift.
    """
    if n == 0:
        return words
    assert n > 0
    q, r = divmod(n, BITS_PER_WORD)
    w = words.shape[-1]
    zeros_shape = words.shape[:-1] + (min(q + 1, w),)
    zpad = jnp.zeros(zeros_shape, dtype=words.dtype)
    if q:
        if q >= w:
            return jnp.zeros_like(words)
        words_q = jnp.concatenate(
            [zpad[..., : q], words[..., : w - q]], axis=-1)
    else:
        words_q = words
    if r == 0:
        return words_q
    # carry bits across word boundaries
    prev = jnp.concatenate([zpad[..., :1], words_q[..., : w - 1]], axis=-1)
    return (words_q << np.uint32(r)) | (prev >> np.uint32(BITS_PER_WORD - r))


def count_range(words, start: int, end: int, width: int | None = None):
    """Count of set bits with column id in [start, end) (static bounds).

    Mirrors roaring CountRange (roaring/roaring.go:573).  The mask is a
    host-built constant captured by jit, so on device this is a fused
    AND + popcount-reduce.
    """
    if width is None:
        width = words.shape[-1] * BITS_PER_WORD
    mask = jnp.asarray(range_mask(start, end, width))
    return count(jnp.bitwise_and(words, mask))


def column_bit(col: int, width: int = SHARD_WIDTH) -> np.ndarray:
    """Packed row with exactly one column set (host helper)."""
    return from_columns([col], width)


# Multi-row folds -----------------------------------------------------------

def union_rows(rows):
    """OR-fold over axis 0: rows (R, W) -> (W,). Used by Rows/GroupBy paths."""
    return jnp.bitwise_or.reduce(rows, axis=0)


def intersect_rows(rows):
    """AND-fold over axis 0.

    Explicit fold: jnp.bitwise_and.reduce seeds its reduction with
    np.array(-1, dtype) — an OverflowError on unsigned dtypes under
    numpy 2's strict conversion rules.
    """
    acc = rows[0]
    for i in range(1, rows.shape[0]):
        acc = jnp.bitwise_and(acc, rows[i])
    return acc


# Stack patching (incremental device-stack maintenance) ---------------------
#
# Device-resident shard stacks are PATCHED on write instead of rebuilt
# (executor/stacked.py TileStackCache): a write's delta log names the
# dirty (lane, word-range) runs, and these ops scatter the replacement
# word runs into the resident array — O(delta) upload instead of an
# O(S*W) host restack + transfer.

def patch_rows(stack2d, idxs, starts, data):
    """Scatter word runs into a (L, W) stack: run k replaces
    ``stack2d[idxs[k], starts[k]:starts[k]+P]`` with ``data[k]``
    (data is (N, P); every run must lie within one lane).  A
    ``lax.scan`` of ``dynamic_update_slice`` so one jitted program
    serves any run count of one padded width — duplicate runs are
    safe (sequential, identical content)."""
    def body(st, seg):
        i, s, d = seg
        return jax.lax.dynamic_update_slice(st, d[None, :], (i, s)), None
    out, _ = jax.lax.scan(body, stack2d, (idxs, starts, data))
    return out


def patch_rows_np(stack2d: np.ndarray, idxs, starts,
                  data: np.ndarray, out=None) -> np.ndarray:
    """Host twin of patch_rows.  Copies by default (resident host
    stacks are shared read-only with concurrent queries); pass a
    scratch `out` to chain width buckets over one copy."""
    if out is None:
        out = stack2d.copy()
    p = data.shape[1]
    for k in range(len(idxs)):
        out[int(idxs[k]), int(starts[k]):int(starts[k]) + p] = data[k]
    return out


# Paged stack assembly (HBM residency manager) -------------------------------
#
# Stack cache entries live as fixed-size device PAGES (memory/pages.py)
# so eviction under budget pressure drops cold page-granular slabs
# instead of whole stacks; a query's operand is gathered back into one
# array here.  jitted per (page count, page shape, logical shape) —
# the shape space is tiny (pages are fixed-size, logical shapes are
# the handful of stack layouts the engine builds).

from functools import partial as _partial


def named(name: str):
    """Decorator: the stable name a function carries into a profile.
    jax.jit names its program after the function (`jit_<name>` on the
    profiler's XLA Modules line) and an op outside every
    jax.named_scope takes it too — so a closure called `run` is given
    its plan kind's name before it is jitted, never its contents'."""
    def deco(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return deco


def concat_pages(pages, shape: tuple, planes: bool = False):
    """The assembly graph, un-jitted: page blocks (each (page_lanes,
    W)) concatenated along the lane axis, the final page's padding
    trimmed, the logical shape restored.  A one-page operand is the
    page itself.  The ragged plan kind (executor/stacked.py) builds
    each of its operands with this inside its own program.

    `planes` asks for a BSI leaf — resident (S, 2+depth, W), lane =
    shard * (2+depth) + plane — as its 2+depth (S, W) planes, each a
    row gather out of the page concatenation: what ops/bsi.py's
    compare and sum read.  Slicing plane r out of the reshaped stack
    instead costs a padded copy of the whole leaf (9 sublanes padded
    to 16) and one T(1,128)-tiled copy per plane: 18.9 ms against 6.2
    for a range Count over 954 shards (PERF.md §6 "PR 34")."""
    n_lanes = 1
    for d in shape[:-1]:
        n_lanes *= int(d)
    flat = jnp.concatenate(pages, axis=0) if len(pages) > 1 else pages[0]
    flat = flat[:n_lanes]
    if planes:
        return tuple(flat[r::shape[1]] for r in range(shape[1]))
    return flat.reshape(shape)


@_partial(jax.jit, static_argnums=(1,))
@named("assemble_pages")
def _assemble_pages_jit(pages, shape: tuple):
    return concat_pages(pages, shape)


def assemble_pages(pages, shape: tuple):
    """Concatenate page blocks (each (page_lanes, W)) along the lane
    axis, trim the final page's padding, and restore the stack's
    logical shape.  On device this is one fused copy; XLA drops the
    slice when the lane count is already exact.  The page tuple pads
    to a pow2 count by repeating the last page (the lane trim drops
    the extras) so jax's per-shape executable cache grows log-, not
    linearly, in page count across varying stack sizes."""
    pages = tuple(pages)
    n = len(pages)
    npad = 1 << max(n - 1, 0).bit_length()
    if npad != n:
        pages = pages + (pages[-1],) * (npad - n)
    return _assemble_pages_jit(pages, tuple(shape))


# Ragged segment reductions (page-table dispatch) ---------------------------
#
# The ragged serving plane (executor/ragged.py) drives ONE device
# program over the pages of many queries' PagedStacks: each operand is
# assembled from its own pages (concat_pages above), and a family of
# single-leaf Counts reduces over the concatenation of its members'
# pages, one segment id per lane (the Ragged Paged Attention shape from
# PAPERS.md — ragged per-query page lists + segment ids instead of
# per-group padding).  This is the segment primitive; it is a plain jnp
# function so the ragged plan kind composes it inside one jitted
# program.

def segment_count(lanes, seg_ids, num_segments: int):
    """Per-segment popcount totals of a flat (L, W) lane block:
    popcount each lane, then segment-sum by ``seg_ids`` — N point
    Counts over different indexes/shard subsets reduce in ONE pass.
    int32-exact while a segment spans < 2^11 full shards (counts
    < 2^20 per lane), the same bound as the in-program cross-shard
    reduce (executor/stacked.py _REDUCE_MAX_SHARDS)."""
    pc = count(lanes)                                  # (L,) int32
    return jax.ops.segment_sum(pc, jnp.asarray(seg_ids),
                               num_segments=num_segments)


def segment_count_np(lanes: np.ndarray, seg_ids, num_segments: int):
    """Host twin of segment_count (numpy, exact int64)."""
    pc = np.bitwise_count(np.asarray(lanes, dtype=np.uint32)).sum(
        axis=-1).astype(np.int64)
    out = np.zeros(num_segments, dtype=np.int64)
    np.add.at(out, np.asarray(seg_ids), pc)
    return out


# Sparse page encodings (container-adaptive device format) ------------------
#
# memory/encode.py stores sparse stack-cache pages as sorted set-bit
# COORDINATES (packed) or word-granular all-ones RUNS + a residual
# coordinate tail (run) — the roaring array/run containers mapped onto
# the fixed page unit.  These are the device arms: a jitted gather-
# expand back to the dense (page_lanes, W) block for operand
# boundaries that need dense tiles, and count kernels that consume
# the coordinates natively (no expand).  All inputs are pow2-padded
# with out-of-range sentinels (coordinate >= page bits, run start >=
# page words), which the scatter/gather arms drop by construction —
# so the executable cache grows log-, not linearly, in payload size.

@_partial(jax.jit, static_argnums=(1, 2))
def _expand_coords_jit(coords, page_lanes: int, width_words: int):
    n_words = page_lanes * width_words
    flat = jnp.zeros((n_words,), dtype=jnp.uint32)
    word_idx = (coords >> jnp.uint32(5)).astype(jnp.int32)
    vals = jnp.uint32(1) << (coords & jnp.uint32(31))
    # coordinates are unique set bits, so add == or; sentinel pads
    # index past n_words and mode="drop" discards them exactly
    flat = flat.at[word_idx].add(vals, mode="drop")
    return flat.reshape(page_lanes, width_words)


def expand_coords(coords, page_lanes: int, width_words: int):
    """Packed coordinate page -> dense (page_lanes, W) uint32 block."""
    return _expand_coords_jit(jnp.asarray(coords), int(page_lanes),
                              int(width_words))


@_partial(jax.jit, static_argnums=(3, 4))
def _expand_runs_jit(starts, lens, coords, page_lanes: int,
                     width_words: int):
    n_words = page_lanes * width_words
    base = _expand_coords_jit(coords, page_lanes,
                              width_words).reshape(-1)
    w = jnp.arange(n_words, dtype=jnp.int32)
    # runs are sorted and disjoint: the covering candidate is the last
    # run starting at or before w (sentinel starts sort past every w)
    j = jnp.clip(jnp.searchsorted(starts, w, side="right") - 1,
                 0, starts.shape[0] - 1)
    inside = (w >= starts[j]) & (w < starts[j] + lens[j])
    flat = jnp.where(inside, jnp.uint32(0xFFFFFFFF), base)
    return flat.reshape(page_lanes, width_words)


def expand_runs(starts, lens, coords, page_lanes: int,
                width_words: int):
    """Run page (all-ones word runs + residual coordinates) -> dense
    (page_lanes, W) uint32 block."""
    return _expand_runs_jit(jnp.asarray(starts), jnp.asarray(lens),
                            jnp.asarray(coords), int(page_lanes),
                            int(width_words))


def packed_count(coords, total_bits: int):
    """Set-bit count of a packed coordinate page (sentinel-aware)."""
    return jnp.sum((jnp.asarray(coords)
                    < jnp.uint32(total_bits)).astype(jnp.int32))


def packed_segment_count(coords, lane_bits: int, num_lanes: int):
    """Per-lane set-bit counts of a packed page: each coordinate's
    lane is coord // lane_bits; sentinel coordinates land past
    num_lanes and drop.  The packed twin of segment_count."""
    lane = (jnp.asarray(coords) // jnp.uint32(lane_bits)).astype(
        jnp.int32)
    return jnp.zeros((num_lanes,), jnp.int32).at[lane].add(
        1, mode="drop")


def packed_intersect_count(coords, dense_words, total_bits: int):
    """popcount(expand(coords) & dense) WITHOUT expanding: gather the
    dense operand word under each coordinate and test its bit — the
    packed intersect-count arm (roaring array-vs-bitmap galloping
    intersection, collapsed to a gather)."""
    coords = jnp.asarray(coords)
    flat = jnp.asarray(dense_words).reshape(-1)
    wi = jnp.minimum((coords >> jnp.uint32(5)).astype(jnp.int32),
                     flat.shape[0] - 1)
    bits = (flat[wi] >> (coords & jnp.uint32(31))) & jnp.uint32(1)
    valid = coords < jnp.uint32(total_bits)
    return jnp.sum(jnp.where(valid, bits,
                             jnp.uint32(0)).astype(jnp.int32))


# Group-code planes (one-pass GroupBy) --------------------------------------
#
# A stack of R DISJOINT packed rows (no column in two rows) is exactly a
# base-R digit per column; these helpers re-encode that digit as
# ceil(log2 R) packed BIT-PLANES so the one-pass GroupBy histogram can
# compose a dense group code per column without ever unpacking the row
# stacks.  Work entirely with | and & so the same code serves numpy host
# arrays and jnp device arrays.

def digit_bits(n_rows: int) -> int:
    """Bit-planes needed to encode a digit in [0, n_rows)."""
    return max(int(n_rows) - 1, 0).bit_length()


def digit_planes(rows):
    """Disjoint row stack (R, ..., W) -> (digit_bits(R), ..., W) packed
    digit planes: plane b = OR of rows whose index has bit b set, so a
    column in row r carries the bits of r.  Caller guarantees
    disjointness (overlap would OR two digits together)."""
    import numpy as _np
    r = rows.shape[0]
    nbits = digit_bits(r)
    xp = _np if isinstance(rows, _np.ndarray) else jnp
    planes = []
    for b in range(nbits):
        acc = None
        for i in range(r):
            if (i >> b) & 1:
                acc = rows[i] if acc is None else acc | rows[i]
        planes.append(acc)
    if not planes:
        return xp.zeros((0,) + rows.shape[1:], dtype=rows.dtype)
    return xp.stack(planes)


def unpack_bits(words):
    """Device bit-unpack: (..., W) uint32 -> (..., W*32) int32 0/1 per
    column (column c = word c>>5, bit c&31)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1],
                        words.shape[-1] * 32).astype(jnp.int32)


def code_from_planes(planes):
    """Bit-unpack + weighted recombine: (CB, ..., W) packed planes ->
    (..., W*32) int32 per-column codes (plane b contributes bit b).
    CB = 0 yields all-zero codes."""
    cb = planes.shape[0]
    if cb == 0:
        return jnp.zeros(planes.shape[1:-1] + (planes.shape[-1] * 32,),
                         dtype=jnp.int32)
    code = unpack_bits(planes[0])
    for b in range(1, cb):
        code = code | (unpack_bits(planes[b]) << b)
    return code


def code_from_planes_np(planes: np.ndarray) -> np.ndarray:
    """Host twin of code_from_planes (numpy, same layout)."""
    planes = np.asarray(planes, dtype=np.uint32)
    cb = planes.shape[0]
    out_shape = planes.shape[1:-1] + (planes.shape[-1] * 32,)
    code = np.zeros(out_shape, dtype=np.int32)
    shifts = np.arange(32, dtype=np.uint32)
    for b in range(cb):
        bits = ((planes[b][..., None] >> shifts) & 1).astype(np.int32)
        code |= bits.reshape(out_shape) << b
    return code
