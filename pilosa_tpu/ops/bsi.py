"""Bit-sliced integer (BSI) kernels.

Encoding matches the reference (fragment.go:34-66, fragment.go:579-718):
an integer field's shard is a stack of packed bit-planes —

- plane 0: exists (not-null) bit        (bsiExistsBit)
- plane 1: sign bit (set => negative)   (bsiSignBit)
- plane 2+i: magnitude bit i, LSB first (bsiOffsetBit)

i.e. sign-magnitude, NOT two's complement.  ``planes`` arrays have shape
``(2 + depth, W)`` uint32 with W packed words per shard-row.  The
range and sum functions below index a plane on the LEADING axis and
are elementwise in the rest, so they take a whole field as they take
one shard: a ``(2 + depth, S, W)`` array, or the 2 + depth ``(S, W)``
planes as a sequence (what a ragged program gathers out of its pages,
ops/bitmap.py PlaneLeaf).

The reference computes Range/Min/Max with data-dependent bitmap walks
(fragment.go:937-1305).  Here the same semantics are expressed as
fixed-shape bit-serial comparator chains over all 2^20 columns at once:
one pass over the magnitude planes yields per-column LT/EQ masks against
a predicate, and all six comparison ops plus BETWEEN are cheap boolean
combinations of those masks with the sign/exists planes.  Predicates
enter as per-plane broadcast masks (a ``(depth,)`` uint32 input array),
so changing the predicate does NOT trigger recompilation and 64-bit
predicates never need 64-bit scalars on device.

Exactness: Sum returns per-plane popcounts; the host combines them as
``sum(+/- pc[i] << i)`` in exact Python ints, so >2^53 totals are exact
without enabling x64 on device (SURVEY §7 "Exactness").
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.shardwidth import (
    BSI_EXISTS_BIT,
    BSI_OFFSET_BIT,
    BSI_SIGN_BIT,
    SHARD_WIDTH,
)

_ONES = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Host-side encode/decode + predicate prep (numpy / exact Python ints)
# ---------------------------------------------------------------------------

def depth_for_range(lo: int, hi: int) -> int:
    """Bit depth needed to store magnitudes in [lo, hi] (>=1)."""
    m = max(abs(int(lo)), abs(int(hi)), 1)
    return max(1, m.bit_length())


def encode(columns, values, depth: int | None = None,
           width: int = SHARD_WIDTH) -> np.ndarray:
    """Pack (column, value) pairs into sign-magnitude planes.

    Mirrors fragment.setValueBase (fragment.go:662-718): exists bit set,
    sign bit iff value < 0, magnitude bits of abs(value) LSB-first.
    Values must fit int64; magnitudes must fit in `depth` bits.
    """
    columns = np.asarray(columns, dtype=np.int64)
    vals = np.asarray(values, dtype=np.int64).reshape(-1)
    assert vals.shape == columns.shape
    if columns.size:
        # last-write-wins on duplicate columns (setValueBase overwrites)
        _, rev_first = np.unique(columns[::-1], return_index=True)
        keep = columns.size - 1 - rev_first
        columns, vals = columns[keep], vals[keep]
    neg = vals < 0
    # two's-complement wrap of -int64min yields magnitude 2^63 in uint64
    mags = np.where(neg, np.negative(vals), vals).view(np.uint64)
    need = depth_for_range(0, int(mags.max())) if vals.size else 1
    if depth is None:
        depth = need
    elif need > depth:
        raise ValueError(
            f"value magnitude needs {need} bits, field depth is {depth}")
    planes = np.zeros((2 + depth, width // 32), dtype=np.uint32)
    planes[BSI_EXISTS_BIT] = bm.from_columns(columns, width)
    planes[BSI_SIGN_BIT] = bm.from_columns(columns[neg], width)
    for i in range(depth):
        planes[BSI_OFFSET_BIT + i] = bm.from_columns(
            columns[(mags >> np.uint64(i)) & np.uint64(1) == 1], width)
    return planes


def decode(planes) -> tuple[np.ndarray, list[int]]:
    """Inverse of encode: -> (columns, values) with exact Python ints.

    Vectorized per plane: one numpy gather+shift per magnitude bit
    (depth passes over the set columns), with an object-int fallback
    only for magnitudes beyond int64 (depth > 62).
    """
    planes = np.asarray(planes)
    depth = planes.shape[0] - 2
    cols = bm.to_columns(planes[BSI_EXISTS_BIT])
    if cols.size == 0:
        return cols, []
    w = (cols >> np.uint64(5)).astype(np.int64)
    b = (cols & np.uint64(31)).astype(np.uint32)

    def bits(plane):
        return ((plane[w] >> b) & 1).astype(np.int64)

    if depth <= 62:
        mags = np.zeros(cols.size, dtype=np.int64)
        for i in range(depth):
            mags |= bits(planes[BSI_OFFSET_BIT + i]) << np.int64(i)
        sign = bits(planes[BSI_SIGN_BIT]).astype(bool)
        values = np.where(sign, -mags, mags).tolist()
    else:
        mags = np.zeros(cols.size, dtype=object)
        for i in range(depth):
            mags += bits(planes[BSI_OFFSET_BIT + i]).astype(object) << i
        sign = bits(planes[BSI_SIGN_BIT]).astype(bool)
        values = [-m if s else m for m, s in zip(mags.tolist(), sign)]
    return cols, values


def unpack_bits(words):
    """Device bit-unpack: (..., W) uint32 words -> (..., W*32) int32
    0/1 per column (column c = word c>>5, bit c&31)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1],
                        words.shape[-1] * 32).astype(jnp.int32)


def decode_device(planes):
    """Device-side BSI decode: planes (..., 2+depth, W) ->
    (exists, sign, lo, hi), each (..., W*32) int32.

    The value of column c is  (-1)^sign * (lo + (hi << 31)); the split
    keeps every device word in int32 (no x64) for depths up to 62.
    This is the fixed-shape replacement for the reference's per-column
    value materialization (executor.go:4758 Extract, 9321 Sort,
    2034 Distinct-BSI): one pass over the plane stack unpacks ALL 2^20
    columns at once, so Sort/Extract/Distinct issue O(shard-chunks)
    device calls instead of O(columns) host work.
    """
    depth = planes.shape[-2] - 2
    assert depth <= 62, "decode_device supports depth <= 62"
    exists = unpack_bits(planes[..., BSI_EXISTS_BIT, :])
    sign = unpack_bits(planes[..., BSI_SIGN_BIT, :])
    lo = jnp.zeros_like(exists)
    hi = jnp.zeros_like(exists)
    for i in range(depth):
        bit = unpack_bits(planes[..., BSI_OFFSET_BIT + i, :])
        if i < 31:
            lo = lo | (bit << i)
        else:
            hi = hi | (bit << (i - 31))
    return exists, sign, lo, hi


def host_combine_decoded(exists, sign, lo, hi):
    """Numpy combine of decode_device outputs -> (exists bool array,
    values int64 array over ALL columns; value 0 where not exists)."""
    ex = np.asarray(exists).astype(bool)
    vals = (np.asarray(lo).astype(np.int64)
            | (np.asarray(hi).astype(np.int64) << 31))
    neg = np.asarray(sign).astype(bool)
    vals = np.where(neg, -vals, vals)
    return ex, np.where(ex, vals, 0)


def unpack_bits_np(words: np.ndarray) -> np.ndarray:
    """Host bit-unpack mirroring unpack_bits: (..., W) uint32 ->
    (..., W*32) bool."""
    words = np.asarray(words, dtype=np.uint32)
    bits = (words[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(*words.shape[:-1],
                        words.shape[-1] * 32).astype(bool)


def predicate_masks(upredicate: int, depth: int) -> np.ndarray:
    """Per-plane broadcast masks for an unsigned predicate.

    mask[i] is 0xFFFFFFFF iff bit i of upredicate is set.  upredicate
    must fit in `depth` bits — the executor clamps/short-circuits
    out-of-range predicates at plan time (see range_* docstrings).
    """
    assert 0 <= upredicate < (1 << depth), (upredicate, depth)
    return np.array(
        [_ONES if (upredicate >> i) & 1 else np.uint32(0) for i in range(depth)],
        dtype=np.uint32,
    )


# ---------------------------------------------------------------------------
# Device-side kernels (pure jnp; compose under one jit)
# ---------------------------------------------------------------------------

def _mag(planes):
    return planes[BSI_OFFSET_BIT:]


def cmp_unsigned(mag_planes, pbits):
    """Bit-serial compare of per-column magnitudes against a predicate.

    mag_planes: (depth, W) uint32, LSB-first.  pbits: (depth,) uint32
    broadcast masks from predicate_masks().  Returns packed masks
    (lt, eq): per-column magnitude <, == the predicate.

    This one pass replaces the reference's rangeLTUnsigned /
    rangeGTUnsigned / rangeEQ bit walks (fragment.go:1044-1100,
    1158-1213, 968-1005): each of depth steps is 4 VPU ops on 32768
    lanes, with no data-dependent control flow.
    """
    depth = len(mag_planes)
    lt = jnp.zeros_like(mag_planes[0])
    eq = jnp.full_like(mag_planes[0], _ONES)
    for i in range(depth - 1, -1, -1):
        m = mag_planes[i]
        p = pbits[i]  # scalar word mask, broadcasts over (W,)
        lt = lt | (eq & ~m & p)
        eq = eq & ~(m ^ p)
    return lt, eq


def range_eq(planes, pbits, pred_is_neg):
    """Columns whose value == predicate (fragment.rangeEQ semantics).

    pred_is_neg: traced bool scalar — predicate sign chooses the sign
    plane filter (negatives-only vs positives-only).
    """
    exists, sign = planes[BSI_EXISTS_BIT], planes[BSI_SIGN_BIT]
    _, eq = cmp_unsigned(_mag(planes), pbits)
    sign_sel = jnp.where(pred_is_neg, exists & sign, exists & ~sign)
    return sign_sel & eq


def range_neq(planes, pbits, pred_is_neg):
    """exists AND NOT eq (fragment.rangeNEQ)."""
    exists = planes[BSI_EXISTS_BIT]
    return exists & ~range_eq(planes, pbits, pred_is_neg)


def range_lt(planes, pbits, pred_is_neg, allow_eq: bool):
    """Columns with value < (or <=) predicate.

    Equivalent to fragment.rangeLT (fragment.go:1007-1042) without its
    dynamic special cases: for predicate p with magnitude masks pbits,
      p >= 0: negatives ∪ (positives with mag <(=) p)
      p <  0: negatives with mag >(=) |p|
    """
    exists, sign = planes[BSI_EXISTS_BIT], planes[BSI_SIGN_BIT]
    lt, eq = cmp_unsigned(_mag(planes), pbits)
    if allow_eq:
        ltu, gtu = lt | eq, ~lt
    else:
        ltu, gtu = lt, ~(lt | eq)
    pos_case = (exists & sign) | (exists & ~sign & ltu)
    neg_case = exists & sign & gtu
    return jnp.where(pred_is_neg, neg_case, pos_case)


def range_gt(planes, pbits, pred_is_neg, allow_eq: bool):
    """Columns with value > (or >=) predicate (fragment.rangeGT).

      p >= 0: positives with mag >(=) p
      p <  0: positives ∪ (negatives with mag <(=) |p|)
    """
    exists, sign = planes[BSI_EXISTS_BIT], planes[BSI_SIGN_BIT]
    lt, eq = cmp_unsigned(_mag(planes), pbits)
    if allow_eq:
        ltu, gtu = lt | eq, ~lt
    else:
        ltu, gtu = lt, ~(lt | eq)
    pos_case = exists & ~sign & gtu
    neg_case = (exists & ~sign) | (exists & sign & ltu)
    return jnp.where(pred_is_neg, neg_case, pos_case)


def range_between(planes, abits, bbits, a_is_neg, b_is_neg):
    """Columns with a <= value <= b (fragment.rangeBetween semantics).

    abits/bbits are magnitude masks of |a| and |b|.  Regimes selected
    by the (traced) predicate signs:
      0 <= a <= b      : positives with a <= mag <= b
      a <= b < 0       : negatives with |b| <= mag <= |a|
      a < 0 <= b       : (negatives with mag <= |a|) ∪ (positives with mag <= b)
      a >= 0 > b       : inverted range — empty
    """
    exists, sign = planes[BSI_EXISTS_BIT], planes[BSI_SIGN_BIT]
    lt_a, eq_a = cmp_unsigned(_mag(planes), abits)
    lt_b, eq_b = cmp_unsigned(_mag(planes), bbits)
    gte_a, lte_a = ~lt_a, lt_a | eq_a
    gte_b, lte_b = ~lt_b, lt_b | eq_b
    pos_case = exists & ~sign & gte_a & lte_b
    neg_case = exists & sign & gte_b & lte_a
    cross_case = (exists & sign & lte_a) | (exists & ~sign & lte_b)
    empty = jnp.zeros_like(exists)
    return jnp.where(
        a_is_neg,
        jnp.where(b_is_neg, neg_case, cross_case),
        jnp.where(b_is_neg, empty, pos_case),
    )


def not_null(planes):
    """The exists row (fragment.notNull)."""
    return planes[BSI_EXISTS_BIT]


def sum_counts(planes, filter_words=None):
    """Per-plane popcounts for exact host-side Sum.

    Returns (count, pos_pc, neg_pc): count of non-null (filtered)
    columns, and per-magnitude-plane popcounts split by sign, each
    (depth,) int32 — over a whole field's planes, (S,) and (S, depth).
    Host computes  sum = Σ (pos[i]-neg[i]) << i  in
    exact Python ints — the analog of roaring.BitmapBSICountFilter
    (fragment.sum, fragment.go:718-746) with int64-exactness preserved.
    """
    exists, sign = planes[BSI_EXISTS_BIT], planes[BSI_SIGN_BIT]
    consider = exists if filter_words is None else exists & filter_words
    pos = consider & ~sign
    neg = consider & sign
    mag = _mag(planes)

    def plane_counts(side):
        if isinstance(mag, tuple):      # gathered planes, one by one
            return jnp.stack([bm.count(m & side) for m in mag], axis=-1)
        # one array, one fusion; plane by plane XLA would cut it into
        # T(1,128)-tiled slices first (compiled for a v5e, PR 34)
        return jnp.moveaxis(bm.count(mag & side[None]), 0, -1)

    return bm.count(consider), plane_counts(pos), plane_counts(neg)


def host_sum(count, pos_pc, neg_pc) -> tuple[int, int]:
    """Combine sum_counts() outputs into (sum, count) exact ints."""
    pos_pc = np.asarray(pos_pc).tolist()
    neg_pc = np.asarray(neg_pc).tolist()
    total = sum((p - n) << i for i, (p, n) in enumerate(zip(pos_pc, neg_pc)))
    return int(total), int(np.asarray(count))


def _max_unsigned_walk(mag_planes, filter_words):
    """fragment.maxUnsigned (fragment.go:836-857) as a fixed-shape scan.

    Returns (bits, count): bits (depth,) bool MSB-walk decisions
    (bit i of the max), count int32 of columns attaining the max.
    """
    depth = mag_planes.shape[0]
    filt = filter_words
    bits = []
    for i in range(depth - 1, -1, -1):
        ones = filt & mag_planes[i]
        took = bm.any_set(ones)
        filt = jnp.where(took, ones, filt)
        bits.append(took)
    bits = jnp.stack(bits[::-1])  # LSB-first
    return bits, bm.count(filt)


def _min_unsigned_walk(mag_planes, filter_words):
    """fragment.minUnsigned (fragment.go:783-803): prefer zero bits."""
    depth = mag_planes.shape[0]
    filt = filter_words
    bits = []
    for i in range(depth - 1, -1, -1):
        zeroes = filt & ~mag_planes[i]
        nonempty = bm.any_set(zeroes)
        filt = jnp.where(nonempty, zeroes, filt)
        bits.append(~nonempty)  # forced 1-bit when no zero survives
    bits = jnp.stack(bits[::-1])
    return bits, bm.count(filt)


def min_op(planes, filter_words=None):
    """fragment.min (fragment.go:745-781) both branches + selector.

    Returns (is_neg, bits, count, nonempty).  If any negative value is
    in scope the min is -(max unsigned over negatives); otherwise the
    min unsigned over positives.  Host assembles value = (+/-) Σ bits<<i.
    """
    exists, sign = planes[BSI_EXISTS_BIT], planes[BSI_SIGN_BIT]
    consider = exists if filter_words is None else exists & filter_words
    negs = consider & sign
    pos = consider & ~sign
    any_neg = bm.any_set(negs)
    nb, ncount = _max_unsigned_walk(_mag(planes), negs)
    pb, pcount = _min_unsigned_walk(_mag(planes), pos)
    bits = jnp.where(any_neg, nb, pb)
    count = jnp.where(any_neg, ncount, pcount)
    return any_neg, bits, count, bm.any_set(consider)


def max_op(planes, filter_words=None):
    """fragment.max (fragment.go:805-834): positives preferred, else
    -(min unsigned over negatives)."""
    exists, sign = planes[BSI_EXISTS_BIT], planes[BSI_SIGN_BIT]
    consider = exists if filter_words is None else exists & filter_words
    pos = consider & ~sign
    negs = consider & sign
    any_pos = bm.any_set(pos)
    pb, pcount = _max_unsigned_walk(_mag(planes), pos)
    nb, ncount = _min_unsigned_walk(_mag(planes), negs)
    bits = jnp.where(any_pos, pb, nb)
    count = jnp.where(any_pos, pcount, ncount)
    return ~any_pos, bits, count, bm.any_set(consider)


def host_minmax(is_neg, bits, count, nonempty) -> tuple[int, int]:
    """Assemble (value, count) from min_op/max_op outputs; exact ints.

    Matches reference behavior of returning (0, 0) on empty scope.
    """
    if not bool(np.asarray(nonempty)):
        return 0, 0
    bits = np.asarray(bits).tolist()
    mag = sum(1 << i for i, b in enumerate(bits) if b)
    val = -mag if bool(np.asarray(is_neg)) else mag
    return int(val), int(np.asarray(count))
