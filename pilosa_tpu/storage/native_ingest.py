"""ctypes bindings for the native ingest scatter kernels
(native/ingest/scatter.cc) with numpy fallbacks.

The columnar import hot loops — bit scatter (np.bitwise_or.at) and
the per-plane BSI fill — are word-at-a-time scatters that numpy
cannot fuse; the C versions run ~10-20x faster.  Build is on demand
like the RBF library (same build.sh, cached by mtime).
"""

from __future__ import annotations

import ctypes as ct
import os
import subprocess
import threading

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE = os.path.join(_ROOT, "native")
_SO = os.path.join(_NATIVE, "build", "libingest_tpu.so")
_SRC = os.path.join(_NATIVE, "ingest", "scatter.cc")

_build_lock = threading.Lock()
_lib = None
_lib_failed = False

_I64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_U32 = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
_U64 = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
_I32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")


def _declare(lib):
    """The entry points' signatures on a loaded libingest_tpu."""
    lib.pt_or_bits.argtypes = [_U32, _I64, ct.c_int64]
    lib.pt_bsi_fill_t.argtypes = [_U32, ct.c_int64, _I64, _I64,
                                  ct.c_int64]
    lib.pt_mutex_fill.argtypes = [_U32, _U32, ct.c_int64, _I64, _I64,
                                  ct.c_int64]
    lib.pt_groupcode_hist.argtypes = [
        _U32, ct.c_int64, _U32, ct.c_void_p, ct.c_int64,
        ct.c_int64, ct.c_int64, ct.c_int64,
        _I64, _I64, _I64, _I64]
    lib.pt_page_coords.argtypes = [
        _U64, _I32, _I64, ct.c_int64, ct.c_int64, ct.c_int64,
        _U32, ct.c_int64, _I64]
    lib.pt_page_coords.restype = ct.c_int64
    lib.pt_page_fill.argtypes = [
        _U64, _I32, _I64, ct.c_int64, ct.c_int64, ct.c_int64,
        ct.c_int64, _U32, _I64]
    return lib


def _load():
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _build_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            if not os.path.exists(_SO) or \
                    os.path.getmtime(_SRC) > os.path.getmtime(_SO):
                subprocess.run(
                    ["sh", os.path.join(_NATIVE, "build.sh")],
                    check=True, capture_output=True)
            _lib = _declare(ct.CDLL(_SO))
        except Exception:
            _lib_failed = True  # no toolchain: numpy fallbacks
    return _lib


def available() -> bool:
    return _load() is not None


def or_bits(words: np.ndarray, cols: np.ndarray) -> None:
    """words[c>>5] |= 1 << (c&31) for every c (bitwise_or.at)."""
    lib = _load()
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    if lib is not None:
        lib.pt_or_bits(words, cols, cols.size)
        return
    np.bitwise_or.at(words, cols >> 5,
                     np.uint32(1) << (cols & 31).astype(np.uint32))


def bsi_fill(scratch: np.ndarray, cols: np.ndarray,
             vals: np.ndarray, depth: int) -> None:
    """Fill a zeroed (2+depth, plane_words) scratch: plane 0 exists,
    1 sign, 2+i magnitude bit i — one reverse pass over the values
    with built-in last-write-wins per column."""
    lib = _load()
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    if lib is not None:
        n_planes, plane_words = scratch.shape
        # interleaved fill (one cache line per value) + one
        # vectorized transpose back to plane-major
        scratch_t = np.zeros((plane_words, n_planes), np.uint32)
        lib.pt_bsi_fill_t(scratch_t, n_planes, cols, vals,
                          cols.size)
        scratch[:] = scratch_t.T
        return
    # numpy fallback dedups explicitly (the kernel's reverse scan)
    if cols.size > 1:
        _, rev_first = np.unique(cols[::-1], return_index=True)
        keep = cols.size - 1 - rev_first
        cols, vals = cols[keep], vals[keep]
    neg = vals < 0
    mags = np.where(neg, -vals, vals).view(np.uint64)
    or_bits(scratch[0], cols)
    or_bits(scratch[1], cols[neg])
    for i in range(depth):
        sel = (mags >> np.uint64(i)) & np.uint64(1) == 1
        or_bits(scratch[2 + i], cols[sel])


def groupcode_hist(code_planes: np.ndarray, valid: np.ndarray,
                   bsi: np.ndarray | None, n_codes: int,
                   signed: bool,
                   counts: np.ndarray, nn: np.ndarray,
                   pos: np.ndarray, neg: np.ndarray) -> None:
    """One shard of the one-pass GroupBy histogram: accumulate counts
    (n_codes,), nn (n_codes,) and sign-split per-plane partials
    pos/neg (n_codes, depth) int64 in place.  code_planes (CB, W)
    packed group-code bit-planes, valid (W,), bsi (2+depth, W) or
    None.  Host twin of ops/kernels.groupby_codes_xla."""
    code_planes = np.ascontiguousarray(code_planes, dtype=np.uint32)
    valid = np.ascontiguousarray(valid, dtype=np.uint32)
    depth = 0 if bsi is None else bsi.shape[0] - 2
    lib = _load()
    if lib is not None:
        if bsi is not None:
            bsi = np.ascontiguousarray(bsi, dtype=np.uint32)
        lib.pt_groupcode_hist(
            code_planes, code_planes.shape[0], valid,
            None if bsi is None else bsi.ctypes.data, depth,
            int(signed), valid.shape[0], int(n_codes),
            counts, nn, pos, neg)
        return
    # numpy fallback: unpack + bincount per payload row
    from pilosa_tpu.ops import bitmap as bmops
    from pilosa_tpu.ops import bsi as bsi_ops
    code = bmops.code_from_planes_np(code_planes)     # (W*32,)
    va = bsi_ops.unpack_bits_np(valid)
    counts += np.bincount(code[va], minlength=n_codes)[:n_codes]
    if bsi is None:
        return
    ex = bsi_ops.unpack_bits_np(bsi[0]) & va
    sg = bsi_ops.unpack_bits_np(bsi[1])
    nn += np.bincount(code[ex], minlength=n_codes)[:n_codes]
    posm = ex & ~sg if signed else ex
    negm = ex & sg
    for p in range(depth):
        mb = bsi_ops.unpack_bits_np(bsi[2 + p])
        pos[:, p] += np.bincount(code[mb & posm],
                                 minlength=n_codes)[:n_codes]
        if signed:
            neg[:, p] += np.bincount(code[mb & negm],
                                     minlength=n_codes)[:n_codes]


def groupcode_minmax(code_planes: np.ndarray, valid: np.ndarray,
                     bsi: np.ndarray, n_codes: int, signed: bool,
                     mm: np.ndarray) -> None:
    """One shard of the per-group Min/Max magnitude table: accumulate
    mm (4, n_codes) int64 rows [max_mag_pos, min_mag_pos, max_mag_neg,
    min_mag_neg] in place (caller pre-fills identities -1 / 1<<depth).
    Host numpy twin of the fused kernel's presence-walk Min/Max
    (ops/kernels.groupby_fused(minmax=True) / minmax_from_table)."""
    from pilosa_tpu.ops import bitmap as bmops
    from pilosa_tpu.ops import bsi as bsi_ops
    depth = bsi.shape[0] - 2
    code = bmops.code_from_planes_np(
        np.ascontiguousarray(code_planes, dtype=np.uint32))
    va = bsi_ops.unpack_bits_np(
        np.ascontiguousarray(valid, dtype=np.uint32))
    ex = bsi_ops.unpack_bits_np(bsi[0]) & va
    sg = bsi_ops.unpack_bits_np(bsi[1])
    mag = np.zeros(code.shape, np.int64)
    for p in range(depth):
        mag |= bsi_ops.unpack_bits_np(bsi[2 + p]).astype(np.int64) << p
    posm = (ex & ~sg if signed else ex).astype(bool)
    negm = (ex & sg).astype(bool) if signed else np.zeros_like(posm)
    inb = code < n_codes
    for row, op, mask in ((0, np.maximum, posm), (1, np.minimum, posm),
                          (2, np.maximum, negm), (3, np.minimum, negm)):
        sel = mask & inb
        if sel.any():
            op.at(mm[row], code[sel], mag[sel])


def mutex_fill(written: np.ndarray, scratch: np.ndarray,
               rowidx: np.ndarray, cols: np.ndarray) -> None:
    """Fill a zeroed (n_rows, plane_words) scratch with one bit per
    (dense row index, column), last write per column winning;
    `written` collects every touched column (the clear-then-set
    mask)."""
    lib = _load()
    rowidx = np.ascontiguousarray(rowidx, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    if lib is not None:
        lib.pt_mutex_fill(written, scratch.reshape(-1),
                          scratch.shape[1], rowidx, cols, cols.size)
        return
    if cols.size > 1:
        _, rev_first = np.unique(cols[::-1], return_index=True)
        keep = cols.size - 1 - rev_first
        cols, rowidx = cols[keep], rowidx[keep]
    or_bits(written, cols)
    for r in np.unique(rowidx):
        or_bits(scratch[int(r)], cols[rowidx == r])


# -- fresh stack pages from the fragments' storage ----------------------
# A lane is None (nobody holds the row there) or (kind, array, bits) as
# Fragment.row_source gives it: "codes" (uint8 / uint16, one a column),
# "cols" (sorted int64 columns) or "words" (packed uint32, bits -1).

_LANE_KINDS = {("codes", 1): 1, ("codes", 2): 2, ("cols", 8): 3,
               ("words", 4): 4}


def _lane_args(lanes, width: int):
    """(addrs, kinds, sizes) of a page's lanes for the native calls.
    The arrays stay referenced by `lanes` while the call reads them."""
    n = len(lanes)
    addrs = np.zeros(n, np.uint64)
    kinds = np.zeros(n, np.int32)
    sizes = np.zeros(n, np.int64)
    for k, lane in enumerate(lanes):
        if lane is None:
            continue
        kind, arr, _bits = lane
        want = (width if kind == "codes" else
                width // 32 if kind == "words" else arr.size)
        if not arr.flags.c_contiguous or arr.size != want:
            raise ValueError("lane %d: %s array of %d does not hold a "
                             "row of %d columns" % (k, kind, arr.size,
                                                    width))
        kinds[k] = _LANE_KINDS[kind, arr.dtype.itemsize]
        addrs[k] = arr.__array_interface__["data"][0]
        sizes[k] = arr.size
    return addrs, kinds, sizes


def _codes_eq(codes: np.ndarray, row: int) -> np.ndarray:
    """`codes == row`, where the codes' width can hold the row at all
    (its largest value is the sentinel of a column with no row)."""
    if 0 <= row < np.iinfo(codes.dtype).max:
        return codes == row
    return np.zeros(codes.shape, dtype=bool)


def page_coords(lanes, row: int, width: int, coords: np.ndarray,
                lane_counts: np.ndarray) -> int:
    """Sorted coordinates `lane * width + column` of `row`'s bits over
    a page's lanes, written into the sentinel-filled `coords`; each
    lane's count into `lane_counts`.  Returns how many, or -1 where
    they do not fit `coords` or a lane is held as words."""
    lib = _load()
    if lib is not None:
        addrs, kinds, sizes = _lane_args(lanes, width)
        return int(lib.pt_page_coords(
            addrs, kinds, sizes, len(lanes), width, int(row), coords,
            coords.size, lane_counts))
    n = 0
    for k, lane in enumerate(lanes):
        if lane is None:
            continue
        if lane[0] == "words":
            return -1
        cols = (lane[1] if lane[0] == "cols"
                else np.flatnonzero(_codes_eq(lane[1], row)))
        if n + cols.size > coords.size:
            return -1
        coords[n:n + cols.size] = cols + k * width
        lane_counts[k] = cols.size
        n += cols.size
    return n


def page_fill(lanes, row: int, width: int,
              block: np.ndarray) -> tuple[int, int, int]:
    """`row`'s packed words over a page's lanes into every word of
    `block` (page_lanes, width / 32), zeros where no lane holds it.
    Returns (all-ones words, their runs over the flat block, lanes
    copied as words): the first two count the lanes made from codes
    or columns only, so they describe the block where the third is 0."""
    lib = _load()
    if lib is not None:
        addrs, kinds, sizes = _lane_args(lanes, width)
        stats = np.zeros(3, np.int64)
        lib.pt_page_fill(addrs, kinds, sizes, len(lanes),
                         block.shape[0], width, int(row),
                         block.reshape(-1), stats)
        return int(stats[0]), int(stats[1]), int(stats[2])
    block[:] = 0
    made = np.zeros(block.shape[0], bool)
    copied = 0
    for k, lane in enumerate(lanes):
        if lane is None:
            continue
        if lane[0] == "words":
            block[k] = lane[1]
            copied += 1
        elif lane[0] == "codes":
            block[k] = np.packbits(_codes_eq(lane[1], row),
                                   bitorder="little").view(np.uint32)
            made[k] = True
        else:
            or_bits(block[k], lane[1])
            made[k] = True
    full = (block == np.uint32(0xFFFFFFFF)) & made[:, None]
    edges = np.diff(np.concatenate(
        ([False], full.reshape(-1), [False])).astype(np.int8))
    return (int(np.count_nonzero(full)),
            int(np.count_nonzero(edges == 1)), copied)
