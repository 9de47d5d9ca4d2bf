"""Container-adaptive page encodings — Roaring on the paged stack.

The reference's bottom layer keeps three container types per 64Ki-
column chunk (roaring: array / bitmap / run — PAPERS.md arxiv
1402.6407, 1603.06549) because dense bitmaps waste memory and
bandwidth on sparse data.  Our device unit is the stack-cache PAGE
(memory/pages.py): a fixed lane-block of ``(page_lanes, W)`` uint32
words.  This module picks, per page block, between

- **dense**  — the page as-is (today's format; the only arm with a
  word-scatter patch path),
- **packed** — the sorted coordinates of the set bits, one uint32 per
  bit (coordinate = flat bit offset inside the page block), padded to
  a pow2 length with an out-of-range sentinel so the jitted expand /
  count kernels compile O(log) distinct shapes,
- **run**    — word-granular runs of all-ones words (sorted
  ``(start, len)`` int32 pairs over the flat word space) plus the
  residual set bits outside the runs as a packed coordinate tail.

The page keeps its identity: it is still the HBM-ledger/eviction/
patch/prefetch unit, its logical shape and lane range are unchanged,
and ``expand()`` reproduces the dense block bit-exactly (the decode-
to-dense boundary used whenever an op has no packed arm).  Only its
resident *byte size* changes — the TileStackCache accounts encoded
pages at their true size, which is exactly the working-set
multiplier the sparse format exists to buy.

Decision rule (per page block, from host words — no stats required):
the cheapest sparse candidate must undercut the dense page by
``1/dense_frac`` (default: sparse must be <= 0.5x dense bytes) to
enter, and once a page is sparse it re-encodes dense only past a
1.5x-looser leave threshold (hysteresis — drift near the boundary
must not re-encode every patch).  The stats catalog's per-
(index, field) density (obs/stats.py) short-circuits the analysis for
clearly-dense fields; pages of unknown fields always analyze.

A FRESH page need not be a host block first: where every lane's
fragment says how it holds the row and how many bits it has
(``Fragment.row_source``), :func:`encode_lanes` decides the form from
the counts and has one native call write the coordinates, or the
words, straight from that storage.

Kill switch: ``PILOSA_TPU_SPARSE_FORMAT=0`` (config twin
``[stacked] sparse-format``) restores the all-dense format.
"""

from __future__ import annotations

import os

import numpy as np

_FULL = np.uint32(0xFFFFFFFF)

# sparse entry threshold: encoded bytes must be <= this fraction of
# the dense page to leave the dense format ([stacked] sparse-dense-
# frac; hysteresis widens it by _LEAVE_RATIO for already-sparse pages)
_DENSE_FRAC = 0.5
_LEAVE_RATIO = 1.5
# stats-catalog density band where analysis is pointless: packed
# can't pay above ~1/64 density and runs only pay near-saturation, so
# a field the catalog pins inside this band skips the per-page scan
_HINT_DENSE_LO = 0.2
_HINT_DENSE_HI = 0.9
# floor for pow2-padded device array lengths: bounds the distinct
# shape count (executable-cache churn) for near-empty pages
_PAD_FLOOR = 8


def enabled() -> bool:
    return os.environ.get("PILOSA_TPU_SPARSE_FORMAT", "1") != "0"


def configure(dense_frac: float | None = None):
    """Apply the [stacked] sparse-format knobs (config.py)."""
    global _DENSE_FRAC
    if dense_frac is not None and dense_frac > 0:
        _DENSE_FRAC = float(dense_frac)


def _pow2(n: int) -> int:
    n = max(int(n), _PAD_FLOOR)
    return 1 << (n - 1).bit_length()


def _packed_bytes(nbits: int) -> int:
    return 4 * _pow2(nbits)


def _run_bytes(n_runs: int, n_resid: int) -> int:
    return 8 * _pow2(n_runs) + 4 * _pow2(n_resid)


def _pays(enc_bytes: int, dense_bytes: int,
          prev_kind: str | None = None) -> bool:
    """The entry rule: an encoding of `enc_bytes` may stand for a page
    of `dense_bytes` (a page that is sparse already stays so up to the
    looser leave threshold)."""
    limit = _DENSE_FRAC if prev_kind in (None, "dense") else min(
        _DENSE_FRAC * _LEAVE_RATIO, 0.95)
    return enc_bytes <= limit * dense_bytes


def _packed_page(page_lanes: int, width_words: int, coords: np.ndarray,
                 n_valid: int, lane_counts: np.ndarray) -> "EncodedPage":
    enc = EncodedPage("packed", page_lanes, width_words, coords, None,
                      None, lane_counts, n_valid, 0)
    enc.host_positions = coords[:n_valid].astype(np.int64)
    return enc


def _positions(flat_words: np.ndarray) -> np.ndarray:
    """Sorted flat bit offsets of the set bits of a flat word array
    (LSB-first inside each word, matching ops/bitmap.py's layout)."""
    # only the words that hold a bit are unpacked: a page that is
    # worth packing is at most 1/64 dense, and most of its words are 0
    # (nonzero over booleans is numpy's fast path: a third of the time
    # of the same over words and bytes, on the chip machine's host)
    nz = np.flatnonzero(flat_words != 0).astype(np.uint32)
    k = np.flatnonzero(np.unpackbits(
        flat_words[nz].view(np.uint8), bitorder="little").view(np.bool_)
    ).astype(np.uint32)
    return (nz[k >> np.uint32(5)] << np.uint32(5)) | (k & np.uint32(31))


class EncodedPage:
    """One page's sparse payload.  ``coords`` / ``run_starts`` /
    ``run_lens`` start as host numpy arrays and move to the device
    via :meth:`to_device` (under the OOM backstop — a page that can't
    allocate stays host-resident, like a dense host-fallback block).
    ``lane_counts`` stays on the host: it is the per-lane popcount
    computed for free at encode time, serving the engine's packed
    Count/TopN arms without touching the device at all."""

    __slots__ = ("kind", "page_lanes", "width_words", "coords",
                 "run_starts", "run_lens", "lane_counts", "n_valid",
                 "n_runs", "host_positions", "_nbytes")

    def __init__(self, kind: str, page_lanes: int, width_words: int,
                 coords, run_starts, run_lens,
                 lane_counts: np.ndarray, n_valid: int, n_runs: int):
        self.kind = kind                    # "packed" | "run"
        self.page_lanes = int(page_lanes)
        self.width_words = int(width_words)
        self.coords = coords                # sentinel-padded uint32
        self.run_starts = run_starts        # sentinel-padded int32
        self.run_lens = run_lens            # zero-padded int32
        self.lane_counts = lane_counts      # host (page_lanes,) int64
        self.n_valid = int(n_valid)         # true coordinate count
        self.n_runs = int(n_runs)
        # packed pages keep their sorted positions host-resident (set
        # at encode time, like lane_counts): the engine's packed
        # set-op Count arm does sorted-coordinate algebra without ever
        # fetching coords back from the device
        self.host_positions: np.ndarray | None = None
        self._nbytes: int | None = None

    def positions(self) -> "np.ndarray | None":
        """Sorted unique flat set-bit offsets, host int64 (packed
        pages only); cached on first use."""
        if self.kind != "packed":
            return None
        if self.host_positions is None:
            self.host_positions = np.asarray(
                self.coords, dtype=np.int64)[:self.n_valid]
        return self.host_positions

    @property
    def nbytes(self) -> int:
        """True resident bytes (what the HBM ledger accounts).
        Payload sizes are fixed at construction (``to_device`` moves
        the arrays but never resizes), so the walk over (possibly
        device) array properties runs once."""
        if self._nbytes is None:
            n = int(self.coords.nbytes)
            if self.run_starts is not None:
                n += int(self.run_starts.nbytes)
                n += int(self.run_lens.nbytes)
            self._nbytes = n
        return self._nbytes

    @property
    def shape(self) -> tuple:
        return (self.page_lanes, self.width_words)

    def bit_count(self) -> int:
        return int(self.lane_counts.sum())

    def to_device(self, device=None) -> "EncodedPage":
        """Move the payload arrays onto the device (in place).
        ``device`` commits them to a specific mesh device (the page's
        placement owner) so ``expand()`` decodes to dense ON that
        device; None keeps the default-device behavior."""
        import jax
        import jax.numpy as jnp
        if device is not None:
            put = lambda a: jax.device_put(np.asarray(a), device)  # noqa: E731
        else:
            put = jnp.asarray
        self.coords = put(self.coords)
        if self.run_starts is not None:
            self.run_starts = put(self.run_starts)
            self.run_lens = put(self.run_lens)
        return self

    def expand(self):
        """Dense (page_lanes, W) device block — bit-exact decode (the
        gather-expand at operand boundaries that need dense tiles)."""
        from pilosa_tpu.ops import bitmap as bm
        if self.kind == "packed":
            _warm_expand(self.page_lanes, self.width_words)
            return bm.expand_coords(self.coords, self.page_lanes,
                                    self.width_words)
        return bm.expand_runs(self.run_starts, self.run_lens,
                              self.coords, self.page_lanes,
                              self.width_words)


_EXPAND_WARM: set = set()


def _warm_expand(page_lanes: int, width_words: int) -> None:
    """Before the first packed page of a shape is expanded, run the
    expand program of every padded length such a page can come in
    (the powers of two from _PAD_FLOOR up to the page's words: at
    most 18), on nothing but sentinels.  The family is closed, so a
    server compiles all of it when it meets its first packed page, in
    its warm-up, and not one length at a time as fresh rows of other
    densities arrive later."""
    shape = (int(page_lanes), int(width_words))
    if shape in _EXPAND_WARM:
        return
    _EXPAND_WARM.add(shape)
    from pilosa_tpu.ops import bitmap as bm
    n_words = shape[0] * shape[1]
    n = _PAD_FLOOR
    while n <= n_words:
        bm.expand_coords(np.full(n, n_words * 32, dtype=np.uint32), *shape)
        n <<= 1


def is_encoded(page) -> bool:
    return isinstance(page, EncodedPage)


def page_kind(page) -> str:
    return page.kind if isinstance(page, EncodedPage) else "dense"


def page_nbytes(page) -> int:
    """True byte size of any page payload (dense array or encoded)."""
    return int(page.nbytes)


def to_dense(page):
    """Decode-to-dense boundary: expand an encoded page, pass a dense
    one through untouched."""
    return page.expand() if isinstance(page, EncodedPage) else page


def encode_block(block: np.ndarray, prev_kind: str | None = None,
                 density_hint: float | None = None):
    """Pick an encoding for one host page block.  Returns an
    :class:`EncodedPage` (host arrays — caller commits to device) or
    None to keep the block dense.  ``prev_kind`` is the page's
    current encoding (hysteresis); ``density_hint`` the stats
    catalog's field density, used only to skip the scan for clearly-
    dense fields."""
    if not enabled():
        return None
    pl, w = block.shape
    total_bits = pl * w * 32
    if total_bits >= 1 << 32:
        return None  # coordinate space must fit uint32
    if (density_hint is not None
            and prev_kind in (None, "dense")
            and _HINT_DENSE_LO <= density_hint <= _HINT_DENSE_HI):
        return None
    dense_b = int(block.nbytes)
    lane_counts = np.bitwise_count(block).sum(axis=1, dtype=np.int64)
    nbits = int(lane_counts.sum())
    flat = np.ascontiguousarray(block, dtype=np.uint32).reshape(-1)
    full = flat == _FULL
    n_full = int(np.count_nonzero(full))
    n_resid = nbits - 32 * n_full
    edges = np.flatnonzero(np.diff(
        np.concatenate(([False], full, [False])).astype(np.int8)))
    n_runs = edges.size // 2
    packed_b = _packed_bytes(nbits)
    run_b = _run_bytes(n_runs, n_resid)
    kind, best_b = (("packed", packed_b) if packed_b <= run_b
                    else ("run", run_b))
    if not _pays(best_b, dense_b, prev_kind):
        return None
    if kind == "packed":
        pos = _positions(flat)
        coords = np.full(_pow2(pos.size), total_bits, dtype=np.uint32)
        coords[:pos.size] = pos
        return _packed_page(pl, w, coords, pos.size, lane_counts)
    starts, ends = edges[0::2], edges[1::2]
    run_starts = np.full(_pow2(starts.size), pl * w, dtype=np.int32)
    run_lens = np.zeros(_pow2(starts.size), dtype=np.int32)
    run_starts[:starts.size] = starts
    run_lens[:starts.size] = ends - starts
    resid = flat.copy()
    resid[full] = 0
    pos = _positions(resid)
    coords = np.full(_pow2(pos.size), total_bits, dtype=np.uint32)
    coords[:pos.size] = pos
    return EncodedPage("run", pl, w, coords, run_starts, run_lens,
                       lane_counts, pos.size, int(starts.size))


def encode_lanes(lanes: list, row: int, page_lanes: int,
                 width_words: int, density_hint: float | None = None):
    """One FRESH page of a row's stack, made from its lanes' storage
    and not from a host block: ``lanes[k]`` is what
    ``Fragment.row_source(row)`` gives for the page's k-th lane (None:
    nobody holds the row there).  Returns the page in its final form,
    an :class:`EncodedPage` or the dense ``(page_lanes, W)`` block.

    Where every lane is counted (codes or columns) the counts decide
    before a byte is read: under :func:`encode_block`'s packing limit
    the page is packed, even where runs would have been smaller, and
    one call writes its coordinates; else one call fills the block and
    says where its all-ones words lie, which is the rest of that
    analysis.  A lane held as words is one copy, and such a block goes
    through :func:`encode_block` as a host block always did
    (``density_hint`` skips that scan for a clearly dense field)."""
    from pilosa_tpu.storage import native_ingest as ni
    w = int(width_words)
    dense_b = page_lanes * w * 4
    total_bits = page_lanes * w * 32
    can_encode = enabled() and total_bits < 1 << 32
    counted = all(ln is None or ln[2] >= 0 for ln in lanes)
    nbits = sum(ln[2] for ln in lanes if ln is not None) if counted else 0
    if counted and can_encode and _pays(_packed_bytes(nbits), dense_b):
        coords = np.full(_pow2(nbits), total_bits, dtype=np.uint32)
        lane_counts = np.zeros(page_lanes, dtype=np.int64)
        n = ni.page_coords(lanes, row, w * 32, coords, lane_counts)
        if n >= 0:
            return _packed_page(page_lanes, w, coords, n, lane_counts)
        # more bits than were counted: a write is racing this build
        # (the cache's versions discard it); the block below is safe
    block = np.empty((page_lanes, w), dtype=np.uint32)
    n_full, n_runs, copied = ni.page_fill(lanes, row, w * 32, block)
    if not can_encode:
        return block
    if copied:
        enc = encode_block(block, density_hint=density_hint)
    elif _pays(_run_bytes(n_runs, nbits - 32 * n_full), dense_b):
        enc = encode_block(block)
    else:
        enc = None
    return block if enc is None else enc
