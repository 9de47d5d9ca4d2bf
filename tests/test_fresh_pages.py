"""A fresh filter row's pages, built straight from the fragments'
storage (ISSUE 37): ``row_stack``'s page source
(``memory/encode.py encode_lanes`` over ``Fragment.row_source``, one
call of ``native/ingest/scatter.cc`` a page) against the parent's way
— the whole stack as a host array through ``Fragment.row_words``, cut
into pages and analysed by ``encode_block``.

Every page must ``expand()`` to the same bits, and wherever both ways
pack, the packed page must be the same page: coordinates, sentinel
and power-of-two padding, lane counts, host positions.
"""

from __future__ import annotations

import numpy as np
import pytest

from pilosa_tpu.executor import stacked
from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.memory import encode
from pilosa_tpu.memory.ledger import Ledger
from pilosa_tpu.memory.pages import PagedStack
from pilosa_tpu.models.fragment import Fragment
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.schema import (FieldOptions, FieldType,
                                      TimeQuantum)
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.obs import metrics
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.storage import native_ingest as ni

W = 1 << 15                       # columns a shard: a lane is 4 KB
PAGE_LANES = 32
FULL = np.uint32(0xFFFFFFFF)

# the rows every deployment below holds: nobody has EMPTY, one column
# in 500 has THIN, one in five DENSE (under SPARSE_MAX at this width,
# so the row stores keep it as columns), every column of every third
# shard has ONES, and the rest of the columns spread over OTHERS
# (past 255 rows the codes are uint16)
EMPTY, THIN, DENSE, ONES = 2, 1, 0, 3
ROWS = (EMPTY, THIN, DENSE, ONES)
FORMS = ("codes8", "codes16", "sparse", "words", "missing", "mixed")


@pytest.fixture(params=["native", "numpy"])
def impl(request, monkeypatch):
    """Both sides of every pair: the library, and its numpy twins as
    a machine with no toolchain runs them."""
    if request.param == "numpy":
        monkeypatch.setattr(ni, "_lib", None)
        monkeypatch.setattr(ni, "_lib_failed", True)
    elif not ni.available():
        pytest.skip("native/ingest/scatter.cc could not be built here")
    return request.param


@pytest.fixture(autouse=True)
def small_pages(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES",
                       str(PAGE_LANES * (W // 32) * 4))


def _assign(rng, shard: int, n_rows: int) -> np.ndarray:
    """The row of every column of one shard (-1: none)."""
    if shard % 3 == 2:
        return np.full(W, ONES, dtype=np.int64)
    a = rng.integers(4, n_rows, size=W)
    u = rng.random(W)
    a[u < 0.2] = DENSE
    a[(u >= 0.2) & (u < 0.202)] = THIN
    a[u > 0.97] = -1
    return a


def _hold(fr: Fragment, assign: np.ndarray, form: str) -> None:
    """Put one shard's rows into the fragment in the storage form
    asked for, whatever `_load` would have picked by size."""
    cols = np.flatnonzero(assign >= 0)
    rows = assign[cols]
    if form in ("codes8", "codes16"):
        dtype = np.uint8 if form == "codes8" else np.uint16
        codes = np.full(W, np.iinfo(dtype).max, dtype=dtype)
        codes[cols] = rows
        fr._codes, fr._code_counts = codes, np.bincount(rows)
    elif form == "sparse":
        fr._store_grouped(rows, cols)    # by cardinality, as a decode
    else:
        assert form == "words"
        for r in np.unique(rows).tolist():
            fr._rows[r] = bm.from_columns(cols[rows == r], W)
    fr.version += 1
    fr.check()


def _deployment(form: str, n_shards: int, seed: int = 7):
    """(holder, index, field, {shard: assign}) with every shard of the
    field held in `form`."""
    rng = np.random.default_rng(seed)
    h = Holder(width=W)
    idx = h.create_index("i")
    field = idx.create_field("f", FieldOptions(type=FieldType.MUTEX))
    view = field.view(VIEW_STANDARD, create=True)
    n_rows = 300 if form == "codes16" else 40
    truth = {}
    for s in range(n_shards):
        how = form
        if form == "missing":
            how = None if s % 2 else "codes8"
        elif form == "mixed":
            how = ("codes8", "words", "sparse", None)[s % 4]
        if how is None:
            continue
        truth[s] = _assign(rng, s, n_rows)
        _hold(view.fragment(s, create=True), truth[s], how)
    return h, idx, field, truth


def _engine(h):
    ex = Executor(h)
    ex.stacked.cache = stacked.TileStackCache(
        ledger=Ledger(budget_bytes=1 << 30))
    return ex


def _fresh(ex, idx, field, row, n_shards, views=(VIEW_STANDARD,)):
    """Ask for the row's stack as raw pages; (PageView, recipe)."""
    with stacked.raw_pages():
        view = ex.stacked.row_stack(idx, field, views, row,
                                    tuple(range(n_shards)))
    [recipe] = [rec[3] for rec in ex.stacked.cache._recipes.values()
                if rec[0][3:5] == (views, row)]
    return view, recipe


def _truth_block(truth, row, ids, page_lanes=PAGE_LANES):
    block = np.zeros((page_lanes, W // 32), dtype=np.uint32)
    for k, s in enumerate(ids):
        if int(s) in truth:
            block[k] = bm.from_columns(
                np.flatnonzero(truth[int(s)] == row), W)
    return block


def _same_page(page, block):
    """`page` holds `block`, and is the page encode_block makes of it
    wherever both pack."""
    assert np.array_equal(np.asarray(encode.to_dense(page)), block)
    ref = encode.encode_block(block)
    if ref is None:
        assert not encode.is_encoded(page)
        return "dense"
    assert encode.is_encoded(page)
    assert np.array_equal(page.lane_counts, ref.lane_counts)
    assert page.lane_counts.dtype == ref.lane_counts.dtype
    if ref.kind == "run" and page.kind == "packed":
        # counted under the packing limit: packed without a look at
        # the words, where their runs would have been smaller still
        assert encode._pays(encode._packed_bytes(page.n_valid),
                            block.nbytes)
        return "packed"
    assert page.kind == ref.kind
    assert page.n_valid == ref.n_valid and page.n_runs == ref.n_runs
    for name in ("coords", "run_starts", "run_lens"):
        a, b = getattr(page, name), getattr(ref, name)
        assert (a is None) == (b is None)
        if a is not None:
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and np.array_equal(a, b), name
    if page.kind == "packed":
        assert page.host_positions.dtype == ref.host_positions.dtype
        assert np.array_equal(page.host_positions, ref.host_positions)
    return page.kind


@pytest.mark.parametrize("n_shards", [1, 32, 33, 70])
@pytest.mark.parametrize("form", FORMS)
def test_fresh_pages_hold_what_build_host_gives(impl, form, n_shards):
    h, idx, field, truth = _deployment(form, n_shards)
    ex = _engine(h)
    seen = set()
    for row in ROWS:
        d0 = metrics.STACK_FRESH_PAGES.value(source="direct")
        h0 = metrics.STACK_FRESH_PAGES.value(source="host")
        view, recipe = _fresh(ex, idx, field, row, n_shards)
        n_pages = -(-n_shards // PAGE_LANES)
        assert len(view.pages) == n_pages
        # a stack under a page is one page of its own lanes
        assert view.page_lanes == min(PAGE_LANES, n_shards)
        assert metrics.STACK_FRESH_PAGES.value(source="direct") \
            == d0 + n_pages
        assert metrics.STACK_FRESH_PAGES.value(source="host") == h0
        host = recipe.build_host()
        assert host.shape == (n_shards, W // 32)
        for pi, page in enumerate(view.pages):
            ids = np.arange(pi * PAGE_LANES,
                            min((pi + 1) * PAGE_LANES, n_shards))
            block = _truth_block(truth, row, ids, view.page_lanes)
            assert np.array_equal(host[ids], block[:ids.size])
            seen.add((row, _same_page(page, block)))
    # the rows are what they are named for
    assert (EMPTY, "packed") in seen and (THIN, "packed") in seen
    if n_shards >= 32:
        assert (DENSE, "dense") in seen
    if n_shards >= 3:
        assert {k for r, k in seen if r == ONES} & {"dense", "run"}


LANE_MIXES = {
    "codes8": ("codes8",) * 5,
    "codes16": ("codes16",) * 5,
    "cols": ("cols",) * 5,
    "words": ("words",) * 5,
    "none": (None, "codes8", None, None, "cols"),
    "mixed": ("codes8", None, "cols", "codes16", "cols", "codes8"),
}


def _lanes(rng, kinds, row):
    """Lanes as Fragment.row_source gives them, and their bits."""
    lanes, bits = [], []
    for k, kind in enumerate(kinds):
        on = rng.random(W) < (0.9 if k == 1 else 0.01 * (k + 1))
        if k == 3:
            on[:] = True                     # all-ones words, one run
        cols = np.flatnonzero(on)
        bits.append(on)
        if kind is None:
            bits[-1] = np.zeros(W, bool)
            lanes.append(None)
        elif kind == "cols":
            lanes.append(("cols", cols.astype(np.int64), cols.size))
        elif kind == "words":
            lanes.append(("words", bm.from_columns(cols, W), -1))
        else:
            dtype = np.uint8 if kind == "codes8" else np.uint16
            codes = rng.integers(0, row, size=W).astype(dtype)
            codes[on] = row
            lanes.append(("codes", codes, cols.size))
    return lanes, np.stack(bits)


@pytest.fixture(scope="module")
def portable_lib(tmp_path_factory):
    """scatter.cc built with -DPT_PORTABLE: the compare that takes
    eight codes a 64-bit word, which is what a machine without SSE2
    compiles and this one otherwise never runs."""
    import ctypes
    import shutil
    import subprocess
    if shutil.which("g++") is None:
        pytest.skip("no g++ here")
    so = tmp_path_factory.mktemp("portable") / "libingest_portable.so"
    subprocess.run(
        ["g++", "-std=c++17", "-shared", "-fPIC", "-O2", "-DNDEBUG",
         "-DPT_PORTABLE", "-Wall", "-Wextra", "-Werror", "-o", str(so),
         ni._SRC], check=True, capture_output=True)
    return ni._declare(ctypes.CDLL(str(so)))


@pytest.mark.parametrize("build", ["as_built", "portable"])
@pytest.mark.parametrize("mix", sorted(LANE_MIXES))
def test_native_entry_points_equal_their_numpy_twins(mix, build, request,
                                                     monkeypatch):
    if build == "portable":
        monkeypatch.setattr(
            ni, "_lib", request.getfixturevalue("portable_lib"))
    elif not ni.available():
        pytest.skip("native/ingest/scatter.cc could not be built here")
    rng = np.random.default_rng(11)
    row = 200 if mix != "codes16" else 4000
    lanes, bits = _lanes(rng, LANE_MIXES[mix], row)
    total = PAGE_LANES * W

    def both():
        coords = np.full(1 << 18, total, dtype=np.uint32)
        counts = np.zeros(PAGE_LANES, dtype=np.int64)
        n = ni.page_coords(lanes, row, W, coords, counts)
        block = np.full((PAGE_LANES, W // 32), 0xDEADBEEF, np.uint32)
        stats = ni.page_fill(lanes, row, W, block)
        return n, coords, counts, block, stats

    native = both()
    monkeypatch.setattr(ni, "_lib", None)
    monkeypatch.setattr(ni, "_lib_failed", True)
    twin = both()
    want = np.zeros((PAGE_LANES, W // 32), dtype=np.uint32)
    for k in range(bits.shape[0]):
        want[k] = bm.from_columns(np.flatnonzero(bits[k]), W)
    has_words = any(ln is not None and ln[0] == "words" for ln in lanes)
    for n, coords, counts, block, stats in (native, twin):
        assert np.array_equal(block, want)
        assert stats[2] == sum(ln is not None and ln[0] == "words"
                               for ln in lanes)
        if has_words:
            assert n == -1
            continue
        pos = encode._positions(want.reshape(-1))
        assert n == pos.size
        assert np.array_equal(coords[:n], pos)
        assert (coords[n:] == total).all()
        assert np.array_equal(counts, np.bitwise_count(want).sum(axis=1))
        full = want.reshape(-1) == FULL
        edges = np.diff(np.concatenate(([False], full, [False]))
                        .astype(np.int8))
        assert stats[:2] == (int(full.sum()), int((edges == 1).sum()))
    assert native[0] == twin[0] and native[4] == twin[4]
    assert np.array_equal(native[1], twin[1])
    assert np.array_equal(native[2], twin[2])


def test_coordinates_never_pass_the_buffer(impl):
    """More bits than the counts promised (a write racing the build):
    the call says so and writes nothing past the end, and the page
    falls to the dense block, still right."""
    rng = np.random.default_rng(3)
    lanes, bits = _lanes(rng, ("codes8", "cols", "codes16"), 9)
    coords = np.full(64, 7, dtype=np.uint32)
    guard = np.concatenate([coords, coords])
    assert ni.page_coords(lanes, 9, W, guard[:64],
                          np.zeros(PAGE_LANES, np.int64)) == -1
    assert (guard[64:] == 7).all()
    short = [None if ln is None else (ln[0], ln[1], 1) for ln in lanes]
    page = encode.encode_lanes(short, 9, PAGE_LANES, W // 32)
    want = np.zeros((PAGE_LANES, W // 32), dtype=np.uint32)
    for k in range(3):
        want[k] = bm.from_columns(np.flatnonzero(bits[k]), W)
    assert np.array_equal(np.asarray(encode.to_dense(page)), want)


def test_a_row_no_code_can_hold_has_no_bit(impl):
    """Row 261 over uint8 codes is not row 5 (its low byte), and the
    codes' sentinel is nobody's row."""
    codes = np.full(W, 5, dtype=np.uint8)
    codes[::3] = 255
    for row, want in ((5, W - len(codes[::3])), (261, 0), (255, 0),
                      (-1, 0)):
        lanes = [("codes", codes, want)]
        coords = np.full(W, W, dtype=np.uint32)
        counts = np.zeros(1, dtype=np.int64)
        assert ni.page_coords(lanes, row, W, coords, counts) == want
        block = np.empty((1, W // 32), dtype=np.uint32)
        ni.page_fill(lanes, row, W, block)
        assert int(np.bitwise_count(block).sum()) == want


def test_a_packed_row_over_codes_reads_no_row_words(impl, monkeypatch):
    """The acceptance line: no Fragment.row_words, no
    encode._positions, no build_host, at most one native call a
    page; the counter moves by the page count."""
    n_shards = 70
    h, idx, field, truth = _deployment("codes8", n_shards)
    ex = _engine(h)
    calls = {"row_words": 0, "_positions": 0, "native": 0}

    def counting(owner, name, key):
        real = getattr(owner, name)

        def wrapped(*a, **kw):
            calls[key] += 1
            return real(*a, **kw)
        monkeypatch.setattr(owner, name, wrapped)

    counting(Fragment, "row_words", "row_words")
    counting(encode, "_positions", "_positions")
    counting(ni, "page_coords", "native")
    counting(ni, "page_fill", "native")
    d0 = metrics.STACK_FRESH_PAGES.value(source="direct")
    t0 = metrics.STACK_REBUILD_TIMED.value()
    s0 = metrics.STACK_REBUILD_SECONDS.value()
    view, recipe = _fresh(ex, idx, field, THIN, n_shards)
    assert [encode.page_kind(p) for p in view.pages] == ["packed"] * 3
    assert calls == {"row_words": 0, "_positions": 0, "native": 3}
    assert metrics.STACK_FRESH_PAGES.value(source="direct") == d0 + 3
    assert metrics.STACK_REBUILD_TIMED.value() == t0 + 1
    assert metrics.STACK_REBUILD_SECONDS.value() > s0
    # and a dense row is one native call a page too
    view, _ = _fresh(ex, idx, field, DENSE, n_shards)
    assert calls == {"row_words": 0, "_positions": 0, "native": 6}
    want = sum(int((a == THIN).sum()) for a in truth.values())
    assert ex.execute("i", f"Count(Row(f={THIN}))")[0] == want


def test_two_views_fall_back_and_still_agree(impl):
    """A time field's quantum cover ORs several views lane by lane:
    that stays build_host's, and its pages count as host-made."""
    from datetime import datetime
    n_shards = 40
    h = Holder(width=W)
    idx = h.create_index("i")
    field = idx.create_field("t", FieldOptions(
        type=FieldType.TIME, time_quantum=TimeQuantum("YM")))
    rng = np.random.default_rng(2)
    cols = rng.choice(n_shards * W, size=6000, replace=False)
    rows = np.full(cols.size, 5)
    field.import_bits(rows[:3000], cols[:3000],
                      timestamps=[datetime(2020, 1, 5)] * 3000)
    field.import_bits(rows[3000:], cols[3000:],
                      timestamps=[datetime(2020, 2, 5)] * 3000)
    views = tuple(sorted(v for v in field.views
                         if v.startswith("standard_2020")
                         and len(v) == len("standard_202001")))
    assert len(views) == 2
    ex = _engine(h)
    d0 = metrics.STACK_FRESH_PAGES.value(source="direct")
    h0 = metrics.STACK_FRESH_PAGES.value(source="host")
    view, recipe = _fresh(ex, idx, field, 5, n_shards, views=views)
    assert recipe.build_page is None
    assert metrics.STACK_FRESH_PAGES.value(source="host") == h0 + 2
    assert metrics.STACK_FRESH_PAGES.value(source="direct") == d0
    got = np.concatenate([np.asarray(encode.to_dense(p))
                          for p in view.pages])[:n_shards]
    want = bm.from_columns(np.sort(cols), n_shards * W).reshape(
        n_shards, W // 32)
    assert np.array_equal(got, want)
    # one view of the same field has the page source
    view, recipe = _fresh(ex, idx, field, 5, n_shards, views=views[:1])
    assert recipe.build_page is not None
    assert metrics.STACK_FRESH_PAGES.value(source="direct") == d0 + 2


@pytest.mark.parametrize("form", ["codes8", "mixed"])
def test_a_lost_page_is_rebuilt_by_the_same_builder(impl, form):
    """The page_rebuild branch: a fresh entry that lost a page to an
    eviction gets it back from the page source, the same page."""
    n_shards = 70
    h, idx, field, truth = _deployment(form, n_shards)
    ex = _engine(h)
    cache = ex.stacked.cache
    for row in (THIN, DENSE):
        view, _ = _fresh(ex, idx, field, row, n_shards)
        [(key, ent)] = [(k, e) for k, e in cache._entries.items()
                        if k[0] == "row" and k[4] == row]
        ps = ent[1]
        assert isinstance(ps, PagedStack) and ps.n_pages == 3
        lost = ps.pages[1]
        with cache._lock:
            ps.pages[1] = None
            cache._sync_entry_locked(key, ps)
        cache._client.release(encode.page_nbytes(lost))
        d0 = metrics.STACK_FRESH_PAGES.value(source="direct")
        t0 = metrics.STACK_REBUILD_TIMED.value()
        n0 = cache.page_rebuilds
        again, _ = _fresh(ex, idx, field, row, n_shards)
        assert cache.page_rebuilds == n0 + 1
        assert metrics.STACK_FRESH_PAGES.value(source="direct") == d0 + 1
        assert metrics.STACK_REBUILD_TIMED.value() == t0 + 1
        assert again.pages[0] is view.pages[0]
        assert again.pages[2] is view.pages[2]
        block = _truth_block(truth, row, np.arange(32, 64))
        assert encode.page_kind(again.pages[1]) == \
            encode.page_kind(lost) == _same_page(again.pages[1], block)


def test_a_placed_pages_scattered_lanes(impl):
    """The serving mesh hands the builder a page's own lane ids, in
    no run: it reads those shards, in that order."""
    n_shards = 70
    h, idx, field, truth = _deployment("mixed", n_shards)
    ex = _engine(h)
    ids = np.array([64, 3, 41, 8, 9, 69, 0], dtype=np.int32)
    for row in (THIN, ONES):
        _, recipe = _fresh(ex, idx, field, row, n_shards)
        page = recipe.build_page(ids, PAGE_LANES, None)
        _same_page(page, _truth_block(truth, row, ids))


def test_with_the_sparse_format_off_every_page_is_dense(impl, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    h, idx, field, truth = _deployment("codes16", 33)
    ex = _engine(h)
    for row in ROWS:
        view, _ = _fresh(ex, idx, field, row, 33)
        for pi, page in enumerate(view.pages):
            assert not encode.is_encoded(page)
            ids = np.arange(pi * 32, min(pi * 32 + 32, 33))
            assert np.array_equal(np.asarray(page),
                                  _truth_block(truth, row, ids))


def test_a_decode_between_the_reads_changes_no_page(impl):
    """row_source reads the counts before the codes, _decode clears
    them in the other order: whichever a reader sees, it sees one
    form whole.  A fragment decoded after its lane was taken is still
    read from the codes the lane holds."""
    h, idx, field, truth = _deployment("codes8", 4)
    frags = [field.view(VIEW_STANDARD).fragment(s) for s in range(4)]
    lanes = [fr.row_source(THIN) for fr in frags]
    assert all(ln is None or ln[0] == "codes" for ln in lanes)
    frags[0]._decode()
    frags[1]._codes = None            # as a reader meets _decode midway
    assert frags[0].row_source(THIN)[0] == "cols"
    assert frags[1].row_source(THIN) is None   # nothing stored as rows
    page = encode.encode_lanes(lanes, THIN, PAGE_LANES, W // 32)
    _same_page(page, _truth_block(truth, THIN, np.arange(4)))
    after = [fr.row_source(THIN) for fr in (frags[0], *frags[2:])]
    page = encode.encode_lanes([after[0], None, *after[1:]], THIN,
                               PAGE_LANES, W // 32)
    del truth[1]
    _same_page(page, _truth_block(truth, THIN, np.arange(4)))


def test_queries_over_fresh_pages_are_exact(impl):
    """End to end through the executor, packed Count arms included
    (they read lane_counts and host_positions and no device)."""
    n_shards = 33
    h, idx, field, truth = _deployment("mixed", n_shards)
    ex = _engine(h)

    def n(row):
        return sum(int((a == row).sum()) for a in truth.values())

    assert ex.execute("i", f"Count(Row(f={THIN}))")[0] == n(THIN)
    assert ex.execute("i", f"Count(Row(f={DENSE}))")[0] == n(DENSE)
    assert ex.execute("i", f"Count(Row(f={ONES}))")[0] == n(ONES)
    assert ex.execute("i", f"Count(Row(f={EMPTY}))")[0] == 0
    assert ex.execute(
        "i", f"Count(Union(Row(f={THIN}), Row(f={DENSE})))")[0] \
        == n(THIN) + n(DENSE)
    assert ex.execute(
        "i", f"Count(Intersect(Row(f={THIN}), Row(f={ONES})))")[0] == 0
    assert ex.execute(
        "i", f"Count(Union(Row(f={THIN}), Row(f={EMPTY})))")[0] == n(THIN)
