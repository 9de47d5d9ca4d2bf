"""Container-adaptive device format (ISSUE 16): per-page
dense / packed-array / run encoding on the paged TPU stack.

Seeded property coverage through the REAL engine: randomized density
sweeps (1e-5 → 0.9) stay bit-exact vs the all-dense arm on the host,
jit, and mesh paths; interleaved writes exercise the delta-patch of a
packed page (rebuild + re-encode), an encode flip mid-stream (a
filling page re-encoding dense), and a generation retire (bulk
re-import) — plus the PILOSA_TPU_SPARSE_FORMAT=0 kill-switch A/B and
the true-byte ledger accounting the format exists to buy.
"""

from __future__ import annotations

import numpy as np
import pytest

from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.memory import encode
from pilosa_tpu.memory.ledger import Ledger
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import metrics
from pilosa_tpu.ops import bitmap as bm

W = 1 << 15  # small shard width keeps stacks tiny and fast


def _bits_at_density(rng, n_bits: int, density: float) -> np.ndarray:
    n = max(int(n_bits * density), 1)
    return rng.choice(n_bits, size=min(n, n_bits), replace=False)


def _build(density: float, n_shards: int = 4, n_rows: int = 6,
           seed: int = 11) -> Holder:
    h = Holder(width=W)
    idx = h.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(seed)
    space = n_shards * W
    rows, cols = [], []
    for r in range(n_rows):
        c = _bits_at_density(rng, space, density)
        rows.append(np.full(c.size, r, dtype=np.int64))
        cols.append(c)
    f.import_bits(np.concatenate(rows), np.concatenate(cols))
    return h


_QUERIES = [
    "Count(Row(f=0))",
    "Count(Row(f=3))",
    "Count(Union(Row(f=0), Row(f=1)))",
    "Count(Intersect(Row(f=2), Row(f=3)))",
    "Count(Difference(Row(f=4), Row(f=5)))",
    "Row(f=1)",
    "TopN(f, n=4)",
    "TopN(f, Row(f=0), n=4)",
]


def _run_all(ex: Executor) -> list[str]:
    return [repr(ex.execute("i", q)) for q in _QUERIES]


# ---------------------------------------------------------------------------
# encode layer (memory/encode.py)
# ---------------------------------------------------------------------------

def test_encode_block_kinds_and_roundtrip():
    rng = np.random.default_rng(3)
    pl, w = 32, 128
    # packed: sparse random bits
    blk = np.zeros((pl, w), np.uint32)
    flat = blk.reshape(-1)
    pos = rng.choice(pl * w * 32, size=200, replace=False)
    flat[pos // 32] |= np.uint32(1) << (pos % 32).astype(np.uint32)
    enc = encode.encode_block(blk)
    assert enc is not None and enc.kind == "packed"
    assert np.array_equal(np.asarray(enc.expand()), blk)
    assert enc.bit_count() == int(np.bitwise_count(blk).sum())
    assert enc.nbytes < blk.nbytes // 2
    # run: near-saturated words + residuals
    blk = np.full((pl, w), 0xFFFFFFFF, np.uint32)
    blk[5, 17] = 0x0000FF00
    blk[20, 100] = 0
    enc = encode.encode_block(blk)
    assert enc is not None and enc.kind == "run"
    assert np.array_equal(np.asarray(enc.expand()), blk)
    assert np.array_equal(
        np.asarray(enc.lane_counts),
        np.bitwise_count(blk).sum(axis=1, dtype=np.int64))
    # dense: mid-density random words never pay
    blk = rng.integers(0, 1 << 32, size=(pl, w), dtype=np.uint32)
    assert encode.encode_block(blk) is None


def test_encode_hysteresis_and_hint():
    rng = np.random.default_rng(4)
    pl, w = 16, 64
    blk = np.zeros((pl, w), np.uint32)
    flat = blk.reshape(-1)
    # just over the 0.5x entry threshold: stays dense on first sight,
    # but an already-packed page holds its encoding (1.5x leave band)
    n = (pl * w) // 7
    pos = rng.choice(pl * w * 32, size=n * 32 // 6, replace=False)
    flat[pos // 32] |= np.uint32(1) << (pos % 32).astype(np.uint32)
    nbits = int(np.bitwise_count(blk).sum())
    packed_b = 4 * encode._pow2(nbits)
    if packed_b <= blk.nbytes * 0.5:
        pytest.skip("geometry landed under the entry threshold")
    assert encode.encode_block(blk) is None
    if packed_b <= blk.nbytes * 0.75:
        assert encode.encode_block(blk, prev_kind="packed") is not None
    # a clearly-dense stats hint skips the scan entirely for a page
    # that WOULD have encoded
    sparse = np.zeros((pl, w), np.uint32)
    sparse[0, 0] = 1
    assert encode.encode_block(sparse) is not None
    assert encode.encode_block(sparse, density_hint=0.5) is None
    # ...but never overrides hysteresis on an already-sparse page
    assert encode.encode_block(sparse, prev_kind="packed",
                               density_hint=0.5) is not None


def test_encode_kill_switch(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    blk = np.zeros((8, 32), np.uint32)
    blk[0, 0] = 7
    assert not encode.enabled()
    assert encode.encode_block(blk) is None


# ---------------------------------------------------------------------------
# packed kernels (ops/bitmap.py)
# ---------------------------------------------------------------------------

class TestPackedKernels:
    def _packed(self, rng, pl, w, n):
        blk = np.zeros((pl, w), np.uint32)
        flat = blk.reshape(-1)
        pos = np.sort(rng.choice(pl * w * 32, size=n, replace=False))
        # unbuffered |=: several coords land in one word
        np.bitwise_or.at(
            flat, pos // 32,
            np.uint32(1) << (pos % 32).astype(np.uint32))
        coords = np.full(encode._pow2(n), pl * w * 32, dtype=np.uint32)
        coords[:n] = pos
        return blk, coords

    def test_expand_coords(self):
        rng = np.random.default_rng(9)
        for pl, w, n in ((4, 16, 3), (16, 64, 500), (8, 32, 1)):
            blk, coords = self._packed(rng, pl, w, n)
            out = np.asarray(bm.expand_coords(coords, pl, w))
            assert np.array_equal(out, blk)

    def test_expand_runs(self):
        rng = np.random.default_rng(10)
        pl, w = 8, 64
        blk = np.full((pl, w), 0xFFFFFFFF, np.uint32)
        blk[2, 10] = 0x12345678
        blk[7, 63] = 0
        enc = encode.encode_block(blk)
        assert enc.kind == "run"
        out = np.asarray(bm.expand_runs(enc.run_starts, enc.run_lens,
                                        enc.coords, pl, w))
        assert np.array_equal(out, blk)

    def test_packed_counts(self):
        rng = np.random.default_rng(12)
        pl, w, n = 8, 32, 300
        blk, coords = self._packed(rng, pl, w, n)
        total = pl * w * 32
        assert int(bm.packed_count(coords, total)) == n
        seg = np.asarray(bm.packed_segment_count(coords, w * 32, pl))
        assert np.array_equal(
            seg, np.bitwise_count(blk).sum(axis=1).astype(seg.dtype))
        other = rng.integers(0, 1 << 32, size=(pl, w), dtype=np.uint32)
        got = int(bm.packed_intersect_count(
            coords, other.reshape(-1), total))
        assert got == int(np.bitwise_count(blk & other).sum())


# ---------------------------------------------------------------------------
# engine property sweep: bit-exact vs the dense arm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("density",
                         [1e-5, 1e-4, 1e-3, 1e-2, 0.1, 0.5, 0.9])
def test_density_sweep_bit_exact(density, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    want = _run_all(Executor(_build(density)))
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    p0 = metrics.STACK_PAGES.total(event="build", encoding="packed")
    ex = Executor(_build(density))
    got = _run_all(ex)
    assert got == want
    # repeat serves the cached (possibly encoded) pages
    assert _run_all(ex) == want
    if density <= 1e-3:
        # the sparse tail of the sweep must actually ride packed pages
        assert metrics.STACK_PAGES.total(
            event="build", encoding="packed") > p0


def test_host_path_bit_exact(monkeypatch):
    """host_only executors never page (whole numpy stacks) — the
    sweep must agree there too."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    h = _build(1e-3)
    ex = Executor(h)
    host = Executor(h)
    host.stacked.host_only = True
    assert _run_all(host) == _run_all(ex)


def test_mesh_path_bit_exact(monkeypatch):
    """Mesh placements keep whole-array dense stacks (not pageable);
    results must equal the single-device sparse arm."""
    import jax

    from pilosa_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    h = _build(1e-3, n_shards=8)
    want = _run_all(Executor(h))
    ex = Executor(h)
    ex.set_mesh(make_mesh(8, rows=1))
    assert _run_all(ex) == want


# ---------------------------------------------------------------------------
# interleaved writes: patch of a packed page, encode flip, gen retire
# ---------------------------------------------------------------------------

def test_write_to_packed_page_rebuilds_and_stays_exact(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    h = _build(1e-4)
    ex = Executor(h)
    before = ex.execute("i", "Count(Row(f=0))")[0]
    ps = [p for e in ex.stacked.cache._entries.values()
          if hasattr(e[1], "pages") for p in e[1].pages]
    assert any(encode.is_encoded(p) for p in ps)
    e0 = metrics.PAGE_ENCODE.total(reason="patch")
    ex.execute("i", f"Set({2 * W + 5}, f=0)")
    assert ex.execute("i", "Count(Row(f=0))")[0] == before + 1
    # the dirty packed page took the rebuild+re-encode path
    assert metrics.PAGE_ENCODE.total(reason="patch") > e0
    # cross-check against a fresh dense engine over the same holder
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    assert Executor(h).execute("i", "Count(Row(f=0))")[0] == before + 1


def test_encode_flip_mid_stream(monkeypatch):
    """A packed page that fills past the leave threshold re-encodes
    dense on its next write; results stay exact throughout."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    rng = np.random.default_rng(21)
    h = _build(1e-4, n_shards=2)
    idx = h.index("i")
    ex = Executor(h)
    want0 = ex.execute("i", "Count(Row(f=1))")[0]
    d0 = metrics.PAGE_ENCODE.total(to="dense")
    # flood row 1 to ~50% density: far past any packed payoff
    cols = rng.choice(2 * W, size=W, replace=False)
    idx.field("f").import_bits(np.ones(cols.size, np.int64), cols)
    got = ex.execute("i", "Count(Row(f=1))")[0]
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    assert Executor(h).execute("i", "Count(Row(f=1))")[0] == got
    assert got >= want0
    assert metrics.PAGE_ENCODE.total(to="dense") > d0


def test_gen_retire_reencodes(monkeypatch):
    """A structural rewrite (fragment generation retire via bulk
    re-import) rebuilds the entry's pages through the encoder."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    h = _build(1e-4, n_shards=2)
    idx = h.index("i")
    ex = Executor(h)
    ex.execute("i", "Count(Row(f=2))")
    frag = next(iter(
        idx.field("f").views["standard"].fragments.values()))
    gen0 = getattr(frag, "gen", None)
    idx.field("f").clear_row(2) if hasattr(idx.field("f"),
                                           "clear_row") else None
    rng = np.random.default_rng(33)
    cols = rng.choice(2 * W, size=64, replace=False)
    idx.field("f").import_bits(np.full(cols.size, 2, np.int64), cols)
    got = ex.execute("i", "Count(Row(f=2))")[0]
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    assert Executor(h).execute("i", "Count(Row(f=2))")[0] == got
    assert gen0 is None or getattr(frag, "gen", None) is not None


# ---------------------------------------------------------------------------
# accounting: the ledger charges TRUE encoded bytes (the small fix)
# ---------------------------------------------------------------------------

def test_ledger_charges_true_encoded_bytes(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    from pilosa_tpu.executor.stacked import TileStackCache
    h = _build(1e-4)
    ex = Executor(h)
    led = Ledger(budget_bytes=1 << 30)
    cache = ex.stacked.cache = TileStackCache(ledger=led)
    ex.execute("i", "Count(Row(f=0))")
    entries = [e for e in cache._entries.values()
               if hasattr(e[1], "pages")]
    assert entries
    for ent in entries:
        ps = ent[1]
        resident = [p for p in ps.pages if p is not None]
        if not any(encode.is_encoded(p) for p in resident):
            continue
        dense_upper = len(resident) * ps.page_nbytes
        assert ps.resident_bytes() == sum(
            encode.page_nbytes(p) for p in resident)
        assert ps.resident_bytes() < dense_upper
        assert ent[2] == ps.resident_bytes()
    # ledger total matches the accounted entry bytes exactly
    assert led.total_bytes == cache.nbytes


def test_flight_records_page_mix(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    from pilosa_tpu.obs import flight
    h = _build(1e-4)
    ex = Executor(h)
    ex.execute("i", "Count(Union(Row(f=0), Row(f=1)))")
    recs = [r for r in flight.recorder.recent(32)
            if "page_mix" in r and r["page_mix"].get("packed")]
    assert recs, "no flight record carried a packed page mix"


def test_stats_encoding_breakdown(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    from pilosa_tpu.obs import stats
    if not stats.enabled():
        pytest.skip("stats plane disabled")
    h = _build(1e-4)
    Executor(h).execute("i", "Count(Row(f=0))")
    fs = stats.get().field_stats("i", "f")
    assert fs is not None and fs.get("encodings", {}).get("packed")


@pytest.mark.parametrize("density", [0.0, 0.0005, 0.004, 0.015, 0.5])
def test_positions_unpack_only_the_words_that_hold_a_bit(density):
    """encode._positions reads the set bits' flat offsets off the
    nonzero words alone; the same offsets, sorted, as unpacking every
    word gives."""
    r = np.random.default_rng(int(density * 1e4))
    bits = r.random(4 * 4096 * 32) < density
    flat = np.packbits(bits, bitorder="little").view(np.uint32)
    got = encode._positions(flat)
    assert got.dtype == np.uint32
    assert np.array_equal(got, np.flatnonzero(bits))


def test_the_first_packed_page_compiles_every_length_of_its_shape():
    """EncodedPage.expand runs the expand program of every padded
    length a page of its shape can have before the first real one: a
    later page of another density finds its program there."""
    from pilosa_tpu.ops import bitmap as bm
    pl, w = 3, 64                      # a shape no other test expands
    r = np.random.default_rng(5)
    block = np.zeros((pl, w), dtype=np.uint32)
    block[1, 7] = 1 << 9
    page = encode.encode_block(block)
    assert page is not None and page.kind == "packed"
    before = bm._expand_coords_jit._cache_size()
    assert np.array_equal(np.asarray(page.expand()), block)
    lengths = [n for n in (8 << k for k in range(32)) if n <= pl * w]
    assert bm._expand_coords_jit._cache_size() == before + len(lengths)
    bits = r.random(pl * w * 32) < 0.004
    block = np.packbits(bits, bitorder="little").view(np.uint32).reshape(pl, w)
    page = encode.encode_block(block)
    assert page.kind == "packed" and page.coords.size > 8
    assert np.array_equal(np.asarray(page.expand()), block)
    assert bm._expand_coords_jit._cache_size() == before + len(lengths)
