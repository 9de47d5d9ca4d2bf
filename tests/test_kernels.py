"""Pallas kernels vs the jnp reference ops and numpy naive impls.

Runs in interpreter mode on the CPU test mesh (kernels auto-select
interpret off-TPU), mirroring the reference's kernel-vs-naive
cross-checks (roaring/naive.go:309).
"""

import numpy as np
import pytest

from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import bsi
from pilosa_tpu.ops import kernels


def _rand_words(rng, shape, density=0.5):
    words = rng.integers(0, 2**32, size=shape, dtype=np.uint32)
    if density < 0.5:
        words &= rng.integers(0, 2**32, size=shape, dtype=np.uint32)
    return words


class TestGroupbySum:
    """Fused GroupBy kernel vs a naive numpy evaluation."""

    def _data(self, rng, depth=4):
        import itertools
        import jax.numpy as jnp
        S, W = 3, 64
        stacks = [jnp.asarray(rng.integers(
            0, 1 << 32, size=(r, S, W), dtype=np.uint32))
            for r in (4, 2)]
        planes = rng.integers(0, 1 << 32, size=(S, 2 + depth, W),
                              dtype=np.uint32)
        combos = np.array(list(itertools.product(range(4), range(2))),
                          dtype=np.int32)
        return stacks, planes, combos

    def test_matches_naive(self, rng):
        from pilosa_tpu.ops import kernels
        stacks, planes, combos = self._data(rng)
        depth = planes.shape[1] - 2
        counts, nn, pos, neg = kernels.groupby_sum(
            stacks, combos, planes, signed=True)
        for ci, (a, b) in enumerate(combos):
            m = np.asarray(stacks[0])[a] & np.asarray(stacks[1])[b]
            em = m & planes[:, 0]
            p_, g_ = em & ~planes[:, 1], em & planes[:, 1]
            assert int(counts[ci]) == int(np.bitwise_count(m).sum())
            assert int(nn[ci]) == int(np.bitwise_count(em).sum())
            assert [int(x) for x in pos[ci]] == [
                int(np.bitwise_count(p_ & planes[:, 2 + i]).sum())
                for i in range(depth)]
            assert [int(x) for x in neg[ci]] == [
                int(np.bitwise_count(g_ & planes[:, 2 + i]).sum())
                for i in range(depth)]

    def test_counts_only(self, rng):
        from pilosa_tpu.ops import kernels
        stacks, _planes, combos = self._data(rng)
        counts, nn, pos, neg = kernels.groupby_sum(stacks, combos, None)
        assert nn is None and pos is None and neg is None
        a, b = combos[3]
        m = np.asarray(stacks[0])[a] & np.asarray(stacks[1])[b]
        assert int(counts[3]) == int(np.bitwise_count(m).sum())

    def test_engine_groupby_kernel_path_matches_xla(
            self, rng, monkeypatch):
        """Force the kernel path (interpreter on CPU) through the REAL
        engine and compare to the default XLA scan."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models import FieldOptions, FieldType, Holder

        W = 1 << 12
        h = Holder(width=W)
        idx = h.create_index("i")
        idx.create_field("g")
        idx.create_field("d")
        idx.create_field("v", FieldOptions(type=FieldType.INT,
                                           min=-50, max=50))
        cols = list(range(0, 3 * W, 7))
        idx.field("g").import_bits([c % 3 for c in cols], cols)
        idx.field("d").import_bits([c % 2 for c in cols], cols)
        vals = [int(v) for v in rng.integers(-50, 50, size=len(cols))]
        idx.field("v").import_values(cols, vals)
        idx.mark_columns_exist(cols)
        q = "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))"
        ex = Executor(h)
        want = ex.execute("i", q)[0]
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_KERNEL", "1")
        got = Executor(h).execute("i", q)[0]
        as_t = lambda res: [(tuple(g["row_id"] for g in r.group),
                             r.count, r.agg, r.agg_count) for r in res]
        assert as_t(got) == as_t(want)

    def test_engine_groupby_kernel_on_mesh(self, rng, monkeypatch):
        """shard_map kernel path over a REAL 2x4 mesh: every device
        runs the fused kernel on its shard slice, partials psum."""
        import jax

        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models import FieldOptions, FieldType, Holder
        from pilosa_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 8:
            import pytest
            pytest.skip("needs 8 devices")
        W = 1 << 12
        h = Holder(width=W)
        idx = h.create_index("i")
        idx.create_field("g")
        idx.create_field("d")
        idx.create_field("v", FieldOptions(type=FieldType.INT,
                                           min=-50, max=50))
        cols = list(range(0, 5 * W, 7))
        idx.field("g").import_bits([c % 3 for c in cols], cols)
        idx.field("d").import_bits([c % 2 for c in cols], cols)
        vals = [int(v) for v in rng.integers(-50, 50, size=len(cols))]
        idx.field("v").import_values(cols, vals)
        idx.mark_columns_exist(cols)
        q = "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))"
        ex_loop = Executor(h)
        ex_loop.use_stacked = False
        want = ex_loop.execute("i", q)[0]
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_KERNEL", "1")
        ex_mesh = Executor(h)
        ex_mesh.set_mesh(make_mesh(8, rows=2))
        got = ex_mesh.execute("i", q)[0]
        as_t = lambda res: [(tuple(g["row_id"] for g in r.group),
                             r.count, r.agg, r.agg_count) for r in res]
        assert as_t(got) == as_t(want)


class TestGroupByKernelGuardLifts:
    """r04 guard lifts: big combo spaces, big shard fleets, and
    filter trees all keep the kernel path (single device) — chunked
    and masked, results equal to the XLA scan."""

    def _holder(self, rng, W):
        from pilosa_tpu.models import FieldOptions, FieldType, Holder
        h = Holder(width=W)
        idx = h.create_index("i")
        idx.create_field("g")
        idx.create_field("d")
        idx.create_field("flt")
        idx.create_field("v", FieldOptions(type=FieldType.INT,
                                           min=-50, max=50))
        cols = list(range(0, 9 * W, 5))
        idx.field("g").import_bits([c % 5 for c in cols], cols)
        idx.field("d").import_bits([c % 4 for c in cols], cols)
        idx.field("flt").import_bits([c % 2 for c in cols], cols)
        vals = [int(v) for v in rng.integers(-50, 50,
                                             size=len(cols))]
        idx.field("v").import_values(cols, vals)
        idx.mark_columns_exist(cols)
        return h

    def _cmp(self, h, q, monkeypatch):
        from pilosa_tpu.executor import Executor
        monkeypatch.delenv("PILOSA_TPU_GROUPBY_KERNEL",
                           raising=False)
        want = Executor(h).execute("i", q)[0]
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_KERNEL", "1")
        got = Executor(h).execute("i", q)[0]
        as_t = lambda res: [(tuple(g["row_id"] for g in r.group),
                             r.count, r.agg, r.agg_count)
                            for r in res]
        assert as_t(got) == as_t(want)

    def test_filter_tree_stays_on_kernel(self, rng, monkeypatch):
        h = self._holder(rng, 1 << 12)
        self._cmp(h, "GroupBy(Rows(g), Rows(d), filter=Row(flt=1), "
                     "aggregate=Sum(field=v))", monkeypatch)

    def test_combo_chunking_matches(self, rng, monkeypatch):
        import pilosa_tpu.executor.stacked as stacked
        monkeypatch.setattr(
            stacked.StackedEngine, "_GROUPBY_KERNEL_MAX_COMBOS", 3)
        h = self._holder(rng, 1 << 12)
        # 5 x 4 = 20 combos >> the patched 3-combo kernel bound
        self._cmp(h, "GroupBy(Rows(g), Rows(d), "
                     "aggregate=Sum(field=v))", monkeypatch)

    def test_shard_chunking_matches(self, rng, monkeypatch):
        import pilosa_tpu.executor.stacked as stacked
        monkeypatch.setattr(stacked, "_REDUCE_MAX_SHARDS", 2)
        h = self._holder(rng, 1 << 12)  # 9 shards >> patched bound
        self._cmp(h, "GroupBy(Rows(g), Rows(d), "
                     "aggregate=Sum(field=v))", monkeypatch)

    def test_all_lifts_composed(self, rng, monkeypatch):
        import pilosa_tpu.executor.stacked as stacked
        monkeypatch.setattr(
            stacked.StackedEngine, "_GROUPBY_KERNEL_MAX_COMBOS", 4)
        monkeypatch.setattr(stacked, "_REDUCE_MAX_SHARDS", 3)
        h = self._holder(rng, 1 << 12)
        self._cmp(h, "GroupBy(Rows(g), Rows(d), filter=Row(flt=0), "
                     "aggregate=Sum(field=v))", monkeypatch)


class TestGroupbyOnepass:
    """One-pass group-code histogram (ISSUE 1): the Pallas MXU kernel,
    the XLA scatter reference, the native host histogram, and the
    per-combo paths must all be bit-exact on disjoint-row data."""

    def _category_field(self, rng, n_rows, s_dim, width):
        """(rows (R, S, W) uint32, per-column assignment (S, width))
        with each column in at most one row — categorical data."""
        assign = rng.integers(-1, n_rows, size=(s_dim, width))
        rows = np.zeros((n_rows, s_dim, width // 32), np.uint32)
        for s in range(s_dim):
            for r in range(n_rows):
                rows[r, s] = bm.from_columns(
                    np.nonzero(assign[s] == r)[0], width)
        return rows, assign

    @pytest.mark.parametrize("signed,nf_rows,depth", [
        (True, (5, 3), 4),
        (False, (4,), 6),
        (True, (3, 2, 4), 3),
    ])
    def test_kernel_vs_xla_vs_naive(self, rng, signed, nf_rows, depth):
        """groupby_fused (interpret) == groupby_codes_xla == numpy."""
        import jax.numpy as jnp
        s_dim, w = 3, 16
        width = w * 32
        fields = [self._category_field(rng, nr, s_dim, width)
                  for nr in nf_rows]
        lo = -(2 ** depth) + 1 if signed else 0
        vals = rng.integers(lo, 2 ** depth, size=(s_dim, width))
        ex = rng.integers(0, 2, size=(s_dim, width)).astype(bool)
        planes = np.stack([
            bsi.encode(np.nonzero(ex[s])[0], vals[s][ex[s]],
                       depth=depth, width=width) for s in range(s_dim)])
        bits = [max(nr - 1, 0).bit_length() for nr in nf_rows]
        n_codes = 1 << sum(bits)
        cp = np.concatenate(
            [np.asarray(bm.digit_planes(rows))
             for rows, _ in fields]).transpose(1, 0, 2) \
            if sum(bits) else np.zeros((s_dim, 0, w), np.uint32)
        valid = np.full((s_dim, w), 0xFFFFFFFF, np.uint32)
        for rows, _ in fields:
            u = rows[0].copy()
            for r in rows[1:]:
                u |= r
            valid &= u
        args = (jnp.asarray(cp), jnp.asarray(valid),
                jnp.asarray(planes), n_codes, signed)
        c_x, n_x, p_x, g_x = (np.asarray(v)
                              for v in kernels.groupby_codes_xla(*args))
        c_k, n_k, p_k, g_k = (
            np.asarray(v) for v in kernels.groupby_fused(
                *args, digits=tuple(zip(bits, nf_rows))))
        np.testing.assert_array_equal(c_x, c_k)
        np.testing.assert_array_equal(n_x, n_k)
        np.testing.assert_array_equal(p_x, p_k)
        np.testing.assert_array_equal(g_x, g_k)
        # naive per-combo ground truth over the dense code space
        import itertools
        shifts = np.cumsum([0] + bits[:-1])
        for combo in itertools.product(*[range(nr) for nr in nf_rows]):
            code = sum(ci << sh for ci, sh in zip(combo, shifts))
            sel = np.ones((s_dim, width), bool)
            for (rows, assign), ci in zip(fields, combo):
                sel &= assign == ci
            assert c_x[code] == sel.sum()
            sele = sel & ex
            assert n_x[code] == sele.sum()
            vv = vals[sele]
            mag = np.abs(vv)
            for p in range(depth):
                bit = (mag >> p) & 1
                assert p_x[code][p] == int(bit[vv >= 0].sum())
                assert g_x[code][p] == int(bit[vv < 0].sum())

    def _engine(self, rng, W, mutexes=True):
        from pilosa_tpu.models import FieldOptions, FieldType, Holder
        h = Holder(width=W)
        idx = h.create_index("i")
        gtype = FieldType.MUTEX if mutexes else FieldType.SET
        idx.create_field("g", FieldOptions(type=gtype))
        idx.create_field("d", FieldOptions(type=gtype))
        idx.create_field("flt")
        idx.create_field("v", FieldOptions(type=FieldType.INT,
                                           min=-50, max=50))
        idx.create_field("vu", FieldOptions(type=FieldType.INT,
                                            min=0, max=100))
        # step 3 is coprime to the row moduli, so g really has 5 rows
        # and d really has 4 (step 5 would collapse c % 5 to row 0)
        cols = list(range(0, 9 * W, 3))
        idx.field("g").import_bits([c % 5 for c in cols], cols)
        idx.field("d").import_bits([(c // 5) % 4 for c in cols], cols)
        idx.field("flt").import_bits([c % 2 for c in cols], cols)
        idx.field("v").import_values(
            cols, [int(v) for v in rng.integers(-50, 50,
                                                size=len(cols))])
        idx.field("vu").import_values(
            cols, [int(v) for v in rng.integers(0, 100,
                                                size=len(cols))])
        idx.mark_columns_exist(cols)
        return h

    QUERIES = [
        "GroupBy(Rows(g), Rows(d))",
        "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))",
        "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=vu))",
        "GroupBy(Rows(g), Rows(d), filter=Row(flt=1), "
        "aggregate=Sum(field=v))",
        "GroupBy(Rows(g), Rows(d), previous=[2, 1], "
        "aggregate=Sum(field=v))",
        "GroupBy(Rows(g), aggregate=Sum(field=v))",
    ]

    @staticmethod
    def _as_t(res):
        return [(tuple(g["row_id"] for g in r.group), r.count, r.agg,
                 r.agg_count) for r in res]

    def test_engine_three_way_bit_exact(self, rng, monkeypatch):
        """Acceptance property: one-pass == per-combo kernel == host
        loop through the REAL engine, across signed/unsigned BSI,
        filters, paging, counts-only."""
        from pilosa_tpu.executor import Executor
        h = self._engine(rng, 1 << 12)
        for q in self.QUERIES:
            monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS", "1")
            one = Executor(h).execute("i", q)[0]
            monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS", "0")
            monkeypatch.setenv("PILOSA_TPU_GROUPBY_KERNEL", "1")
            combo = Executor(h).execute("i", q)[0]
            monkeypatch.delenv("PILOSA_TPU_GROUPBY_KERNEL")
            ex_loop = Executor(h)
            ex_loop.use_stacked = False
            loop = ex_loop.execute("i", q)[0]
            monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS")
            assert self._as_t(one) == self._as_t(loop), q
            assert self._as_t(combo) == self._as_t(loop), q

    def test_engine_onepass_mesh(self, rng, monkeypatch):
        """Multi-shard mesh: the shard_map/psum one-pass wrapper over
        a REAL 2x4 device mesh equals the host loop."""
        import jax

        from pilosa_tpu.executor import Executor
        from pilosa_tpu.parallel.mesh import make_mesh
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        h = self._engine(rng, 1 << 12)
        for q in self.QUERIES:
            ex_loop = Executor(h)
            ex_loop.use_stacked = False
            want = ex_loop.execute("i", q)[0]
            monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS", "1")
            ex_mesh = Executor(h)
            ex_mesh.set_mesh(make_mesh(8, rows=2))
            got = ex_mesh.execute("i", q)[0]
            monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS")
            assert self._as_t(got) == self._as_t(want), q

    def test_overlapping_rows_fall_back(self, rng, monkeypatch):
        """A column in TWO rows of one field belongs to two combos —
        inexpressible as a digit, so the disjointness gate must refuse
        one-pass even when forced, and results stay correct."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.obs.metrics import GROUPBY_ONEPASS
        W = 1 << 12
        h = self._engine(rng, W, mutexes=False)
        idx = h.index("i")
        # overlap: every 10th column joins g row 0 AND g row 1
        extra = list(range(0, 9 * W, 10))
        idx.field("g").import_bits([0] * len(extra), extra)
        idx.field("g").import_bits([1] * len(extra), extra)
        q = "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))"
        before = GROUPBY_ONEPASS.total()
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS", "1")
        got = Executor(h).execute("i", q)[0]
        monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS")
        assert GROUPBY_ONEPASS.total() == before  # fell back
        ex_loop = Executor(h)
        ex_loop.use_stacked = False
        assert self._as_t(got) == self._as_t(ex_loop.execute("i", q)[0])

    def test_sparse_combo_selection_stays_per_combo(self, rng,
                                                    monkeypatch):
        """Cost model: a paged tail of 2 combos out of 20 is cheaper
        per-combo than a full-space histogram — one-pass must not
        claim it (but must still be forceable)."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.obs.metrics import GROUPBY_ONEPASS
        h = self._engine(rng, 1 << 12)
        q = "GroupBy(Rows(g), Rows(d), previous=[4, 1])"  # tail: 2
        before = GROUPBY_ONEPASS.total()
        got = Executor(h).execute("i", q)[0]
        assert GROUPBY_ONEPASS.total() == before
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS", "1")
        forced = Executor(h).execute("i", q)[0]
        monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS")
        assert GROUPBY_ONEPASS.total() == before + 1
        assert self._as_t(got) == self._as_t(forced)

    def test_numpy_fallback_histogram(self, rng, monkeypatch):
        """Host path without a toolchain (bincount fallback) matches
        the host loop."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.storage import native_ingest as ni
        monkeypatch.setattr(ni, "_lib", None)
        monkeypatch.setattr(ni, "_lib_failed", True)
        h = self._engine(rng, 1 << 12)
        q = "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))"
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS", "1")
        got = Executor(h).execute("i", q)[0]
        monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS")
        ex_loop = Executor(h)
        ex_loop.use_stacked = False
        assert self._as_t(got) == self._as_t(ex_loop.execute("i", q)[0])

    def test_digit_planes_roundtrip(self, rng):
        """bitmap.digit_planes / code_from_planes invert each other on
        disjoint rows."""
        width = 1 << 9
        rows, assign = self._category_field(rng, 6, 2, width)
        dp = bm.digit_planes(rows)         # numpy in, numpy out
        assert isinstance(dp, np.ndarray) and dp.shape[0] == 3
        code = bm.code_from_planes_np(dp[:, 0])
        member = assign[0] >= 0
        np.testing.assert_array_equal(code[member], assign[0][member])
