"""Fused single-pass GroupBy kernel family (ISSUE 11) — property
suite pinning the int8 MXU popcount-accumulate kernel bit-exact
against the XLA scatter reference and the numpy host twins, across
signed BSI edge cases (negative sums, extreme magnitudes, all-invalid
groups, empty combos), plus the Min/Max presence-walk table, the
value-histogram Range/Distinct byproduct, and the serving/ragged
batched path.  Everything runs under Pallas interpret mode on the CPU
test mesh, so tier-1 exercises the kernel without TPU hardware.
"""

import numpy as np
import pytest

from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import bsi
from pilosa_tpu.ops import kernels


def _category_field(rng, n_rows, s_dim, width):
    """(rows (R, S, W) uint32, per-column assignment (S, width)) with
    each column in at most one row — categorical (disjoint) data."""
    assign = rng.integers(-1, n_rows, size=(s_dim, width))
    rows = np.zeros((n_rows, s_dim, width // 32), np.uint32)
    for s in range(s_dim):
        for r in range(n_rows):
            rows[r, s] = bm.from_columns(
                np.nonzero(assign[s] == r)[0], width)
    return rows, assign


def _fixture(rng, nf_rows, depth, s_dim=3, w=16, signed=True,
             all_invalid=False, extreme=False):
    """Random group-code stack + BSI planes + the naive per-column
    ground truth arrays."""
    import jax.numpy as jnp
    width = w * 32
    fields = [_category_field(rng, nr, s_dim, width) for nr in nf_rows]
    lo = -(2 ** depth) + 1 if signed else 0
    vals = rng.integers(lo, 2 ** depth, size=(s_dim, width))
    if extreme:
        # saturate magnitudes at the depth bound (all-ones planes)
        ext = rng.integers(0, 2, size=(s_dim, width)).astype(bool)
        vals[ext] = np.where(rng.integers(0, 2, size=int(ext.sum())),
                             2 ** depth - 1,
                             lo if signed else 0)
    ex = rng.integers(0, 2, size=(s_dim, width)).astype(bool)
    planes = np.stack([
        bsi.encode(np.nonzero(ex[s])[0], vals[s][ex[s]],
                   depth=depth, width=width) for s in range(s_dim)])
    bits = [max(nr - 1, 0).bit_length() for nr in nf_rows]
    n_codes = 1 << sum(bits)
    cp = np.concatenate(
        [np.asarray(bm.digit_planes(rows)) for rows, _ in fields]
    ).transpose(1, 0, 2) if sum(bits) else \
        np.zeros((s_dim, 0, w), np.uint32)
    if all_invalid:
        valid = np.zeros((s_dim, w), np.uint32)
    else:
        valid = np.full((s_dim, w), 0xFFFFFFFF, np.uint32)
        for rows, _ in fields:
            u = rows[0].copy()
            for r in rows[1:]:
                u |= r
            valid &= u
    args = (jnp.asarray(cp), jnp.asarray(valid), jnp.asarray(planes),
            n_codes, signed)
    return args, fields, vals, ex, bits, width


def _digits(nf_rows):
    """((bits, rows), ...): the layout stacked._code_digits hands the
    fused kernel, for the fixture's fields."""
    return tuple((max(nr - 1, 0).bit_length(), nr) for nr in nf_rows)


def _filtered(rng, args):
    """The fixture's args with a random filter ANDed into `valid`."""
    import jax.numpy as jnp
    cp, valid, planes, n_codes, signed = args
    filt = rng.integers(0, 2**32, size=valid.shape, dtype=np.uint32)
    return (cp, jnp.asarray(np.asarray(valid) & filt), planes, n_codes,
            signed)


class TestFusedKernelBitExact:
    """groupby_fused == groupby_codes_xla == numpy host twin, over
    randomized trials + named edge cases."""

    # the packed body with the fields' digit layout handed over
    # (ISSUE 32): (nf_rows, depth, signed, s_dim, w, filtered,
    #              block_vregs or None)
    PACKED_CASES = [
        ((6, 2, 5), 7, False, 2, 16, False, None),     # the able query
        ((6, 2, 5, 4), 3, True, 1, 16, True, None),    # 240 groups
        ((6, 2, 5), 0, True, 2, 16, False, None),      # count-only
        ((1, 5), 4, True, 2, 16, False, None),         # a field of one row
        ((1,), 3, False, 3, 16, True, None),           # cb == 0
        ((3, 2), 5, True, 1, 40, True, None),          # one shard
        ((5, 3), 4, True, 2, 2100, False, 1),          # W not a multiple
        ((6, 2, 5), 7, False, 1, 1030, True, 1),       # of the block
        # blocks of 8 vregs (two chunks of 4 to an operation) and of 2
        ((6, 2, 5), 0, False, 1, 8192, True, None),
        ((5, 3), 2, True, 1, 2048, False, None),
    ]

    @pytest.mark.parametrize("case", PACKED_CASES)
    def test_packed_vs_reference(self, rng, monkeypatch, case):
        nf_rows, depth, signed, s_dim, w, filtered, block = case
        if block is not None:
            monkeypatch.setattr(kernels, "_PACKED_BLOCK_VREGS", block)
        args, *_ = _fixture(rng, nf_rows, max(depth, 1), s_dim=s_dim,
                            w=w, signed=signed)
        if filtered:
            args = _filtered(rng, args)
        if depth == 0:
            args = args[:2] + (None,) + args[3:]
        digits = _digits(nf_rows)
        assert kernels.fused_body(digits, depth, signed) == "packed"
        ref = kernels.groupby_codes_xla(*args)
        fused = kernels.groupby_fused(*args, digits=digits)
        for r, f in zip(ref, fused):
            if r is None:
                assert f is None
            else:
                np.testing.assert_array_equal(np.asarray(r),
                                              np.asarray(f))
        if depth and not signed:
            assert not np.asarray(fused[3]).any()
        # with no layout handed over every code is live: same table
        dense = kernels.groupby_fused(*args)
        np.testing.assert_array_equal(np.asarray(dense[0]),
                                      np.asarray(ref[0]))

    def test_onehot_body_still_serves(self, rng, monkeypatch):
        """Shapes whose accumulators do not fit take the one-hot body
        (here by a budget of nothing) and answer the same."""
        monkeypatch.setattr(kernels, "_PACKED_VMEM_BYTES", 0)
        args, *_ = _fixture(rng, (6, 2, 5), 4, s_dim=2)
        digits = _digits((6, 2, 5))
        assert kernels.fused_body(digits, 4, True, True) == "onehot"
        ref = kernels.groupby_codes_xla(*args, minmax=True)
        fused = kernels.groupby_fused(*args, minmax=True, digits=digits)
        for r, f in zip(ref, fused):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(f))

    def test_body_by_shape(self):
        """The choice of body from the static arguments: the able
        forms and the 240-group form packed; the kernel's bound
        shapes, a 12-bit value histogram and every Min/Max (which the
        packed body does not compute) one-hot."""
        able = _digits((6, 2, 5))
        for depth, signed in ((0, False), (7, False), (7, True)):
            assert kernels.fused_body(able, depth, signed) == "packed"
        for signed in (False, True):
            assert kernels.fused_body(able, 7, signed, True) == "onehot"
        assert kernels.fused_body(_digits((6, 2, 5, 4)), 7,
                                  False) == "packed"
        bound = ((1, 2),) * 12
        assert kernels.fused_body(bound, 16, True) == "onehot"
        assert kernels.fused_body(bound, 16, True, True) == "onehot"
        assert kernels.fused_body(((1, 2),) * 13, 0) == "onehot"
        # a value histogram's codes as two fields (dense_digits): 4,096
        # codes are 64 upper masks x 64 rows, one walk
        assert kernels.dense_digits(12) == ((6, 64), (6, 64))
        assert kernels.dense_digits(7) == ((4, 16), (3, 8))
        assert kernels.dense_digits(1) == ((1, 2),)
        assert kernels.dense_digits(0) == ((0, 1),)
        assert kernels.fused_plan(kernels.dense_digits(12), 0) \
            == ("packed", 1)

    CASES = [
        # (nf_rows, depth, signed, all_invalid, extreme)
        ((5, 3), 4, True, False, False),
        ((4,), 6, False, False, False),
        ((3, 2, 4), 3, True, False, False),
        ((5, 3), 4, True, True, False),       # all-invalid groups
        ((6,), 7, True, False, True),         # extreme magnitudes
        ((2, 2), 1, True, False, False),      # depth-1 negative sums
    ]

    @pytest.mark.parametrize("case", CASES)
    def test_fused_vs_references(self, rng, case):
        nf_rows, depth, signed, all_invalid, extreme = case
        args, *_ = _fixture(rng, nf_rows, depth, signed=signed,
                            all_invalid=all_invalid, extreme=extreme)
        ref = [np.asarray(v) for v in kernels.groupby_codes_xla(*args)]
        fused = [np.asarray(v) for v in kernels.groupby_fused(*args)]
        for r, f in zip(ref, fused):
            np.testing.assert_array_equal(r, f)

    @pytest.mark.parametrize("trial", range(4))
    def test_randomized_property(self, rng, trial):
        """Random shapes/depths/signedness; fused == XLA == numpy
        twin (the native_ingest numpy fallback histogram)."""
        from pilosa_tpu.storage import native_ingest as ni
        nf = int(rng.integers(1, 4))
        nf_rows = tuple(int(rng.integers(1, 7)) for _ in range(nf))
        depth = int(rng.integers(1, 9))
        signed = bool(rng.integers(0, 2))
        args, *_ = _fixture(rng, nf_rows, depth, signed=signed,
                            w=int(rng.integers(1, 4)) * 8)
        cp, valid, planes, n_codes, _ = args
        ref = [np.asarray(v)
               for v in kernels.groupby_codes_xla(*args)]
        fused = [np.asarray(v) for v in kernels.groupby_fused(*args)]
        for r, f in zip(ref, fused):
            np.testing.assert_array_equal(r, f)
        # numpy host twin, shard by shard
        c = np.zeros(n_codes, np.int64)
        n_ = np.zeros(n_codes, np.int64)
        p_ = np.zeros((n_codes, depth), np.int64)
        g_ = np.zeros((n_codes, depth), np.int64)
        cp_np, va_np, pl_np = (np.asarray(cp), np.asarray(valid),
                               np.asarray(planes))
        prev = (ni._lib, ni._lib_failed)
        ni._lib, ni._lib_failed = None, True
        try:
            for s in range(cp_np.shape[0]):
                # numpy fallback forced so the twin itself is covered
                ni.groupcode_hist(cp_np[s], va_np[s], pl_np[s],
                                  n_codes, signed, c, n_, p_, g_)
        finally:
            ni._lib, ni._lib_failed = prev
        np.testing.assert_array_equal(ref[0], c)
        np.testing.assert_array_equal(ref[1], n_)
        np.testing.assert_array_equal(ref[2], p_)
        np.testing.assert_array_equal(ref[3], g_)

    def test_empty_combo_space(self, rng):
        """Single-row fields (cb == 0 code planes) still histogram —
        the whole index is combo 0."""
        args, *_ = _fixture(rng, (1,), 3)
        ref = [np.asarray(v) for v in kernels.groupby_codes_xla(*args)]
        fused = [np.asarray(v) for v in kernels.groupby_fused(*args)]
        for r, f in zip(ref, fused):
            np.testing.assert_array_equal(r, f)

    def test_counts_only(self, rng):
        """No BSI planes: the (1, G) counts table alone."""
        import jax.numpy as jnp
        args, *_ = _fixture(rng, (4, 3), 2)
        cp, valid = args[0], args[1]
        n_codes = args[3]
        cx = np.asarray(kernels.groupby_codes_xla(
            cp, jnp.asarray(valid), None, n_codes)[0])
        cf = np.asarray(kernels.groupby_fused(
            cp, jnp.asarray(valid), None, n_codes)[0])
        np.testing.assert_array_equal(cx, cf)


class TestFusedMinMax:
    """The per-group Min/Max plane-presence walk vs the scatter
    reference, the numpy twin, and naive ground truth."""

    @pytest.mark.parametrize("signed,depth", [(True, 4), (False, 5),
                                              (True, 1)])
    def test_table_three_way(self, rng, signed, depth):
        from pilosa_tpu.storage import native_ingest as ni
        nf_rows = (4, 3)
        args, fields, vals, ex, bits, width = _fixture(
            rng, nf_rows, depth, signed=signed)
        ref = kernels.groupby_codes_xla(*args, minmax=True)
        fused = kernels.groupby_fused(*args, minmax=True)
        np.testing.assert_array_equal(np.asarray(ref[4]),
                                      np.asarray(fused[4]))
        # numpy twin
        cp, valid, planes, n_codes, _ = args
        big = 1 << depth
        mm = np.stack([np.full(n_codes, -1, np.int64),
                       np.full(n_codes, big, np.int64),
                       np.full(n_codes, -1, np.int64),
                       np.full(n_codes, big, np.int64)])
        for s in range(np.asarray(cp).shape[0]):
            ni.groupcode_minmax(np.asarray(cp)[s], np.asarray(valid)[s],
                                np.asarray(planes)[s], n_codes, signed,
                                mm)
        np.testing.assert_array_equal(np.asarray(ref[4]), mm)
        # naive per-combo ground truth through minmax_from_table
        import itertools
        vmax, hasmax = kernels.minmax_from_table(mm, depth, "max")
        vmin, hasmin = kernels.minmax_from_table(mm, depth, "min")
        shifts = np.cumsum([0] + bits[:-1])
        s_dim = np.asarray(cp).shape[0]
        for combo in itertools.product(*[range(n) for n in nf_rows]):
            code = sum(ci << sh for ci, sh in zip(combo, shifts))
            sel = np.ones((s_dim, width), bool)
            for (rows, assign), ci in zip(fields, combo):
                sel &= assign == ci
            vv = vals[sel & ex]
            if len(vv):
                assert hasmax[code] and hasmin[code]
                assert vmax[code] == vv.max()
                assert vmin[code] == vv.min()
            else:
                assert not hasmax[code] and not hasmin[code]


    # With a digit layout handed over (row counts that are no powers
    # of two), as the executor hands one to every GroupBy; Min/Max
    # takes the one-hot body whatever the layout.
    # (nf_rows, depth, signed, s_dim, w, mode): mode "filter" ANDs a
    # random filter into valid, "empty" keeps only the first 40
    # columns of each shard valid (most groups and sides stay empty),
    # "invalid" none at all
    LAYOUT_CASES = [
        ((6, 2, 5), 7, False, 2, 16, "filter"),
        ((6, 2, 5), 7, True, 2, 16, "filter"),
        ((6, 2, 5, 4), 3, False, 1, 16, "empty"),
        ((4, 3), 5, True, 2, 16, "empty"),
        ((1, 5), 1, True, 2, 16, "filter"),
        ((5, 3), 4, False, 1, 16, "invalid"),
        ((3, 2), 6, True, 3, 70, "filter"),
    ]

    @pytest.mark.parametrize("case", LAYOUT_CASES)
    def test_layout_table_vs_reference(self, rng, case):
        import jax.numpy as jnp
        nf_rows, depth, signed, s_dim, w, mode = case
        args, *_ = _fixture(rng, nf_rows, depth, s_dim=s_dim, w=w,
                            signed=signed, all_invalid=mode == "invalid")
        if mode == "filter":
            args = _filtered(rng, args)
        elif mode == "empty":
            keep = np.zeros((s_dim, w), np.uint32)
            keep[:, 0] = 0xFFFFFFFF
            keep[:, 1] = 0xFF
            args = (args[0], jnp.asarray(np.asarray(args[1]) & keep)
                    ) + args[2:]
        digits = _digits(nf_rows)
        assert kernels.fused_body(digits, depth, signed, True) == "onehot"
        ref = kernels.groupby_codes_xla(*args, minmax=True)
        fused = kernels.groupby_fused(*args, minmax=True, digits=digits)
        for r, f in zip(ref, fused):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(f))
        mm = np.asarray(fused[4])
        big = 1 << depth
        if mode != "filter":
            # empty sides carry the identities
            assert (mm[0] == -1).any() and (mm[1] == big).any()
        if not signed or mode == "invalid":
            assert (mm[2] == -1).all() and (mm[3] == big).all()


class TestValueHistByproduct:
    """Range/Distinct/MinMax out of the fused value histogram."""

    @pytest.mark.parametrize("depth,s_dim,w,block", [
        (7, 2, 16, None), (3, 1, 40, None), (5, 2, 1030, 1)])
    def test_hist_packed_every_code_live(self, rng, monkeypatch, depth,
                                         s_dim, w, block):
        """bsi_value_hist hands no layout over: every one of the
        2^(depth+1) codes is live in the packed body."""
        import jax.numpy as jnp
        if block is not None:
            monkeypatch.setattr(kernels, "_PACKED_BLOCK_VREGS", block)
        assert kernels.fused_body(kernels.dense_digits(depth + 1),
                                  0) == "packed"
        planes = jnp.asarray(rng.integers(
            0, 2**32, size=(s_dim, 2 + depth, w), dtype=np.uint32))
        filt = jnp.asarray(rng.integers(0, 2**32, size=(s_dim, w),
                                        dtype=np.uint32))
        pos, neg = kernels.bsi_value_hist(planes, filt)
        posr, negr = kernels.bsi_value_hist(planes, filt,
                                            use_kernel=False)
        np.testing.assert_array_equal(np.asarray(pos), np.asarray(posr))
        np.testing.assert_array_equal(np.asarray(neg), np.asarray(negr))
        assert int(np.asarray(pos).sum() + np.asarray(neg).sum()) == int(
            np.bitwise_count(np.asarray(planes[:, 0])
                             & np.asarray(filt)).sum())

    @pytest.mark.parametrize("depth,filtered", [(4, False), (6, True),
                                                (1, False)])
    def test_hist_vs_decode(self, rng, depth, filtered):
        import jax.numpy as jnp
        s_dim, w = 2, 16
        width = w * 32
        vals = rng.integers(-(2**depth) + 1, 2**depth,
                            size=(s_dim, width))
        ex = rng.integers(0, 2, size=(s_dim, width)).astype(bool)
        planes = np.stack([
            bsi.encode(np.nonzero(ex[s])[0], vals[s][ex[s]],
                       depth=depth, width=width)
            for s in range(s_dim)])
        filt = (rng.integers(0, 2**32, size=(s_dim, w),
                             dtype=np.uint32) if filtered else None)
        fj = jnp.asarray(filt) if filt is not None else None
        pos, neg = kernels.bsi_value_hist(jnp.asarray(planes), fj)
        posr, negr = kernels.bsi_value_hist(jnp.asarray(planes), fj,
                                            use_kernel=False)
        np.testing.assert_array_equal(np.asarray(pos),
                                      np.asarray(posr))
        np.testing.assert_array_equal(np.asarray(neg),
                                      np.asarray(negr))
        sel = ex.copy()
        if filt is not None:
            fbits = np.stack([
                np.asarray(bsi.unpack_bits_np(filt[s]))
                for s in range(s_dim)])
            sel &= fbits
        vv = vals[sel]
        for v in range(2 ** depth):
            assert int(pos[v]) == int((vv == v).sum())
            want_neg = int((vv == -v).sum()) if v > 0 else 0
            assert int(neg[v]) == want_neg
        assert kernels.distinct_from_hist(pos, neg) == \
            sorted(set(vv.tolist()))
        lo, hi = int(vals.min()) + 1, int(vals.max()) - 1
        assert kernels.range_count_from_hist(pos, neg, lo, hi) == \
            int(((vv >= lo) & (vv <= hi)).sum())


def _engine(rng, W, signed=True):
    from pilosa_tpu.models import FieldOptions, FieldType, Holder
    h = Holder(width=W)
    idx = h.create_index("i")
    idx.create_field("g", FieldOptions(type=FieldType.MUTEX))
    idx.create_field("d", FieldOptions(type=FieldType.MUTEX))
    idx.create_field("flt")
    lo = -50 if signed else 0
    idx.create_field("v", FieldOptions(type=FieldType.INT,
                                       min=lo, max=50))
    cols = list(range(0, 9 * W, 3))
    idx.field("g").import_bits([c % 5 for c in cols], cols)
    idx.field("d").import_bits([(c // 5) % 4 for c in cols], cols)
    idx.field("flt").import_bits([c % 2 for c in cols], cols)
    idx.field("v").import_values(
        cols, [int(v) for v in rng.integers(lo, 50, size=len(cols))])
    idx.mark_columns_exist(cols)
    return h


def _as_t(res):
    return [(tuple(g["row_id"] for g in r.group), r.count, r.agg,
             r.agg_count) for r in res]


QUERIES = [
    "GroupBy(Rows(g), Rows(d))",
    "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))",
    "GroupBy(Rows(g), Rows(d), filter=Row(flt=1), "
    "aggregate=Sum(field=v))",
    "GroupBy(Rows(g), aggregate=Min(field=v))",
    "GroupBy(Rows(g), Rows(d), aggregate=Max(field=v))",
    "GroupBy(Rows(g), Rows(d), filter=Row(flt=0), "
    "aggregate=Min(field=v))",
]


# (backend, n_codes, depth, mesh Min/Max, PILOSA_TPU_GROUPBY_ONEPASS_ARM)
# -> (arm, host histogram on one device): stacked._onepass_arm's whole
# table.  The variable stands in for the backend and lifts no bound; a
# name it no longer knows ("onehot") is as good as unset.
ARM_TABLE = [
    ("tpu", 64, 7, False, None, "fused", False),
    ("cpu", 64, 7, False, None, "xla", True),
    ("tpu", 8192, 7, False, None, "xla", False),       # taxi-1b's Q4
    ("tpu", 64, 17, False, None, "xla", False),
    ("tpu", 64, 7, True, None, "xla", False),
    ("cpu", 64, 7, False, "fused", "fused", False),
    ("tpu", 64, 7, False, "xla", "xla", False),
    ("cpu", 8192, 7, False, "fused", "xla", False),
    ("cpu", 64, 7, True, "fused", "xla", False),
    ("tpu", 64, 7, False, "onehot", "fused", False),
    ("cpu", 64, 7, False, "onehot", "xla", True),
]


@pytest.mark.parametrize("case", ARM_TABLE)
def test_onepass_arm_table(monkeypatch, case):
    import types

    from pilosa_tpu.executor import stacked
    backend, n_codes, depth, mesh_minmax, forced, arm, host = case
    monkeypatch.setattr(stacked.jax, "default_backend", lambda: backend)
    if forced is None:
        monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", raising=False)
    else:
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", forced)
    assert stacked._onepass_arm(n_codes, depth,
                                mesh_minmax=mesh_minmax) == arm
    eng = types.SimpleNamespace(host_only=False)
    assert stacked.StackedEngine._onepass_host(eng, False) is host


class TestEngineFusedArm:
    """The fused arm forced through the REAL engine (interpret mode)
    == the host loop, across Sum/Min/Max/filters/signedness."""

    @pytest.mark.parametrize("signed", [True, False])
    def test_engine_bit_exact(self, rng, monkeypatch, signed):
        from pilosa_tpu.executor import Executor
        h = _engine(rng, 1 << 12, signed=signed)
        for q in QUERIES:
            monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM",
                               "fused")
            got = Executor(h).execute("i", q)[0]
            monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM")
            ex_loop = Executor(h)
            ex_loop.use_stacked = False
            want = ex_loop.execute("i", q)[0]
            assert _as_t(got) == _as_t(want), q

    def test_fused_metric_counts(self, rng, monkeypatch):
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.obs.metrics import GROUPBY_FUSED
        h = _engine(rng, 1 << 12)
        before = GROUPBY_FUSED.total()
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
        Executor(h).execute(
            "i", "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))")
        assert GROUPBY_FUSED.total() > before

    def test_body_label_by_shape(self, rng, monkeypatch):
        """pilosa_groupby_fused_total{body=}: the able shapes (rows 6,
        2, 5; an unsigned 7-bit int) dispatch the packed body, the
        kernel's bound shapes (4,096 codes, depth 16) and Min/Max the
        one-hot body — and all answer like the host loop."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models import FieldOptions, FieldType, Holder
        from pilosa_tpu.obs.metrics import GROUPBY_FUSED
        W = 1 << 12
        h = Holder(width=W)
        idx = h.create_index("i")
        for name in ("edu", "gen", "dom", "p", "q"):
            idx.create_field(name, FieldOptions(type=FieldType.MUTEX))
        idx.create_field("age", FieldOptions(type=FieldType.INT,
                                             min=0, max=127))
        idx.create_field("wide", FieldOptions(type=FieldType.INT,
                                              min=-65535, max=65535))
        cols = list(range(0, 2 * W, 3))
        for name, rows in (("edu", 6), ("gen", 2), ("dom", 5),
                           ("p", 64), ("q", 64)):
            idx.field(name).import_bits(
                [int(r) for r in rng.integers(0, rows, size=len(cols))],
                cols)
        idx.field("age").import_values(
            cols, [int(v) for v in rng.integers(0, 128, size=len(cols))])
        idx.field("wide").import_values(
            cols, [int(v) for v in rng.integers(-65535, 65536,
                                                size=len(cols))])
        idx.mark_columns_exist(cols)
        assert idx.field("wide").bit_depth == 16
        ex_loop = Executor(h)
        ex_loop.use_stacked = False
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS", "1")
        for q, body in (
                ("GroupBy(Rows(edu), Rows(gen), Rows(dom), "
                 "aggregate=Sum(field=age))", "packed"),
                ("GroupBy(Rows(edu), Rows(gen), Rows(dom))", "packed"),
                ("GroupBy(Rows(edu), Rows(gen), Rows(dom), "
                 "aggregate=Max(field=age))", "onehot"),
                ("GroupBy(Rows(p), Rows(q), aggregate=Sum(field=wide))",
                 "onehot")):
            was = {b: GROUPBY_FUSED.value(path="onepass", body=b)
                   for b in ("packed", "onehot")}
            got = Executor(h).execute("i", q)[0]
            moved = {b: GROUPBY_FUSED.value(path="onepass", body=b) - v
                     for b, v in was.items()}
            other = "onehot" if body == "packed" else "packed"
            assert moved == {body: 1, other: 0}, (q, moved)
            assert _as_t(got) == _as_t(ex_loop.execute("i", q)[0]), q

    def test_minmax_falls_back_on_overlap(self, rng, monkeypatch):
        """Overlapping rows refuse the one-pass gate; Min/Max must
        still answer correctly via the host loop."""
        from pilosa_tpu.models import FieldOptions, FieldType, Holder
        from pilosa_tpu.executor import Executor
        W = 1 << 12
        h = Holder(width=W)
        idx = h.create_index("i")
        idx.create_field("g")          # SET field — overlap allowed
        idx.create_field("v", FieldOptions(type=FieldType.INT,
                                           min=-50, max=50))
        cols = list(range(0, 3 * W, 5))
        idx.field("g").import_bits([c % 3 for c in cols], cols)
        extra = cols[::4]
        idx.field("g").import_bits([(c % 3 + 1) % 3 for c in extra],
                                   extra)
        idx.field("v").import_values(
            cols, [int(v) for v in rng.integers(-50, 50,
                                                size=len(cols))])
        idx.mark_columns_exist(cols)
        q = "GroupBy(Rows(g), aggregate=Max(field=v))"
        got = Executor(h).execute("i", q)[0]
        ex_loop = Executor(h)
        ex_loop.use_stacked = False
        assert _as_t(got) == _as_t(ex_loop.execute("i", q)[0])

    def test_minmax_distinct_queries_fused(self, rng, monkeypatch):
        """Min/Max/Distinct standalone queries ride the value-hist
        byproduct (fused arm forced) and equal the shard loop."""
        from pilosa_tpu.executor import Executor
        h = _engine(rng, 1 << 12)
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
        ex = Executor(h)
        ex_loop = Executor(h)
        ex_loop.use_stacked = False
        for q in ("Min(field=v)", "Max(field=v)",
                  "Min(Row(flt=1), field=v)"):
            got, want = ex.execute("i", q)[0], \
                ex_loop.execute("i", q)[0]
            assert (got.value, got.count) == (want.value, want.count)
        gd = ex.execute("i", "Distinct(field=v)")[0]
        wd = ex_loop.execute("i", "Distinct(field=v)")[0]
        assert gd.values == wd.values


class TestBatchedGroupBy:
    """GroupBy riders inside the fused serving batch (the ragged
    "gb_hist" subplan) — bit-exact vs solo, served by the one fused
    program."""

    def test_batched_vs_solo(self, rng):
        import threading

        from pilosa_tpu.executor import Executor
        from pilosa_tpu.obs import metrics
        h = _engine(rng, 1 << 12)
        qs = ["GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))",
              "GroupBy(Rows(g), Rows(d))",
              "GroupBy(Rows(g), Rows(d), filter=Row(flt=1), "
              "aggregate=Sum(field=v))",
              "Count(Intersect(Row(g=1), Row(d=1)))"]
        solo = [Executor(h).execute("i", q) for q in qs]
        ex = Executor(h)
        ex.enable_serving(window_s=0.02, max_batch=16)
        d0 = metrics.SERVING_DISPATCH.total(kind="ragged")
        results = [None] * 8

        def worker(k):
            results[k] = ex.execute_serving("i", qs[k % len(qs)])

        ts = [threading.Thread(target=worker, args=(k,))
              for k in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for k in range(8):
            got, want = results[k], solo[k % len(qs)]
            if qs[k % len(qs)].startswith("GroupBy"):
                assert _as_t(got[0]) == _as_t(want[0]), k
            else:
                assert got == want, k
        assert metrics.SERVING_DISPATCH.total(kind="ragged") > d0

    def test_unbatchable_shapes_stay_solo(self, rng):
        """previous=/having=/Min-aggregate GroupBys fall back to the
        solo path and stay correct under serving."""
        from pilosa_tpu.executor import Executor
        h = _engine(rng, 1 << 12)
        ex = Executor(h)
        ex.enable_serving(window_s=0.001, max_batch=8)
        for q in ("GroupBy(Rows(g), Rows(d), previous=[2, 1], "
                  "aggregate=Sum(field=v))",
                  "GroupBy(Rows(g), aggregate=Min(field=v))",
                  "GroupBy(Rows(g), Rows(d), limit=3)"):
            got = ex.execute_serving("i", q)
            want = Executor(h).execute("i", q)
            assert _as_t(got[0]) == _as_t(want[0]), q


class TestRooflineBytesModel:
    """The honest per-arm bytes accounting (ISSUE 11 satellite): each
    GroupBy arm notes ITS schedule's traffic, and the single-pass
    model is combo-count-free while the scan model is not."""

    def test_models_ordering(self):
        one = kernels.groupby_onepass_hbm_bytes(8, 1024, 6, depth=8)
        per = kernels.groupby_percombo_hbm_bytes(8, 1024, 60, 3,
                                                 depth=8)
        scan = kernels.groupby_scan_hbm_bytes(8, 1024, 60, 3, depth=8)
        assert one < per < scan
        # one-pass traffic is independent of combo count
        assert one == kernels.groupby_onepass_hbm_bytes(
            8, 1024, 6, depth=8)
        assert kernels.groupby_scan_hbm_bytes(
            8, 1024, 240, 3, depth=8) > scan

    def test_onepass_note_uses_model(self, rng, monkeypatch):
        """The engine's one-pass dispatch notes exactly the
        single-pass model bytes (not operand-array sums)."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.obs import roofline
        h = _engine(rng, 1 << 12)
        notes = []
        monkeypatch.setattr(
            roofline, "note",
            lambda op, b, s: notes.append((op, b)))
        Executor(h).execute(
            "i", "GroupBy(Rows(g), Rows(d), aggregate=Sum(field=v))")
        gb = [b for op, b in notes if op == "groupby"]
        assert gb, notes
        idx = h.index("i")
        n_shards = len(idx.field("g").views["standard"].shards)
        depth = idx.field("v").bit_depth
        want = kernels.groupby_onepass_hbm_bytes(
            n_shards, idx.width // 32, 3 + 2, depth)
        assert gb[-1] == want, (gb, want)
