"""The taxi deployment (benchmark/configs/taxi-1b.json) on the CPU at
two shards: every one of the twenty fields at its full row count, the
generator's skewed and correlated values, loaded through the
fragments' own imports as benchmark/harness/server.py:load does.

The program through ``api.query`` — solo path and serving layer —
against tests/reference_taxi.py (numpy over the per-trip columns) for
the four dashboard queries under each of the five filter forms; the
arm and the counters a GroupBy past 4,096 codes moves; the passes of
the packed body against one walk.
"""

import importlib.util
import json
import os
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import reference_taxi
from pilosa_tpu.api import API
from pilosa_tpu.executor import stacked
from pilosa_tpu.models import FieldOptions, FieldType, Holder
from pilosa_tpu.models.fragment import Fragment
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.obs import metrics
from pilosa_tpu.ops import kernels
from pilosa_tpu.shardwidth import SPARSE_MAX

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEED, SHARDS = 2147483777, 2
Q4_DIGITS = ((4, 10), (3, 8), (6, 60))


def _module(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "taxi_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # a dataclass looks its module up
    spec.loader.exec_module(mod)
    return mod


pql = _module("harness", "pql.py")

QUERIES = {
    "q1_cab": "TopN(cab_type, {F}, n=2)",
    "q2_amount": "GroupBy(Rows(passenger_count), filter={F}, "
                 "aggregate=Sum(field=total_amount_dollars))",
    "q3_year": "GroupBy(Rows(passenger_count), Rows(pickup_year), "
               "filter={F})",
    "q4_dist": "GroupBy(Rows(passenger_count), Rows(pickup_year), "
               "Rows(dist_miles), filter={F})",
}
FILTERS = {
    "month_time": "Intersect(Row(pickup_month=3), Row(pickup_time=17))",
    "day_time": "Intersect(Row(pickup_day=4), Row(pickup_time=38))",
    "month_day_time": "Intersect(Row(pickup_month=7), Row(pickup_day=5), "
                      "Row(pickup_time=37))",
    "cell_month": "Intersect(Row(pickup_grid_id={hot}), "
                  "Row(pickup_month=1))",
    "month_amount": "Intersect(Row(pickup_month=10), "
                    "Row(total_amount_dollars > 12))",
}


@pytest.fixture(scope="module")
def taxi():
    """(solo API, serving API, per-trip columns, the best hot cell)."""
    with open(os.path.join(BENCH, "configs", "taxi-1b.json")) as f:
        params = json.load(f)["params"]
    gen = _module("generators", "taxi_trips.py")
    mp = pytest.MonkeyPatch()
    # the arm a TPU picks by itself, here in interpret mode
    mp.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
    h = Holder()
    idx = h.create_index("taxi", track_existence=True)
    for f in params["fields"]:
        opts = dict(f["options"])
        idx.create_field(f["name"], FieldOptions(
            type=FieldType(f["type"]), **opts))
    assert len(idx.fields) == 20 + 1            # and the existence field
    idx._ensure_existence()
    columns = {}
    every = np.full(idx.width // 32, 0xFFFFFFFF, dtype=np.uint32)
    for shard in range(SHARDS):
        rows, _tables = gen.make_shard(params, SEED, shard)
        for name, col in gen.trips(params, SEED, shard).items():
            columns.setdefault(name, []).append(col)
        rows["_exists"] = {0: every}
        for name, data in rows.items():
            field = idx.fields[name]
            frag = field.view(
                field.bsi_view if field.options.type.is_bsi
                else VIEW_STANDARD, create=True).fragment(shard, create=True)
            if isinstance(data, dict):
                for r, w in data.items():
                    frag.import_row_words(r, w)
            elif field.options.type.is_bsi:
                frag.import_values(*data, field.bit_depth)
            else:
                frag.import_mutex(*data)
    columns = {k: np.concatenate(v) for k, v in columns.items()}
    solo, served = API(h), API(h)
    served.executor.enable_serving(cache_bytes=0)
    hot = int(gen.ranking(params["values"], SEED)[0])
    yield {"solo": solo, "served": served}, columns, hot, params
    mp.undo()


def _canonical(name, result):
    if name == "TopN":
        return [(p["id"], p["count"]) for p in result]
    return {tuple(g["row_id"] for g in r["group"]): (r["count"], r.get("agg"))
            for r in result}


def test_every_field_at_its_row_count_and_skewed(taxi):
    _apis, columns, _hot, params = taxi
    want = {"cab_type": 2, "passenger_count": 10, "dist_miles": 60,
            "duration_minutes": 180, "speed_mph": 80}
    for side in ("pickup", "dropoff"):
        want.update({f"{side}_time": 48, f"{side}_mday": 31,
                     f"{side}_month": 12, f"{side}_day": 7,
                     f"{side}_year": 8, f"{side}_grid_id": 10000,
                     f"{side}_elevation": 120})
    got = {f["name"]: f["rows"] for f in params["fields"] if "rows" in f}
    assert got == want
    amount = next(f for f in params["fields"]
                  if f["name"] == "total_amount_dollars")
    assert (amount["type"], amount["options"]) == (
        "int", {"min": 0, "max": 500})
    for name, rows in want.items():
        assert 0 <= columns[name].min() and columns[name].max() < rows, name
    n = len(columns["cab_type"])
    assert 0.65 < (columns["passenger_count"] == 1).mean() < 0.75
    assert 0.05 < columns["cab_type"].mean() < 0.15
    assert (columns["dist_miles"] < 5).mean() > 0.7
    assert columns["dist_miles"].max() == 59
    cells = np.sort(np.bincount(columns["pickup_grid_id"],
                                minlength=10000))[::-1]
    assert cells[:300].sum() > 0.6 * n          # a few hundred hot cells
    # correlated: a longer trip lasts longer and costs more
    far, near = columns["dist_miles"] >= 10, columns["dist_miles"] <= 1
    for name in ("duration_minutes", "total_amount_dollars"):
        assert columns[name][far].mean() > 3 * columns[name][near].mean()


@pytest.mark.parametrize("path", ["solo", "served"])
@pytest.mark.parametrize("form", list(FILTERS))
@pytest.mark.parametrize("query", list(QUERIES))
def test_query_equals_the_plain_reference(taxi, query, form, path):
    apis, columns, hot, _params = taxi
    q = QUERIES[query].replace("{F}", FILTERS[form]).replace(
        "{hot}", str(hot))
    loop = metrics.STACKED_QUERIES.value(path="loop")
    got = apis[path].query("taxi", q)["results"][0]
    call = pql.parse(q)
    want = reference_taxi.answer(columns, call)
    assert want, q                      # the filter selects some trips
    assert _canonical(call.name, got) == want
    assert metrics.STACKED_QUERIES.value(path="loop") == loop


def _counters():
    return {
        "loop": metrics.STACKED_QUERIES.value(path="loop"),
        "fused_arm": metrics.GROUPBY_ONEPASS.value(arm="fused"),
        "onepass": metrics.GROUPBY_ONEPASS.total(),
        "packed": metrics.GROUPBY_FUSED.total(body="packed"),
        "fused": metrics.GROUPBY_FUSED.total(),
        "passes": metrics.GROUPBY_PASSES.total(),
        "groups": metrics.GROUPBY_REPLY_GROUPS.total(),
    }


@pytest.mark.parametrize("path", ["solo", "served"])
def test_a_groupby_past_4096_codes_takes_the_packed_kernel(taxi, path):
    """Q4's code space is 8,192: the packed body serves its 4,800
    groups — in one walk since PR 40 (20 passes of 240 before) — and
    the counters say what kernels.fused_plan says."""
    apis, columns, _hot, _params = taxi
    q = QUERIES["q4_dist"].replace("{F}", "Row(pickup_month=5)")
    assert kernels.fused_plan(Q4_DIGITS, 0, False) == ("packed", 1)
    before = _counters()
    got = apis[path].query("taxi", q)["results"][0]
    want = reference_taxi.answer(columns, pql.parse(q))
    assert _canonical("GroupBy", got) == want and len(want) > 2000
    moved = _counters()
    assert {k: moved[k] - before[k] for k in moved} == {
        "loop": 0, "fused_arm": 1, "onepass": 1, "packed": 1, "fused": 1,
        "passes": 1, "groups": len(want)}


@pytest.mark.parametrize("path", ["solo", "served"])
def test_q4_walks_its_4800_groups_with_no_prepass(taxi, path):
    """Q4 counts 4,800 groups of one payload row: under
    stacked._PRUNE_MIN_UNITS, so no prepass drops rows first — it
    walks its fields' whole product, as before pruning existed, and
    the record names no ``groupby.prune`` stage."""
    from pilosa_tpu.obs import flight
    apis, columns, _hot, _params = taxi
    q = QUERIES["q4_dist"].replace("{F}", FILTERS["month_time"])
    assert not stacked._prunes([(None, range(n)) for n in (10, 8, 60)], 0,
                               True)
    walked = metrics.GROUPBY_WALKED.total()
    payload = metrics.GROUPBY_PAYLOAD.value(arm="fused", depth="le16")
    got = apis[path].query("taxi", q)["results"][0]
    assert _canonical("GroupBy", got) == reference_taxi.answer(
        columns, pql.parse(q))
    assert metrics.GROUPBY_WALKED.total() - walked == 4800
    assert metrics.GROUPBY_PAYLOAD.value(arm="fused", depth="le16") \
        == payload + 1
    rec = flight.recorder.recent(1)
    if path == "served" and rec:
        assert rec[0]["walked_groups"] == 4800
        assert "groupby.prune" not in {s[0] for s in rec[0]["spans"]}


def test_a_small_groupby_is_one_pass_and_a_topn_counts_no_groups(taxi):
    apis, _columns, _hot, _params = taxi
    passes = metrics.GROUPBY_PASSES.total()
    groups = metrics.GROUPBY_REPLY_GROUPS.total()
    got = apis["solo"].query("taxi", QUERIES["q3_year"].replace(
        "{F}", "Row(pickup_month=2)"))["results"][0]
    assert metrics.GROUPBY_PASSES.total() - passes == 1
    assert metrics.GROUPBY_REPLY_GROUPS.total() - groups == len(got) == 80
    apis["solo"].query("taxi", "TopN(cab_type, Row(pickup_month=2), n=2)")
    assert metrics.GROUPBY_REPLY_GROUPS.total() - groups == 80


# -- the packed body's walks ----------------------------------------------

MIB = 1 << 20
PAIRS = ((3, 7), (4, 11))
PASS_CASES = {
    # name: (digits, depth, signed, VMEM the body may count and block
    # vregs at most (None: the kernel's own), the plan that gives:
    # (block vregs, last field, rows a walk, walks)).  The count-only
    # forms are one walk under the kernel's own 29 MiB; 13 MiB is what
    # a kernel had before PR 40.  Widest field last, first, in the
    # middle; 50 rows in 4 walks of 13: the last walk's slots past row
    # 49 are dead
    "q4_count": (Q4_DIGITS, 0, False, (13 * MIB, 16), (16, 2, 12, 5)),
    "widest_first": (((6, 60), (4, 10), (3, 7)), 0, False,
                     (13 * MIB, 16), (16, 0, 15, 4)),
    "widest_middle": (((3, 8), (6, 50), (4, 10)), 0, False,
                      (13 * MIB, 16), (16, 1, 13, 4)),
    # with payload planes, off the register-formed mask (one walk of
    # Q4's 4,800 groups x 11 rows takes the interpreter minutes: 960
    # groups here)
    "sum_unsigned": (((4, 10), (3, 8), (6, 12)), 9, False, None,
                     (16, 2, 4, 3)),
    "sum_signed": (((4, 10), (3, 8), (6, 12)), 3, True, None,
                   (16, 2, 6, 2)),
    # one field: no upper level, `valid` is the one upper mask
    "single_field": (((6, 60),), 0, False, (MIB // 4, 1), (1, 0, 20, 3)),
    # 11 upper masks: an inner turn of 6 and 5 left over; 13 rows in 3
    # walks of 5: two dead slots
    "inner_left_over": (((4, 11), (4, 13)), 0, False, (MIB // 2, 1),
                        (1, 1, 5, 3)),
    # three and four payload rows: two upper masks an inner turn, one
    # left over; 11 rows in 6 walks of 2
    "sum_pairs": (PAIRS, 1, False, (MIB // 2, 1), (1, 1, 2, 6)),
    "sum_signed_pairs": (PAIRS, 1, True, (MIB // 2, 1), (1, 1, 2, 6)),
    # every column of the first shard (a whole block) invalid
    "valid_zero_block": (PAIRS, 1, True, (MIB // 2, 1), (1, 1, 2, 6)),
}


def test_inside_the_one_hot_bodys_code_space_nothing_goes_in_passes():
    """A shape of up to 4,096 codes whose accumulators do not fit one
    walk keeps the one-hot body, as before PR 36: on the chip it was
    the faster there (kernels.fused_plan)."""
    bound = ((6, 64), (6, 64))
    assert kernels._packed_passes(bound, 16, True)[3] == 32
    assert kernels.fused_plan(bound, 16, True) == ("onehot", 1)
    assert kernels.fused_plan(((4, 10), (3, 8), (5, 32)), 9, False) \
        == ("onehot", 1)
    assert kernels.fused_plan(((4, 10), (3, 8), (6, 32)), 9, False) \
        == ("packed", 7)
    assert stacked._ONEPASS_KERNEL_MAX_CODES == kernels.ONEHOT_MAX_CODES


def test_several_walks_only_at_the_full_block_width():
    """Three fields of 60 rows, count-only: one row's 3,600 upper masks
    and accumulators fit 29 MiB at one vreg a block, and 60 such walks
    read 8.92 s on the chip where the scatter reads 1.91 (PR 40): the
    packed body does not take what it cannot walk at full width."""
    wide = ((6, 60),) * 3
    assert kernels._packed_block_vregs(wide, 0, 1, 0, False) == 1
    assert kernels._packed_passes(wide, 0, False)[0] == 0
    assert kernels.fused_plan(wide, 0, False) == ("onehot", 1)


def _operands(rng, digits, depth, s_dim=2, w_dim=1024):
    n = s_dim * w_dim * 32
    code, shift = np.zeros(n, np.int64), 0
    for bits, rows in digits:
        # skewed digits: the first row of each field holds half
        d = np.where(rng.random(n) < 0.5, 0, rng.integers(0, rows, size=n))
        code |= d << shift
        shift += bits
    cp = np.stack([np.packbits(
        ((code >> b) & 1).astype(np.uint8).reshape(s_dim, -1), axis=1,
        bitorder="little").view(np.uint32) for b in range(shift)], 1)
    valid = rng.integers(0, 1 << 32, size=(s_dim, w_dim), dtype=np.uint32)
    planes = (rng.integers(0, 1 << 32, size=(s_dim, 2 + depth, w_dim),
                           dtype=np.uint32) if depth else None)
    return [None if a is None else jnp.asarray(a)
            for a in (cp, valid, planes)]


def _budget(monkeypatch, nbytes, block=None):
    monkeypatch.setattr(kernels, "_PACKED_VMEM_BYTES", nbytes)
    if block is not None:
        monkeypatch.setattr(kernels, "_PACKED_BLOCK_VREGS", block)
    kernels._packed_passes.cache_clear()


@pytest.mark.parametrize("case", list(PASS_CASES))
def test_passes_add_up_to_one_walk(rng, monkeypatch, case):
    digits, depth, signed, budget, plan = PASS_CASES[case]
    cp, valid, planes = _operands(rng, digits, depth)
    if case == "valid_zero_block":
        valid = valid.at[0].set(0)
    n_codes = 1 << sum(b for b, _ in digits)
    scatter = kernels.groupby_codes_xla(cp, valid, planes, n_codes, signed)
    # the walk itself is the same inside the one-hot body's code space,
    # where fused_plan would not take several
    monkeypatch.setattr(kernels, "ONEHOT_MAX_CODES", 0)
    try:
        if budget is not None:
            _budget(monkeypatch, *budget)
        nv, fi, rp, n_pass = kernels._packed_passes(digits, depth, signed)
        assert (nv, fi, rp, n_pass) == plan
        assert fi == max(range(len(digits)), key=lambda i: digits[i][1])
        assert n_pass > 1 and n_pass == -(-digits[fi][1] // rp)
        assert kernels.fused_plan(digits, depth, signed) == ("packed", n_pass)
        split = kernels.groupby_fused(cp, valid, planes, n_codes, signed,
                                      digits=digits)
        # one walk: the same body with room for every accumulator
        _budget(monkeypatch, 1 << 34)
        assert kernels.fused_plan(digits, depth, signed) == ("packed", 1)
        whole = kernels.groupby_fused(cp, valid, planes, n_codes, signed,
                                      digits=digits)
    finally:
        monkeypatch.undo()
        kernels._packed_passes.cache_clear()
    for a, b, c in zip(split, whole, scatter):
        if a is not None:
            assert np.array_equal(a, b) and np.array_equal(a, c)
    assert int(np.asarray(split[0]).sum()) > 0


ARMS = [
    # backend, n_codes, depth, digits, signed, minmax -> arm
    ("tpu", 8192, 0, Q4_DIGITS, False, False, "fused"),
    ("tpu", 8192, 9, Q4_DIGITS, False, False, "fused"),
    ("tpu", 8192, 0, None, False, False, "xla"),       # a value histogram
    ("tpu", 8192, 9, Q4_DIGITS, False, True, "xla"),   # Min/Max: one-hot
    ("tpu", 1 << 18, 0, ((6, 60), (6, 60), (6, 60)), False, False, "xla"),
    # past 16 bits the packed body serves (the one-hot keeps its bound)
    ("tpu", 8192, 17, Q4_DIGITS, False, False, "fused"),
    ("cpu", 8192, 0, Q4_DIGITS, False, False, "xla"),
    ("tpu", 64, 7, ((3, 6), (1, 2), (3, 5)), False, False, "fused"),
]


@pytest.mark.parametrize("case", ARMS)
def test_onepass_arm_past_the_code_bound(monkeypatch, case):
    backend, n_codes, depth, digits, signed, minmax, arm = case
    monkeypatch.setattr(stacked.jax, "default_backend", lambda: backend)
    monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", raising=False)
    if digits is None:          # bsi_value_hist: no fields, no plan
        assert stacked._onepass_arm(n_codes, depth) == arm
        return
    plan = stacked._onepass_plan(n_codes, depth, digits, signed, minmax)
    assert plan[0] == arm
    assert plan[1:] == kernels.fused_plan(digits, depth, signed, minmax)


def test_pass_plan_of_the_dashboards_groupbys():
    """Q4 in one walk of dist_miles' 60 rows against 80 parked upper
    masks, blocks of 16 vregs; with the amount summed, 12 walks of 5
    rows; Q2 and Q3 in one walk."""
    assert kernels._packed_passes(Q4_DIGITS, 0, False) == (16, 2, 60, 1)
    assert kernels._packed_passes(Q4_DIGITS, 9, False) == (16, 2, 5, 12)
    assert kernels._packed_passes(Q4_DIGITS[:2], 0, False) == (16, 0, 10, 1)
    assert kernels._packed_passes(Q4_DIGITS[:1], 9, False) == (16, 0, 10, 1)
    assert kernels._inner_groups(80, 1) == (8, 10, 0)
    assert kernels._inner_groups(80, 11) == (1, 80, 0)
    assert kernels._inner_groups(11, 1) == (6, 1, 5)
    # what the body counts of the VMEM it asks for stays inside it
    assert kernels._PACKED_VMEM_BYTES < kernels._PACKED_VMEM_LIMIT
    codes = kernels._pass_codes(Q4_DIGITS, 2, 60, 1)
    assert codes.shape == (1, 4800) and (codes >= 0).all()
    assert len(set(codes.ravel().tolist())) == 4800
    codes = kernels._pass_codes(Q4_DIGITS, 2, 3, 20)
    assert codes.shape == (20, 240) and (codes >= 0).all()
    assert len(set(codes.ravel().tolist())) == 4800
    # 7 rows a walk do not divide 60: the last walk's dead slots are -1
    codes = kernels._pass_codes(Q4_DIGITS, 2, 7, 9)
    assert (codes >= 0).sum() == 4800 and (codes[-1] < 0).sum() == 3 * 80


# -- the bulk load of a single-valued field -------------------------------

def _pairs(rng, rows, n=200_000, width=1 << 20):
    cols = np.sort(rng.choice(width, size=n, replace=False))
    # Zipf over the rows: a few dense ones and a long sparse tail
    p = 1.0 / np.arange(1, rows + 1)
    return rng.choice(rows, size=n, p=p / p.sum()), cols


def _twins(rng, rows, n=200_000):
    r, c = _pairs(rng, rows, n)
    bulk, bits = (Fragment("i", "f", "standard", 0) for _ in range(2))
    bulk.import_mutex(r, c)
    bits.import_bits(r, c)
    bulk.check()
    return bulk, bits, r, c


def _same_reads(a, b):
    assert a.row_ids == b.row_ids
    for row in b.row_ids + [max(b.row_ids) + 1, 70000]:
        assert np.array_equal(a.row_words(row), b.row_words(row)), row
        assert a.row_count(row) == b.row_count(row)
    assert a.block_checksums() == b.block_checksums()


def test_import_mutex_into_an_empty_fragment_keeps_thin_rows_sparse(rng):
    """A fresh fragment loads in bulk.  Where the rows are the smaller
    form (10,000 rows over a fifth of a shard): every row reads as the
    per-bit import leaves it, a row of up to SPARSE_MAX bits is stored
    as columns whatever the field's row count, and a stack built from
    an earlier snapshot can only rebuild."""
    bulk, bits, r, _c = _twins(rng, 10000)
    _same_reads(bulk, bits)
    counts = np.bincount(r, minlength=10000)
    assert bulk._codes is None
    assert len(bulk._rows) == int((counts > SPARSE_MAX).sum()) > 0
    assert bulk.sparse_row_count == int(
        ((counts > 0) & (counts <= SPARSE_MAX)).sum()) > 0
    assert bulk.memory_bytes() == bits.memory_bytes()
    assert bulk.version > 0 and bulk.deltas_since(0) is None
    assert bulk.deltas_since(bulk.version) == []


@pytest.mark.parametrize("rows,n,itemsize", [
    (60, 200_000, 1), (255, 1 << 20, 1), (256, 1 << 20, 2),
    (10000, 1 << 20, 2)])
def test_import_mutex_holds_a_full_single_valued_shard_by_codes(
        rng, rows, n, itemsize):
    """Where one row id a column is the smaller form the fragment is
    code-held: 1 byte a column up to 255 rows, else 2; every read is
    the rows' read; a column with no row reads as in none."""
    bulk, bits, r, c = _twins(rng, rows, n)
    assert bulk._codes is not None and not bulk._rows and not bulk._sparse
    assert bulk._codes.itemsize == itemsize
    assert bulk.memory_bytes() < bits.memory_bytes()
    assert bulk.memory_bytes() <= (1 << 20) * itemsize + 8 * rows
    assert bulk.sparse_row_count == len(bits.row_ids)   # none is dense
    _same_reads(bulk, bits)
    for row, col in zip(r[:50].tolist(), c[:50].tolist()):
        assert bulk.contains(row, col) and not bulk.contains(row + 1, col)
    free = np.setdiff1d(np.arange(1 << 20), c)[:5]
    sentinel = int(np.iinfo(bulk._codes.dtype).max)
    assert all(not bulk.contains(sentinel, int(col)) for col in free)
    assert bulk.version > 0 and bulk.deltas_since(0) is None


def _write(frag, how, r, c):
    if how == "set_bit":
        return frag.set_bit(7, int(c[3]) ^ 1), frag.set_bit(
            int(r[3]), int(c[3]))
    if how == "clear_bit":
        return frag.clear_bit(int(r[5]), int(c[5])), frag.clear_bit(
            int(r[5]) + 1, int(c[5]))
    if how == "import_bits":
        return frag.import_bits(np.full(100, 3), c[:100])
    if how == "import_bits_clear":
        return frag.import_bits(r[:100], c[:100], clear=True)
    if how == "import_row_words":
        return frag.import_row_words(2, np.full((1 << 20) // 32, 0x0F0F0F0F,
                                                dtype=np.uint32))
    if how == "set_row_words":
        return frag.set_row_words(1, 0)
    if how == "clear_columns":
        mask = np.zeros((1 << 20) // 32, dtype=np.uint32)
        mask[10:500] = 0xFFFF0000
        return frag.clear_columns(mask)
    if how == "set_block_rows":
        return frag.set_block_rows(0, {4: frag.row_words(9).copy()})
    assert how == "import_mutex"
    return frag.import_mutex(np.full(1000, 59), c[:1000])


@pytest.mark.parametrize("how", [
    "set_bit", "clear_bit", "import_bits", "import_bits_clear",
    "import_row_words", "set_row_words", "clear_columns", "set_block_rows",
    "import_mutex"])
def test_a_write_turns_a_code_held_fragment_back_into_rows(rng, how):
    """Every mutator but the bulk load writes rows: the codes are
    decoded first (the version does not move for that), the write
    lands as on a fragment that never held codes, and a reader that
    snapshot the loaded version can patch."""
    bulk, bits, r, c = _twins(rng, 60)
    assert bulk._codes is not None
    v = bulk.version
    bulk._decode()
    assert bulk.version == v and bulk._codes is None
    _same_reads(bulk, bits)
    again = Fragment("i", "f", "standard", 0)
    again.import_mutex(r, c)
    assert _write(again, how, r, c) == _write(bits, how, r, c)
    assert again._codes is None
    again.check()
    _same_reads(again, bits)
    assert again.version > v and again.deltas_since(v) is not None


def _deployment():
    with open(os.path.join(BENCH, "configs", "taxi-1b.json")) as f:
        return json.load(f)["params"], _module("generators", "taxi_trips.py")


def test_the_fields_of_a_shard_fit_the_host_the_configuration_states():
    """The generator reads the program's own accounting of one
    shard's twenty fields, loaded as the harness loads them: by codes
    they are a third of the limit that 263 shards on 40 GiB leave,
    and the thirteen fields of more than 10 rows are code-held."""
    params, gen = _deployment()
    rows, _tables = gen.make_shard(params, SEED, 1)
    need = gen.host_bytes(params, rows)
    assert 20e6 < need < 26e6 < params["host"][
        "fragment_bytes_per_shard_max"]
    held = 0
    for f in params["fields"]:
        if f["form"] == "ids":
            frag = Fragment("taxi", f["name"], "standard", 0)
            frag.import_mutex(*rows[f["name"]])
            held += frag._codes is not None
            assert frag._codes.dtype == (
                np.uint16 if f["rows"] > 255 else np.uint8)
    assert held == 13 == sum(f["form"] == "ids" for f in params["fields"])


def test_shard_0_ends_the_run_where_the_fragments_pass_the_hosts_limit():
    """A program that needs more host memory a shard than the
    configuration's host leaves (the parent of PR 36: 117.6 MB) gives
    no result: the generator ends the run before the load, and only
    the first shard looks."""
    params, gen = _deployment()
    small = dict(params, host=dict(params["host"],
                                   fragment_bytes_per_shard_max=10_000_000))
    with pytest.raises(SystemExit, match="do not fit the deployment's host"):
        gen.make_shard(small, SEED, 0)
    gen.make_shard(small, SEED, 1)


def test_the_traffic_file_names_the_generators_hot_cells():
    """dashboard-q1-4.json draws its grid cell from the 64 cells that
    the configuration's ranking puts first, whatever the seed."""
    with open(os.path.join(BENCH, "configs", "taxi-1b.json")) as f:
        values = json.load(f)["params"]["values"]
    with open(os.path.join(BENCH, "traffic", "dashboard-q1-4.json")) as f:
        traffic = json.load(f)
    gen = _module("generators", "taxi_trips.py")
    drawn = {int(g) for g in traffic["params"]["g"]["zipf"]["values"]}
    assert drawn == set(values["hot_cells"]) and len(drawn) == 64
    for seed in (1, SEED, 2**31 + 5):
        assert set(gen.ranking(values, seed)[:64].tolist()) == drawn
    forms = [text for _w, text in traffic["params"]["F"]["choice"]]
    warm = {(w["t"], w["fixed"]["F"]) for w in traffic["warmup"]["sequential"]}
    assert warm == {(t["name"], f) for t in traffic["templates"]
                    for f in forms} and len(warm) == 20
    for t in traffic["templates"]:
        assert re.sub(r"\s+", "", t["pql"]) == re.sub(
            r"\s+", "", QUERIES[t["name"]])
