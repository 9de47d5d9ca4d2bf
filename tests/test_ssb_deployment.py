"""The Star Schema Benchmark deployment (benchmark/configs/ssb-sf100.json)
on the CPU: the generator's lines at two shards of the suite's width
(the first 4,096 lines of its shards 0 and 1), every field at its
published row count, loaded through the fragments' own imports as
benchmark/harness/server.py:load does.

Each of SSB's thirteen query forms through ``api.query`` on the served
path, the fused arm forced (interpret mode), against
tests/reference_ssb.py (numpy over the per-line columns), with the
groups each walk visited; the pruned walk's edges (a filter that
empties a field, one that leaves one row of each, a 27-bit Sum past
int32, Q4.3's form past the dense code bound); the kernel's selection
at each bucket and in several walks; and what is not pruned (taxi-1b's
Q4 and able-1b's 60-group form keep their walks).
"""

import importlib.util
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import reference_ssb
from pilosa_tpu.api import API
from pilosa_tpu.executor import stacked
from pilosa_tpu.models import FieldOptions, FieldType, Holder
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.obs import flight, metrics
from pilosa_tpu.ops import kernels

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEED, SHARDS, W = 2147483901, 2, 1 << 12


def _module(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "ssb_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # a dataclass looks its module up
    spec.loader.exec_module(mod)
    return mod


pql = _module("harness", "pql.py")
gen = _module("generators", "ssb_lineorder.py")


def _index(h, name, fields):
    idx = h.create_index(name, track_existence=True)
    for f in fields:
        idx.create_field(f["name"], FieldOptions(
            type=FieldType(f["type"]), **f["options"]))
    idx._ensure_existence()
    return idx


def _load(idx, shard, columns):
    """One shard's columns (row ids; an int field's values) into its
    fragments, the harness's hand-over forms."""
    cols = np.arange(len(next(iter(columns.values()))), dtype=np.int64)
    every = np.full(idx.width // 32, 0xFFFFFFFF, dtype=np.uint32)
    rows = {"_exists": {0: every}}
    for name, col in columns.items():
        field = idx.fields[name]
        if field.options.type.is_bsi:
            rows[name] = (cols, col)
        else:
            rows[name] = (col.astype(np.uint16), cols)
    for name, data in rows.items():
        field = idx.fields[name]
        frag = field.view(field.bsi_view if field.options.type.is_bsi
                          else VIEW_STANDARD,
                          create=True).fragment(shard, create=True)
        if isinstance(data, dict):
            for r, w in data.items():
                frag.import_row_words(r, w)
        elif field.options.type.is_bsi:
            frag.import_values(*data, field.bit_depth)
        else:
            frag.import_mutex(*data)


@pytest.fixture(scope="module")
def ssb():
    """(serving API, per-line columns, field list)."""
    with open(os.path.join(BENCH, "configs", "ssb-sf100.json")) as f:
        params = json.load(f)["params"]
    mp = pytest.MonkeyPatch()
    # the arm a TPU picks by itself, here in interpret mode
    mp.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
    h = Holder(width=W)
    idx = _index(h, "ssb", params["fields"])
    assert len(idx.fields) == 17 + 1            # and the existence field
    names = [f["name"] for f in params["fields"]]
    columns = {}
    for shard in range(SHARDS):
        lines = gen.lines(params, SEED, shard)
        part = {k: lines[k][:W] for k in names}
        _load(idx, shard, part)
        for k, v in part.items():
            columns.setdefault(k, []).append(v)
    columns = {k: np.concatenate(v) for k, v in columns.items()}
    api = API(h)
    api.executor.enable_serving(cache_bytes=0)
    yield api, columns, params
    mp.undo()


def _line(columns, j):
    return {k: int(v[j]) for k, v in columns.items()}


def _union(field, rows):
    return "Union(" + ", ".join(f"Row({field}={r})" for r in rows) + ")"


def _years(a, n):
    """A range of `n` years holding year row `a`, as a Union."""
    first = min(a, 7 - n)
    return _union("d_year", range(first, first + n))


def _forms(a):
    """SSB's thirteen forms with constants taken from line `a`, so that
    each selects at least that line."""
    disc = min(a["lo_discount"], 8)
    qty = min(a["lo_quantity"], 41)
    band = f"Row(lo_discount >= {disc}), Row(lo_discount <= {disc + 2})"
    qrange = f"Row(lo_quantity >= {qty}), Row(lo_quantity <= {qty + 9})"
    rev, profit = ("aggregate=Sum(field=lo_revenue))",
                   "aggregate=Sum(field=lo_profit))")
    cat, brand = a["p_category"], a["p_brand1"]
    first = cat * 40 + min(brand - cat * 40, 32)
    cities = {s: _union(f"{s}_city", sorted({a[f"{s}_city"],
                                             a[f"{s}_nation"] * 10 + 9}))
              for s in "cs"}
    mfgrs = _union("p_mfgr", sorted({a["p_mfgr"], (a["p_mfgr"] + 1) % 5}))
    yy = _union("d_year", sorted({a["d_year"], (a["d_year"] + 3) % 7}))
    regions = f"Row(c_region={a['c_region']}), Row(s_region={a['s_region']})"
    return {
        "q1_1": f"Sum(Intersect(Row(d_year={a['d_year']}), {band}, "
                f"Row(lo_quantity < {a['lo_quantity'] + 1})), "
                "field=lo_revenue_computed)",
        "q1_2": f"Sum(Intersect(Row(d_yearmonthnum={a['d_yearmonthnum']}), "
                f"{band}, {qrange}), field=lo_revenue_computed)",
        "q1_3": f"Sum(Intersect(Row(d_weeknuminyear={a['d_weeknuminyear']}), "
                f"Row(d_year={a['d_year']}), {band}, {qrange}), "
                "field=lo_revenue_computed)",
        "q2_1": "GroupBy(Rows(d_year), Rows(p_brand1), filter=Intersect("
                f"Row(p_category={cat}), Row(s_region={a['s_region']})), "
                + rev,
        "q2_2": "GroupBy(Rows(d_year), Rows(p_brand1), filter=Intersect("
                f"{_union('p_brand1', range(first, first + 8))}, "
                f"Row(s_region={a['s_region']})), " + rev,
        "q2_3": "GroupBy(Rows(d_year), Rows(p_brand1), filter=Intersect("
                f"Row(p_brand1={brand}), Row(s_region={a['s_region']})), "
                + rev,
        "q3_1": "GroupBy(Rows(c_nation), Rows(s_nation), Rows(d_year), "
                f"filter=Intersect({regions}, {_years(a['d_year'], 6)}), "
                + rev,
        "q3_2": "GroupBy(Rows(c_city), Rows(s_city), Rows(d_year), "
                f"filter=Intersect(Row(c_nation={a['c_nation']}), "
                f"Row(s_nation={a['s_nation']}), {_years(a['d_year'], 6)}), "
                + rev,
        "q3_3": "GroupBy(Rows(c_city), Rows(s_city), Rows(d_year), "
                f"filter=Intersect({cities['c']}, {cities['s']}, "
                f"{_years(a['d_year'], 6)}), " + rev,
        "q3_4": "GroupBy(Rows(c_city), Rows(s_city), Rows(d_year), "
                f"filter=Intersect({cities['c']}, {cities['s']}, "
                f"Row(d_yearmonthnum={a['d_yearmonthnum']})), " + rev,
        "q4_1": "GroupBy(Rows(d_year), Rows(c_nation), filter=Intersect("
                f"{regions}, {mfgrs}), " + profit,
        "q4_2": "GroupBy(Rows(d_year), Rows(s_nation), Rows(p_category), "
                f"filter=Intersect({regions}, {yy}, {mfgrs}), " + profit,
        "q4_3": "GroupBy(Rows(d_year), Rows(s_city), Rows(p_brand1), "
                f"filter=Intersect(Row(c_region={a['c_region']}), "
                f"Row(s_nation={a['s_nation']}), {yy}, "
                f"Row(p_category={cat})), " + profit,
    }


FORMS = list(_forms(dict.fromkeys(
    ["lo_discount", "lo_quantity", "p_category", "p_brand1", "c_city",
     "s_city", "c_nation", "s_nation", "p_mfgr", "d_year", "c_region",
     "s_region", "d_yearmonthnum", "d_weeknuminyear"], 0)))


def _canonical(name, result):
    if name == "Sum":
        return result["value"], result["count"]
    return {tuple(g["row_id"] for g in r["group"]): (r["count"], r.get("agg"))
            for r in result}


def _walk(columns, call):
    """What a pruned walk of `call` visits: per field the rows of the
    lines the filter selects, each count rounded up to a power of two."""
    sel = reference_ssb.mask(columns, call.kwargs.get("filter"))
    walked = 1
    for a in call.args:
        live = np.unique(columns[a.args[0]][sel]).size
        walked *= stacked._bucket(live) if live else 0
    return walked


def _ask(api, columns, q):
    """(got, want, groups walked, the flight record) of one query."""
    before = metrics.GROUPBY_WALKED.total()
    with flight.request():
        got = api.query("ssb", q)["results"][0]
    rec = flight.recorder.recent(1)[0] if flight.recorder.enabled else None
    call = pql.parse(q)
    return (_canonical(call.name, got), reference_ssb.answer(columns, call),
            metrics.GROUPBY_WALKED.total() - before, rec)


@pytest.mark.parametrize("form", FORMS)
def test_form_equals_the_plain_reference(ssb, form):
    """Every form on the served path: the answer is the reference's,
    and a GroupBy walks what its shape says — the pruned forms the
    rows their filter leaves, bucketed; Q4.1 (175 combinations of 26
    payload rows, under _PRUNE_MIN_UNITS) its fields' whole product."""
    api, columns, _params = ssb
    loop = metrics.STACKED_QUERIES.value(path="loop")
    q = _forms(_line(columns, 4097))[form]
    got, want, walked, rec = _ask(api, columns, q)
    assert want, q                      # the filter selects some lines
    assert got == want
    assert metrics.STACKED_QUERIES.value(path="loop") == loop
    call = pql.parse(q)
    if call.name == "Sum":
        assert walked == 0
        return
    fields_rows = [(None, range(np.unique(columns[a.args[0]]).size))
                   for a in call.args]
    depth = 24
    if stacked._prunes(fields_rows, depth, True):
        assert walked == _walk(columns, call) <= 2048
        if rec is not None:
            assert "groupby.prune" in {s[0] for s in rec["spans"]}
    else:
        assert form == "q4_1" and walked == 7 * 25
    if rec is not None:
        assert rec["walked_groups"] == walked
        assert rec["reply_groups"] == len(want)


def test_a_filter_that_empties_a_field(ssb):
    """A brand of another category: the brand field has no live row,
    the reply is empty and nothing is walked."""
    api, columns, _params = ssb
    a = _line(columns, 11)
    other = (a["p_brand1"] + 40) % 1000
    q = ("GroupBy(Rows(d_year), Rows(s_city), Rows(p_brand1), "
         f"filter=Intersect(Row(p_category={a['p_category']}), "
         f"Row(p_brand1={other})), aggregate=Sum(field=lo_profit))")
    got, want, walked, _rec = _ask(api, columns, q)
    assert got == want == {} and walked == 0


def test_a_filter_that_leaves_one_row_of_each(ssb):
    """One line's month, cities and brand: one live row a field, a walk
    of one group."""
    api, columns, _params = ssb
    a = _line(columns, 5)
    q = ("GroupBy(Rows(d_year), Rows(s_city), Rows(p_brand1), "
         f"filter=Intersect(Row(d_yearmonthnum={a['d_yearmonthnum']}), "
         f"Row(c_city={a['c_city']}), Row(s_city={a['s_city']}), "
         f"Row(p_brand1={a['p_brand1']})), aggregate=Sum(field=lo_profit))")
    got, want, walked, _rec = _ask(api, columns, q)
    assert got == want and len(want) == 1 and walked == 1


def test_a_27_bit_sum_past_int32(ssb):
    """lo_revenue_computed is 27 bits: its per-plane counts, combined,
    pass 2^31 in the Sum and in each group of a GroupBy, which the
    fused kernel serves with its whole depth."""
    api, columns, params = ssb
    depth = next(f for f in params["fields"]
                 if f["name"] == "lo_revenue_computed")["options"]["max"]
    assert depth.bit_length() == 27
    got, want, _w, _rec = _ask(api, columns,
                               "Sum(field=lo_revenue_computed)")
    assert got == want and want[0] > 1 << 31
    fused = metrics.GROUPBY_PAYLOAD.value(arm="fused", depth="gt16")
    q = ("GroupBy(Rows(d_year), Rows(c_region), filter=Row(lo_discount >= 5), "
         "aggregate=Sum(field=lo_revenue_computed))")
    got, want, walked, _rec = _ask(api, columns, q)
    assert got == want and walked == 35
    assert max(s for _c, s in want.values()) > 1 << 31
    assert metrics.GROUPBY_PAYLOAD.value(arm="fused", depth="gt16") \
        == fused + 1


def test_q4_3_past_the_dense_code_bound(ssb):
    """Q4.3's fields hold 7 x 250 x 1,000 rows: 2^21 codes, past
    _ONEPASS_MAX_CODES.  The one-pass kernel serves it over the rows
    its filter leaves; no per-combination path runs."""
    api, columns, _params = ssb
    rows = [np.unique(columns[f]).size
            for f in ("d_year", "s_city", "p_brand1")]
    assert rows == [7, 250, 1000]
    fields_rows = [(None, range(r)) for r in rows]
    assert stacked._code_space(fields_rows)[2] > stacked._ONEPASS_MAX_CODES
    before = (metrics.GROUPBY_ONEPASS.value(arm="fused"),
              metrics.GROUPBY_KERNEL.total())
    q = _forms(_line(columns, 123))["q4_3"]
    got, want, walked, _rec = _ask(api, columns, q)
    assert got == want and 0 < walked <= 2 * 16 * 64
    assert (metrics.GROUPBY_ONEPASS.value(arm="fused"),
            metrics.GROUPBY_KERNEL.total()) == (before[0] + 1, before[1])


@pytest.mark.parametrize("arm", ["host", "xla"])
def test_the_other_arms_prune_by_a_stack_of_the_rows_left(ssb, monkeypatch,
                                                          arm):
    """Off the kernel — the host histogram a CPU picks by itself, the
    XLA scatter a mesh takes — the same prepass finds the rows, and a
    group-code stack of those rows alone is histogrammed: Q4.3's answer,
    over a code space of the rows left."""
    api, columns, _params = ssb
    if arm == "host":
        monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM")
    else:
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", arm)
    before = metrics.GROUPBY_ONEPASS.value(arm=arm)
    q = _forms(_line(columns, 3001))["q4_3"]
    got, want, walked, _rec = _ask(api, columns, q)
    assert got == want and want
    assert metrics.GROUPBY_ONEPASS.value(arm=arm) == before + 1
    assert 0 < walked <= _walk(columns, pql.parse(q))


@pytest.mark.parametrize("arm", ["host", "xla"])
def test_the_other_arms_walk_the_whole_code_space_inside_the_bound(
        ssb, monkeypatch, arm):
    """Q3.2 (2^19 codes, inside _ONEPASS_MAX_CODES) off the kernel: no
    prepass and no stack of the rows left, the whole code space walked
    as before, the reference's answer."""
    api, columns, _params = ssb
    if arm == "host":
        monkeypatch.delenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM")
    else:
        monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", arm)
    q = _forms(_line(columns, 3001))["q3_2"]
    call = pql.parse(q)
    rows = [np.unique(columns[a.args[0]]).size for a in call.args]
    n_codes = stacked._code_space([(None, range(r)) for r in rows])[2]
    assert n_codes <= stacked._ONEPASS_MAX_CODES
    got, want, walked, rec = _ask(api, columns, q)
    assert got == want and want
    assert walked == n_codes
    if rec is not None:
        assert "groupby.prune" not in {s[0] for s in rec["spans"]}


# -- the walk over a selection ----------------------------------------------

def _operands(rng, digits, depth, s_dim=2, w_dim=1024):
    n = s_dim * w_dim * 32
    code, shift = np.zeros(n, np.int64), 0
    for bits, rows in digits:
        code |= rng.integers(0, rows, size=n) << shift
        shift += bits
    cp = np.stack([np.packbits(
        ((code >> b) & 1).astype(np.uint8).reshape(s_dim, -1), axis=1,
        bitorder="little").view(np.uint32) for b in range(shift)], 1)
    valid = rng.integers(0, 1 << 32, size=(s_dim, w_dim), dtype=np.uint32)
    planes = (rng.integers(0, 1 << 32, size=(s_dim, 2 + depth, w_dim),
                           dtype=np.uint32) if depth else None)
    return [None if x is None else jnp.asarray(x)
            for x in (cp, valid, planes)]


FULL = ((3, 7), (7, 100))


@pytest.mark.parametrize("bucket", [1, 2, 4, 8, 16, 32, 64])
def test_selection_at_each_bucket(rng, bucket):
    """Three rows of the first field, `bucket` slots of the second with
    a live row or two fewer (dead slots): the selection's table is the
    scatter's at the selected combinations, 0 in every dead slot."""
    depth, signed = 5, True
    cp, valid, planes = _operands(rng, FULL, depth)
    full = kernels.groupby_codes_xla(cp, valid, planes, 1 << 10, signed)
    live = [np.array([0, 3, 6]),
            np.sort(rng.choice(100, max(1, bucket - 2), replace=False))]
    digits = ((3, 4), (7, bucket))
    sel = kernels.selection(digits, depth, signed, live)
    n_sel = 4 * bucket
    got = kernels.groupby_fused(cp, valid, planes, n_sel, signed,
                                digits=digits, sel=jnp.asarray(sel))
    local = stacked.product_combos([len(x) for x in live])
    want_codes = live[0][local[:, 0]] | (live[1][local[:, 1]] << 3)
    # the selection's codes: two bits for the first field's four slots
    assert kernels.selected_digits(digits)[0] == (2, 4)
    got_codes = local[:, 0] | (local[:, 1] << 2)
    for a, b in zip(got, full):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a[got_codes], b[want_codes])
        dead = np.ones(n_sel, bool)
        dead[got_codes] = False
        assert not a[dead].any()
    assert int(np.asarray(got[0]).sum()) > 0


def test_selection_in_several_walks(rng, monkeypatch):
    """A selection whose accumulators do not fit one walk: the walks'
    tables add up to the one walk's, the last walk's slots past the
    selection dead."""
    depth, signed = 3, False
    cp, valid, planes = _operands(rng, FULL, depth)
    live = [np.array([1, 2, 5]), np.arange(3, 60, 2)]     # 29 of 32 slots
    digits = ((3, 4), (7, 32))
    outs = []
    for budget in (1 << 19, 1 << 34):
        monkeypatch.setattr(kernels, "_PACKED_VMEM_BYTES", budget)
        monkeypatch.setattr(kernels, "_PACKED_BLOCK_VREGS", 1)
        kernels._packed_passes.cache_clear()
        plan = kernels._packed_passes(digits, depth, signed, True)
        sel = kernels.selection(digits, depth, signed, live)
        outs.append((plan, kernels.groupby_fused(
            cp, valid, planes, 128, signed, digits=digits,
            sel=jnp.asarray(sel))))
    monkeypatch.undo()
    kernels._packed_passes.cache_clear()
    (split_plan, split), (whole_plan, whole) = outs
    assert split_plan[3] > 1 and whole_plan[3] == 1
    for a, b in zip(split, whole):
        if a is not None:
            assert np.array_equal(a, b)


def test_a_selection_the_packed_body_cannot_walk_is_refused():
    """Not one row of the widest field fits: no selection, and the
    pruned GroupBy histograms a stack of the surviving rows instead."""
    wide = ((6, 64),) * 3
    assert kernels._packed_passes(wide, 0, False, True)[0] == 0
    assert kernels.selection(wide, 0, False, [[0]] * 3) is None


def test_only_a_selection_walks_at_narrow_blocks():
    """Q3.2's pruned walk (16 x 16 x 8 groups, a 24-bit Sum) fits one
    row at 8 vregs and none at 16: a selection walks it in 16 slices
    of 8-vreg blocks; the same digits unselected keep the full-width
    floor and are not walked at all."""
    q32 = ((8, 16), (8, 16), (3, 8))
    assert kernels._packed_passes(q32, 24, False) == (0, 0, 0, 1)
    assert kernels._packed_passes(q32, 24, False, True) == (8, 0, 1, 16)
    assert kernels.selection(q32, 24, False, [[0], [0], [0]]) is not None


# -- what is not pruned -----------------------------------------------------

def _rows(*counts):
    return [(None, range(n)) for n in counts]


def test_what_is_pruned():
    """taxi-1b's Q4 (4,800 groups, count-only), able-1b's 60 groups
    with a 7-bit Sum and SSB's Q4.1 (175 x 26) walk their whole
    product in one walk; SSB's other GroupBys and taxi's Q4 with a
    Sum (4,800 x 11 units) prune first where the kernel walks a
    selection, and elsewhere only Q4.3, past the dense code bound."""
    assert stacked._PRUNE_MIN_UNITS == 14000
    assert not stacked._prunes(_rows(10, 8, 60), 0, True)
    assert not stacked._prunes(_rows(6, 2, 5), 7, True)
    assert not stacked._prunes(_rows(7, 25), 24, True)
    assert stacked._prunes(_rows(10, 8, 60), 9, True)
    for shape in ((7, 1000), (25, 25, 7), (250, 250, 7), (7, 25, 25),
                  (7, 250, 1000)):
        assert stacked._prunes(_rows(*shape), 24, True), shape
        assert stacked._prunes(_rows(*shape), 24, False) \
            == (shape == (7, 250, 1000)), shape
    assert [stacked._bucket(n) for n in (1, 2, 3, 7, 10, 40, 64, 65)] == \
        [1, 2, 4, 8, 16, 64, 64, 128]


def test_able_60_group_form_walks_its_60_groups(rng):
    """able-1b's query (6 x 2 x 5 rows, a 7-bit Sum, a range filter) on
    the served path: the reference's answer, 60 groups walked, no
    prepass."""
    h = Holder(width=W)
    fields = [{"name": n, "type": "mutex", "options": {}}
              for n in ("edu", "gen", "dom")]
    fields.append({"name": "age", "type": "int",
                   "options": {"min": 0, "max": 127}})
    idx = _index(h, "able", fields)
    columns = {}
    for shard in range(SHARDS):
        part = {"edu": rng.integers(0, 6, W), "gen": rng.integers(0, 2, W),
                "dom": rng.integers(0, 5, W), "age": rng.integers(0, 128, W)}
        _load(idx, shard, part)
        for k, v in part.items():
            columns.setdefault(k, []).append(v)
    columns = {k: np.concatenate(v) for k, v in columns.items()}
    api = API(h)
    api.executor.enable_serving(cache_bytes=0)
    q = ("GroupBy(Rows(edu), Rows(gen), Rows(dom), filter=Row(age > 40), "
         "aggregate=Sum(field=age))")
    mp = pytest.MonkeyPatch()
    mp.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
    try:
        before = metrics.GROUPBY_WALKED.total()
        got = api.query("able", q)["results"][0]
        walked = metrics.GROUPBY_WALKED.total() - before
    finally:
        mp.undo()
    call = pql.parse(q)
    assert _canonical("GroupBy", got) == reference_ssb.answer(columns, call)
    assert walked == 60
