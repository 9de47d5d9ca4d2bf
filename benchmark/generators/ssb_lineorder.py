"""Lines first: one shard of the Star Schema Benchmark's lineorder,
denormalized, and its reference tables.

A configuration's ``params`` carry the seventeen fields
(``harness/fields.py``'s list) and, under ``values``, SSB's scale and
its dimension tables' cardinalities.  ``dimensions`` builds the
customer, supplier, part and date tables from the seed, as SSB's
dbgen draws them: uniform and independent.  ``lines`` draws one
shard's 2^20 lineorder rows (a customer, a supplier, a part, an order
date, a quantity and a discount each) and looks their attributes up
through the dimensions: one column a line, one small integer a field.
``make_shard`` turns the columns into the harness's hand-over forms
and into the shard's additive part of the reference's tables.

The reference answers the thirteen queries from joint histograms, one
per query flight, each cell with its row count and its measure's sum:

- ``f1``: order day x discount x quantity, ``lo_revenue_computed``;
- ``q2``: year x brand x supplier region, ``lo_revenue``;
- ``q3``: customer city x supplier city x month, ``lo_revenue``;
- ``q41``: year x customer nation x supplier region x manufacturer,
  ``lo_profit``;
- ``q42``: year x supplier nation x category x customer region,
  ``lo_profit``;
- ``q43``: year x supplier city x brand x customer region,
  ``lo_profit``.

A query's fields are each a function of one of its table's axes (a
city's nation and region, a month's year, a brand's category and
manufacturer), so a filter is a mask along each axis and a group a
sum over the others.  A shard's part of the large tables is sparse
(the cells its lines fall in), the sums dense.  ``Reference`` is numpy
over those tables and imports nothing of the program.

Before the load, ``make_shard`` of shard 0 asks the program the one
thing this deployment cannot run without (``probe``): a filtered
GroupBy of the shape of Q4.3 has to walk the combinations of the rows
its filter leaves, not the fields' whole product, by the program's own
count of the groups it walked.  A program that cannot say so ends the
run there, exit code 1 and one line on standard error, before its
per-combination paths would spend the run on Q4.3's 1.75 million
combinations.  ``tests/reference_ssb.py`` answers the same queries by
a pass over the per-line columns; ``benchmark/tests/test_ssb_reference.py``
holds the two equal.
"""

from __future__ import annotations

import os
import threading

import numpy as np

SHARD_WIDTH = 1 << 20          # the program's default; stated, not imported
_COLUMNS = np.arange(SHARD_WIDTH, dtype=np.int64)
_COLUMNS.setflags(write=False)  # every shard's column ids: handed over, never written
MEASURES = ("lo_revenue", "lo_profit", "lo_revenue_computed")


class Unanswerable(ValueError):
    """The reference tables hold no closed form for this query."""


# ---------------------------------------------------------------------------
# the dimensions
# ---------------------------------------------------------------------------

class Dimensions:
    """The seed's dimension tables: each customer's and supplier's
    city, each part's brand and retail price, each order day's year,
    month and week — and the hierarchy above them."""

    def __init__(self, values: dict, seed: int):
        v = values
        sf = v["scale_factor"]
        rng = np.random.default_rng([seed, 1 << 21])
        per = v["cities_per_nation"]
        self.region_of_nation = np.asarray(v["nation_region"], np.int32)
        nations = self.region_of_nation.size

        def cities(n):
            return (rng.integers(0, nations, n, dtype=np.int32) * per
                    + rng.integers(0, per, n, dtype=np.int32)).astype(np.uint8)

        self.cust_city = cities(v["customers_per_sf"] * sf)
        self.supp_city = cities(v["suppliers_per_sf"] * sf)
        n_parts = v["parts_base"] * int(1 + np.floor(np.log2(sf)))
        cats, brands = v["categories_per_mfgr"], v["brands_per_category"]
        self.part_brand = (rng.integers(0, 5 * cats, n_parts, dtype=np.int32)
                           * brands + rng.integers(0, brands, n_parts,
                                                   dtype=np.int32)
                           ).astype(np.uint16)
        key = np.arange(1, n_parts + 1, dtype=np.int64)
        self.part_price = (90000 + (key // 10) % 20001
                           + 100 * (key % 1000)).astype(np.int64)
        days = np.arange(np.datetime64(v["first_order_date"]),
                         np.datetime64(v["last_order_date"]) + 1)
        first_year = int(str(days[0].astype("datetime64[Y]")))
        year = days.astype("datetime64[Y]")
        self.day_year = (year.astype(np.int64) + 1970 - first_year
                         ).astype(np.uint8)
        self.day_month = (days.astype("datetime64[M]").astype(np.int64)
                          - (first_year - 1970) * 12).astype(np.uint8)
        self.day_week = ((days - year).astype(np.int64) // 7).astype(np.uint8)
        self.cats_per_mfgr = cats


_DIMS: dict = {}
_DIMS_LOCK = threading.Lock()


def dimensions(params: dict, seed: int) -> Dimensions:
    """The seed's dimension tables, built once a process."""
    key = (seed, repr(sorted(params["values"].items())))
    with _DIMS_LOCK:
        if key not in _DIMS:
            _DIMS[key] = Dimensions(params["values"], seed)
        return _DIMS[key]


def lines(params: dict, seed: int, shard: int) -> dict:
    """One shard's lines: {field name: array of 2^20 row ids (an int
    field: values)}."""
    v = params["values"]
    d = dimensions(params, seed)
    n = SHARD_WIDTH
    rng = np.random.default_rng([seed, shard])
    cust = rng.integers(0, d.cust_city.size, n)
    supp = rng.integers(0, d.supp_city.size, n)
    part = rng.integers(0, d.part_brand.size, n)
    day = rng.integers(0, d.day_year.size, n)
    qty = rng.integers(v["quantity"][0], v["quantity"][1] + 1, n)
    disc = rng.integers(v["discount"][0], v["discount"][1] + 1, n)
    # "day" is in no field: flight 1's table is kept by it
    t = {"day": day, "d_year": d.day_year[day],
         "d_yearmonthnum": d.day_month[day],
         "d_weeknuminyear": d.day_week[day]}
    for side, city in (("c", d.cust_city[cust]), ("s", d.supp_city[supp])):
        nation = (city // v["cities_per_nation"]).astype(np.uint8)
        t[f"{side}_city"], t[f"{side}_nation"] = city, nation
        t[f"{side}_region"] = d.region_of_nation[nation].astype(np.uint8)
    brand = d.part_brand[part]
    t["p_brand1"] = brand
    t["p_category"] = (brand // v["brands_per_category"]).astype(np.uint8)
    t["p_mfgr"] = (t["p_category"] // d.cats_per_mfgr).astype(np.uint8)
    price = d.part_price[part]
    extended = qty * price
    revenue = extended * (100 - disc) // 100
    t["lo_quantity"], t["lo_discount"] = qty, disc
    t["lo_revenue"] = revenue
    t["lo_profit"] = revenue - 6 * price // 10
    t["lo_revenue_computed"] = extended * disc
    return t


# ---------------------------------------------------------------------------
# the tables
# ---------------------------------------------------------------------------

# name: (measure, ((axis, rows), ...)), each axis a field of the lines
TABLES = {
    "f1": ("lo_revenue_computed", (("day", 2406), ("lo_discount", 11),
                                   ("lo_quantity", 50))),
    "q2": ("lo_revenue", (("d_year", 7), ("p_brand1", 1000),
                          ("s_region", 5))),
    "q3": ("lo_revenue", (("c_city", 250), ("s_city", 250),
                          ("d_yearmonthnum", 80))),
    "q41": ("lo_profit", (("d_year", 7), ("c_nation", 25), ("s_region", 5),
                          ("p_mfgr", 5))),
    "q42": ("lo_profit", (("d_year", 7), ("s_nation", 25),
                          ("p_category", 25), ("c_region", 5))),
    "q43": ("lo_profit", (("d_year", 7), ("s_city", 250), ("p_brand1", 1000),
                          ("c_region", 5))),
}
_DENSE_MAX = 1 << 16           # a part is dense up to so many cells


def table_cells(t: dict, axes, first_quantity: int) -> np.ndarray:
    cell = np.zeros(SHARD_WIDTH, np.int64)
    for name, rows in axes:
        col = t[name].astype(np.int64)
        if name == "lo_quantity":
            col = col - first_quantity
        cell = cell * rows + col
    return cell


def make_shard(params: dict, seed: int, shard: int):
    """({field: rows in its form}, the shard's part of the reference
    tables: {table: (cells or None where dense, counts, sums)})."""
    if shard == 0:
        probe()
    t = lines(params, seed, shard)
    rows = {}
    for f in params["fields"]:
        name = f["name"]
        if f["type"] == "int":
            rows[name] = (_COLUMNS, t[name])
        else:
            rows[name] = (t[name].astype(
                np.uint8 if f["rows"] <= 256 else np.uint16), _COLUMNS)
    tables = {}
    for name, (measure, axes) in TABLES.items():
        cell = table_cells(t, axes, params["values"]["quantity"][0])
        size = int(np.prod([r for _a, r in axes]))
        if size <= _DENSE_MAX:
            tables[name] = (None, np.bincount(cell, minlength=size),
                            np.bincount(cell, weights=t[measure],
                                        minlength=size).astype(np.int64))
        else:
            code, inv = np.unique(cell, return_inverse=True)
            tables[name] = (code, np.bincount(inv, minlength=code.size),
                            np.bincount(inv, weights=t[measure],
                                        minlength=code.size).astype(np.int64))
    return rows, tables


def add_tables(total, part):
    """{table: [counts, sums]}, each dense over the table's cells."""
    if total is None:
        total = {name: [np.zeros(int(np.prod([r for _a, r in axes])),
                                 np.int64) for _ in range(2)]
                 for name, (_m, axes) in TABLES.items()}
    for name, (code, count, amount) in part.items():
        if code is None:
            total[name][0] += count
            total[name][1] += amount
        else:                   # the cells are distinct
            total[name][0][code] += count
            total[name][1][code] += amount
    return total


def drop_columns(tables: dict, part: dict) -> dict:
    """The tables without one shard's part (the control's stale read)."""
    out = {name: [a.copy() for a in tab] for name, tab in tables.items()}
    for name, (code, count, amount) in part.items():
        at = slice(None) if code is None else code
        out[name][0][at] -= count
        out[name][1][at] -= amount
    return out


# ---------------------------------------------------------------------------
# the program's own statement: a pruned walk
# ---------------------------------------------------------------------------

_ARM = "PILOSA_TPU_GROUPBY_ONEPASS_ARM"


def probe() -> None:
    """A filtered GroupBy of two 64-row fields summing a 24-bit field,
    over 4,096 lines and through the program's own API: its filter
    leaves one row of the first field, so a walk of the rows it leaves
    is 64 groups and the fields' whole product 4,096.  The program's
    count of the groups it walked (pilosa_groupby_walked_groups_total)
    has to say the first; anything else ends the run."""
    from pilosa_tpu.api import API
    from pilosa_tpu.models import FieldOptions, FieldType, Holder
    from pilosa_tpu.obs import metrics

    h = Holder()
    idx = h.create_index("ssb_probe")
    idx.create_field("a", FieldOptions(type=FieldType.MUTEX))
    idx.create_field("b", FieldOptions(type=FieldType.MUTEX))
    idx.create_field("v", FieldOptions(type=FieldType.INT, min=0,
                                       max=(1 << 24) - 1))
    cols = np.arange(4096, dtype=np.int64)
    for name, rows in (("a", cols % 64), ("b", cols // 64)):
        idx.fields[name].view("standard", create=True).fragment(
            0, create=True).import_mutex(rows.astype(np.uint8), cols)
    v = idx.fields["v"]
    v.view(v.bsi_view, create=True).fragment(0, create=True).import_values(
        cols, cols * 4093, v.bit_depth)
    walked = getattr(metrics, "GROUPBY_WALKED", None)
    before = walked.total() if walked is not None else 0.0
    # the arm a TPU takes by itself, on any backend (a CPU interprets
    # it): the kernel's walk over a selection is what prunes
    arm = os.environ.get(_ARM)
    os.environ[_ARM] = "fused"
    try:
        got = API(h).query("ssb_probe", "GroupBy(Rows(a), Rows(b), "
                           "filter=Row(a=3), aggregate=Sum(field=v))")
    finally:
        if arm is None:
            os.environ.pop(_ARM, None)
        else:
            os.environ[_ARM] = arm
    groups = len(got["results"][0])
    moved = (walked.total() - before) if walked is not None else 0.0
    if groups != 64 or not 0 < moved <= 2 * groups:
        raise SystemExit(
            f"benchmark: this program walked {moved:g} groups for a "
            f"GroupBy whose filter leaves {groups} of 4,096 (a pruned walk "
            f"is at most {2 * groups}): Q4.3's 1.75 million combinations "
            f"would not be pruned")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _axis_attrs(values: dict) -> dict:
    """{axis: {field: the field's row id (a condition's value) along
    the axis}} — every field a query may name, as a function of the
    one axis of a table it depends on."""
    per, brands = values["cities_per_nation"], values["brands_per_category"]
    cats = values["categories_per_mfgr"]
    region = np.asarray(values["nation_region"], np.int64)
    days = np.arange(np.datetime64(values["first_order_date"]),
                     np.datetime64(values["last_order_date"]) + 1)
    first = int(str(days[0].astype("datetime64[Y]")))
    year = days.astype("datetime64[Y]")
    month = np.arange(80, dtype=np.int64)
    brand = np.arange(25 * brands, dtype=np.int64)
    out = {
        "day": {"d_year": year.astype(np.int64) + 1970 - first,
                "d_yearmonthnum": days.astype("datetime64[M]").astype(
                    np.int64) - (first - 1970) * 12,
                "d_weeknuminyear": (days - year).astype(np.int64) // 7},
        "lo_discount": {"lo_discount": np.arange(
            values["discount"][0], values["discount"][1] + 1)},
        "lo_quantity": {"lo_quantity": np.arange(
            values["quantity"][0], values["quantity"][1] + 1)},
        "d_year": {"d_year": np.arange(7)},
        "d_yearmonthnum": {"d_yearmonthnum": month, "d_year": month // 12},
        "p_brand1": {"p_brand1": brand, "p_category": brand // brands,
                     "p_mfgr": brand // brands // cats},
        "p_category": {"p_category": np.arange(25),
                       "p_mfgr": np.arange(25) // cats},
        "p_mfgr": {"p_mfgr": np.arange(5)},
    }
    for side in ("c", "s"):
        city = np.arange(region.size * per)
        out[f"{side}_city"] = {f"{side}_city": city,
                               f"{side}_nation": city // per,
                               f"{side}_region": region[city // per]}
        nation = np.arange(region.size)
        out[f"{side}_nation"] = {f"{side}_nation": nation,
                                 f"{side}_region": region}
        out[f"{side}_region"] = {f"{side}_region": np.arange(5)}
    return out


_CMP = {">": np.greater, "<": np.less, ">=": np.greater_equal,
        "<=": np.less_equal, "==": np.equal, "!=": np.not_equal}


class Reference:
    """Closed-form answers from the summed tables.

    ``answer(call)`` takes a ``harness.pql.Call`` and returns the
    canonical form ``harness.check.canonical`` gives a response: ``Sum``
    ``(value, count)``; ``GroupBy`` ``{(row ids): (count, sum or
    None)}``.  A filter is an ``Intersect`` of ``Row`` calls (a row, or
    conditions on an int field) and ``Union`` calls of rows of one
    field."""

    def __init__(self, params: dict, tables: dict):
        self.attrs = _axis_attrs(params["values"])
        self.tables = {name: (measure, axes,
                              [a.reshape([r for _x, r in axes])
                               for a in tables[name]])
                       for name, (measure, axes) in TABLES.items()}

    def _table(self, names: set, measure):
        for name, (m, axes, tabs) in self.tables.items():
            if measure is not None and m != measure:
                continue
            have = set()
            for axis, _r in axes:
                have |= set(self.attrs[axis])
            if names <= have:
                return axes, tabs
        raise Unanswerable(f"no table holds {sorted(names)} with {measure}")

    def _terms(self, call) -> list:
        """The filter as [(field, test on that field's values)]."""
        if call is None:
            return []
        if call.name == "Intersect" and not call.kwargs and not call.conds:
            return [t for a in call.args for t in self._terms(a)]
        if call.name == "Row" and not call.args:
            out = [(name, lambda x, r=row: x == r)
                   for name, row in call.kwargs.items()]
            out += [(name, lambda x, op=op, k=k: _CMP[op](x, k))
                    for name, op, k in call.conds]
            return out
        if call.name == "Union" and not call.kwargs and all(
                getattr(a, "name", None) == "Row" and len(a.kwargs) == 1
                and not a.conds and not a.args for a in call.args):
            names = {next(iter(a.kwargs)) for a in call.args}
            if len(names) == 1:
                rows = [next(iter(a.kwargs.values())) for a in call.args]
                return [(names.pop(), lambda x, r=rows: np.isin(x, r))]
        raise Unanswerable(f"filter part {call}")

    def _select(self, group: list, filt, measure):
        """(counts, sums) over the named group fields' rows, in their
        order, of the lines the filter selects."""
        terms = self._terms(filt)
        axes, tabs = self._table(set(group) | {n for n, _t in terms},
                                 measure)
        keep, key_of = [], {}
        for i, (axis, rows) in enumerate(axes):
            ok = np.ones(rows, bool)
            for name, test in terms:
                if name in self.attrs[axis]:
                    ok &= test(self.attrs[axis][name])
            keep.append(np.flatnonzero(ok))
            for g in group:
                if g in self.attrs[axis]:
                    key_of[g] = i
        sub = [t[np.ix_(*keep)] for t in tabs]
        if not group:
            return [t.sum() for t in sub]
        at = [key_of[g] for g in group]
        rest = tuple(i for i in range(len(axes)) if i not in at)
        sub = [np.transpose(t.sum(axis=rest, keepdims=True),
                            at + list(rest)).reshape(
                                [keep[i].size for i in at]) for t in sub]
        shape = [int(self.attrs[axes[i][0]][g].max()) + 1
                 for g, i in zip(group, at)]
        out = [np.zeros(shape, np.int64) for _ in range(2)]
        index = np.ix_(*(self.attrs[axes[i][0]][g][keep[i]]
                         for g, i in zip(group, at)))
        for o, t in zip(out, sub):
            np.add.at(o, index, t)
        return out

    def answer(self, call):
        if call.name == "Sum":
            measure = call.kwargs.get("field")
            if measure not in MEASURES or len(call.args) > 1:
                raise Unanswerable(f"Sum {call}")
            count, total = self._select(
                [], call.args[0] if call.args else None, measure)
            return int(total), int(count)
        if call.name != "GroupBy":
            raise Unanswerable(f"call {call.name}")
        group = []
        for a in call.args:
            if getattr(a, "name", None) != "Rows" or len(a.args) != 1 \
                    or a.kwargs:
                raise Unanswerable(f"GroupBy over {a}")
            group.append(a.args[0])
        if len(set(group)) != len(group) or not group:
            raise Unanswerable("GroupBy over a field twice, or none")
        if set(call.kwargs) - {"filter", "aggregate"}:
            raise Unanswerable(f"GroupBy with {sorted(call.kwargs)}")
        agg = call.kwargs.get("aggregate")
        if agg is not None and (agg.name != "Sum" or agg.args
                                or agg.kwargs.get("field") not in MEASURES):
            raise Unanswerable(f"aggregate {agg}")
        counts, sums = self._select(group, call.kwargs.get("filter"),
                                    agg.kwargs["field"] if agg else None)
        return {tuple(int(i) for i in ids): (
            int(counts[ids]), int(sums[ids]) if agg is not None else None)
            for ids in zip(*np.nonzero(counts))}
