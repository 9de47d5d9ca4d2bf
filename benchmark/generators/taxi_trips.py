"""Trips first: one shard of the taxi index and its reference tables.

A configuration's ``params`` carry the twenty fields
(``harness/fields.py``'s list, each with the ``form`` it is handed over
in) and, under ``values``, the parameters of the distributions.
``trips`` draws one shard's 2^20 trips as per-trip columns, one small
integer a field: skewed (one passenger in 7 trips of 10, Zipf grid
cells, most trips under 5 miles) and correlated (duration and speed
from distance, the drop-off from the pick-up plus the duration, the
amount from distance and duration, the weekday from the date).
``make_shard`` turns the columns into the harness's three hand-over
forms and into the shard's additive part of the reference's tables.

The reference cannot answer a window's hundreds of responses by a pass
over 276 million trips each, so it answers from joint histograms, one
per filter family of the dashboard's slicers x the group cell
(cab_type 2 x passenger_count 10 x pickup_year 8 x dist_miles 60 =
9,600), each with the count and the amount's sum:

- ``mdt``: pickup_month x pickup_day x pickup_time (4,032 filter
  cells; a filter on two of the three sums over the third);
- ``gm``: hot grid cell x pickup_month (64 x 12; a trip outside the
  hot cells is in no filter this table answers);
- ``ma``: pickup_month x amount bucket (12 x 62; bucket = min(amount,
  61), so ``amount > k`` for k <= 60 is a sum of buckets).

A shard's part is sparse (the cells its trips fall in), the sum dense.
``Reference`` is numpy over those tables and imports nothing of the
program.

The configuration also states the host its node runs on.  Before the
load, ``make_shard`` of shard 0 hands that shard's fields to scratch
fragments of the program and reads their own accounting
(``Fragment.memory_bytes``): a program whose host rows for one shard
pass ``host.fragment_bytes_per_shard_max`` cannot hold the node's
shards on that host, and the run ends there with no result, before a
machine's memory limit ends it later (``host_bytes``).  ``tests/reference_taxi.py`` answers the same queries by a
pass over the per-trip columns; ``benchmark/tests/test_taxi_reference.py``
holds the two equal.
"""

from __future__ import annotations

import numpy as np

SHARD_WIDTH = 1 << 20          # the program's default; stated, not imported
_COLUMNS = np.arange(SHARD_WIDTH, dtype=np.int64)
_COLUMNS.setflags(write=False)  # every shard's column ids: handed over, never written
GROUP = ("cab_type", "passenger_count", "pickup_year", "dist_miles")
GROUP_ROWS = (2, 10, 8, 60)
GROUP_CELLS = 2 * 10 * 8 * 60
AMOUNT = "total_amount_dollars"
AMOUNT_BUCKETS = 62            # 0..60 and "61 or more"
MONTH_DAYS = np.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])
FAMILY_CELLS = {"mdt": 12 * 7 * 48, "gm": 64 * 12, "ma": 12 * AMOUNT_BUCKETS}


class Unanswerable(ValueError):
    """The reference tables hold no closed form for this query."""


def ranking(values: dict, seed: int) -> np.ndarray:
    """The seed's ranking of the grid cells, best first: the hot
    cells in a drawn order, then the others in a drawn order."""
    rng = np.random.default_rng([seed, 1 << 20])
    hot = np.asarray(values["hot_cells"], dtype=np.int32)
    rest = np.setdiff1d(np.arange(values["grid_side"] ** 2, dtype=np.int32),
                        hot)
    return np.concatenate([rng.permutation(hot), rng.permutation(rest)])


def _time_probs(values: dict) -> np.ndarray:
    t = np.arange(48, dtype=np.float64)
    p = np.full(48, values["time_base"])
    for centre, width, height in (values["time_morning_peak"],
                                  values["time_evening_peak"]):
        d = np.minimum(np.abs(t - centre), 48 - np.abs(t - centre))
        p += height * np.exp(-0.5 * (d / width) ** 2)
    return p / p.sum()


def _elevation(values: dict) -> np.ndarray:
    """Every cell's elevation row."""
    side = values["grid_side"]
    cell = np.arange(side * side, dtype=np.int32)
    r, c = cell // side - side // 2, cell % side - side // 2
    return np.minimum((r * r + c * c) // values["elevation_divisor"], 119)


class _Calendar:
    """Day number (from 2009-01-01, a Thursday; 365-day years) to
    year row, month, day of month; weekday 0 is Monday."""

    def __init__(self):
        day = np.arange(8 * 365, dtype=np.int32)
        doy = day % 365
        self.month_start = np.concatenate(
            [[0], np.cumsum(MONTH_DAYS)[:-1]]).astype(np.int32)
        self.year = day // 365
        self.month = (np.searchsorted(self.month_start, doy, side="right")
                      - 1).astype(np.int32)
        self.mday = doy - self.month_start[self.month]


_CALENDAR = _Calendar()


def trips(params: dict, seed: int, shard: int) -> dict:
    """One shard's trips: {field name: int32 array of 2^20 row ids
    (the amount: values)}."""
    v = params["values"]
    n = SHARD_WIDTH
    rng = np.random.default_rng([seed, shard])
    side, cal, i32 = v["grid_side"], _CALENDAR, np.int32

    def pick(probs):
        return np.searchsorted(np.cumsum(probs), rng.random(n, np.float32)
                               ).astype(i32)

    def share(limit):           # uniform integers below a per-trip limit
        return (rng.random(n, np.float32) * limit).astype(i32)

    t = {}
    year = np.minimum(pick(v["year_probs"]), 7)
    month = share(np.where(year == 7, i32(v["last_year_months"]), i32(12)))
    mday = share(MONTH_DAYS.astype(i32)[month])
    day_no = year * i32(365) + cal.month_start[month] + mday
    time = np.minimum(pick(_time_probs(v)), 47)
    t["pickup_year"], t["pickup_month"], t["pickup_mday"] = year, month, mday
    t["pickup_day"], t["pickup_time"] = (day_no + i32(3)) % i32(7), time
    t["cab_type"] = ((year >= 4) & (rng.random(n, np.float32) < v[
        "cab_green_share_from_year_4"])).astype(i32)
    t["passenger_count"] = np.minimum(pick(v["passenger_count_probs"]), 9)

    cells = side * side
    zipf = 1.0 / np.arange(1, cells + 1) ** v["grid_zipf_s"]
    rank = np.minimum(pick(zipf / zipf.sum()), cells - 1)
    cell = ranking(v, seed)[rank]
    elevation = _elevation(v)
    t["pickup_grid_id"], t["pickup_elevation"] = cell, elevation[cell]

    dist = rng.standard_exponential(n, np.float32) * np.float32(
        v["dist_mean_miles"])
    far = rng.random(n, np.float32) < v["dist_tail_share"]
    dist = np.where(far, v["dist_tail_from"] + share(
        i32(60 - v["dist_tail_from"])), np.minimum(dist, 59).astype(i32))
    rush = np.where((np.abs(time - 17) <= 2) | (np.abs(time - 37) <= 3),
                    np.float32(v["minutes_rush_factor"]), np.float32(1.0))
    shape = v["minutes_noise_shape"]
    minutes = ((np.float32(v["minutes_base"])
                + np.float32(v["minutes_per_mile"]) * dist) * rush
               * rng.standard_gamma(shape, n, np.float32)
               * np.float32(1.0 / shape))
    duration = np.minimum(minutes, 179).astype(i32)
    t["dist_miles"], t["duration_minutes"] = dist, duration
    t["speed_mph"] = np.minimum(dist * i32(60) // np.maximum(duration, 1), 79)

    end = time * i32(30) + share(i32(30)) + duration
    drop_day = np.minimum(day_no + end // i32(1440), 8 * 365 - 1)
    t["dropoff_year"], t["dropoff_month"] = cal.year[drop_day], \
        cal.month[drop_day]
    t["dropoff_mday"], t["dropoff_day"] = cal.mday[drop_day], \
        (drop_day + i32(3)) % i32(7)
    t["dropoff_time"] = end % i32(1440) // i32(30)
    theta = rng.random(n, np.float32) * np.float32(2 * np.pi)
    step = dist * np.float32(v["cells_per_mile"])
    drop = (np.clip(cell // side + np.rint(step * np.sin(theta)).astype(i32),
                    0, side - 1) * side
            + np.clip(cell % side + np.rint(step * np.cos(theta)).astype(i32),
                      0, side - 1)).astype(i32)
    t["dropoff_grid_id"], t["dropoff_elevation"] = drop, elevation[drop]
    t[AMOUNT] = np.clip(
        np.float32(v["fare_base"]) + np.float32(v["fare_per_mile"]) * dist
        + np.float32(v["fare_per_minute"]) * duration
        + rng.standard_exponential(n, np.float32) * np.float32(v["tip_mean"]),
        0, 500).astype(i32)
    return t


def group_cell(t: dict) -> np.ndarray:
    """Each trip's cell of the four group dimensions (cab_type
    slowest, dist_miles fastest)."""
    cell = np.zeros_like(t[GROUP[0]])
    for name, rows in zip(GROUP, GROUP_ROWS):
        cell = cell * rows + t[name]
    return cell


def filter_cells(t: dict, hot_rank: np.ndarray) -> dict:
    """Each trip's cell in every filter family, -1 where it is in no
    filter the family answers.  `hot_rank`: cell id -> index among
    the hot cells, -1 elsewhere."""
    g = hot_rank[t["pickup_grid_id"]]
    return {
        "mdt": (t["pickup_month"] * 7 + t["pickup_day"]) * 48
        + t["pickup_time"],
        "gm": np.where(g >= 0, g * 12 + t["pickup_month"], -1),
        "ma": t["pickup_month"] * AMOUNT_BUCKETS
        + np.minimum(t[AMOUNT], AMOUNT_BUCKETS - 1),
    }


def _hot_rank(values: dict) -> np.ndarray:
    out = np.full(values["grid_side"] ** 2, -1, dtype=np.int32)
    out[np.asarray(values["hot_cells"])] = np.arange(len(values["hot_cells"]))
    return out


def _pack(bits) -> np.ndarray:
    return np.packbits(bits, bitorder="little").view(np.uint32)


def make_shard(params: dict, seed: int, shard: int):
    """({field: rows in its ``form``}, the shard's sparse part of the
    reference tables: {family: (cells, counts, amount sums)})."""
    t = trips(params, seed, shard)
    rows = {}
    for f in params["fields"]:
        name, form = f["name"], f["form"]
        if form == "words":
            d = t[name].astype(np.uint8)     # at most 10 rows
            rows[name] = {r: _pack(d == r) for r in range(f["rows"])}
        elif form == "ids":     # in the smallest type that holds a row id
            rows[name] = (t[name].astype(
                np.uint8 if f["rows"] <= 256 else np.uint16), _COLUMNS)
        else:                   # "values"
            rows[name] = (_COLUMNS, t[name])
    if shard == 0:
        need, limit = host_bytes(params, rows), params["host"][
            "fragment_bytes_per_shard_max"]
        if need > limit:
            raise SystemExit(
                f"benchmark: this program holds one shard's fields in "
                f"{need} bytes of host rows; {params['shards']} shards at "
                f"more than {limit} do not fit the deployment's host "
                f"({params['host']['memory_bytes']} bytes)")
    cell = group_cell(t)
    tables = {}
    for fam, fc in filter_cells(t, _hot_rank(params["values"])).items():
        keep = fc >= 0
        code, inv = np.unique(fc[keep] * np.int32(GROUP_CELLS) + cell[keep],
                              return_inverse=True)
        tables[fam] = (code, np.bincount(inv, minlength=code.size),
                       np.bincount(inv, weights=t[AMOUNT][keep],
                                   minlength=code.size).astype(np.int64))
    return rows, tables


def host_bytes(params: dict, rows: dict) -> int:
    """What the program's fragments hold on the host for one shard's
    fields, by their own accounting, loaded the way the harness loads
    them (``harness/server.py:load``)."""
    from pilosa_tpu.models.fragment import Fragment
    total = 0
    for f in params["fields"]:
        frag = Fragment(params["index"], f["name"], "probe", 0,
                        width=SHARD_WIDTH)
        data = rows[f["name"]]
        if isinstance(data, dict):
            for r, w in data.items():
                frag.import_row_words(r, w)
        elif f["type"] == "int":
            lo, hi = f["options"]["min"], f["options"]["max"]
            frag.import_values(*data, max(abs(lo), abs(hi)).bit_length())
        else:
            frag.import_mutex(*data)
        total += frag.memory_bytes()
    return total


def add_tables(total, part):
    """{family: [counts, amount sums]}, each over family cells x
    group cells."""
    if total is None:
        total = {fam: [np.zeros(n * GROUP_CELLS, dtype=np.int64)
                       for _ in range(2)]
                 for fam, n in FAMILY_CELLS.items()}
    for fam, (code, count, amount) in part.items():
        np.add.at(total[fam][0], code, count)
        np.add.at(total[fam][1], code, amount)
    return total


def drop_columns(tables: dict, part: dict) -> dict:
    """The tables without one shard's part (the control's stale read)."""
    out = {fam: [a.copy() for a in tab] for fam, tab in tables.items()}
    for fam, (code, count, amount) in part.items():
        np.subtract.at(out[fam][0], code, count)
        np.subtract.at(out[fam][1], code, amount)
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

class Reference:
    """Closed-form answers from the summed tables.

    ``answer(call)`` takes a ``harness.pql.Call`` and returns the
    canonical form ``harness.check.canonical`` gives a response:
    ``TopN`` ``[(row id, count), ...]``; ``GroupBy``
    ``{(row ids): (count, aggregate or None)}``.  The bitmap argument
    is an ``Intersect`` of ``Row`` calls of one filter family.
    """

    def __init__(self, params: dict, tables: dict):
        self.hot = _hot_rank(params["values"])
        self.amount = AMOUNT
        shapes = {"mdt": (12, 7, 48), "gm": (64, 12),
                  "ma": (12, AMOUNT_BUCKETS)}
        # [counts, amount sums], each (filter cells..., group cells...)
        self.tables = {fam: [a.reshape(*shape, *GROUP_ROWS)
                             for a in tables[fam]]
                       for fam, shape in shapes.items()}

    def _selected(self, call) -> np.ndarray:
        """The (2, cab, passengers, year, miles) table of the trips a
        filter selects: counts and amount sums."""
        if call is None:
            return np.stack([a.sum(axis=(0, 1, 2))
                             for a in self.tables["mdt"]])
        rows = [call] if call.name == "Row" else call.args
        if call.name not in ("Row", "Intersect") or call.kwargs and \
                call.name == "Intersect":
            raise Unanswerable(f"bitmap call {call.name}")
        eq, above = {}, None
        for r in rows:
            if getattr(r, "name", None) != "Row" or r.args:
                raise Unanswerable(f"filter part {r}")
            for name, op, k in r.conds:
                if name != self.amount or op != ">" or above is not None \
                        or not 0 <= k < AMOUNT_BUCKETS - 1:
                    raise Unanswerable(f"condition {name} {op} {k}")
                above = k
            for name, row in r.kwargs.items():
                if name in eq:
                    raise Unanswerable(f"two rows of {name}")
                eq[name] = row
        dims = {"mdt": ("pickup_month", "pickup_day", "pickup_time"),
                "gm": ("pickup_grid_id", "pickup_month"),
                "ma": ("pickup_month",)}
        fam = ("ma" if above is not None else
               "gm" if "pickup_grid_id" in eq else "mdt")
        if set(eq) - set(dims[fam]):
            raise Unanswerable(f"filter on {sorted(eq)}")
        shape = self.tables[fam][0].shape
        index = []
        for name in dims[fam]:
            if name not in eq:
                index.append(slice(None))
                continue
            row = eq[name]
            if name == "pickup_grid_id":
                if not 0 <= row < self.hot.size or self.hot[row] < 0:
                    raise Unanswerable(f"grid cell {row} is not a hot cell")
                row = int(self.hot[row])
            if not 0 <= row < shape[len(index)]:
                return np.zeros((2, *GROUP_ROWS), dtype=np.int64)
            index.append(slice(row, row + 1))
        if fam == "ma":
            index.append(slice(above + 1, None))
        over = tuple(range(len(shape) - 4))
        return np.stack([a[tuple(index)].sum(axis=over)
                         for a in self.tables[fam]])

    def answer(self, call):
        if call.name == "TopN":
            return self._topn(call)
        if call.name == "GroupBy":
            return self._groupby(call)
        raise Unanswerable(f"call {call.name}")

    def _topn(self, call):
        name = call.args[0]
        if name not in GROUP:
            raise Unanswerable(f"TopN of {name}")
        if len(call.args) > 2:
            raise Unanswerable(f"TopN with {len(call.args) - 1} bitmaps")
        counts = self._selected(
            call.args[1] if len(call.args) > 1 else None)[0]
        axis = GROUP.index(name)
        per_row = counts.sum(axis=tuple(a for a in range(4) if a != axis))
        pairs = sorted(((r, int(c)) for r, c in enumerate(per_row) if c > 0),
                       key=lambda p: (-p[1], p[0]))
        n = call.kwargs.get("n")
        return pairs[:n] if n else pairs

    def _groupby(self, call):
        axes = []
        for a in call.args:
            if getattr(a, "name", None) != "Rows" or len(a.args) != 1 \
                    or a.args[0] not in GROUP or a.kwargs:
                raise Unanswerable(f"GroupBy over {a}")
            axes.append(GROUP.index(a.args[0]))
        if len(set(axes)) != len(axes) or not axes:
            raise Unanswerable("GroupBy over a field twice, or none")
        agg = call.kwargs.get("aggregate")
        if agg is not None and (agg.name != "Sum"
                                or agg.kwargs.get("field") != self.amount):
            raise Unanswerable(f"aggregate {agg}")
        if set(call.kwargs) - {"filter", "aggregate"}:
            raise Unanswerable(f"GroupBy with {sorted(call.kwargs)}")
        sel = self._selected(call.kwargs.get("filter"))
        rest = tuple(1 + a for a in range(4) if a not in axes)
        # the fields in the order the query names them
        tab = np.transpose(sel.sum(axis=rest, keepdims=True),
                           [0] + [1 + a for a in axes] + list(rest))
        tab = tab.reshape(2, *(GROUP_ROWS[a] for a in axes))
        out = {}
        for ids in zip(*np.nonzero(tab[0])):
            out[tuple(int(i) for i in ids)] = (
                int(tab[0][ids]), int(tab[1][ids]) if agg is not None else None)
        return out
