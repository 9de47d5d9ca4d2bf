"""``taxi_trips``: the reference's histogram form against the per-trip
form (``tests/reference_taxi.py``: one numpy pass over the trips a
query) at the rehearsal size, for every template of the dashboard mix
under every form of its filter; what the tables cannot answer is
refused; a stale shard changes the answers; the bytes a query is
priced at follow the twenty-field list.
"""

import importlib.util
import os

import numpy as np
import pytest
from harness import bytes_model, pql, schedule, server

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2147483700


def _per_trip():
    spec = importlib.util.spec_from_file_location(
        "reference_taxi", os.path.join(ROOT, "tests", "reference_taxi.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def world():
    config = server.load_json("configs", "taxi-1b.json")
    params = config["params"]
    gen = server.load_module("generators", config["generator"])
    tables, columns, parts = None, {}, []
    for shard in range(params["rehearsal_shards"]):
        _rows, part = gen.make_shard(params, SEED, shard)
        parts.append(part)
        tables = gen.add_tables(tables, part)
        for name, col in gen.trips(params, SEED, shard).items():
            columns.setdefault(name, []).append(col)
    columns = {k: np.concatenate(v) for k, v in columns.items()}
    return params, gen, tables, parts, columns


def test_histogram_form_equals_per_trip_form(world):
    params, gen, tables, _parts, columns = world
    mix = server.load_json("traffic", "dashboard-q1-4.json")
    ref, per_trip = gen.Reference(params, tables), _per_trip()
    asked = list(schedule.warm_sequential(mix, 3))
    asked += schedule.build(mix, 3, schedule.WINDOW, 1.0)[0][:80]
    seen = set()
    for item in asked:
        call = pql.parse(item["q"])
        assert ref.answer(call) == per_trip.answer(columns, call), item["q"]
        seen.add(item["t"])
    assert seen == {t["name"] for t in mix["templates"]}
    # unfiltered, and a filter on one field alone
    for q in ("TopN(cab_type, n=2)",
              "GroupBy(Rows(passenger_count), Rows(pickup_year))",
              "GroupBy(Rows(dist_miles), filter=Row(pickup_time=17))",
              "GroupBy(Rows(pickup_year), filter=Row(pickup_day=2), "
              "aggregate=Sum(field=total_amount_dollars))"):
        call = pql.parse(q)
        assert ref.answer(call) == per_trip.answer(columns, call), q


def test_reference_refuses_what_its_tables_cannot_answer(world):
    params, gen, tables, _parts, _columns = world
    ref = gen.Reference(params, tables)
    for q in ("Count(Row(pickup_month=1))",
              "GroupBy(Rows(speed_mph))",
              "GroupBy(Rows(passenger_count), filter=Row(dropoff_day=1))",
              "GroupBy(Rows(passenger_count), filter=Intersect("
              "Row(pickup_time=1), Row(total_amount_dollars > 5)))",
              "GroupBy(Rows(passenger_count), filter=Intersect("
              "Row(pickup_grid_id=0), Row(pickup_month=1)))",
              "GroupBy(Rows(passenger_count), "
              "filter=Row(total_amount_dollars > 61))",
              "TopN(cab_type, Union(Row(pickup_month=1), "
              "Row(pickup_month=2)), n=2)",
              "GroupBy(Rows(passenger_count), "
              "aggregate=Min(field=total_amount_dollars))"):
        with pytest.raises(ValueError, match="."):     # Unanswerable
            ref.answer(pql.parse(q))


def test_a_stale_shard_changes_the_answers(world):
    params, gen, tables, parts, _columns = world
    ref = gen.Reference(params, tables)
    stale = gen.Reference(params, gen.drop_columns(tables, parts[-1]))
    q = pql.parse("TopN(cab_type, Intersect(Row(pickup_month=1), "
                  "Row(pickup_time=17)), n=2)")
    assert all(s[1] < r[1] for s, r in zip(stale.answer(q), ref.answer(q)))
    # drop_columns left the summed tables as they were
    assert gen.Reference(params, tables).answer(q) == ref.answer(q)


def test_necessary_bytes_follow_the_twenty_fields(world):
    params = world[0]
    kinds = lambda q: sorted(p[0] for p in bytes_model.planes(  # noqa: E731
        pql.parse(q), params))
    f = "Intersect(Row(pickup_month=1), Row(total_amount_dollars > 9))"
    # Q4: 4 + 3 + 6 code planes, one valid plane, one filter row and
    # the amount's 2 + 9 planes for the range
    q4 = kinds("GroupBy(Rows(passenger_count), Rows(pickup_year), "
               f"Rows(dist_miles), filter={f})")
    assert (q4.count("code"), q4.count("valid"), q4.count("row"),
            q4.count("bsi")) == (13, 1, 1, 11)
    q2 = kinds("GroupBy(Rows(passenger_count), filter=Row(pickup_day=1), "
               "aggregate=Sum(field=total_amount_dollars))")
    assert (q2.count("code"), q2.count("bsi"), q2.count("row")) == (4, 11, 1)
    q1 = kinds("TopN(cab_type, Intersect(Row(pickup_grid_id=4646), "
               "Row(pickup_month=1)), n=2)")
    assert q1 == ["row"] * 4            # cab_type's two rows + two filter rows
    assert bytes_model.necessary_bytes(
        pql.parse("TopN(cab_type, Row(pickup_month=1), n=2)"), params,
        params["shards"]) == 3 * params["shards"] * 131072
