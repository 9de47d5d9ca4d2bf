"""``ssb_lineorder``: the reference's table form against the per-line
form (``tests/reference_ssb.py``: one numpy pass over the lines a
query) on two of the generator's shards, for every template of the
flights mix; what the tables cannot answer is refused; a stale shard
changes the answers and, through ``benchmark/run.py`` at the
rehearsal size, turns ``correct`` false; the GroupBy reader prices
the GroupBy requests alone.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from harness import bytes_model, pql, schedule, server

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SEED = 2147483705


def _per_line():
    spec = importlib.util.spec_from_file_location(
        "reference_ssb", os.path.join(ROOT, "tests", "reference_ssb.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def world():
    config = server.load_json("configs", "ssb-sf100.json")
    params = config["params"]
    gen = server.load_module("generators", config["generator"])
    gen.probe = lambda: None            # the program is not what is tested
    tables, columns, parts = None, {}, []
    for shard in range(2):
        _rows, part = gen.make_shard(params, SEED, shard)
        parts.append(part)
        tables = gen.add_tables(tables, part)
        for name, col in gen.lines(params, SEED, shard).items():
            columns.setdefault(name, []).append(col)
    columns = {k: np.concatenate(v) for k, v in columns.items()}
    return params, gen, tables, parts, columns


def test_table_form_equals_per_line_form(world):
    params, gen, tables, _parts, columns = world
    mix = server.load_json("traffic", "ssb-flights-1-4.json")
    ref, per_line = gen.Reference(params, tables), _per_line()
    asked = list(schedule.warm_sequential(mix, 3))
    asked += schedule.build(mix, 3, schedule.WINDOW, 1.0)[0][:52]
    seen = set()
    for item in asked:
        call = pql.parse(item["q"])
        assert ref.answer(call) == per_line.answer(columns, call), item["q"]
        seen.add(item["t"])
    assert seen == {t["name"] for t in mix["templates"]}
    # unfiltered, count-only, and a filter on one field alone
    for q in ("Sum(field=lo_revenue_computed)",
              "GroupBy(Rows(d_year), Rows(c_region))",
              "GroupBy(Rows(p_mfgr), filter=Row(s_region=2), "
              "aggregate=Sum(field=lo_profit))"):
        call = pql.parse(q)
        assert ref.answer(call) == per_line.answer(columns, call), q


def test_reference_refuses_what_its_tables_cannot_answer(world):
    params, gen, tables, _parts, _columns = world
    ref = gen.Reference(params, tables)
    for q in ("Count(Row(d_year=1))",
              "GroupBy(Rows(c_city), Rows(p_brand1))",
              "GroupBy(Rows(d_year), filter=Row(lo_quantity > 3), "
              "aggregate=Sum(field=lo_revenue))",
              "Sum(Row(p_mfgr=1), field=lo_revenue_computed)",
              "GroupBy(Rows(d_year), filter=Union(Row(d_year=1), "
              "Row(c_region=1)), aggregate=Sum(field=lo_revenue))",
              "GroupBy(Rows(d_year), aggregate=Min(field=lo_revenue))"):
        with pytest.raises(ValueError, match="."):     # Unanswerable
            ref.answer(pql.parse(q))


def test_a_stale_shard_changes_the_answers(world):
    params, gen, tables, parts, _columns = world
    ref = gen.Reference(params, tables)
    stale = gen.Reference(params, gen.drop_columns(tables, parts[-1]))
    q = pql.parse("GroupBy(Rows(d_year), Rows(c_nation), filter=Intersect("
                  "Row(c_region=1), Row(s_region=1), Union(Row(p_mfgr=0), "
                  "Row(p_mfgr=1))), aggregate=Sum(field=lo_profit))")
    want, old = ref.answer(q), stale.answer(q)
    assert want.keys() == old.keys()
    assert all(old[k][0] < want[k][0] for k in want)
    assert gen.Reference(params, tables).answer(q) == want


def test_the_control_turns_correct_false_at_the_rehearsal_size():
    """``--control stale-shard`` on ``--rehearse-cpu``: the last
    shard's import never reaches the server while the reference counts
    it.  The scatter arm stands in for the kernel here (the rehearsal
    interprets the kernel, minutes a GroupBy at 2^20 columns); what is
    tested is the comparison."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PILOSA_TPU_GROUPBY_ONEPASS_ARM"] = "xla"
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "ssb-sf100.flights", "--seed", "2147483777", "--seconds", "3",
         "--trace", "0", "--rehearse-cpu", "--control", "stale-shard"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    wrong = next(r for r in result["compared"] if r["name"] == "wrong")
    assert wrong["value"] > 0 and not wrong["ok"]


def test_the_groupby_reader_prices_the_groupby_requests_alone(world):
    """A GroupBy, a Sum and a TopN each execute wholly inside the trace:
    the reader counts the GroupBy's bytes, readers/trace.py all
    three's."""
    params = world[0]
    config = server.load_json("configs", "ssb-sf100.json")
    qs = ["GroupBy(Rows(d_year), Rows(p_brand1), filter=Row(s_region=1), "
          "aggregate=Sum(field=lo_revenue))",
          "Sum(Row(d_year=2), field=lo_revenue_computed)",
          "TopN(c_region, Row(d_year=2), n=2)"]
    records = [{"client": 0, "seq": i, "send": 100.0 + i,
                "recv": 100.5 + i} for i in range(3)]
    flights = [{"start": 100.0005 + i, "duration_ms": 450.0,
                "route": "direct", "phases": {"execute": 300.0}}
               for i in range(3)]
    ctx = {"traced_wall": [99.0, 104.0], "flights": flights,
           "records": records, "ok": [True] * 3,
           "plans": [[{"t": "t", "q": q} for q in qs]], "config": config,
           "shards": 287, "peaks": {"hbm_bytes_per_s": 819e9},
           "cell": {"chips": 1},
           "trace": {"ops": {0: [("%groupby_fused_sum.3", 0, 10 ** 8),
                                 ("%fusion.1", 0, 10 ** 9)]},
                     "busy_s": 1.0, "window_s": 5.0}}
    reader = server.load_module("readers", "trace_ops_groupby")
    each = [bytes_model.necessary_bytes(pql.parse(q), params, 287)
            for q in qs]
    assert reader.groupby_bytes(ctx) == pytest.approx(each[0])
    assert server.load_module("readers", "trace").served_bytes(ctx) \
        == pytest.approx(sum(each))
    assert reader.read(ctx, {"ops": "groupby_fused_(sum|passes)"}) \
        == pytest.approx(100 * each[0] / 819e9 / 0.1)
    ctx["plans"] = [[{"t": "t", "q": q} for q in qs[1:] + qs[1:2]]]
    assert reader.read(ctx, {"ops": "groupby_fused_(sum|passes)"}) is None
