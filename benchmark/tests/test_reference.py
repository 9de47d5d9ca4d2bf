"""The reference tables against brute force over the columns, on two
shards, for every template of every traffic file."""

import glob
import json
import os

import numpy as np
import pytest
from harness import bytes_model, pql, schedule, server

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))
SHARDS = 2


def _load(mix_path):
    with open(mix_path) as f:
        mix = json.load(f)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg_name = next(w["config"] for w in bench["workloads"]
                    if w["traffic"] == mix["name"])
    config = server.load_json("configs", f"{cfg_name}.json")
    gen = server.load_module("generators", config["generator"])
    return mix, config, gen


class Brute:
    """Every record as a column; a query evaluated record by record."""

    def __init__(self, params, gen, seed):
        self.params = params
        self.cols = {}
        tables = None
        parts = []
        for shard in range(SHARDS):
            rows, part = gen.make_shard(params, seed, shard)
            parts.append(rows)
            tables = gen.add_tables(tables, part)
        self.tables = tables
        unpack = lambda w: np.unpackbits(  # noqa: E731
            w.view(np.uint8), bitorder="little").astype(bool)
        for f in (*params["plain"], *params["categorical"]):
            ids = parts[0][f["name"]].keys()
            self.cols[f["name"]] = {r: np.concatenate(
                [unpack(p[f["name"]][r]) for p in parts]) for r in ids}
        bsi = params["bsi"]
        self.value = sum(
            np.concatenate([unpack(p[bsi["name"]][2 + b]) for p in parts])
            .astype(np.int64) << b for b in range(bsi["depth"]))
        self.n = len(self.value)

    def bitmap(self, call):
        if call.name == "Row":
            if call.conds:
                (_f, op, k), = call.conds
                return {">": self.value > k, "<": self.value < k}[op]
            (f, r), = call.kwargs.items()
            return self.cols[f].get(r, np.zeros(self.n, dtype=bool))
        parts = [self.bitmap(a) for a in call.args]
        out = parts[0]
        for p in parts[1:]:
            out = {"Intersect": out & p, "Union": out | p, "Xor": out ^ p,
                   "Difference": out & ~p}[call.name]
        return out

    def answer(self, call):
        every = np.ones(self.n, dtype=bool)
        if call.name == "Count":
            return int(self.bitmap(call.args[0]).sum())
        if call.name in ("Sum", "Min", "Max"):
            sel = self.bitmap(call.args[0]) if call.args else every
            v = self.value[sel]
            if call.name == "Sum":
                return int(v.sum()), int(sel.sum())
            m = int(v.min() if call.name == "Min" else v.max())
            return m, int((v == m).sum())
        if call.name == "TopN":
            sel = self.bitmap(call.args[1]) if len(call.args) > 1 else every
            counts = [(r, int((bits & sel).sum()))
                      for r, bits in self.cols[call.args[0]].items()]
            counts = sorted((c for c in counts if c[1]),
                            key=lambda c: (-c[1], c[0]))
            return counts[:call.kwargs.get("n")]
        assert call.name == "GroupBy"
        sel = every
        if "filter" in call.kwargs:
            sel = self.bitmap(call.kwargs["filter"])
        fields = [a.args[0] for a in call.args]
        agg = call.kwargs.get("aggregate")
        out = {}

        def walk(i, ids, mask):
            if i == len(fields):
                n = int(mask.sum())
                if n:
                    v = self.value[mask]
                    a = None if agg is None else int(
                        {"Sum": v.sum, "Min": v.min, "Max": v.max}
                        [agg.name]())
                    out[ids] = (n, a)
                return
            for r, bits in self.cols[fields[i]].items():
                walk(i + 1, (*ids, r), mask & bits)
        walk(0, (), sel)
        return out


@pytest.fixture(scope="module")
def worlds():
    cache = {}

    def get(path):
        if path not in cache:
            mix, config, gen = _load(path)
            brute = Brute(config["params"], gen, seed=2147483700)
            ref = gen.Reference(config["params"], brute.tables)
            cache[path] = (mix, config, brute, ref)
        return cache[path]
    return get


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_reference_equals_brute_force_for_every_template(worlds, path):
    mix, _config, brute, ref = worlds(path)
    asked = list(schedule.warm_sequential(mix, 3))
    for client in schedule.build(mix, 3, schedule.WINDOW, 1.0)[:1]:
        asked += client[:40]
    seen = set()
    for item in asked:
        call = pql.parse(item["q"])
        assert ref.answer(call) == brute.answer(call), item["q"]
        seen.add(item["t"])
    assert seen == {t["name"] for t in mix["templates"]}


def test_reference_refuses_what_its_tables_cannot_answer(worlds):
    _mix, _config, _brute, ref = worlds(MIXES[0])
    for q in ("Count(Intersect(Row(t=1), Row(t=2)))",
              "Count(Union(Row(t=1), Row(a=1)))",
              "Count(Not(Row(a=1)))", "Extract(Row(a=1))"):
        with pytest.raises(ValueError, match="."):     # Unanswerable
            ref.answer(pql.parse(q))


def test_a_stale_shard_changes_the_answers(worlds):
    """The control's broken guarantee is visible to the comparison."""
    _mix, config, brute, ref = worlds(MIXES[0])
    gen = server.load_module("generators", config["generator"])
    _rows, part = gen.make_shard(config["params"], 2147483700, SHARDS - 1)
    stale = gen.Reference(config["params"],
                          gen.drop_columns(brute.tables, part))
    q = pql.parse("Count(Intersect(Row(a=1), Row(b=1)))")
    assert stale.answer(q) < ref.answer(q)


def test_necessary_bytes_of_the_able_groupby():
    config = server.load_json("configs", "able-1b.json")
    q = pql.parse("GroupBy(Rows(edu), Rows(gen), Rows(dom), "
                  "filter=Row(a=1), aggregate=Sum(field=age))")
    # 7 code planes + valid + 9 BSI planes + 1 filter row, once each
    assert len(bytes_model.planes(q, config["params"])) == 18
    assert bytes_model.necessary_bytes(q, config["params"], 954) \
        == 18 * 954 * 131072
    q = pql.parse("Count(Intersect(Row(a=1), Row(edu=2), Row(age > 9)))")
    assert len(bytes_model.planes(q, config["params"])) == 2 + 9
