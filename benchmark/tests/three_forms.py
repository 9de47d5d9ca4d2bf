"""A fixture deployment of two shards with a field in each form the
loader takes (``harness/server.py``), and its plain reference: every
record as a column, a query evaluated record by record.

- ``seg``: a ``set`` field of 3 half-dense rows, handed over as words;
- ``tag``: a ``set`` field of 40 thin rows, as (row ids, column ids);
- ``zone``: a ``mutex`` field of 300 rows with one value per record,
  row r about 1 / (r + 1) as likely as row 0, as (row ids, column
  ids): rows from about 20 up hold fewer than ``SPARSE_MAX`` = 8192
  columns of a shard;
- ``fare`` (0..1000) and ``tip`` (-20..20): ``int`` fields of depths 10
  and 5, as (column ids, values).

With ``params["missing_one_in"]`` = k a record in k does not exist:
it has no value anywhere and the generator brings the existence row.
"""

from __future__ import annotations

import itertools

import numpy as np

SHARD_WIDTH = 1 << 20
ZONES, TAGS, SEGS = 300, 40, 3

CONFIG = {
    "name": "three-forms", "generator": "three_forms",
    "params": {
        "index": "forms", "shards": 2, "rehearsal_shards": 2,
        "missing_one_in": 0,
        "fields": [
            {"name": "seg", "type": "set", "rows": SEGS,
             "options": {"cache_type": "none"}},
            {"name": "tag", "type": "set", "rows": TAGS,
             "options": {"cache_type": "none"}},
            {"name": "zone", "type": "mutex", "rows": ZONES,
             "options": {"cache_type": "none"}},
            {"name": "fare", "type": "int",
             "options": {"min": 0, "max": 1000}},
            {"name": "tip", "type": "int",
             "options": {"min": -20, "max": 20}},
        ],
    },
}


def make_shard(params: dict, seed: int, shard: int):
    rng = np.random.default_rng([seed, shard])
    n = SHARD_WIDTH
    exists = np.ones(n, dtype=bool)
    if params.get("missing_one_in"):
        exists = rng.integers(0, params["missing_one_in"], size=n) != 0
    cols = np.flatnonzero(exists)
    seg = rng.integers(0, 2, size=(SEGS, n), dtype=np.uint8).astype(bool)
    seg &= exists
    tag_rows = rng.integers(0, TAGS, size=3000)
    tag_cols = rng.choice(cols, size=3000)
    tag = np.zeros((TAGS, n), dtype=bool)
    tag[tag_rows, tag_cols] = True
    p = 1.0 / np.arange(1, ZONES + 1)
    zone = np.where(exists, rng.choice(ZONES, size=n, p=p / p.sum()), -1)
    fare = np.where(exists, rng.integers(0, 1001, size=n), 0)
    tip = np.where(exists, rng.integers(-20, 21, size=n), 0)
    rows = {
        "seg": {r: np.packbits(seg[r], bitorder="little").view(np.uint32)
                for r in range(SEGS)},
        "tag": (tag_rows, tag_cols),
        "zone": (zone[cols], cols),
        "fare": (cols, fare[cols]),
        "tip": (cols, tip[cols]),
    }
    if params.get("missing_one_in"):
        rows["_exists"] = {0: np.packbits(
            exists, bitorder="little").view(np.uint32)}
    return rows, {"exists": exists, "seg": seg, "tag": tag, "zone": zone,
                  "fare": fare, "tip": tip}


def add_tables(total, part):
    if total is None:
        return dict(part)
    return {k: np.concatenate([total[k], v], axis=-1)
            for k, v in part.items()}


def drop_columns(tables: dict, part: dict) -> dict:
    """The tables without the last shard's part."""
    return {k: v[..., :-part[k].shape[-1]] for k, v in tables.items()}


class Reference:
    def __init__(self, params: dict, tables: dict):
        self.t = tables

    def _rows(self, name: str) -> dict:
        if name == "zone":
            return {r: self.t["zone"] == r for r in range(ZONES)}
        return dict(enumerate(self.t[name]))

    def _bitmap(self, call):
        if call.name == "Not":
            return self.t["exists"] & ~self._bitmap(call.args[0])
        if call.name == "Intersect":
            out = self._bitmap(call.args[0])
            for a in call.args[1:]:
                out = out & self._bitmap(a)
            return out
        assert call.name == "Row", call
        if call.conds:
            (name, op, k), = call.conds
            v = self.t[name]
            return self.t["exists"] & {">": v > k, "<": v < k}[op]
        (name, row), = call.kwargs.items()
        return self._rows(name).get(
            row, np.zeros_like(self.t["exists"]))

    def answer(self, call):
        sel = self.t["exists"]
        if call.name == "Count":
            return int(self._bitmap(call.args[0]).sum())
        if call.name == "Sum":
            if call.args:
                sel = self._bitmap(call.args[0])
            return (int(self.t[call.kwargs["field"]][sel].sum()),
                    int(sel.sum()))
        if call.name == "TopN":
            if len(call.args) > 1:
                sel = self._bitmap(call.args[1])
            counts = [(r, int((bits & sel).sum()))
                      for r, bits in self._rows(call.args[0]).items()]
            counts = sorted((c for c in counts if c[1]),
                            key=lambda c: (-c[1], c[0]))
            return counts[:call.kwargs.get("n")]
        assert call.name == "GroupBy", call
        if "filter" in call.kwargs:
            sel = self._bitmap(call.kwargs["filter"])
        agg = call.kwargs.get("aggregate")
        per_field = [self._rows(a.args[0]) for a in call.args]
        out = {}
        for ids in itertools.product(*per_field):
            mask = sel
            for rows, r in zip(per_field, ids):
                mask = mask & rows[r]
            n = int(mask.sum())
            if n:
                out[ids] = (n, None if agg is None else int(
                    self.t[agg.kwargs["field"]][mask].sum()))
        return out
