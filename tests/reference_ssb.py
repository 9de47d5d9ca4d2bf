"""The plain reference for the Star Schema Benchmark's thirteen query
forms: numpy over per-line columns, one pass a query.

``columns`` is ``{field name: int array}`` with one entry a lineorder
row (the row id the line has in that field; for an ``int`` field its
value).  A query is a parsed call in the shape of
``benchmark/harness/pql.py``'s ``Call`` (``name``, ``args``, ``kwargs``,
``conds``); nothing of ``pilosa_tpu`` and nothing of the benchmark is
imported.  A boolean mask for the filter, a combined group code, exact
int64 sums by ``np.add.at``: no table, no kernel, no cache.  Answers are
in ``harness/check.canonical``'s form: ``Sum`` ``(value, count)``,
``GroupBy`` ``{(row ids): (count, sum or None)}`` without empty groups.
"""

from __future__ import annotations

import numpy as np

_CMP = {">": np.greater, "<": np.less, ">=": np.greater_equal,
        "<=": np.less_equal, "==": np.equal, "!=": np.not_equal}


def mask(columns: dict, call) -> np.ndarray:
    """The lines a bitmap call selects, as booleans."""
    n = len(next(iter(columns.values())))
    if call is None:
        return np.ones(n, dtype=bool)
    if call.name == "Row":
        out = np.ones(n, dtype=bool)
        for name, row in call.kwargs.items():
            out &= columns[name] == row
        for name, op, k in call.conds:
            out &= _CMP[op](columns[name], k)
        return out
    parts = [mask(columns, a) for a in call.args]
    out = parts[0].copy()
    for p in parts[1:]:
        if call.name == "Intersect":
            out &= p
        elif call.name == "Union":
            out |= p
        else:
            raise ValueError(f"bitmap call {call.name}")
    return out


def total(columns: dict, call) -> tuple:
    sel = mask(columns, call.args[0] if call.args else None)
    values = columns[call.kwargs["field"]][sel].astype(np.int64)
    return int(values.sum()), int(sel.sum())


def groupby(columns: dict, call) -> dict:
    fields = [a.args[0] for a in call.args]
    sel = mask(columns, call.kwargs.get("filter"))
    sizes = [int(columns[f].max()) + 1 for f in fields]
    code = np.zeros(int(sel.sum()), dtype=np.int64)
    for f, size in zip(fields, sizes):
        code = code * size + columns[f][sel]
    cells = int(np.prod(sizes))
    counts = np.bincount(code, minlength=cells)
    agg = call.kwargs.get("aggregate")
    sums = None
    if agg is not None:
        if agg.name != "Sum":
            raise ValueError(f"aggregate {agg.name}")
        sums = np.zeros(cells, np.int64)
        np.add.at(sums, code,
                  columns[agg.kwargs["field"]][sel].astype(np.int64))
    out = {}
    for c in np.flatnonzero(counts):
        ids = tuple(int(i) for i in np.unravel_index(c, sizes))
        out[ids] = (int(counts[c]), int(sums[c]) if sums is not None else None)
    return out


def answer(columns: dict, call):
    if call.name == "Sum":
        return total(columns, call)
    if call.name == "GroupBy":
        return groupby(columns, call)
    raise ValueError(f"call {call.name}")
