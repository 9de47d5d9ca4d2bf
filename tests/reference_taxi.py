"""The plain reference for the taxi deployment's four queries: numpy
over per-trip columns, one pass a query.

``columns`` is ``{field name: int array}`` with one entry a trip (the
row id the trip has in that field; for the ``int`` field its value).
A query is a parsed call in the shape of ``benchmark/harness/pql.py``'s
``Call`` (``name``, ``args``, ``kwargs``, ``conds``); nothing of
``pilosa_tpu`` and nothing of the benchmark is imported.  A boolean
mask for the filter, ``np.bincount`` over the combined group code, a
masked sum for the measure: no kernel, no cache, no histogram kept
between queries.  Answers are in ``harness/check.canonical``'s form:
``TopN`` ``[(row id, count), ...]`` by count then id, ``GroupBy``
``{(row ids): (count, sum or None)}`` without empty groups.
"""

from __future__ import annotations

import numpy as np

_CMP = {">": np.greater, "<": np.less, ">=": np.greater_equal,
        "<=": np.less_equal, "==": np.equal, "!=": np.not_equal}


def mask(columns: dict, call) -> np.ndarray:
    """The trips a bitmap call selects, as booleans."""
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
        elif call.name == "Difference":
            out &= ~p
        else:
            raise ValueError(f"bitmap call {call.name}")
    return out


def topn(columns: dict, call) -> list:
    field = call.args[0]
    sel = mask(columns, call.args[1] if len(call.args) > 1 else None)
    counts = np.bincount(columns[field][sel])
    pairs = sorted(((r, int(c)) for r, c in enumerate(counts) if c),
                   key=lambda p: (-p[1], p[0]))
    n = call.kwargs.get("n")
    return pairs[:n] if n else pairs


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
        sums = np.bincount(code, weights=columns[agg.kwargs["field"]][sel],
                           minlength=cells).astype(np.int64)
    out = {}
    for c in np.flatnonzero(counts):
        ids = tuple(int(i) for i in np.unravel_index(c, sizes))
        out[ids] = (int(counts[c]), int(sums[c]) if sums is not None else None)
    return out


def answer(columns: dict, call):
    if call.name == "TopN":
        return topn(columns, call)
    if call.name == "GroupBy":
        return groupby(columns, call)
    raise ValueError(f"call {call.name}")
