"""The bytes a query cannot avoid reading, from the configuration's
shapes alone: every operand plane once, whatever implements it.

Copied in idea from ``pilosa_tpu/ops/kernels.py:groupby_onepass_hbm_bytes``
(the single-pass model: code planes + valid + BSI planes + filter, once
each), which lives in the program where later PRs may change it, and
widened to the point reads: a ``Row(f=v)`` is one plane, a BSI
condition or aggregate that ``int`` field's ``2 + depth`` planes
(not-null, sign, magnitude) once per query, ``Rows(f)`` of a GroupBy
the ``ceil(log2(rows of f))`` code planes that tell its rows apart plus
one valid plane per GroupBy, ``TopN(f, ...)`` every row of ``f``.  Each
field is looked up by name in the configuration's field list
(``harness/fields.py``).  A plane is ``shards * 2^20 / 8`` bytes.
"""

from __future__ import annotations

from harness import fields

PLANE_BYTES_PER_SHARD = (1 << 20) // 8


def planes(call, params: dict) -> set:
    """The distinct operand planes of one parsed call."""
    by_name = {f["name"]: f for f in fields.field_list(params)}
    out = set()

    def int_planes(name):
        out.update(("bsi", name, p)
                   for p in range(2 + fields.depth(by_name[name])))

    def walk(c):
        if isinstance(c, str):
            return
        if c.name == "Row":
            out.update(("row", name, v) for name, v in c.kwargs.items())
            for name, _op, _k in c.conds:
                int_planes(name)
        elif c.name == "Rows":
            name = c.args[0]
            bits = (len(fields.row_ids(by_name[name])) - 1).bit_length()
            out.update(("code", name, b) for b in range(max(1, bits)))
            out.add(("valid",))
        elif c.name == "TopN":
            name = c.args[0]
            out.update(("row", name, r)
                       for r in fields.row_ids(by_name[name]))
        elif "field" in c.kwargs:
            int_planes(c.kwargs["field"])
        for a in c.args:
            walk(a)
        for v in c.kwargs.values():
            if not isinstance(v, (int, str)):
                walk(v)

    walk(call)
    return out


def necessary_bytes(call, params: dict, shards: int) -> int:
    return len(planes(call, params)) * shards * PLANE_BYTES_PER_SHARD
