"""The bytes a query cannot avoid reading, from the configuration's
shapes alone: every operand plane once, whatever implements it.

Copied in idea from ``pilosa_tpu/ops/kernels.py:groupby_onepass_hbm_bytes``
(the single-pass model: code planes + valid + BSI planes + filter, once
each), which lives in the program where later PRs may change it, and
widened to the point reads: a ``Row(f=v)`` is one plane, a BSI
condition or aggregate the field's ``2 + depth`` planes (not-null,
sign, magnitude) once per query, ``Rows(f)`` of a GroupBy the field's
``bits`` code planes plus one valid plane per GroupBy, ``TopN(f, ...)``
every row of ``f``.  A plane is ``shards * 2^20 / 8`` bytes.
"""

from __future__ import annotations

PLANE_BYTES_PER_SHARD = (1 << 20) // 8


def planes(call, params: dict) -> set:
    """The distinct operand planes of one parsed call."""
    bsi = params["bsi"]
    cats = {c["name"]: c for c in params["categorical"]}
    plain = {f["name"]: f for f in params["plain"]}
    out = set()

    def bsi_planes():
        out.update(("bsi", p) for p in range(2 + bsi["depth"]))

    def walk(c):
        if isinstance(c, str):
            return
        if c.name == "Row":
            for name in c.kwargs:
                out.add(("row", name, c.kwargs[name]))
            if c.conds:
                bsi_planes()
        elif c.name == "Rows":
            cat = cats[c.args[0]]
            out.update(("code", cat["name"], b) for b in range(cat["bits"]))
            out.add(("valid",))
        elif c.name == "TopN":
            name = c.args[0]
            rows = (plain[name]["rows"] if name in plain
                    else range(cats[name]["rows"]))
            out.update(("row", name, r) for r in rows)
        if c.kwargs.get("field") == bsi["name"]:
            bsi_planes()
        for a in c.args:
            walk(a)
        for v in c.kwargs.values():
            if not isinstance(v, (int, str)):
                walk(v)

    walk(call)
    return out


def necessary_bytes(call, params: dict, shards: int) -> int:
    return len(planes(call, params)) * shards * PLANE_BYTES_PER_SHARD
