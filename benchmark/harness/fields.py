"""A configuration's fields, in the one form the harness reads.

``params["fields"]`` is a list of ``{"name", "type", "options"}``
entries in the words of the program's own ``POST /schema`` (``set``,
``mutex``, ``bool``, ``time`` with ``time_quantum``, ``int`` with
``min`` and ``max``; ``cache_type``, ``keys``), plus ``rows`` (how many
row ids from 0, or the ids themselves) where a query may ask
``Rows(f)`` or ``TopN(f)``, plus whatever else the generator wants to
read of a field.  ``able-1b.json`` came before the list and says
``plain``, ``categorical`` and ``bsi``: ``field_list`` turns that into
the same list, here and nowhere else.  The whole protocol between the
harness and a generator is at the top of ``harness/server.py``.
"""

from __future__ import annotations


def field_list(params: dict) -> list[dict]:
    if "fields" in params:
        return params["fields"]
    unranked = {"cache_type": "none"}
    bsi = params["bsi"]
    return [
        *({"name": f["name"], "type": "set", "options": unranked,
           "rows": f["rows"]} for f in params["plain"]),
        *({"name": c["name"], "type": "set", "options": unranked,
           "rows": [c.get("row_base", 0) + r for r in range(c["rows"])]}
          for c in params["categorical"]),
        {"name": bsi["name"], "type": "int",
         "options": {"min": 0, "max": (1 << bsi["depth"]) - 1}}]


def schema_fields(params: dict) -> list[dict]:
    """The list as ``POST /schema`` takes it."""
    return [{"name": f["name"],
             "options": {"type": f["type"], **f.get("options", {})}}
            for f in field_list(params)]


def row_ids(field: dict):
    rows = field["rows"]
    return range(rows) if isinstance(rows, int) else rows


def depth(field: dict) -> int:
    """Magnitude planes of an ``int`` field, as the program derives them
    from ``min`` and ``max`` (stated here, not imported)."""
    opts = field["options"]
    return max(abs(opts["min"]), abs(opts["max"]), 1).bit_length()
