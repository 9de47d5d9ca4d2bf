"""Which flight record belongs to which client record.

The server stamps a flight record's ``start`` with the wall clock when
it begins to serve a query, and keeps the query as it prints it again
(``Row(age=Condition('>', 12))``, ``TopN(..., _field='t')``, cut at 200
characters); the child stamps send and receive on the same machine's
wall clock.  A flight record belongs to the request, not yet taken,
whose send..receive interval holds its start, whose call has the same
name, and whose text shares the most words and numbers with it.
"""

from __future__ import annotations

import bisect
import re
from collections import Counter

_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*|-?\d+|[<>=!]+")
_NOISE = {"Condition", "_field", "="}


def words(text: str) -> Counter:
    return Counter(w for w in _WORD.findall(text) if w not in _NOISE)


def flights_by_record(records: list[dict], plans: list,
                      flights: list[dict]) -> dict:
    """{index into records: flight record}."""
    order = sorted(range(len(records)), key=lambda i: records[i]["send"])
    sends = [records[i]["send"] for i in order]
    longest = max((r["recv"] - r["send"] for r in records), default=0.0)
    texts = {}
    out = {}
    for rec in sorted(flights, key=lambda f: f["start"]):
        theirs = words(rec.get("query", ""))
        name = rec.get("query", "").split("(", 1)[0]
        lo = bisect.bisect_left(sends, rec["start"] - longest)
        hi = bisect.bisect_right(sends, rec["start"])
        best, best_score = None, 0
        for i in order[lo:hi]:
            r = records[i]
            if i in out or r["recv"] < rec["start"]:
                continue
            q = plans[r["client"]][r["seq"]]["q"]
            if q.split("(", 1)[0] != name:
                continue
            if q not in texts:
                texts[q] = words(q)
            score = sum((texts[q] & theirs).values())
            if score > best_score:
                best, best_score = i, score
        if best is not None:
            out[best] = rec
    return out
