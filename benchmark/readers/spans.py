"""A percentile over the stage spans of the flight records
(``/debug/queries``) that the window's requests left, in ms.

A record's ``spans`` are ``[name, off_ms, dur_ms, parent, thread]``:
every stage of the request at its offset from the record's start,
``parent`` an index into the list or -1 (``pilosa_tpu/obs/flight.py``).

args: {"spans": [names], "q": 50} per record the summed duration of
the spans with those names; "served": true keeps only the records the
device served (those with an ``execute`` phase: a result-cache hit
never executes); "self": true takes each span's self time, its
duration less what its direct child spans cover.  A record with none
of the named spans is left out, never counted as 0; no record left, or
a program whose records have no ``spans`` at all, gives None.
"""

from __future__ import annotations

from harness import stats


def _self_ms(spans: list, i: int) -> float:
    covered = sum(s[2] for s in spans if s[3] == i)
    return max(spans[i][2] - covered, 0.0)


def record_ms(spans: list, names, self_time: bool = False):
    """The summed (self) time of the named spans, None where the
    record has none of them."""
    hits = [i for i, s in enumerate(spans) if s[0] in names]
    if not hits:
        return None
    if self_time:
        return sum(_self_ms(spans, i) for i in hits)
    return sum(spans[i][2] for i in hits)


def read(ctx: dict, args: dict):
    names = frozenset(args["spans"])
    values = []
    for f in ctx["flights"]:
        if args.get("served") and "execute" not in f.get("phases", {}):
            continue
        ms = record_ms(f.get("spans") or [], names,
                       bool(args.get("self")))
        if ms is not None:
            values.append(ms)
    if not values:
        return None
    return stats.percentile(values, args["q"])
