"""What decides ``correct``: every response of the window against the
plain reference, and the counters that say the host did not answer in
the device's place.

Each number compared is a count with the limit 0 (the comparison is
exact; the configuration states "answers exact"):

- ``wrong``: responses with status 200 whose canonical form differs
  from the reference's answer;
- ``failed``: requests with another status, or no reply;
- ``never_answered``: client threads still waiting two minutes after
  the window closed;
- ``host_loop``, ``host_fallback``, ``server_errors``: movement of
  ``pilosa_stacked_queries_total{path="loop"}``,
  ``pilosa_device_oom_total{outcome="host_fallback"}`` and
  ``/debug/errors`` inside the window;
- ``compared``: how many responses were compared (its limit is a
  minimum: at least one).
"""

from __future__ import annotations

import json

from harness import pql


def canonical(call_name: str, result):
    """A response's ``results[0]`` in the reference's canonical form (a
    row of a keyed field by its key); raises on any other shape."""
    if call_name == "Count":
        if type(result) is not int:
            raise ValueError(f"Count gave {result!r}")
        return result
    if call_name in ("Sum", "Min", "Max"):
        return (result["value"], result["count"])
    if call_name == "TopN":
        return [(p.get("key", p["id"]), p["count"]) for p in result]
    if call_name == "GroupBy":
        out = {}
        for r in result:
            ids = tuple(g.get("row_key", g["row_id"]) for g in r["group"])
            if ids in out:
                raise ValueError(f"group {ids} twice")
            out[ids] = (r["count"], r.get("agg"))
        return out
    raise ValueError(f"no canonical form for {call_name}")


def judge(records: list[dict], plans: list, reference) -> tuple:
    """(ok per record, examples of what went wrong).  The query of a
    record is looked up in the schedule the parent drew itself, so the
    child is trusted with nothing but the stamps and the bodies."""
    answers = {}
    ok, wrong = [], []
    for r in records:
        q = plans[r["client"]][r["seq"]]["q"]
        if r["status"] != 200:
            ok.append(False)
            continue
        if q not in answers:
            call = pql.parse(q)
            answers[q] = (call.name, reference.answer(call))
        name, want = answers[q]
        try:
            got = canonical(name, json.loads(r["body"])["results"][0])
        except (ValueError, KeyError, IndexError, TypeError) as e:
            got = f"unreadable: {e}"
        ok.append(got == want)
        if got != want and len(wrong) < 3:
            wrong.append({"q": q, "got": repr(got)[:300],
                          "want": repr(want)[:300]})
    return ok, wrong


def verdict(numbers: dict) -> tuple:
    """(correct, [{name, value, limit, ok}]) from
    {name: (value, limit, "max" | "min")}."""
    rows = []
    for name, (value, limit, kind) in numbers.items():
        good = value <= limit if kind == "max" else value >= limit
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(good)})
    return all(r["ok"] for r in rows), rows
