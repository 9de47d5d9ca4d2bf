"""Per-layer metrics read from the device trace of the traced window.

args: {"what": "idle_share"} the share of the traced window in which
no operation ran on the device, averaged over the cell's chips;
{"what": "mfu"} the necessary bytes (harness/bytes_model.py) of the
queries that the device served in the traced window, over the peak
bytes/s of the cell's chips together (``hbm_peak``), over the traced
window's seconds.  A query counts by the share of its execute phase
that lies inside the traced window, so one that straddles an edge is
counted on both sides in proportion.  Nothing to read (no device
operations, no device-served query) gives None, never 0.

Which query a flight record is: the server stamps a record's ``start``
with the wall clock when it begins to serve, the child stamps ``send``
on the same machine's clock a moment before; the record belongs to the
request with the latest send not after that start.  (Its own ``query``
is the server's re-print, cut at 200 characters; a request id echoed
into the record would replace this: PERF.md, tracing list.)
"""

from __future__ import annotations

import bisect

from harness import bytes_model, pql


def hbm_peak(ctx: dict) -> float:
    """Bytes a second the cell's chips can move together: one chip's
    peak times the chips.  The bytes a share counts are those of every
    chip, and its seconds are averaged over the chips."""
    return ctx["peaks"]["hbm_bytes_per_s"] * ctx["cell"]["chips"]


def served_bytes(ctx: dict) -> float:
    t0, t1 = ctx["traced_wall"]
    records = sorted((r for r, good in zip(ctx["records"], ctx["ok"])
                      if good), key=lambda r: r["send"])
    sends = [r["send"] for r in records]
    total = 0.0
    for f in ctx["flights"]:
        execute = f.get("phases", {}).get("execute", 0.0) / 1e3
        i = bisect.bisect_right(sends, f["start"]) - 1
        if execute <= 0 or i < 0 or records[i]["recv"] < f["start"]:
            continue        # a cache hit, or no request of the window
        end = f["start"] + f["duration_ms"] / 1e3
        inside = min(end, t1) - max(end - execute, t0)
        if inside <= 0:
            continue
        r = records[i]
        call = pql.parse(ctx["plans"][r["client"]][r["seq"]]["q"])
        total += min(1.0, inside / execute) * bytes_model.necessary_bytes(
            call, ctx["config"]["params"], ctx["shards"])
    return total


def read(ctx: dict, args: dict):
    trace = ctx["trace"]
    if not trace["ops"] or trace["busy_s"] <= 0:
        return None
    if args["what"] == "idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if ctx["peaks"] is None:
        return None
    nbytes = served_bytes(ctx)
    if not nbytes:
        return None
    return 100.0 * nbytes / hbm_peak(ctx) / trace["window_s"]
