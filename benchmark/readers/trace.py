"""Per-layer metrics read from the device trace of the traced window.

args: {"what": "idle_share"} the share of the traced window in which
no operation ran on the device; {"what": "roofline", "ops": regex,
"calls": [...]} the necessary bytes (harness/bytes_model.py) of the
matching queries answered by the device inside the traced window, over
the chip's peak bytes/s, over the seconds of the device operations
whose name matches `ops`; {"what": "mfu"} the same bytes of every
device-served query over the whole traced window's seconds.  Nothing
to read (no device operations, no matching operation or query) gives
None, never 0.
"""

from __future__ import annotations

from harness import bytes_model, match, pql, trace_reduce


def _served_bytes(ctx, calls) -> int:
    t0, t1 = ctx["traced_wall"]
    flights = match.flights_by_record(ctx["records"], ctx["plans"],
                                      ctx["flights"])
    total = 0
    for i, r in enumerate(ctx["records"]):
        if not (ctx["ok"][i] and t0 <= r["recv"] <= t1):
            continue
        if flights.get(i, {}).get("route") == "cached":
            continue
        call = pql.parse(ctx["plans"][r["client"]][r["seq"]]["q"])
        if calls and call.name not in calls:
            continue
        total += bytes_model.necessary_bytes(
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
    nbytes = _served_bytes(ctx, args.get("calls"))
    if args["what"] == "roofline":
        seconds = trace_reduce.op_seconds(trace["ops"], args["ops"])
    else:
        seconds = trace["window_s"]
    if not nbytes or seconds <= 0:
        return None
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
