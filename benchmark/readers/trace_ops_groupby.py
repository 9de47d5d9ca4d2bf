"""The GroupBy kernels' share of the chip's memory bandwidth, from the
device trace of the traced window, counting the GroupBy requests'
bytes alone.

args: {"ops": "<regular expression>"} the names the kernels carry on
the device plane's ``XLA Ops`` line, as ``trace_ops`` reads them.  The
share is the necessary bytes (harness/bytes_model.py) of the GroupBy
requests that the device served inside the trace, each counted by the
share of its execute phase inside the traced window (the rule of
``readers/trace.py``'s ``served_bytes``, which counts every request:
a TopN's or a Sum's bytes are not the GroupBy kernels' to read), over
the peak bytes/s of the cell's chips together (readers/trace.py
``hbm_peak``), over the union seconds of the matching operations
averaged over the chips (harness/trace_reduce.py ``op_seconds``), in
%.  Nothing matching, no peak for the device, or no GroupBy the
device served gives None, never 0.
"""

from __future__ import annotations

import bisect

from harness import bytes_model, pql, server, trace_reduce


def groupby_bytes(ctx: dict) -> float:
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
        if call.name != "GroupBy":
            continue
        total += min(1.0, inside / execute) * bytes_model.necessary_bytes(
            call, ctx["config"]["params"], ctx["shards"])
    return total


def read(ctx: dict, args: dict):
    ops = ctx["trace"]["ops"]
    if not ops or ctx["peaks"] is None:
        return None
    seconds = trace_reduce.op_seconds(ops, args["ops"])
    if seconds <= 0:
        return None
    nbytes = groupby_bytes(ctx)
    if not nbytes:
        return None
    return (100.0 * nbytes
            / server.load_module("readers", "trace").hbm_peak(ctx) / seconds)
