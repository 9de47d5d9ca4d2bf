"""A percentile of the child's own records, in ms.

args: {"q": 90} over all requests of the window (failures count as
worse than any latency); with "minus_flight_phase": "execute" each
request's latency less that phase of its own flight record (the host's
share: HTTP, parse, admission, batch wait, plan build, demux, encode),
over the requests whose flight record was found.
"""

from __future__ import annotations

from harness import match, stats


def read(ctx: dict, args: dict):
    lat = ctx["latencies_ms"]
    phase = args.get("minus_flight_phase")
    if phase is None:
        return stats.percentile(lat, args["q"]) if lat else None
    flights = match.flights_by_record(ctx["records"], ctx["plans"],
                                      ctx["flights"])
    values = [lat[i] - f.get("phases", {}).get(phase, 0.0)
              for i, f in flights.items() if ctx["ok"][i]]
    return stats.percentile(values, args["q"]) if values else None
