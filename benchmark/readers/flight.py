"""A percentile over the flight records (``/debug/queries``) that the
window's requests left, in ms.

args: {"phase": "execute", "q": 50} that phase; {"field":
"duration_ms", "minus_phase": "execute", "q": 50} the server's own
time for the request less that phase (what the host adds: parse,
admission, batch wait, plan build, demux, encode; the socket is not in
it).  Records without the phase (a result-cache hit never executes)
are left out; none left gives None.
"""

from __future__ import annotations

from harness import stats


def read(ctx: dict, args: dict):
    phase = args.get("phase") or args["minus_phase"]
    served = [f for f in ctx["flights"] if phase in f.get("phases", {})]
    if "field" in args:
        values = [f[args["field"]] - f["phases"][phase] for f in served
                  if args["field"] in f]
    else:
        values = [f["phases"][phase] for f in served]
    if not values:
        return None
    return stats.percentile(values, args["q"])
