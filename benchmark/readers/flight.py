"""A percentile over the flight records (``/debug/queries``) that the
window's requests left, in ms.

args: {"phase": "execute", "q": 50} that phase; {"field":
"duration_ms", "minus_phase": "execute", "q": 50} the record's
``duration_ms`` less that phase, over the records that have it
(``http.host_p50_ms``: device-served records only).  The record begins
after the body is read, the query parsed and classified and the heavy
slot taken, and is committed before the reply is encoded and sent, so
this never held any of those nor the socket
(``http.envelope_p50_ms`` does); it holds the cache lookup, the whole
stay in the batcher (the wait behind another caller, plan build, stack
work, dispatch, demux, the store-side re-check) and the audit tap.
Records without the phase (a result-cache hit never executes) are
left out; none left gives None.
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
