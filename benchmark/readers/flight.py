"""A percentile of one phase of the flight records (``/debug/queries``)
that the window's requests left, in ms.

args: {"phase": "execute", "q": 50}.  Records without the phase (a
result-cache hit never executes) are left out; none left gives None.
"""

from __future__ import annotations

from harness import stats


def read(ctx: dict, args: dict):
    values = [f["phases"][args["phase"]] for f in ctx["flights"]
              if args["phase"] in f.get("phases", {})]
    if not values:
        return None
    return stats.percentile(values, args["q"])
