"""A ratio of counter movements over the window, from two
``/metrics.json`` scrapes.

args: {"num": [[counter, label part], ...], "den": [[...]] (optional),
"scale": 1}.  A histogram's name with the label part "count" or "sum"
reads that part.  A denominator that did not move gives None.
"""

from __future__ import annotations


def _moved(ctx, series) -> float:
    total = 0.0
    for name, label in series:
        for m, sign in ((ctx["m1"], 1), (ctx["m0"], -1)):
            total += sign * (m.hist(name)[label] if label in ("count", "sum")
                             else m.total(name, label))
    return total


def read(ctx: dict, args: dict):
    num = _moved(ctx, args["num"])
    if "den" not in args:
        return args.get("scale", 1) * num
    den = _moved(ctx, args["den"])
    if den <= 0:
        return None
    return args.get("scale", 1) * num / den
