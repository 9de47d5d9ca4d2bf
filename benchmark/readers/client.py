"""A percentile of the child's own records, in ms.

args: {"q": 90} over all requests of the window (failures count as
worse than any latency).
"""

from __future__ import annotations

from harness import stats


def read(ctx: dict, args: dict):
    lat = ctx["latencies_ms"]
    return stats.percentile(lat, args["q"]) if lat else None
