"""The arithmetic of the end-to-end metrics, and of a spread.

All over *all* requests of the window: a request that failed, was shed
or answered wrongly is not completed, and its latency is worse than
any measured one, so failures move the tail and never
shorten it.
"""

from __future__ import annotations

import math
import statistics


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100), nearest rank: the smallest value
    with at least q% of the sample at or below it."""
    if not values:
        raise ValueError("percentile of no sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def latencies_ms(records: list[dict], ok: list[bool],
                 worst_ms: float) -> list[float]:
    """Client-side latency of every request of the window; a request
    that is not `ok` counts as `worst_ms`, which the caller sets above
    any wait the harness allows."""
    return [1e3 * (r["recv"] - r["send"]) if good else worst_ms
            for r, good in zip(records, ok)]


def completed_per_s(records: list[dict], ok: list[bool], start: float,
                    seconds: float) -> float:
    """Requests answered correctly inside the window, over its length."""
    end = start + seconds
    return sum(1 for r, good in zip(records, ok)
               if good and r["recv"] <= end) / seconds


def spread(values: list[float]) -> float:
    """Interquartile range as a share of the median (the contract's
    measure, with Python's exclusive quartiles)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
