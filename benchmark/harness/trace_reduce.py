"""From the profiler's ``.xplane.pb`` to device busy time, the time of
named operations, and the longest idle gaps.

``read_events`` is the only part that knows the file format
(``jax.profiler.ProfileData``); everything else works on plain tuples
``(plane, line, name, start_ns, duration_ns)``, so the arithmetic is
tested on events written by hand and on a trace recorded on the chip
(``tests/data``).

On a TPU v5e the device plane is ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per executed HLO operation (a Pallas
kernel is one ``custom-call`` op named after its kernel function),
``XLA Modules`` one per executed program.  Busy time is the union of
the ``XLA Ops`` intervals; where a plane has no such line, of all its
lines.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def read_events(path: str) -> list[tuple]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def union(intervals: list[tuple]) -> list[tuple]:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def device_ops(events: list[tuple], chips: int) -> dict:
    """{chip number: [(name, start_ns, end_ns)]} of the ops lines of
    the first `chips` device planes."""
    by_plane = {}
    for plane, line, name, start, dur in events:
        m = DEVICE_PLANE.match(plane)
        if m and int(m.group(1)) < chips:
            by_plane.setdefault(int(m.group(1)), {}).setdefault(
                line, []).append((name, start, start + dur))
    out = {}
    for chip, lines in by_plane.items():
        if OPS_LINE in lines:
            out[chip] = lines[OPS_LINE]
        else:
            out[chip] = [ev for evs in lines.values() for ev in evs]
    return out


def op_seconds(ops: dict, pattern: str) -> float:
    """Seconds of the operations whose name matches, averaged over the
    chips: the union of their intervals, so overlapping or nested
    events are not counted twice."""
    if not ops:
        return 0.0
    rx = re.compile(pattern)
    per_chip = [sum(e - s for s, e in union(
        [(s, e) for n, s, e in evs if rx.search(n)])) for evs in ops.values()]
    return sum(per_chip) / len(per_chip) / 1e9


def busy_seconds(ops: dict) -> float:
    """Seconds in which any operation ran, averaged over the chips."""
    return op_seconds(ops, "")


def top_ops(ops: dict, n: int = 10) -> list[list]:
    total = {}
    for evs in ops.values():
        for name, s, e in evs:
            total[name] = total.get(name, 0) + (e - s)
    chips = max(1, len(ops))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / chips / 1e9] for name, ns in ranked]


def idle_gaps(events: list[tuple], ops: dict, n: int = 10) -> list[list]:
    """The longest gaps between operations on the first chip, each
    named by the longest host event that spans its middle (the host
    is what the device waited for), or by the operation that ended it."""
    if not ops:
        return []
    evs = sorted(ops[min(ops)], key=lambda ev: ev[1])
    merged = union([(s, e) for _n, s, e in evs])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)[:n]
    host = [(name, s, s + d) for plane, _l, name, s, d in events
            if not DEVICE_PLANE.match(plane) and d > 0]
    out = []
    for length, start, end in gaps:
        mid = (start + end) // 2
        over = [(e - s, name) for name, s, e in host if s <= mid <= e]
        if over:
            what = "host: " + max(over)[1]
        else:
            nxt = next((name for name, s, _e in evs if s >= end), "?")
            what = "no host span; before " + nxt
        out.append([what[:120], length / 1e9])
    return out


def reduce_events(events: list[tuple], chips: int, window_s: float) -> dict:
    ops = device_ops(events, chips)
    return {
        "busy_s": busy_seconds(ops),
        "window_s": window_s,
        "ops": ops,
        "breakdown": {"device_ops": top_ops(ops),
                      "idle_gaps": idle_gaps(events, ops)},
        "planes": sorted({(p, l) for p, l, _n, _s, _d in events}),
    }


def reduce_dir(trace_dir: str, chips: int, window_s: float) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise SystemExit(f"benchmark: {len(paths)} .xplane.pb under "
                         f"{trace_dir}")
    return reduce_events(read_events(paths[0]), chips, window_s)
