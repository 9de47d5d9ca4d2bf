"""Incident-forensics smoke (ISSUE 15): check.sh's
``bench.py --incident-smoke``.

Gates (correctness + fixed-cost only — the 2-core-box rule):

- **watchdog stamp cost probe**: the per-stamp cycle (4-thread
  contended, monitor running) must hold
  ``PILOSA_TPU_WATCHDOG_STAMP_MAX_US`` (default 8 µs — the same
  budget class as the flight recorder's disabled path; a lock or
  allocation creeping into ``LoopWatch.stamp`` shows as 10x), and
  the ``report()`` hot-path cycle (rate-limited path) must hold
  ``PILOSA_TPU_INCIDENT_REPORT_MAX_US`` (default 60 µs) — capture
  itself runs on the dedicated worker, fully off the hot path.
- **injected stall drill**: a delay-armed ``serving-dispatch`` fault
  wedges the batch leader past a lowered watchdog deadline while a
  client storm runs → EXACTLY ONE ``watchdog-stall`` bundle captures
  (deduped within the rate-limit window), it carries thread stacks
  AND flight records, every query answers bit-exact, zero failures
  during capture.
"""

from __future__ import annotations

import json
import os
import threading
import time

from bench.common import build_index, log


def stamp_cost_probe(n: int = 20000, threads: int = 4) -> dict:
    """Load-independent fixed cost of LoopWatch.stamp under
    contention, with the background monitor alive (the production
    shape), plus the report() rate-limited cycle."""
    from pilosa_tpu.obs import incidents, watchdog

    watchdog.configure(enabled=True, interval_s=1.0)
    w = watchdog.register("probe-loop", deadline_s=60.0)

    def storm(nthreads: int, fn) -> float:
        def worker():
            for _ in range(n):
                fn()
        ts = [threading.Thread(target=worker)
              for _ in range(nthreads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return (time.perf_counter() - t0) / (nthreads * n) * 1e6

    try:
        stamp_1t = storm(1, lambda: w.stamp("probe"))
        stamp_4t = storm(threads, lambda: w.stamp("probe"))
    finally:
        watchdog.deregister("probe-loop")
    # report() steady state = the SUPPRESSED path (one rate-limit
    # check): the first call captures, the storm measures the rest
    mgr = incidents.IncidentManager(min_interval_s=3600.0)
    prev = incidents.swap(mgr)
    try:
        incidents.report("manual", "probe-warm")
        report_4t = storm(threads,
                          lambda: incidents.report("manual", "p"))
        mgr.wait_idle(10)
    finally:
        incidents.swap(prev)
    return {"stamp_cycle_us_1t": round(stamp_1t, 3),
            "stamp_cycle_us_4t": round(stamp_4t, 3),
            "report_cycle_us_4t": round(report_4t, 3)}


def incident_stall_drill(tmpdir: str) -> dict:
    """The black-box acceptance drill on a live serving stack."""
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.obs import faults, incidents, watchdog

    h, _meta = build_index(2, 4)
    ex = Executor(h)
    # the production default: ragged canonical program SERIALIZES
    # dispatches (one in flight), so the shared serving watch covers
    # exactly the dispatch that can wedge — which is also why the
    # watchdog's single-watch model is honest here
    ex.enable_serving(window_s=0.0, max_batch=16, ragged=True,
                      admission=False)
    queries = ["Count(Row(a=1))", "Count(Row(edu=0))",
               "Count(Union(Row(a=1), Row(b=1)))"]
    expect = {q: json.dumps(ex.execute("bench", q), default=str)
              for q in queries}

    mgr = incidents.IncidentManager(
        dir=os.path.join(tmpdir, "incidents"),
        min_interval_s=60.0)
    prev = incidents.swap(mgr)
    watchdog.register("serving-batcher", deadline_s=0.08)
    watchdog.configure(enabled=True, interval_s=0.02)
    faults.inject("serving-dispatch", delay_s=0.5, times=1)
    failures: list[str] = []
    served = [0]
    try:
        stop = threading.Event()

        def client():
            while not stop.is_set():
                for q in queries:
                    try:
                        got = json.dumps(
                            ex.execute_serving("bench", q),
                            default=str)
                        if got != expect[q]:
                            failures.append(f"mismatch on {q}")
                        served[0] += 1
                    except Exception as e:
                        failures.append(f"{type(e).__name__}: {e}")

        ts = [threading.Thread(target=client) for _ in range(4)]
        for t in ts:
            t.start()
        time.sleep(1.2)  # the 0.5s stall + capture + recovery traffic
        stop.set()
        for t in ts:
            t.join()
        mgr.wait_idle(15)
        bundles = [m for m in mgr.list(100)
                   if m["trigger"] == "watchdog-stall"]
        out = {"queries_served": served[0],
               "failed": len(failures),
               "stall_bundles": len(bundles),
               "fault_fired": not faults.active()}
        if failures:
            out["first_failure"] = failures[0]
        if len(bundles) != 1:
            return out
        b = mgr.fetch(bundles[0]["id"])
        out["bundle_has_stacks"] = bool(b.get("stacks"))
        out["bundle_has_flight"] = bool(b.get("flight"))
        out["bundle_persisted"] = bundles[0]["persisted"]
        out["bundle_loop"] = (b.get("context") or {}).get("loop")
        return out
    finally:
        faults.clear("serving-dispatch")
        watchdog.register("serving-batcher", deadline_s=10.0)
        watchdog.configure(interval_s=1.0)
        incidents.swap(prev)


def incident_smoke() -> int:
    """check.sh gate (bench.py --incident-smoke)."""
    import tempfile

    probe = stamp_cost_probe()
    with tempfile.TemporaryDirectory() as d:
        drill = incident_stall_drill(d)
    lim_stamp = float(os.environ.get(
        "PILOSA_TPU_WATCHDOG_STAMP_MAX_US", "8"))
    lim_report = float(os.environ.get(
        "PILOSA_TPU_INCIDENT_REPORT_MAX_US", "60"))
    out = {**probe, **drill,
           "thresholds": {"stamp_cycle_us": lim_stamp,
                          "report_cycle_us": lim_report}}
    print(json.dumps({"metric": "incident_smoke", **out}))
    failures = []
    if probe["stamp_cycle_us_4t"] > lim_stamp:
        failures.append(
            f"watchdog stamp cycle {probe['stamp_cycle_us_4t']}us > "
            f"{lim_stamp}us")
    if probe["report_cycle_us_4t"] > lim_report:
        failures.append(
            f"incident report cycle {probe['report_cycle_us_4t']}us "
            f"> {lim_report}us")
    if drill["failed"]:
        failures.append(
            f"{drill['failed']} queries failed during capture "
            f"({drill.get('first_failure')})")
    if drill["stall_bundles"] != 1:
        failures.append(
            f"expected exactly 1 watchdog-stall bundle, got "
            f"{drill['stall_bundles']}")
    else:
        if not drill.get("bundle_has_stacks"):
            failures.append("bundle missing thread stacks")
        if not drill.get("bundle_has_flight"):
            failures.append("bundle missing flight records")
        if not drill.get("bundle_persisted"):
            failures.append("bundle not persisted to disk")
    if not drill.get("fault_fired"):
        failures.append("serving-dispatch fault never consumed")
    for msg in failures:
        log("incident smoke: " + msg)
    return 1 if failures else 0
