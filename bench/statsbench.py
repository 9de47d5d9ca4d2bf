"""Statistics-catalog gauntlets (ISSUE 12): the fixed-cost probe for
the per-dispatch stats note, the check.sh ``--stats-smoke``
correctness gate (stats-on vs stats-off bit-exact + restart reloads a
non-empty catalog), and the stats-fed vs static admission A/B cell
(heavy-slot misclassification rate) recorded in the BENCH JSON."""

from __future__ import annotations

import json
import os
import tempfile
import time

from bench.common import log


def stats_cost_probe(n: int = 20000, threads: int = 4) -> dict:
    """Load-independent fixed cost of the per-dispatch stats note
    (flight.commit's stats.note_flight hook): the note cycle timed
    under `threads`-way contention with the catalog enabled (pending
    append + amortized fold) and disabled (one env/flag check) —
    same STABLE-probe style as flight_cost_probe, and gated with the
    same budgets (<=8us disabled / <=60us enabled)."""
    import threading

    from pilosa_tpu.obs import stats

    rec = {"fingerprint": "probe-fp", "route": "cached",
           "duration_ms": 0.2, "phases": {"execute": 0.0001},
           "batch": 1, "bytes_moved": 1024}

    def storm(nthreads: int) -> float:
        def worker():
            for _ in range(n):
                stats.note_flight(rec)
        ts = [threading.Thread(target=worker)
              for _ in range(nthreads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return (time.perf_counter() - t0) / (nthreads * n) * 1e6

    prev_cat = stats.swap(stats.StatsCatalog())
    prev_en = stats._enabled
    try:
        stats._enabled = True
        on_1t, on_4t = storm(1), storm(threads)
        stats._enabled = False
        off_4t = storm(threads)
    finally:
        stats._enabled = prev_en
        stats.swap(prev_cat)
    return {"stats_on_cycle_us_1t": round(on_1t, 2),
            "stats_on_cycle_us_4t": round(on_4t, 2),
            "stats_off_cycle_us_4t": round(off_4t, 2)}


def _mini_holder():
    """Tiny 2-shard holder with a 2-row categorical (cheap GroupBy)
    and a point field — the misclassification workload."""
    from pilosa_tpu.models.holder import Holder

    h = Holder()
    h.create_index("sb", track_existence=False)
    from pilosa_tpu.api import API
    api = API(h)
    api.create_field("sb", "seg", {"type": "set"})
    api.create_field("sb", "p", {"type": "set"})
    rows, cols = [], []
    for s in range(2):
        for c in range(256):
            rows.append(c % 2)
            cols.append(s * h.width + c)
    api.import_bits("sb", "seg", rows=rows, cols=cols)
    api.import_bits("sb", "p", rows=[0] * len(cols), cols=cols)
    return api


_POINT_Q = "Count(Row(p=0))"
_HEAVY_KIND_Q = "GroupBy(Rows(field=seg))"


def _digest(api, queries) -> dict:
    return {q: json.dumps(api.query("sb", q), sort_keys=True,
                          default=str) for q in queries}


def stats_ab_gauntlet(duration_s: float = 1.2,
                      n_clients: int = 8) -> dict:
    """Stats-fed vs static admission A/B: a mixed storm of point
    Counts + a CHEAP kind-heavy GroupBy (2 combos, cache-served)
    under heavy_slots=1.  The static arm classes every GroupBy heavy
    (kind walk) and burns the heavy gate on sub-ms serves; the
    stats-fed arm classes by measured fingerprint cost after warmup.
    Records the heavy-slot misclassification rate per arm (a query
    is misclassified when its assigned class disagrees with its
    measured duration vs the heavy-cost threshold) — bit-exact
    results hard-asserted across arms."""
    import threading

    from pilosa_tpu.obs import flight, stats

    queries = [_POINT_Q, _POINT_Q, _POINT_Q, _HEAVY_KIND_Q]
    prev_flight = (flight.recorder.enabled,
                   flight.recorder._ring.maxlen)
    flight.recorder.configure(enabled=True, keep=1 << 15)
    prev_cat = stats.swap(stats.StatsCatalog())
    prev_en = stats._enabled
    out: dict = {}
    digests = {}
    try:
        for arm in ("static", "stats"):
            stats._enabled = arm == "stats"
            if arm == "stats":
                stats.get().clear()
            api = _mini_holder()
            api.executor.enable_serving(ragged=False, heavy_slots=1)
            # warm: compile + caches; in the stats arm this also
            # warms the fingerprint profiles the classifier reads
            for _ in range(24):
                for q in queries:
                    api.query("sb", q)
            if arm == "stats":
                stats.get().fold()
            digests[arm] = _digest(api, set(queries))
            flight.recorder.clear()
            stop = time.perf_counter() + duration_s
            errs: list = []

            def client(api=api, stop=stop, errs=errs):
                i = 0
                while time.perf_counter() < stop:
                    try:
                        api.query("sb", queries[i % len(queries)])
                    except Exception as e:  # hard-gated below
                        errs.append(repr(e))
                        return
                    i += 1

            ts = [threading.Thread(target=client)
                  for _ in range(n_clients)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            recs = flight.recorder.recent(1 << 15)
            thr = stats.get().heavy_cost_ms
            total = len(recs)
            mis = sum(
                1 for r in recs
                if (r.get("priority") == "heavy")
                != (r.get("duration_ms", 0.0) >= thr))
            heavy_cheap = sum(
                1 for r in recs
                if r.get("priority") == "heavy"
                and r.get("duration_ms", 0.0) < thr)
            out[arm] = {
                "queries": total,
                "failed": len(errs),
                "heavy_cost_threshold_ms": thr,
                "misclassified": mis,
                "misclassification_rate": round(mis / total, 4)
                if total else None,
                "heavy_classed_but_cheap": heavy_cheap,
            }
        assert digests["static"] == digests["stats"], \
            "stats-fed vs static arms must be bit-exact"
        out["bit_exact"] = True
        s, t = (out["stats"]["misclassification_rate"],
                out["static"]["misclassification_rate"])
        if s is not None and t is not None:
            out["improvement"] = {
                "misclassification_static": t,
                "misclassification_stats": s,
                "improved": s < t,
            }
    finally:
        stats._enabled = prev_en
        stats.swap(prev_cat)
        flight.recorder.clear()
        flight.recorder.configure(enabled=prev_flight[0],
                                  keep=prev_flight[1])
    return out


def stats_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --stats-smoke).  Hard gates:

    - per-dispatch stats-note probe: disabled cycle (4-thread)
      <= PILOSA_TPU_STATS_OFF_MAX_US (default 8us — the always-on
      path), enabled cycle <= PILOSA_TPU_STATS_ON_MAX_US (default
      60us)
    - stats-on vs stats-off BIT-EXACT over the query set (the
      catalog steers plan/schedule choices only)
    - restart reloads a NON-EMPTY catalog: profiles persisted by one
      catalog are served by a fresh one over the same path, with the
      same cost estimate
    - the admission A/B arms are bit-exact and the stats arm's
      misclassification rate does not exceed the static arm's
    """
    from pilosa_tpu.obs import stats

    probe = stats_cost_probe()
    lim_off = float(os.environ.get("PILOSA_TPU_STATS_OFF_MAX_US", "8"))
    lim_on = float(os.environ.get("PILOSA_TPU_STATS_ON_MAX_US", "60"))
    failures = []
    if probe["stats_off_cycle_us_4t"] > lim_off:
        failures.append(
            f"disabled stats-note cycle "
            f"{probe['stats_off_cycle_us_4t']}us > {lim_off}us")
    if probe["stats_on_cycle_us_4t"] > lim_on:
        failures.append(
            f"enabled stats-note cycle "
            f"{probe['stats_on_cycle_us_4t']}us > {lim_on}us")

    # restart round-trip: profiles persisted -> reloaded non-empty
    restart: dict = {}
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "stats.jsonl")
        cat = stats.StatsCatalog(path=path)
        prev_cat = stats.swap(cat)
        prev_en = stats._enabled
        try:
            stats._enabled = True
            api = _mini_holder()
            api.executor.enable_serving(ragged=False)
            for _ in range(10):
                api.query("sb", _HEAVY_KIND_Q)
                api.query("sb", _POINT_Q)
            cat.fold()
            fps = list(cat.payload()["runtime"])
            est_before = {fp: cat.est_cost_ms(fp) for fp in fps}
            cat.save()
            cat2 = stats.StatsCatalog(path=path)
            est_after = {fp: cat2.est_cost_ms(fp) for fp in fps}
            restart = {
                "profiles_persisted": len(fps),
                "reloaded_non_empty": bool(cat2.payload()["runtime"]),
                "estimates_equal": est_before == est_after,
            }
            if not fps or not restart["reloaded_non_empty"]:
                failures.append("restart did not reload a non-empty "
                                "catalog")
            if not restart["estimates_equal"]:
                failures.append("post-restart cost estimates differ "
                                "from pre-restart")
            cat2.close()
        finally:
            stats._enabled = prev_en
            stats.swap(prev_cat)
            cat.close()

    ab = stats_ab_gauntlet(duration_s=0.5, n_clients=4)
    if not ab.get("bit_exact"):
        failures.append("stats-fed vs static arms not bit-exact")
    if ab["static"]["failed"] or ab["stats"]["failed"]:
        failures.append("A/B storm had failed queries")
    imp = ab.get("improvement")
    if imp and imp["misclassification_stats"] \
            > imp["misclassification_static"]:
        failures.append(
            "stats arm misclassifies MORE than the static arm "
            f"({imp['misclassification_stats']} > "
            f"{imp['misclassification_static']})")

    out = {"metric": "stats_smoke", **probe,
           "thresholds": {"stats_off_cycle_us": lim_off,
                          "stats_on_cycle_us": lim_on},
           "restart": restart, "ab": ab}
    print(json.dumps(out))
    for msg in failures:
        log("stats smoke: " + msg)
    return 1 if failures else 0
