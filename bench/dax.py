"""Disaggregation gauntlet (ISSUE 20): the DAX tier's two acceptance
cells.  **Cold start**: a stateless worker boots with an EMPTY data
dir and serves a corpus >=10x over its HBM-ledger budget straight from
blob manifests, bit-exact vs the local-disk fleet that wrote them
(warmup bounded + recorded, paged residency never over budget).
**Autoscale**: an injected query storm trips the SLO burn threshold, a
standby joins live through the fenced migration machine with zero
failed / zero mismatched queries, burn recovers, the drained worker
returns to the pool, and the scale event's incident bundle is fetched
over HTTP.  ``dax_smoke`` is the check.sh arm: same drills, smaller,
with a scale-event-interrupted fault armed so the run must prove
resume (correctness-only gates per the 2-core-box rule; latency and
warmup numbers are recorded, never asserted)."""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time
import urllib.request

from bench.common import _pct, log

N_SHARDS = 24  # >=24 so jump-hash actually splits "t" across workers

SCHEMA = {"indexes": [{"name": "t", "fields": [
    {"name": "f", "options": {"type": "set"}},
    {"name": "v", "options": {"type": "int", "min": 0, "max": 1000}},
]}]}

DAX_QUERIES = [
    "Row(f=1)",
    "Row(f=2)",
    "Count(Row(f=1))",
    "Count(Union(Row(f=1), Row(f=2)))",
    "Count(Intersect(Row(f=1), Row(f=2)))",
    "Sum(Row(f=1), field=v)",
]

# deterministic knobs via the env twins — every Server construction
# re-applies its config's [dax] stanza over settings.configure() state
_KNOBS = {"PILOSA_TPU_DAX_PREFETCH": "0",
          "PILOSA_TPU_DAX_COOLDOWN_S": "0"}


def _seed(svc, n_shards=N_SHARDS):
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    svc.queryer.apply_schema(SCHEMA)
    cols = [s * SHARD_WIDTH + 7 for s in range(n_shards)]
    svc.queryer.import_bits("t", "f", [1] * n_shards, cols)
    svc.queryer.import_values("t", "v", cols,
                              [(s % 90) + 10 for s in range(n_shards)])
    return cols


def _checkpoint(svc):
    for w in svc.workers:
        for t, shards in list(w.held.items()):
            for s in sorted(shards):
                w.snapshot_shard(t, s)


def _seal(svc):
    for w in svc.workers:
        for t, shards in list(w.held.items()):
            for s in sorted(shards):
                w.hyd.seal_tail(t, s)


def _query_set(svc) -> dict:
    return {q: svc.queryer.query("t", q)["results"]
            for q in DAX_QUERIES}


def _cold_service(root: str, name: str, blob, budget=None):
    """A fresh service whose ONLY worker boots with an empty private
    data dir — everything it serves must come from the blob tier."""
    from pilosa_tpu.dax.server import DAXService
    svc = DAXService(os.path.join(root, name), n_workers=0, blob=blob)
    svc.queryer.apply_schema(SCHEMA)
    svc.add_blob_worker(f"{name}-w0", budget_bytes=budget)
    for t, s in blob.shards():
        svc.controller.add_shards(t, [s])
    return svc


def _cold_start_cell(root: str) -> dict:
    """Empty-data-dir worker vs the local-disk oracle, at >=10x
    ledger overcommit; hydration/eviction counters and warmup wall
    times recorded, correctness + budget invariant gated in the
    smoke."""
    from pilosa_tpu.dax.server import DAXService
    from pilosa_tpu.storage.blob import BlobStore, MemBackend

    blob = BlobStore(MemBackend())
    out: dict = {"shards": N_SHARDS}
    src = DAXService(os.path.join(root, "src"), n_workers=2,
                     blob=blob)
    probe = cold = None
    try:
        cols = _seed(src)
        _checkpoint(src)                 # wave 1 -> blob snapshots
        src.queryer.import_bits("t", "f", [2] * N_SHARDS,
                                [c + 1 for c in cols])
        _seal(src)                       # wave 2 -> blob WAL segments
        oracle = _query_set(src)

        # unbudgeted probe: measures the corpus (import-built source
        # fragments account zero restore bytes) and doubles as the
        # blob-path bit-exactness check
        t0 = time.perf_counter()
        probe = _cold_service(root, "probe", blob)
        out["probe_bit_exact"] = _query_set(probe) == oracle
        out["probe_cold_pass_s"] = round(time.perf_counter() - t0, 3)
        total = probe.workers[0].hyd.payload()["resident_bytes"]
        out["corpus_bytes"] = total

        budget = max(total // 12, 64)
        out["budget_bytes"] = budget
        out["overcommit_x"] = round(total / budget, 1)

        cold = _cold_service(root, "cold", blob, budget=budget)
        t0 = time.perf_counter()
        first = _query_set(cold)
        out["cold_first_pass_s"] = round(time.perf_counter() - t0, 3)
        lat: list[float] = []
        mismatched = 0
        for q in DAX_QUERIES:            # second pass: steady paging
            t0 = time.perf_counter()
            r = cold.queryer.query("t", q)
            lat.append(time.perf_counter() - t0)
            if r["results"] != oracle[q]:
                mismatched += 1
        out["bit_exact"] = first == oracle and mismatched == 0
        out["paged_pass_p50_ms"] = _pct(lat, 0.5)
        out["paged_pass_p99_ms"] = _pct(lat, 0.99)
        p = cold.workers[0].hyd.payload()
        out["resident_bytes"] = p["resident_bytes"]
        out["budget_respected"] = p["resident_bytes"] <= budget
        out["evictions"] = p["evictions"]
        out["hydrations"] = p["hydrations"]
        out["pressure"] = p["pressure"]
        log(f"dax cold-start: corpus {total}B over budget {budget}B "
            f"({out['overcommit_x']}x) bit_exact={out['bit_exact']} "
            f"hydrations={p['hydrations']} evictions={p['evictions']}")
    finally:
        for s in (probe, cold, src):
            if s is not None:
                s.close()
    return out


def _storm(svc, expected: dict, n_clients: int,
           duration_s: float) -> dict:
    """Barrier-synced readers through the queryer, every response
    checked bit-exact against the pre-storm oracle."""
    lock = threading.Lock()
    lat: list[float] = []
    errors: list[str] = []
    failed = mismatched = 0
    stop_at = time.perf_counter() + duration_s
    barrier = threading.Barrier(n_clients)

    def client(ci: int):
        nonlocal failed, mismatched
        my: list[float] = []
        my_e: list[str] = []
        my_f = my_m = 0
        barrier.wait()
        i = ci
        while time.perf_counter() < stop_at:
            q = DAX_QUERIES[i % len(DAX_QUERIES)]
            i += 1
            t0 = time.perf_counter()
            try:
                if svc.queryer.query("t", q)["results"] != expected[q]:
                    my_m += 1
            except Exception as e:
                my_f += 1
                if len(my_e) < 3:
                    my_e.append(f"{type(e).__name__}: {e}")
            my.append(time.perf_counter() - t0)
        with lock:
            lat.extend(my)
            errors.extend(my_e)
            failed += my_f
            mismatched += my_m

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    out = {"requests": len(lat), "failed": failed,
           "mismatched": mismatched,
           "qps": round(len(lat) / wall, 1) if wall > 0 else 0.0,
           "p50_ms": _pct(lat, 0.5), "p99_ms": _pct(lat, 0.99)}
    if errors:
        out["error_sample"] = errors[:5]
    return out


def _autoscale_cell(root: str, n_clients: int, burn_s: float,
                    storm_s: float, interrupt: bool) -> dict:
    """Storm -> SLO burn over threshold -> reconcile admits the
    standby live -> burn recovers -> reconcile drains it back; the
    scale-out incident bundle fetched over HTTP on the queryer
    front."""
    from pilosa_tpu.dax.server import DAXService
    from pilosa_tpu.obs import faults, incidents, slo
    from pilosa_tpu.storage.blob import BlobStore, MemBackend

    blob = BlobStore(MemBackend())
    svc = DAXService(os.path.join(root, "fleet"), n_workers=0,
                     blob=blob)
    out: dict = {"clients": n_clients,
                 "interrupt_armed": bool(interrupt)}
    try:
        svc.queryer.apply_schema(SCHEMA)
        svc.add_blob_worker("w0")
        svc.add_standby("s0")
        _seed(svc)
        _checkpoint(svc)
        front = svc.serve_queryer()
        expected = _query_set(svc)
        incidents.get().clear()

        # burn injection: a fresh tracker whose latency objective no
        # real query can meet — the storm's QUERY_DURATION
        # observations all land over threshold, so the 5m window's
        # burn rate goes >>(1-objective)^-1-sustainable
        tracker = slo.configure(latency_ms=1e-4)
        tracker.sample()                  # window base sample
        out["burn_storm"] = _storm(svc, expected, n_clients, burn_s)
        sig = svc.controller.signals()
        out["burn_injected"] = sig["burn"]

        if interrupt:
            faults.inject("scale-event-interrupted", times=1)
        events: dict = {}

        def driver():
            try:
                time.sleep(min(0.3, storm_s / 4))
                t0 = time.perf_counter()
                d = svc.controller.reconcile_once()
                events["scale_out"] = {
                    k: d.get(k) for k in ("action", "worker",
                                          "outcome")}
                moved = sum(1 for v in d.get("outcomes", {}).values()
                            if v == "done")
                if d.get("outcome") == "partial":
                    events["interrupted"] = True
                    d2 = svc.controller.reconcile_once()
                    events["resume"] = {
                        "action": d2.get("action"),
                        "ok": all(v in ("done", "noop") for v in
                                  d2.get("outcomes", {}).values())}
                    moved += sum(1 for v in
                                 d2.get("outcomes", {}).values()
                                 if v == "done")
                events["shards_moved"] = moved
                events["scale_out_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 1)
            except Exception as e:
                events["driver_error"] = f"{type(e).__name__}: {e}"

        drv = threading.Thread(target=driver)
        drv.start()
        out["scale_storm"] = _storm(svc, expected, n_clients,
                                    storm_s)
        drv.join()
        out["events"] = events
        out["workers_after_scale_out"] = sorted(
            svc.controller.workers)
        s0 = next(w for w in svc.workers if w.address == "s0")
        out["s0_assigned"] = sum(len(s) for s in s0.held.values())
        out["post_scale_bit_exact"] = _query_set(svc) == expected

        # recovery: the real objective back on a fresh window — the
        # same fleet's quiet-period queries all answer under it
        tracker = slo.configure()
        tracker.sample()
        for q in DAX_QUERIES:
            svc.queryer.query("t", q)
        sig = svc.controller.signals()
        out["burn_recovered"] = sig["burn"]

        d = svc.controller.reconcile_once()
        out["scale_in"] = {k: d.get(k)
                           for k in ("action", "worker", "outcome")}
        out["standbys_after"] = sorted(svc.controller.standbys)
        out["post_scale_in_bit_exact"] = _query_set(svc) == expected
        out["fences_leaked"] = [f"{t}/{s}" for t, s in
                                sorted(svc.controller._fences)]

        # the scale event's forensics, fetched the operator's way
        incidents.get().wait_idle(30)
        base = f"http://127.0.0.1:{front.port}"
        with urllib.request.urlopen(base + "/debug/incidents",
                                    timeout=10) as r:
            listing = json.loads(r.read())
        got = {b["trigger"]: b
               for b in listing.get("incidents", [])}
        out["incident_triggers"] = sorted(got)
        iid = got.get("dax-scale-out", {}).get("id")
        if iid:
            with urllib.request.urlopen(
                    f"{base}/debug/incidents?id={iid}",
                    timeout=10) as r:
                bundle = json.loads(r.read())
            ctx = bundle.get("context", {})
            out["incident_http_fetch"] = {
                "id": iid,
                "admitted": ctx.get("admitted"),
                "plan_moves": len(ctx.get("plan", [])),
                "outcomes_ok": all(
                    v in ("done", "noop")
                    for v in ctx.get("outcomes", {}).values()),
            }
        log(f"dax autoscale: burn {out['burn_injected']} -> "
            f"{out['burn_recovered']}, scale storm "
            f"{out['scale_storm']['requests']} reqs "
            f"failed={out['scale_storm']['failed']} "
            f"mism={out['scale_storm']['mismatched']}, s0 held "
            f"{out['s0_assigned']} shards, scale-in "
            f"{out['scale_in'].get('outcome')}")
    finally:
        from pilosa_tpu.obs import faults as _f, slo as _slo
        _f.clear("scale-event-interrupted")
        _slo.configure()                  # real objective, fresh ring
        svc.close()
    return out


def dax_gauntlet(n_clients: int = 8, burn_s: float = 1.2,
                 storm_s: float = 3.0,
                 interrupt: bool = False) -> dict:
    """The BENCH_r16 acceptance run: both cells over a throwaway
    storage root, with the scale knobs pinned via their env twins."""
    saved = {k: os.environ.get(k) for k in _KNOBS}
    os.environ.update(_KNOBS)
    root = tempfile.mkdtemp(prefix="dax-bench-")
    out: dict = {}
    try:
        for name, fn in (
                ("cold_start", lambda: _cold_start_cell(root)),
                ("autoscale", lambda: _autoscale_cell(
                    root, n_clients, burn_s, storm_s, interrupt))):
            try:
                out[name] = fn()
            except Exception as e:
                out[name] = {"error": f"{type(e).__name__}: {e}"}
    finally:
        from pilosa_tpu.obs import faults
        faults.clear()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(root, ignore_errors=True)
    return out


def dax_smoke() -> int:
    """check.sh gate (bench.py --dax-smoke): cold start at >=10x
    overcommit bit-exact, autoscale cycle with the
    scale-event-interrupted fault armed (the run must resume), zero
    failed / zero mismatched storm queries, burn recovery, and the
    incident bundle over HTTP.  Correctness-only gates (2-core-box
    rule): warmup walls, QPS, and latency are recorded, never
    asserted."""
    out = dax_gauntlet(
        n_clients=int(os.environ.get("PILOSA_TPU_DAX_CLIENTS", "6")),
        burn_s=float(os.environ.get("PILOSA_TPU_DAX_BURN_S", "1.0")),
        storm_s=float(os.environ.get("PILOSA_TPU_DAX_STORM_S",
                                     "2.5")),
        interrupt=True)
    failures: list[str] = []

    cs = out.get("cold_start", {})
    if cs.get("error"):
        failures.append("cold-start cell crashed: " + cs["error"])
    else:
        if not cs.get("probe_bit_exact"):
            failures.append("unbudgeted blob-path worker diverged "
                            "from the local-disk oracle")
        if not cs.get("bit_exact"):
            failures.append("budget-paged worker diverged from the "
                            "local-disk oracle")
        if (cs.get("overcommit_x") or 0) < 10:
            failures.append(f"corpus only {cs.get('overcommit_x')}x "
                            "over budget (acceptance: >=10x)")
        if not cs.get("budget_respected"):
            failures.append(
                f"ledger over budget: {cs.get('resident_bytes')} > "
                f"{cs.get('budget_bytes')}")
        if not cs.get("evictions"):
            failures.append("no evictions at 10x overcommit — the "
                            "ledger never paged")
        if (cs.get("hydrations") or 0) <= N_SHARDS:
            failures.append("no re-hydrations — paging never "
                            "round-tripped through blob")

    a = out.get("autoscale", {})
    if a.get("error"):
        failures.append("autoscale cell crashed: " + a["error"])
    else:
        ev = a.get("events", {})
        if ev.get("driver_error"):
            failures.append("scale driver failed: "
                            + ev["driver_error"])
        if (a.get("burn_injected") or 0) < 2.0:
            failures.append(
                f"injected load never tripped the scale-out burn "
                f"threshold (burn={a.get('burn_injected')})")
        if ev.get("scale_out", {}).get("action") != "scale-out":
            failures.append("reconcile did not scale out: "
                            f"{ev.get('scale_out')}")
        if not ev.get("interrupted"):
            failures.append("armed scale-event-interrupted fault "
                            "never fired (the drill proved nothing)")
        elif not ev.get("resume", {}).get("ok"):
            failures.append("interrupted scale-out never resumed "
                            f"clean: {ev.get('resume')}")
        if not ev.get("shards_moved"):
            failures.append("scale-out moved zero shards")
        if "s0" not in (a.get("workers_after_scale_out") or []):
            failures.append("standby s0 never joined the roster")
        if not a.get("s0_assigned"):
            failures.append("admitted standby owns zero shards")
        for arm in ("burn_storm", "scale_storm"):
            cell = a.get(arm, {})
            if cell.get("failed", 1):
                failures.append(f"{arm}: {cell.get('failed')} "
                                "queries failed (acceptance: zero)")
            if cell.get("mismatched", 1):
                failures.append(f"{arm}: {cell.get('mismatched')} "
                                "responses diverged")
        if not a.get("post_scale_bit_exact"):
            failures.append("post-scale-out reads diverged")
        if a.get("burn_recovered") is None \
                or a["burn_recovered"] >= 2.0:
            failures.append("burn never recovered after the storm "
                            f"(burn={a.get('burn_recovered')})")
        if a.get("scale_in", {}).get("outcome") != "done":
            failures.append("scale-in drain did not complete: "
                            f"{a.get('scale_in')}")
        if "s0" not in (a.get("standbys_after") or []):
            failures.append("drained worker never returned to the "
                            "standby pool")
        if not a.get("post_scale_in_bit_exact"):
            failures.append("post-scale-in reads diverged")
        if a.get("fences_leaked"):
            failures.append("fences leaked: "
                            f"{a['fences_leaked'][:3]}")
        # outcomes_ok is False by design when the interrupt drill
        # fired mid-event (the bundle records the partial truth);
        # the gate is that the bundle exists, names the admitted
        # worker, and carries the move plan
        inc = a.get("incident_http_fetch") or {}
        if inc.get("admitted") != "s0" or not inc.get("plan_moves"):
            failures.append("scale-out incident bundle missing or "
                            f"incomplete over HTTP: {inc}")

    out["failures"] = failures
    print(json.dumps({"metric": "dax_smoke", **out}))
    for msg in failures:
        log("dax smoke: " + msg)
    return 1 if failures else 0
