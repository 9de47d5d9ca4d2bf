"""Correctness-audit plane gauntlets (ISSUE 19): the serve-time
sampling-hook fixed-cost probe, the 32-client mixed read/write
gauntlet at production sampling rates (zero false positives), the
one-shot corruption drill (exactly one incident bundle), and the
audit-on/off QPS A/B."""

from __future__ import annotations

import json
import os
import time

from bench.common import _pct, log

INDEX = "aud"
READ_PQL = [
    "Count(Row(f=1))",
    "Row(f=2)",
    "Count(Union(Row(f=1), Row(f=3)))",
    "TopN(t, n=8)",
    "GroupBy(Rows(e))",
]


def _build(n_shards: int = 4):
    from pilosa_tpu.api import API
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    h = Holder()
    api = API(h)
    api.apply_schema({"indexes": [{"name": INDEX, "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "t", "options": {"type": "set",
                                  "cache_type": "none"}},
        {"name": "e", "options": {"type": "set"}}]}]})
    for shard in range(n_shards):
        cols = [shard * SHARD_WIDTH + 13 * k for k in range(96)]
        api.import_bits(INDEX, "f", [1 + (k % 4) for k in range(96)],
                        cols)
        api.import_bits(INDEX, "t", [k % 16 for k in range(96)], cols)
        api.import_bits(INDEX, "e", [k % 6 for k in range(96)], cols)
    h.index(INDEX).sync()
    ex = api.executor
    ex.enable_serving(window_s=0.001, max_batch=64,
                      cache_bytes=64 << 20)
    return h, api, ex


def audit_cost_probe(n: int = 50000) -> dict:
    """Load-independent fixed cost of the serve-time audit tap on the
    NOT-sampled path — the tax every served read pays: one enabled()
    check, one armed() check, one route-rate lookup, one RNG draw.
    A vanishing (but nonzero) sample rate keeps the RNG draw on the
    measured path without ever actually sampling."""
    from pilosa_tpu.executor.serving import _shard_set, field_snapshot
    from pilosa_tpu.obs import audit
    from pilosa_tpu.pql import parse

    h, api, ex = _build(n_shards=1)
    srv = ex.serving
    q = parse("Count(Row(f=1))")
    idx = h.index(INDEX)
    results = ex.execute(INDEX, q)
    fields = frozenset({"f"})
    snap = field_snapshot(idx, fields, _shard_set(None))
    key = (INDEX, repr(q.calls), None)
    audit.configure(sample_rate=1e-12, route_rates={})
    try:
        t0 = time.perf_counter()
        for _ in range(n):
            audit.tap(srv.audit, INDEX, idx, q, None, key, fields,
                      snap, "solo", results, None)
        tap_us = (time.perf_counter() - t0) / n * 1e6
    finally:
        audit.configure(sample_rate=0.01)
    return {"tap_not_sampled_us": round(tap_us, 3), "probe_n": n}


def audit_gauntlet(n_clients: int = 32, n_writers: int = 2,
                   arm_s: float = 2.0, sample_rate: float = 0.02,
                   n_shards: int = 4) -> dict:
    """ISSUE 19 acceptance: ``n_clients`` readers hammer the fused
    serving plane at a production sampling rate (1-5%) while writers
    interleave mutations — run twice (audited vs ``PILOSA_TPU_AUDIT=0``)
    for the QPS overhead A/B (recorded, NEVER asserted on a 2-core GIL
    box), then a one-shot corruption drill at rate 1.0 proves the
    auditor detects: exactly ONE ``audit-mismatch`` incident bundle,
    carrying both digests and the producing arm.

    Bars: zero mismatches across the storm arms (matches and
    stale_skips are the only legal outcomes — the write storm makes
    stale_skips expected), the drill caught exactly once, and the
    sampling hook's fixed cost stays <= the probe gate."""
    import threading

    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.obs import audit, faults, incidents

    out: dict = {"clients": n_clients, "writers": n_writers,
                 "arm_s": arm_s, "sample_rate": sample_rate,
                 "shards": n_shards, "queries": READ_PQL}
    h, api, ex = _build(n_shards)
    srv = ex.serving
    for q in READ_PQL:  # warm compiles + the serving batcher
        ex.execute_serving(INDEX, q)

    def run_arm(label: str, dur: float = arm_s) -> dict:
        stop = threading.Event()
        lat: list[float] = []
        rfails = [0]
        lk = threading.Lock()
        bar = threading.Barrier(n_clients + n_writers)

        def reader(ci):
            my, myf = [], 0
            bar.wait()
            i = ci
            while not stop.is_set():
                q = READ_PQL[i % len(READ_PQL)]
                i += 1
                t0 = time.perf_counter()
                try:
                    ex.execute_serving(INDEX, q)
                except Exception:
                    myf += 1
                my.append(time.perf_counter() - t0)
            with lk:
                lat.extend(my)
                rfails[0] += myf

        muts = [0] * n_writers

        def writer(wi):
            from pilosa_tpu.shardwidth import SHARD_WIDTH
            seq = wi
            bar.wait()
            while not stop.is_set():
                shard = seq % n_shards
                col = shard * SHARD_WIDTH + 13 * (seq % 96)
                op = "Clear" if seq % 5 == 4 else "Set"
                row = 1 + (seq % 4)
                try:
                    ex.execute_serving(
                        INDEX, f"{op}({col}, f={row})")
                    muts[wi] += 1
                except Exception:
                    pass
                seq += n_writers
                time.sleep(0.001)

        ths = ([threading.Thread(target=reader, args=(ci,))
                for ci in range(n_clients)]
               + [threading.Thread(target=writer, args=(wi,))
                  for wi in range(n_writers)])
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        time.sleep(dur)
        stop.set()
        for t in ths:
            t.join()
        wall = time.perf_counter() - t0
        srv.audit.wait_idle(30)
        arm = {"reads": len(lat), "read_failed": rfails[0],
               "qps": round(len(lat) / wall, 1),
               "read_p50_ms": _pct(lat, 0.5),
               "read_p99_ms": _pct(lat, 0.99),
               "mutations": sum(muts),
               "audit_counts": {f"{k}:{o}": v for (k, o), v
                                in sorted(srv.audit.counts.items())}}
        log(f"audit[{label}]: {arm['reads']} reads "
            f"({arm['qps']}/s) p50={arm['read_p50_ms']}ms, "
            f"{arm['mutations']} muts, counts={arm['audit_counts']}")
        return arm

    # -- discarded warmup arm: the first storm pays every fused-batch
    # shape's JIT compile; charging that to whichever A/B arm runs
    # first would fabricate (or hide) overhead
    os.environ["PILOSA_TPU_AUDIT"] = "0"
    try:
        run_arm("warmup")
    finally:
        os.environ.pop("PILOSA_TPU_AUDIT", None)

    # -- audited arm at the production rate ---------------------------
    audit.configure(sample_rate=sample_rate, route_rates={})
    out["audited"] = run_arm("audited")
    mismatches = sum(v for (k, o), v in srv.audit.counts.items()
                     if o == "mismatch")
    out["false_positives"] = mismatches
    out["quarantine"] = list(srv.audit.quarantine)

    # -- kill-switch arm: same storm, plane off -----------------------
    os.environ["PILOSA_TPU_AUDIT"] = "0"
    try:
        out["unaudited"] = run_arm("unaudited")
    finally:
        os.environ.pop("PILOSA_TPU_AUDIT", None)
    if out["unaudited"]["qps"]:
        # recorded, never asserted: on a 2-core GIL host the delta is
        # scheduler noise; at TPU scale this is the honest cost of
        # always-on auditing at the configured rate
        out["qps_overhead_pct"] = round(
            (out["unaudited"]["qps"] - out["audited"]["qps"])
            / out["unaudited"]["qps"] * 100, 2)

    # -- the corruption drill: detection is guaranteed ----------------
    import tempfile
    mgr = incidents.IncidentManager(
        dir=os.path.join(tempfile.mkdtemp(prefix="audit-bench-"),
                         "inc"),
        min_interval_s=3600.0)
    prev = incidents.swap(mgr)
    try:
        audit.configure(sample_rate=1.0)
        before = srv.audit.counts.get(("shadow", "mismatch"), 0)
        faults.inject("audit-corrupt", match="serve:", times=1)
        cold = Executor(h)
        dq = READ_PQL[0]
        served = ex.execute_serving(INDEX, dq)
        corrupted_served = repr(served) != repr(cold.execute(INDEX, dq))
        srv.audit.wait_idle(30)
        mgr.wait_idle(10)
        caught = srv.audit.counts.get(("shadow", "mismatch"), 0) \
            - before
        bundles = [b for b in mgr.list()
                   if b["trigger"] == "audit-mismatch"]
        ctx = (mgr.fetch(bundles[0]["id"]) or {}).get("context", {}) \
            if bundles else {}
        out["drill"] = {
            "served_was_corrupted": corrupted_served,
            "caught": caught,
            "bundles": len(bundles),
            "has_both_digests": bool(ctx.get("live_digest")
                                     and ctx.get("shadow_digest")),
            "live_arm": ctx.get("live_arm"),
            "shadow_arm": ctx.get("shadow_arm"),
        }
    finally:
        faults.clear("audit-corrupt")
        incidents.swap(prev)
        audit.configure(sample_rate=0.01)
    log(f"audit drill: caught={out['drill']['caught']} "
        f"bundles={out['drill']['bundles']} "
        f"overhead={out.get('qps_overhead_pct')}%")
    return out


def audit_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --audit-smoke): the mixed
    read/write gauntlet at a production sampling rate — CORRECTNESS
    GATES ONLY (zero false positives across the storm, the injected
    corruption caught with exactly one incident bundle carrying both
    digests, zero read failures) plus the sampling-hook fixed-cost
    probe, gated like the flight/standing probes
    (<= PILOSA_TPU_AUDIT_TAP_MAX_US, default 8us).  The QPS overhead
    A/B is recorded in the BENCH JSON and never asserted on a 2-core
    box."""
    probe = audit_cost_probe()
    out = audit_gauntlet(
        n_clients=int(os.environ.get("PILOSA_TPU_AUDIT_CLIENTS",
                                     "32")),
        n_writers=int(os.environ.get("PILOSA_TPU_AUDIT_WRITERS",
                                     "2")),
        arm_s=float(os.environ.get("PILOSA_TPU_AUDIT_DURATION_S",
                                   "1.5")),
        sample_rate=float(os.environ.get("PILOSA_TPU_AUDIT_RATE",
                                         "0.02")))
    out["cost_probe"] = probe
    failures: list[str] = []
    lim_tap = float(os.environ.get("PILOSA_TPU_AUDIT_TAP_MAX_US",
                                   "8"))
    if probe["tap_not_sampled_us"] > lim_tap:
        failures.append(
            f"audit tap fixed cost {probe['tap_not_sampled_us']}us "
            f"> {lim_tap}us — the sampler taxes every served read")
    if out.get("false_positives", 1):
        failures.append(
            f"{out['false_positives']} audit mismatches on CLEAN "
            f"traffic — false positives: {out.get('quarantine')}")
    for arm in ("audited", "unaudited"):
        a = out.get(arm, {})
        if a.get("read_failed", 1):
            failures.append(f"{a.get('read_failed')} reads failed "
                            f"in the {arm} arm")
        if a.get("reads", 0) <= 0:
            failures.append(f"zero reads completed in the {arm} arm")
        if a.get("mutations", 0) <= 0:
            failures.append(f"zero mutations landed in the {arm} arm")
    aud = out.get("audited", {}).get("audit_counts", {})
    if not any(k.startswith("shadow:") for k in aud):
        failures.append("the audited arm never sampled a serve — "
                        "the plane is not wired into serving")
    d = out.get("drill", {})
    if not d.get("served_was_corrupted"):
        failures.append("the corruption drill did not corrupt the "
                        "served answer — the seam is dead")
    if d.get("caught") != 1:
        failures.append(f"drill caught {d.get('caught')} times, "
                        "want exactly 1")
    if d.get("bundles") != 1:
        failures.append(f"{d.get('bundles')} audit-mismatch bundles, "
                        "want exactly 1")
    if not d.get("has_both_digests"):
        failures.append("the incident bundle is missing the "
                        "live/shadow digest pair")
    out["failures"] = failures
    print(json.dumps({"metric": "audit_smoke", **out}))
    for msg in failures:
        log("audit smoke: " + msg)
    return 1 if failures else 0
