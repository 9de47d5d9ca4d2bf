"""SQL serving gauntlets (ISSUE 13): the 32-client mixed
point-lookup / join / GROUP BY storm through ``/sql`` with the
pushdown-vs-host A/B, and the check.sh ``--sql-smoke`` correctness
gate.

The gauntlet arm "pushdown" routes SELECT plans onto the fused
serving plane (statement admission, inner calls through the
batcher/ragged program, the canonicalized-statement result cache);
the "host" arm is the same server with ``PILOSA_TPU_SQL_PUSHDOWN=0``
— the solo row-by-row SelectExec path.  Bit-exactness against a
precomputed host-path answer key is HARD-GATED in both arms; QPS and
latency ratios are recorded in the BENCH JSON (the smoke never
asserts them — 2-core-box rule; the committed gauntlet run carries
the >=5x acceptance ratio)."""

from __future__ import annotations

import json
import os
import threading
import time

from bench.common import _pct, log


def _http(port, method, path, body=None, headers=None, timeout=30):
    import http.client
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    data = json.dumps(body) if isinstance(body, (dict, list)) else body
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    c.request(method, path, body=data, headers=hdrs)
    r = c.getresponse()
    raw = r.read()
    rh = dict(r.getheaders())
    c.close()
    try:
        return r.status, json.loads(raw), rh
    except json.JSONDecodeError:
        return r.status, raw.decode(), rh


def _build_sql_dataset(h, n_rows: int, n_dim: int, seed: int = 7):
    """Two SQL tables on one holder: a fact table ``f`` (bulk-loaded
    through the import path, so the statistics catalog sees real
    ingest stats) and a small dimension ``d`` for joins."""
    import numpy as np

    from pilosa_tpu.api import API

    rng = np.random.default_rng(seed)
    api = API(h)
    api.sql("create table f (_id id, seg int, val int, cat string)")
    api.sql("create table d (_id id, seg int, name string)")
    cols = np.arange(n_rows, dtype=np.int64)
    seg = rng.integers(0, n_dim, size=n_rows)
    val = rng.integers(0, 1000, size=n_rows)
    cat = rng.integers(0, 6, size=n_rows)
    api.import_values("f", "seg", cols=cols, values=seg)
    api.import_values("f", "val", cols=cols, values=val)
    api.import_bits("f", "cat", row_keys=[f"c{c}" for c in cat],
                    cols=cols)
    dcols = np.arange(n_dim, dtype=np.int64)
    api.import_values("d", "seg", cols=dcols, values=dcols)
    api.import_bits("d", "name", row_keys=[f"seg{i}" for i in dcols],
                    cols=dcols)
    return api


def _statement_mix(n_rows: int, n_dim: int):
    """(name, statement) storm items: point lookups, aggregates with
    WHERE pushdown, PQL GroupBy pushdown, value-hist DISTINCT, and a
    hash join — one of each family per ISSUE 13's gauntlet shape."""
    out = []
    for k in (1, n_rows // 3, n_rows - 2):
        out.append(("point", f"select val, seg from f where _id = {k}"))
    for s in (0, n_dim // 2):
        out.append(("agg", "select count(*), sum(val) from f "
                           f"where seg = {s}"))
    out.append(("groupby", "select cat, count(*), sum(val) from f "
                           "group by cat"))
    out.append(("distinct", "select distinct seg from f"))
    out.append(("join", "select d.name, count(*) from f "
                        "inner join d on f.seg = d.seg "
                        f"where d.seg = {n_dim // 3} group by d.name"))
    return out


def sql_gauntlet(n_clients: int = 32, duration_s: float = 1.2,
                 n_rows: int = 4096, n_dim: int = 16) -> dict:
    """The ISSUE 13 acceptance cell: N clients of mixed SQL via
    ``/sql``, pushdown-on vs host A/B on the same server, bit-exact
    hard-gated against a precomputed host answer key, with per-arm
    roofline windows and the /debug/queries fused-route evidence."""
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs import flight, roofline
    from pilosa_tpu.server.http import Server

    h = Holder()
    _build_sql_dataset(h, n_rows, n_dim)
    mix = _statement_mix(n_rows, n_dim)

    # the answer key: every statement's HOST-path rows, canonical
    # (sorted) form — both arms must reproduce it bit-for-bit
    os.environ["PILOSA_TPU_SQL_PUSHDOWN"] = "0"
    try:
        from pilosa_tpu.api import API
        key_api = API(h)
        expected = {q: sorted(map(repr, key_api.sql(q)["data"]))
                    for _n, q in mix}
    finally:
        del os.environ["PILOSA_TPU_SQL_PUSHDOWN"]

    out: dict = {"clients": n_clients, "duration_s": duration_s,
                 "rows": n_rows, "statements": len(mix)}
    with Server(holder=h, port=0).start() as srv:
        # AFTER start: Server.__init__ applies the config's flight
        # settings, which would shrink a pre-set ring
        flight.recorder.configure(enabled=True, keep=4096)
        roofline.ensure_peak()
        for arm in ("pushdown", "host"):
            if arm == "host":
                os.environ["PILOSA_TPU_SQL_PUSHDOWN"] = "0"
            # warm pass per arm (outside the timed window): first
            # serves pay jit compiles (the fused serving programs on
            # the pushdown arm, the solo programs on the host arm) —
            # the storm measures steady-state serving, not XLA
            flight.recorder.clear()
            for _n, q in mix:
                st, _b, _h2 = _http(srv.port, "POST", "/sql",
                                    {"sql": q})
                assert st == 200, (arm, q, st)
            # the cold pass is where inner dispatches actually run
            # (steady state serves from the statement cache): keep
            # its fused/direct route evidence before clearing
            cold_routes = sorted({
                rt for r in flight.recorder.recent(4096)
                if r.get("route") == "sql"
                for rt in r.get("serving_routes", ())})
            flight.recorder.clear()
            lat: list[float] = []
            lock = threading.Lock()
            mism: list = []
            errs: list = []
            stop_t = time.perf_counter() + duration_s
            barrier = threading.Barrier(n_clients)

            def client(ci):
                import random
                rng = random.Random(ci)
                barrier.wait()
                while time.perf_counter() < stop_t:
                    _name, q = rng.choice(mix)
                    t0 = time.perf_counter()
                    try:
                        st, body, _hd = _http(srv.port, "POST", "/sql",
                                              {"sql": q})
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            errs.append(repr(e))
                        continue
                    dt = time.perf_counter() - t0
                    with lock:
                        if st != 200:
                            errs.append((st, body))
                        elif sorted(map(repr, body["data"])) \
                                != expected[q]:
                            mism.append((q, body["data"]))
                        else:
                            lat.append(dt)

            snap0 = roofline.snapshot()
            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(n_clients)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            rl = roofline.window(snap0, roofline.snapshot())
            sql_recs = [r for r in flight.recorder.recent(4096)
                        if r.get("route") == "sql"]
            routes = sorted({rt for r in sql_recs
                             for rt in r.get("serving_routes", ())})
            out[arm] = {
                "qps": round(len(lat) / wall, 1),
                "p50_ms": _pct(lat, 0.50),
                "p99_ms": _pct(lat, 0.99),
                "completed": len(lat),
                "mismatched": len(mism),
                "errors": len(errs),
                "sql_flight_records": len(sql_recs),
                "inner_serving_routes": routes,
                "inner_serving_routes_cold": cold_routes,
                "pushdown_decisions_recorded": sum(
                    1 for r in sql_recs if r.get("pushdown")),
                "roofline_window": rl,
            }
            if arm == "pushdown":
                # /debug/queries shows the storm's statements as
                # route-"sql" records (checked while the ring still
                # holds them, before the host arm clears it)
                _st, dbg, _hd = _http(
                    srv.port, "GET",
                    "/debug/queries?route=sql&limit=20")
                out["debug_queries_sql_matched"] = dbg.get(
                    "matched", 0)
            if arm == "host":
                del os.environ["PILOSA_TPU_SQL_PUSHDOWN"]
    pd, hs = out["pushdown"], out["host"]
    out["acceptance"] = {
        "bit_exact": pd["mismatched"] == 0 and hs["mismatched"] == 0,
        "zero_failed": pd["errors"] == 0 and hs["errors"] == 0,
        "fused_routes_seen": any(
            rt in ("fused", "cached") for rt in
            pd["inner_serving_routes"]
            + pd["inner_serving_routes_cold"]),
        "fused_dispatches_cold": "fused"
        in pd["inner_serving_routes_cold"],
        "debug_queries_visible": None,
        "qps_ratio_pushdown_vs_host": round(
            pd["qps"] / hs["qps"], 2) if hs["qps"] else None,
    }
    out["acceptance"]["debug_queries_visible"] = \
        out.get("debug_queries_sql_matched", 0) > 0
    log(f"sql gauntlet: pushdown {pd['qps']} qps p99={pd['p99_ms']}ms"
        f" vs host {hs['qps']} qps p99={hs['p99_ms']}ms "
        f"(ratio {out['acceptance']['qps_ratio_pushdown_vs_host']}x)")
    return out


def sql_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --sql-smoke): ISSUE 13
    CORRECTNESS bars on the 2-core box —

    - both arms bit-exact vs the precomputed host answer key, zero
      failed statements;
    - pushdown actually engaged (route-"sql" flight records whose
      inner dispatches rode the serving plane, planner decisions
      recorded per statement);
    - a dead-on-arrival deadline on /sql sheds as a typed 504, an
      overflowing heavy admission queue as a typed 503 with
      Retry-After.

    QPS/latency ratios are recorded in the JSON, never asserted here
    (the committed gauntlet run carries the >=5x acceptance)."""
    out = sql_gauntlet(
        n_clients=int(os.environ.get("PILOSA_TPU_SQL_CLIENTS", "8")),
        duration_s=float(os.environ.get("PILOSA_TPU_SQL_DURATION_S",
                                        "0.8")),
        n_rows=1024, n_dim=8)
    failures: list[str] = []
    acc = out["acceptance"]
    if not acc["bit_exact"]:
        failures.append("responses diverged from the host answer key")
    if not acc["zero_failed"]:
        failures.append("statements failed during the storm")
    if not acc["fused_routes_seen"]:
        failures.append("no SQL statement rode the serving plane — "
                        "pushdown silently fell back")
    if out["pushdown"]["pushdown_decisions_recorded"] < 1:
        failures.append("planner decisions missing from the flight "
                        "records")
    failures += _backpressure_probe()
    out["failures"] = failures
    print(json.dumps({"metric": "sql_smoke", **out}))
    for msg in failures:
        log("sql smoke: " + msg)
    return 1 if failures else 0


def _backpressure_probe() -> list[str]:
    """Typed 503/504 on /sql: a dead deadline sheds 504 before
    execution; a saturated heavy gate sheds 503 + Retry-After."""
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.server.http import Server

    from pilosa_tpu.obs import stats

    failures: list[str] = []
    h = Holder()
    _build_sql_dataset(h, 256, 4)
    # cold catalog: the gauntlet just taught the process profiles
    # that these statements serve from cache in sub-ms, which would
    # (correctly!) classify them onto the point lane — the probe
    # needs the static heavy class to exercise the gate
    stats.get().clear()
    with Server(holder=h, port=0).start() as srv:
        st, body, _hd = _http(
            srv.port, "POST", "/sql",
            {"sql": "select cat, count(*) from f group by cat"},
            headers={"X-Pilosa-Deadline-Ms": "0.000001"})
        if st != 504:
            failures.append(f"dead deadline returned {st}, not a "
                            "typed 504")
        sched = srv.api.executor.serving.sched
        sched.heavy_slots, sched.queue_max = 1, 1
        slot = sched.heavy_slot(None)
        slot.__enter__()
        try:
            queued: list = []

            def bg():
                queued.append(_http(
                    srv.port, "POST", "/sql",
                    {"sql": "select cat, count(*), sum(val) from f "
                            "group by cat"}, timeout=30))
            t = threading.Thread(target=bg)
            t.start()
            for _ in range(200):
                if sched.queued():
                    break
                time.sleep(0.01)
            st, body, hd = _http(
                srv.port, "POST", "/sql",
                {"sql": "select seg, count(*) from f group by seg"})
            if st != 503:
                failures.append(f"queue overflow returned {st}, not a "
                                "typed 503")
            elif "Retry-After" not in hd:
                failures.append("503 shed carried no Retry-After")
        finally:
            slot.__exit__(None, None, None)
            t.join(timeout=30)
    return failures
