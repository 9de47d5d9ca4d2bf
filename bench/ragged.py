"""Ragged dispatch + QoS gauntlet (ISSUE 8 acceptance).

Two A/Bs over a mixed-index, mixed-size workload at 32 clients:

- **ragged vs per-group dispatch**: the same storm served with the
  cross-index page-table program (executor/ragged.py) vs one "multi"
  program per (index, shards) group.  Acceptance: device dispatches
  per query drop >= 2x, QPS no worse, every response bit-exact.
- **admission classes vs FIFO**: a GroupBy-heavy storm (240-combo
  GroupBys from dedicated heavy clients) alongside point readers,
  with the QoS scheduler (executor/sched.py) on vs off.  Acceptance:
  point-read p99 improves >= 2x with classes on (the RATIO is the
  assertion; absolute latencies are recorded only — 2-core-box rule).

The smoke (``bench.py --ragged-smoke``) gates CORRECTNESS only:
bit-exact, zero failed, shed requests surface as typed 503 with
Retry-After; every latency/dispatch ratio is recorded in the BENCH
JSON, never asserted at tier-1 time.
"""

from __future__ import annotations

import json
import os
import threading
import time

from bench.common import _pct, build_index, log


def build_events_index(h, n_shards: int = 3, seed: int = 11):
    """A second, differently-shaped index on the same holder: fewer
    shards, its own categorical/BSI fields — the 'different index,
    different shard subset' half of the heterogeneous mix."""
    import numpy as np

    from pilosa_tpu.models.schema import (
        CACHE_TYPE_NONE,
        FieldOptions,
        FieldType,
    )
    from pilosa_tpu.models.view import VIEW_STANDARD
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(seed)
    idx = h.create_index("events", track_existence=False)
    words = SHARD_WIDTH // 32
    for fname, rows in (("c", 4), ("u", 8)):
        f = idx.create_field(fname,
                             FieldOptions(cache_type=CACHE_TYPE_NONE))
        view = f.view(VIEW_STANDARD, create=True)
        for shard in range(n_shards):
            frag = view.fragment(shard, create=True)
            for r in range(rows):
                frag.import_row_words(
                    r, rng.integers(0, 1 << 32, size=words,
                                    dtype=np.uint32))
    m = idx.create_field("m", FieldOptions(
        type=FieldType.INT, min=0, max=511))
    mview = m.view(m.bsi_view, create=True)
    for shard in range(n_shards):
        frag = mview.fragment(shard, create=True)
        frag.import_row_words(0, np.full(words, 0xFFFFFFFF,
                                         dtype=np.uint32))
        for plane in range(9):
            frag.import_row_words(
                2 + plane, rng.integers(0, 1 << 32, size=words,
                                        dtype=np.uint32))
    return idx


def mixed_queries(bench_shards: int, events_shards: int):
    """(index, query, shards) storm items: point reads over both
    indexes incl. explicit shard subsets, plus batchable TopNs."""
    items = [
        ("bench", "Count(Row(a=1))", None),
        ("bench", "Count(Intersect(Row(a=1), Row(b=1)))", None),
        ("bench", "Count(Union(Row(a=1), Row(b=1)))", None),
        ("bench", "Row(a=1)", None),
        ("bench", "Sum(Row(a=1), field=age)", None),
        ("bench", "Count(Row(age > 63))", None),
        ("events", "Count(Row(c=1))", None),
        ("events", "Count(Union(Row(c=0), Row(c=1)))", None),
        ("events", "Count(Row(m > 255))", None),
        ("events", "Sum(field=m)", None),
        ("events", "Row(c=2)", None),
        ("bench", "TopN(t, n=10)", None),
        ("events", "TopN(u, n=5)", None),
    ]
    # explicit shard subsets: same query text, different skey — its
    # own dispatch group on the per-group path, fused by ragged
    items.append(("bench", "Count(Row(a=1))",
                  list(range(max(1, bench_shards // 2)))))
    items.append(("bench", "Count(Row(b=1))", [bench_shards - 1]))
    items.append(("events", "Count(Row(c=1))",
                  list(range(max(1, events_shards - 1)))))
    return items


HEAVY_QUERY = ("GroupBy(Rows(edu), Rows(gen), Rows(dom), Rows(reg), "
               "aggregate=Sum(field=age))")
POINT_QUERIES = [
    ("bench", "Count(Row(a=1))", None),
    ("bench", "Count(Intersect(Row(a=1), Row(b=1)))", None),
    ("events", "Count(Row(c=1))", None),
    ("events", "Sum(field=m)", None),
]


def _digest(results) -> str:
    """Bit-exact fingerprint of a result list, cheap enough for the
    storm hot loop (serializing a dense Row result to a million-entry
    column list costs 100x the query itself — the storm must measure
    serving, not JSON encoding).  RowResults hash their raw segment
    words; everything else hashes its repr."""
    import hashlib

    import numpy as np

    from pilosa_tpu.executor.results import RowResult

    hs = hashlib.blake2b(digest_size=16)
    for r in results:
        if isinstance(r, RowResult):
            for s in sorted(r.segments):
                hs.update(str(s).encode())
                hs.update(np.ascontiguousarray(
                    np.asarray(r.segments[s])).tobytes())
        else:
            hs.update(repr(r).encode())
    return hs.hexdigest()


def _mixed_storm(call, items, expected, n_clients: int,
                 duration_s: float) -> dict:
    """N barrier-synced clients round-robin over (index, q, shards)
    items; every response checked bit-exact (segment-word digest)
    against `expected`."""
    lock = threading.Lock()
    lat: list[float] = []
    failed = [0]
    mismatched = [0]
    shed = [0]
    stop = time.perf_counter() + duration_s
    barrier = threading.Barrier(n_clients)

    def client(ci: int):
        my: list[float] = []
        myf = mym = mys = 0
        barrier.wait()
        i = ci
        while time.perf_counter() < stop:
            index, q, shards = items[i % len(items)]
            i += 1
            t0 = time.perf_counter()
            try:
                r = _digest(call(index, q, shards))
                if r != expected[(index, q,
                                  tuple(shards) if shards else None)]:
                    mym += 1
            except Exception as e:
                if getattr(e, "status", None) in (503, 504):
                    mys += 1
                else:
                    myf += 1
            my.append(time.perf_counter() - t0)
        with lock:
            lat.extend(my)
            failed[0] += myf
            mismatched[0] += mym
            shed[0] += mys

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return {"requests": len(lat), "failed": failed[0],
            "mismatched": mismatched[0], "shed": shed[0],
            "qps": round(len(lat) / wall, 1) if wall > 0 else 0.0,
            "p50_ms": _pct(lat, 0.5), "p99_ms": _pct(lat, 0.99)}


def ragged_gauntlet(h=None, n_clients: int = 32,
                    duration_s: float = 2.0,
                    bench_shards: int = 8,
                    events_shards: int = 3) -> dict:
    """The two ISSUE 8 A/Bs; returns the BENCH_r08 cell."""
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.executor.sched import ServingShedError
    from pilosa_tpu.obs import metrics

    if h is None:
        h, _cells = build_index(bench_shards, 8)
        build_events_index(h, events_shards)
    items = mixed_queries(bench_shards, events_shards)
    plain = Executor(h)
    expected = {(i, q, tuple(s) if s else None):
                _digest(plain.execute(i, q, s))
                for i, q, s in items}
    expected.update({(i, q, tuple(s) if s else None):
                     _digest(plain.execute(i, q, s))
                     for i, q, s in POINT_QUERIES})
    out: dict = {"clients": n_clients, "duration_s": duration_s,
                 "mix": {"items": len(items),
                         "indexes": ["bench", "events"]}}

    # -- A/B 1: ragged page-table dispatch vs per-group multi --------
    for arm, ragged in (("ragged", True), ("per_group", False)):
        ex = Executor(h)
        ex.enable_serving(window_s=0.001, max_batch=64,
                          cache_bytes=0,  # dispatch A/B: no cache arm
                          ragged=ragged, admission=False)
        for index, q, shards in items:   # warm compiles + stacks
            ex.execute_serving(index, q, shards)
        # unmeasured convergence pre-storm (hedge-gauntlet rule): a
        # fused program compiles per batch COMPOSITION, and the first
        # storm seconds are spent populating that executable space —
        # measuring them reports compile throughput, not serving
        _mixed_storm(ex.execute_serving, items, expected,
                     n_clients, duration_s * 0.75)
        d0 = (metrics.SERVING_DISPATCH.value(kind="ragged"),
              metrics.SERVING_DISPATCH.value(kind="group"))
        cell = _mixed_storm(ex.execute_serving, items, expected,
                            n_clients, duration_s)
        dr = metrics.SERVING_DISPATCH.value(kind="ragged") - d0[0]
        dg = metrics.SERVING_DISPATCH.value(kind="group") - d0[1]
        cell["device_dispatches"] = dr + dg
        cell["dispatches_per_query"] = round(
            (dr + dg) / max(cell["requests"], 1), 4)
        out[arm] = cell
        log(f"ragged A/B {arm}: {cell['qps']} qps "
            f"p99={cell['p99_ms']}ms "
            f"dispatches/query={cell['dispatches_per_query']} "
            f"mism={cell['mismatched']} failed={cell['failed']}")
    rg, pg = out["ragged"], out["per_group"]
    out["dispatch_reduction"] = round(
        pg["dispatches_per_query"]
        / max(rg["dispatches_per_query"], 1e-9), 2)
    out["qps_ratio_ragged_over_group"] = round(
        rg["qps"] / max(pg["qps"], 1e-9), 2)

    # -- A/B 2: QoS admission classes vs FIFO under a GroupBy storm --
    n_heavy = max(4, n_clients // 4)
    n_point = n_clients - n_heavy
    for arm, admission in (("classes", True), ("fifo", False)):
        ex = Executor(h)
        ex.enable_serving(window_s=0.001, max_batch=64,
                          cache_bytes=0, ragged=True,
                          admission=admission, heavy_slots=2,
                          queue_max=256)
        for index, q, shards in POINT_QUERIES:
            ex.execute_serving(index, q, shards)
        ex.execute_serving("bench", HEAVY_QUERY)   # warm the GroupBy
        stop_ev = threading.Event()
        heavy_done = [0]
        heavy_errs = [0]

        def heavy_client():
            while not stop_ev.is_set():
                try:
                    ex.execute_serving("bench", HEAVY_QUERY)
                    heavy_done[0] += 1
                except Exception:
                    heavy_errs[0] += 1
        hth = [threading.Thread(target=heavy_client)
               for _ in range(n_heavy)]
        for t in hth:
            t.start()
        time.sleep(0.2)  # let the heavy storm saturate first
        # unmeasured convergence pre-storm under the SAME heavy load:
        # point-batch compositions warm their executables before the
        # measured window opens (both arms equally)
        _mixed_storm(ex.execute_serving, POINT_QUERIES, expected,
                     n_point, duration_s * 0.75)
        cell = _mixed_storm(ex.execute_serving, POINT_QUERIES,
                            expected, n_point, duration_s)
        stop_ev.set()
        for t in hth:
            t.join()
        cell["heavy_completed"] = heavy_done[0]
        cell["heavy_errors"] = heavy_errs[0]
        out[f"qos_{arm}"] = cell
        log(f"QoS A/B {arm}: point p99={cell['p99_ms']}ms "
            f"p50={cell['p50_ms']}ms ({cell['requests']} point reads, "
            f"{heavy_done[0]} GroupBys, mism={cell['mismatched']})")
    fifo_p99 = out["qos_fifo"]["p99_ms"] or 1e-3
    cls_p99 = out["qos_classes"]["p99_ms"] or 1e-3
    out["point_p99_improvement_vs_fifo"] = round(fifo_p99 / cls_p99, 2)

    # -- backpressure: overflowing the heavy queue sheds typed 503 ---
    ex = Executor(h)
    layer = ex.enable_serving(window_s=0.001, max_batch=8,
                              cache_bytes=0, heavy_slots=1,
                              queue_max=2)
    ex.execute_serving("bench", HEAVY_QUERY)
    sheds = [0]
    typed = [0]
    other = [0]

    def flood():
        try:
            ex.execute_serving("bench", HEAVY_QUERY)
        except ServingShedError as e:
            sheds[0] += 1
            if e.status == 503 and e.retry_after_s > 0:
                typed[0] += 1
        except Exception:
            other[0] += 1
    fth = [threading.Thread(target=flood) for _ in range(10)]
    for t in fth:
        t.start()
    for t in fth:
        t.join()
    out["backpressure"] = {
        "flooded": len(fth), "shed": sheds[0],
        "shed_typed_503_retry_after": typed[0],
        "other_errors": other[0],
        "queue_max": layer.sched.queue_max}
    log(f"backpressure: {sheds[0]}/{len(fth)} shed "
        f"({typed[0]} typed 503+Retry-After), {other[0]} other errors")

    # acceptance booleans (asserted by the committed gauntlet run;
    # the smoke gates only the correctness subset)
    out["acceptance"] = {
        "bit_exact": (rg["mismatched"] == 0 and pg["mismatched"] == 0
                      and out["qos_classes"]["mismatched"] == 0
                      and out["qos_fifo"]["mismatched"] == 0),
        "zero_failed": (rg["failed"] == 0 and pg["failed"] == 0
                        and out["qos_classes"]["failed"] == 0
                        and out["qos_fifo"]["failed"] == 0),
        "dispatch_reduction_ge_2x": out["dispatch_reduction"] >= 2.0,
        "qps_no_worse": out["qps_ratio_ragged_over_group"] >= 0.95,
        "point_p99_improves_ge_2x":
            out["point_p99_improvement_vs_fifo"] >= 2.0,
        "sheds_typed": sheds[0] > 0 and typed[0] == sheds[0]
            and other[0] == 0,
    }
    return out


def ragged_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --ragged-smoke): a small
    mixed-index run proving the ISSUE 8 CORRECTNESS bars cheaply —

    - every response in every arm BIT-EXACT vs solo execution;
    - zero failed queries (sheds are typed, counted separately);
    - overflowing the heavy admission queue sheds as typed 503 with
      Retry-After (and nothing else leaks out);
    - the ragged program actually dispatched (the mechanism under
      test engaged, not silently fallen back).

    Latency and dispatch ratios are RECORDED in the JSON, never
    asserted — scheduler noise on a shared 2-core box swamps them
    (the committed BENCH_r08 gauntlet run asserts the ratios).
    """
    from pilosa_tpu.obs import metrics

    r0 = metrics.SERVING_DISPATCH.value(kind="ragged")
    out = ragged_gauntlet(
        n_clients=int(os.environ.get("PILOSA_TPU_RAGGED_CLIENTS",
                                     "12")),
        duration_s=float(os.environ.get(
            "PILOSA_TPU_RAGGED_DURATION_S", "1.0")),
        bench_shards=3, events_shards=2)
    ragged_fired = metrics.SERVING_DISPATCH.value(kind="ragged") - r0
    failures: list[str] = []
    acc = out["acceptance"]
    if not acc["bit_exact"]:
        failures.append("responses diverged from solo execution")
    if not acc["zero_failed"]:
        failures.append("queries failed during the storm")
    bp = out["backpressure"]
    if bp["shed"] < 1:
        failures.append("backpressure never shed — the bounded queue "
                        "was not exercised")
    if bp["shed_typed_503_retry_after"] != bp["shed"]:
        failures.append("a shed was not a typed 503 with Retry-After")
    if bp["other_errors"]:
        failures.append(f"{bp['other_errors']} non-typed errors "
                        "escaped the admission plane")
    if ragged_fired < 1:
        failures.append("no ragged dispatch fired — the fused path "
                        "silently fell back")
    out["ragged_dispatches"] = ragged_fired
    out["failures"] = failures
    print(json.dumps({"metric": "ragged_smoke", **out}))
    for msg in failures:
        log("ragged smoke: " + msg)
    return 1 if failures else 0
