"""Serving-path gauntlets: concurrent-serving A/B, flight-recorder
overhead, and the mixed read/write (delta-patch) gauntlet."""

from __future__ import annotations

import json
import os
import sys
import time

from bench.common import (
    SERVING_QUERIES,
    _client_storm,
    build_index,
    log,
)


def serving_gauntlet(h, clients_list=(1, 8, 32),
                     duration_s: float = 1.2) -> dict:
    """Concurrent-serving A/B: QPS and p50/p99 per client count, with
    the serving path (micro-batcher + versioned result cache,
    executor/serving.py) ON vs OFF over the same holder and query mix.
    The mix is a hot set of distinct read queries, the shape a serving
    tier sees from dashboard fan-out — exactly what cross-query
    dispatch coalescing and the result cache exist for.  Each mode
    cell now carries the flight recorder's per-phase breakdown
    (compile/upload/execute/wait) so future PRs can attribute wins
    instead of reporting only end-to-end percentiles."""
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.obs import flight

    queries = SERVING_QUERIES
    # ONE executor per mode, shared across client counts: each
    # Executor pins its own device tile stacks, and at 954 shards a
    # fresh engine per (mode, clients) cell would multiply HBM
    # residency 6x
    ex_plain = Executor(h)
    ex_srv = Executor(h)
    ex_srv.enable_serving(window_s=0.001, max_batch=64,
                          cache_bytes=64 << 20)
    prev_enabled = flight.recorder.enabled
    prev_keep = flight.recorder._ring.maxlen

    from pilosa_tpu.obs import roofline
    roofline.ensure_peak()  # one-time blocking probe, outside cells

    def run_mode(batched: bool, n_clients: int) -> dict:
        call = ex_srv.execute_serving if batched else ex_plain.execute
        for q in queries:  # warm: compile + tile-stack upload
            call("bench", q)
        # ring sized for the window so the breakdown sees every record
        flight.recorder.configure(enabled=True, keep=16384)
        flight.recorder.clear()
        rl0 = roofline.snapshot()
        cell = _client_storm(call, queries, n_clients, duration_s)
        cell["phase_breakdown_ms"] = flight.phase_breakdown(
            flight.recorder.recent(16384))
        # per-cell roofline: achieved GB/s + fraction-of-peak per op
        # over this cell's dispatches (ISSUE 10; recorded, not
        # asserted — CPU numbers are honest-but-humble host bandwidth)
        cell["roofline"] = roofline.window(rl0, roofline.snapshot())
        return cell

    out: dict = {}
    try:
        for nc in clients_list:
            ab = {"unbatched": run_mode(False, nc),
                  "batched": run_mode(True, nc)}
            ub, bt = ab["unbatched"]["qps"], ab["batched"]["qps"]
            ab["qps_speedup"] = round(bt / ub, 2) if ub else None
            out[f"c{nc}"] = ab
            log(f"serving c{nc}: unbatched {ub} qps "
                f"p99={ab['unbatched']['p99_ms']}ms | batched {bt} qps "
                f"p99={ab['batched']['p99_ms']}ms "
                f"({ab['qps_speedup']}x)")
    finally:
        flight.recorder.configure(enabled=prev_enabled, keep=prev_keep)
    from pilosa_tpu.obs import metrics as _m
    out["batch_size_p50"] = round(
        _m.SERVING_BATCH_SIZE.quantile(0.5), 2)
    out["result_cache_hits"] = _m.RESULT_CACHE.value(outcome="hit")
    return out


def tracing_overhead_gauntlet(h, n_clients: int = 8,
                              duration_s: float = 1.0,
                              rounds: int = 3) -> dict:
    """Flight-recorder overhead A/B on the serving gauntlet: the SAME
    workload with the recorder enabled vs disabled, interleaved
    (off/on per round) so clock drift cancels; best-of-rounds qps per
    mode.  `overhead_pct` is the cost of leaving the recorder ON;
    recorder-off is the shipped default-off-tracing cost the <2%
    acceptance bound speaks to (NopTracer + inactive accumulators)."""
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.obs import flight

    queries = SERVING_QUERIES
    ex = Executor(h)
    ex.enable_serving(window_s=0.001, max_batch=64,
                      cache_bytes=64 << 20)
    for q in queries:  # warm: compile + upload outside the A/B
        ex.execute_serving("bench", q)
    prev_enabled = flight.recorder.enabled
    import statistics as stats
    pair_overheads = []
    best = {"off": 0.0, "on": 0.0}
    p50s = {"off": [], "on": []}
    try:
        for _ in range(rounds):
            qps = {}
            for mode in ("off", "on"):
                flight.recorder.configure(enabled=mode == "on")
                flight.recorder.clear()
                cell = _client_storm(ex.execute_serving, queries,
                                     n_clients, duration_s)
                qps[mode] = cell["qps"]
                best[mode] = max(best[mode], cell["qps"])
                if cell["p50_ms"]:
                    p50s[mode].append(cell["p50_ms"])
            if qps["off"]:
                # back-to-back pairing cancels machine drift; the
                # median across pairs kills scheduler outliers
                pair_overheads.append(
                    (qps["off"] - qps["on"]) / qps["off"] * 100)
    finally:
        flight.recorder.configure(enabled=prev_enabled)
    overhead = (round(stats.median(pair_overheads), 2)
                if pair_overheads else None)
    p50_off = stats.median(p50s["off"]) if p50s["off"] else None
    probe = flight_cost_probe()
    probe.update(roofline_cost_probe())
    out = {"recorder_off_qps": best["off"],
           "recorder_on_qps": best["on"],
           "overhead_pct": overhead,
           **probe,
           "recorder_off_fixed_cost_pct_of_p50": round(
               probe["disabled_cycle_us_4t"] / (p50_off * 1e3) * 100, 3)
           if p50_off else None}
    log(f"tracing overhead: recorder off {best['off']} qps vs "
        f"on {best['on']} qps ({overhead}% median on-overhead); "
        f"fixed cycle cost on/off 4t = "
        f"{probe['enabled_cycle_us_4t']}/"
        f"{probe['disabled_cycle_us_4t']}us")
    return out


def flight_cost_probe(n: int = 20000, threads: int = 4) -> dict:
    """Load-independent fixed cost of the flight instrumentation: the
    begin/note/commit cycle timed solo and under `threads`-way
    contention, recorder on and off.  Unlike the qps A/B (scheduler
    noise swamps a ~5% effect on a shared 2-core box), these are
    stable and directly catch the regressions the smoke gate exists
    for — e.g. a contended lock reappearing on the hot path shows up
    as ~10x in the 4-thread cycle cost (the convoy measured and fixed
    in this PR), and the disabled cost bounds the always-on path the
    <2% acceptance criterion speaks to."""
    import threading

    from pilosa_tpu.obs import flight

    def cycle():
        f = flight.begin("bench", "probe")
        flight.note_phase("cache_lookup", 0.0001)
        flight.commit(f, 0.0002, route="cached")

    def storm(nthreads: int) -> float:
        def worker():
            for _ in range(n):
                cycle()
        ts = [threading.Thread(target=worker)
              for _ in range(nthreads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return (time.perf_counter() - t0) / (nthreads * n) * 1e6

    prev = flight.recorder.enabled
    try:
        flight.recorder.configure(enabled=True)
        on_1t, on_4t = storm(1), storm(threads)
        flight.recorder.configure(enabled=False)
        off_4t = storm(threads)
    finally:
        flight.recorder.configure(enabled=prev)
    return {"enabled_cycle_us_1t": round(on_1t, 2),
            "enabled_cycle_us_4t": round(on_4t, 2),
            "disabled_cycle_us_4t": round(off_4t, 2)}


def roofline_cost_probe(n: int = 8000, threads: int = 4) -> dict:
    """Fixed cost of trace propagation + roofline attribution
    (ISSUE 10 acceptance), same STABLE-probe style as
    flight_cost_probe.  The enabled cycle is the full remote-leg
    shape a cluster RPC pays: inherit the caller's trace id, record
    one span under a pushed tracer, serialize it to wire form
    (span_to_wire), run a flight begin/commit with one per-dispatch
    roofline.note.  Shares the PR 4 <=60us budget — a lock convoy,
    an accidental peak probe, or serialization blowup shows up here
    as a 10-1000x jump the qps A/B would drown in scheduler noise."""
    import threading

    from pilosa_tpu.obs import flight, roofline
    from pilosa_tpu.obs import tracing as _tr

    def cycle():
        # the PRODUCTION remote-leg scaffold (flight.remote_leg is
        # what server/http.py runs per traced RPC), so the gate
        # measures the real code path, not a probe-local imitation
        with flight.remote_leg("qprobe", keep=4):
            f = flight.begin("bench", "probe")
            with _tr.start_span("rpc:probe", node="probe"):
                # a dedicated op label: the synthetic notes must not
                # fold into a real op family's bandwidth gauge
                roofline.note("probe", 1 << 20, 0.001)
            flight.commit(f, 0.0002, route="cached")

    def storm(nthreads: int) -> float:
        def worker():
            for _ in range(n):
                cycle()
        ts = [threading.Thread(target=worker)
              for _ in range(nthreads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return (time.perf_counter() - t0) / (nthreads * n) * 1e6

    prev_rec = flight.recorder.enabled
    # fraction-branch cost included via a fake peak; swap_state
    # restores EXACTLY what was there (enabled flag and peak,
    # including unset) so later bench cells never attribute against
    # the probe's made-up denominator
    prev_state = roofline.swap_state(
        enabled=True,
        peak_bytes_per_s=roofline.peak_or_none() or 1e9)
    try:
        flight.recorder.configure(enabled=True)
        on_4t = storm(threads)
        roofline.configure(enabled=False)
        off_4t = storm(threads)
    finally:
        roofline.swap_state(*prev_state)
        flight.recorder.configure(enabled=prev_rec)
    return {"roofline_on_cycle_us_4t": round(on_4t, 2),
            "roofline_off_cycle_us_4t": round(off_4t, 2)}


def mixed_rw_gauntlet(h, n_readers: int = 32,
                      write_rates=(10, 100, 1000),
                      duration_s: float = 1.2) -> dict:
    """Mixed-workload serving: N concurrent readers + 1 writer doing
    point writes at each target rate, A/B with the incremental stack
    maintenance path (delta patching, executor/stacked.py) on vs off.
    Without patching every point write invalidates whole device
    stacks and the next read pays a full O(S*W) restack + upload;
    with it the read pays an O(delta) patch.  Reports read p50/p99
    and restacked-bytes-per-write from the TileStackCache counters —
    the direct attribution of the write-path win."""
    import statistics as stats
    import threading

    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    from pilosa_tpu.obs import flight

    read_qs = [
        "Count(Intersect(Row(a=1), Row(b=1)))",
        "Count(Row(a=1))",
        "TopN(t, n=10)",
        "Sum(Row(a=1), field=age)",
    ]
    out: dict = {}
    prev_flag = os.environ.get("PILOSA_TPU_STACK_PATCH")
    prev_rec = (flight.recorder.enabled, flight.recorder._ring.maxlen)
    try:
        for patch_on in (True, False):
            os.environ["PILOSA_TPU_STACK_PATCH"] = \
                "1" if patch_on else "0"
            ex = Executor(h)
            cache = ex.stacked.cache
            for q in read_qs:  # warm: compile + resident stacks
                ex.execute("bench", q)
            mode_key = "patch_on" if patch_on else "patch_off"
            for rate in write_rates:
                patched0, rebuilt0 = (cache.patched_bytes,
                                      cache.rebuilt_bytes)
                flight.recorder.configure(enabled=True, keep=16384)
                flight.recorder.clear()
                lat: list[float] = []
                lock = threading.Lock()
                writes = 0
                stop_t = time.perf_counter() + duration_s
                barrier = threading.Barrier(n_readers + 1)

                def writer():
                    nonlocal writes
                    barrier.wait()
                    period = 1.0 / rate
                    nxt, i = time.perf_counter(), 0
                    while time.perf_counter() < stop_t:
                        # toggle pairs over advancing columns so
                        # (nearly) every write flips a bit and bumps
                        # the fragment version — a no-op Set would
                        # invalidate nothing and measure nothing
                        col = (i // 2) % SHARD_WIDTH
                        op = "Set" if i % 2 == 0 else "Clear"
                        ex.execute("bench", f"{op}({col}, a=1)")
                        writes += 1
                        i += 1
                        nxt += period
                        d = nxt - time.perf_counter()
                        if d > 0:
                            time.sleep(d)

                def reader(ci: int):
                    my: list[float] = []
                    barrier.wait()
                    i = ci
                    while time.perf_counter() < stop_t:
                        q = read_qs[i % len(read_qs)]
                        i += 1
                        t0 = time.perf_counter()
                        ex.execute("bench", q)
                        my.append(time.perf_counter() - t0)
                    with lock:
                        lat.extend(my)

                threads = [threading.Thread(target=writer)] + [
                    threading.Thread(target=reader, args=(ci,))
                    for ci in range(n_readers)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                lat.sort()
                n = len(lat)
                pb = cache.patched_bytes - patched0
                rb = cache.rebuilt_bytes - rebuilt0
                cell = {
                    "reads": n,
                    "writes": writes,
                    "read_p50_ms": round(lat[n // 2] * 1e3, 3)
                    if n else None,
                    "read_p99_ms": round(
                        lat[min(n - 1, int(n * 0.99))] * 1e3, 3)
                    if n else None,
                    "read_mean_ms": round(stats.fmean(lat) * 1e3, 3)
                    if n else None,
                    "restacked_bytes_per_write": round(
                        (pb + rb) / writes) if writes else None,
                    "patched_bytes": pb,
                    "rebuilt_bytes": rb,
                    # per-phase attribution: under writes the A/B
                    # should show the patch path's upload_ms shrink
                    "phase_breakdown_ms": flight.phase_breakdown(
                        flight.recorder.recent(16384)),
                }
                out.setdefault(f"w{rate}", {})[mode_key] = cell
                log(f"mixed-rw w{rate}/s {mode_key}: "
                    f"p50={cell['read_p50_ms']}ms "
                    f"p99={cell['read_p99_ms']}ms "
                    f"restacked/write={cell['restacked_bytes_per_write']}B "
                    f"({n} reads, {writes} writes)")
    finally:
        if prev_flag is None:
            os.environ.pop("PILOSA_TPU_STACK_PATCH", None)
        else:
            os.environ["PILOSA_TPU_STACK_PATCH"] = prev_flag
        flight.recorder.configure(enabled=prev_rec[0],
                                  keep=prev_rec[1])
    for rate_key, ab in out.items():
        on, off = ab.get("patch_on"), ab.get("patch_off")
        if on and off and on["read_p50_ms"]:
            ab["read_p50_speedup"] = round(
                off["read_p50_ms"] / on["read_p50_ms"], 2)
    return out


def overhead_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --overhead-smoke): a tiny
    serving micro-bench with the flight recorder on vs off.  The HARD
    gates are the stable fixed-cost probes (see flight_cost_probe —
    the qps A/B jitters ±30% on a shared 2-core box, far above the
    ~5% true effect, so it only backstops catastrophic regressions):

    - disabled cycle (4-thread) <= PILOSA_TPU_OVERHEAD_OFF_MAX_US
      (default 8us — measured ~1.2us; this is the always-on path the
      <2% acceptance bound speaks to)
    - enabled cycle (4-thread) <= PILOSA_TPU_OVERHEAD_ON_MAX_US
      (default 60us — measured ~11us; a hot-path lock convoy shows
      up here as ~10x)
    - median qps overhead <= PILOSA_TPU_OVERHEAD_MAX_PCT (default 60)
    - roofline-attribution cycle (flight cycle + per-dispatch note,
      4-thread, attribution ON) <= PILOSA_TPU_ROOFLINE_ON_MAX_US
      (default 60us — the ISSUE 10 acceptance budget; an accidental
      peak probe or lock convoy on the dispatch path shows as
      1000x)
    """
    h, _ = build_index(2, 4)
    out = tracing_overhead_gauntlet(h, n_clients=4, duration_s=0.6,
                                    rounds=3)
    lim_pct = float(os.environ.get("PILOSA_TPU_OVERHEAD_MAX_PCT", "60"))
    lim_off = float(os.environ.get("PILOSA_TPU_OVERHEAD_OFF_MAX_US", "8"))
    lim_on = float(os.environ.get("PILOSA_TPU_OVERHEAD_ON_MAX_US", "60"))
    lim_roof = float(os.environ.get("PILOSA_TPU_ROOFLINE_ON_MAX_US",
                                    "60"))
    out["thresholds"] = {"qps_overhead_pct": lim_pct,
                         "disabled_cycle_us": lim_off,
                         "enabled_cycle_us": lim_on,
                         "roofline_on_cycle_us": lim_roof}
    print(json.dumps({"metric": "tracing_overhead_smoke", **out}))
    failures = []
    if out["disabled_cycle_us_4t"] > lim_off:
        failures.append(
            f"disabled cycle {out['disabled_cycle_us_4t']}us > "
            f"{lim_off}us")
    if out["enabled_cycle_us_4t"] > lim_on:
        failures.append(
            f"enabled cycle {out['enabled_cycle_us_4t']}us > "
            f"{lim_on}us")
    if out["roofline_on_cycle_us_4t"] > lim_roof:
        failures.append(
            f"roofline-attribution cycle "
            f"{out['roofline_on_cycle_us_4t']}us > {lim_roof}us")
    if out["overhead_pct"] is not None and out["overhead_pct"] > lim_pct:
        failures.append(
            f"qps overhead {out['overhead_pct']}% > {lim_pct}%")
    for msg in failures:
        log("tracing-overhead smoke: " + msg)
    return 1 if failures else 0
