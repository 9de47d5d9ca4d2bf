"""Standing-query plane gauntlets (ISSUE 18): the maintained-vs-
invalidated poller storm A/B, and the check.sh standing smoke."""

from __future__ import annotations

import json
import os
import time

from bench.common import _pct, log

INDEX = "sq"
POLL_PQL = [
    "Count(Row(f=1))",
    "Count(Union(Row(f=1), Row(f=2)))",
    "TopN(t, n=8)",
    "GroupBy(Rows(e), Rows(g))",
]
POLL_SQL = "SELECT COUNT(*) FROM sq WHERE f = 1"


def _stack_builds():
    """Total stack constructions so far (anything that wasn't served
    from residency): the maintained arm must not add to this."""
    from pilosa_tpu.obs import metrics
    total = 0.0
    for oc in ("miss", "rebuild", "page_rebuild", "patch"):
        total += metrics.STACK_CACHE.value(outcome=oc)
    return int(total)


def _maintain_totals(reg) -> dict:
    tot = {"incremental": 0, "fallback": 0, "noop": 0}
    for info in reg.list_info():
        for k in tot:
            tot[k] += info["maintains"].get(k, 0)
    return tot


def standing_cost_probe(n: int = 5000) -> dict:
    """Load-independent fixed cost of the standing plane's write-path
    tax (same STABLE-probe style as the flight/watchdog/stats
    probes): ``on_write`` when the written fields miss every
    registration (the narrowing check every non-subscribed write
    pays — one set intersection per registration), and the noop
    maintenance cycle when a registration's fields match but nothing
    actually changed (snapshot + compare, no state touched)."""
    from pilosa_tpu.api import API
    from pilosa_tpu.models.holder import Holder

    h = Holder(width=1 << 12)
    API(h).apply_schema({"indexes": [{"name": "probe", "fields": [
        {"name": "a", "options": {"type": "set",
                                  "cache_type": "none"}},
        {"name": "z", "options": {"type": "set"}}]}]})
    from pilosa_tpu.executor.executor import Executor
    ex = Executor(h)
    ex.enable_serving(window_s=0.0, max_batch=4)
    reg = ex.serving.standing
    idx = h.index("probe")
    f = idx.field("a")
    for r in range(4):
        f.set_bit(r, 7)
    for q in ("Count(Row(a=1))", "Count(Union(Row(a=1), Row(a=2)))",
              "TopN(a, n=4)", "GroupBy(Rows(a))"):
        reg.register("probe", q)

    t0 = time.perf_counter()
    for _ in range(n):
        reg.on_write("probe", fields={"z"})  # misses every read set
    miss_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n // 10):
        reg.on_write("probe", fields={"a"})  # match, nothing changed
    noop_us = (time.perf_counter() - t0) / (n // 10) * 1e6
    return {"onwrite_miss_cycle_us": round(miss_us, 2),
            "noop_maintain_cycle_us": round(noop_us, 2)}


def standing_gauntlet(n_pollers: int = 32, n_writers: int = 2,
                      arm_s: float = 4.0, n_shards: int = 4,
                      batch_cols: int = 48,
                      poll_interval_s: float = 0.02,
                      rate_target: int = 50000) -> dict:
    """ISSUE 18 acceptance: Count/TopN/GroupBy/SQL standing queries
    registered on the fused serving plane while ``n_writers`` land a
    mutation storm through the streaming write plane and
    ``n_pollers`` hammer the registered queries — run twice:

    - **maintained** arm: the standing plane advances each result
      write-through from per-fragment delta-log spans, so every poll
      is a version-fresh cache hit and ZERO stacks are built during
      the whole arm (maintenance — including any declared structural
      fallback — is host-side);
    - **invalidated** arm: ``PILOSA_TPU_STANDING=0`` — the same
      entries go stale on every write and each post-write poll pays
      a full cold re-execution through the fused dispatch.

    Bars: bit-exact at quiesce — after the maintained storm drains,
    every registered query's served result equals a cold executor
    run on the same holder (hard-gated); zero stack builds during
    the maintained arm (hard-gated); maintenance ran incrementally
    (delta in, delta out — not fallback-only); poll p50/p99 ratio
    invalidated/maintained recorded (gated only at TPU scale: on a
    2-core GIL host the ratio is scheduler noise, though maintained
    polls still win by construction).  Pollers refresh on a fixed
    ``poll_interval_s`` cadence (the dashboard model — see the
    poller comment); writers pace toward ``rate_target`` mutations/s
    and the sustained rate is recorded.
    """
    import threading

    import numpy as np

    from pilosa_tpu.api import API
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.ingest.stream import StreamWriter
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs import flight
    from pilosa_tpu.pql import parse
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    W = SHARD_WIDTH
    SPAN = 4096  # live column range per shard
    out: dict = {"pollers": n_pollers, "writers": n_writers,
                 "arm_s": arm_s, "shards": n_shards,
                 "rate_target": rate_target,
                 "poll_interval_ms": round(poll_interval_s * 1e3, 1),
                 "queries": POLL_PQL + [POLL_SQL]}

    h = Holder()
    api = API(h)
    api.apply_schema({"indexes": [{"name": INDEX, "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "t", "options": {"type": "set",
                                  "cache_type": "none"}},
        {"name": "e", "options": {"type": "set"}},
        {"name": "g", "options": {"type": "set"}}]}]})
    # seed every row the storm will touch (GroupBy re-scopes — one
    # declared fallback — if a write mints a brand-new row id, so the
    # steady-state storm stays inside the seeded row sets)
    for shard in range(n_shards):
        cols = [shard * W + 11 * k for k in range(80)]
        api.import_bits(INDEX, "f", [1 + (k % 4) for k in range(80)],
                        cols)
        api.import_bits(INDEX, "t", [k % 16 for k in range(80)], cols)
        api.import_bits(INDEX, "e", [k % 6 for k in range(80)], cols)
        api.import_bits(INDEX, "g", [k % 4 for k in range(80)], cols)
    h.index(INDEX).sync()
    ex = api.executor
    ex.enable_serving(window_s=0.001, max_batch=64,
                      cache_bytes=64 << 20)
    reg = ex.serving.standing
    wtr = StreamWriter(api, window_s=0.002, max_batch=1 << 13,
                       queue_max=1 << 14).start()

    registered = []
    for q in POLL_PQL:
        registered.append(reg.register(INDEX, q))
    registered.append(reg.register_sql(api.sql_engine, POLL_SQL))
    out["registered_n"] = len(registered)
    for q in POLL_PQL:  # warm compiles + serving batcher
        ex.execute_serving(INDEX, q)
    api.sql_engine.query_one(POLL_SQL)

    # -- one storm arm -------------------------------------------------
    def run_arm(label: str) -> dict:
        stop = threading.Event()
        lat: list[float] = []
        pfails = [0]
        lk = threading.Lock()
        bar = threading.Barrier(n_pollers + n_writers)

        def poller(ci):
            # dashboard model: each client REFRESHES on a fixed
            # cadence rather than free-running — without pacing the
            # invalidated arm's p50 is survivorship (stalled pollers
            # contribute few samples, fresh-gap hits dominate); paced,
            # p50 is the honest per-refresh cost and polls_per_s
            # shows who keeps cadence
            my, myf = [], 0
            bar.wait()
            i = ci
            nxt = time.perf_counter()
            while not stop.is_set():
                sql = (ci % 5 == 4)
                q = POLL_PQL[i % len(POLL_PQL)]
                i += 1
                t0 = time.perf_counter()
                try:
                    if sql:
                        api.sql_engine.query_one(POLL_SQL)
                    else:
                        ex.execute_serving(INDEX, q)
                except Exception:
                    myf += 1
                my.append(time.perf_counter() - t0)
                nxt = max(nxt + poll_interval_s, time.perf_counter())
                d = nxt - time.perf_counter()
                if d > 0:
                    stop.wait(d)
            with lk:
                lat.extend(my)
                pfails[0] += myf

        muts = [0] * n_writers
        werrs: list = [None] * n_writers

        def writer(wi):
            # deterministic batches: stride 11 never self-collides in
            # SPAN, row cycle stays inside the seeded sets, and small
            # batches keep each fragment's per-window delta spans well
            # under the log's overflow threshold (overflow is a
            # DECLARED fallback, but steady state should be delta-in/
            # delta-out)
            period = batch_cols * n_writers / (1.25 * rate_target)
            inflight = []
            seq = wi
            bar.wait()
            nxt = time.perf_counter()
            try:
                while not stop.is_set():
                    shard = seq % n_shards
                    off = ((seq * batch_cols
                            + np.arange(batch_cols)) * 11) % SPAN
                    cols = shard * W + off
                    fld, mod = (("f", 4) if seq % 3 == 0 else
                                ("t", 16) if seq % 3 == 1 else
                                ("e", 6))
                    rows = (off + seq) % mod + (1 if fld == "f" else 0)
                    m = wtr.submit(INDEX, fld, rows=rows, cols=cols,
                                   clear=(seq % 5 == 4), wait=False)
                    inflight.append(m)
                    muts[wi] += batch_cols
                    seq += n_writers
                    while len(inflight) > 4:
                        inflight.pop(0).event.wait(timeout=60)
                    nxt = max(nxt + period,
                              time.perf_counter() - 5 * period)
                    d = nxt - time.perf_counter()
                    if d > 0:
                        time.sleep(d)
                for m in inflight:  # drain: quiesce means LANDED
                    if not m.event.wait(timeout=60):
                        raise TimeoutError("ack never arrived")
                    if m.error is not None:
                        raise RuntimeError(str(m.error))
            except Exception as e:  # noqa: BLE001 — recorded, gated
                werrs[wi] = f"writer {wi}: {type(e).__name__}: {e}"

        builds0 = _stack_builds()
        maint0 = _maintain_totals(reg)
        ths = ([threading.Thread(target=poller, args=(ci,))
                for ci in range(n_pollers)]
               + [threading.Thread(target=writer, args=(wi,))
                  for wi in range(n_writers)])
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        time.sleep(arm_s)
        stop.set()
        for t in ths:
            t.join()
        wall = time.perf_counter() - t0
        time.sleep(0.05)  # let the last window's sweep+maintain land
        maint1 = _maintain_totals(reg)
        arm = {"polls": len(lat), "poll_failed": pfails[0],
               "polls_per_s": round(len(lat) / wall, 1),
               "poll_p50_ms": _pct(lat, 0.5),
               "poll_p99_ms": _pct(lat, 0.99),
               "mutations": sum(muts),
               "mutations_per_s": round(sum(muts) / wall, 1),
               "stack_builds": _stack_builds() - builds0,
               "maintain": {k: maint1[k] - maint0[k] for k in maint1},
               "writer_errors": [e for e in werrs if e]}
        log(f"standing[{label}]: {arm['polls']} polls p50="
            f"{arm['poll_p50_ms']}ms p99={arm['poll_p99_ms']}ms, "
            f"{arm['mutations_per_s']}/s muts, "
            f"stacks+{arm['stack_builds']}, maintain={arm['maintain']}")
        return arm

    flight.recorder.clear()
    out["maintained"] = run_arm("maintained")

    # -- quiesce: served results must equal a cold executor -----------
    cold = Executor(h)
    per_q = []
    for q in POLL_PQL:
        got = ex.execute_serving(INDEX, q)
        want = cold.execute(INDEX, parse(q))
        per_q.append({"query": q, "bit_exact": repr(got) == repr(want)})
    sql_got = api.sql_engine.query_one(POLL_SQL)
    sql_want = cold.execute(INDEX, parse("Count(Row(f=1))"))[0]
    per_q.append({"query": POLL_SQL,
                  "bit_exact": sql_got.rows[0][0] == sql_want})
    out["quiesce"] = per_q
    out["bit_exact_at_quiesce"] = all(p["bit_exact"] for p in per_q)

    # flight evidence: maintenance committed standing-route records,
    # and none of them built a stack (declared fallbacks included —
    # the structural re-seed is host-side)
    recs = [r for r in flight.recorder.recent(512)
            if r.get("route") == "standing"]
    outcomes: dict = {}
    stacked_recs = 0
    for r in recs:
        oc = r.get("maintain", "poll")
        outcomes[oc] = outcomes.get(oc, 0) + 1
        if any(k not in ("hit", "wait") for k in r.get("stack", {})):
            stacked_recs += 1
    out["flight_standing_records"] = len(recs)
    out["flight_maintain_outcomes"] = outcomes
    out["flight_standing_stack_builds"] = stacked_recs

    # -- invalidated arm: kill switch off, same storm -----------------
    os.environ["PILOSA_TPU_STANDING"] = "0"
    try:
        out["invalidated"] = run_arm("invalidated")
    finally:
        os.environ.pop("PILOSA_TPU_STANDING", None)

    m, i = out["maintained"], out["invalidated"]
    if m["poll_p50_ms"] and i["poll_p50_ms"]:
        out["poll_p50_invalidated_over_maintained"] = round(
            i["poll_p50_ms"] / m["poll_p50_ms"], 2)
        out["poll_p99_invalidated_over_maintained"] = round(
            i["poll_p99_ms"] / m["poll_p99_ms"], 2)
    if i["polls_per_s"]:
        # cadence-keeping under the same write storm: both arms aim
        # for n_pollers/poll_interval_s refreshes per second; the
        # invalidated arm's pollers stall on re-executions and fall
        # off cadence
        out["poll_throughput_maintained_over_invalidated"] = round(
            m["polls_per_s"] / i["polls_per_s"], 2)
    out["registered"] = reg.list_info()
    wtr.close()
    log(f"standing: p50 ratio "
        f"{out.get('poll_p50_invalidated_over_maintained')}x, p99 "
        f"ratio {out.get('poll_p99_invalidated_over_maintained')}x, "
        f"bit-exact={out['bit_exact_at_quiesce']}, maintained-arm "
        f"stacks={m['stack_builds']}")
    return out


def standing_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --standing-smoke): the full
    maintained-vs-invalidated A/B at 8 pollers — CORRECTNESS GATES
    ONLY (every registration admitted, zero poll/writer failures,
    bit-exact vs a cold executor at quiesce, zero stack builds on the
    maintained arm, maintenance actually incremental) plus the
    fixed-cost maintenance probes, gated like the watchdog/flight
    probes (onwrite-miss <= PILOSA_TPU_STANDING_ONWRITE_MAX_US,
    default 25us — the tax every non-subscribed write pays; noop
    maintain <= PILOSA_TPU_STANDING_NOOP_MAX_US, default 200us);
    the poll latency ratio is reported but never gated on a small
    box."""
    probe = standing_cost_probe()
    out = standing_gauntlet(
        n_pollers=int(os.environ.get(
            "PILOSA_TPU_STANDING_POLLERS", "8")),
        n_writers=int(os.environ.get(
            "PILOSA_TPU_STANDING_WRITERS", "2")),
        arm_s=float(os.environ.get(
            "PILOSA_TPU_STANDING_DURATION_S", "1.5")),
        n_shards=int(os.environ.get(
            "PILOSA_TPU_STANDING_SHARDS", "4")))
    out["cost_probe"] = probe
    failures: list[str] = []
    lim_miss = float(os.environ.get(
        "PILOSA_TPU_STANDING_ONWRITE_MAX_US", "25"))
    lim_noop = float(os.environ.get(
        "PILOSA_TPU_STANDING_NOOP_MAX_US", "200"))
    if probe["onwrite_miss_cycle_us"] > lim_miss:
        failures.append(
            f"on_write miss cycle {probe['onwrite_miss_cycle_us']}us "
            f"> {lim_miss}us — the standing plane taxes every "
            "non-subscribed write")
    if probe["noop_maintain_cycle_us"] > lim_noop:
        failures.append(
            f"noop maintain cycle {probe['noop_maintain_cycle_us']}us "
            f"> {lim_noop}us — snapshot/compare crept onto the "
            "write path")
    if out.get("registered_n", 0) < len(POLL_PQL) + 1:
        failures.append("not every standing query was admitted")
    for arm in ("maintained", "invalidated"):
        a = out.get(arm, {})
        if a.get("poll_failed", 1):
            failures.append(f"{a.get('poll_failed')} polls failed "
                            f"in the {arm} arm")
        if a.get("writer_errors"):
            failures.append(f"{arm} arm writer errors: "
                            + "; ".join(a["writer_errors"]))
        if a.get("polls", 0) <= 0:
            failures.append(f"zero polls completed in the {arm} arm")
        if a.get("mutations", 0) <= 0:
            failures.append(f"zero mutations landed in the {arm} arm")
    if not out.get("bit_exact_at_quiesce"):
        bad = [p["query"] for p in out.get("quiesce", [])
               if not p["bit_exact"]]
        failures.append("maintained results diverged from a cold "
                        "executor at quiesce: " + "; ".join(bad))
    m = out.get("maintained", {})
    if m.get("stack_builds", 1):
        failures.append(f"{m.get('stack_builds')} stacks built "
                        "during the maintained arm — polls paid "
                        "re-execution on the write-through path")
    if m.get("maintain", {}).get("incremental", 0) <= 0:
        failures.append("maintenance never advanced a result "
                        "incrementally — every write fell back")
    if out.get("flight_standing_stack_builds", 0):
        failures.append("a standing-route flight record shows a "
                        "stack build")
    out["failures"] = failures
    print(json.dumps({"metric": "standing_smoke", **out}))
    for msg in failures:
        log("standing smoke: " + msg)
    return 1 if failures else 0
