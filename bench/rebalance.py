"""Scale-out chaos gauntlet (ISSUE 14): a third node joins a live
2-node cluster under the 32-client mixed storm, shards rebalance
through the epoch-fenced state machine with ZERO failed / ZERO
mismatched queries, while-transfer writes land bit-exact on the
recipient vs a cold rebuild, and a node then drains back out under
the same gates.  ``rebalance_smoke`` is the check.sh arm: the same
drill, smaller, with a transfer-interrupted fault armed so the run
must prove resume-or-rollback (correctness-only gates per the
2-core-box rule; latency ratios are recorded, never asserted)."""

from __future__ import annotations

import json
import os
import threading
import time

from bench.common import _pct, log

REB_QUERIES = [
    "Count(Row(f=1))",
    "Count(Row(f=2))",
    "Row(f=2)",
    "Sum(Row(f=1), field=v)",
    "Count(Union(Row(f=1), Row(f=2)))",
    "Count(Intersect(Row(f=1), Row(f=3)))",
]

N_SHARDS = 6
PER_SHARD = 48


def _seed_rows(n_shards=N_SHARDS, per_shard=PER_SHARD):
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    rows, cols, vals = [], [], []
    for s in range(n_shards):
        for i in range(per_shard):
            col = s * SHARD_WIDTH + (i * 9973) % SHARD_WIDTH
            rows.append(1 + (i % 3))
            cols.append(col)
            vals.append((col * 7) % 1000)
    return rows, cols, vals


def _build_cluster(n_nodes: int = 2):
    from pilosa_tpu.cluster import ClusterNode, InMemDisCo
    from pilosa_tpu.models.holder import Holder

    disco = InMemDisCo(lease_ttl=30)
    holders = [Holder() for _ in range(n_nodes + 1)]
    nodes = [ClusterNode(f"node{i}", disco, holder=holders[i],
                         replica_n=1, heartbeat_interval=5).open()
             for i in range(n_nodes)]
    nodes[0].apply_schema({"indexes": [{"name": "c", "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "v", "options": {"type": "int", "min": 0,
                                  "max": 1 << 20}}]}]})
    rows, cols, vals = _seed_rows()
    nodes[0].import_bits("c", "f", rows, cols)
    nodes[0].import_values("c", "v", cols, vals)
    return nodes, holders, disco


def _owner_probe(nodes, violations: list, stop: threading.Event,
                 index: str = "c", n_shards: int = N_SHARDS):
    """Sample the write-owner invariant through the whole storm: at
    no instant may a shard's routed owner set be empty or entirely
    fenced away (zero owners), and a node whose fence says MOVED must
    never be the routed primary (two disagreeing owners)."""
    while not stop.is_set():
        try:
            by_id = {n.node_id: n for n in nodes if n is not None}
            snap = next(iter(by_id.values())).snapshot()
            for s in range(n_shards):
                owners = snap.shard_nodes(index, s)
                if not owners:
                    violations.append(f"shard {s}: zero owners")
                    continue
                accepting = 0
                for o in owners:
                    node = by_id.get(o.id)
                    if node is None:
                        continue
                    fenced = {(e["index"], e["shard"]): e["state"]
                              for e in node.api.fences.payload()}
                    st = fenced.get((index, s))
                    if st != "moved":
                        accepting += 1
                    elif o is owners[0]:
                        violations.append(
                            f"shard {s}: routed primary {o.id} is "
                            f"fenced MOVED")
                if accepting == 0:
                    violations.append(
                        f"shard {s}: every routed owner fenced")
        except Exception:
            pass  # a node closing mid-sample is not an invariant hit
        time.sleep(0.02)


def _storm(node, expected, n_clients: int, duration_s: float,
           write_log: list, write_errors: list) -> dict:
    """n_clients mixed readers (bit-exact asserted per response) plus
    ONE writer appending row-9 bits on a deterministic schedule —
    disjoint from the read mix, so reads stay comparable while the
    writes prove live-migration visibility."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    lock = threading.Lock()
    lat: list[tuple[float, float]] = []
    failed = 0
    mismatched = 0
    stop_at = time.perf_counter() + duration_s
    stop = threading.Event()
    barrier = threading.Barrier(n_clients + 1)

    def client(ci: int):
        nonlocal failed, mismatched
        my: list[tuple[float, float]] = []
        my_f = my_m = 0
        barrier.wait()
        i = ci
        while time.perf_counter() < stop_at:
            q = REB_QUERIES[i % len(REB_QUERIES)]
            i += 1
            t0 = time.perf_counter()
            try:
                r = node.query("c", q)
                if r["results"] != expected[q] or "partial" in r:
                    my_m += 1
            except Exception:
                my_f += 1
            my.append((time.perf_counter(), time.perf_counter() - t0))
        with lock:
            lat.extend(my)
            failed += my_f
            mismatched += my_m

    def writer():
        barrier.wait()
        k = 0
        while time.perf_counter() < stop_at and not stop.is_set():
            col = ((k % N_SHARDS) * SHARD_WIDTH
                   + 1000 + (k // N_SHARDS) % 2000)
            try:
                node.import_bits("c", "f", [9], [col])
                write_log.append(col)
            except Exception as e:
                write_errors.append(f"{type(e).__name__}: {e}")
            k += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    wt = threading.Thread(target=writer)
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    wt.start()
    for t in threads:
        t.join()
    stop.set()
    wt.join()
    return {"lat": lat, "failed": failed, "mismatched": mismatched,
            "wall": time.perf_counter() - t_start}


def _cell(storm: dict) -> dict:
    durs = [d for _, d in storm["lat"]]
    return {"requests": len(durs), "failed": storm["failed"],
            "mismatched": storm["mismatched"],
            "qps": round(len(durs) / storm["wall"], 1)
            if storm["wall"] > 0 else 0.0,
            "p50_ms": _pct(durs, 0.5), "p99_ms": _pct(durs, 0.99)}


def _cold_row9_counts(write_log: list):
    """Oracle: a cold single-node rebuild of seed + row-9 writes;
    returns (total, per-shard) Count(Row(f=9))."""
    from pilosa_tpu.api import API
    from pilosa_tpu.models.holder import Holder

    api = API(Holder())
    api.apply_schema({"indexes": [{"name": "c", "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "v", "options": {"type": "int", "min": 0,
                                  "max": 1 << 20}}]}]})
    rows, cols, vals = _seed_rows()
    api.import_bits("c", "f", rows=rows, cols=cols)
    api.import_values("c", "v", cols=cols, values=vals)
    if write_log:
        api.import_bits("c", "f", rows=[9] * len(write_log),
                        cols=list(write_log))
    total = api.query("c", "Count(Row(f=9))")["results"][0]
    per_shard = {s: api.query("c", "Count(Row(f=9))",
                              shards=[s])["results"][0]
                 for s in range(N_SHARDS)}
    return total, per_shard


def rebalance_gauntlet(n_clients: int = 32, duration_s: float = 6.0,
                       join_at_s: float = 1.0,
                       interrupt: bool = False) -> dict:
    """The BENCH_r12 acceptance run: join-under-load then
    drain-under-load, each gated on 0 failed / 0 mismatched, p99
    spike recorded against the fault-free baseline, while-transfer
    writes bit-exact on the recipient vs cold rebuild, and the
    owner-invariant probe sampling throughout.  ``interrupt=True``
    arms a one-shot transfer-interrupted fault so the join must
    resume (the smoke's crash drill)."""
    from pilosa_tpu.cluster import (
        ClusterNode,
        RebalanceController,
        RebalanceError,
    )
    from pilosa_tpu.obs import faults, metrics as _m

    nodes, holders, disco = _build_cluster()
    out: dict = {"clients": n_clients, "duration_s": duration_s,
                 "interrupt_armed": bool(interrupt)}
    violations: list = []
    probe_stop = threading.Event()
    try:
        expected = {q: nodes[0].query("c", q)["results"]
                    for q in REB_QUERIES}
        for q in REB_QUERIES:  # warm compile + stacks
            nodes[0].query("c", q)
        base = _storm(nodes[0], expected, n_clients, 1.5, [], [])
        out["baseline"] = _cell(base)

        write_log: list = []
        write_errors: list = []
        events: dict = {}
        probe = threading.Thread(
            target=_owner_probe,
            args=(nodes, violations, probe_stop))
        probe.start()

        def driver():
            try:
                t0 = time.perf_counter()
                time.sleep(join_at_s)
                joiner = ClusterNode(
                    "node2", disco, holder=holders[2], replica_n=1,
                    heartbeat_interval=5).open(member=False)
                nodes.append(joiner)
                if interrupt:
                    faults.inject("transfer-interrupted", times=1)
                ctl = RebalanceController(nodes[0])
                plan = ctl.plan_join("node2")
                t_j = time.perf_counter()
                try:
                    ctl.run(plan)
                except RebalanceError:
                    events["interrupted"] = True
                    ctl.resume(plan)
                events["join_s"] = round(
                    time.perf_counter() - t0, 3)
                events["join_ms"] = round(
                    (time.perf_counter() - t_j) * 1e3, 1)
                out["join_plan"] = {
                    k: v for k, v in plan.to_dict().items()
                    if k != "phases"}
            except Exception as e:
                out["driver_error"] = f"{type(e).__name__}: {e}"

        drv = threading.Thread(target=driver)
        t_storm0 = time.perf_counter()
        drv.start()
        storm = _storm(nodes[0], expected, n_clients, duration_s,
                       write_log, write_errors)
        drv.join()
        cell = _cell(storm)
        w0 = t_storm0 + join_at_s
        w1 = t_storm0 + events.get("join_s", duration_s) + 0.5
        win = [d for t, d in storm["lat"] if w0 <= t <= w1]
        cell["event_window_p99_ms"] = _pct(win, 0.99)
        base_p99 = out["baseline"]["p99_ms"] or 1e-3
        cell["event_window_p99_spike"] = round(
            (cell["event_window_p99_ms"] or 0.0) / base_p99, 2)
        out["join_storm"] = cell
        out["events"] = events
        out["write_errors"] = write_errors[:5]
        out["writes_landed"] = len(write_log)

        # while-transfer writes: visible everywhere, and on the
        # recipient's own shards bit-exact vs a cold rebuild
        total, per_shard = _cold_row9_counts(write_log)
        out["row9_expected"] = total
        out["row9_cluster"] = nodes[0].query(
            "c", "Count(Row(f=9))")["results"][0]
        snap = nodes[0].snapshot()
        recip = {}
        joiner = nodes[-1]
        for s in range(N_SHARDS):
            if snap.shard_nodes("c", s)[0].id != "node2":
                continue
            got = joiner.api.query("c", "Count(Row(f=9))",
                                   shards=[s])["results"][0]
            recip[s] = (got, per_shard[s])
        out["recipient_shards_checked"] = len(recip)
        out["recipient_bit_exact"] = all(g == w
                                         for g, w in recip.values())
        out["post_join_reads_exact"] = all(
            n.query("c", q)["results"] == expected[q]
            for n in nodes for q in REB_QUERIES)

        # drain the newest node back out under the same storm
        drain_log: list = []
        drain_errors: list = []
        d_expected = {q: nodes[0].query("c", q)["results"]
                      for q in REB_QUERIES}

        def drain_driver():
            try:
                time.sleep(0.6)
                t_d = time.perf_counter()
                nodes[0].rebalance_drain("node2")
                events["drain_ms"] = round(
                    (time.perf_counter() - t_d) * 1e3, 1)
            except Exception as e:
                out["driver_error"] = (out.get("driver_error", "")
                                       + f" drain: {e}")

        # row 9 is now part of expected state: refresh expectations
        ddrv = threading.Thread(target=drain_driver)
        ddrv.start()
        dstorm = _storm(nodes[0], d_expected, n_clients,
                        max(3.0, duration_s / 2), drain_log,
                        drain_errors)
        ddrv.join()
        out["drain_storm"] = _cell(dstorm)
        out["drain_write_errors"] = drain_errors[:5]
        probe_stop.set()
        probe.join(timeout=5)
        out["owner_invariant_violations"] = violations[:10]
        total2, _ = _cold_row9_counts(write_log + drain_log)
        out["row9_after_drain_expected"] = total2
        out["row9_after_drain"] = nodes[0].query(
            "c", "Count(Row(f=9))")["results"][0]
        out["post_drain_reads_exact"] = all(
            nodes[0].query("c", q)["results"] == d_expected[q]
            for q in REB_QUERIES)
        out["roster"] = disco.roster()
        out["rebalance_counters"] = {
            "copy_ok": _m.REBALANCE_TOTAL.value(phase="copy",
                                                outcome="ok"),
            "fence_ok": _m.REBALANCE_TOTAL.value(phase="fence",
                                                 outcome="ok"),
            "release_ok": _m.REBALANCE_TOTAL.value(phase="release",
                                                   outcome="ok"),
            "rolled_back": _m.REBALANCE_TOTAL.value(
                phase="fence", outcome="rolled_back"),
            "bytes_copied": _m.REBALANCE_BYTES.value(kind="copied"),
            "bytes_delta": _m.REBALANCE_BYTES.value(
                kind="delta_replayed")}
        log(f"rebalance c{n_clients}: join "
            f"{out['join_storm']['requests']} reqs "
            f"failed={out['join_storm']['failed']} "
            f"mism={out['join_storm']['mismatched']} "
            f"p99 spike={out['join_storm']['event_window_p99_spike']}x"
            f" | drain failed={out['drain_storm']['failed']} "
            f"mism={out['drain_storm']['mismatched']}")
    finally:
        probe_stop.set()
        from pilosa_tpu.obs import faults as _f
        _f.clear("transfer-interrupted")
        for n in nodes:
            try:
                n.close()
            except Exception:
                pass
    return out


def rebalance_smoke() -> int:
    """check.sh gate (bench.py --rebalance-smoke): join-under-load
    with a one-shot transfer-interrupted fault armed — the migration
    must RESUME (or roll back and retry) and the run must show zero
    failed / zero mismatched queries, while-transfer writes bit-exact
    on the recipient, no owner-invariant violation, and a clean
    drain.  Correctness-only gates (2-core-box rule): the p99 spike
    is recorded in the JSON, never asserted here."""
    out = rebalance_gauntlet(
        n_clients=int(os.environ.get(
            "PILOSA_TPU_REBALANCE_CLIENTS", "8")),
        duration_s=float(os.environ.get(
            "PILOSA_TPU_REBALANCE_DURATION_S", "4")),
        join_at_s=0.8, interrupt=True)
    failures: list[str] = []
    if out.get("driver_error"):
        failures.append("rebalance driver failed: "
                        + out["driver_error"])
    for arm in ("join_storm", "drain_storm"):
        cell = out.get(arm, {})
        if cell.get("failed", 1):
            failures.append(f"{arm}: {cell.get('failed')} queries "
                            "failed (acceptance: zero)")
        if cell.get("mismatched", 1):
            failures.append(f"{arm}: {cell.get('mismatched')} "
                            "responses diverged")
    if not out.get("events", {}).get("interrupted"):
        failures.append("armed transfer-interrupted fault never "
                        "fired (the drill proved nothing)")
    if out.get("join_plan", {}).get("state") != "done":
        failures.append("join plan did not complete after resume")
    if not out.get("join_plan", {}).get("shards_moved"):
        failures.append("no shards moved — the join was a no-op")
    if out.get("write_errors") or out.get("drain_write_errors"):
        failures.append("writes failed during migration: "
                        f"{out.get('write_errors')}"
                        f"{out.get('drain_write_errors')}")
    if out.get("row9_cluster") != out.get("row9_expected"):
        failures.append(
            f"while-transfer writes lost: cluster row9="
            f"{out.get('row9_cluster')} vs cold rebuild "
            f"{out.get('row9_expected')}")
    if not out.get("recipient_bit_exact", False):
        failures.append("recipient-owned shards diverged from the "
                        "cold rebuild")
    if not out.get("recipient_shards_checked"):
        failures.append("joiner ended up owning zero shards")
    if out.get("owner_invariant_violations"):
        failures.append("owner invariant violated: "
                        f"{out['owner_invariant_violations'][:3]}")
    if out.get("row9_after_drain") != out.get(
            "row9_after_drain_expected"):
        failures.append("drain lost writes")
    if not out.get("post_drain_reads_exact"):
        failures.append("post-drain reads diverged")
    out["failures"] = failures
    print(json.dumps({"metric": "rebalance_smoke", **out}))
    for msg in failures:
        log("rebalance smoke: " + msg)
    return 1 if failures else 0
