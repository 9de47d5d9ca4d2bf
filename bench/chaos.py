"""Chaos gauntlets (ISSUE 6): kill/rejoin under a client storm on a
real in-process cluster, the hedged-read A/B, and the check.sh chaos
smoke."""

from __future__ import annotations

import json
import os
import time

from bench.common import _pct, log


CHAOS_QUERIES = [
    "Count(Row(f=1))",
    "Count(Row(f=2))",
    "Row(f=2)",
    "Sum(Row(f=1), field=v)",
    "TopN(f, n=3)",
    "Count(Union(Row(f=1), Row(f=2)))",
    "Count(Intersect(Row(f=1), Row(f=3)))",
]


def _build_cluster(n_nodes: int = 3, replica_n: int = 2,
                   n_shards: int = 6, cols_per_shard: int = 64,
                   lease_ttl: float = 5.0):
    """In-process ClusterNode ring (real HTTP data plane between
    nodes) populated through the replicated import path.  The lease
    sits well above this box's GIL scheduling jitter — at 32 storm
    clients a starved heartbeat thread must not false-DOWN a healthy
    node (kill detection does not depend on the lease: a dead node's
    closed socket fails over on connection-refused immediately)."""
    from pilosa_tpu.cluster import ClusterNode, InMemDisCo
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    disco = InMemDisCo(lease_ttl=lease_ttl)
    holders = [Holder() for _ in range(n_nodes)]
    nodes = [ClusterNode(f"node{i}", disco, holder=holders[i],
                         replica_n=replica_n,
                         heartbeat_interval=0.2).open()
             for i in range(n_nodes)]
    nodes[0].apply_schema({"indexes": [{"name": "c", "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "v", "options": {"type": "int", "min": 0,
                                  "max": 1 << 20}}]}]})
    rows, cols, vals = [], [], []
    for s in range(n_shards):
        for i in range(cols_per_shard):
            col = s * SHARD_WIDTH + (i * 9973) % SHARD_WIDTH
            rows.append(1 + (i % 3))
            cols.append(col)
            vals.append((col * 7) % 1000)
    nodes[0].import_bits("c", "f", rows, cols)
    nodes[0].import_values("c", "v", cols, vals)
    return nodes, holders, disco


def _chaos_storm(node, queries, expected, n_clients: int,
                 duration_s: float) -> dict:
    """N client threads hammering the cluster query path; every
    response is checked bit-exact against `expected` and timestamped
    so event-window percentiles can be carved out afterwards."""
    import threading

    lock = threading.Lock()
    lat: list[tuple[float, float]] = []  # (t_end, dt)
    failed = 0
    mismatched = 0
    stop = time.perf_counter() + duration_s
    barrier = threading.Barrier(n_clients)

    def client(ci: int):
        nonlocal failed, mismatched
        my: list[tuple[float, float]] = []
        my_failed = my_mis = 0
        barrier.wait()
        i = ci
        while time.perf_counter() < stop:
            q = queries[i % len(queries)]
            i += 1
            t0 = time.perf_counter()
            try:
                r = node.query("c", q)
                if r["results"] != expected[q] or "partial" in r:
                    my_mis += 1
            except Exception:
                my_failed += 1
            my.append((time.perf_counter(), time.perf_counter() - t0))
        with lock:
            lat.extend(my)
            failed += my_failed
            mismatched += my_mis

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    return {"lat": lat, "failed": failed, "mismatched": mismatched,
            "wall": wall}


def _storm_cell(storm: dict) -> dict:
    durs = [d for _, d in storm["lat"]]
    return {"requests": len(durs),
            "failed": storm["failed"],
            "mismatched": storm["mismatched"],
            "qps": round(len(durs) / storm["wall"], 1)
            if storm["wall"] > 0 else 0.0,
            "p50_ms": _pct(durs, 0.5), "p99_ms": _pct(durs, 0.99)}


def chaos_gauntlet(n_clients: int = 32, duration_s: float = 6.0,
                   kill_at_s: float = 1.5,
                   rejoin_at_s: float = 3.5) -> dict:
    """The ROADMAP item 5 acceptance run: the mixed read gauntlet at
    ``n_clients`` while one worker is KILLED mid-traffic (node-crash
    fault through its heartbeat loop) and REJOINED via the warm-start
    protocol (peer resync + flight-recorder cache prefill before
    taking traffic).  Zero failed queries and a bounded p99 spike in
    the kill→rejoin event window are the acceptance bars; writes made
    while the victim is down prove the resync carried real deltas."""
    import threading

    from pilosa_tpu.cluster import ClusterNode
    from pilosa_tpu.obs import faults, flight, metrics as _m

    nodes, holders, disco = _build_cluster()
    prev_rec = (flight.recorder.enabled, flight.recorder._ring.maxlen)
    flight.recorder.configure(enabled=True, keep=4096)
    out: dict = {"clients": n_clients, "duration_s": duration_s}
    ev_names = ("node_down", "node_rejoin", "failover",
                "hedge_fired", "hedge_won", "load_shed")
    # snapshot so the cell reports THIS gauntlet's events, not the
    # process-cumulative counters (other gauntlets run first)
    ev0 = {e: _m.CLUSTER_EVENTS.value(event=e) for e in ev_names}
    try:
        expected = {q: nodes[0].query("c", q)["results"]
                    for q in CHAOS_QUERIES}
        for q in CHAOS_QUERIES:  # warm: per-node compile + stacks
            nodes[0].query("c", q)
        # fault-free baseline over the same cluster
        base = _chaos_storm(nodes[0], CHAOS_QUERIES, expected,
                            n_clients, duration_s=1.5)
        out["baseline"] = _storm_cell(base)

        events: dict[str, float] = {}

        def driver():
            try:
                _driver()
            except Exception as e:
                # a failed kill/rejoin must surface as ITSELF in the
                # cell (and fail the smoke), not as misleading
                # downstream assertions about resync/exactness
                out["driver_error"] = f"{type(e).__name__}: {e}"

        def _driver():
            from pilosa_tpu.cluster import InternalClient
            t0 = time.perf_counter()
            time.sleep(kill_at_s)
            # kill: armed node-crash fires in the victim's heartbeat
            # loop — it pauses (socket closed, beats stop) mid-traffic
            faults.inject("node-crash", match="node2")
            # wait until the socket is really gone before the
            # while-down write: a write the victim still acks would
            # leave the rejoin resync nothing to prove
            probe = InternalClient(timeout=0.5, retries=0)
            for _ in range(100):
                try:
                    probe.status(nodes[2].uri)
                    time.sleep(0.05)
                except Exception:
                    break
            events["kill"] = time.perf_counter() - t0
            # writes while the victim is down: the rejoin resync must
            # carry them (row 9 is outside the read mix, so reads stay
            # bit-exact throughout)
            from pilosa_tpu.shardwidth import SHARD_WIDTH
            down_cols = [s * SHARD_WIDTH + 5 for s in range(6)]
            nodes[0].import_bits("c", "f", [9] * len(down_cols),
                                 down_cols)
            time.sleep(max(rejoin_at_s - kill_at_s, 0.1))
            t_r = time.perf_counter()
            rejoined = ClusterNode("node2", disco, holder=holders[2],
                                   replica_n=2,
                                   heartbeat_interval=0.2)
            rejoined.open(warm=True)
            nodes[2] = rejoined
            events["rejoin"] = time.perf_counter() - t0
            events["warm_start_ms"] = round(
                (time.perf_counter() - t_r) * 1e3, 1)
            out["rejoin"] = {**(rejoined.warm_stats or {}),
                             "warm_start_ms": events["warm_start_ms"]}

        drv = threading.Thread(target=driver)
        t_storm0 = time.perf_counter()
        drv.start()
        storm = _chaos_storm(nodes[0], CHAOS_QUERIES, expected,
                             n_clients, duration_s)
        drv.join()
        cell = _storm_cell(storm)
        # event window: kill → 1 s after the rejoin completed
        w0 = t_storm0 + events.get("kill", 0.0)
        w1 = t_storm0 + events.get("rejoin", duration_s) + 1.0
        win = [d for t, d in storm["lat"] if w0 <= t <= w1]
        cell["event_window_p99_ms"] = _pct(win, 0.99)
        base_p99 = out["baseline"]["p99_ms"] or 1e-3
        cell["event_window_p99_spike"] = round(
            (cell["event_window_p99_ms"] or 0.0) / base_p99, 2)
        out["chaos"] = cell
        out["events_s"] = {k: round(v, 3) for k, v in events.items()
                           if k != "warm_start_ms"}
        # the rejoined node serves: fan-out THROUGH it stays exact,
        # and the while-down write is visible cluster-wide
        post = {q: nodes[2].query("c", q)["results"]
                for q in CHAOS_QUERIES}
        out["post_rejoin_exact"] = post == expected
        out["resync_write_visible"] = \
            nodes[2].query("c", "Count(Row(f=9))")["results"][0] == 6
        out["cluster_events"] = {
            e: _m.CLUSTER_EVENTS.value(event=e) - ev0[e]
            for e in ev_names}
        log(f"chaos c{n_clients}: {cell['requests']} reqs "
            f"failed={cell['failed']} mism={cell['mismatched']} "
            f"window p99={cell['event_window_p99_ms']}ms "
            f"({cell['event_window_p99_spike']}x baseline "
            f"{base_p99}ms)")
    finally:
        faults.clear("node-crash")
        flight.recorder.configure(enabled=prev_rec[0],
                                  keep=prev_rec[1])
        for n in nodes:
            try:
                n.close()
            except Exception:
                pass
    return out


def hedge_ab_gauntlet(n_clients: int = 2, duration_s: float = 5.0,
                      delay_ms: float = 200.0) -> dict:
    """Hedged-read A/B (ISSUE 6 acceptance): with a ``delay_ms``
    rpc-delay injected on ONE replica, read p99 without hedging grows
    by the full injected delay; with hedging (delay auto-derived from
    flight-recorder attempt records) it must come back to within 2x
    of the no-fault baseline — bit-exact in both arms.  Low client
    count on purpose: the A/B measures LATENCY restoration, and on a
    GIL-bound CPU host extra clients turn hedge RPCs into scheduler
    noise that swamps the per-request signal (on TPU serving hosts
    the RPC threads park in sockets, not the GIL).  Every arm runs an
    UNMEASURED pre-storm first: p99 over a few hundred requests is
    within a whisker of the sample max, so one cold-path straggler —
    a late compile, the hedged arm still converging its auto-derived
    delay from an empty flight ring — flips the cell; the measured
    storm must see steady state only."""
    from pilosa_tpu.obs import faults, flight, metrics as _m

    nodes, _holders, _disco = _build_cluster()
    prev_rec = (flight.recorder.enabled, flight.recorder._ring.maxlen)
    prev_hedge = os.environ.get("PILOSA_TPU_CLUSTER_HEDGE_MS")
    flight.recorder.configure(enabled=True, keep=4096)
    out: dict = {"clients": n_clients, "delay_injected_ms": delay_ms}
    try:
        expected = {q: nodes[0].query("c", q)["results"]
                    for q in CHAOS_QUERIES}
        for _ in range(3):  # warm: per-node compile + stacks
            for q in CHAOS_QUERIES:
                nodes[0].query("c", q)
        # baseline (no fault, hedging moot) — also populates the
        # flight ring the auto-derived hedge delay reads from
        os.environ["PILOSA_TPU_CLUSTER_HEDGE_MS"] = "-1"
        _chaos_storm(nodes[0], CHAOS_QUERIES, expected,
                     n_clients, duration_s=1.5)  # unmeasured
        base = _chaos_storm(nodes[0], CHAOS_QUERIES, expected,
                            n_clients, duration_s)
        out["baseline"] = _storm_cell(base)
        # the slow replica: every RPC to node1 pays delay_ms
        victim_uri = nodes[1].uri
        faults.inject("rpc-delay", match=victim_uri, times=0,
                      delay_s=delay_ms / 1e3)
        # delta base: only hedges fired by THIS A/B's arms count
        fired0 = _m.CLUSTER_EVENTS.value(event="hedge_fired")
        won0 = _m.CLUSTER_EVENTS.value(event="hedge_won")
        for mode, hedge_env in (("nohedge", "-1"), ("hedged", "0")):
            os.environ["PILOSA_TPU_CLUSTER_HEDGE_MS"] = hedge_env
            # fresh ring per arm: the hedged arm's auto-derived delay
            # must converge from ITS OWN attempt records, not inherit
            # the nohedge arm's delay-poisoned tail
            flight.recorder.clear()
            # unmeasured convergence pre-storm (same length per arm):
            # lets the hedged arm derive its delay from real attempt
            # records before the measured window opens
            _chaos_storm(nodes[0], CHAOS_QUERIES, expected,
                         n_clients, duration_s=1.5)
            storm = _chaos_storm(nodes[0], CHAOS_QUERIES, expected,
                                 n_clients, duration_s)
            out[mode] = _storm_cell(storm)
        base_p99 = out["baseline"]["p99_ms"] or 1e-3
        out["hedged_p99_over_baseline"] = round(
            (out["hedged"]["p99_ms"] or 0.0) / base_p99, 2)
        out["nohedge_p99_over_baseline"] = round(
            (out["nohedge"]["p99_ms"] or 0.0) / base_p99, 2)
        out["hedges"] = {
            "fired": _m.CLUSTER_EVENTS.value(event="hedge_fired")
            - fired0,
            "won": _m.CLUSTER_EVENTS.value(event="hedge_won") - won0}
        log(f"hedge A/B: baseline p99={base_p99}ms | "
            f"delay {delay_ms}ms nohedge "
            f"p99={out['nohedge']['p99_ms']}ms | hedged "
            f"p99={out['hedged']['p99_ms']}ms "
            f"({out['hedged_p99_over_baseline']}x baseline)")
    finally:
        faults.clear("rpc-delay")
        if prev_hedge is None:
            os.environ.pop("PILOSA_TPU_CLUSTER_HEDGE_MS", None)
        else:
            os.environ["PILOSA_TPU_CLUSTER_HEDGE_MS"] = prev_hedge
        flight.recorder.configure(enabled=prev_rec[0],
                                  keep=prev_rec[1])
        for n in nodes:
            try:
                n.close()
            except Exception:
                pass
    return out


def chaos_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --chaos-smoke): a short
    kill/rejoin run on a small in-process cluster proving the ISSUE 6
    acceptance bars cheaply —

    - ZERO failed queries while a worker dies (node-crash fault
      through its heartbeat loop) and warm-start-rejoins under a
      concurrent read storm;
    - every response BIT-EXACT vs the fault-free expectations (and
      never silently partial);
    - the rejoin resync actually carried the writes made while the
      victim was down (block repair > 0, write visible through the
      rejoined node).
    """
    out = chaos_gauntlet(
        n_clients=int(os.environ.get("PILOSA_TPU_CHAOS_CLIENTS", "8")),
        duration_s=float(os.environ.get(
            "PILOSA_TPU_CHAOS_DURATION_S", "4")),
        kill_at_s=1.0, rejoin_at_s=2.2)
    failures: list[str] = []
    if out.get("driver_error"):
        # the kill/rejoin driver's own failure is the root cause —
        # lead with it instead of the downstream resync assertions
        failures.append("chaos driver failed: " + out["driver_error"])
    chaos = out.get("chaos", {})
    if chaos.get("failed", 1):
        failures.append(f"{chaos.get('failed')} queries failed during "
                        "kill/rejoin (acceptance: zero)")
    if chaos.get("mismatched", 1):
        failures.append(f"{chaos.get('mismatched')} responses diverged "
                        "from the fault-free results")
    if not out.get("post_rejoin_exact"):
        failures.append("post-rejoin fan-out through the rejoined "
                        "node diverged")
    if not out.get("resync_write_visible"):
        failures.append("write made while the victim was down is not "
                        "visible after warm-start resync")
    if not (out.get("rejoin", {}).get("sync", {}) or {}).get("blocks"):
        failures.append("warm-start resync repaired zero fragment "
                        "blocks (expected the while-down write)")
    out["failures"] = failures
    print(json.dumps({"metric": "chaos_smoke", **out}))
    for msg in failures:
        log("chaos smoke: " + msg)
    return 1 if failures else 0
