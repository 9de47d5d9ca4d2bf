"""Streaming write plane gauntlets (ISSUE 7): the multi-writer
kill-mid-window storm with restart + replay, and the check.sh
write-storm smoke."""

from __future__ import annotations

import json
import os
import time

from bench.common import _index_state, _pct, log


def write_storm_gauntlet(n_readers: int = 32, n_writers: int = 4,
                         post_crash_s: float = 4.0,
                         rate_target: int = 50000,
                         batch_cols: int = 8192,
                         pipeline_depth: int = 4,
                         crash_after_windows: int = 3) -> dict:
    """ISSUE 7 acceptance: a sustained multi-writer mutation storm at
    ``rate_target`` mutations/s through the streaming write plane
    (coalesced windows, durable acks, pipelined client batches) while
    ``n_readers`` hammer the read path — and the process is KILLED
    mid-window (armed wal-torn fault tears a shard WAL during a
    window's sync) and restarted from disk, writers replaying their
    unacked batches.  The crash trigger is PROGRESS-based, not
    wall-clock: the fault arms only after ``crash_after_windows``
    windows durably landed, so the kill always strikes a plane with
    real acked state behind it (a wall-clock trigger on a starved box
    kills window #1 and proves nothing).  Bars:

    - ZERO acknowledged-record loss: the final state (and a fresh
      reopen from disk) is bit-exact vs a cold rebuild that applies
      every ACKED batch exactly once — so replayed unacked batches
      converged idempotently and nothing acked went missing;
    - read p99 under the storm within 2x of the read-only baseline
      (reported always; hard-gated only on TPU/large-box runs — on a
      2-core GIL host the ratio is scheduler noise);
    - the crash actually exercised replay (failed window + replayed
      batches > 0) and the restarted plane landed windows of its own.

    Writers pipeline ``pipeline_depth`` batches in flight (submit
    wait=False, journal on ack) — per-tenant FIFO admission + arrival-
    order window groups keep each writer's batches landing in submit
    order, so the unacked tail at the crash is a contiguous suffix
    and replaying it in order preserves last-write-wins.  Batches are
    deterministic (no RNG): a replayed submission is bitwise the
    original, and value-batch columns stride a coprime so no two
    batches close enough to share a window collide.
    """
    import shutil
    import tempfile
    import threading
    from collections import deque

    import numpy as np

    from pilosa_tpu.api import API
    from pilosa_tpu.ingest.stream import StreamWriter, WriteBacklogError
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs import faults
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    W = SHARD_WIDTH
    INDEX = "ws"
    SPAN = 200000  # live column range per shard
    n_shards = max(2 * n_writers, 8)
    datadir = tempfile.mkdtemp(prefix="pilosa_write_storm_")
    schema = {"indexes": [{"name": INDEX, "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "v", "options": {"type": "int", "min": 0,
                                  "max": 1 << 20}}]}]}
    read_qs = ["Count(Row(f=1))",
               "Count(Intersect(Row(f=1), Row(f=2)))",
               "Sum(field=v)"]
    out: dict = {"readers": n_readers, "writers": n_writers,
                 "rate_target": rate_target, "batch_cols": batch_cols,
                 "pipeline_depth": pipeline_depth}
    state: dict = {}
    state_lock = threading.Lock()
    restart_done = threading.Event()
    stop = threading.Event()
    abort = threading.Event()  # driver gave up — writers bail out

    def open_plane(fresh: bool):
        h = Holder(path=datadir)
        api = API(h)
        if fresh:
            api.apply_schema(schema)
        else:
            h.load_schema()
        # readers ride the PR 2 serving layer on the API's OWN
        # executor — the production read plane (fused dispatch +
        # versioned result cache), and the executor whose cache the
        # write plane's narrowed per-window sweeps actually target
        api.executor.enable_serving(window_s=0.001, max_batch=64,
                                    cache_bytes=64 << 20)
        wtr = StreamWriter(api, window_s=0.002, max_batch=1 << 14,
                           queue_max=1 << 15).start()
        with state_lock:
            state["holder"], state["api"] = h, api
            state["writer"], state["ex"] = wtr, api.executor
        return h, api, wtr

    h, api, wtr = open_plane(fresh=True)
    # seed the read set: rows 1..3 across the shard space
    for s in range(n_shards):
        cols = [s * W + k for k in range(64)]
        api.import_bits(INDEX, "f",
                        [1 + (k % 3) for k in range(64)], cols)
        api.import_values(INDEX, "v", cols,
                          [(c % 997) for c in cols])
    h.index(INDEX).sync()
    ex0 = state["ex"]
    for q in read_qs:  # warm compiles + stacks
        ex0.execute_serving(INDEX, q)

    # -- readers (event-driven: one storm helper serves the baseline
    # and the full-duration storm) -----------------------------------
    def read_storm(stop_ev):
        lat: list[float] = []
        fails = [0]
        lk = threading.Lock()
        bar = threading.Barrier(n_readers)

        def reader(ci):
            my = []
            myf = 0
            bar.wait()
            i = ci
            while not stop_ev.is_set():
                q = read_qs[i % len(read_qs)]
                i += 1
                t0 = time.perf_counter()
                try:
                    with state_lock:
                        ex = state["ex"]
                    ex.execute_serving(INDEX, q)
                except Exception:
                    myf += 1
                my.append(time.perf_counter() - t0)
            with lk:
                lat.extend(my)
                fails[0] += myf
        ths = [threading.Thread(target=reader, args=(ci,))
               for ci in range(n_readers)]
        for t in ths:
            t.start()
        return ths, lat, fails

    bstop = threading.Event()
    ths, base_lat, base_fails = read_storm(bstop)
    time.sleep(1.5)
    bstop.set()
    for t in ths:
        t.join()
    base_p99 = _pct(base_lat, 0.99)
    out["baseline"] = {"reads": len(base_lat), "failed": base_fails[0],
                       "p50_ms": _pct(base_lat, 0.5),
                       "p99_ms": base_p99}

    # -- the storm -----------------------------------------------------
    journals: list[list] = [[] for _ in range(n_writers)]
    replays = [0] * n_writers
    sheds = [0] * n_writers
    werrs: list = [None] * n_writers

    def make_entry(wi: int, seq: int):
        """Deterministic batch #seq of writer wi: disjoint shard pair
        per writer, columns stride 7 (coprime with SPAN) so a batch
        never self-collides and value batches near enough to coalesce
        into one window never overlap (LWW stays well-defined)."""
        base = (2 * wi + (seq % 2)) * W
        off = ((seq * batch_cols + np.arange(batch_cols)) * 7) % SPAN
        if seq % 3 == 2:
            return ("v", None, base + off, (off * 31 + seq) % 1000)
        return ("f", 8 + (off % 4), base + off, None)

    def writer(wi: int):
        tenant = f"w{wi}"
        # offered load carries 25% headroom over the bar so the
        # measured sustained rate is plane-limited, not pacing-
        # limited (pacing at exactly the bar can only ever show
        # <100% of it — open-loop load-testing practice)
        period = batch_cols * n_writers / (1.25 * max(rate_target, 1))
        inflight: deque = deque()  # (entry, Mutation) in submit order

        def submit_entry(entry):
            kind, rows, cols, vals = entry
            with state_lock:
                w = state["writer"]
            if kind == "v":
                return w.submit(INDEX, "v", cols=cols, values=vals,
                                tenant=tenant, wait=False)
            return w.submit(INDEX, "f", rows=rows, cols=cols,
                            tenant=tenant, wait=False)

        def resubmit(entry):
            """Submit with shed-retry + crash-wait; None iff aborted.
            Deadline-bounded so a plane that never recovers surfaces
            as a writer error instead of hanging the gauntlet."""
            t0 = time.perf_counter()
            while not abort.is_set():
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("plane never recovered")
                try:
                    return submit_entry(entry)
                except WriteBacklogError as e:
                    sheds[wi] += 1
                    time.sleep(min(e.retry_after_s, 0.25))
                except Exception:
                    # plane (still) dead — wait out the restart
                    restart_done.wait(timeout=60)
                    time.sleep(0.02)
            return None

        def recover():
            """The plane died under our in-flight batches: wait out
            the restart, then replay every unacked batch in order —
            the client half of the exactly-once contract (per-tenant
            FIFO acks make the unacked tail a contiguous suffix)."""
            replays[wi] += len(inflight)
            restart_done.wait(timeout=120)
            old = list(inflight)
            inflight.clear()
            for entry, _m in old:
                m = resubmit(entry)
                if m is None:
                    return
                inflight.append((entry, m))

        def await_oldest():
            entry, m = inflight[0]
            if not m.event.wait(timeout=120):
                raise TimeoutError("ack never arrived")
            if m.error is not None:
                recover()
                return
            journals[wi].append(entry)  # acked ⇒ journaled
            inflight.popleft()

        try:
            nxt = time.perf_counter()
            seq = 0
            while not stop.is_set() and not abort.is_set():
                while len(inflight) >= pipeline_depth:
                    await_oldest()
                entry = make_entry(wi, seq)
                m = resubmit(entry)
                if m is None:
                    return
                inflight.append((entry, m))
                seq += 1
                # pace toward rate_target; after a stall (crash +
                # restart) allow a bounded catch-up burst only
                nxt = max(nxt + period,
                          time.perf_counter() - 5 * period)
                d = nxt - time.perf_counter()
                if d > 0:
                    time.sleep(d)
            while inflight and not abort.is_set():
                await_oldest()
        except Exception as e:  # pragma: no cover - diagnostics
            werrs[wi] = f"{type(e).__name__}: {e}"

    events: dict = {}

    def crash_driver():
        try:
            with state_lock:
                wtr1 = state["writer"]
            t0 = time.perf_counter()
            # warm mark: the sustained rate is measured from AFTER
            # the first window landed — the cold ramp (first
            # compiles, first stack/cache fills) is not "sustained"
            while wtr1.windows_landed < 1:
                if time.perf_counter() - t0 > 90:
                    raise RuntimeError(
                        "no window landed in 90s — nothing to "
                        "crash into")
                time.sleep(0.005)
            t_warm = time.perf_counter()
            landed_warm = wtr1.mutations_landed
            # progress trigger: arm only once the plane has durable
            # acked windows behind it AND the writers have journaled
            # a full pipeline turn of acks (so the kill puts real
            # acknowledged state at risk and the pre-crash rate is a
            # measured steady state, not a cold start)
            min_acked = n_writers * pipeline_depth
            while (wtr1.windows_landed < crash_after_windows
                   or sum(len(j) for j in journals) < min_acked
                   or time.perf_counter() - t_warm < 2.5):
                if time.perf_counter() - t0 > 90:
                    raise RuntimeError(
                        f"only {wtr1.windows_landed} windows / "
                        f"{sum(len(j) for j in journals)} acked "
                        f"batches in 90s — nothing to crash into")
                time.sleep(0.005)
            events["windows_before_crash"] = wtr1.windows_landed
            # landed = durably synced AND acked to submitters (the
            # plane fires the ack events before bumping the counter);
            # the journals lag one pipeline turn behind under load,
            # so they undercount the sustained rate
            events["landed_before_crash"] = \
                wtr1.mutations_landed - landed_warm
            events["acked_before_crash"] = sum(
                len(j) for j in journals) * batch_cols
            events["precrash_wall_s"] = time.perf_counter() - t_warm
            faults.inject("wal-torn", match=datadir, times=1)
            t1 = time.perf_counter()
            while wtr1.failed is None:
                if time.perf_counter() - t1 > 60:
                    raise RuntimeError("wal-torn never fired")
                time.sleep(0.005)
            events["crash_detect_s"] = time.perf_counter() - t1
            # restart: drop the dead process's state, reopen from
            # disk (native WAL recovery drops the torn tx), resume
            t2 = time.perf_counter()
            with state_lock:
                old_h = state["holder"]
            old_h.close()
            open_plane(fresh=False)
            events["restart_ms"] = round(
                (time.perf_counter() - t2) * 1e3, 1)
            events["restarted_at"] = time.perf_counter()
        except Exception as e:
            out["driver_error"] = f"{type(e).__name__}: {e}"
            abort.set()
        finally:
            restart_done.set()

    wths = [threading.Thread(target=writer, args=(wi,))
            for wi in range(n_writers)]
    drv = threading.Thread(target=crash_driver)
    t_storm0 = time.perf_counter()
    rths, storm_lat, storm_fails = read_storm(stop)
    for t in wths:
        t.start()
    drv.start()
    restart_done.wait(timeout=240)
    # post-crash phase: keep the storm up until the RESTARTED plane
    # proved productive (landed its own windows) or the budget ran out
    t_post = time.perf_counter()
    while time.perf_counter() - t_post < max(post_crash_s, 1.0):
        if abort.is_set():
            break
        with state_lock:
            wcur = state["writer"]
        if (wcur is not wtr
                and wcur.windows_landed >= crash_after_windows
                and time.perf_counter() - t_post >= post_crash_s / 2):
            break
        time.sleep(0.05)
    stop.set()
    for t in wths:  # drain their in-flight tails (windows keep landing)
        t.join()
    drv.join()
    storm_wall = time.perf_counter() - t_storm0
    for t in rths:
        t.join()
    with state_lock:
        w2, h2 = state["writer"], state["holder"]
    w2.close()  # drain + final sync

    acked = sum(len(j) for j in journals) * batch_cols
    post_landed = w2.windows_landed if w2 is not wtr else 0
    storm_p99 = _pct(storm_lat, 0.99)
    out["storm"] = {
        "reads": len(storm_lat), "read_failed": storm_fails[0],
        "read_p50_ms": _pct(storm_lat, 0.5), "read_p99_ms": storm_p99,
        "acked_mutations": acked,
        "mutations_per_s": round(acked / storm_wall, 1),
        "windows_landed": wtr.windows_landed + post_landed,
        "windows_failed": wtr.windows_failed + (
            w2.windows_failed if w2 is not wtr else 0),
        "windows_landed_post_restart": post_landed,
        "mutations_per_window": round(
            (wtr.mutations_landed + (
                w2.mutations_landed if w2 is not wtr else 0))
            / max(1, wtr.windows_landed + post_landed), 1),
        "replayed_batches": sum(replays),
        "backpressure_sheds": sum(sheds),
    }
    if "precrash_wall_s" in events and events["precrash_wall_s"] > 0:
        # steady-state rate before the kill (the restart's dead time
        # — crash detect + reopen — dilutes the overall average)
        out["storm"]["sustained_pre_crash_per_s"] = round(
            events["landed_before_crash"]
            / events["precrash_wall_s"], 1)
    t_end = events.pop("restarted_at", None)
    if t_end is not None and w2 is not wtr:
        post_wall = storm_wall - (t_end - t_storm0)
        if post_wall > 0:
            out["storm"]["sustained_post_restart_per_s"] = round(
                w2.mutations_landed / post_wall, 1)
    out["events_s"] = {k: round(v, 3) if isinstance(v, float) else v
                       for k, v in events.items()}
    out["writer_errors"] = [e for e in werrs if e]
    out["read_p99_over_baseline"] = round(
        (storm_p99 or 0.0) / (base_p99 or 1e-3), 2)

    # -- convergence: live state vs cold rebuild vs fresh reopen ------
    got = _index_state(h2, INDEX)
    cold = Holder()
    capi = API(cold)
    capi.apply_schema(schema)
    for s in range(n_shards):
        cols = [s * W + k for k in range(64)]
        capi.import_bits(INDEX, "f",
                         [1 + (k % 3) for k in range(64)], cols)
        capi.import_values(INDEX, "v", cols,
                           [(c % 997) for c in cols])
    for j in journals:
        for kind, rows, cols, vals in j:
            if kind == "v":
                capi.import_values(INDEX, "v", cols, vals)
            else:
                capi.import_bits(INDEX, "f", rows, cols)
    out["bit_exact_vs_cold_rebuild"] = got == _index_state(cold, INDEX)
    h2.close()
    h3 = Holder(path=datadir)
    h3.load_schema()
    out["reopen_bit_exact"] = _index_state(h3, INDEX) == got
    h3.close()
    out["acked_record_loss"] = 0 if (
        out["bit_exact_vs_cold_rebuild"]
        and out["reopen_bit_exact"]) else None
    faults.clear("wal-torn")
    shutil.rmtree(datadir, ignore_errors=True)
    log(f"write-storm: {out['storm']['mutations_per_s']}/s acked "
        f"overall, "
        f"{out['storm'].get('sustained_pre_crash_per_s')}/s "
        f"pre-crash ({acked} mutations, "
        f"{out['storm']['windows_landed']} windows, "
        f"{sum(replays)} replayed batches after kill, "
        f"{post_landed} windows post-restart), read p99 "
        f"{storm_p99}ms = {out['read_p99_over_baseline']}x baseline, "
        f"bit-exact={out['bit_exact_vs_cold_rebuild']} "
        f"reopen={out['reopen_bit_exact']}")
    return out


def write_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --write-smoke): a short
    sustained-write burst through the streaming write plane with one
    injected kill-mid-window (wal-torn) + restart + replay, proving
    the ISSUE 7 acceptance bars cheaply — CORRECTNESS GATES ONLY
    (zero acked-record loss, bit-exact convergence vs a cold rebuild
    and vs a fresh reopen, replay actually exercised, zero read
    failures); the read-latency ratio is reported but never gated on
    a small box (scheduler noise swamps it).
    """
    out = write_storm_gauntlet(
        n_readers=int(os.environ.get("PILOSA_TPU_WRITE_READERS", "8")),
        n_writers=int(os.environ.get("PILOSA_TPU_WRITE_WRITERS", "2")),
        post_crash_s=float(os.environ.get(
            "PILOSA_TPU_WRITE_DURATION_S", "2")),
        crash_after_windows=2,
        rate_target=int(os.environ.get(
            "PILOSA_TPU_WRITE_RATE", "50000")))
    failures: list[str] = []
    if out.get("driver_error"):
        failures.append("crash driver failed: " + out["driver_error"])
    if out.get("writer_errors"):
        failures.append("writer errors: "
                        + "; ".join(out["writer_errors"]))
    storm = out.get("storm", {})
    if not out.get("bit_exact_vs_cold_rebuild"):
        failures.append("restarted state diverged from the cold "
                        "rebuild (acked-record loss or replay "
                        "double-apply)")
    if not out.get("reopen_bit_exact"):
        failures.append("fresh reopen from disk diverged (acked "
                        "writes not durable)")
    if storm.get("acked_mutations", 0) <= 0:
        failures.append("zero mutations acked — the plane never "
                        "landed a window")
    if out.get("events_s", {}).get("windows_before_crash", 0) < 1:
        failures.append("kill struck before any window landed — "
                        "nothing acked was ever at risk")
    if storm.get("windows_failed", 0) < 1:
        failures.append("no window failed — the kill never happened")
    if storm.get("replayed_batches", 0) < 1:
        failures.append("no batch replayed — recovery untested")
    if storm.get("windows_landed_post_restart", 0) < 1:
        failures.append("restarted plane never landed a window — "
                        "recovery unproductive")
    if storm.get("read_failed", 1):
        failures.append(f"{storm.get('read_failed')} reads failed "
                        "during the kill/restart")
    out["failures"] = failures
    print(json.dumps({"metric": "write_storm_smoke", **out}))
    for msg in failures:
        log("write-storm smoke: " + msg)
    return 1 if failures else 0
