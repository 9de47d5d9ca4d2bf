"""HBM residency gauntlets: paged-vs-whole eviction A/B under a
clamped device budget, and the check.sh memory-pressure smoke."""

from __future__ import annotations

import json
import os
import time

from bench.common import _MEM_QUERIES, build_index, log


def memory_pressure_gauntlet(h, ratios=(0.5, 1.0, 2.0),
                             reps: int = 3) -> dict:
    """HBM residency A/B: run the query suite with the device budget
    clamped so the working set is 0.5x / 1x / 2x the budget, paged
    stack entries (memory/pages.py) vs whole-stack entries.  Reports
    hit rate, restacked bytes/query (the direct cost of eviction
    granularity — at 2x overcommit paged eviction must beat
    whole-stack on this) and read p50/p99, asserting every result
    stays bit-exact vs the unbounded run (paging correctness)."""
    import gc

    from pilosa_tpu import memory
    from pilosa_tpu.executor.executor import Executor

    out: dict = {}
    prev_paged = os.environ.get("PILOSA_TPU_MEMORY_PAGED")
    prev_page_bytes = os.environ.get("PILOSA_TPU_MEMORY_PAGE_BYTES")
    try:
        # page ~ one shard-row lane group well below the smallest
        # stack so the A/B measures granularity, not page quantization
        os.environ["PILOSA_TPU_MEMORY_PAGE_BYTES"] = str(512 << 10)
        os.environ["PILOSA_TPU_MEMORY_PAGED"] = "1"
        memory.configure(budget_bytes=1 << 40)  # unbounded baseline
        ex0 = Executor(h)
        baseline = [repr(ex0.execute("bench", q)) for q in _MEM_QUERIES]
        ws = int(ex0.stacked.cache.nbytes)
        out["working_set_bytes"] = ws
        del ex0
        gc.collect()
        for ratio in ratios:
            budget = max(int(ws / ratio), 1 << 20)
            cell_key = f"ws_{ratio:g}x_budget"
            for paged in (True, False):
                os.environ["PILOSA_TPU_MEMORY_PAGED"] = \
                    "1" if paged else "0"
                memory.configure(budget_bytes=budget)
                ex = Executor(h)
                cache = ex.stacked.cache
                for q, want in zip(_MEM_QUERIES, baseline):  # warm
                    got = repr(ex.execute("bench", q))
                    assert got == want, \
                        f"budget-clamped result drift: {q}"
                p0, r0 = cache.patched_bytes, cache.rebuilt_bytes
                h0, m0 = cache.hits, cache.misses
                lat: list[float] = []
                # skewed serving shape: the small hot stacks run 3x
                # per round, the broad TopN candidate scan once —
                # real traffic is zipf-ish, and this is exactly the
                # pattern where whole-stack eviction loses (a broad
                # scan evicts the hot set wholesale; paged admission
                # streams its tail).  GroupBy stays in the exactness
                # warm pass but out of the pressure loop: on CPU it
                # runs the host-histogram path whose numpy twins are
                # whole entries in BOTH modes — churning them would
                # measure the host path, not eviction granularity.
                hot = [(q, w) for q, w in zip(_MEM_QUERIES, baseline)
                       if "TopN" not in q and "GroupBy" not in q]
                cold = [(q, w) for q, w in zip(_MEM_QUERIES, baseline)
                        if "TopN" in q]
                for _ in range(reps):
                    for q, want in hot * 3 + cold:
                        t0 = time.perf_counter()
                        got = repr(ex.execute("bench", q))
                        lat.append(time.perf_counter() - t0)
                        assert got == want, \
                            f"budget-clamped result drift: {q}"
                lat.sort()
                nq = len(lat)
                restacked = (cache.patched_bytes - p0
                             + cache.rebuilt_bytes - r0)
                accesses = (cache.hits - h0) + (cache.misses - m0)
                cell = {
                    "budget_bytes": budget,
                    "queries": nq,
                    "hit_rate": round(
                        (cache.hits - h0) / max(accesses, 1), 3),
                    "restacked_bytes_per_query": round(restacked / nq),
                    "p50_ms": round(lat[nq // 2] * 1e3, 3),
                    "p99_ms": round(
                        lat[min(nq - 1, int(nq * 0.99))] * 1e3, 3),
                }
                mode = "paged" if paged else "whole"
                out.setdefault(cell_key, {})[mode] = cell
                log(f"mem-pressure {cell_key} {mode}: "
                    f"hit={cell['hit_rate']} "
                    f"restacked/q={cell['restacked_bytes_per_query']}B "
                    f"p50={cell['p50_ms']}ms")
                del ex
                gc.collect()
            ab = out[cell_key]
            ab["restacked_ratio_whole_over_paged"] = round(
                ab["whole"]["restacked_bytes_per_query"]
                / max(ab["paged"]["restacked_bytes_per_query"], 1), 2)
    finally:
        for var, prev in (("PILOSA_TPU_MEMORY_PAGED", prev_paged),
                          ("PILOSA_TPU_MEMORY_PAGE_BYTES",
                           prev_page_bytes)):
            if prev is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prev
        memory.configure(budget_bytes=0)  # back to auto
    return out


def memory_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --memory-smoke): clamp the
    device budget below the working set and prove the residency
    manager's acceptance bar cheaply —

    - every query shape (Count/Row/TopN/GroupBy/Sum) stays BIT-EXACT
      vs the unbounded run across repeated rounds (paging + eviction
      correctness under genuine pressure);
    - the accounted resident bytes never exceed the clamped budget;
    - an injected RESOURCE_EXHAUSTED is absorbed (evict + retry), a
      double injection degrades to the host engine — neither fails
      the query, and the ladder's terminal 'raised' counter stays 0.
    """
    import gc

    from pilosa_tpu import memory
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.memory import pressure
    from pilosa_tpu.obs import metrics

    h, _ = build_index(2, 4)
    failures: list[str] = []
    try:
        memory.configure(budget_bytes=1 << 40)
        ex0 = Executor(h)
        baseline = [repr(ex0.execute("bench", q)) for q in _MEM_QUERIES]
        ws = int(ex0.stacked.cache.nbytes)
        del ex0
        gc.collect()
        budget = max(ws // 2, 1 << 20)
        memory.configure(budget_bytes=budget)
        ex = Executor(h)
        cache = ex.stacked.cache
        for _ in range(3):
            for q, want in zip(_MEM_QUERIES, baseline):
                got = repr(ex.execute("bench", q))
                if got != want:
                    failures.append(f"result drift under budget: {q}")
            if cache.nbytes > budget:
                failures.append(
                    f"cache over budget: {cache.nbytes} > {budget}")
        if memory.ledger().total_bytes > budget:
            failures.append("ledger total exceeded the clamped budget")
        raised0 = metrics.OOM_TOTAL.value(outcome="raised")
        for inject, rung in ((1, "evict+retry"), (2, "host fallback")):
            pressure.inject_oom(inject)
            try:
                got = repr(ex.execute("bench", _MEM_QUERIES[0]))
                if got != baseline[0]:
                    failures.append(f"OOM {rung} result drift")
            except Exception as e:  # the whole point is NO escape
                failures.append(f"injected OOM escaped ({rung}): {e}")
        if metrics.OOM_TOTAL.value(outcome="raised") > raised0:
            failures.append("OOM passed the backstop unabsorbed")
        out = {
            "metric": "memory_pressure_smoke",
            "working_set_bytes": ws,
            "budget_bytes": budget,
            "stack_hits": cache.hits,
            "stack_misses": cache.misses,
            "oom_absorbed": {
                "retry_ok": metrics.OOM_TOTAL.value(outcome="retry_ok"),
                "host_fallback": metrics.OOM_TOTAL.value(
                    outcome="host_fallback"),
            },
            "failures": failures,
        }
        print(json.dumps(out))
    finally:
        memory.configure(budget_bytes=0)  # back to auto
    for msg in failures:
        log("memory-pressure smoke: " + msg)
    return 1 if failures else 0
