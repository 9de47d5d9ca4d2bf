"""North-star headline queries through the real engine: wall p50s at
full scale and 1 shard (dispatch-floor subtraction), and the one-pass
GroupBy arm A/B."""

from __future__ import annotations

import statistics
import time

from bench.common import _preview, log


def run_queries(h, reps: int, label: str):
    """Time the two north-star queries through Executor.execute.
    Returns (per-query wall times, windowed roofline attribution) —
    the headline cells emit achieved-GB/s + fraction-of-peak per op
    family (ISSUE 10; ROADMAP item 3's acceptance as live data)."""
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.obs import roofline

    ex = Executor(h)
    queries = {
        "count_intersect": "Count(Intersect(Row(a=1), Row(b=1)))",
        "topn": "TopN(t, n=10)",
        # filtered TopN: exact full candidate scan (cache none) vs
        # the ranked-cache-bounded scan (VERDICT r03 item 5) — same
        # data, results asserted equal below
        "topn_filtered": "TopN(t, Row(a=1), n=10)",
        "topn_ranked_filtered": "TopN(tr, Row(a=1), n=10)",
        # the reference's own 1B-row gauntlet query shape
        # (qa/scripts/perf/able/ableTest.sh:63)
        "able_groupby": "GroupBy(Rows(edu), Rows(gen), Rows(dom), "
                        "aggregate=Sum(field=age))",
        # combo-count sweep around the 60-combo gauntlet shape: the
        # one-pass group-code path must hold roughly FLAT wall time
        # from 10 to 240 combos (its traffic is O(S*W), combo-free),
        # where the per-combo paths scale linearly in C
        "groupby_c10": "GroupBy(Rows(gen), Rows(dom), "
                       "aggregate=Sum(field=age))",
        "groupby_c240": "GroupBy(Rows(edu), Rows(gen), Rows(dom), "
                        "Rows(reg), aggregate=Sum(field=age))",
    }
    # warmup: compiles the stacked programs + uploads the tile stacks
    warm = {}
    for name, q in queries.items():
        t0 = time.perf_counter()
        res = ex.execute("bench", q)
        warm[name] = res
        log(f"[{label}] warm {name}: {time.perf_counter() - t0:.2f}s "
            f"(compile+upload) result={_preview(res)}")
    # exactness: the ranked-cache-bounded filtered TopN must equal
    # the full scan (same underlying rows; covering cache)
    a = [(p.id, p.count) for p in warm["topn_filtered"][0]]
    b = [(p.id, p.count) for p in warm["topn_ranked_filtered"][0]]
    assert a == b, f"ranked TopN != exact TopN: {a} vs {b}"
    # roofline window over the MEASURED reps only (the warm pass's
    # compile dispatches never note, but its stack uploads ran there)
    roofline.ensure_peak()  # blocking probe: one-time, pre-timing
    snap0 = roofline.snapshot()
    times: dict[str, list[float]] = {k: [] for k in queries}
    for _ in range(reps):
        for name, q in queries.items():
            t0 = time.perf_counter()
            ex.execute("bench", q)
            times[name].append(time.perf_counter() - t0)
    rl = roofline.window(snap0, roofline.snapshot())
    for name, ts in times.items():
        log(f"[{label}] {name}: p50={statistics.median(ts)*1e3:.2f}ms "
            f"min={min(ts)*1e3:.2f}ms max={max(ts)*1e3:.2f}ms")
    for op, ent in rl.get("ops", {}).items():
        log(f"[{label}] roofline {op}: {ent['gbps']} GB/s"
            + (f" ({ent['fraction']:.1%} of "
               f"{rl['peak_gbps']} GB/s peak)"
               if "fraction" in ent else ""))
    return times, rl


def groupby_fused_ab(h, reps: int, on_tpu: bool) -> dict:
    """Fused-vs-onehot(-vs-XLA) one-pass GroupBy kernel A/B over the
    combo sweep (C in {10, 60, 240}) — ISSUE 11 bench satellite.

    Every arm runs the SAME queries through the real engine with the
    one-pass arm forced (PILOSA_TPU_GROUPBY_ONEPASS_ARM) and records
    wall p50 plus the per-cell roofline window (achieved GB/s +
    fraction-of-peak for op=groupby, derived from each arm's own
    single-pass traffic model).  On the 2-core CPU box the kernels
    only interpret, so the sweep shrinks to a 2-shard subset and the
    HARD GATE IS CORRECTNESS ONLY: all arms bit-exact (latency and
    roofline are recorded, never asserted).  On TPU the sweep runs at
    full scale and the fused arm's fraction is the ROADMAP item 2
    acceptance cell."""
    import os

    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.models.view import VIEW_STANDARD
    from pilosa_tpu.obs import roofline

    queries = {
        "c10": "GroupBy(Rows(gen), Rows(dom), "
               "aggregate=Sum(field=age))",
        "c60": "GroupBy(Rows(edu), Rows(gen), Rows(dom), "
               "aggregate=Sum(field=age))",
        "c240": "GroupBy(Rows(edu), Rows(gen), Rows(dom), Rows(reg), "
                "aggregate=Sum(field=age))",
    }
    idx = h.index("bench")
    all_shards = sorted(idx.field("gen").views[VIEW_STANDARD].shards)
    shards = all_shards if on_tpu else all_shards[:2]
    arms = ("fused", "onehot") if on_tpu else ("fused", "onehot",
                                               "xla")
    roofline.ensure_peak()
    as_t = lambda res: [(tuple(g["row_id"] for g in r.group), r.count,
                         r.agg, r.agg_count) for r in res]
    out = {"shards": len(shards), "reps": reps,
           "correctness_only": not on_tpu, "arms": {}}
    oracle: dict[str, list] = {}
    prev = os.environ.get("PILOSA_TPU_GROUPBY_ONEPASS_ARM")
    try:
        for arm in arms:
            os.environ["PILOSA_TPU_GROUPBY_ONEPASS_ARM"] = arm
            ex = Executor(h)
            cells = {}
            for name, q in queries.items():
                res = ex.execute("bench", q, shards)  # compile+warm
                tup = as_t(res[0])
                if name not in oracle:
                    oracle[name] = tup
                # the hard gate: every arm bit-exact vs the first
                assert tup == oracle[name], \
                    f"groupby A/B mismatch: arm={arm} cell={name}"
                snap0 = roofline.snapshot()
                ts = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    ex.execute("bench", q, shards)
                    ts.append(time.perf_counter() - t0)
                rl = roofline.window(snap0, roofline.snapshot())
                cell = {"wall_p50_ms":
                        round(statistics.median(ts) * 1e3, 3)}
                gb = rl.get("ops", {}).get("groupby")
                if gb is not None:
                    cell["roofline"] = gb
                cells[name] = cell
                log(f"[gb-ab {arm}] {name}: "
                    f"p50={cell['wall_p50_ms']}ms"
                    + (f" {gb['gbps']} GB/s"
                       + (f" ({gb['fraction']:.1%} of peak)"
                          if 'fraction' in gb else "")
                       if gb else ""))
            out["arms"][arm] = cells
    finally:
        if prev is None:
            os.environ.pop("PILOSA_TPU_GROUPBY_ONEPASS_ARM", None)
        else:
            os.environ["PILOSA_TPU_GROUPBY_ONEPASS_ARM"] = prev
    return out
