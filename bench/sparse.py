"""Sparse-format gauntlets (ISSUE 16): container-adaptive paged
device layout A/B — packed/run pages vs the dense-only arm — plus
the check.sh sparse smoke.

bench/common.py's build_index draws ~0.5-dense random words, which is
exactly the regime the adaptive format refuses to touch (mid-density
pages stay dense by the 0.5x entry rule).  The skewed gauntlet here
builds its own Zipfian index: the BIGGEST row is 1% dense and the
tail decays ~1/r — the cardinality skew the format exists for.

Gates: bit-exactness across arms is HARD (any drift fails the run);
the byte and latency ratios are RECORDED, never asserted — CPU wall
times are correctness-scale, the HBM-bytes claim needs a TPU window.
"""

from __future__ import annotations

import json
import os
import statistics
import time

from bench.common import log


def build_sparse_index(n_shards: int, n_rows: int, width: int = None,
                       seed: int = 17, max_density: float = 0.001):
    """A skewed index through the real bulk-import path: row r of
    field ``seg`` carries ~max_density/(r+1) of the column space
    (Zipf s=1), so NO row is denser than 0.1% and the tail is orders
    sparser — every page block lands well inside packed territory.
    cache_type none on ``seg`` forces TopN through the real stacked
    scan (a ranked-cache field would serve TopN from the host rank
    cache in BOTH arms, measuring nothing — bench/common.py's
    build_index makes the same call)."""
    import numpy as np

    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.models.schema import CACHE_TYPE_NONE, FieldOptions
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    w = width or SHARD_WIDTH
    h = Holder(width=width) if width else Holder()
    idx = h.create_index("sparse", track_existence=False)
    f = idx.create_field("seg", FieldOptions(cache_type=CACHE_TYPE_NONE))
    rng = np.random.default_rng(seed)
    space = n_shards * w
    rows, cols, bits = [], [], 0
    for r in range(n_rows):
        n = max(int(space * max_density / (r + 1)), 8)
        c = rng.choice(space, size=n, replace=False)
        rows.append(np.full(c.size, r, dtype=np.int64))
        cols.append(c)
        bits += n
    f.import_bits(np.concatenate(rows), np.concatenate(cols))
    return h, bits


_SPARSE_QUERIES = [
    "Count(Row(seg=0))",
    "Count(Row(seg=5))",
    "Count(Union(Row(seg=0), Row(seg=1)))",
    "Count(Intersect(Row(seg=0), Row(seg=2)))",
    "Count(Difference(Row(seg=1), Row(seg=3)))",
    "Row(seg=2)",
    "TopN(seg, n=8)",
]


def _battery(ex) -> list[str]:
    return [repr(ex.execute("sparse", q)) for q in _SPARSE_QUERIES]


def _timed_battery(ex, reps: int) -> dict:
    """Per-family wall p50s over `reps` rounds (pages already warm)."""
    fams: dict[str, list[float]] = {"count": [], "topn": []}
    for _ in range(reps):
        for q in _SPARSE_QUERIES:
            fam = ("topn" if q.startswith("TopN")
                   else "count" if q.startswith("Count") else None)
            t0 = time.perf_counter()
            ex.execute("sparse", q)
            dt = time.perf_counter() - t0
            if fam:
                fams[fam].append(dt)
    return {f"{k}_p50_ms": round(statistics.median(v) * 1e3, 3)
            for k, v in fams.items() if v}


def sparse_format_ab_gauntlet(n_shards: int = 16, n_rows: int = 16,
                              reps: int = 15) -> dict:
    """Skewed-gauntlet A/B: same Zipfian holder served with the
    container-adaptive format on (packed/run pages) vs off (the
    dense-only seed layout).  Bit-exactness across arms is asserted
    on every query; resident ledger bytes and Count/TopN wall p50
    ratios are recorded (acceptance geometry: working set >= 4x per
    ledger byte and >= 3x p50 on the sparse arm — recorded, never
    asserted)."""
    import gc

    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.obs import metrics

    h, bits = build_sparse_index(n_shards, n_rows)
    out: dict = {"shards": n_shards, "rows": n_rows, "set_bits": bits}
    prev = os.environ.get("PILOSA_TPU_SPARSE_FORMAT")
    baseline = None
    try:
        for arm, flag in (("dense", "0"), ("sparse", "1")):
            os.environ["PILOSA_TPU_SPARSE_FORMAT"] = flag
            packed0 = metrics.STACK_PAGES.total(
                event="build", encoding="packed")
            ex = Executor(h)
            got = _battery(ex)  # warm pass builds every page
            if baseline is None:
                baseline = got
            else:
                assert got == baseline, \
                    "sparse-format arm drifted from the dense arm"
            cell = _timed_battery(ex, reps)
            cell["resident_ledger_bytes"] = int(ex.stacked.cache.nbytes)
            cell["packed_pages_built"] = round(metrics.STACK_PAGES.total(
                event="build", encoding="packed") - packed0)
            out[arm] = cell
            log(f"sparse-ab {arm}: ledger="
                f"{cell['resident_ledger_bytes']}B "
                f"count_p50={cell.get('count_p50_ms')}ms "
                f"topn_p50={cell.get('topn_p50_ms')}ms")
            del ex
            gc.collect()
        d, s = out["dense"], out["sparse"]
        out["working_set_per_ledger_byte_ratio"] = round(
            d["resident_ledger_bytes"]
            / max(s["resident_ledger_bytes"], 1), 2)
        out["count_p50_speedup"] = round(
            d["count_p50_ms"] / max(s["count_p50_ms"], 1e-3), 2)
        out["topn_p50_speedup"] = round(
            d["topn_p50_ms"] / max(s["topn_p50_ms"], 1e-3), 2)
        out["bit_exact"] = True
    finally:
        if prev is None:
            os.environ.pop("PILOSA_TPU_SPARSE_FORMAT", None)
        else:
            os.environ["PILOSA_TPU_SPARSE_FORMAT"] = prev
    return out


def sparse_smoke() -> int:
    """check.sh tier-1 smoke (bench.py --sparse-smoke): prove the
    container-adaptive format's correctness bar cheaply —

    - the Zipfian battery is BIT-EXACT between the sparse arm and the
      PILOSA_TPU_SPARSE_FORMAT=0 dense arm (kill-switch A/B);
    - the sparse arm actually rides packed pages
      (pilosa_stack_pages_total{event=build,encoding=packed} moves);
    - a write landing on a packed page re-encodes (rebuild path,
      pilosa_page_encode_total moves) and the count stays exact vs a
      fresh dense engine over the mutated holder;
    - compression/latency ratios are recorded, never gated here.
    """
    import gc

    import numpy as np

    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.obs import metrics

    width = 1 << 15  # small shards keep the smoke in seconds
    h, bits = build_sparse_index(2, 8, width=width, max_density=0.005)
    failures: list[str] = []
    prev = os.environ.get("PILOSA_TPU_SPARSE_FORMAT")
    try:
        os.environ["PILOSA_TPU_SPARSE_FORMAT"] = "0"
        want = _battery(Executor(h))
        gc.collect()
        os.environ["PILOSA_TPU_SPARSE_FORMAT"] = "1"
        packed0 = metrics.STACK_PAGES.total(
            event="build", encoding="packed")
        ex = Executor(h)
        got = _battery(ex)
        if got != want:
            failures.append("sparse arm drifted from the dense arm")
        if _battery(ex) != want:  # repeat serves the encoded pages
            failures.append("cached encoded pages drifted on re-read")
        packed_built = metrics.STACK_PAGES.total(
            event="build", encoding="packed") - packed0
        if not packed_built > 0:
            failures.append("no packed pages were built on the "
                            "Zipfian battery")
        sparse_bytes = int(ex.stacked.cache.nbytes)
        # write onto a packed page: rebuild + re-encode, still exact
        enc0 = metrics.PAGE_ENCODE.total()
        before = ex.execute("sparse", "Count(Row(seg=3))")[0]
        rng = np.random.default_rng(5)
        cols = rng.choice(2 * width, size=32, replace=False)
        h.index("sparse").field("seg").import_bits(
            np.full(cols.size, 3, np.int64), cols)
        got_w = ex.execute("sparse", "Count(Row(seg=3))")[0]
        os.environ["PILOSA_TPU_SPARSE_FORMAT"] = "0"
        want_w = Executor(h).execute("sparse", "Count(Row(seg=3))")[0]
        if got_w != want_w or got_w < before:
            failures.append(
                f"write-through drift: sparse={got_w} dense={want_w}")
        if not metrics.PAGE_ENCODE.total() > enc0:
            failures.append("write onto an encoded page did not "
                            "re-encode")
        exd = Executor(h)
        _battery(exd)  # populate the dense arm's ledger for the ratio
        dense_bytes = int(exd.stacked.cache.nbytes)
    finally:
        if prev is None:
            os.environ.pop("PILOSA_TPU_SPARSE_FORMAT", None)
        else:
            os.environ["PILOSA_TPU_SPARSE_FORMAT"] = prev
    out = {
        "metric": "sparse_format_smoke",
        "set_bits": bits,
        "packed_pages_built": round(packed_built),
        "resident_ledger_bytes": {"sparse": sparse_bytes,
                                  "dense": dense_bytes},
        # recorded, never asserted: CPU-scale compression evidence
        "working_set_per_ledger_byte_ratio": round(
            dense_bytes / max(sparse_bytes, 1), 2),
        "failures": failures,
    }
    print(json.dumps(out))
    for msg in failures:
        log("sparse smoke: " + msg)
    return 1 if failures else 0
