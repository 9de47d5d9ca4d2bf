"""Shared bench harness — index builders and storm helpers.

The bench suite is a package (one module per gauntlet family, see
bench/main.py for the map); everything two gauntlets share lives
here.  Entry points stay exactly what they were: ``python bench.py``
and ``python -m bench`` (plus the ``--*-smoke`` flags check.sh
gates on).
"""

from __future__ import annotations

import sys
import time

NORTH_STAR_MS = 10.0
NORTH_STAR_CHIPS = 16


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _disjoint_category_rows(rng, n_rows: int, words: int):
    """Packed rows of a CATEGORICAL field: every column belongs to at
    most one row (what real GROUP BY attributes look like — the able
    gauntlet's edu/gen/dom are single-valued per record).  Built by
    drawing ceil(log2 R) random bit-planes as each column's category
    digit; digits >= n_rows mean "attribute absent" for that column."""
    import numpy as np
    bits = max(n_rows - 1, 0).bit_length()
    planes = rng.integers(0, 1 << 32, size=(max(bits, 1), words),
                          dtype=np.uint32)
    rows = []
    for r in range(n_rows):
        acc = np.full(words, 0xFFFFFFFF, dtype=np.uint32)
        for b in range(bits):
            acc &= planes[b] if (r >> b) & 1 else ~planes[b]
        rows.append(acc)
    return rows


def build_index(n_shards: int, topn_rows: int, seed: int = 7):
    """A real index populated through the bulk import path."""
    import numpy as np
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.models.view import VIEW_STANDARD
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    from pilosa_tpu.models.schema import (
        CACHE_TYPE_NONE,
        FieldOptions,
        FieldType,
    )

    rng = np.random.default_rng(seed)
    h = Holder()  # full 2^20-column shards
    idx = h.create_index("bench", track_existence=False)
    words = SHARD_WIDTH // 32
    cells = 0
    t0 = time.perf_counter()
    # north-star fields + the "able" gauntlet categoricals (qa/
    # scripts/perf/able/ableTest.sh:63: GroupBy over 3 Rows fields
    # with a Sum): edu/gen/dom/reg are DISJOINT categorical rows (one
    # category per column, like the reference's single-valued record
    # attributes — also what qualifies them for the one-pass
    # group-code GroupBy), age is BSI.  reg exists only for the
    # combo-count sweep (2*5*6*4 = 240 combos at the top end).
    # "tr" mirrors "t" with the RANKED cache: filtered TopN on it
    # scans only cache candidates (the reference's TopN strategy,
    # cache.go:130) — measured against the exact full scan on "t"
    categorical = {"edu": 6, "gen": 2, "dom": 5, "reg": 4}
    for fname, rows, cache in (
            ("a", [1], CACHE_TYPE_NONE), ("b", [1], CACHE_TYPE_NONE),
            ("t", list(range(topn_rows)), CACHE_TYPE_NONE),
            ("tr", list(range(topn_rows)), "ranked"),
            ("edu", list(range(6)), CACHE_TYPE_NONE),
            ("gen", list(range(2)), CACHE_TYPE_NONE),
            ("dom", list(range(5)), CACHE_TYPE_NONE),
            ("reg", list(range(4)), CACHE_TYPE_NONE)):
        # cache_type none on the TopN field forces the stacked device
        # scan — an unfiltered TopN on a ranked-cache field would be
        # served by the host rank-cache merge instead, measuring the
        # wrong path (advisor r02)
        f = idx.create_field(fname, FieldOptions(cache_type=cache))
        view = f.view(VIEW_STANDARD, create=True)
        for shard in range(n_shards):
            frag = view.fragment(shard, create=True)
            cat_rows = (_disjoint_category_rows(
                rng, categorical[fname], words)
                if fname in categorical else None)
            for r in rows:
                if fname == "tr":
                    # copy t's words so results compare exactly
                    w = idx.field("t").view(VIEW_STANDARD) \
                        .fragment(shard).row_words(r)
                elif cat_rows is not None:
                    w = cat_rows[r]
                else:
                    w = rng.integers(0, 1 << 32, size=words,
                                     dtype=np.uint32)
                frag.import_row_words(r, w)
                cells += int(np.bitwise_count(
                    np.asarray(w, dtype=np.uint32)).sum())
    # BSI age: random 7-bit magnitudes built directly as plane words
    # (the bulk-restore path; random planes = random values 0..127)
    age = idx.create_field("age", FieldOptions(
        type=FieldType.INT, min=0, max=127))
    aview = age.view(age.bsi_view, create=True)
    for shard in range(n_shards):
        frag = aview.fragment(shard, create=True)
        frag.import_row_words(0, np.full(words, 0xFFFFFFFF,
                                         dtype=np.uint32))  # exists
        cells += SHARD_WIDTH
        for plane in range(7):
            w = rng.integers(0, 1 << 32, size=words, dtype=np.uint32)
            frag.import_row_words(2 + plane, w)
            cells += int(np.bitwise_count(w).sum())
    log(f"index built: {n_shards} shards x {SHARD_WIDTH} cols, "
        f"{cells / 1e9:.2f}e9 cells, {time.perf_counter() - t0:.1f}s host")
    return h, cells


SERVING_QUERIES = [
    "Count(Intersect(Row(a=1), Row(b=1)))",
    "Count(Row(a=1))",
    "Count(Row(b=1))",
    "Count(Union(Row(a=1), Row(b=1)))",
    "TopN(t, n=10)",
    "TopN(t, Row(a=1), n=10)",
    "Row(a=1)",
    "Count(Row(age > 63))",
    "Sum(Row(a=1), field=age)",
    "Count(Xor(Row(a=1), Row(b=1)))",
    "Count(Difference(Row(a=1), Row(b=1)))",
    "Count(Row(age < 32))",
]


def _client_storm(call, queries, n_clients: int,
                  duration_s: float) -> dict:
    """N barrier-synced client threads hammering `call` round-robin
    over `queries` for `duration_s`; returns qps + latency summary."""
    import statistics as stats
    import threading

    lat: list[float] = []
    lock = threading.Lock()
    stop = time.perf_counter() + duration_s
    barrier = threading.Barrier(n_clients)

    def client(ci: int):
        my: list[float] = []
        barrier.wait()
        i = ci
        while time.perf_counter() < stop:
            q = queries[i % len(queries)]
            i += 1
            t0 = time.perf_counter()
            call("bench", q)
            my.append(time.perf_counter() - t0)
        with lock:
            lat.extend(my)

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    lat.sort()
    n = len(lat)
    return {
        "requests": n,
        "qps": round(n / wall, 1) if wall > 0 else 0.0,
        "p50_ms": round(lat[n // 2] * 1e3, 3) if n else None,
        "p99_ms": round(lat[min(n - 1, int(n * 0.99))] * 1e3, 3)
        if n else None,
        "mean_ms": round(stats.fmean(lat) * 1e3, 3) if n else None,
    }


def _index_state(h, index: str) -> dict:
    """Bit-exact fingerprint of one index: block checksums of every
    non-empty fragment (representation-independent)."""
    out = {}
    idx = h.index(index)
    for fname in sorted(idx.fields):
        f = idx.fields[fname]
        for vname in sorted(f.views):
            v = f.views[vname]
            for shard in sorted(v.fragments):
                cs = v.fragments[shard].block_checksums()
                if cs:
                    out[(fname, vname, shard)] = cs
    return out


# the memory-pressure suites run every north-star query shape
# (Count/Row/TopN/GroupBy/Sum) so "bit-exact under a clamped budget"
# covers the whole read surface, not one lucky path
_MEM_QUERIES = [
    "Count(Intersect(Row(a=1), Row(b=1)))",
    "Count(Row(b=1))",
    "TopN(t, n=10)",
    "Sum(Row(a=1), field=age)",
    "GroupBy(Rows(edu), Rows(gen), Rows(dom), "
    "aggregate=Sum(field=age))",
]


def _pct(durs: list[float], q: float) -> float | None:
    if not durs:
        return None
    durs = sorted(durs)
    return round(durs[min(len(durs) - 1, int(len(durs) * q))] * 1e3, 3)



def _preview(res):
    r = res[0]
    if isinstance(r, list):
        return [(p.id, p.count) if hasattr(p, "id")
                else (tuple(g["row_id"] for g in p.group), p.count)
                for p in r[:3]]
    return r
