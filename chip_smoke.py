#!/usr/bin/env python3
"""Drive the HTTP-served query path once on the chip, at upstream's scale.

One process (the only one that touches JAX) starts the server an
operator gets from ``pilosa-tpu server``, loads the "able" index
(SURVEY section 6; ``bench/common.py:build_index``: 35 set rows in 8
fields and a 7-bit BSI ``age``; 954 shards = 1.0e9 columns) from
``--seed``, and asks it over HTTP what a numpy oracle computed from
the same columns.  Every phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}`` only when every phase passed on a
TPU.  No phase's exception is caught to keep going.

    python chip_smoke.py                          the chip, 954 shards
    python chip_smoke.py --chips 4                mesh of 4 vs 1, nothing else
    python chip_smoke.py --rehearse-cpu --shards 2    the CPU rehearsal

The rehearsal is asked for by name, forces the CPU (and, with
``--chips``, that many virtual devices), runs the Pallas kernels in
interpret mode and can never report success.
"""

from __future__ import annotations

import argparse
import base64
import glob
import http.client
import itertools
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ABLE_SHARDS = 954                      # 1.0e9 columns
ROOT = os.path.dirname(os.path.abspath(__file__))
TOPN_ROWS = 8
# categorical field -> (rows, code bits, code shift): one digit per
# column, digits >= rows mean "no value" (build_index's disjoint rows)
CATS = {"edu": (6, 3, 0), "gen": (2, 1, 3), "dom": (5, 3, 4),
        "reg": (4, 2, 7)}
N_CODES = 1 << 9
DEPTH = 7
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# data and oracle: columns first, so the reference never sees a bitmap op
# ---------------------------------------------------------------------------

def make_shard(seed: int, shard: int):
    """One shard's packed rows and its additive oracle partials."""
    import numpy as np

    from pilosa_tpu.models.index import EXISTENCE_FIELD
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    rng = np.random.default_rng([seed, shard])
    words = SHARD_WIDTH // 32

    def pack(bits):
        return np.packbits(bits, bitorder="little").view(np.uint32)

    rows = {f: {1: rng.integers(0, 1 << 32, size=words, dtype=np.uint32)}
            for f in ("a", "b")}
    rows["t"] = {r: rng.integers(0, 1 << 32, size=words, dtype=np.uint32)
                 for r in range(TOPN_ROWS)}
    rows["tr"] = dict(rows["t"])
    code = np.zeros(SHARD_WIDTH, dtype=np.int64)
    valid = np.ones(SHARD_WIDTH, dtype=bool)
    for f, (n_rows, bits, shift) in CATS.items():
        d = rng.integers(0, 1 << bits, size=SHARD_WIDTH, dtype=np.uint8)
        rows[f] = {r: pack(d == r) for r in range(n_rows)}
        code |= d.astype(np.int64) << shift
        valid &= d < n_rows
    age = rng.integers(0, 1 << DEPTH, size=SHARD_WIDTH, dtype=np.uint8)
    planes = [pack((age >> p) & 1) for p in range(DEPTH)]
    every = np.full(words, 0xFFFFFFFF, dtype=np.uint32)
    rows["age"] = {0: every, **{2 + p: w for p, w in enumerate(planes)}}
    rows[EXISTENCE_FIELD] = {0: every}
    raw = {"a": rows["a"][1], "b": rows["b"][1], "t": rows["t"],
           "code": code, "valid": valid, "age": age, "planes": planes}
    return rows, partial(raw), (raw if shard == 0 else None)


def partial(raw) -> dict:
    """Additive oracle partials of one shard, from its columns."""
    import numpy as np
    a, b, age = raw["a"], raw["b"], raw["age"].astype(np.int64)

    def pc(w):
        return int(np.bitwise_count(w).sum())
    a_bits = np.unpackbits(a.view(np.uint8), bitorder="little").astype(bool)
    t = [raw["t"][r] for r in range(TOPN_ROWS)]
    return {
        "a": pc(a), "b": pc(b), "ab": pc(a & b), "aub": pc(a | b),
        "t": np.array([pc(w) for w in t]),
        "ta": np.array([pc(w & a) for w in t]),
        "tub": np.array([pc(w | b) for w in t]),
        # sum of age over t_r & a, from the plane words
        "ta_age": np.array([sum(pc(w & a & pw) << p
                                for p, pw in enumerate(raw["planes"]))
                            for w in t]),
        "vhist": np.bincount(age, minlength=1 << DEPTH),
        "vhist_a": np.bincount(age[a_bits], minlength=1 << DEPTH),
        "ghist": np.bincount(
            (raw["code"][raw["valid"]] << DEPTH) | age[raw["valid"]],
            minlength=N_CODES << DEPTH),
    }


class Oracle:
    def __init__(self):
        self.tot = None
        self.raw0 = None
        self.p0 = None

    def add(self, part, raw0=None):
        if raw0 is not None:
            self.raw0, self.p0 = raw0, part
        self.tot = part if self.tot is None else {
            k: self.tot[k] + v for k, v in part.items()}

    def rewrite_shard0(self):
        """Shard 0's columns changed (the write phase): swap its
        partial for the recomputed one."""
        new = partial(self.raw0)
        self.tot = {k: self.tot[k] - self.p0[k] + new[k] for k in new}
        self.p0 = new

    # -- answers ------------------------------------------------------

    def topn(self, key: str, n: int):
        c = self.tot[key]
        order = sorted(range(len(c)), key=lambda r: (-int(c[r]), r))
        return [(r, int(c[r])) for r in order[:n] if c[r] > 0]

    def valcount(self, hist: str, how: str):
        import numpy as np
        h = self.tot[hist]
        vals = np.arange(len(h))
        if how == "sum":
            return int((h * vals).sum()), int(h.sum())
        v = int(vals[h > 0].min() if how == "min" else vals[h > 0].max())
        return v, int(h[v])

    def range_gt(self, x: int) -> int:
        return int(self.tot["vhist"][x + 1:].sum())

    def groups(self, fields, how: str = "sum") -> dict:
        """{row ids: (count, aggregate)} of a GroupBy over categorical
        fields, zero groups dropped."""
        import numpy as np
        g = self.tot["ghist"].reshape(N_CODES, 1 << DEPTH)
        vals = np.arange(1 << DEPTH)
        codes = np.arange(N_CODES)
        out = {}
        for combo in itertools.product(*(range(CATS[f][0]) for f in fields)):
            sel = np.ones(N_CODES, dtype=bool)
            for f, r in zip(fields, combo):
                _n, bits, shift = CATS[f]
                sel &= ((codes >> shift) & ((1 << bits) - 1)) == r
            h = g[sel].sum(axis=0)
            if not h.sum():
                continue
            agg = (int((h * vals).sum()) if how == "sum" else
                   int(vals[h > 0].min()) if how == "min" else
                   int(vals[h > 0].max()))
            out[combo] = (int(h.sum()), agg)
        return out

    def t_by_a(self) -> dict:
        return {(r, 1): (int(self.tot["ta"][r]), int(self.tot["ta_age"][r]))
                for r in range(TOPN_ROWS) if self.tot["ta"][r]}


# ---------------------------------------------------------------------------
# the queries, each with its oracle answer
# ---------------------------------------------------------------------------

def _pairs(res):
    return [(p["id"], p["count"]) for p in res]


def _valcount(res):
    return (res["value"], res["count"])


def _groups(res):
    return {tuple(g["row_id"] for g in r["group"]): (r["count"], r.get("agg"))
            for r in res}


def query_set(o: Oracle) -> list[dict]:
    """name, PQL (or SQL), how to read the response, what the oracle
    says, and which device program must have served it."""
    able = "GroupBy(Rows(edu), Rows(gen), Rows(dom), aggregate={}(field=age))"
    qs = [
        ("count_intersect", "Count(Intersect(Row(a=1), Row(b=1)))",
         int, o.tot["ab"], None),
        ("count_union", "Count(Union(Row(a=1), Row(b=1)))",
         int, o.tot["aub"], None),
        ("topn", "TopN(t, n=5)", _pairs, o.topn("t", 5), None),
        ("topn_filtered", "TopN(t, Row(a=1), n=5)", _pairs,
         o.topn("ta", 5), None),
        ("sum", "Sum(field=age)", _valcount, o.valcount("vhist", "sum"),
         None),
        ("sum_filtered", "Sum(Row(a=1), field=age)", _valcount,
         o.valcount("vhist_a", "sum"), None),
        ("range_count", "Count(Row(age > 40))", int, o.range_gt(40), None),
        ("min", "Min(field=age)", _valcount, o.valcount("vhist", "min"),
         None),
        ("max", "Max(field=age)", _valcount, o.valcount("vhist", "max"),
         None),
        ("able_groupby", able.format("Sum"), _groups,
         o.groups(("edu", "gen", "dom")), "onepass"),
        ("groupby_c240",
         "GroupBy(Rows(edu), Rows(gen), Rows(dom), Rows(reg), "
         "aggregate=Sum(field=age))", _groups,
         o.groups(("edu", "gen", "dom", "reg")), "onepass"),
        ("groupby_overlapping",
         "GroupBy(Rows(t), Rows(a), aggregate=Sum(field=age))", _groups,
         o.t_by_a(), "percombo"),
        ("groupby_min", able.format("Min"), _groups,
         o.groups(("edu", "gen", "dom"), "min"), "onepass"),
        ("groupby_max", able.format("Max"), _groups,
         o.groups(("edu", "gen", "dom"), "max"), "onepass"),
    ]
    out = [dict(name=n, pql=q, read=rd, want=w, arm=arm)
           for n, q, rd, w, arm in qs]
    out.append(dict(
        name="sql_group_by",
        # flatten(): a set column grouped member by member, which is
        # what pushes the GROUP BY down to the device GroupBy
        sql="SELECT edu, dom, COUNT(*), SUM(age) FROM bench "
            "WITH (flatten(edu), flatten(dom)) GROUP BY edu, dom",
        # a set field reads back as its list of row ids
        read=lambda data: {(r[0][0], r[1][0]): (r[2], r[3]) for r in data},
        want=o.groups(("edu", "dom")), arm=None))
    return out


def concurrent_set(o: Oracle):
    """32 distinct point queries for the barrier phase, and the same
    questions worded differently for the sequential comparison (a new
    result-cache key, so the second asking reaches the device too)."""
    conc, seq = [], []
    t, ta, tub = o.tot["t"], o.tot["ta"], o.tot["tub"]
    for r in range(TOPN_ROWS):
        x = f"Row(t={r})"
        for tmpl, y, want in (
                ("Count(Intersect({}, {}))", "Row(a=1)", ta[r]),
                ("Count(Union({}, {}))", "Row(b=1)", tub[r]),
                ("Count(Xor({}, {}))", "Row(a=1)", t[r] + o.tot["a"]
                 - 2 * ta[r])):
            conc.append((tmpl.format(x, y), int(want)))
            seq.append((tmpl.format(y, x), int(want)))
        conc.append((f"Count({x})", int(t[r])))
        seq.append((f"Count(Union({x}))", int(t[r])))
    return conc, seq


# ---------------------------------------------------------------------------
# HTTP client
# ---------------------------------------------------------------------------

class Metrics:
    """One /metrics.json scrape: {name: {labels: value}}."""

    def __init__(self, scrape: dict):
        self.scrape = scrape

    def total(self, name: str, label: str = "") -> float:
        """Sum of a counter's series whose labels contain `label`."""
        return sum(v for k, v in self.scrape.get(name, {}).items()
                   if label in k)

    def since(self, old: "Metrics", name: str, label: str = "") -> float:
        return self.total(name, label) - old.total(name, label)

    def moved(self, old: "Metrics", name: str) -> dict:
        """The series of a counter that changed, by label."""
        was = old.scrape.get(name, {})
        return {k: v - was.get(k, 0)
                for k, v in self.scrape.get(name, {}).items()
                if v != was.get(k, 0)}


class Http:
    def __init__(self, port: int, timeout: float = 900.0):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
        resp = self.conn.getresponse()
        raw = resp.read()
        require(resp.status == 200,
                f"{method} {path} -> {resp.status}: {raw[:300]!r}")
        return json.loads(raw) if raw else None

    def pql(self, q: str):
        return self.call("POST", "/index/bench/query",
                         {"query": q})["results"][0]

    def sql(self, stmt: str):
        return self.call("POST", "/sql", {"sql": stmt})["data"]

    def metrics(self) -> "Metrics":
        return Metrics(self.call("GET", "/metrics.json"))

    def flight(self, since: float) -> dict:
        """The flight record of the query this client sent at wall
        time `since` (the first one opened after it)."""
        recs = self.call("GET", "/debug/queries?limit=32")["queries"]
        mine = [r for r in recs if r["start"] >= since]
        return min(mine, key=lambda r: r["start"]) if mine else {}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class Compiles:
    """Counts what JAX compiled and what its persistent cache served."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = self.hits = 0
        self.secs = 0.0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._event)

    def _dur(self, event, secs, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1
            self.secs += secs

    def _event(self, event, **_kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def snap(self):
        return (self.n, self.hits, self.secs)


def phase_device(args):
    import jax

    from pilosa_tpu import compile_cache, memory
    from pilosa_tpu.memory.ledger import _FALLBACK_BUDGET
    cache_dir = compile_cache.place()
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: no TPU (jax.devices() = {devs}); a CPU "
              "rehearsal is asked for with --rehearse-cpu",
              file=sys.stderr)
        raise SystemExit(2)
    require(len(devs) >= args.chips,
            f"--chips {args.chips} but JAX sees {len(devs)} devices")
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    budget = memory.ledger().budget()
    derived = bool(limit) and budget != _FALLBACK_BUDGET and budget < limit
    require(derived or dev.platform != "tpu",
            f"ledger budget {budget} is not derived from bytes_limit {limit}")
    emit(phase="device", ok=True, **device, bytes_limit=limit,
         ledger_budget=budget, compile_cache=cache_dir,
         rehearsal=args.rehearse_cpu)
    return device


def phase_native():
    """Build the native libraries from native/*.cc, never from a
    stale .so that the disk happened to carry."""
    from pilosa_tpu.storage import native_ingest
    shutil.rmtree(os.path.join(ROOT, "native", "build"),
                  ignore_errors=True)
    gxx = shutil.which("g++")
    if gxx is None:
        emit(phase="native", ok=True, gxx=None, libingest_tpu=False,
             note="no g++ on this machine: bulk paths run on numpy")
        return
    t0 = time.perf_counter()
    subprocess.run(["sh", os.path.join(ROOT, "native", "build.sh")],
                   check=True, capture_output=True)
    require(native_ingest.available(), "libingest_tpu built but not loaded")
    emit(phase="native", ok=True, gxx=gxx, libingest_tpu=True,
         build_s=round(time.perf_counter() - t0, 2))


def phase_start(args):
    from pilosa_tpu import config as cfgmod
    from pilosa_tpu.cli.main import build_server
    cfg = cfgmod.load(None, overrides={
        "bind": "127.0.0.1", "port": 0,
        "cluster_mesh_devices": args.chips if args.chips > 1 else None})
    srv = build_server(cfg).start()
    http_ = Http(srv.port)
    schema = {"indexes": [{"name": "bench", "fields": (
        [{"name": f, "options": {"type": "set", "cache_type": "none"}}
         for f in ("a", "b", "t", *CATS)]
        + [{"name": "tr", "options": {"type": "set",
                                      "cache_type": "ranked"}},
           {"name": "age", "options": {"type": "int", "min": 0,
                                       "max": (1 << DEPTH) - 1}}])}]}
    http_.call("POST", "/schema", schema)
    got = http_.call("GET", "/schema")["indexes"][0]
    require(len(got["fields"]) == 9, f"schema came back as {got}")
    serving = srv.api.executor.serving
    require(serving is not None and serving.batching
            and serving.cache is not None, "serving plane is not on")
    emit(phase="start", ok=True, port=srv.port, fields=len(got["fields"]),
         mesh_devices=cfg.cluster_mesh_devices)
    return srv, cfg, http_


def phase_load(args, srv, http_) -> Oracle:
    """Bulk rows through Fragment.import_row_words (the restore path
    build_index uses); shard 0 of `b` through POST import-roaring."""
    import numpy as np

    from pilosa_tpu.models.view import VIEW_STANDARD
    from pilosa_tpu.storage import roaring
    idx = srv.holder.index("bench")
    oracle = Oracle()
    t0 = time.perf_counter()
    n_bytes = http_bytes = 0
    idx._ensure_existence()     # every column exists, as in a restore
    views = {name: f.view(f.bsi_view if name == "age" else VIEW_STANDARD,
                          create=True) for name, f in idx.fields.items()}
    with ThreadPoolExecutor(max(1, min(12, os.cpu_count() or 1))) as pool:
        for lo in range(0, args.shards, 48):
            chunk = range(lo, min(lo + 48, args.shards))
            for shard, (rows, part, raw0) in zip(chunk, pool.map(
                    lambda s: make_shard(args.seed, s), chunk)):
                oracle.add(part, raw0)
                for f, frows in rows.items():
                    if f == "b" and shard == 0:
                        cols = np.flatnonzero(np.unpackbits(
                            frows[1].view(np.uint8), bitorder="little"))
                        blob = base64.b64encode(
                            roaring.encode(cols)).decode()
                        got = http_.call(
                            "POST",
                            "/index/bench/field/b/import-roaring/0",
                            {"rows": {"1": blob}})
                        require(got["imported"] == len(cols),
                                f"import-roaring said {got}")
                        http_bytes = len(blob)
                        continue
                    frag = views[f].fragment(shard, create=True)
                    for r, w in frows.items():
                        frag.import_row_words(r, w)
                        n_bytes += w.nbytes
    emit(phase="load", ok=True, shards=args.shards,
         columns=args.shards << 20, seconds=round(time.perf_counter() - t0, 1),
         bulk_bytes=n_bytes, http_import_bytes=http_bytes)
    return oracle


_ARM_METRICS = {
    "onepass": "pilosa_groupby_onepass_total",
    "percombo": "pilosa_groupby_kernel_total",
}


def ask(http_, q: dict, comp: Compiles, tag: str = "query"):
    """One query over HTTP, checked against the oracle; prints route,
    arm, compile count and wall seconds."""
    m0 = http_.metrics()
    c0 = comp.snap()
    sent = time.time()
    t0 = time.perf_counter()
    if "sql" in q:
        got = q["read"](http_.sql(q["sql"]))
    else:
        got = q["read"](http_.pql(q["pql"]))
    wall = time.perf_counter() - t0
    c1 = comp.snap()
    require(got == q["want"],
            f"{q['name']}: server said {got!r}, oracle {q['want']!r}")
    rec = http_.flight(sent)
    arm = None
    if q["arm"]:
        m1 = http_.metrics()
        # counted where the plan is built, so on the first asking
        if m1.since(m0, _ARM_METRICS[q["arm"]]):
            arm = q["arm"] + (
                "/fused" if m1.since(m0, "pilosa_groupby_fused_total")
                else "")
        require(arm or tag in ("again", "mesh1"),
                f"{q['name']}: {_ARM_METRICS[q['arm']]} did not move")
    emit(phase=tag, name=q["name"], ok=True, route=rec.get("route"),
         serving_routes=rec.get("serving_routes"), batch=rec.get("batch"),
         arm=arm, compiles=c1[0] - c0[0], cache_hits=c1[1] - c0[1],
         compile_s=round(c1[2] - c0[2], 2), wall_s=round(wall, 4),
         phases_ms=rec.get("phases"))
    return got


def phase_concurrent(http_, port: int, o: Oracle):
    conc, seq = concurrent_set(o)
    m0 = http_.metrics()
    barrier = threading.Barrier(len(conc))
    got = [None] * len(conc)
    errs = []

    def client(i):
        try:
            c = Http(port)
            c.conn.connect()
            barrier.wait(timeout=120)
            got[i] = c.pql(conc[i][0])
        except BaseException as e:      # re-raised below, on the main thread
            errs.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(conc))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=1100)
        require(not t.is_alive(), "a concurrent client did not finish")
    if errs:
        raise errs[0]
    wall = time.perf_counter() - t0
    want = [w for _q, w in conc]
    require(got == want, f"concurrent answers {got} != oracle {want}")
    seq_got = [http_.pql(q) for q, _w in seq]
    require(seq_got == got, "sequential answers differ from concurrent")
    size0, size1 = (m.scrape.get("pilosa_serving_batch_size", {}).get(
        "", {"count": 0, "sum": 0}) for m in (m0, http_.metrics()))
    batches = size1["count"] - size0["count"]
    riders = size1["sum"] - size0["sum"]
    require(batches >= 1 and riders > batches,
            f"the batcher fused nothing: {batches} batches, "
            f"{riders} riders")
    emit(phase="concurrent", ok=True, threads=len(conc),
         batches=batches, riders=riders, wall_s=round(wall, 3))


def phase_write(http_, o: Oracle):
    """An acknowledged write is read back: one bit of `a` and one int
    value at a column of shard 0, then the reads that must see them
    (which also bring the device stacks of `a` and `age` up to date)."""
    import numpy as np
    raw = o.raw0
    a_bits = np.unpackbits(raw["a"].view(np.uint8), bitorder="little")
    col = int(np.flatnonzero(a_bits == 0)[0])
    new_age = (int(raw["age"][col]) + 64) % (1 << DEPTH)
    m0 = http_.metrics()
    for what in (f"Set({col}, a=1)", f"Set({col}, age={new_age})"):
        require(http_.pql(what) is True, f"{what} not acknowledged")
    raw["a"] = raw["a"].copy()
    raw["a"][col >> 5] |= np.uint32(1 << (col & 31))
    raw["age"] = raw["age"].copy()
    raw["age"][col] = new_age
    raw["planes"] = [np.packbits((raw["age"] >> p) & 1, bitorder="little")
                     .view(np.uint32) for p in range(DEPTH)]
    o.rewrite_shard0()
    got_a = http_.pql("Count(Row(a=1))")
    require(got_a == o.tot["a"], f"Count(a) {got_a} != {o.tot['a']}")
    got_s = _valcount(http_.pql("Sum(field=age)"))
    require(got_s == o.valcount("vhist", "sum"),
            f"Sum(age) {got_s} after the write")
    m1 = http_.metrics()
    emit(phase="write_then_read", ok=True, column=col, age=new_age,
         stack_cache=m1.moved(m0, "pilosa_stack_cache_total"),
         maintenance_bytes=m1.moved(
             m0, "pilosa_stack_maintenance_bytes_total"))


def require_on_device(http_, m0: Metrics) -> dict:
    """Nothing since `m0` was served by the host in the device's
    place: no shard loop, no OOM fallback, no captured error (a fused
    dispatch that fails re-executes its riders solo and says so only
    there)."""
    import jax
    m1 = http_.metrics()
    loop = m1.since(m0, "pilosa_stacked_queries_total", 'path="loop"')
    fallback = m1.total("pilosa_device_oom_total", "host_fallback")
    errors = http_.call("GET", "/debug/errors")
    require(loop == 0, f"{loop} query ops fell to the host shard loop")
    require(fallback == 0, f"{fallback} dispatches fell back to the host")
    require(not errors, f"the server captured errors: {errors[:3]}")
    return dict(
        loop_path=loop, host_fallback=fallback,
        oom_caught=m1.total("pilosa_device_oom_total", "caught"),
        resident_bytes=m1.total("pilosa_memory_resident_bytes"),
        peak_bytes_in_use=[(d.memory_stats() or {}).get("peak_bytes_in_use")
                           for d in jax.devices()])


def phase_device_did_the_work(http_, m0: Metrics, device: dict, ir_dir: str):
    on_device = require_on_device(http_, m0)
    m1 = http_.metrics()
    fused = m1.since(m0, "pilosa_groupby_fused_total")
    percombo = m1.since(m0, "pilosa_groupby_kernel_total")
    require(fused > 0 and percombo > 0,
            f"GroupBy kernels idle: fused={fused} percombo={percombo}")
    kernels = "skipped: cpu rehearsal"
    if device["platform"] == "tpu":
        kernels = 0
        for path in glob.glob(os.path.join(ir_dir, "*")):
            with open(path) as f:
                kernels += "tpu_custom_call" in f.read()
        require(kernels >= 4, f"only {kernels} lowered programs carry a "
                "Pallas kernel (tpu_custom_call)")
    emit(phase="device_did_the_work", ok=True, **on_device,
         groupby_fused=fused, groupby_percombo_kernel=percombo,
         programs_with_tpu_custom_call=kernels)


def one_chip(args, device, comp, ir_dir):
    srv, _cfg, http_ = phase_start(args)
    try:
        oracle = phase_load(args, srv, http_)
        m0 = http_.metrics()
        for q in query_set(oracle):
            ask(http_, q, comp)
        # 32 clients at once, then the same 32 one by one: more than
        # the 32 batches after which the batcher forgets a sighting
        phase_concurrent(http_, srv.port, oracle)
        # The same questions again, once a write to `a` and `age` has
        # made every answer but TopN(t)'s stale: they reach the device
        # again and compile nothing.
        phase_write(http_, oracle)
        c0 = comp.n
        for q in query_set(oracle):
            ask(http_, q, comp, tag="again")
        require(comp.n == c0,
                f"the second pass compiled {comp.n - c0} programs")
        emit(phase="again", name="(all)", ok=True, compiles=0)
        # A query the batcher sees twice within 32 batches is promoted
        # into the canonical fused program (executor/ragged.py), which
        # evaluates every promoted query per dispatch and compiles
        # once per change of composition.  Only the two point reads
        # are promoted here: with the GroupBys and TopN in it the
        # canonical program asks for 20 GB at 954 shards (PERF.md).
        for tag in ("promote", "steady"):
            phase_write(http_, oracle)
            c0 = comp.n
            for q in query_set(oracle)[:2]:
                ask(http_, q, comp, tag=tag)
            emit(phase=tag, name="(all)", ok=True, compiles=comp.n - c0)
        require(comp.n == c0,
                f"the steady pass compiled {comp.n - c0} programs")
        phase_device_did_the_work(http_, m0, device, ir_dir)
    finally:
        srv.close()


def four_chips(args, device, comp):
    """Load once; the query set over a serving mesh of `chips`
    devices, then over one, compared with each other and the oracle."""
    import jax

    from pilosa_tpu import memory
    from pilosa_tpu.executor import stacked
    from pilosa_tpu.memory import placement
    calls = {"assemble_permuted": 0}
    inner = stacked._assemble_permuted

    def counted(*a, **kw):
        calls["assemble_permuted"] += 1
        return inner(*a, **kw)
    stacked._assemble_permuted = counted
    srv, cfg, http_ = phase_start(args)
    try:
        oracle = phase_load(args, srv, http_)
        require(placement.mesh_devices() == args.chips, "mesh not configured")
        m0 = http_.metrics()
        answers = {q["name"]: ask(http_, q, comp, tag="mesh")
                   for q in query_set(oracle)}
        phase_concurrent(http_, srv.port, oracle)
        mesh_dispatches = http_.metrics().since(
            m0, "pilosa_serving_dispatch_total", "ragged_mesh")
        dev_bytes = memory.ledger().device_bytes(args.chips)
        page_devs = set()
        for ent in list(srv.api.executor.stacked.cache._entries.values()):
            for page in getattr(ent[1], "pages", None) or ():
                if page is not None:
                    arrs = jax.tree_util.tree_leaves(page)
                    page_devs.update(d.id for a in arrs
                                     for d in a.devices())
        require(all(b > 0 for b in dev_bytes),
                f"ledger device bytes {dev_bytes}")
        require(len(page_devs) == args.chips,
                f"pages sit on devices {sorted(page_devs)}")
        require(mesh_dispatches > 0, "the mesh program never dispatched")
        emit(phase="mesh_layout", ok=True, chips=args.chips,
             ledger_device_bytes=dev_bytes, page_devices=sorted(page_devs),
             mesh_dispatches=mesh_dispatches,
             assemble_permuted_calls=calls["assemble_permuted"],
             placement=placement.snapshot())
        # the same server over one device
        cfg.cluster_mesh_devices = 1
        cfg.apply_placement_settings()
        srv.api.executor.serving.cache.clear()
        srv.api.executor.stacked.cache.clear()
        require(placement.mesh_devices() == 1, "mesh still on")
        for q in query_set(oracle):
            got = ask(http_, q, comp, tag="mesh1")
            require(got == answers[q["name"]],
                    f"{q['name']}: 1 device and {args.chips} disagree")
        emit(phase="mesh_vs_one", ok=True, queries=len(answers),
             **require_on_device(http_, m0))
    finally:
        srv.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--shards", type=int, default=ABLE_SHARDS)
    ap.add_argument("--chips", type=int, default=1,
                    help="serving-mesh width; >1 runs only the mesh "
                         "comparison")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU; never a success")
    ap.add_argument("--reason", default="",
                    help="why --shards is below 954 (printed)")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")
        # what a TPU picks by itself (stacked._onepass_arm,
        # _groupby_kernel_ok): the kernels, here in interpret mode
        os.environ.setdefault("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
        os.environ.setdefault("PILOSA_TPU_GROUPBY_KERNEL", "1")
    import jax
    ir_dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
    jax.config.update("jax_dump_ir_to", ir_dir)
    try:
        device = phase_device(args)
        if args.shards != ABLE_SHARDS:
            emit(phase="reduced", shards=args.shards, of=ABLE_SHARDS,
                 reason=args.reason or (
                     "cpu rehearsal" if args.rehearse_cpu else "not given"))
        phase_native()
        comp = Compiles()
        if args.chips > 1:
            four_chips(args, device, comp)
        else:
            one_chip(args, device, comp, ir_dir)
    finally:
        shutil.rmtree(ir_dir, ignore_errors=True)
    ok = device["platform"] == "tpu"
    emit(ok=ok, device=device)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
