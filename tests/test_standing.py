"""Standing queries (executor/standing.py): write-through maintained
results on the fused serving plane.

The contract under test: a registered Count/TopN/GroupBy/SQL result
is BIT-EXACT against cold execution at every poll, stays on the
O(delta) incremental path for plain set/clear traffic, and declares
exactly one full-re-seed fallback per structural event (TTL quantum
expiry, rollup fold, delta-log overflow).  The kill switch
(PILOSA_TPU_STANDING=0) restores untouched sweep-on-write serving.
"""

import datetime as dt

import numpy as np
import pytest

from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.executor.serving import _MISS
from pilosa_tpu.executor.standing import StandingUnsupported
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.schema import (
    FieldOptions,
    FieldType,
    TimeQuantum,
)


def build(n=300):
    # small shards (test_timeq idiom): the maintenance math is
    # width-independent and the tier-1 budget is not
    h = Holder(width=1 << 12)
    idx = h.create_index("i")
    idx.create_field("a", FieldOptions(type=FieldType.SET,
                                       cache_type="none"))
    idx.create_field("b")
    ex = Executor(h)
    for c in range(n):
        ex.execute("i", f"Set({c}, a={c % 4})")
        ex.execute("i", f"Set({c}, b={c % 6})")
    srv = ex.enable_serving(window_s=0.0, max_batch=8)
    return h, ex, srv


def test_count_incremental_bit_exact():
    h, ex, srv = build()
    q = "Count(Row(a=1))"
    srv.standing.register("i", q)
    cold_ex = Executor(h)
    # columns inside the seeded shard: a write to a virgin shard
    # creates fragments (structural), these stay purely incremental
    for w in ["Set(3001, a=1)", "Set(3002, a=1)", "Clear(1, a=1)",
              "Set(3001, a=1)", "Clear(3002, a=1)"]:
        ex.execute_serving("i", w)
        assert ex.execute_serving("i", q) == cold_ex.execute("i", q)
    (sq,) = srv.standing._by_id.values()
    assert sq.stats["fallback"] == 0
    assert sq.stats["incremental"] >= 4  # idempotent replays may noop


def test_maintained_polls_build_no_stacks():
    """Polls of maintained results ride the write-through cache:
    between registration and quiesce, under interleaved writes, no
    stack is built, rebuilt or patched for them (maintenance is
    host-side)."""
    from pilosa_tpu.obs import metrics
    h, ex, srv = build()
    qs = ["Count(Row(a=1))", "TopN(a, n=3)", "GroupBy(Rows(a), Rows(b))"]
    for q in qs:
        srv.standing.register("i", q)

    def builds():
        return sum(metrics.STACK_CACHE.value(outcome=oc)
                   for oc in ("miss", "rebuild", "page_rebuild", "patch"))
    b0 = builds()
    for w in ["Set(3001, a=1)", "Set(3002, b=2)", "Clear(1, a=1)",
              "Set(7, a=3)", "Clear(3002, b=2)"]:
        ex.execute_serving("i", w)
        got = [ex.execute_serving("i", q) for q in qs]
    assert builds() == b0
    cold_ex = Executor(h)
    assert got == [cold_ex.execute("i", q) for q in qs]


def test_property_interleaved_all_kinds():
    """Seeded property suite: randomized interleaved writes vs
    standing Count/TopN/GroupBy, bit-exact vs cold at every poll."""
    h, ex, srv = build(n=160)
    rng = np.random.default_rng(0xC0FFEE)
    qs = [
        "Count(Row(a=1))",
        "Count(Union(Row(a=0), Row(b=5)))",
        "Count(Not(Row(a=2)))",
        "TopN(a, n=3)",
        "TopN(a, Row(b=1), n=2)",
        "GroupBy(Rows(a), Rows(b))",
    ]
    for q in qs:
        srv.standing.register("i", q)
    cold_ex = Executor(h)
    for step in range(40):
        col = int(rng.integers(0, 400))
        row = int(rng.integers(0, 6))
        fld = "a" if rng.integers(0, 2) else "b"
        op = "Clear" if rng.integers(0, 3) == 0 else "Set"
        rid = row % 4 if fld == "a" else row
        ex.execute_serving("i", f"{op}({col}, {fld}={rid})")
        if step % 4 == 0:
            for q in qs:
                assert (ex.execute_serving("i", q)
                        == cold_ex.execute("i", q)), (step, q)
    # quiesce: every registration still bit-exact, all maintained
    for q in qs:
        assert ex.execute_serving("i", q) == cold_ex.execute("i", q)
    for sq in srv.standing._by_id.values():
        assert sq.stats["incremental"] > 0, sq.describe()
        assert sq.stats["fallback"] == 0, sq.describe()


def test_sql_standing_bit_exact():
    from pilosa_tpu.sql.engine import SQLEngine
    h, ex, srv = build()
    eng = SQLEngine(h, ex)
    s = "SELECT COUNT(*) FROM i WHERE a = 1"
    srv.standing.register_sql(eng, s)
    cold = SQLEngine(h, Executor(h))
    for w in ["INSERT INTO i (_id, a) VALUES (9001, 1)",
              "INSERT INTO i (_id, b) VALUES (9002, 2)",
              "DELETE FROM i WHERE _id = 9001"]:
        eng.query_one(w)
        got, want = eng.query_one(s), cold.query_one(s)
        assert got.rows == want.rows and got.schema == want.schema
    (sq,) = srv.standing._by_id.values()
    assert sq.kind == "sql" and sq.stats["incremental"] > 0


def test_unsupported_shapes_reject_typed():
    h, ex, srv = build()
    h.index("i").create_field("v", FieldOptions(
        type=FieldType.INT, min=0, max=100))
    for bad in ["Count(Row(v > 3))", "Sum(field=v)", "TopK(b, k=4)",
                "GroupBy(Rows(a), aggregate=Count(Distinct(field=b)))",
                "Row(a=1)"]:
        with pytest.raises(StandingUnsupported):
            srv.standing.register("i", bad)
    # unfiltered TopN over a rank-cached field would have to match
    # the cold path's APPROXIMATE cache merge — rejected
    with pytest.raises(StandingUnsupported):
        srv.standing.register("i", "TopN(b, n=3)")
    assert srv.standing.list_info() == []


def test_ttl_expiry_rescopes_standing_cover():
    """Regression (ISSUE 18 satellite): a TTL-expired quantum under
    a standing registration must re-scope the cover — ONE declared
    full re-evaluation — and never serve the retired gens."""
    h = Holder()
    idx = h.create_index("t", track_existence=False)
    f = idx.create_field("ev", FieldOptions(
        type=FieldType.TIME, time_quantum=TimeQuantum("YMD"),
        ttl=86400.0))
    old = dt.datetime(2021, 3, 1, 12)
    recent = dt.datetime.now(dt.timezone.utc).replace(tzinfo=None)
    f.set_bit(1, 10, timestamp=old)
    f.set_bit(1, 11, timestamp=old)
    f.set_bit(1, 20, timestamp=recent)
    ex = Executor(h)
    srv = ex.enable_serving(window_s=0.0, max_batch=8)
    q = ("Count(Row(ev=1, from='2021-01-01T00:00',"
         " to='2030-01-01T00:00'))")
    srv.standing.register("t", q)
    assert ex.execute_serving("t", q) == [3]
    removed = h.remove_expired_views()
    assert any(v.startswith("standard_2021") for v in removed)
    srv.standing.on_write()  # the server maintenance tick's notify
    # only the recent bit survives the expired quantum — maintained
    # and cold agree, through exactly one declared fallback
    assert ex.execute_serving("t", q) == [1]
    assert ex.execute("t", q) == [1]
    (sq,) = srv.standing._by_id.values()
    assert sq.stats["fallback"] == 1


def test_rollup_fold_keeps_standing_bit_exact():
    """A [timeq] rollup fold (fine view OR-folded into its coarser
    parent) is a structural event: the cover re-scopes through one
    fallback and the maintained result stays bit-exact."""
    h = Holder()
    idx = h.create_index("t", track_existence=False)
    f = idx.create_field("ev", FieldOptions(
        type=FieldType.TIME, time_quantum=TimeQuantum("MD")))
    old = dt.datetime(2021, 3, 1, 12)
    for c in range(20):
        f.set_bit(1, c, timestamp=old)
    ex = Executor(h)
    srv = ex.enable_serving(window_s=0.0, max_batch=8)
    q = ("Count(Row(ev=1, from='2021-03-01T00:00',"
         " to='2021-03-02T00:00'))")
    srv.standing.register("t", q)
    assert ex.execute_serving("t", q) == [20]
    folded = f.rollup_views(now=dt.datetime(2022, 1, 1))
    assert folded  # day views folded into month views
    srv.standing.on_write()
    assert ex.execute_serving("t", q) == [20]
    assert ex.execute("t", q) == [20]


def test_delta_log_overflow_falls_back_once():
    """More landed mutations than the fragment delta log holds
    between polls: deltas_since() cannot prove coverage, so the
    registration declares ONE full re-seed — and stays exact."""
    from pilosa_tpu.models import fragment
    h, ex, srv = build()
    q = "Count(Row(a=1))"
    srv.standing.register("i", q)
    (sq,) = srv.standing._by_id.values()
    # land an over-log burst directly (bypassing the serving layer's
    # per-write push, like a bulk import would)
    idx = h.index("i")
    f = idx.field("a")
    for c in range(fragment.DELTA_LOG_MAX + 10):
        f.set_bit(1, 1000 + c)
    srv.standing.on_write("i", {"a"})
    cold_ex = Executor(h)
    assert ex.execute_serving("i", q) == cold_ex.execute("i", q)
    assert sq.stats["fallback"] == 1


def test_kill_switch_disables_plane(monkeypatch):
    h, ex, srv = build()
    q = "Count(Row(a=1))"
    srv.standing.register("i", q)
    monkeypatch.setenv("PILOSA_TPU_STANDING", "0")
    # registration rejects...
    with pytest.raises(StandingUnsupported):
        srv.standing.register("i", "Count(Row(a=2))")
    # ...the push and the pull both no-op...
    srv.standing.on_write("i", {"a"})
    assert srv.standing.catch_up(("i", "x", None)) is _MISS
    # ...and polls stay bit-exact through the normal swept path
    cold_ex = Executor(h)
    ex.execute_serving("i", "Set(7001, a=1)")
    assert ex.execute_serving("i", q) == cold_ex.execute("i", q)
    (sq,) = srv.standing._by_id.values()
    assert sq.stats["incremental"] == 0
    monkeypatch.delenv("PILOSA_TPU_STANDING")
    # re-enabled: the next landed write routes back through
    # maintenance and the registration catches up from its stale
    # snapshot (the disabled-era write arrives in the same diff)
    ex.execute_serving("i", "Set(3005, a=1)")
    assert ex.execute_serving("i", q) == cold_ex.execute("i", q)
    assert sq.stats["incremental"] + sq.stats["fallback"] > 0


def test_standing_entry_survives_sweeps_and_eviction():
    h, ex, srv = build()
    q = "Count(Row(a=1))"
    srv.standing.register("i", q)
    key = ("i", repr(__import__("pilosa_tpu.pql",
                                fromlist=["parse"]).parse(q).calls),
           None)
    assert key in srv.cache
    # a full sweep after a write must NOT evict the maintained entry
    ex.execute("i", "Set(8001, a=1)")  # solo write, no push
    srv.cache.sweep(h)
    assert key in srv.cache
    # stale get misses without dropping it; catch_up then serves
    cold_ex = Executor(h)
    assert ex.execute_serving("i", q) == cold_ex.execute("i", q)
    # reclaim pressure cannot evict it either
    assert srv.cache._reclaim(1 << 30) == 0
    assert key in srv.cache
    # unregister returns the key to normal lifecycle and drops it
    (sq,) = srv.standing._by_id.values()
    assert srv.standing.unregister(sq.sid)
    assert key not in srv.cache
    assert ex.execute_serving("i", q) == cold_ex.execute("i", q)


def test_registration_admission_limits():
    from pilosa_tpu.executor import standing as st
    h, ex, srv = build()
    st.configure(max_registrations=2)
    try:
        srv.standing.register("i", "Count(Row(a=1))")
        srv.standing.register("i", "Count(Row(a=2))")
        with pytest.raises(StandingUnsupported):
            srv.standing.register("i", "Count(Row(a=3))")
        # duplicate registration of a live key rejects too
        st.configure(max_registrations=256)
        with pytest.raises(StandingUnsupported):
            srv.standing.register("i", "Count(Row(a=1))")
    finally:
        st.configure(max_registrations=256)


def test_http_standing_surface():
    import json
    import urllib.request

    from pilosa_tpu.server.http import Server

    h = Holder(width=1 << 12)
    idx = h.create_index("i")
    idx.create_field("a", FieldOptions(type=FieldType.SET,
                                       cache_type="none"))
    ex = Executor(h)
    for c in range(50):
        ex.execute("i", f"Set({c}, a={c % 3})")
    srv_http = Server(h, port=0).start()
    try:
        base = f"http://127.0.0.1:{srv_http.port}"

        def call(method, path, body=None):
            data = (json.dumps(body).encode()
                    if body is not None else None)
            req = urllib.request.Request(base + path, data=data,
                                         method=method)
            with urllib.request.urlopen(req) as r:
                return json.loads(r.read() or b"{}")

        out = call("POST", "/index/i/standing",
                   {"query": "Count(Row(a=1))"})
        assert out["kind"] == "count" and out["id"] == 1
        out = call("POST", "/index/i/standing",
                   {"sql": "SELECT COUNT(*) FROM i"})
        assert out["kind"] == "sql"
        listed = call("GET", "/standing")["standing"]
        assert [e["id"] for e in listed] == [1, 2]
        dbg = call("GET", "/debug/standing")
        assert dbg["enabled"] and len(dbg["standing"]) == 2
        # writes through the HTTP query surface maintain; poll serves
        call("POST", "/index/i/query", {"query": "Set(9001, a=1)"})
        got = call("POST", "/index/i/query",
                   {"query": "Count(Row(a=1))"})
        want = Executor(h).execute("i", "Count(Row(a=1))")
        assert got["results"] == want
        assert call("DELETE", "/standing/1") == {"removed": 1}
        assert [e["id"] for e in call("GET", "/standing")["standing"]
                ] == [2]
        # unsupported shape is a typed 400
        try:
            call("POST", "/index/i/standing",
                 {"query": "Sum(field=a)"})
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
    finally:
        srv_http.close()
