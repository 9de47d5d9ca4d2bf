"""Observability tests — logger, metrics exposition, tracing spans."""

import io
import threading

from pilosa_tpu.obs import (
    Logger,
    MetricsRegistry,
    NopTracer,
    RecordingTracer,
    set_tracer,
    span_into,
    start_span,
)
from pilosa_tpu.obs import logger as lg


def test_logger_levels_and_format():
    buf = io.StringIO()
    log = Logger(buf, level=lg.INFO)
    log.debug("hidden %d", 1)
    log.info("hello %s", "world")
    log.error("boom")
    out = buf.getvalue()
    assert "hidden" not in out
    assert "INFO" in out and "hello world" in out
    assert "ERROR" in out and "boom" in out


def test_logger_prefix():
    buf = io.StringIO()
    log = Logger(buf).with_prefix("executor")
    log.info("x")
    assert "[executor]" in buf.getvalue()


def test_counter_gauge_labels():
    r = MetricsRegistry()
    c = r.counter("q_total", "queries")
    c.inc()
    c.inc(2, index="i0")
    g = r.gauge("open_dbs")
    g.set(5)
    g.add(-1)
    text = r.render_text()
    assert "# TYPE q_total counter" in text
    assert "q_total 1" in text
    assert 'q_total{index="i0"} 2' in text
    assert "open_dbs 4" in text
    assert c.value(index="i0") == 2


def test_histogram_buckets():
    r = MetricsRegistry()
    h = r.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    text = r.render_text()
    assert 'lat_bucket{le="0.01"} 1' in text
    assert 'lat_bucket{le="0.1"} 3' in text
    assert 'lat_bucket{le="1"} 4' in text
    assert 'lat_bucket{le="+Inf"} 5' in text
    assert "lat_count 5" in text
    # bucket boundary: le is inclusive
    h2 = r.histogram("lat2", buckets=(0.01, 0.1, 1.0))
    h2.observe(0.1)
    assert 'lat2_bucket{le="0.1"} 1' in r.render_text()


def test_metrics_registry_same_instance():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")


def test_render_json():
    r = MetricsRegistry()
    r.counter("c").inc(3)
    r.histogram("h").observe(0.2)
    j = r.render_json()
    assert j["c"][""] == 3
    assert j["h"][""]["count"] == 1


def test_tracer_span_tree():
    t = RecordingTracer()
    set_tracer(t)
    try:
        with start_span("query", index="i") as root:
            with start_span("mapReduce"):
                with start_span("shard", shard=0):
                    pass
            with start_span("translate"):
                pass
        assert len(t.roots) == 1
        d = t.roots[0].to_dict()
        assert d["name"] == "query"
        assert d["tags"] == {"index": "i"}
        names = [c["name"] for c in d["children"]]
        assert names == ["mapReduce", "translate"]
        assert d["children"][0]["children"][0]["tags"] == {"shard": 0}
        assert d["duration_us"] >= 0
    finally:
        set_tracer(NopTracer())


def test_tracer_thread_isolation():
    t = RecordingTracer()
    set_tracer(t)
    try:
        def work(i):
            with start_span(f"root{i}"):
                with start_span("child"):
                    pass
        ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        [x.start() for x in ts]
        [x.join() for x in ts]
        assert len(t.roots) == 4
        for r in t.roots:
            assert len(r.children) == 1
    finally:
        set_tracer(NopTracer())


def test_nop_tracer_cheap():
    set_tracer(NopTracer())
    with start_span("x") as s:
        s.set_tag("a", 1)  # no-op, no error


def test_diagnostics_payload_and_version_check():
    from pilosa_tpu.obs.diagnostics import Diagnostics

    sent = []
    d = Diagnostics(version="1.2.3", send=sent.append)
    d.set("node_id", "n0")
    d.flush()
    assert sent and sent[0]["version"] == "1.2.3"
    assert sent[0]["node_id"] == "n0"
    assert sent[0]["num_cpu"] >= 1
    # reporting disabled: start() is a no-op, flush keeps local copy
    d2 = Diagnostics(version="x")
    assert d2.start()._thread is None
    d2.flush()
    assert d2.last_payload is not None
    assert Diagnostics.check_version("1.0.0", "1.2.0") is not None
    assert Diagnostics.check_version("2.0.0", "1.9.9") is None
    assert Diagnostics.check_version("2.0.0", "weird") is None


def test_performance_counters():
    from pilosa_tpu.obs.diagnostics import PerformanceCounters

    pc = PerformanceCounters()
    pc.add("queries", 3)
    pc.add("queries")
    pc.set_gauge("goroutines", 7)
    snap = pc.snapshot()
    assert snap == {"queries": 4, "goroutines": 7}
    assert '"queries": 4' in pc.dump_json()


def test_monitor_capture_and_http_wiring():
    from pilosa_tpu.obs.monitor import Monitor, global_monitor
    from pilosa_tpu.cluster.client import InternalClient, RemoteError
    from pilosa_tpu.server.http import Server
    import pytest as _pytest

    m = Monitor(keep=2)
    for i in range(3):
        try:
            raise ValueError(f"e{i}")
        except ValueError as e:
            m.capture_exception(e, query=f"q{i}")
    ev = m.recent()
    assert len(ev) == 2 and ev[-1]["message"] == "e2"
    assert "ValueError" in ev[-1]["traceback"]

    # a handler crash is captured by the global monitor and surfaced
    # at /debug/errors
    srv = Server().start()
    uri = f"127.0.0.1:{srv.port}"
    srv.add_route("GET", "/boom", lambda req: 1 / 0, admin_only=False)
    cli = InternalClient()
    try:
        before = len(global_monitor.recent())
        with _pytest.raises(RemoteError):
            cli._request(uri, "GET", "/boom")
        events = cli._request(uri, "GET", "/debug/errors")
        assert len(events) > before
        assert events[-1]["type"] == "ZeroDivisionError"
        # diagnostics + perf counters endpoints respond
        d = cli._request(uri, "GET", "/internal/diagnostics")
        assert "version" in d and "num_cpu" in d
        assert isinstance(
            cli._request(uri, "GET", "/internal/perf-counters"), dict)
    finally:
        srv.close()


class TestTesthook:
    """Resource leak auditor (testhook/hook.go, auditor.go analog)."""

    def test_open_close_cycle(self):
        from pilosa_tpu.obs import testhook
        if not testhook.ENABLED:
            import pytest
            pytest.skip("PILOSA_TPU_TESTHOOK disabled")
        obj = object()
        testhook.opened("unit.res", obj, "thing")
        assert "unit.res" in testhook.audit()
        assert testhook.audit()["unit.res"] == ["thing"]
        assert testhook.audit_stacks()["unit.res"]
        testhook.closed("unit.res", obj)
        assert "unit.res" not in testhook.audit()

    def test_rbf_db_tracked(self, tmp_path):
        from pilosa_tpu.obs import testhook
        from pilosa_tpu.storage import rbf
        if not testhook.ENABLED:
            import pytest
            pytest.skip("PILOSA_TPU_TESTHOOK disabled")
        db = rbf.DB(str(tmp_path / "x.rbf"))
        assert any(str(tmp_path) in d
                   for d in testhook.audit().get("rbf.DB", []))
        db.close()
        assert not any(str(tmp_path) in d
                       for d in testhook.audit().get("rbf.DB", []))


def test_histogram_quantiles_render():
    r = MetricsRegistry()
    lat = r.histogram("lat3", "latency", buckets=(0.01, 0.1, 1.0),
                      quantiles=(0.5, 0.99))
    for v in (0.005, 0.02, 0.05, 0.5, 0.9):
        lat.observe(v)
    # p50 falls in the (0.01, 0.1] bucket, interpolated
    q = lat.quantile(0.5)
    assert 0.01 < q <= 0.1
    assert lat.quantile(0.99) <= 1.0
    text = r.render_text()
    assert "lat3_p50 " in text
    assert "lat3_p99 " in text
    assert "# TYPE lat3_p50 gauge" in text


def test_histogram_quantile_empty_is_zero():
    r = MetricsRegistry()
    assert r.histogram("lat4", "x", quantiles=(0.5,)).quantile(0.5) == 0.0


def test_span_into_list_grafts_a_copy_into_every_context():
    """One interval a batch leader runs for several riders: recorded
    once, with what nests inside it, and grafted as its own copy into
    each traced rider's tree; untraced riders (None) are skipped."""
    from pilosa_tpu.obs.tracing import (
        capture_context,
        pop_thread_tracer,
        push_thread_tracer,
    )
    tracers = [RecordingTracer(), RecordingTracer()]
    ctxs = []
    for t in tracers:
        prev = push_thread_tracer(t)
        try:
            ctxs.append(capture_context())
        finally:
            pop_thread_tracer(prev)
    with span_into([ctxs[0], None, ctxs[1]], "execute", batch=3) as sp:
        with start_span("dispatch"):
            pass
    assert sp.name == "execute"
    trees = [t.roots[0] for t in tracers]
    assert trees[0] is not trees[1]
    for tree in trees:
        d = tree.to_dict()
        assert d["name"] == "execute" and d["tags"] == {"batch": 3}
        assert [c["name"] for c in d["children"]] == ["dispatch"]
    # a batch with no traced rider silences the borrowed thread
    outer = RecordingTracer()
    prev = push_thread_tracer(outer)
    try:
        with start_span("root"):
            with span_into([None, None], "execute"):
                with start_span("dispatch"):
                    pass
    finally:
        pop_thread_tracer(prev)
    assert "children" not in outer.roots[0].to_dict()


def test_recording_tracer_joins_a_tree_and_never_roots_one():
    from pilosa_tpu.obs.tracing import (
        pop_thread_tracer,
        push_thread_tracer,
        recording_tracer,
    )
    assert recording_tracer() is None           # the nop default
    t = RecordingTracer()
    prev = push_thread_tracer(t)
    try:
        assert recording_tracer() is t
        assert recording_tracer(nested=True) is None    # nothing open
        with start_span("root"):
            assert recording_tracer(nested=True) is t
    finally:
        pop_thread_tracer(prev)
    assert recording_tracer() is None


def test_ragged_dispatch_counts_the_bytes_of_its_page_leaves():
    """One ragged dispatch moves pilosa_ragged_assembled_bytes_total by
    the bytes of the pages the program was handed — the real pages of
    every operand, once each — and the rider's flight record carries
    the same figure."""
    from pilosa_tpu import memory
    from pilosa_tpu.executor import stacked as stk
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs import flight, metrics

    h = Holder()
    idx = h.create_index("ab", track_existence=False)
    idx.create_field("a")
    idx.create_field("b")
    plain = Executor(h)
    for shard in range(5):
        plain.execute("ab", f"Set({shard * idx.width + shard}, a=1)")
        plain.execute("ab", f"Set({shard * idx.width + 7}, b=2)")
    prev = memory.page_bytes()
    memory.configure(page_bytes=256 << 10)  # 2 lanes of 2**15 words
    try:
        ex = Executor(h)
        ex.enable_serving(window_s=0.0, max_batch=8, cache_bytes=0,
                          admission=False)
        flight.recorder.configure(enabled=True)
        flight.recorder.clear()
        skey = tuple(sorted(idx.available_shards))
        with stk.raw_pages():
            views = [ex.stacked.row_stack(idx, idx.field(f), ("standard",),
                                          row, skey)
                     for f, row in (("a", 1), ("b", 2))]
        want = sum(int(p.nbytes) for pv in views
                   for p in pv.dense_pages())
        # 5 lanes in 3 pages of 2: the padding lane counts, a pow2
        # fourth page does not exist
        assert want == 2 * 3 * (256 << 10)
        b0 = metrics.RAGGED_ASSEMBLED_BYTES.value()
        d0 = metrics.SERVING_DISPATCH.value(kind="ragged")
        (n,) = ex.execute_serving(
            "ab", "Count(Union(Row(a=1), Row(b=2)))")
    finally:
        memory.configure(page_bytes=prev)
    assert n == 10
    assert metrics.SERVING_DISPATCH.value(kind="ragged") == d0 + 1
    assert metrics.RAGGED_ASSEMBLED_BYTES.value() == b0 + want
    rec = flight.recorder.recent(1)[0]
    assert rec["route"] == "fused" and rec["assembled_bytes"] == want
