"""Query flight recorder tests (ISSUE 4): nop-span isolation,
cross-thread trace-context propagation through the serving batcher,
the per-query flight-record ring + Chrome trace export, the /debug
endpoint surface (auth included), and monitor capture with batch
trace ids."""

import json
import threading
import time

import pytest

from pilosa_tpu.api import API, serialize_result
from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import flight, metrics
from pilosa_tpu.obs.tracing import (
    NopTracer,
    RecordingTracer,
    Span,
    capture_context,
    pop_thread_tracer,
    push_thread_tracer,
    span_into,
    start_span,
)


def build_holder() -> Holder:
    h = Holder()
    idx = h.create_index("i", track_existence=True)
    idx.create_field("a")
    idx.create_field("b")
    ex = Executor(h)
    for c in range(200):
        ex.execute("i", f"Set({c}, a={c % 3})")
        ex.execute("i", f"Set({c}, b={c % 5})")
    return h


@pytest.fixture(scope="module")
def holder():
    return build_holder()


# ---------------------------------------------------------------------------
# satellite: nop spans must not share mutable state
# ---------------------------------------------------------------------------

def test_nop_span_not_shared():
    t = NopTracer()
    with t.span("x") as s1:
        s1.children.append(Span("evil"))
        s1.tags["k"] = "v"
        s1.start = -1.0
    with t.span("y") as s2:
        # a fresh nop span every time: nothing leaked from s1
        assert s2 is not s1
        assert s2.children == []
        assert "k" not in s2.tags
        assert s2.start != -1.0
        # duration is frozen: finish/set_tag are inert
        s2.set_tag("a", 1)
        s2.finish()
        assert s2.duration == 0.0
        assert s2.tags == {}


def test_span_copy_is_deep():
    s = Span("root")
    s.set_tag("k", "v")
    c = Span("child")
    c.finish()
    s.children.append(c)
    s.finish()
    cp = s.copy()
    assert cp.to_dict() == s.to_dict()
    cp.children.append(Span("extra"))
    cp.tags["other"] = 1
    assert len(s.children) == 1 and "other" not in s.tags


# ---------------------------------------------------------------------------
# cross-thread trace-context propagation
# ---------------------------------------------------------------------------

def test_capture_context_none_when_untraced():
    assert capture_context() is None  # NopTracer default: zero work


def test_span_into_grafts_across_threads():
    tracer = RecordingTracer()
    prev = push_thread_tracer(tracer)
    try:
        with start_span("root") as root:
            ctx = capture_context()
            assert ctx is not None and ctx.parent is root

            def leader():
                with span_into(ctx, "leader.work", batch=3):
                    with start_span("leader.nested"):
                        pass

            t = threading.Thread(target=leader)
            t.start()
            t.join()
        d = tracer.roots[0].to_dict()
        assert d["name"] == "root"
        names = [c["name"] for c in d["children"]]
        assert "leader.work" in names
        lw = d["children"][names.index("leader.work")]
        assert lw["tags"] == {"batch": 3}
        assert [c["name"] for c in lw["children"]] == ["leader.nested"]
    finally:
        pop_thread_tracer(prev)


def test_span_into_none_silences_borrowed_thread():
    """A traced batch leader serving an UNtraced follower must not
    adopt the follower's inner spans into its own tree."""
    tracer = RecordingTracer()
    prev = push_thread_tracer(tracer)
    try:
        with start_span("root"):
            with span_into(None, "follower.plan"):
                with start_span("follower.inner"):
                    pass
        d = tracer.roots[0].to_dict()
        assert "children" not in d, d
    finally:
        pop_thread_tracer(prev)


def test_span_into_rootless_context_records_root():
    tracer = RecordingTracer()
    prev = push_thread_tracer(tracer)
    try:
        ctx = capture_context()  # no open span: parent is None
    finally:
        pop_thread_tracer(prev)
    with span_into(ctx, "detached"):
        pass
    assert [s.name for s in tracer.roots] == ["detached"]


# ---------------------------------------------------------------------------
# flight records
# ---------------------------------------------------------------------------

def test_flight_record_routes_and_phases(holder):
    ex = Executor(holder)
    ex.enable_serving(window_s=0.0, max_batch=8)
    flight.recorder.configure(enabled=True)
    flight.recorder.clear()
    ex.execute_serving("i", "Count(Row(a=1))")
    ex.execute_serving("i", "Count(Row(a=1))")  # result-cache hit
    recs = flight.recorder.recent(10)
    assert len(recs) >= 2
    hit, first = recs[0], recs[1]
    assert hit["route"] == "cached"
    assert "cache_lookup" in hit["phases"]
    assert first["route"] in ("fused", "direct")
    assert first["trace_id"] != hit["trace_id"]
    assert first["index"] == "i"
    assert first["query"].startswith("Count")
    assert first["duration_ms"] > 0
    if first["route"] == "fused":
        # device phases stamped by the leader path, plus the derived
        # wait (batch minus attributed phases) — which must also reach
        # the phase histogram, not just the record dict
        assert ("compile" in first["phases"]
                or "execute" in first["phases"])
        assert "wait" in first["phases"]
        assert "fingerprint" in first
        flight.flush_metrics()
        assert metrics.PHASE_DURATION.count(phase="wait") > 0


def test_flight_solo_path_records(holder):
    ex = Executor(holder)  # no serving layer at all
    flight.recorder.configure(enabled=True)
    flight.recorder.clear()
    ex.execute("i", "Count(Row(b=2))")
    recs = flight.recorder.recent(5)
    assert recs and recs[0]["route"] == "solo"
    # the stacked engine attributed its dispatch
    assert ("compile" in recs[0]["phases"]
            or "execute" in recs[0]["phases"])


def test_flight_disabled_records_nothing(holder):
    ex = Executor(holder)
    flight.recorder.configure(enabled=False)
    try:
        flight.recorder.clear()
        ex.execute("i", "Count(Row(a=0))")
        assert flight.recorder.recent(5) == []
    finally:
        flight.recorder.configure(enabled=True)


def test_flight_ring_bounded():
    flight.recorder.configure(enabled=True, keep=4)
    try:
        flight.recorder.clear()
        for i in range(10):
            flight.recorder.record({"trace_id": f"t{i}", "start": 0.0,
                                    "duration_ms": 1.0, "phases": {}})
        recs = flight.recorder.recent(100)
        assert len(recs) == 4
        assert recs[0]["trace_id"] == "t9"  # newest first
    finally:
        flight.recorder.configure(keep=512)


def test_chrome_trace_is_valid_trace_event_json(holder):
    ex = Executor(holder)
    ex.enable_serving(window_s=0.0, max_batch=8)
    flight.recorder.configure(enabled=True)
    flight.recorder.clear()
    ex.execute_serving("i", "Count(Intersect(Row(a=1), Row(b=1)))")
    raw = flight.recorder.chrome_trace_json(50)
    doc = json.loads(raw)  # must round-trip as strict JSON
    evs = doc["traceEvents"]
    assert evs, "no trace events exported"
    for ev in evs:
        # Chrome trace_event invariants: complete events ("X") plus
        # the process_name metadata ("M") cluster node lanes emit
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["name"], str) and ev["name"]
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], (int, float))
        assert ev["dur"] > 0
        assert "pid" in ev and "tid" in ev
    assert any(ev.get("cat") == "query" for ev in evs)
    assert doc["displayTimeUnit"] == "ms"


def test_phase_histogram_exemplars(holder):
    ex = Executor(holder)
    flight.recorder.configure(enabled=True)
    ex.execute("i", "Count(Row(a=2))")
    flight.flush_metrics()  # drain this thread's buffered samples
    assert metrics.PHASE_DURATION.count(phase="execute") + \
        metrics.PHASE_DURATION.count(phase="compile") > 0
    ex_val = (metrics.PHASE_DURATION.exemplar(phase="execute")
              or metrics.PHASE_DURATION.exemplar(phase="compile"))
    assert ex_val is not None and ex_val[1].startswith("q")
    # exemplars render ONLY under OpenMetrics: the classic 0.0.4 text
    # parser fails the whole scrape on a mid-line '#'
    assert 'trace_id="q' in metrics.registry.render_text(
        openmetrics=True)
    assert 'trace_id="' not in metrics.registry.render_text()


# ---------------------------------------------------------------------------
# stages: one call feeds phases, spans, the Profile tree and the profiler
# ---------------------------------------------------------------------------

def _check_spans(rec):
    """The invariants of a record's `spans`: offsets monotone per
    thread in list order, children inside their parents, and per name
    the spans sum to `phases`."""
    spans = rec["spans"]
    last = {}
    for name, off, dur, parent, thread in spans:
        assert dur is not None and dur >= 0, (name, dur)
        assert off >= last.get(thread, -1e18), (name, off, spans)
        last[thread] = off
    eps = 0.01  # ms: offsets and durations are rounded to 1e-4 each
    for i, (name, off, dur, parent, _thr) in enumerate(spans):
        assert -1 <= parent < i, (name, parent)
        if parent >= 0:
            _pn, poff, pdur, _pp, _pt = spans[parent]
            assert poff - eps <= off and off + dur <= poff + pdur + eps, \
                (name, spans[parent], spans[i])
    sums = {}
    for name, _off, dur, _p, _t in spans:
        sums[name] = sums.get(name, 0.0) + dur
    for name, total in sums.items():
        if name in rec["phases"]:
            assert abs(total - rec["phases"][name]) <= 0.01 + 1e-3 * total, \
                (name, total, rec["phases"][name])
    return [n for n, *_ in spans]


def test_served_record_spans_cover_the_request():
    """Over HTTP the record holds every stage from the socket to the
    socket: the envelope's stages sit before `start` and past
    `duration_ms`, and request_ms covers the whole."""
    from pilosa_tpu.server.http import Server

    flight.recorder.configure(enabled=True)
    srv = Server().start()
    try:
        _req(srv.port, "POST", "/index/sp", {})
        _req(srv.port, "POST", "/index/sp/field/f", {})
        _req(srv.port, "POST", "/index/sp/field/g", {})
        for c in range(40):
            _req(srv.port, "POST", "/index/sp/query",
                 {"query": f"Set({c}, f={c % 3}) Set({c}, g={c % 5})"})
        q = "Count(Intersect(Row(f=1), Row(g=2)))"
        _req(srv.port, "POST", "/index/sp/query", {"query": q})
        _req(srv.port, "POST", "/index/sp/query", {"query": q})  # a hit
        time.sleep(0.05)    # the handler appends the tail after the reply
        st, d = _req(srv.port, "GET", "/debug/queries?n=50")
        recs = [r for r in d["queries"] if r["query"].startswith("Count")]
        hit, served = recs[0], recs[1]
    finally:
        srv.close()
    assert served["route"] in ("fused", "direct")
    names = _check_spans(served)
    for want in ("http.read", "pql.parse", "admission.classify",
                 "cache_lookup", "batch", "batch.wait", "plan_build",
                 "dispatch", "demux", "result.encode", "http.write"):
        assert want in names, (want, names)
    assert "execute" in names or "compile" in names
    assert served["request_ms"] >= served["duration_ms"]
    by = {n: (off, dur) for n, off, dur, _p, _t in served["spans"]}
    assert by["http.read"][0] < 0 and by["pql.parse"][0] < 0
    assert by["http.write"][0] >= served["duration_ms"] - 0.01
    assert by["http.write"][0] + by["http.write"][1] <= \
        served["request_ms"] + by["http.read"][0] + 0.5
    # the envelope's stages are spans alone: `phases` keeps its keys
    assert "http.read" not in served["phases"]
    # dispatch is the host's share of its execute/compile parent
    i = names.index("dispatch")
    assert served["spans"][served["spans"][i][3]][0] in ("execute",
                                                         "compile")
    # a cache hit never executes, and its record says so
    assert hit["route"] == "cached"
    hit_names = _check_spans(hit)
    assert "cache_lookup" in hit_names and "http.write" in hit_names
    for absent in ("execute", "compile", "dispatch", "plan_build",
                   "batch"):
        assert absent not in hit_names and absent not in hit["phases"]
    assert hit["request_ms"] >= hit["duration_ms"]


def test_follower_record_carries_leader_stages(holder):
    """A request fused into another thread's batch shows the leader's
    stages in ITS record, with the leader's thread."""
    ex = Executor(holder)
    ex.enable_serving(window_s=0.05, max_batch=64, cache_bytes=0)
    flight.recorder.configure(enabled=True)
    queries = [f"Count(Intersect(Row(a={i % 3}), Row(b={i % 5})))"
               for i in range(8)]
    found = None
    for _attempt in range(4):
        flight.recorder.clear()
        barrier = threading.Barrier(len(queries))

        def run(q):
            barrier.wait()
            ex.execute_serving("i", q)

        threads = [threading.Thread(target=run, args=(q,))
                   for q in queries]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        for rec in flight.recorder.recent(50):
            if rec["route"] != "fused" or rec["batch"] < 2:
                continue
            _check_spans(rec)
            own = next(t for n, _o, _d, _p, t in rec["spans"]
                       if n == "batch")
            lead = [(n, t) for n, _o, _d, _p, t in rec["spans"]
                    if t != own]
            if lead:
                found = (rec, own, lead)
                break
        if found:
            break
    assert found, "no follower rode another thread's batch"
    rec, own, lead = found
    lead_names = {n for n, _t in lead}
    assert "plan_build" in lead_names
    assert lead_names & {"execute", "compile"}
    assert "dispatch" in lead_names and "demux" in lead_names
    assert len({t for _n, t in lead}) == 1      # one leader
    # the follower itself waited, on its own thread, inside `batch`
    waits = [s for s in rec["spans"] if s[0] == "batch.wait"]
    assert waits and waits[0][4] == own
    assert rec["spans"][waits[0][3]][0] == "batch"
    # what the leader did hangs under the follower's stay in the batch
    i = next(i for i, s in enumerate(rec["spans"])
             if s[0] == "plan_build")
    assert rec["spans"][rec["spans"][i][3]][0] == "batch"


def test_span_cap_holds_and_phases_keep_every_second():
    acc = flight.Acc()
    prev = flight.push_acc(acc)
    try:
        with flight.stage("plan_build"):
            for _ in range(200):
                with flight.stage("stack_patch"):
                    pass
    finally:
        flight.pop_acc(prev)
    assert len(acc.spans) == flight.Acc._MAX_SPANS == 64
    assert acc.depth == 0 and acc.cur == -1
    assert acc.phases["stack_patch"] > 0
    # the spans that were kept nest; the sum counts all 200
    assert all(s[3] == 0 for s in acc.spans[1:])
    kept = sum(s[2] for s in acc.spans[1:])
    assert acc.phases["stack_patch"] * 1e3 >= kept - 0.01
    # root seconds: the outermost stage alone
    assert abs(acc.root_s - acc.phases["plan_build"]) < 1e-9


def test_spanless_stage_hands_children_to_its_parent():
    """A stack hit is a sum and a count, no span: what ran inside it
    (an assemble) hangs under what was open around it."""
    acc = flight.Acc()
    prev = flight.push_acc(acc)
    try:
        with flight.stage("plan_build"):
            with flight.stage("stack_hit") as st:
                with flight.stage("stack.assemble"):
                    pass
                st.keep = False
            with flight.stage("stack_hit") as st:
                st.name = "stack_rebuild"
    finally:
        flight.pop_acc(prev)
    assert [s[0] for s in acc.spans] == ["plan_build", "stack.assemble",
                                         "stack_rebuild"]
    assert [s[3] for s in acc.spans] == [-1, 0, 0]
    assert set(acc.phases) == {"plan_build", "stack_hit",
                               "stack.assemble", "stack_rebuild"}


def test_stage_adds_nothing_with_recorder_disabled(holder):
    ex = Executor(holder)
    ex.enable_serving(window_s=0.0, max_batch=8)
    flight.recorder.configure(enabled=False)
    try:
        flight.recorder.clear()
        with flight.request():
            with flight.stage("http.read") as st:
                pass
            assert flight.active_acc() is None
            assert getattr(flight._tls, "env", None) is None
            assert ex.execute_serving("i", "Count(Row(a=0))")
        assert st.seconds >= 0 and st.span is None
        assert flight.recorder.recent(5) == []
    finally:
        flight.recorder.configure(enabled=True)


def test_shared_stage_is_one_interval_in_every_rider():
    a, b = flight.Acc(), flight.Acc()
    with flight.stage("execute", accs=[a, b]) as st:
        with flight.stage("dispatch"):
            pass
    for acc in (a, b):
        assert [s[0] for s in acc.spans] == ["execute", "dispatch"]
        assert acc.spans[1][3] == 0
        assert acc.phases["execute"] == st.seconds
    assert flight.active_acc() is None


def test_profiler_host_plane_names_work_stages_only(holder, tmp_path):
    """Under jax.profiler a work stage is a TraceAnnotation on the
    host plane — the device trace's clock — and a wait is not: a
    parked thread would otherwise name every idle gap."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    ex = Executor(holder)
    ex.enable_serving(window_s=0.0, max_batch=8, cache_bytes=0)
    flight.recorder.configure(enabled=True)
    ex.execute_serving("i", "Count(Intersect(Row(a=1), Row(b=3)))")
    jax.profiler.start_trace(str(tmp_path))
    try:
        ex.execute_serving("i", "Count(Intersect(Row(a=1), Row(b=3)))")
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    names = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
    assert "plan_build" in names and "dispatch" in names, sorted(names)[:50]
    assert "cache_lookup" in names and "demux" in names
    for wait in flight.WAITS:
        assert wait not in names, wait


def test_debug_trace_events_sit_at_recorded_offsets(holder):
    ex = Executor(holder)
    ex.enable_serving(window_s=0.0, max_batch=8, cache_bytes=0)
    flight.recorder.configure(enabled=True)
    flight.recorder.clear()
    ex.execute_serving("i", "Count(Union(Row(a=1), Row(b=1)))")
    rec = flight.recorder.recent(1)[0]
    evs = [e for e in flight.recorder.chrome_trace(5)["traceEvents"]
           if e.get("cat") == "stage"]
    assert len(evs) == len(rec["spans"]) > 0
    for ev, (name, off, dur, parent, _thr) in zip(evs, rec["spans"]):
        assert ev["name"] == name
        assert abs(ev["ts"] - (rec["start"] * 1e6 + off * 1e3)) < 1.0
        assert abs(ev["dur"] - max(dur * 1e3, 0.5)) < 1e-6
        assert ev["args"]["parent"] == parent
    # the invented layout is gone: nothing is categorised a "phase"
    assert not any(e.get("cat") == "phase"
                   for e in flight.recorder.chrome_trace(5)["traceEvents"])
    q = next(e for e in flight.recorder.chrome_trace(5)["traceEvents"]
             if e.get("cat") == "query")
    assert q["args"]["phases"] == rec["phases"]


def test_profile_tree_from_the_single_stage_call(holder):
    """Profile=true on the solo path: the same tree as before the
    stages — Execute > executeCount > plan_build > stack, then the
    dispatch — built by the one stage call per site."""
    api = API(holder)  # serving never enabled
    # Not() has no packed host arm: the count is a device dispatch
    resp = api.query("i", "Count(Not(Row(a=2)))", profile=True)
    root = resp["profile"][0]
    assert root["name"] == "executor.Execute"
    call = next(c for c in root["children"]
                if c["name"] == "executor.executeCount")
    kids = {c["name"]: c for c in call.get("children", [])}
    assert "plan_build" in kids and kids["plan_build"]["tags"] == {
        "call": "Not"}
    stacks = [c for c in kids["plan_build"].get("children", [])
              if c["name"].startswith("stack_")]
    assert stacks and all("outcome" in c["tags"] for c in stacks)
    run = kids.get("execute") or kids.get("compile")
    assert run and run["tags"]["kind"] == "count"
    assert run["tags"]["compile"] == (run["name"] == "compile")
    assert [c["name"] for c in run["children"]] == ["dispatch"]


# ---------------------------------------------------------------------------
# acceptance: Profile=true fused into a concurrent batch
# ---------------------------------------------------------------------------

def _span_names(d, out):
    out.append((d["name"], d.get("tags", {})))
    for c in d.get("children", []):
        _span_names(c, out)
    return out


def test_profile_fused_batch_multithreaded(holder):
    """A Profile=true query fused into a concurrent batch returns a
    span tree including its leader-executed device phases, attributed
    per subquery (the PR's acceptance criterion)."""
    api = API(holder)
    api.executor.enable_serving(window_s=0.05, max_batch=64,
                                cache_bytes=0)  # no cache: force fusion
    plain = Executor(holder)
    queries = [f"Count(Row(a={i % 3}))" for i in range(3)] + [
        "Count(Intersect(Row(a=1), Row(b=1)))",
        "Count(Union(Row(a=0), Row(b=4)))",
        "Count(Row(b=2))",
        "Count(Xor(Row(a=2), Row(b=3)))",
        "Count(Difference(Row(a=1), Row(b=0)))",
    ]
    want = {q: [serialize_result(r) for r in plain.execute("i", q)]
            for q in queries}

    for _attempt in range(3):
        got = {}
        lock = threading.Lock()
        barrier = threading.Barrier(len(queries))

        def run(q):
            barrier.wait()
            resp = api.query("i", q, profile=True)
            with lock:
                got[q] = resp

        threads = [threading.Thread(target=run, args=(q,))
                   for q in queries]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # bit-exactness never bends for observability
        assert {q: r["results"] for q, r in got.items()} == want
        fused_trees = []
        for q, resp in got.items():
            prof = resp.get("profile")
            assert prof and prof[0]["name"] == "executor.Execute"
            spans = _span_names(prof[0], [])
            names = [n for n, _t in spans]
            if "execute" in names or "compile" in names:
                fused_trees.append(spans)
        # at least one query must have ridden a real (>=2) batch and
        # carry the leader-executed device phases in ITS OWN tree
        batched = []
        for spans in fused_trees:
            for name, tags in spans:
                if (name in ("execute", "compile")
                        and tags.get("batch", 0) >= 2):
                    batched.append((spans, tags))
        if batched:
            break
    assert batched, "no profiled query ever fused into a >=2 batch"
    spans, dtags = batched[0]
    names = [n for n, _t in spans]
    # per-subquery stages: plan + dispatch + demux all present, and
    # the shared execute/compile span says whether it compiled or hit
    # the jit cache and holds the host's `dispatch` share as a child
    assert "plan_build" in names
    assert "demux" in names
    assert "dispatch" in names
    assert "compile" in dtags and "subqueries" in dtags
    # the fused subtree includes the trace-tagged root on the caller
    assert any(n == "executor.Execute" for n in names)


def test_profile_solo_still_works(holder):
    api = API(holder)  # serving never enabled
    resp = api.query("i", "Count(Row(a=1))", profile=True)
    assert resp["profile"][0]["name"] == "executor.Execute"
    kids = [c["name"] for c in resp["profile"][0].get("children", [])]
    assert "executor.executeCount" in kids


# ---------------------------------------------------------------------------
# satellite: monitor capture with the batch's trace ids
# ---------------------------------------------------------------------------

def test_batch_failure_captured_with_trace_ids(holder):
    from pilosa_tpu.obs.monitor import global_monitor

    ex = Executor(holder)
    layer = ex.enable_serving(window_s=0.0, max_batch=8, cache_bytes=0)
    flight.recorder.configure(enabled=True)

    def boom(batch):
        raise RuntimeError("leader died mid-batch")

    layer._run_batch = boom
    before = len(global_monitor.recent())
    with pytest.raises(RuntimeError, match="leader died"):
        ex.execute_serving("i", "Count(Row(a=1))")
    events = global_monitor.recent()
    assert len(events) > before
    ev = events[-1]
    assert ev["type"] == "RuntimeError"
    assert ev["where"] == "serving.batch"
    assert ev["batch"] >= 1
    assert ev["trace_ids"], "batch trace ids missing from capture"
    # the failing query's own flight record carries the error too
    recs = flight.recorder.recent(5)
    assert recs and recs[0].get("error", "").startswith("RuntimeError")
    assert recs[0]["trace_id"] in ev["trace_ids"]


# ---------------------------------------------------------------------------
# /debug endpoint surface
# ---------------------------------------------------------------------------

def _req(port, method, path, body=None, headers=None):
    import http.client
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    data = json.dumps(body) if isinstance(body, (dict, list)) else body
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    c.request(method, path, body=data, headers=hdrs)
    r = c.getresponse()
    raw = r.read()
    c.close()
    try:
        return r.status, json.loads(raw)
    except json.JSONDecodeError:
        return r.status, raw.decode()


def test_debug_queries_and_trace_endpoints():
    from pilosa_tpu.server.http import Server

    flight.recorder.configure(enabled=True)
    srv = Server().start()
    try:
        _req(srv.port, "POST", "/index/dq", {})
        _req(srv.port, "POST", "/index/dq/field/f", {})
        _req(srv.port, "POST", "/index/dq/query",
             {"query": "Set(1, f=1)"})
        _req(srv.port, "POST", "/index/dq/query",
             {"query": "Count(Row(f=1))"})
        st, d = _req(srv.port, "GET", "/debug/queries?n=50")
        assert st == 200 and d["enabled"] is True
        qs = d["queries"]
        assert any(r["index"] == "dq" and r["query"].startswith("Count")
                   for r in qs)
        rec = next(r for r in qs if r["query"].startswith("Count"))
        for field in ("trace_id", "route", "duration_ms", "phases",
                      "batch", "start"):
            assert field in rec, field
        st, trace = _req(srv.port, "GET", "/debug/trace?n=50")
        assert st == 200
        assert isinstance(trace, dict) and trace["traceEvents"]
        assert all(ev["ph"] in ("X", "M")
                   for ev in trace["traceEvents"])
        # /metrics: phase histograms flushed; exemplars only under a
        # negotiated OpenMetrics Accept header
        st, text = _req(srv.port, "GET", "/metrics")
        assert st == 200
        assert "pilosa_query_phase_seconds_bucket" in text
        assert 'trace_id="' not in text
        # Accept-header negotiation is deliberately NOT honored:
        # stock Prometheus sends the OpenMetrics Accept header by
        # default but would reject this exposition — exemplars are an
        # explicit opt-in query param
        st, text = _req(srv.port, "GET", "/metrics", headers={
            "Accept": "application/openmetrics-text"})
        assert st == 200 and 'trace_id="' not in text
        st, text = _req(srv.port, "GET", "/metrics?exemplars=1")
        assert st == 200 and 'trace_id="q' in text
        # /metrics.json flushes too
        st, j = _req(srv.port, "GET", "/metrics.json")
        assert st == 200 and "pilosa_query_phase_seconds" in j
    finally:
        srv.close()


def test_debug_endpoints_admin_gated():
    from pilosa_tpu.server.authn import Authenticator, encode_jwt
    from pilosa_tpu.server.authz import Authorizer
    from pilosa_tpu.server.http import Server

    secret = b"flight-test-secret"
    authn = Authenticator(secret)
    authz = Authorizer(user_groups={"readers": {"dq": "read"}},
                       admin_group="admins")
    srv = Server(auth=(authn, authz)).start()
    try:
        rtok = encode_jwt({"groups": ["readers"],
                           "exp": time.time() + 60}, secret)
        atok = encode_jwt({"groups": ["admins"],
                           "exp": time.time() + 60}, secret)
        for path in ("/debug/queries", "/debug/trace",
                     "/debug/profile?seconds=0.05&hz=20",
                     "/debug/allocs", "/debug/errors"):
            st, _ = _req(srv.port, "GET", path)
            assert st == 401, path             # no token
            st, _ = _req(srv.port, "GET", path, headers={
                "Authorization": f"Bearer {rtok}"})
            assert st == 403, path             # read-only token
            st, _ = _req(srv.port, "GET", path, headers={
                "Authorization": f"Bearer {atok}"})
            assert st == 200, path             # admin passes
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------

def test_flight_config_knobs(tmp_path):
    from pilosa_tpu import config as cfgmod

    p = tmp_path / "c.toml"
    p.write_text("[flight]\nrecorder = false\nring = 9\n")
    cfg = cfgmod.load(str(p), env={})
    assert cfg.flight_recorder is False and cfg.flight_ring == 9
    prev = (flight.recorder.enabled, flight.recorder._ring.maxlen)
    try:
        cfg.apply_flight_settings()
        assert flight.recorder.enabled is False
        assert flight.recorder._ring.maxlen == 9
    finally:
        flight.recorder.configure(enabled=prev[0], keep=prev[1])
    # env wins over file (the standard layering)
    cfg2 = cfgmod.load(str(p), env={"PILOSA_TPU_FLIGHT_RECORDER": "1"})
    assert cfg2.flight_recorder is True
