"""The readers of the stage spans and of the named kernels, on records
and events written by hand; then one traced CPU rehearsal in which the
span metrics read a number from the program's own records."""

import json
import os
import subprocess
import sys

import pytest
from harness import bytes_model, pql, server

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = server.load_json("configs", "able-1b.json")
SHARDS = 954
GROUPBY = ("GroupBy(Rows(edu), Rows(gen), Rows(dom), filter=Row(a=1), "
           "aggregate=Sum(field=age))")
ONE = bytes_model.necessary_bytes(pql.parse(GROUPBY), CONFIG["params"],
                                  SHARDS)
T = 7   # a thread


def _served(plan_ms=40.0, rebuild_ms=None, dispatch_ms=2.0, wait_ms=600.0):
    """One device-served record: the envelope, a batch with the
    leader's stages under it, the tail after commit."""
    spans = [
        ["http.read", -0.3, 0.05, -1, T],
        ["pql.parse", -0.2, 0.10, -1, T],
        ["admission.classify", -0.1, 0.05, -1, T],
        ["admission.wait", -0.05, 0.02, -1, T],
        ["cache_lookup", 0.0, 0.2, -1, T],
        ["batch", 0.3, 700.0, -1, T],                       # 5
        ["batch.wait", 0.4, wait_ms, 5, T],
        ["plan_build", 601.0, plan_ms, 5, 9],               # 7
    ]
    if rebuild_ms is not None:
        spans.append(["stack_rebuild", 602.0, rebuild_ms, 7, 9])
        spans.append(["stack.assemble", 603.0 + rebuild_ms, 1.0, 7, 9])
    spans += [
        ["execute", 650.0, 50.0, 5, 9],
        ["dispatch", 650.1, dispatch_ms, len(spans), 9],
        ["demux", 700.1, 0.1, 5, 9],
        ["result.encode", 700.5, 0.3, -1, T],
        ["http.write", 700.9, 0.1, -1, T],
    ]
    return {"route": "fused", "phases": {"execute": 50.0}, "spans": spans}


def _hit():
    return {"route": "cached", "phases": {"cache_lookup": 0.2}, "spans": [
        ["http.read", -0.3, 0.05, -1, T], ["pql.parse", -0.2, 0.05, -1, T],
        ["admission.classify", -0.1, 0.05, -1, T],
        ["cache_lookup", 0.0, 0.2, -1, T],
        ["result.encode", 0.3, 0.02, -1, T],
        ["http.write", 0.4, 0.03, -1, T]]}


ENVELOPE = {"spans": ["http.read", "pql.parse", "admission.classify",
                      "result.encode", "http.write"], "q": 50}


def test_the_envelope_is_summed_per_record_cache_hits_too():
    spans = server.load_module("readers", "spans")
    ctx = {"flights": [_served(), _hit(), _hit()]}
    # 0.6 for the served record, 0.2 for each hit: the median is a hit
    assert spans.read(ctx, ENVELOPE) == pytest.approx(0.2)
    assert spans.read({"flights": [_served()]}, ENVELOPE) \
        == pytest.approx(0.6)


def test_served_only_and_the_waits_of_a_record():
    spans = server.load_module("readers", "spans")
    ctx = {"flights": [_served(wait_ms=600.0), _served(wait_ms=10.0),
                       _served(wait_ms=650.0), _hit()]}
    args = {"spans": ["batch.wait", "admission.wait"], "served": True,
            "q": 50}
    assert spans.read(ctx, args) == pytest.approx(600.02)
    assert spans.read({"flights": [_hit()]}, args) is None


def test_self_time_is_the_span_less_its_direct_children():
    spans = server.load_module("readers", "spans")
    args = {"spans": ["plan_build"], "self": True, "served": True, "q": 50}
    bare = {"flights": [_served(plan_ms=40.0)]}
    assert spans.read(bare, args) == pytest.approx(40.0)
    held = {"flights": [_served(plan_ms=40.0, rebuild_ms=30.0)]}
    assert spans.read(held, args) == pytest.approx(9.0)    # 40 - 30 - 1
    # the grandchild `dispatch` is not taken off `batch`: direct only
    assert spans.record_ms(_served()["spans"], {"batch"}, True) \
        == pytest.approx(700.0 - 600.0 - 40.0 - 50.0 - 0.1)


def test_a_record_without_the_span_is_left_out_never_zero():
    spans = server.load_module("readers", "spans")
    args = {"spans": ["stack_patch", "stack_rebuild", "stack_page_rebuild",
                      "stack.assemble"], "served": True, "q": 50}
    ctx = {"flights": [_served(), _served(rebuild_ms=30.0), _served()]}
    assert spans.read(ctx, args) == pytest.approx(31.0)   # the one that has
    assert spans.read({"flights": [_served(), _served()]}, args) is None
    # a program that records no spans (the parent of the PR that added
    # them): nothing to read, and no error
    old = {"route": "fused", "phases": {"execute": 50.0}}
    assert spans.read({"flights": [old]}, ENVELOPE) is None
    assert spans.read({"flights": []}, ENVELOPE) is None
    assert spans.read({"flights": [_served(dispatch_ms=2.5)]},
                      {"spans": ["dispatch"], "served": True, "q": 50}) \
        == pytest.approx(2.5)


def _trace_ctx(ops):
    records = [{"client": 0, "seq": 0, "send": 100.5, "recv": 101.7}]
    flights = [{"start": 100.5005, "duration_ms": 1199.5, "route": "fused",
                "phases": {"execute": 600.0}}]
    return {
        "traced_wall": [100.0, 105.0], "flights": flights,
        "records": records, "ok": [True],
        "plans": [[{"t": "g", "q": GROUPBY}] * 2],
        "config": CONFIG, "shards": SHARDS,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"ops": ops, "busy_s": 1.0, "window_s": 5.0},
    }


KERNELS = {"ops": "groupby_(fused_sum|fused_minmax|onehot|sum)"}
SUM = ('%groupby_fused_sum.1 = s32[18,128]{1,0} custom-call(%copy), '
       'custom_call_target="tpu_custom_call"')
MINMAX = SUM.replace("fused_sum", "fused_minmax")


def test_the_kernel_s_share_is_bytes_over_peak_over_its_own_seconds():
    trace_ops = server.load_module("readers", "trace_ops")
    ns = 10**9
    ops = {0: [(SUM, 0, ns // 2), (MINMAX, ns, 2 * ns),
               ("%pad_add_fusion = u32[] fusion()", 3 * ns, 4 * ns)]}
    got = trace_ops.read(_trace_ctx(ops), KERNELS)
    assert got == pytest.approx(100 * ONE / 819e9 / 1.5, rel=1e-6)
    # the two variants apart
    assert trace_ops.read(_trace_ctx(ops), {"ops": "groupby_fused_sum"}) \
        == pytest.approx(100 * ONE / 819e9 / 0.5, rel=1e-6)


def test_a_pattern_that_matches_nothing_reads_nothing_never_zero():
    trace_ops = server.load_module("readers", "trace_ops")
    unnamed = {0: [('%run.1 = s32[18,128] custom-call(%copy), '
                    'custom_call_target="tpu_custom_call"', 0, 10**9)]}
    assert trace_ops.read(_trace_ctx(unnamed), KERNELS) is None
    assert trace_ops.read(_trace_ctx({}), KERNELS) is None
    named = {0: [(SUM, 0, 10**9)]}
    ctx = _trace_ctx(named)
    ctx["peaks"] = None                     # a device not in peaks.json
    assert trace_ops.read(ctx, KERNELS) is None
    ctx = _trace_ctx(named)
    ctx["flights"] = []                     # nothing served by the device
    assert trace_ops.read(ctx, KERNELS) is None


def test_every_new_metric_file_names_a_reader_and_a_declared_metric():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("http.envelope_p50_ms", "batch.wait_p50_ms",
                 "plan.build_p50_ms", "stack.assemble_p50_ms",
                 "plan.dispatch_p50_ms", "groupby.kernel_hbm_share",
                 "audit.shadow_s"):
        spec = server.load_json("layer_metrics", f"{name}.json")
        assert spec["name"] == name and name in declared
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == declared[name][key], (name, key)
        assert spec.get("workloads") == declared[name].get("workloads")
        assert hasattr(server.load_module("readers", spec["reader"]), "read")


def test_a_traced_rehearsal_reads_the_span_metrics():
    """The program's own records carry the spans the metric files name
    (no profiler plane is asserted here: a CPU has no device plane)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "able-1b.groupby60", "--seed", "2147483778", "--seconds", "6",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    metrics = result["metrics"]
    for name in ("http.envelope_p50_ms", "batch.wait_p50_ms",
                 "plan.build_p50_ms", "stack.assemble_p50_ms",
                 "plan.dispatch_p50_ms"):
        assert name in metrics, (name, sorted(metrics))
        assert metrics[name]["value"] >= 0 and metrics[name]["unit"] == "ms"
    assert all(row["ok"] for row in result["compared"]), result["compared"]
