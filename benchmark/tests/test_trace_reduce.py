import json
import os

import pytest
from harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_events.json")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_busy_is_the_union_of_the_ops_line():
    events = [
        (DEV, "XLA Ops", "%a", 0, 100),
        (DEV, "XLA Ops", "%b", 50, 100),          # overlaps a: union 0..150
        (DEV, "XLA Ops", "%kernel custom_call_target=\"tpu_custom_call\"",
         400, 200),
        (DEV, "XLA Modules", "jit_run", 0, 1000),  # not counted: ops exist
        (HOST, "python", "PjitFunction(run)", 160, 230),
    ]
    ops = tr.device_ops(events, chips=1)
    assert tr.busy_seconds(ops) == pytest.approx(350e-9)
    assert tr.op_seconds(ops, "tpu_custom_call") == pytest.approx(200e-9)
    assert tr.op_seconds(ops, "no such op") == 0.0
    out = tr.reduce_events(events, 1, window_s=1000e-9)
    assert 100 * (1 - out["busy_s"] / out["window_s"]) == pytest.approx(65.0)
    (what, seconds), = out["breakdown"]["idle_gaps"]
    assert seconds == pytest.approx(250e-9)
    assert what == "host: PjitFunction(run)"
    assert out["breakdown"]["device_ops"][0][1] == pytest.approx(200e-9)


def test_busy_is_averaged_over_the_chips_asked_for():
    events = [(f"/device:TPU:{c}", "XLA Ops", "%a", 0, 100 * (c + 1))
              for c in range(4)]
    assert tr.busy_seconds(tr.device_ops(events, 4)) == pytest.approx(250e-9)
    assert tr.busy_seconds(tr.device_ops(events, 1)) == pytest.approx(100e-9)


def test_no_device_plane_reads_nothing():
    events = [(HOST, "python", "x", 0, 10)]
    out = tr.reduce_events(events, 1, window_s=1.0)
    assert out["busy_s"] == 0.0 and not out["ops"]
    assert out["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_a_plane_without_an_ops_line_counts_all_its_lines():
    events = [(DEV, "Steps", "s", 0, 10), (DEV, "Other", "o", 20, 10)]
    assert tr.busy_seconds(tr.device_ops(events, 1)) == pytest.approx(20e-9)


def test_recorded_chip_trace():
    """Four GroupBy programs recorded on a TPU v5 lite (PR 25): the
    Pallas kernel is a custom-call op, and nearly all the busy time."""
    with open(DATA) as f:
        data = json.load(f)
    events = [tuple(e) for e in data["events"]]
    window_s = data["window_ns"] / 1e9
    out = tr.reduce_events(events, 1, window_s)
    kernel = tr.op_seconds(out["ops"], "tpu_custom_call")
    assert 0 < kernel < out["busy_s"] < window_s
    assert out["busy_s"] == pytest.approx(2.3896, abs=1e-3)
    assert kernel == pytest.approx(2.2018, abs=1e-3)
    assert (DEV, "XLA Ops") in out["planes"]
    assert "tpu_custom_call" in out["breakdown"]["device_ops"][0][0]
    assert len(out["breakdown"]["idle_gaps"]) == 10
