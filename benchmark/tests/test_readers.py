"""The readers that turn flight records and client records into
per-layer metrics, on records written by hand."""

import pytest
from harness import bytes_model, pql, server

CONFIG = server.load_json("configs", "able-1b.json")
SHARDS = 954
GROUPBY = ("GroupBy(Rows(edu), Rows(gen), Rows(dom), filter=Row(a=1), "
           "aggregate=Sum(field=age))")
ONE = bytes_model.necessary_bytes(pql.parse(GROUPBY), CONFIG["params"],
                                  SHARDS)


def _ctx(flights, records, traced=(100.0, 105.0)):
    return {
        "traced_wall": list(traced), "flights": flights, "records": records,
        "ok": [True] * len(records),
        "plans": [[{"t": "g", "q": GROUPBY}] * 8, [{"t": "g", "q": GROUPBY}] * 8],
        "config": CONFIG, "shards": SHARDS,
        "peaks": {"hbm_bytes_per_s": 819e9}, "cell": {"chips": 1},
        "trace": {"ops": {0: [("%k", 0, 1)]}, "busy_s": 4.0, "window_s": 5.0},
    }


def _flight(start, duration_s, execute_s, route="fused"):
    phases = {"execute": 1e3 * execute_s} if execute_s else {}
    return {"start": start, "duration_ms": 1e3 * duration_s,
            "route": route, "phases": phases}


def _record(client, seq, send, recv):
    return {"client": client, "seq": seq, "send": send, "recv": recv}


def test_a_query_counts_by_the_share_of_its_execute_inside_the_trace():
    trace = server.load_module("readers", "trace")
    records = [
        _record(0, 0, 99.0, 100.4),     # executes 99.8..100.4: 2/3 inside
        _record(1, 0, 100.5, 101.7),    # waits, executes 101.1..101.7: whole
        _record(0, 1, 104.6, 105.3),    # executes 104.7..105.3: half inside
        _record(1, 1, 102.0, 102.001),  # a cache hit: nothing read
        _record(0, 2, 106.0, 106.7),    # after the trace
    ]
    flights = [
        _flight(99.0005, 1.3995, 0.6), _flight(100.5005, 1.1995, 0.6),
        _flight(104.6005, 0.6995, 0.6), _flight(102.0001, 0.0005, 0.0,
                                                "cached"),
        _flight(106.0005, 0.6995, 0.6),
    ]
    ctx = _ctx(flights, records)
    assert trace.served_bytes(ctx) == pytest.approx(
        ONE * (2 / 3 + 1 + 1 / 2), rel=1e-6)
    value = trace.read(ctx, {"what": "mfu"})
    assert value == pytest.approx(
        100 * ONE * (2 / 3 + 1 + 1 / 2) / 819e9 / 5.0, rel=1e-6)
    # one chip: the one-chip formula, to the last digit
    assert value == 100.0 * trace.served_bytes(ctx) / 819e9 / 5.0
    assert trace.read(ctx, {"what": "idle_share"}) == pytest.approx(20.0)


def test_four_chips_read_a_quarter_of_the_one_chip_formula():
    """A cell of four chips: the bytes all four served, over what four
    chips move in the time, which the trace averages over the chips."""
    from harness import trace_reduce
    trace = server.load_module("readers", "trace")
    records = [_record(0, 0, 100.5, 101.7)]
    flights = [_flight(100.5005, 1.1995, 0.6)]
    events = [(f"/device:TPU:{c}", "XLA Ops", "%groupby_fused_sum.1",
               10 ** 9 * c, 10 ** 9) for c in range(4)]
    ctx = _ctx(flights, records)
    ctx["trace"] = trace_reduce.reduce_events(events, 4, 5.0)
    ctx["cell"] = {"chips": 4}
    nbytes = trace.served_bytes(ctx)
    assert nbytes == pytest.approx(ONE)
    assert ctx["trace"]["busy_s"] == pytest.approx(1.0)
    assert trace.read(ctx, {"what": "mfu"}) == pytest.approx(
        100 * nbytes / 819e9 / 5.0 / 4)
    assert trace.read(ctx, {"what": "idle_share"}) == pytest.approx(80.0)
    kernels = {"ops": "groupby_fused_sum"}
    for reader in ("trace_ops", "trace_ops_groupby"):
        share = server.load_module("readers", reader).read(ctx, kernels)
        assert share == pytest.approx(100 * nbytes / 819e9 / 1.0 / 4)
        ctx["cell"] = {"chips": 1}
        assert server.load_module("readers", reader).read(ctx, kernels) \
            == pytest.approx(4 * share)
        ctx["cell"] = {"chips": 4}


def test_nothing_served_reads_nothing_never_zero():
    trace = server.load_module("readers", "trace")
    ctx = _ctx([_flight(102.0001, 0.0005, 0.0, "cached")],
               [_record(0, 0, 102.0, 102.001)])
    assert trace.read(ctx, {"what": "mfu"}) is None
    ctx["trace"] = {"ops": {}, "busy_s": 0.0, "window_s": 5.0}
    assert trace.read(ctx, {"what": "idle_share"}) is None


def test_the_host_share_is_the_flight_s_duration_less_its_execute():
    flight = server.load_module("readers", "flight")
    ctx = {"flights": [_flight(1.0, 0.700, 0.6), _flight(2.0, 0.650, 0.6),
                       _flight(3.0, 0.800, 0.6),
                       _flight(4.0, 0.001, 0.0, "cached")]}
    assert flight.read(ctx, {"phase": "execute", "q": 50}) \
        == pytest.approx(600.0)
    assert flight.read(ctx, {"field": "duration_ms",
                             "minus_phase": "execute", "q": 50}) \
        == pytest.approx(100.0)
    assert flight.read({"flights": ctx["flights"][3:]},
                       {"phase": "execute", "q": 50}) is None
