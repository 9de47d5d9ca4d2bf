"""`gc.tenured_share` is a data file read by `prom_delta`: of the full
collections on threads serving a request, the share that walked only
what the previous one had not frozen, from two `/metrics.json`
scrapes."""

import json
import os

import pytest
from harness import server

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "gc.tenured_share"
FULL = "pilosa_gc_full_total"
GC = "pilosa_gc_collections_total"


def _scrape(tenured, whole, served_gen2, background=0):
    """Labels render sorted, as the program's registry renders them."""
    return {
        FULL: {'path="served",walk="tenured"': tenured,
               'path="served",walk="whole"': whole,
               'path="background",walk="whole"': background},
        GC: {'generation="0",path="served"': 500 * served_gen2,
             'generation="2",path="served"': served_gen2,
             'generation="2",path="background"': background},
    }


def _spec():
    return server.load_json("layer_metrics", f"{NAME}.json")


def _read(m0, m1):
    spec = _spec()
    read = server.load_module("readers", spec["reader"]).read
    return read({"m0": server.Metrics(m0), "m1": server.Metrics(m1)},
                spec["args"])


def test_the_metric_file_matches_its_declaration():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = next(m for m in bench["per_layer"] if m["name"] == NAME)
    spec = _spec()
    assert spec["name"] == NAME
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == declared[key], key
    assert spec["layer"] == "host threads"
    gc_pause = next(m for m in bench["per_layer"] if m["name"] == "gc.pause_s")
    assert spec["workloads"] == gc_pause["workloads"]
    assert spec["reader"] == "prom_delta"


@pytest.mark.parametrize("m0, m1, want", [
    # 9 of the window's 10 served full collections were tenured walks;
    # the background's whole walks are not served collections
    (_scrape(3, 1, 4, background=2), _scrape(12, 2, 14, background=9), 90.0),
    (_scrape(0, 0, 0), _scrape(5, 0, 5), 100.0),
    (_scrape(7, 1, 8), _scrape(7, 3, 10), 0.0),
])
def test_it_reads_the_served_full_collections_tenured_share(m0, m1, want):
    assert _read(m0, m1) == pytest.approx(want)


def test_a_program_without_the_walk_counter_reads_zero():
    """The parent commit collects but has no walk counter: 0, and
    nothing raises."""
    m0 = {GC: {'generation="2",path="served"': 3}}
    m1 = {GC: {'generation="2",path="served"': 20}}
    assert _read(m0, m1) == 0


def test_a_window_without_a_served_full_collection_reads_nothing():
    m0 = _scrape(3, 1, 4, background=2)
    assert _read(m0, m0) is None
    assert _read({}, {"pilosa_query_total": {"": 12}}) is None
