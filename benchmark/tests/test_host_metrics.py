"""The host-thread metrics (PR 41) are data files read by `prom_delta`:
the served threads' collection pauses and the compiles a served thread
paid for, each from two `/metrics.json` scrapes."""

import json
import os

import pytest
from harness import server

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GC = "pilosa_gc_pause_seconds_total"
JIT = "pilosa_jit_compiles_total"
NAMES = ("gc.pause_s", "compile.served_in_window")


def _gc(served, audit=0.0):
    """Labels render sorted, as the program's registry renders them."""
    return {GC: {'generation="0",path="served"': served * 0.25,
                 'generation="2",path="served"': served * 0.75,
                 'generation="0",path="audit"': audit}}


def _jit(served, audit, hits):
    return {JIT: {'event="compile",path="served"': served,
                  'event="compile",path="audit"': audit,
                  'event="cache_hit",path="served"': hits}}


CASES = {
    # name: (first scrape, second scrape, what the window reads)
    "gc.pause_s": (_gc(0.5, 0.1), _gc(0.9, 3.0), 0.4),
    "compile.served_in_window": (_jit(4, 10, 3), _jit(6, 30, 5), 2.0),
}


def _spec(name):
    return server.load_json("layer_metrics", f"{name}.json")


def _read(spec, m0, m1):
    read = server.load_module("readers", spec["reader"]).read
    return read({"m0": server.Metrics(m0), "m1": server.Metrics(m1)},
                spec["args"])


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_file_matches_its_declaration(name):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = _spec(name)
    assert spec["name"] == name
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == declared[key], key
    assert spec["layer"] == "host threads"
    assert set(spec["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert spec["reader"] == "prom_delta"


@pytest.mark.parametrize("name", NAMES)
def test_it_reads_the_served_threads_movement_over_the_window(name):
    m0, m1, want = CASES[name]
    assert _read(_spec(name), m0, m1) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counters_reads_zero(name):
    """The parent commit has none of the counters: each reads 0, and
    nothing raises; nor does a window in which nothing moved."""
    other = {"pilosa_query_total": {"": 12}}
    assert _read(_spec(name), {}, other) == 0
    m0, _m1, _want = CASES[name]
    assert _read(_spec(name), m0, m0) == 0
