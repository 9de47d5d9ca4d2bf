"""`stack.expand_kept_share` is a data file read by `prom_delta`: the
share of encoded pages handed out dense from a block kept since an
earlier expansion, over the window, from two `/metrics.json` scrapes."""

import json
import os

import pytest
from harness import server

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "stack.expand_kept_share"
EXPAND = "pilosa_stack_expand_total"


def _scrape(kept, expanded):
    return {EXPAND: {'outcome="kept"': kept,
                     'outcome="expanded"': expanded}}


def _read(m0, m1):
    spec = server.load_json("layer_metrics", f"{NAME}.json")
    read = server.load_module("readers", spec["reader"]).read
    return read({"m0": server.Metrics(m0), "m1": server.Metrics(m1)},
                spec["args"])


def test_the_metric_file_matches_its_declaration():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = next(m for m in bench["per_layer"] if m["name"] == NAME)
    spec = server.load_json("layer_metrics", f"{NAME}.json")
    assert spec["name"] == NAME
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == declared[key], key
    assert spec["layer"] == "residency"
    assert spec["workloads"] == ["taxi-1b.dashboard"]
    assert spec["reader"] == "prom_delta"


@pytest.mark.parametrize("m0, m1, want", [
    # 300 kept and 100 expanded in the window: 75%
    (_scrape(40, 60), _scrape(340, 160), 75.0),
    # every use in the window a first use
    (_scrape(0, 10), _scrape(0, 30), 0.0),
    # a window that starts before the first encoded page
    ({}, _scrape(9, 1), 90.0),
])
def test_it_reads_the_kept_share_of_the_window(m0, m1, want):
    assert _read(m0, m1) == pytest.approx(want)


def test_a_program_without_the_counter_reads_nothing():
    """The parent commit has no such counter, and a cell that hands
    out no encoded page dense moves it not at all: no value, and
    nothing raises."""
    assert _read({}, {"pilosa_query_total": {"": 12}}) is None
    assert _read(_scrape(5, 5), _scrape(5, 5)) is None
