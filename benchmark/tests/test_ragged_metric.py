"""`ragged.assembled_gb` (PR 28) is a data file read by `prom_delta`:
bytes of page leaves per ragged dispatch, from two scrapes."""

import json
import os

import pytest
from harness import server

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "ragged.assembled_gb"
BYTES = "pilosa_ragged_assembled_bytes_total"
DISPATCH = "pilosa_serving_dispatch_total"


def _scrape(nbytes, ragged, mesh=0):
    return server.Metrics({
        BYTES: {"": nbytes},
        DISPATCH: {'kind="ragged"': ragged, 'kind="ragged_mesh"': mesh,
                   'kind="group"': 5}})


def test_the_metric_file_matches_its_declaration():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = bench["per_layer"][-1]
    spec = server.load_json("layer_metrics", f"{NAME}.json")
    assert declared["name"] == spec["name"] == NAME
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == declared[key], key
    assert spec["workloads"] == [w["name"] for w in bench["workloads"]]
    assert spec["reader"] == "prom_delta"


def test_it_reads_gigabytes_per_ragged_dispatch():
    spec = server.load_json("layer_metrics", f"{NAME}.json")
    read = server.load_module("readers", spec["reader"]).read
    ctx = {"m0": _scrape(4e9, 10, mesh=3), "m1": _scrape(4e9 + 13e9, 20, mesh=9)}
    assert read(ctx, spec["args"]) == pytest.approx(1.3)
    # no ragged dispatch in the window: nothing to read, never zero
    ctx = {"m0": _scrape(4e9, 10), "m1": _scrape(4e9, 10, mesh=7)}
    assert read(ctx, spec["args"]) is None
    # a program without the counter (the parent commit): zero bytes over
    # the dispatches that did happen, and no error
    bare = {DISPATCH: {'kind="ragged"': 3}}
    ctx = {"m0": server.Metrics({}), "m1": server.Metrics(bare)}
    assert read(ctx, spec["args"]) == 0
