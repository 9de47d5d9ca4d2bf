"""The command end to end on the CPU, at the rehearsal size.

- the rehearsal, run as the driver runs the command, never prints
  ``correct: true``, though every number compared is within its limit;
- the control (``--control stale-shard``: the last shard's import
  never reaches the server, the reference counts it) comes out as not
  correct;
- with the look for a chip skipped (``rehearsal_counts``), a clean run
  is correct and a run whose answers are altered where they are
  produced (one group's count, one Count, one Sum: the window's first
  answer and every seventh after it) is not.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = os.path.join(BENCH, "run.py")


def _cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _command(cell, *extra):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "2147483777",
         "--seconds", "3", "--trace", "0", *extra],
        capture_output=True, text=True, env=env, timeout=600)
    return out


@pytest.mark.parametrize("cell", _cells())
def test_rehearsal_is_never_correct(cell):
    out = _command(cell, "--rehearse-cpu")
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(row["ok"] for row in result["compared"]), result["compared"]
    assert list(result)[-1] == "compared"
    assert "compared wrong: 0 (limit 0) ok" in out.stderr[-2000:]


def test_no_chip_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, RUN, "--workload", _cells()[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("cell", _cells())
def test_control_is_not_correct(cell):
    out = _command(cell, "--rehearse-cpu", "--control", "stale-shard")
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    wrong = next(r for r in result["compared"] if r["name"] == "wrong")
    assert wrong["value"] > 0 and not wrong["ok"]
    assert result["failed"] >= wrong["value"]


FAULT = r"""
import json, sys
sys.path.insert(0, {bench!r})
import run
from harness import server

fault = {fault!r}
real_start, real_start_run = server.start, run.LoadGen.start_run
state = {{"n": 0}}

def start(config, chips):
    srv, http_ = real_start(config, chips)
    inner = srv.api.query

    def query(index, pql, *a, **kw):
        resp = inner(index, pql, *a, **kw)
        state["n"] += 1
        if fault == "altered" and state["n"] % 7 == 1 and resp.get("results"):
            first = resp["results"][0]
            if isinstance(first, int):
                resp = {{**resp, "results": [first + 1]}}
            elif isinstance(first, dict) and "value" in first:
                bent = dict(first, value=first["value"] + 1)
                resp = {{**resp, "results": [bent]}}
            elif isinstance(first, list) and first and "count" in first[0]:
                bent = [dict(first[0], count=first[0]["count"] + 1)]
                resp = {{**resp, "results": [bent + list(first[1:])]}}
        return resp
    srv.api.query = query
    return srv, http_

def start_run(self, i):
    # the window's first answer, and every seventh after it, is altered:
    # a window of a few slow requests holds one too
    state["n"] = 0
    real_start_run(self, i)

server.start = start
run.LoadGen.start_run = start_run
args = run.parse(["--workload", {cell!r}, "--seed", "2147483778",
                  "--seconds", "3", "--trace", "0", "--rehearse-cpu"])
result = run.run_cell(args, rehearsal_counts=True)
print(json.dumps({{"correct": result["correct"],
                   "compared": result["compared"]}}))
"""


@pytest.mark.parametrize("cell", _cells())
@pytest.mark.parametrize("fault", ["none", "altered"])
def test_an_altered_answer_turns_correct_false(cell, fault):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-c", FAULT.format(bench=BENCH, fault=fault,
                                            cell=cell)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    wrong = next(r for r in result["compared"] if r["name"] == "wrong")
    if fault == "none":
        assert result["correct"] is True and wrong["value"] == 0
    else:
        assert result["correct"] is False and wrong["value"] > 0
