"""A cell of four chips, on four host devices of the CPU
(``data/able-mesh4.json``: ``able-1b``'s generator at 8 shards).

- ``harness/server.start`` with ``chips`` 4 starts a serving mesh of
  four: after a few point reads every device holds stack bytes, the
  ``ragged_mesh`` program served them, and each answer equals the
  generator's reference;
- ``run.py --rehearse-cpu`` gives JAX a host device for each chip of
  the cell, and a rehearsal of the four-chip cell is correct;
- a server whose serving mesh is not as wide as the cell has chips
  (here the mesh's environment twin, ``PILOSA_TPU_MESH_DEVICES``, set
  as an operator might) ends the run with exit code 2 and no result
  line.

Each case runs in a child process: JAX fixes its device count when it
starts, and this process may have started it with one.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data", "able-mesh4.json")
SEED = 2147483779

LOADED = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path[:0] = [{bench!r}, {root!r}]
from harness import check, pql, schedule, server

with open({data!r}) as f:
    config = json.load(f)
params = config["params"]
server.device(4, rehearse_cpu=True)
srv, http_ = server.start(config, 4)
try:
    from pilosa_tpu import memory
    from pilosa_tpu.memory import placement
    gen = server.load_module("generators", config["generator"])
    tables = server.load(srv, config, gen, {seed}, params["shards"])
    ref = gen.Reference(params, tables)
    traffic = server.load_json("traffic", "points-zipf-solo.json")
    asked = (schedule.warm_sequential(traffic, {seed})
             + schedule.build(traffic, {seed}, schedule.WINDOW, 1.0)[0][:20])
    m0 = http_.metrics()
    wrong = []
    for item in asked:
        call = pql.parse(item["q"])
        if check.canonical(call.name, http_.pql(item["q"])) \
                != ref.answer(call):
            wrong.append(item["q"])
    mesh = 'kind="ragged_mesh"'
    out = {{"width": placement.mesh_devices(),
           "device_bytes": memory.ledger().device_bytes(4),
           "mesh_dispatches": (
               http_.metrics().total("pilosa_serving_dispatch_total", mesh)
               - m0.total("pilosa_serving_dispatch_total", mesh)),
           "asked": len(asked), "wrong": wrong}}
finally:
    http_.close()
    srv.close()
print(json.dumps(out))
"""

RUN = r"""
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
from harness import server

with open({data!r}) as f:
    config = json.load(f)
real_load_json = server.load_json

def load_json(*parts):
    if parts == ("configs", "mesh-test.json"):
        return config
    return real_load_json(*parts)

bench, _cell = run.read_cell("able-1b.points-solo")

def read_cell(name):
    return bench, {{"name": name, "config": "mesh-test",
                   "traffic": "points-zipf-solo", "chips": {chips}}}

server.load_json = load_json
run.read_cell = read_cell
argv = ["--workload", "mesh-test", "--seed", "{seed}", "--seconds", "3",
        "--trace", "0", "--rehearse-cpu"]
if {counts}:
    result = run.run_cell(run.parse(argv), rehearsal_counts=True)
    import jax
    print(json.dumps({{"jax_devices": jax.device_count(),
                      "correct": result["correct"],
                      "device": result["device"],
                      "compared": result["compared"]}}))
else:
    sys.exit(run.main(argv))
"""


def _child(script: str, timeout: float, width: str | None = None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                        "PILOSA_TPU_MESH_DEVICES")}
    if width is not None:
        env["PILOSA_TPU_MESH_DEVICES"] = width
    return subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env=env, timeout=timeout)


def _run(chips: int, counts: bool = False, width: str | None = None):
    return _child(RUN.format(bench=BENCH, root=ROOT, data=DATA, seed=SEED,
                             chips=chips, counts=counts),
                  timeout=600, width=width)


def test_start_serves_from_a_mesh_of_four():
    out = _child(LOADED.format(bench=BENCH, root=ROOT, data=DATA, seed=SEED),
                 timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["width"] == 4
    assert len(got["device_bytes"]) == 4
    assert all(b > 0 for b in got["device_bytes"]), got["device_bytes"]
    assert got["mesh_dispatches"] > 0
    assert got["asked"] >= 35 and got["wrong"] == []


def test_a_rehearsal_of_a_four_chip_cell_has_four_devices():
    out = _run(chips=4, counts=True)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["jax_devices"] == 4
    assert got["device"]["count"] == 4 and got["device"]["platform"] == "cpu"
    assert got["correct"] is True, got["compared"]


@pytest.mark.parametrize("chips,width", [(4, "1"), (4, "2")],
                         ids=["four-chips-served-from-one",
                              "four-chips-served-from-two"])
def test_a_layout_that_is_not_the_cell_s_ends_the_run(chips, width):
    out = _run(chips, width=width)
    assert out.returncode == 2, out.stderr[-3000:]
    assert out.stdout.strip() == ""
    assert (f"the cell has {chips} chips and the server a serving mesh "
            f"of {width}") in out.stderr
