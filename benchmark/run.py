#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It starts the server an operator gets
(``build_server``, default config, serving plane on, existence
tracked) inside this process, generates and loads the configuration's
index from ``--seed``, warms every program shape the cell's traffic
uses, lets a child process (which imports no JAX) send the seed's
traffic over HTTP for ``--seconds``, checks every response of the
window against the plain reference, prints one JSON line and exits.

The cell's configuration, traffic mix and per-layer metrics are found
by name: ``configs/<config>.json``, ``traffic/<traffic>.json``, every
``layer_metrics/*.json`` that lists the cell (or lists none), each
read by ``readers/<reader>.py``.  A new cell is new files and one
entry in BENCHMARK.json.

Without a TPU the run ends with code 2 and no result.
``--rehearse-cpu`` forces the CPU at the configuration's tiny
rehearsal size, runs the kernels in interpret mode and can never
report ``correct: true``.  ``--control stale-shard`` keeps the last
shard from the server while the reference counts it (the broken
guarantee that ``correct`` has to catch); it is never run by the
driver.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from harness import check, schedule, stats  # noqa: E402

TRACE_SECONDS = 5.0


def note(**line) -> None:
    """Progress and findings, on standard error: standard output
    carries the result line alone."""
    print(json.dumps(line), file=sys.stderr, flush=True)


def read_cell(name: str) -> tuple:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    return bench, cells[name]


def layer_metric_files(cell: str) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if "workloads" not in spec or cell in spec["workloads"]:
            out.append(spec)
    return out


class LoadGen:
    """The child process that sends the traffic (harness/loadgen.py)."""

    def __init__(self, port: int, index: str, traffic: dict, seed: int,
                 runs: list[dict]):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "harness", "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
        self._say({"port": port, "index": index, "traffic": traffic,
                   "seed": seed, "runs": runs})

    def _say(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def _hear(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise SystemExit("benchmark: the load generator died")
        return json.loads(line)

    def wait_ready(self) -> None:
        self._hear()

    def start_run(self, i: int) -> None:
        self._say({"run": i})

    def finish_run(self) -> tuple:
        return self._hear(), self._hear()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self._say({"exit": True})
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


class FlightPoller(threading.Thread):
    """Reads /debug/queries once a second during a traced run: the
    server's ring keeps 512 records, a window may hold thousands."""

    def __init__(self, http_):
        super().__init__(daemon=True)
        self.http, self.seen, self.stop = http_, {}, threading.Event()

    def poll(self) -> None:
        for rec in self.http.flights():
            self.seen[rec["trace_id"]] = rec

    def run(self):
        while not self.stop.wait(1.0):
            self.poll()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU at a tiny size; never correct")
    ap.add_argument("--control", choices=("stale-shard",), default=None,
                    help="break one stated guarantee; must come out "
                         "as not correct")
    return ap.parse_args(argv)


def run_cell(args, rehearsal_counts: bool = False) -> dict:
    """One run; the result line as a dict.  `rehearsal_counts` is for
    the benchmark's own tests alone: it lets a CPU rehearsal's
    `correct` stand as the comparison found it, so that a test can see
    a planted fault turn it false."""
    with contextlib.ExitStack() as cleanup:
        return _run_cell(args, rehearsal_counts, cleanup)


def _run_cell(args, rehearsal_counts: bool, cleanup) -> dict:
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # what a TPU picks by itself: the kernels, here in interpret mode
        os.environ.setdefault("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
        os.environ.setdefault("PILOSA_TPU_GROUPBY_KERNEL", "1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    bench, cell = read_cell(args.workload)
    from harness import server
    config = server.load_json("configs", f"{cell['config']}.json")
    traffic = server.load_json("traffic", f"{cell['traffic']}.json")
    generator = server.load_module("generators", config["generator"])
    layer_specs = layer_metric_files(cell["name"])
    params = config["params"]
    shards = params["rehearsal_shards"] if args.rehearse_cpu \
        else params["shards"]
    warm_n = int(traffic["warmup"].get("concurrent_requests", 0))

    device = server.device(cell["chips"], args.rehearse_cpu)
    server.build_native()
    comp = server.Compiles()
    srv, http_ = server.start(config)
    gen = None
    trace_dir = None
    try:
        gen = LoadGen(srv.port, params["index"], traffic, args.seed, [
            {"stream": schedule.WARMUP, "requests": warm_n},
            {"stream": schedule.WINDOW, "seconds": args.seconds}])
        t0 = time.time()
        skip = frozenset([shards - 1]) if args.control else frozenset()
        tables = server.load(srv, config, generator, args.seed, shards, skip)
        note(phase="load", shards=shards, seconds=round(time.time() - t0, 2),
             control=args.control)
        gen.wait_ready()

        # warm-up: every program shape, alone and then under the loop
        t0 = time.time()
        asked = 0
        if traffic["warmup"].get("sequential"):
            for item in schedule.warm_sequential(traffic, args.seed):
                t1, n1 = time.time(), comp.n
                http_.pql(item["q"])
                asked += 1
                note(phase="warm_query", t=item["t"], q=item["q"][-40:],
                     seconds=round(time.time() - t1, 2),
                     compiles=comp.n - n1)
        if warm_n > 0:
            gen.start_run(0)
            summary, _records = gen.finish_run()
            asked += summary["requests"]
        note(phase="warmup", requests=asked,
             seconds=round(time.time() - t0, 2), compiles=comp.snap())

        # the window
        m0, c0, e0 = http_.metrics(), comp.snap(), len(http_.errors())
        poller = None
        traced = None
        if args.trace:
            import jax
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            cleanup.callback(shutil.rmtree, trace_dir, ignore_errors=True)
            poller = FlightPoller(server.Http(srv.port, params["index"]))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = [time.time(), None]
            poller.start()
        gen.start_run(1)
        if args.trace:
            time.sleep(min(TRACE_SECONDS, args.seconds) + 0.05)
            traced[1] = time.time()
            jax.profiler.stop_trace()
        summary, records = gen.finish_run()
        m1, c1, e1 = http_.metrics(), comp.snap(), http_.errors()
        if poller is not None:
            poller.stop.set()
            poller.join(10)
            poller.poll()
            flights = list(poller.seen.values())
            poller.http.close()
        else:
            flights = http_.flights()    # the ring's newest 512
        flights = [r for r in flights if r["start"] >= summary["start"]]
        # a stall names itself: the server's own record of its longest
        slow = sorted(flights, key=lambda r: -r.get("duration_ms", 0.0))[:3]
        note(phase="slowest_flights", flights=[
            {"ms": r.get("duration_ms"), "route": r.get("route"),
             "at_s": round(r["start"] - summary["start"], 2),
             "phases": r.get("phases"), "q": str(r.get("query"))[:80]}
            for r in slow])
        peak = server.memory_peak_bytes(cell["chips"])
        setup_s = summary["start"] - T_PROCESS
        note(phase="window", **summary)
    finally:
        if gen is not None:
            gen.close()
        http_.close()
        srv.close()

    # the server is closed and the peak is read; now the reference,
    # which is numpy on the host and touches no device
    t0 = time.time()
    reference = generator.Reference(params, tables)
    plans = schedule.build(traffic, args.seed, schedule.WINDOW, args.seconds)
    ok, wrong = check.judge(records, plans, reference)
    worst_ms = 1e3 * (args.seconds + 2 * 60.0)
    lat = stats.latencies_ms(records, ok, worst_ms)
    numbers = {
        "wrong": (sum(1 for r, g in zip(records, ok)
                      if r["status"] == 200 and not g), 0, "max"),
        "failed": (sum(1 for r in records if r["status"] != 200), 0, "max"),
        "never_answered": (summary["hung"], 0, "max"),
        "host_loop": (m1.total("pilosa_stacked_queries_total", 'path="loop"')
                      - m0.total("pilosa_stacked_queries_total",
                                 'path="loop"'), 0, "max"),
        "host_fallback": (
            m1.total("pilosa_device_oom_total", "host_fallback")
            - m0.total("pilosa_device_oom_total", "host_fallback"), 0, "max"),
        "server_errors": (len(e1) - e0, 0, "max"),
        "compared": (sum(1 for r in records if r["status"] == 200), 1, "min"),
    }
    correct, compared = check.verdict(numbers)
    if summary["exhausted"]:
        raise SystemExit("benchmark: a client ran out of schedule; raise "
                         "max_requests_per_client_per_s in the traffic file")
    if not rehearsal_counts and (args.rehearse_cpu
                                 or device["platform"] != "tpu"):
        correct = False     # a rehearsal is never a result
    slowest = sorted(range(len(records)), key=lambda i: -lat[i])[:3]
    note(phase="check", seconds=round(time.time() - t0, 2),
         slowest=[{"t": records[i]["t"], "client": records[i]["client"],
                   "ms": round(lat[i], 1),
                   "sent_at_s": round(records[i]["send"] - summary["start"], 2)}
                  for i in slowest],
         distinct=len({plans[r["client"]][r["seq"]]["q"] for r in records}),
         wrong_examples=wrong,
         server_errors=[str(e)[:600] for e in e1[e0:e0 + 3]])

    values = {
        "qps": stats.completed_per_s(records, ok, summary["start"],
                                     args.seconds),
        "p50_ms": stats.percentile(lat, 50),
        "p95_ms": stats.percentile(lat, 95),
        "setup_s": setup_s,
    }
    device["memory_peak_bytes"] = peak
    metrics, breakdown = {}, None
    if args.trace:
        from harness import trace_reduce
        trace = trace_reduce.reduce_dir(trace_dir, cell["chips"],
                                        traced[1] - traced[0])
        note(phase="trace", planes=trace["planes"][:40])
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        breakdown = trace["breakdown"]
        ctx = {
            "cell": cell, "config": config, "traffic": traffic,
            "shards": shards, "seconds": args.seconds,
            "m0": m0, "m1": m1, "compiles": (c0, c1), "flights": flights,
            "records": records, "ok": ok, "plans": plans, "latencies_ms": lat,
            "trace": trace, "traced_wall": traced, "peak_bytes": peak,
            "peaks": server.load_json("harness", "peaks.json").get(
                device["kind"]),
            "summary": summary,
        }
        for spec in layer_specs:
            reader = server.load_module("readers", spec["reader"])
            value = reader.read(ctx, spec.get("args", {}))
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for m in bench["end_to_end"]:
            if "workloads" not in m or cell["name"] in m["workloads"]:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    result = {
        "correct": correct,
        "attempted": len(records),
        "failed": sum(1 for g in ok if not g),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["end_to_end"] = values
    result["generator"] = {k: summary[k] for k in (
        "lateness_mean_ms", "lateness_max_ms", "requests")}
    result["compiles_in_window"] = c1["n"] - c0["n"]
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    result = run_cell(parse(argv))
    for row in result["compared"]:
        print(f"compared {row['name']}: {row['value']} "
              f"(limit {row['limit']}) {'ok' if row['ok'] else 'NOT OK'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
