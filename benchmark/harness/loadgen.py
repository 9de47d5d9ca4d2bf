"""The load generator: a child process that imports no JAX.

The parent (the only process on the chip, with the server in it) starts
``python loadgen.py`` and writes one JSON line to its stdin: port,
index, traffic file's contents, seed and the runs to prepare
(``[{"stream": 1, "requests": 200}, {"stream": 0, "seconds": 40}]``: a
run lasts so many seconds, or until each client has sent so many
requests).  The
child draws each run's schedule from the seed, opens one kept-open
connection per client and prints ``{"ready": true}``.  For every later
line ``{"run": i}`` it drives run ``i`` and prints two lines: a summary
(requests, lateness) and the records, one per request: client, place
in the client's schedule, template, send and receive on the wall
clock, HTTP status and the body as received.

Closed loop: a client sends its next request as soon as the last is
answered, until the window's seconds are over; a request sent inside
the window is always waited for.  Client i sends its first request
``start_stagger_s * i`` into the window (a traffic file's parameter;
0 when absent).  The lateness reported is the time
between a client's reply and its next send, which is this process's
own share of every latency.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import schedule  # noqa: E402

REPLY_TIMEOUT_S = 120.0     # a minute past the longest window and more
RUN_LIMIT_S = 300.0         # a run that is counted in requests ends here


class Client(threading.Thread):
    def __init__(self, port: int, path: str, number: int, plan: list,
                 barrier: threading.Barrier, clock: dict):
        super().__init__(daemon=True)
        self.port, self.path, self.number = port, path, number
        self.plan, self.barrier, self.clock = plan, barrier, clock
        self.records: list[dict] = []
        self.exhausted = False
        self.conn = None

    def connect(self):
        self.conn = http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=REPLY_TIMEOUT_S)
        self.conn.connect()

    def ask(self, body: bytes):
        """(status, body text); 0 and the error's name when no reply
        came, with one reconnect where the server closed a kept-open
        connection between requests."""
        for attempt in (0, 1):
            try:
                if self.conn is None:
                    self.connect()
                self.conn.request("POST", self.path, body=body, headers={
                    "Content-Type": "application/json"})
                resp = self.conn.getresponse()
                return resp.status, resp.read().decode("utf-8", "replace")
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError) as e:
                self.conn = None
                if attempt:
                    return 0, type(e).__name__
            except (OSError, http.client.HTTPException) as e:
                self.conn = None
                return 0, type(e).__name__
        return 0, "unreachable"

    def run(self):
        self.barrier.wait()
        end = self.clock["end"]
        time.sleep(self.number * self.clock["stagger"])
        for seq, item in enumerate(self.plan):
            sent = time.time()
            if sent >= end:
                return
            status, text = self.ask(json.dumps({"query": item["q"]}).encode())
            self.records.append({
                "client": self.number, "seq": seq, "t": item["t"],
                "send": sent, "recv": time.time(), "status": status,
                "body": text})
        self.exhausted = True


def drive(port: int, index: str, plans: list, seconds: float,
          stagger: float) -> tuple:
    clock = {"stagger": stagger}
    barrier = threading.Barrier(len(plans) + 1)
    clients = [Client(port, f"/index/{index}/query", i, plan, barrier, clock)
               for i, plan in enumerate(plans)]
    for c in clients:
        c.connect()
    clock["start"] = time.time() + 0.05
    clock["end"] = clock["start"] + seconds
    for c in clients:
        c.start()
    time.sleep(max(0.0, clock["start"] - time.time()))
    barrier.wait()
    for c in clients:
        c.join(seconds + 2 * REPLY_TIMEOUT_S)
    records = [r for c in clients for r in c.records]
    gaps = [b["send"] - a["recv"] for c in clients
            for a, b in zip(c.records, c.records[1:])]
    summary = {
        "start": clock["start"], "end": clock["end"],
        "requests": len(records),
        "exhausted": any(c.exhausted for c in clients)
        and seconds < RUN_LIMIT_S,
        "hung": sum(c.is_alive() for c in clients),
        "lateness_mean_ms": 1e3 * sum(gaps) / len(gaps) if gaps else 0.0,
        "lateness_max_ms": 1e3 * max(gaps) if gaps else 0.0,
    }
    for c in clients:
        if c.conn is not None and not c.is_alive():
            c.conn.close()
    return summary, records


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    plans = []
    for run in spec["runs"]:
        if "requests" in run:
            run["seconds"] = RUN_LIMIT_S
            plans.append(schedule.plans(spec["traffic"], spec["seed"],
                                        run["stream"], run["requests"]))
        else:
            plans.append(schedule.build(spec["traffic"], spec["seed"],
                                        run["stream"], run["seconds"]))
    print(json.dumps({"ready": True}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if "run" not in cmd:
            break
        i = cmd["run"]
        summary, records = drive(
            spec["port"], spec["index"], plans[i], spec["runs"][i]["seconds"],
            float(spec["traffic"].get("start_stagger_s", 0.0)))
        print(json.dumps(summary), flush=True)
        print(json.dumps(records), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
