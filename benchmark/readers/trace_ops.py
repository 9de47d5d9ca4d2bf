"""A named kernel's share of the chip's memory bandwidth, from the
device trace of the traced window.

args: {"ops": "<regular expression>"} the names the kernel stage's
Pallas kernels carry on the device plane's ``XLA Ops`` line (a kernel
is one ``custom-call`` named for its ``pl.pallas_call(name=...)``).
The share is the necessary bytes (harness/bytes_model.py) of the
queries that the device served inside the trace (readers/trace.py
``served_bytes``), over the chip's peak bytes/s, over the union
seconds of the matching operations (harness/trace_reduce.py
``op_seconds``), in %.  It names kernels by the stage's own names, so
a later kernel under the same names is read the same way.  Nothing
matching (a program whose kernels have no names, a cell that runs
none), no peak for the device, or no device-served query gives None,
never 0.
"""

from __future__ import annotations

from harness import server, trace_reduce


def read(ctx: dict, args: dict):
    ops = ctx["trace"]["ops"]
    if not ops or ctx["peaks"] is None:
        return None
    seconds = trace_reduce.op_seconds(ops, args["ops"])
    if seconds <= 0:
        return None
    nbytes = server.load_module("readers", "trace").served_bytes(ctx)
    if not nbytes:
        return None
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
