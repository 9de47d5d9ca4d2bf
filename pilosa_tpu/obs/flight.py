"""Query flight recorder — always-on per-query phase attribution.

The serving hot path (PRs 2-3) made a query's execution opaque from
the outside: it may be fused into a leader's multi-program, served
from the versioned result cache, satisfied by a patched device stack,
or trigger a jit recompile — and ``/metrics`` aggregates can't say
which happened to WHICH query.  This module keeps a bounded ring of
per-query *flight records* (trace id, route, phase durations, cache
outcomes, batch occupancy, bytes moved) cheap enough to leave on in
production, feeding:

- ``/debug/queries``  — recent records as JSON (server/http.py)
- ``/debug/trace``    — the same records exported as Chrome
  ``trace_event`` JSON, loadable in Perfetto / chrome://tracing
- ``pilosa_query_phase_seconds`` histograms with exemplar trace ids
  (obs/metrics.py)

Attribution flows through thread-local :class:`Acc` accumulators: the
serving layer pushes one per query, and every layer boundary of the
served path is ONE :class:`stage` — the single instrumentation point
that feeds the record's ``phases`` (seconds per name) and ``spans``
(``[name, off_ms, dur_ms, parent, thread]``, offsets from the record's
``Acc.t0``), the ``Profile=true`` span tree (obs/tracing.py) and, for
stages in which a thread WORKS, the profiler's host plane
(``jax.profiler.TraceAnnotation``) — so a device-idle gap in an
``.xplane.pb`` names the stage the host was in.  Stages in
:data:`WAITS` are never annotated: a reader names a gap by the
longest host event over it, and a parked thread would name them all.
Work a batch LEADER performs for a follower is staged into a
per-request Acc on the leader's thread and merged into the follower's
record at commit (executor/serving.py) — the same cross-thread shape
as ``obs.tracing.TraceContext``.  Stages outside begin()..commit() on
a thread with an open :func:`request` (the HTTP handler: body read,
parse, admission, encode, socket write) reach the same record with
offsets before ``start`` or past ``duration_ms``.

What happens BETWEEN the stages of a thread is seen by two hooks that
:func:`install` puts in at server start: each garbage collection, timed
on the thread that triggered it (``phases["gc"]`` of the active record,
``pilosa_gc_pause_seconds_total``, a ``gc`` annotation while a profiler
runs), and each JAX compile, counted on the thread that paid for it
(``pilosa_jit_compiles_total``, the record's ``compiles``).  Every
sample carries the thread's ``path``: ``served`` while it serves a
request, ``audit`` on a thread that marked itself
(:func:`mark_thread`), ``background`` otherwise.  The gc hook also
freezes what a served thread's full collection leaves alive, so the
next one does not walk the long-lived heap again (:func:`_on_gc`,
``pilosa_gc_full_total{path,walk}``).
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager

from pilosa_tpu.obs import tracing as _tr

# the stages in which a thread is PARKED, not working: recorded like
# any other, never annotated into the profiler's host plane.
# ``execute`` waits for the device (its child ``dispatch`` is the
# host's share); ``batch`` is a caller's whole stay in the batcher
# (the leader's work inside it is staged per rider); ``audit.shadow``
# is one whole shadow run (its ``audit.step`` children are the work).
WAITS = frozenset(("admission.wait", "batch", "batch.wait", "stack_wait",
                   "execute", "audit.shadow"))

class _Tls(threading.local):
    # class-level defaults: a thread that never set one reads None by
    # a plain attribute load — getattr(local, missing, None) raises
    # and catches inside, ~0.5 us per stage entry
    acc = None        # the active Acc (or _Fan)
    rec = None        # the open flight record
    env = None        # the open request envelope
    inherit = None    # a remote caller's trace id
    path = None       # what the thread marked itself (mark_thread)


_tls = _Tls()
_get_ident = threading.get_ident
_perf = time.perf_counter


def mark_thread(path: str | None):
    """Name what this thread does for every gc and compile sample it
    leaves (``audit``: the audit plane's workers); None unmarks."""
    _tls.path = path


def _path(tls) -> str:
    """The `path` label of a sample taken on this thread now."""
    p = tls.path
    if p is not None:
        return p
    if tls.acc is not None or tls.env is not None:
        return "served"
    return "background"


class Acc:
    """Per-query phase accumulator (seconds + stack-cache outcomes).
    Plain mutable object — only ever touched by one thread at a time
    (the owning request thread, or the leader while it serves the
    request)."""

    __slots__ = ("phases", "stack", "bytes_moved", "keys", "attempts",
                 "t0", "node_spans", "ops", "pages", "expands", "spans",
                 "cur", "depth", "root_s", "assembled_bytes", "compiles",
                 "walked")

    # per-record stack-key cap: a pathological query touching hundreds
    # of stacks must not bloat the ring
    _MAX_KEYS = 32
    # per-record attempt cap (cluster fan-out: one entry per per-node
    # RPC attempt incl. hedges — a 100-node fan-out must not bloat
    # the ring either)
    _MAX_ATTEMPTS = 32
    # per-record cap on per-node span-tree payloads (cluster trace
    # propagation, ISSUE 10): legs past the cap keep their timings in
    # `attempts` but drop the span detail
    _MAX_NODE_SPANS = 16
    # per-record stage-span cap: a query touching dozens of stacks
    # keeps its first 64 spans; `phases` keeps every second
    _MAX_SPANS = 64
    # per-record cap on the compiles a request paid for
    _MAX_COMPILES = 16

    def __init__(self):
        self.phases: dict[str, float] = {}
        self.stack: dict[str, int] = {}
        self.bytes_moved = 0
        # (key fingerprint, outcome) per NON-HIT stack access — the
        # prefetcher's prediction signal (memory/policy.py): keys
        # that keep rebuilding are keys worth warming
        self.keys: list[tuple[str, str]] = []
        # per-node RPC attempt timings from the cluster fan-out
        # (node, ms, outcome, start-offset ms) incl. hedge attempts —
        # what makes hedge delays debuggable at /debug/queries, and
        # what renders hedges as parallel spans in /debug/trace
        self.attempts: list[tuple[str, float, str, float]] = []
        # this record's perf_counter origin: attempt/node-span offsets
        # are relative to it so /debug/trace can lay legs out in time
        self.t0 = time.perf_counter()
        # per-node serialized span trees returned in RPC trailers
        # (obs.tracing.span_to_wire): [{"node", "anchor_off_us",
        # "spans"}] — the coordinator's one-timeline-with-node-lanes
        # Perfetto view
        self.node_spans: list[dict] = []
        # op-family roofline shares: op -> [bytes touched, execute s]
        # (obs/roofline.py note() feeds this per device dispatch)
        self.ops: dict[str, list] = {}
        # page-encoding mix of the stack operands this query touched
        # (encoding -> page count; memory/encode.py container kinds) —
        # how a record shows which arm served it, packed or dense
        self.pages: dict[str, int] = {}
        # encoded pages handed out dense: "kept" (the block kept from
        # an earlier expansion) or "expanded" (memory/encode.py)
        self.expands: dict[str, int] = {}
        # bytes of the page leaves the ragged programs that served
        # this query were handed (executor/ragged.py)
        self.assembled_bytes = 0
        # stage spans [name, offset, duration, parent, thread]: seconds
        # from t0 here (ms_spans() makes the record's ms), parent an
        # index into this list or -1; `cur` is the
        # innermost OPEN span, `depth` the open stages (capped ones
        # too), `root_s` the seconds of closed depth-0 stages — the
        # non-overlapping total that `wait` and the cache's
        # recompute-cost hint are derived from
        self.spans: list[list] = []
        self.cur = -1
        self.depth = 0
        self.root_s = 0.0
        # [fun_name, ms] per JAX compile this request's thread paid for
        self.compiles: list[list] = []
        # groups the one-pass GroupBy dispatches serving this query
        # accumulated (stacked._count_onepass)
        self.walked = 0

    def open_span(self, name: str, t_start: float):
        """Begin a stage: returns (span index or -1 past the cap, the
        span that was innermost before) for close_span."""
        prev = self.cur
        self.depth += 1
        spans = self.spans
        if len(spans) >= self._MAX_SPANS:
            return -1, prev
        # raw seconds here; ms_spans() rounds once, at commit
        spans.append([name, t_start - self.t0, None, prev,
                      _get_ident()])
        self.cur = len(spans) - 1
        return self.cur, prev

    def close_span(self, tok, name: str, dt: float, keep: bool = True):
        idx, prev = tok
        self.phases[name] = self.phases.get(name, 0.0) + dt
        self.depth -= 1
        if self.depth <= 0:
            self.depth = 0
            self.root_s += dt
        self.cur = prev
        spans = self.spans
        if not 0 <= idx < len(spans):
            return
        if keep:
            sp = spans[idx]
            sp[0] = name
            sp[2] = dt
            return
        # a span-less stage (a stack HIT: one query touches dozens):
        # what it enclosed moves up to its parent
        par = spans[idx][3]
        del spans[idx]
        for sp in spans[idx:]:
            if sp[3] == idx:
                sp[3] = par
            elif sp[3] > idx:
                sp[3] -= 1

    def add_span(self, name: str, t_start: float, dt: float,
                 thread: int | None = None):
        """A CLOSED interval (no stage was open over it: an envelope
        stage adopted by begin(), a hand-timed host arm)."""
        if len(self.spans) < self._MAX_SPANS:
            self.spans.append([
                name, t_start - self.t0, dt, self.cur,
                _get_ident() if thread is None else thread])

    def ms_spans(self, duration_s: float) -> list:
        """The record's `spans`: [name, off_ms, dur_ms, parent,
        thread].  A stage an exception left open ends with the
        record."""
        return [[n, round(off * 1e3, 4),
                 round((max(duration_s - off, 0.0) if dur is None
                        else dur) * 1e3, 4), par, thr]
                for n, off, dur, par, thr in self.spans]

    def add_pages(self, mix: dict):
        for k, v in mix.items():
            self.pages[k] = self.pages.get(k, 0) + int(v)

    def add_expand(self, outcome: str):
        self.expands[outcome] = self.expands.get(outcome, 0) + 1

    def add_phase(self, name: str, dt: float):
        """Seconds for `name` with no stage open over them (hand-timed
        host arms): a closed span ending now."""
        self._sum(name, dt)
        self.add_span(name, _perf() - dt, dt)

    def _sum(self, name: str, dt: float):
        """Seconds no stage closed: into `phases`, and into the root
        total where no stage is open around them."""
        self.phases[name] = self.phases.get(name, 0.0) + dt
        if self.depth == 0:
            self.root_s += dt

    def add_stack(self, outcome: str, nbytes: int,
                  key_fp: str | None = None, dt: float | None = None):
        """One stack-cache access's outcome and bytes.  `dt` only
        where no stage timed the access (the probe fast path's hits):
        a sum in `phases`, no span."""
        self.stack[outcome] = self.stack.get(outcome, 0) + 1
        self.bytes_moved += int(nbytes)
        if dt is not None:
            self._sum("stack_" + outcome, dt)
        if key_fp is not None and len(self.keys) < self._MAX_KEYS:
            self.keys.append((key_fp, outcome))

    def add_attempt(self, node: str, dt: float, outcome: str):
        if len(self.attempts) < self._MAX_ATTEMPTS:
            off = max(time.perf_counter() - self.t0 - dt, 0.0)
            self.attempts.append((node, round(dt * 1e3, 3), outcome,
                                  round(off * 1e3, 3)))

    def add_node_spans(self, node: str, spans: list,
                       anchor_perf: float):
        if spans and len(self.node_spans) < self._MAX_NODE_SPANS:
            self.node_spans.append({
                "node": node,
                "anchor_off_us": max(
                    int((anchor_perf - self.t0) * 1e6), 0),
                "spans": spans,
            })

    def add_compile(self, fun_name: str, secs: float):
        if len(self.compiles) < self._MAX_COMPILES:
            self.compiles.append([fun_name, round(secs * 1e3, 4)])

    def add_walked(self, n: int):
        self.walked += int(n)

    def add_gc(self, dt: float):
        """A collection's pause: a sum in `phases`, no span (one
        request can trigger dozens; the span cap keeps the stages)."""
        self.phases["gc"] = self.phases.get("gc", 0.0) + dt

    def add_op(self, op: str, nbytes: int, dt: float):
        st = self.ops.get(op)
        if st is None:
            st = self.ops[op] = [0, 0.0]
        st[0] += int(nbytes)
        st[1] += dt

    def merge(self, other: "Acc"):
        """Fold a leader-side per-request Acc into the record's own:
        its spans keep the leader's thread, move onto this record's
        origin, and hang under the `batch` stage they ran inside."""
        for k, v in other.phases.items():
            self.phases[k] = self.phases.get(k, 0.0) + v
        self.root_s += other.root_s
        base = len(self.spans)
        room = self._MAX_SPANS - base
        if room > 0 and other.spans:
            shift = other.t0 - self.t0
            under = next((i for i in range(base - 1, -1, -1)
                          if self.spans[i][0] == "batch"), -1)
            for name, off, dur, par, thr in other.spans[:room]:
                self.spans.append([
                    name, off + shift, dur,
                    under if par < 0 else par + base, thr])
        for k, v in other.stack.items():
            self.stack[k] = self.stack.get(k, 0) + v
        self.bytes_moved += other.bytes_moved
        room = self._MAX_KEYS - len(self.keys)
        if room > 0 and other.keys:
            self.keys.extend(other.keys[:room])
        room = self._MAX_ATTEMPTS - len(self.attempts)
        if room > 0 and other.attempts:
            self.attempts.extend(other.attempts[:room])
        room = self._MAX_NODE_SPANS - len(self.node_spans)
        if room > 0 and other.node_spans:
            self.node_spans.extend(other.node_spans[:room])
        for op, (b, s) in other.ops.items():
            st = self.ops.get(op)
            if st is None:
                self.ops[op] = [b, s]
            else:
                st[0] += b
                st[1] += s
        self.add_pages(other.pages)
        for k, v in other.expands.items():
            self.expands[k] = self.expands.get(k, 0) + v
        self.assembled_bytes += other.assembled_bytes
        self.walked += other.walked
        room = self._MAX_COMPILES - len(self.compiles)
        if room > 0 and other.compiles:
            self.compiles.extend(other.compiles[:room])


def push_acc(acc: Acc):
    """Install `acc` as this thread's active accumulator; returns the
    previous one to restore via pop_acc."""
    prev = getattr(_tls, "acc", None)
    _tls.acc = acc
    return prev


def pop_acc(prev):
    _tls.acc = prev


def active_acc() -> Acc | None:
    return getattr(_tls, "acc", None)


def note_phase(name: str, dt: float):
    """`dt` seconds of `name` that ended now, where no :class:`stage`
    was open over them (the hand-timed host arms of the stacked
    engine)."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_phase(name, dt)


class _Fan:
    """Several riders' Accs behind the one-Acc stage interface: an
    interval a batch leader measures once for N riders is one stage
    (one timer, one annotation) recorded into every rider's Acc."""

    __slots__ = ("accs",)

    def __init__(self, accs):
        self.accs = accs

    def open_span(self, name, t_start):
        return [a.open_span(name, t_start) for a in self.accs]

    def close_span(self, toks, name, dt, keep=True):
        for a, tok in zip(self.accs, toks):
            a.close_span(tok, name, dt, keep)

    def add_phase(self, name, dt):
        for a in self.accs:
            a.add_phase(name, dt)

    def add_stack(self, outcome, nbytes, key_fp=None, dt=None):
        for a in self.accs:
            a.add_stack(outcome, nbytes, key_fp, dt)

    def add_pages(self, mix):
        for a in self.accs:
            a.add_pages(mix)

    def add_expand(self, outcome):
        for a in self.accs:
            a.add_expand(outcome)

    def add_op(self, op, nbytes, dt):
        for a in self.accs:
            a.add_op(op, nbytes, dt)

    def add_compile(self, fun_name, secs):
        for a in self.accs:
            a.add_compile(fun_name, secs)

    def add_gc(self, dt):
        for a in self.accs:
            a.add_gc(dt)

    def add_walked(self, n):
        for a in self.accs:
            a.add_walked(n)


_annotation = None


def _trace_annotation():
    """jax.profiler.TraceAnnotation, imported at first use (this
    module is imported by processes that never touch JAX)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def annotate(name: str):
    """The profiler's host-plane annotation ALONE, for work whose
    stage is only named when it ends (a stack access is a patch or a
    rebuild once it is done: the stage records it, this names the
    work while it runs).  A null context while no profiler runs."""
    ann = _trace_annotation()
    return ann(name) if ann.is_enabled() else _NULL


_NULL = contextlib.nullcontext()
_THREAD = object()      # stage(ctx=): "this thread's own tracer"


class stage:
    """THE instrumentation point: one named interval of the served
    path, timed once.  For the thread's active :class:`Acc` it adds
    the seconds to ``phases[name]`` and a span with its offset, parent
    and thread; under a recording tracer (``Profile=true``, the
    long-query log) it is the ``obs.tracing`` span of the same name;
    and unless `name` is one of :data:`WAITS` it is a
    ``TraceAnnotation`` on the profiler's host plane while a profiler
    runs.  With no Acc but an open :func:`request` on the thread the
    span is kept for the record that request opens or has committed.

    ``accs``: the rider Acc(s) a batch leader works for — installed as
    the thread's active accumulator for the stage's duration (what
    push_acc/pop_acc did), several of them behind one fan-out.
    ``ctx``: the rider trace context(s) to record the tracing span
    into (``span_into``; None silences the borrowed thread); left out,
    the thread's own tracer.  ``tags`` go to the tracing span.

    The body may rename the stage before it ends (``st.name``: a stack
    access learns its outcome last) and drop its span
    (``st.keep = False``); ``st.seconds`` holds the duration after."""

    __slots__ = ("name", "keep", "seconds", "span", "_accs", "_ctx",
                 "_tags", "_target", "_tok", "_prev", "_t0", "_ann",
                 "_tcm")

    def __init__(self, name: str, accs=None, ctx=_THREAD, **tags):
        self.name = name
        self.keep = True
        self.span = None
        self._accs = accs
        self._ctx = ctx
        self._tags = tags

    def __enter__(self):
        # the hot path: ~1 us with nothing tracing or profiling — two
        # thread-local reads, one clock read, one list append
        tls = _tls
        name = self.name
        accs = self._accs
        if accs is None:
            target = tls.acc
        else:
            self._prev = tls.acc
            target = accs[0] if len(accs) == 1 else _Fan(accs)
            tls.acc = target
        self._target = target
        tcm = None
        ctx = self._ctx
        if ctx is _THREAD:
            # join this thread's open span tree, never root one
            t = _tr.recording_tracer(nested=True)
            if t is not None:
                tcm = t.span(name, **self._tags)
        else:
            if ctx.__class__ is list and not any(ctx):
                ctx = None      # a batch with no traced rider
            if ctx is not None or _tr.recording_tracer() is not None:
                tcm = _tr.span_into(ctx, name, **self._tags)
        self._tcm = tcm
        if tcm is not None:
            self.span = tcm.__enter__()
        ann = None
        if name not in WAITS:
            cls = _annotation or _trace_annotation()
            if cls.is_enabled():
                ann = cls(name)
                ann.__enter__()
        self._ann = ann
        self._t0 = t0 = _perf()
        if target is not None:
            self._tok = target.open_span(name, t0)
        return self

    def __exit__(self, et, ev, tb):
        self.seconds = dt = _perf() - self._t0
        target = self._target
        if target is not None:
            target.close_span(self._tok, self.name, dt, self.keep)
        else:
            env = _tls.env
            if env is not None:
                env.pending.append((self.name, self._t0, dt,
                                    _get_ident()))
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        if self._tcm is not None:
            if self.span.name != self.name:
                self.span.name = self.name
            self._tcm.__exit__(et, ev, tb)
        if self._accs is not None:
            _tls.acc = self._prev
        return False


class _Request:
    """One served request on this thread, from its first byte read to
    its last byte written: where the stages outside the flight
    record's begin()..commit() wait for the record."""

    __slots__ = ("t0", "pending", "rec", "rec_t0")

    def __init__(self):
        self.t0 = _perf()
        self.pending: list[tuple] = []
        self.rec = None
        self.rec_t0 = 0.0


class request:
    """Open the request envelope on this thread (the HTTP handler,
    around read → dispatch → write; ServingLayer.execute for callers
    with no transport in front).  Re-entrant: an inner one joins the
    open one.  On exit the stages that ran after the record's commit
    (``result.encode``, ``http.write``) are appended to that record
    and ``request_ms`` — entry to last byte — is set; a reader that
    saw the record in between saw a valid record without its tail."""

    __slots__ = ("_env",)

    def __enter__(self):
        self._env = None
        if recorder.enabled and _tls.env is None:
            self._env = _tls.env = _Request()
        return self

    def __exit__(self, et, ev, tb):
        env = self._env
        if env is None:
            return False
        _tls.env = None
        rec = env.rec
        if rec is not None:
            done = _perf()
            t0 = env.rec_t0
            spans = rec["spans"]
            room = Acc._MAX_SPANS - len(spans)
            if env.pending and room > 0:
                rec["spans"] = spans + [
                    [n, round((s - t0) * 1e3, 4), round(d * 1e3, 4),
                     -1, thr] for n, s, d, thr in env.pending[:room]]
            rec["request_ms"] = round((done - env.t0) * 1e3, 4)
        return False


def note_route(route: str, cap: int = 32):
    """Record a nested serving-path route (fused/cached/direct) into
    the thread's ACTIVE record's ``serving_routes`` list — how a SQL
    statement record (route "sql") shows which of its inner PQL
    dispatches rode the fused plane.  No-op without an open record;
    capped so a many-call statement cannot grow a record without
    bound."""
    rec = getattr(_tls, "rec", None)
    if rec is not None:
        routes = rec.setdefault("serving_routes", [])
        if len(routes) < cap:
            routes.append(route)


def note_stack(outcome: str, nbytes: int, key_fp: str | None = None,
               dt: float | None = None):
    """One stack-cache access's outcome and bytes moved.  The access's
    time comes from the stage around it; `dt` is for the probe fast
    path, whose hits are a sum and a count with no span."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_stack(outcome, nbytes, key_fp, dt)


def note_attempt(node: str, dt: float, outcome: str):
    """Record one cluster per-node RPC attempt (incl. hedges) into
    the active record's ``attempts`` field."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_attempt(node, dt, outcome)


def note_node_spans(node: str, spans: list, anchor_perf: float):
    """Record a remote (or local-leg) serialized span tree returned
    in an RPC trailer, anchored at the caller-clock instant the
    attempt left (cluster/coordinator.py)."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_node_spans(node, spans, anchor_perf)


def note_pages(mix: dict):
    """Record the page-encoding mix of one stack operand fetch
    (executor/stacked.py _assemble) into the active record."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_pages(mix)


def note_expand(outcome: str):
    """Record one encoded page handed out dense, kept or expanded
    (memory/encode.py EncodedPage.dense), into the active record."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_expand(outcome)


def note_walked(n: int):
    """Groups a one-pass GroupBy dispatch accumulated, into the active
    record."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_walked(n)


def note_reply_groups(n: int):
    """The groups of a GroupBy reply, into the record this request
    committed (the encode runs after the commit)."""
    env = getattr(_tls, "env", None)
    rec = env.rec if env is not None else None
    if rec is not None:
        rec["reply_groups"] = rec.get("reply_groups", 0) + int(n)


def note_op(op: str, nbytes: int, dt: float):
    """Record one device dispatch's roofline share (bytes touched +
    execute seconds) by op family (obs/roofline.py calls this)."""
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.add_op(op, nbytes, dt)


def inherit_trace(trace_id: str | None):
    """Adopt a REMOTE caller's trace id for the next record this
    thread opens (RPC trace propagation: the X-Pilosa-Trace-Id header
    / gRPC trace-id metadata land here, so a remote leg's flight
    record joins the coordinator's under one cluster-wide id).
    Returns the previous value to restore via pop_inherit."""
    prev = getattr(_tls, "inherit", None)
    _tls.inherit = trace_id
    return prev


def pop_inherit(prev):
    _tls.inherit = prev


def current_trace_id() -> str | None:
    """The trace id of this thread's active (or inherited) flight
    record, or None — the log-correlation stamp (obs/logger.py)."""
    rec = getattr(_tls, "rec", None)
    if rec is not None:
        return rec["trace_id"]
    return getattr(_tls, "inherit", None)


@contextmanager
def remote_leg(trace_id: str, keep: int = 8):
    """The remote-leg scaffold every trace-propagating RPC surface
    shares (server/http.py, cluster/coordinator.py's local leg, the
    overhead probe): inherit the caller's trace id so this thread's
    flight record joins it, record the leg's spans on a thread-local
    tracer, and on exit serialize the roots to wire form.  Yields
    ``(tracer, spans)`` — ``spans`` fills AFTER the body exits (wire
    dicts for the response trailer); ``tracer.roots`` keeps the live
    Span objects for callers that need absolute anchors.  One
    implementation so a fix to the pop-ordering or wire shape cannot
    drift between surfaces."""
    spans: list[dict] = []
    prev_inh = inherit_trace(trace_id)
    tracer = _tr.RecordingTracer(keep=keep)
    prev = _tr.push_thread_tracer(tracer)
    try:
        yield tracer, spans
    finally:
        _tr.pop_thread_tracer(prev)
        pop_inherit(prev_inh)
        spans.extend(_tr.span_to_wire(s) for s in tracer.roots)


class FlightRecorder:
    """Bounded ring of finished per-query flight records."""

    def __init__(self, keep: int = 512, enabled: bool | None = None):
        if enabled is None:
            enabled = os.environ.get("PILOSA_TPU_FLIGHT", "1") != "0"
        self.enabled = enabled
        self._ring: deque[dict] = deque(maxlen=keep)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()

    def configure(self, enabled: bool | None = None,
                  keep: int | None = None):
        """Apply config knobs ([flight] in config.py).  Resizing
        keeps the newest records."""
        if enabled is not None:
            self.enabled = bool(enabled)
        if keep is not None and keep != self._ring.maxlen:
            with self._lock:
                self._ring = deque(self._ring, maxlen=int(keep))

    def next_id(self) -> str:
        return f"q{next(self._ids):x}"  # itertools.count: atomic

    def record(self, rec: dict):
        # LOCK-FREE hot path: deque.append with maxlen is atomic under
        # the GIL, and a contended threading.Lock costs ~20us of GIL
        # ping-pong per acquisition — measured to dominate the whole
        # recorder at serving qps.  Readers snapshot with retry.
        self._ring.append(rec)

    def recent(self, n: int = 100) -> list[dict]:
        """Newest-first records (the /debug/queries payload)."""
        while True:
            try:
                items = list(self._ring)
                break
            except RuntimeError:
                continue  # deque mutated mid-iteration: retry
        return list(reversed(items))[: max(0, int(n))]

    def clear(self):
        self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)

    # -- Chrome trace_event export -------------------------------------

    def chrome_trace(self, n: int = 100) -> dict:
        """Recent records as the Chrome ``trace_event`` JSON object
        format (loadable in Perfetto / chrome://tracing): one complete
        ("ph": "X") event per query plus one per stage span at its
        recorded offset, on per-query virtual threads so concurrent
        queries render as parallel tracks.  Cluster fan-out records
        additionally render one PROCESS LANE per node (``pid`` + a process_name metadata
        event): per-node RPC attempts — hedges as parallel spans —
        and the span trees each node returned in its response
        trailer, all under the query's one trace id."""
        events = []
        # pid 1 is the serving process itself; cluster legs get one
        # pid per node so Perfetto renders per-node lanes
        node_pids: dict[str, int] = {}

        def pid_for(node: str) -> int:
            p = node_pids.get(node)
            if p is None:
                p = node_pids[node] = len(node_pids) + 2
                events.append({"name": "process_name", "ph": "M",
                               "pid": p,
                               "args": {"name": f"node:{node}"}})
            return p

        for rec in self.recent(n):
            ts = rec["start"] * 1e6          # epoch microseconds
            dur = rec["duration_ms"] * 1e3
            tid = rec["trace_id"]
            args = {"index": rec.get("index", ""),
                    "query": rec.get("query", ""),
                    "route": rec.get("route", ""),
                    "batch": rec.get("batch", 1)}
            if rec.get("stack"):
                args["stack"] = rec["stack"]
            if rec.get("bytes_moved"):
                args["bytes_moved"] = rec["bytes_moved"]
            if rec.get("phases"):
                # the sums, stack hits and the derived wait included,
                # which have no span of their own
                args["phases"] = rec["phases"]
            events.append({
                "name": f"query:{rec.get('route', '?')}",
                "cat": "query", "ph": "X", "pid": 1, "tid": tid,
                "ts": ts, "dur": max(dur, 1.0), "args": args,
            })
            # stages sit where they were recorded: offsets from the
            # record's start (negative for what the request did before
            # its record opened), one track per thread under the query
            # — the request's own thread shares the query's track, a
            # batch leader working for it gets its own
            own = None
            for name, off_ms, dur_ms, parent, thread in rec.get(
                    "spans", ()):
                if own is None:
                    own = thread
                events.append({
                    "name": name, "cat": "stage", "ph": "X", "pid": 1,
                    "tid": tid if thread == own else f"{tid}/{thread}",
                    "ts": ts + off_ms * 1e3,
                    "dur": max((dur_ms or 0.0) * 1e3, 0.5),
                    "args": {"ms": dur_ms, "parent": parent},
                })
            # cluster fan-out: per-node attempt slices (true start
            # offsets — a hedge renders in parallel with the primary
            # attempt it raced) ...
            for a in rec.get("attempts", ()):
                events.append({
                    "name": f"attempt:{a.get('outcome', '?')}",
                    "cat": "attempt", "ph": "X",
                    "pid": pid_for(str(a.get("node", "?"))),
                    "tid": tid,
                    "ts": ts + a.get("t_off_ms", 0.0) * 1e3,
                    "dur": max(a.get("ms", 0.0) * 1e3, 0.5),
                    "args": {"trace_id": tid,
                             "node": a.get("node"),
                             "outcome": a.get("outcome")},
                })
            # ... and the span trees each leg returned in its
            # response trailer, re-anchored on the coordinator clock
            for ent in rec.get("node_spans", ()):
                pid = pid_for(str(ent.get("node", "?")))
                base = ts + ent.get("anchor_off_us", 0)
                stack = list(ent.get("spans", ()))
                while stack:
                    w = stack.pop()
                    ev = {"name": str(w.get("name", "span")),
                          "cat": "node", "ph": "X", "pid": pid,
                          "tid": tid,
                          "ts": base + w.get("off_us", 0),
                          "dur": max(w.get("dur_us", 0), 0.5),
                          "args": {"trace_id": tid,
                                   "node": ent.get("node")}}
                    if w.get("tags"):
                        ev["args"]["tags"] = w["tags"]
                    events.append(ev)
                    stack.extend(w.get("children", ()))
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"source": "pilosa-tpu flight recorder"}}

    def chrome_trace_json(self, n: int = 100) -> str:
        return json.dumps(self.chrome_trace(n))


# process-global recorder (the /debug surface and metrics exemplars
# read this one); config.apply_flight_settings() reconfigures it
recorder = FlightRecorder()


def begin(index: str, query) -> dict | None:
    """Open a flight record for this thread's query, or None when the
    recorder is off or a record is already active (nested execute calls
    — e.g. the serving layer's direct fallback re-entering
    Executor.execute — must not double-record)."""
    if not recorder.enabled or getattr(_tls, "rec", None) is not None:
        return None
    inherited = getattr(_tls, "inherit", None)
    rec = {
        "trace_id": inherited or recorder.next_id(),
        "index": index,
        "query": str(query)[:200],
        "start": time.time(),
        "acc": Acc(),
    }
    if inherited:
        # a remote leg of a cluster fan-out: same id as the
        # coordinator's record so /debug/cluster/queries merges them
        rec["inherited"] = True
    _tls.rec = rec
    rec["prev_acc"] = push_acc(rec["acc"])
    env = getattr(_tls, "env", None)
    if env is not None and env.pending:
        # what the request did before its record opened (body read,
        # parse, admission): offsets before `start`, so negative
        acc = rec["acc"]
        for name, t_start, dt, thr in env.pending:
            acc.add_span(name, t_start, dt, thr)
        env.pending.clear()
    return rec


def commit(rec: dict | None, duration_s: float, route: str = "solo",
           batch: int = 1, error: str | None = None,
           fingerprint: str | None = None,
           extra_acc: Acc | None = None):
    """Finish and ring-buffer a record opened by begin(); exports the
    per-phase histograms with this record's trace id as exemplar."""
    if rec is None:
        return
    acc: Acc = rec.pop("acc")
    pop_acc(rec.pop("prev_acc"))
    _tls.rec = None
    if extra_acc is not None:
        acc.merge(extra_acc)
    # wait = time parked in the batcher not accounted to a device
    # phase (admission window + other requests' share of the batch).
    # Derived INTO acc.phases so it reaches the phase histogram, not
    # just the record dict.  Stages nest (a plan_build holds its stack
    # fetches, an execute its dispatch), so what is accounted is the
    # seconds of the outermost stages, not the sum of the phases.
    if "batch" in acc.phases:
        accounted = (acc.root_s - acc.phases["batch"]
                     - acc.phases.get("cache_lookup", 0.0))
        acc.phases["wait"] = max(acc.phases["batch"] - accounted, 0.0)
    phases = {k: round(v * 1e3, 4) for k, v in acc.phases.items()}
    env = getattr(_tls, "env", None)
    if env is not None:
        env.rec, env.rec_t0 = rec, acc.t0
    rec.update({
        "duration_ms": round(duration_s * 1e3, 4),
        "route": route,
        "batch": int(batch),
        "phases": phases,
        # every stage of this request at its offset from `start`:
        # [name, off_ms, dur_ms, parent index or -1, thread]
        "spans": acc.ms_spans(duration_s),
        "stack": dict(acc.stack),
        "bytes_moved": acc.bytes_moved,
        # non-hit stack-key fingerprints feeding the prefetcher's
        # prediction scan (memory/policy.py Prefetcher.step)
        "stack_keys": list(acc.keys),
    })
    if acc.attempts:
        # per-node cluster attempt timings (hedges included) — only
        # fan-out queries carry the field, so solo records stay small.
        # t_off_ms = start offset inside the query, so /debug/trace
        # renders hedges as genuinely PARALLEL spans
        rec["attempts"] = [
            {"node": n, "ms": ms, "outcome": o, "t_off_ms": off}
            for n, ms, o, off in acc.attempts]
    if acc.node_spans:
        # per-node span trees from RPC trailers (+ the local leg) —
        # the /debug/trace node lanes
        rec["node_spans"] = list(acc.node_spans)
    if acc.pages:
        # page-encoding mix of the stack operands touched (sparse
        # device format, memory/encode.py): packed vs dense served
        rec["page_mix"] = dict(acc.pages)
    if acc.expands:
        # how its encoded pages were made dense: kept vs expanded
        rec["expand"] = dict(acc.expands)
    if acc.assembled_bytes:
        rec["assembled_bytes"] = acc.assembled_bytes
    if acc.walked:
        # beside `reply_groups`, which the reply's encode adds
        rec["walked_groups"] = acc.walked
    if acc.compiles:
        # the programs this request waited for a compile of
        rec["compiles"] = list(acc.compiles)
    if acc.ops:
        # roofline share: bytes touched / execute time per op family,
        # with achieved GB/s (+ fraction once the peak probe landed)
        from pilosa_tpu.obs import roofline
        peak = roofline.peak_or_none()
        rl = {}
        for op, (b, s) in acc.ops.items():
            if s <= 0:
                continue
            ent = {"bytes": b, "ms": round(s * 1e3, 4),
                   "gbps": round(b / s / 1e9, 4)}
            if peak:
                ent["fraction"] = round((b / s) / peak, 5)
            rl[op] = ent
        if rl:
            rec["roofline"] = rl
    if error is not None:
        rec["error"] = error[:200]
    if fingerprint is not None:
        rec["fingerprint"] = fingerprint
    recorder.record(rec)
    _buffer_phase_samples(acc, rec["trace_id"])
    # statistics catalog (obs/stats.py): one enabled check + a
    # lock-free pending append; profile folding is amortized off the
    # hot path (same budget class as the phase-sample buffer above)
    from pilosa_tpu.obs import stats as _stats
    _stats.note_flight(rec)


# -- buffered export: the phase histogram and the gc counters ---------------
# A contended threading.Lock costs ~20us of GIL ping-pong per
# acquisition; observing every phase of every query directly into the
# shared histogram would convoy the serving threads.  Samples append
# to GLOBAL lock-free pending lists (list.append is GIL-atomic) and
# drain in one flush_metrics() every _FLUSH_N phase samples —
# amortizing the histogram lock ~64x — and on /metrics render, so a
# scrape always sees current samples.  Not per-thread:
# ThreadingHTTPServer spawns a thread per connection, and thread-local
# buffers would die (samples and all) with their threads.  A flush
# copies, then deletes, the prefix it folds (appends land past it), so
# no sample is lost.  A collection may start inside any allocation, a
# counter's lock held too, so the gc hook only appends.

_FLUSH_N = 64
_pending: list = []           # (phase s, labels, trace id)
_gc_pending: list = []        # (generation, path, pause s, walk)
_flush_lock = threading.Lock()


def _take(buf: list) -> list:
    n = len(buf)
    out = buf[:n]
    del buf[:n]
    return out


def flush_metrics():
    """Drain the pending phase samples into the shared
    pilosa_query_phase_seconds histogram and the pending collections
    into the gc counters."""
    with _flush_lock:
        phases = _take(_pending)
        gcs = _take(_gc_pending)
    if not (phases or gcs):
        return
    from pilosa_tpu.obs import metrics
    if phases:
        metrics.PHASE_DURATION.observe_batch(phases)
    pause: dict[tuple, list] = {}
    full: dict[tuple, int] = {}
    for gen, path, dt, walk in gcs:
        acc = pause.get((gen, path))
        if acc is None:
            acc = pause[(gen, path)] = [0.0, 0]
        acc[0] += dt
        acc[1] += 1
        if walk is not None:
            full[(path, walk)] = full.get((path, walk), 0) + 1
    for (gen, path), (secs, n) in pause.items():
        metrics.GC_PAUSE_SECONDS.inc(secs, generation=str(gen), path=path)
        metrics.GC_COLLECTIONS.inc(n, generation=str(gen), path=path)
    for (path, walk), n in full.items():
        metrics.GC_FULL.inc(n, path=path, walk=walk)


def _buffer_phase_samples(acc: Acc, trace_id: str):
    pend = _pending
    for name, dt in acc.phases.items():
        pend.append((dt, {"phase": name}, trace_id))
    if len(pend) >= _FLUSH_N:
        flush_metrics()


# -- between the stages: collections and compiles ----------------------------

_gc_t0 = 0.0        # collections never overlap: one start at a time
_gc_ann = None
_gc_walk = None     # "tenured" / "whole": the full collection under way
_frozen = False     # survivors of a full collection are frozen
_whole_blocks = 0   # sys.getallocatedblocks() after the last whole walk
# the paths whose full collections leave their survivors frozen
_TENURE_PATHS = frozenset(("served", "audit"))


def _on_gc(phase: str, info: dict):
    """gc.callbacks hook: a collection timed on the thread that
    triggered it — into the active record's ``phases["gc"]``, the gc
    counters, and a ``gc`` annotation while a profiler runs.

    It also decides what a full (generation-2) collection walks.  One
    on a thread serving a request (or on the audit plane's) leaves its
    survivors frozen (``gc.freeze()`` at ``stop``: a splice of lists),
    so the next walks only what was promoted since — the ``tenured``
    walk.  Elsewhere —
    set-up, the load's pool threads, an explicit ``gc.collect()``
    outside a request — and wherever the heap has doubled since the
    last ``whole`` walk, ``gc.unfreeze()`` at ``start`` gives the
    collection the whole heap: frozen cyclic garbage is reclaimed
    there, and can never hold more than the live heap did.  A full
    collection with nothing frozen is a whole walk too."""
    global _gc_t0, _gc_ann, _gc_walk, _frozen, _whole_blocks
    if phase == "start":
        if info.get("generation") == 2:
            if (_frozen and _path(_tls) in _TENURE_PATHS
                    and sys.getallocatedblocks() < 2 * _whole_blocks):
                _gc_walk = "tenured"
            else:
                gc.unfreeze()
                _frozen = False
                _gc_walk = "whole"
        ann = _annotation
        if ann is not None and ann.is_enabled():
            _gc_ann = ann("gc")
            _gc_ann.__enter__()
        _gc_t0 = _perf()
        return
    dt = _perf() - _gc_t0
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    tls = _tls
    acc = tls.acc
    if acc is not None:
        acc.add_gc(dt)
    gen, path, walk = info.get("generation", -1), _path(tls), None
    if gen == 2:
        walk, _gc_walk = _gc_walk, None
        if walk == "whole":
            _whole_blocks = sys.getallocatedblocks()
        if path in _TENURE_PATHS:
            gc.freeze()
            _frozen = True
    _gc_pending.append((gen, path, dt, walk))


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_compile(event: str, secs: float, **kw):
    """jax.monitoring duration listener: every backend compile (a
    persistent-cache load included), counted on the thread that
    compiled, and named in that request's record."""
    if event != _COMPILE_EVENT:
        return
    from pilosa_tpu.obs import metrics
    tls = _tls
    path = _path(tls)
    metrics.JIT_COMPILES.inc(event="compile", path=path)
    metrics.JIT_COMPILE_SECONDS.inc(secs, path=path)
    acc = tls.acc
    if acc is not None:
        acc.add_compile(str(kw.get("fun_name", "?")), secs)


def _on_jax_event(event: str, **_kw):
    """jax.monitoring event listener: the compiles the persistent
    cache served (a subset of ``event="compile"``)."""
    if event == _CACHE_HIT_EVENT:
        from pilosa_tpu.obs import metrics
        metrics.JIT_COMPILES.inc(event="cache_hit", path=_path(_tls))


_installed = False
_install_lock = threading.Lock()


def install():
    """Put in the between-stages hooks, once a process (server start):
    the ``gc.callbacks`` hook and the two ``jax.monitoring``
    listeners."""
    global _installed
    with _install_lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring as mon
    _trace_annotation()
    gc.callbacks.append(_on_gc)
    mon.register_event_duration_secs_listener(_on_compile)
    mon.register_event_listener(_on_jax_event)
