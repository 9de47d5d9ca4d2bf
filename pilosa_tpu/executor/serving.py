"""Cross-query dispatch coalescing — the serving path.

The committed TPU record shows the engine's scans are bandwidth-bound
(~88% of v5e HBM peak) while WALL time is dispatch-bound: ~75 ms wall
vs ~0.35 ms device for Count at 954 shards, one device dispatch per
query.  Under concurrent load the per-query path therefore pays one
full dispatch/RTT per request.  This module amortizes that cost the
way TPU inference serving does (continuous batching, cf. Ragged Paged
Attention in PAPERS.md):

- ``QueryBatcher`` — concurrent in-flight queries over the same index
  are admitted for a short window (default 1 ms, or until
  ``max_batch``), their plans fused into ONE jitted program over a
  shared tile-stack upload (stacked.py's "multi" plan kind: leaves are
  deduplicated across queries by the shared ``PlanBuilder``), executed
  as ONE device dispatch and demultiplexed back to the waiting handler
  threads.  The admission lock is held only for queue flips; the
  device runs while the next batch accumulates (continuous batching).

- ``ResultCache`` — a versioned whole-query result cache keyed by the
  plan fingerprint (index, canonical call repr, shard set) and guarded
  by the write-versions of every fragment the query can read: any
  host write bumps its fragment's version (models/fragment.py), so a
  stale entry misses — and an explicit ``sweep()`` after serving-path
  writes evicts exactly the entries whose snapshot no longer matches.
  LRU byte-bounded like ``TileStackCache``.

Consistency bar: a query admitted before a write either executes
against a fragment-version snapshot that is still intact when its
batch completes, or it is re-executed solo (the same consistency the
unbatched path provides).  Anything the batcher cannot express falls
back to ``Executor.execute`` — results are bit-exact by construction
because candidate selection (TopN) and plan building are shared with
the solo path.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict

import numpy as np

from pilosa_tpu.executor.results import Pair, RowResult, ValCount
from pilosa_tpu.executor.stacked import (
    PlanBuilder,
    Unstackable,
    _compiled,
    _dispatch_kind,
    dispatch_ready,
)
from pilosa_tpu.models.index import EXISTENCE_FIELD
from pilosa_tpu.obs import audit as _audit
from pilosa_tpu.obs import faults as _faults
from pilosa_tpu.obs import flight, metrics
from pilosa_tpu.obs import stats as _stats
from pilosa_tpu.obs.monitor import capture_exception
from pilosa_tpu.obs.tracing import capture_context, start_span
from pilosa_tpu.pql import parse
from pilosa_tpu.pql.ast import Call, Query

# the executor's own write-call table: one source of truth so the
# serving layer's write routing can never drift from dispatch
from pilosa_tpu.executor.executor import _WRITE_CALLS

# bitmap-producing calls the stacked PlanBuilder can express without
# per-query precompute (no Distinct/UnionRows/ConstRow leaves)
_PURE_BITMAP = {"Row", "Range", "Union", "Intersect", "Difference",
                "Xor", "Not", "All", "Shift"}

# read calls whose results depend only on fragment contents (plus
# append-only key translation) — the cacheable dispatch surface of
# Executor._execute_call
_READ_CALLS = _PURE_BITMAP | {
    "Count", "Sum", "Min", "Max", "MinRow", "MaxRow", "Distinct",
    "Rows", "UnionRows", "TopN", "TopK", "GroupBy", "Percentile",
    "Sort", "Extract", "Limit", "IncludesColumn", "FieldValue",
    "ConstRow",
}


class Uncacheable(Exception):
    """Raised when a query's read set cannot be proven version-stable."""


def _fingerprint(key) -> str:
    """Stable short plan fingerprint of a cache key (index, canonical
    call repr, shard set) — correlates flight records across runs,
    unlike the salted builtin hash()."""
    import hashlib
    return hashlib.blake2b(repr(key).encode(),
                           digest_size=8).hexdigest()


# ---------------------------------------------------------------------------
# dependency tracking
# ---------------------------------------------------------------------------

def _dep_fields(idx, call: Call, out: set) -> None:
    """Collect the field names a call tree can read, conservatively
    (over-inclusion only widens invalidation; under-inclusion would be
    a stale-read bug).  Raises Uncacheable for calls whose results
    depend on state outside fragment versions."""
    name = call.name
    if name in _WRITE_CALLS or name not in _READ_CALLS:
        raise Uncacheable(f"not a cacheable call: {name}")
    if name == "Distinct":
        iname = call.arg("index")
        if iname is not None and iname != idx.name:
            raise Uncacheable("cross-index Distinct")
    if name == "ConstRow":
        # keyed columns resolve through the index translator, whose
        # key set can grow without any fragment version bump
        if any(isinstance(c, str) for c in call.arg("columns", []) or []):
            raise Uncacheable("ConstRow with string keys")
    if name in ("Not", "All"):
        out.add(EXISTENCE_FIELD)
    k, cond = call.condition_field()
    if k is not None:
        out.add(k)
        if cond is not None and cond.value is None:
            out.add(EXISTENCE_FIELD)  # null predicates read existence
    for key in ("_field", "field"):
        v = call.args.get(key)
        if isinstance(v, str):
            out.add(v)
    fk, _ = call.field_arg()
    if fk is not None and idx.field(fk) is not None:
        out.add(fk)
    for v in call.args.values():
        if isinstance(v, Call):
            _dep_fields(idx, v, out)
    for c in call.children:
        _dep_fields(idx, c, out)


def _write_targets(idx, q: Query) -> tuple[set | None, set | None]:
    """(fields, shards) a write query touches — the targeted cache
    sweep.  fields None: reach unbounded (Delete removes columns from
    every field; unknown shapes likewise).  shards None: every shard
    of the fields (Store/ClearRow span the whole row; keyed columns
    resolve through the translator).  A point Set/Clear with integer
    columns names exactly the (field, shard) slices its delta
    dirtied — the sweep then compares only those fragments' stamps
    instead of re-walking each entry's whole read set."""
    fields: set = set()
    shards: set | None = set()
    for c in q.calls:
        if c.name not in _WRITE_CALLS or c.name == "Delete":
            return None, None
        fk, _ = c.field_arg()
        if fk is not None:
            fields.add(fk)
        v = c.args.get("_field")
        if isinstance(v, str):
            fields.add(v)
        col = c.args.get("_col")
        if (shards is not None and idx is not None
                and c.name in ("Set", "Clear")
                and isinstance(col, int)
                and not isinstance(col, bool)):
            shards.add(col // idx.width)
        else:
            shards = None
    # Set marks column existence; Store may create the target field —
    # both can stale existence-reading entries
    fields.add(EXISTENCE_FIELD)
    return fields, shards


def _slices_stale(idx, ent_fields: frozenset, snap: tuple,
                  fields: set, shards: set) -> bool:
    """Exact staleness of one cache entry against a POINT write:
    compare only the written (field, shard) fragments' (gen, version)
    stamps with the entry's snapshot — O(written slices), not
    O(entry read set x views x shards).  Sound because the caller
    knows the write touched nothing outside (fields x shards); every
    other write path still hits the full-snapshot comparison at
    get()-time."""
    smap: dict = {}
    absent: set = set()
    for e in snap:
        if len(e) == 2:
            absent.add(e[0])
        else:
            smap[(e[0], e[1], e[2])] = (e[3], e[4])
    for fname in fields & ent_fields:
        f = idx.fields.get(fname)
        if f is None:
            if fname not in absent:
                return True  # field vanished since the snapshot
            continue
        if fname in absent:
            return True  # snapshotted as absent, exists now
        for vname in list(f.views):
            v = f.views.get(vname)
            if v is None:
                continue
            for s in shards:
                fr = v.fragments.get(s)
                cur = None if fr is None else (fr.gen, fr.version)
                if smap.get((fname, vname, s)) != cur:
                    return True
    return False


def query_fields(idx, q: Query) -> frozenset:
    """The field read-set of a whole query (Uncacheable if any call
    escapes version tracking)."""
    out: set = set()
    for c in q.calls:
        _dep_fields(idx, c, out)
    return frozenset(out)


def _shard_set(shards) -> frozenset | None:
    """An explicit-shards query arg as the snapshot restriction; None
    (all shards) stays None."""
    return None if shards is None else frozenset(
        int(s) for s in shards)


def field_snapshot(idx, fields: frozenset, shards=None) -> tuple:
    """Version snapshot of every fragment the fields currently hold:
    ((fname, vname, shard, frag.gen, version), ...).  A write bumps a
    version; a new fragment/view/field changes the tuple's shape; a
    deleted-and-recreated field gets fresh generation stamps (a
    process-global monotonic counter — id() would be unsound, CPython
    reuses freed addresses) — all compare unequal, so comparison-to-
    snapshot is the staleness test.

    ``shards`` (a set) restricts the walk to those shards' fragments:
    a query executed over an explicit shard subset reads nothing
    outside it, so its cache entry must survive writes to OTHER
    shards of the same fields — the (field, shard)-granular
    invalidation bulk imports rely on."""
    snap = []
    for fname in sorted(fields):
        f = idx.fields.get(fname)
        if f is None:
            snap.append((fname, None))
            continue
        for vname in sorted(f.views):
            # .get, skipping None: a concurrent view/field deletion
            # between the key listing and the lookup must produce a
            # (correct) snapshot mismatch, not a KeyError in a read
            v = f.views.get(vname)
            if v is None:
                continue
            for shard in sorted(v.fragments):
                if shards is not None and shard not in shards:
                    continue
                fr = v.fragments.get(shard)
                if fr is None:
                    continue
                snap.append((fname, vname, shard, fr.gen, fr.version))
    return tuple(snap)


def _result_nbytes(r) -> int:
    """Rough byte estimate of one result for LRU accounting.  Every
    container result type gets a size-proportional estimate — a flat
    default would let large Extract/Distinct results slip under the
    byte bound and grow the cache past its budget."""
    from pilosa_tpu.executor.results import (
        DistinctValues,
        ExtractedTable,
        GroupCount,
        SortedRow,
    )
    if isinstance(r, RowResult):
        return 64 + sum(int(w.nbytes) for w in r.segments.values()) + \
            (len(r.keys) * 24 if r.keys else 0)
    if isinstance(r, (list, tuple)):
        return 48 + sum(_result_nbytes(x) for x in r)
    if isinstance(r, dict):
        return 64 + sum(48 + _result_nbytes(v) for v in r.values())
    if isinstance(r, np.ndarray):
        return int(r.nbytes)
    if isinstance(r, DistinctValues):
        return 48 + 24 * len(r.values)
    if isinstance(r, SortedRow):
        return 48 + 16 * (len(r.columns) + len(r.values))
    if isinstance(r, GroupCount):
        return 96 + 64 * len(r.group)
    if isinstance(r, ExtractedTable):
        return 96 + 48 * len(r.fields) + sum(
            64 + 24 * len(c.get("rows", ()))
            if isinstance(c, dict) else 64 for c in r.columns)
    if hasattr(r, "schema") and hasattr(r, "rows"):
        # SQLResult (duck-typed: serving must not import the sql
        # layer) — cached SQL statements size by their row payload
        return 96 + 48 * len(r.schema) + sum(
            48 + 24 * len(row) for row in r.rows)
    return 64


# ---------------------------------------------------------------------------
# versioned result cache
# ---------------------------------------------------------------------------

_MISS = object()


class ResultCache:
    """LRU byte-bounded whole-query result cache, recompute-cost
    aware: entries carry the measured/estimated cost of recomputing
    them (statistics catalog, obs/stats.py), and eviction drops the
    cheapest-to-recompute entry among the LRU window — a hot
    expensive GroupBy survives pressure that flushes point Counts.
    With the catalog disabled every cost is None and eviction is
    pure LRU (the PILOSA_TPU_STATS=0 A/B arm).

    Entry: key -> (fields, snapshot, results, nbytes, cost_ms).  A
    lookup
    recomputes the fields' current snapshot and misses (evicting the
    entry) on any mismatch — so writes invalidate lazily, exactly the
    entries whose read set they touched; ``sweep()`` performs the same
    eviction eagerly after serving-path writes.

    Bytes also account through the process device-memory ledger
    (pilosa_tpu/memory): the local ``max_bytes`` stays as this cache's
    own cap, and under cross-cache pressure the ledger's reclaim
    callback sheds the LRU tail here too — result bytes can no longer
    silently stack on top of a full tile-stack budget."""

    def __init__(self, max_bytes: int = 64 << 20, ledger=None):
        from pilosa_tpu import memory
        self.max_bytes = max_bytes
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self._client = (memory.ledger() if ledger is None
                        else ledger).register(
            "result_cache", reclaim=self._reclaim)
        self.hits = 0
        self.misses = 0
        # write-through entry keys owned by the standing-query
        # registry (executor/standing.py): maintenance ADVANCES their
        # snapshot in place, so sweeps/eviction must not drop them —
        # a stale get() still misses (no wrong answers) but leaves
        # the entry for the registry's catch_up to advance
        self._standing: set = set()

    # cost-aware eviction scans this many LRU-end entries for the
    # cheapest recompute; small so eviction stays O(1)-ish
    _EVICT_WINDOW = 8

    def _evict_one_locked(self, exclude=None) -> int:
        """Drop one entry (caller holds the lock): the cheapest
        recompute cost among the _EVICT_WINDOW oldest (None cost =
        no evidence = first out; all-None degrades to LRU).
        ``exclude`` protects the entry a put() just inserted — a
        cheap newcomer must not evict ITSELF (it would pin expensive
        entries forever and give the hottest cheap query a 0% hit
        rate).  Returns the freed bytes (0 = nothing evictable)."""
        window = [(k, e) for k, e in itertools.islice(
            self._entries.items(), self._EVICT_WINDOW)
            if k != exclude and k not in self._standing]
        if not window:
            return 0
        best = min(range(len(window)),
                   key=lambda i: (window[i][1][4]
                                  if window[i][1][4] is not None
                                  else -1.0, i))
        key, ent = window[best]
        self._entries.pop(key)
        self._bytes -= ent[3]
        return ent[3]

    def _reclaim(self, need: int) -> int:
        freed = 0
        with self._lock:
            while self._entries and freed < need:
                got = self._evict_one_locked()
                if not got:
                    break  # only standing entries left: not evictable
                freed += got
        if freed:
            self._client.release(freed)
        return freed

    def get(self, idx, key, cur_snap: tuple | None = None):
        """`cur_snap`, when given, must be field_snapshot() of the
        entry's read set taken just now — callers that already walked
        the fragments pass it to avoid a second walk."""
        with self._lock:
            ent = self._entries.get(key)
        if ent is None:
            with self._lock:
                self.misses += 1
            return _MISS
        fields, snap, results, _nb, _cost = ent
        # snapshot outside the lock: touches only holder structures;
        # narrowed to the entry's explicit shard subset (key[2]) so a
        # write to another shard cannot stale it
        if (field_snapshot(idx, fields, _shard_set(key[2]))
                if cur_snap is None else cur_snap) != snap:
            dropped = 0
            with self._lock:
                cur = self._entries.get(key)
                # standing entries stay put on staleness: the
                # registry advances them instead of re-executing
                if cur is ent and key not in self._standing:
                    self._entries.pop(key)
                    self._bytes -= ent[3]
                    dropped = ent[3]
                self.misses += 1
            if dropped:
                self._client.release(dropped)
            return _MISS
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
            self.hits += 1
        return results

    def put(self, key, fields: frozenset, snapshot: tuple, results,
            cost_ms: float | None = None):
        """``cost_ms`` is the entry's recompute cost (fingerprint
        profile estimate, or the duration just measured) — the
        cost-aware eviction's ranking signal; None with the stats
        catalog disabled keeps pure LRU semantics."""
        if _faults.armed("audit-corrupt") and _faults.take(
                "audit-corrupt", f"cache:{key[0]}"):
            # corruption drill (obs/audit.py): the STORED entry gets a
            # flipped bit while the serve in flight stays clean — the
            # injection the cache-audit scrubber must catch
            results = _audit.corrupt_results(results)
        nbytes = _result_nbytes(results)
        if nbytes > self.max_bytes:
            return
        # ledger reservation OUTSIDE our lock (reclaim may call back
        # into _reclaim); denial = serve uncached, exactly like an
        # entry over the local cap
        if not self._client.reserve(nbytes):
            return
        released = 0
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[3]
                released += old[3]
            self._entries[key] = (fields, snapshot, results, nbytes,
                                  cost_ms)
            self._bytes += nbytes
            while self._bytes > self.max_bytes and self._entries:
                freed = self._evict_one_locked(exclude=key)
                if not freed:  # only the new entry left: it fits
                    break      # (nbytes <= max_bytes guard above)
                released += freed
        if released:
            self._client.release(released)

    def mark_standing(self, key) -> None:
        with self._lock:
            self._standing.add(key)

    def unmark_standing(self, key) -> None:
        """Return a key to normal swept-entry lifecycle (and drop the
        now-unmaintained entry so it cannot serve stale)."""
        dropped = 0
        with self._lock:
            self._standing.discard(key)
            ent = self._entries.pop(key, None)
            if ent is not None:
                self._bytes -= ent[3]
                dropped = ent[3]
        if dropped:
            self._client.release(dropped)

    def advance(self, key, fields: frozenset, snapshot: tuple,
                results, cost_ms: float | None = None) -> None:
        """Write-through maintenance: replace a standing entry's
        snapshot+results in place.  put() already replaces in place
        and its eviction excludes standing keys; a ledger denial just
        drops the entry — the registry's catch_up still serves."""
        self.put(key, fields, snapshot, results, cost_ms)

    def sweep(self, holder, touched: set | None = None,
              shards: set | None = None) -> int:
        """Evict exactly the entries whose snapshot is stale (called
        after serving-path writes).  `touched` narrows the scan to
        entries whose read set intersects the written fields; `shards`
        (a point write's delta naming exactly the (field, shard)
        slices it dirtied) further narrows the staleness test to those
        fragments' stamps — entries a write cannot have staled are not
        re-snapshotted, so per-Set sweep cost tracks relevance, not
        cache occupancy (lazy get-time validation still covers every
        other write path).  Returns the eviction count."""
        with self._lock:
            items = list(self._entries.items())
        evicted = 0
        for key, ent in items:
            if key in self._standing:
                continue  # maintained, not swept
            if touched is not None and not (ent[0] & touched):
                continue
            eshards = shards
            if shards is not None and key[2] is not None:
                # an explicit-shard entry can only be staled by the
                # written shards it actually reads
                eshards = shards & set(key[2])
                if not eshards:
                    continue  # entirely outside the write
            idx = holder.index(key[0])
            if idx is None:
                stale = True
            elif eshards is not None and touched is not None:
                stale = _slices_stale(idx, ent[0], ent[1], touched,
                                      eshards)
            else:
                stale = field_snapshot(idx, ent[0],
                                       _shard_set(key[2])) != ent[1]
            if stale:
                dropped = 0
                with self._lock:
                    cur = self._entries.get(key)
                    if cur is ent:
                        self._entries.pop(key)
                        self._bytes -= ent[3]
                        dropped = ent[3]
                        evicted += 1
                if dropped:
                    self._client.release(dropped)
        return evicted

    def sweep_shards(self, index: str, shards: set[int]) -> int:
        """Online-resharding FENCE/RELEASE sweep: evict exactly the
        entries of ``index`` whose read set can touch the moved
        shards — an explicit-shard entry only when its shard subset
        intersects them, a whole-index entry always (it could have
        read the moved shard).  Unconditional on snapshot equality:
        the donor's fragments are about to leave, and a cached result
        covering the shard would otherwise keep serving answers that
        miss the recipient's new writes.  Entries over OTHER shards
        (and other indexes) survive — a rebalance must never flush
        the whole cache (test-pinned)."""
        with self._lock:
            items = list(self._entries.items())
        evicted = 0
        for key, ent in items:
            if key[0] != index:
                continue
            if key in self._standing:
                continue  # registry fallback re-seeds from the move
            if key[2] is not None and not (set(key[2]) & shards):
                continue
            dropped = 0
            with self._lock:
                cur = self._entries.get(key)
                if cur is ent:
                    self._entries.pop(key)
                    self._bytes -= ent[3]
                    dropped = ent[3]
                    evicted += 1
            if dropped:
                self._client.release(dropped)
        return evicted

    def clear(self):
        with self._lock:
            total = self._bytes
            self._entries.clear()
            self._bytes = 0
        if total:
            self._client.release(total)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    @property
    def nbytes(self) -> int:
        return self._bytes


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------

class _Req:
    """One in-flight batchable query."""

    __slots__ = ("index", "idx", "q", "call", "kind", "shards", "skey",
                 "fields", "key", "snapshot", "result", "error",
                 "direct", "event", "ctx", "trace_id", "acc",
                 "batch_size")

    def __init__(self, index, idx, q, call, kind, shards, skey,
                 fields, key, snapshot):
        self.index = index
        self.idx = idx
        self.q = q
        self.call = call
        self.kind = kind
        self.shards = shards          # caller's shards arg (may be None)
        self.skey = skey              # resolved shard tuple
        self.fields = fields          # frozenset | None (uncacheable)
        self.key = key
        self.snapshot = snapshot      # admission-time version snapshot
        self.result = None            # list of results when served
        self.error = None
        self.direct = False           # fall back to Executor.execute
        self.event = threading.Event()
        # flight-recorder / tracing plumbing: the follower's captured
        # trace context (obs.tracing.TraceContext — the leader records
        # spans INTO it), its flight trace id, the leader-side phase
        # accumulator merged back at commit, and the batch occupancy
        # the leader stamped
        self.ctx = None
        self.trace_id = None
        self.acc = None
        self.batch_size = 1


class QueryBatcher:
    """Leader/follower continuous batching.

    The first thread to arrive while no leader is active becomes the
    leader: it waits out the admission window (or until ``max_batch``
    requests queue), flips the queue, and executes the fused batch
    while the NEXT batch accumulates behind a new leader.  Followers
    park on a per-request event.
    """

    def __init__(self, serving: "ServingLayer", window_s: float,
                 max_batch: int):
        self.serving = serving
        self.window_s = window_s
        self.max_batch = max_batch
        self._cond = threading.Condition()
        self._pending: list[_Req] = []
        self._leader = False
        self._inflight = 0  # batches currently executing
        # serialize=True (the ragged canonical program, set by
        # ServingLayer): at most ONE batch executes at a time and the
        # next leader waits for it rather than for a wall-clock
        # window.  The canonical program computes every canonical
        # slot per dispatch, so overlapping batches would multiply
        # that fixed cost for ~no extra riders — serializing maximizes
        # occupancy per dispatch, which is the whole amortization.
        self.serialize = False

    def run(self, req: _Req) -> None:
        """Serve one request through the batch path; on return the
        request carries ``result`` or ``error``."""
        with self._cond:
            self._pending.append(req)
            metrics.SERVING_QUEUE_DEPTH.set(len(self._pending))
            if self._leader:
                if len(self._pending) >= self.max_batch:
                    self._cond.notify_all()  # leader stops waiting
                follower = True
            else:
                self._leader = True
                follower = False
        if follower:
            with flight.stage("batch.wait"):
                req.event.wait()
            return
        t_lead = time.perf_counter()
        deadline = t_lead + self.window_s
        with flight.stage("batch.wait"), self._cond:
            # continuous batching: dispatch IMMEDIATELY when the
            # device is idle (a lone request must not eat the window
            # as pure latency); wait out the admission window only
            # while another batch is executing — that is exactly when
            # requests naturally accumulate
            while (self._inflight > 0
                   and len(self._pending) < self.max_batch):
                if self.serialize:
                    # wait out the in-flight batch itself (notified on
                    # completion), not a wall-clock window — arrivals
                    # during the dispatch become the next full batch
                    self._cond.wait(0.05)
                    continue
                rem = deadline - time.perf_counter()
                if rem <= 0:
                    break
                self._cond.wait(rem)
            batch = self._pending
            self._pending = []
            self._leader = False
            self._inflight += 1
            metrics.SERVING_QUEUE_DEPTH.set(0)
        metrics.SERVING_BATCH_WAIT.observe(time.perf_counter() - t_lead)
        metrics.SERVING_BATCH_SIZE.observe(len(batch))
        # watchdog arming (obs/watchdog.py): the leader is entering
        # the fused dispatch — a dispatch wedged past the deadline is
        # a named stall ("serving-batcher"/"dispatch"), not a silent
        # latency cliff.  begin/end TOKENS, not stamp/idle: under
        # load a full batch dispatches while another is still in
        # flight (the wait loop exits at max_batch even with
        # inflight > 0), and a healthy leader finishing must not
        # disarm or re-stamp away a wedged sibling — staleness is
        # judged against the OLDEST in-flight dispatch.
        wd_tok = self.serving.watch.begin("dispatch")
        try:
            self.serving._run_batch(batch)
        except Exception as e:  # belt-and-braces: never strand a waiter
            # leader-thread failures are otherwise invisible to the
            # followers' own monitoring — capture with the BATCH's
            # trace ids so /debug/errors points at every affected query
            capture_exception(
                e, where="serving.batch", batch=len(batch),
                trace_ids=[r.trace_id for r in batch if r.trace_id])
            # incident trigger (obs/incidents.py): an unhandled batch-
            # leader exception strands no waiter (the loop below fails
            # them typed) but is a serving-plane fault worth a bundle
            from pilosa_tpu.obs import incidents
            incidents.report(
                "batch-leader-exception", detail=type(e).__name__,
                context={"message": str(e)[:300], "batch": len(batch),
                         "trace_ids": [r.trace_id for r in batch
                                       if r.trace_id][:16]})
            for r in batch:
                if r.result is None and r.error is None:
                    r.error = e
        finally:
            self.serving.watch.end(wd_tok)
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()  # wake a window-waiting leader
            for r in batch:
                r.event.set()


# ---------------------------------------------------------------------------
# serving layer
# ---------------------------------------------------------------------------

class ServingLayer:
    """Front of Executor for the HTTP/gRPC serving path: QoS admission
    first (executor/sched.py), result cache second, micro-batcher
    (per-group or ragged cross-index fused dispatch) third,
    ``Executor.execute`` fallback always."""

    def __init__(self, executor, window_s: float = 0.001,
                 max_batch: int = 32, cache_bytes: int = 64 << 20,
                 batching: bool = True, ragged: bool | None = None,
                 admission: bool | None = None, heavy_slots: int = 2,
                 queue_max: int = 128, tenant_weights=None,
                 default_deadline_ms: float = 0.0):
        import os

        from pilosa_tpu.executor import sched as _sched
        self.executor = executor
        self.batching = batching and max_batch > 1
        self.cache = ResultCache(cache_bytes) if cache_bytes > 0 else None
        self.batcher = QueryBatcher(self, window_s, max_batch)
        self.prefetcher = None
        # ragged cross-index page-table dispatch (executor/ragged.py):
        # one fused device program per batch instead of one per
        # (index, shards) group.  Env-overridable for the bench A/B.
        env_r = os.environ.get("PILOSA_TPU_SERVING_RAGGED")
        if ragged is None:
            ragged = True
        if env_r is not None:
            ragged = env_r != "0"
        self.ragged = ragged
        # QoS admission (executor/sched.py): point reads bypass, heavy
        # reads pass a bounded weighted-fair gate, overflow sheds 503
        env_a = os.environ.get("PILOSA_TPU_SERVING_ADMISSION")
        if admission is None:
            admission = True
        if env_a is not None:
            admission = env_a != "0"
        weights = (tenant_weights
                   if isinstance(tenant_weights, dict)
                   else _sched.parse_weights(tenant_weights))
        self.sched = _sched.AdmissionScheduler(
            heavy_slots=heavy_slots, queue_max=queue_max,
            tenant_weights=weights) if admission else None
        self.default_deadline_ms = float(default_deadline_ms or 0.0)
        # one canonical dispatch at a time (see QueryBatcher)
        self.batcher.serialize = self.ragged
        # stall watchdog on the batch-leader dispatch (obs/watchdog.py;
        # registration is idempotent by name — serving layers are
        # rebuilt freely in-process and the loop identity is the name)
        from pilosa_tpu.obs import watchdog
        self.watch = watchdog.register("serving-batcher")
        # standing-query registry (executor/standing.py): maintained
        # write-through entries over this cache.  Runtime import —
        # standing imports serving's module surface
        from pilosa_tpu.executor.standing import StandingRegistry
        self.standing = StandingRegistry(self)
        # continuous correctness auditing (obs/audit.py): the shadow
        # sampler taps every successful read route; workers spawn
        # lazily on the first sampled serve
        self.audit = _audit.AuditPlane(self)

    def start_prefetcher(self, interval_s: float = 0.5):
        """Warm predicted stack pages off the serving hot path
        (memory/policy.py Prefetcher over the flight recorder's
        per-query stack-outcome records).  Idempotent."""
        if self.prefetcher is None:
            from pilosa_tpu.memory.policy import Prefetcher
            self.prefetcher = Prefetcher(
                self.executor.stacked.cache,
                interval_s=interval_s).start()
        return self.prefetcher

    def stop_prefetcher(self):
        if self.prefetcher is not None:
            self.prefetcher.stop()
            self.prefetcher = None

    # -- entry point ---------------------------------------------------

    def execute(self, index: str, query, shards=None,
                remote: bool = False, qos=None) -> list:
        # the request envelope: joins the transport's (the HTTP
        # handler opened one at the first byte) or, for callers with
        # no transport in front, opens here — so the stages before the
        # flight record begins (parse, admission) reach the record
        with flight.request():
            return self._execute(index, query, shards, remote, qos)

    def _execute(self, index, query, shards, remote, qos) -> list:
        from pilosa_tpu.executor import sched as _sched
        ex = self.executor
        if remote:
            # node-to-node calls carry the _REMOTE contextvar, which a
            # leader thread would not inherit — serve them solo
            return ex.execute(index, query, shards, remote=True)
        if isinstance(query, str):
            with flight.stage("pql.parse"):
                q = parse(query)
        else:
            q = query
        if any(c.name in _WRITE_CALLS for c in q.calls):
            try:
                return ex.execute(index, q, shards)
            finally:
                if self.cache is not None:
                    wf, ws = _write_targets(ex.holder.index(index), q)
                    self.cache.sweep(ex.holder, wf, ws)
                    metrics.RESULT_CACHE.inc(outcome="write")
                    # push the landed delta through the standing
                    # registrations this write can have touched
                    self.standing.on_write(index, wf, ws)
        # default deadline: a [serving] default-deadline-ms applies to
        # every request that carried no deadline of its own — a
        # tenant/priority header must not opt a request out of the
        # operator's configured budget
        if self.default_deadline_ms > 0:
            if qos is None:
                qos = _sched.QoS.make(
                    deadline_ms=self.default_deadline_ms)
            elif qos.deadline_s is None:
                dflt = _sched.QoS.make(
                    deadline_ms=self.default_deadline_ms)
                qos.deadline_ms = dflt.deadline_ms
                qos.deadline_s = dflt.deadline_s
        # cost-based admission (obs/stats.py): classify by the plan
        # fingerprint's MEASURED cost profile when the catalog is warm
        # (query kind stays the cold-start fallback inside classify).
        # An explicit priority override skips the hash — classify
        # returns before reading it, and SQL's inner calls (always
        # explicit point) would otherwise pay a blake2b over a
        # possibly-huge ConstRow repr per call; _execute_read
        # recomputes the key (and commit the fingerprint) when a
        # flight record actually consumes them
        key = None
        fp = None
        with flight.stage("admission.classify"):
            if _stats.enabled() and not (
                    qos is not None and qos.priority in (
                        _sched.CLASS_POINT, _sched.CLASS_HEAVY)):
                key = (index, repr(q.calls),
                       None if shards is None
                       else tuple(sorted(shards)))
                fp = _fingerprint(key)
            cls = _sched.classify(q, qos, fingerprint=fp)
            # a dead-on-arrival deadline sheds regardless of class —
            # the client stopped waiting, executing would only burn
            # device time
            if (qos is not None and qos.deadline_s is not None
                    and time.monotonic() > qos.deadline_s):
                metrics.ADMISSION_TOTAL.inc(**{"class": cls,
                                               "outcome": "expired"})
                raise _sched.ServingDeadlineExceeded(
                    "deadline expired before execution")
        # span on the CALLER's thread so the long-query log keeps its
        # executor.Execute root even for fused/cached serves (the
        # direct fallback nests its own copy inside — the root name
        # is what the log consumers pin on)
        if cls == _sched.CLASS_HEAVY and self.sched is not None:
            # bounded heavy concurrency + weighted per-tenant fair
            # queueing: a GroupBy storm can no longer occupy every
            # engine thread, so point reads never queue behind it
            with self.sched.heavy_slot(qos):
                with start_span("executor.Execute", index=index) as root:
                    return self._execute_read(ex, index, q, shards,
                                              root, qos=qos, cls=cls,
                                              key=key, fp=fp)
        metrics.ADMISSION_TOTAL.inc(**{"class": cls,
                                       "outcome": "admitted"})
        with start_span("executor.Execute", index=index) as root:
            return self._execute_read(ex, index, q, shards, root,
                                      qos=qos, cls=cls, key=key,
                                      fp=fp)

    def _execute_read(self, ex, index, q, shards, root=None, qos=None,
                      cls=None, key=None, fp=None):
        t0 = time.perf_counter()
        route = "direct"
        fl = flight.begin(index, q)
        if fl is not None:
            # QoS attribution: every serving-path record names its
            # tenant, admission class, and deadline budget so
            # /debug/queries can answer "whose query, how urgent"
            fl["tenant"] = qos.tenant if qos is not None else "default"
            fl["priority"] = cls or "point"
            if qos is not None and qos.deadline_ms is not None:
                fl["deadline_ms"] = round(float(qos.deadline_ms), 1)
        if fl is not None and root is not None:
            root.set_tag("trace_id", fl["trace_id"])
        req = None
        err = None
        try:
            idx = ex.holder.index(index)
            if idx is None:  # canonical "index not found" error path
                return ex.execute(index, q, shards)
            if key is None:  # stats-off path: execute() skipped it
                key = (index, repr(q.calls),
                       None if shards is None else tuple(sorted(shards)))
            # the read set drives BOTH the cache guard and the
            # batcher's mid-flight consistency re-check, so compute it
            # even with the cache disabled
            fields = None
            with flight.stage("cache_lookup"):
                try:
                    fields = query_fields(idx, q)
                except Uncacheable:
                    if self.cache is not None:
                        metrics.RESULT_CACHE.inc(outcome="bypass")
                # ONE snapshot walk serves the cache guard, batch
                # admission, and the miss-path store protocol (the
                # walk is O(fields x views x shards) Python — at 954
                # shards it must not run three times per query);
                # explicit-shard queries snapshot only their subset,
                # so writes elsewhere never stale them
                sset = _shard_set(shards)
                snap = (field_snapshot(idx, fields, sset)
                        if fields is not None else None)
                cache_res = _MISS
                if self.cache is not None and fields is not None:
                    cache_res = self.cache.get(idx, key, cur_snap=snap)
            if cache_res is not _MISS:
                route = "cached"
                metrics.RESULT_CACHE.inc(outcome="hit")
                metrics.QUERY_TOTAL.inc(index=index, status="ok")
                metrics.QUERY_DURATION.observe(
                    time.perf_counter() - t0)
                # audit tap with the hit's OWN guard snapshot: get()
                # verified the entry against `snap`, so the answer is
                # proven to reflect exactly that fragment-version state
                return _audit.tap(self.audit, index, idx, q, shards,
                                  key, fields, snap, "cached",
                                  cache_res, fl)
            if self.cache is not None and fields is not None:
                metrics.RESULT_CACHE.inc(outcome="miss")
            # a registry-owned key pulls maintenance instead of
            # re-executing: the poll pays O(delta), never a restack
            if self.standing.owns(key):
                got = self.standing.catch_up(key)
                if got is not _MISS:
                    route = "standing"
                    metrics.QUERY_TOTAL.inc(index=index, status="ok")
                    metrics.QUERY_DURATION.observe(
                        time.perf_counter() - t0)
                    # the registry's snapshot is the one that provably
                    # covers the maintained result (catch_up may have
                    # advanced past `snap` taken at admission)
                    sq = self.standing._by_key.get(key)
                    return _audit.tap(
                        self.audit, index, idx, q, shards, key,
                        fields,
                        sq.snapshot if sq is not None else None,
                        "standing", got, fl)
            # classification pays a shard-list sort — skip it
            # entirely in cache-only mode
            req = (self._classify(index, idx, q, shards, fields, key,
                                  snap)
                   if self.batching else None)
            if req is not None:
                # cross-thread propagation: the leader records this
                # request's device phases into the captured context
                # (None when nothing traces — zero overhead)
                req.ctx = capture_context()
                if fl is not None:
                    req.trace_id = fl["trace_id"]
                with flight.stage("batch"):
                    self.batcher.run(req)
                if req.error is not None:
                    raise req.error
                if req.result is not None and not req.direct:
                    route = "fused"
                    metrics.QUERY_TOTAL.inc(index=index, status="ok")
                    metrics.QUERY_DURATION.observe(
                        time.perf_counter() - t0)
                    # req.snapshot survived the batch post-pass
                    # re-check, so it provably covers the fused answer
                    return _audit.tap(self.audit, index, idx, q,
                                      shards, key, fields,
                                      req.snapshot, "fused",
                                      req.result, fl)
                # fallback on THIS thread: failed/stale fused serves
                # re-execute in parallel across their callers, not
                # serially on the batch leader.  snap is stale here by
                # definition — _exec_and_cache takes a fresh one.
                snap = None
            return self._exec_and_cache(index, idx, q, shards, fields,
                                        key, snap, fl=fl)
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
            raise
        finally:
            metrics.SERVING_BATCHED.inc(route=route)
            if fl is None:
                # nested under an open record (a SQL statement's
                # inner PQL dispatch): stamp this serve's route into
                # the parent so /debug/queries shows which of a
                # statement's calls rode the fused plane
                flight.note_route(route)
            dur = time.perf_counter() - t0
            metrics.SERVING_LATENCY.observe(dur)
            flight.commit(
                fl, dur, route=route,
                batch=req.batch_size if req is not None else 1,
                error=err,
                # reuse the admission fingerprint (stats path) —
                # repr+hash of the whole key must not be paid twice;
                # with stats off, pay it only when a record is open
                fingerprint=(fp if fp is not None else
                             (_fingerprint(key)
                              if fl is not None and key else None)),
                extra_acc=req.acc if req is not None else None)

    # -- classification ------------------------------------------------

    def _classify(self, index, idx, q: Query, shards, fields, key,
                  snapshot=None):
        """A _Req when the query can ride a fused batch, else None."""
        if len(q.calls) != 1 or not getattr(self.executor,
                                            "use_stacked", False):
            return None
        call = q.calls[0]
        name = call.name
        if name == "Count":
            if len(call.children) != 1:
                return None
            kind, tree_call = "count", call.children[0]
        elif name == "Sum":
            kind = "sum"
            tree_call = call.children[0] if call.children else None
        elif name in ("TopN", "TopK"):
            kind = "topn"
            tree_call = call.children[0] if call.children else None
        elif name == "GroupBy":
            # batchable subset (ISSUE 11): Rows children over plain
            # fields, optional pure filter tree, optional Sum
            # aggregate — the shapes the one-pass "gb_hist" subplan
            # expresses.  previous=/having=/limit= and
            # Min/Max/Count(Distinct) aggregates stay solo.
            if any(call.arg(k) is not None
                   for k in ("previous", "having", "limit")):
                return None
            if not call.children or any(
                    c.name != "Rows" or c.children
                    or set(c.args) - {"_field"}
                    for c in call.children):
                return None
            agg = call.arg("aggregate")
            if agg is not None and (
                    not isinstance(agg, Call) or agg.name != "Sum"
                    or agg.children or agg.arg("_field") is None):
                return None
            kind, tree_call = "groupby", call.arg("filter")
        elif name in _PURE_BITMAP:
            kind, tree_call = "words", call
        else:
            return None
        if tree_call is not None and not _pure_tree(tree_call):
            return None
        skey = tuple(self.executor._shard_list(idx, shards))
        if snapshot is None and fields is not None:
            snapshot = field_snapshot(idx, fields, _shard_set(shards))
        return _Req(index, idx, q, call, kind, shards, skey, fields,
                    key, snapshot)

    # -- batch execution (leader thread) -------------------------------

    def _run_batch(self, batch: list[_Req]) -> None:
        # group by index IDENTITY, not name: two requests straddling a
        # drop-and-recreate of the same index name must not share one
        # PlanBuilder (reqs[0].idx would serve the other's query from
        # the wrong generation's fragments)
        groups: dict[tuple, list[_Req]] = {}
        for r in batch:
            r.batch_size = len(batch)  # flight-record occupancy
            groups.setdefault((id(r.idx), r.skey), []).append(r)
        # ragged cross-index dispatch: ONE fused page-table program
        # serves every group (executor/ragged.py) — a planning failure
        # degrades to the per-group path, a dispatch failure marks the
        # riders direct (both non-fatal, like _run_group's own ladder).
        # Mesh placements keep per-group programs: concatenating
        # differently-sharded operands in one program is not expressible.
        ragged_done = False
        if (self.ragged and groups
                and self.executor.stacked.mesh is None):
            try:
                from pilosa_tpu.executor import ragged as _ragged
                _ragged.run_ragged(self, groups)
                ragged_done = True
            except Exception as e:
                capture_exception(e, where="serving.ragged_plan",
                                  batch=len(batch))
        if not ragged_done:
            for reqs in groups.values():
                self._run_group(reqs)
        # post-pass: snapshot re-check.  Fallbacks are NOT executed
        # here — the leader running every solo re-execution serially
        # would hold all followers hostage; instead the request is
        # marked direct and each CALLER thread re-executes its own
        # query after its event fires (parallel, like batching off).
        for r in batch:
            # the result cache's store side, staged per rider like its
            # lookup side: the version walk is O(fields x views x
            # shards) Python while every follower is still parked
            with flight.stage("cache_store",
                              accs=[r.acc or flight.Acc()]):
                if (not r.direct and r.error is None
                        and r.result is not None
                        and r.fields is not None
                        and field_snapshot(r.idx, r.fields,
                                           _shard_set(r.shards))
                        != r.snapshot):
                    # a write landed while the batch was in flight:
                    # the fused result may span versions — re-execute
                    # solo
                    r.direct = True
                    r.result = None
                if r.result is not None and not r.direct and \
                        r.error is None and r.fields is not None and \
                        self.cache is not None:
                    self.cache.put(
                        r.key, r.fields, r.snapshot, r.result,
                        cost_ms=self._recompute_cost(r.key, r.acc))

    def _run_group(self, reqs: list[_Req]) -> None:
        ex = self.executor
        eng = ex.stacked
        idx = reqs[0].idx
        shards = list(reqs[0].skey)
        b = PlanBuilder(eng, idx, shards, {})
        subs, demuxes, pend = [], [], []
        # canonical build order: leaf indices are assigned during
        # build, so permutations of the same query set must BUILD in
        # one order to share a compiled multi program (sorting only
        # the finished subplans would leave arrival-dependent leaf
        # numbering behind)
        for r in sorted(reqs, key=lambda r: repr(r.call)):
            if r.result is not None or r.error is not None:
                continue
            # per-request attribution ON the leader thread: stack
            # fetches/uploads inside the build accumulate into THIS
            # request's Acc, and spans graft into its TraceContext
            r.acc = flight.Acc()
            try:
                with flight.stage("plan_build", accs=[r.acc],
                                  ctx=r.ctx, kind=r.kind):
                    built = self._build_sub(b, r, shards)
            except Exception:
                r.direct = True
                continue
            if built is None:
                continue  # constant result already set on r
            sub, demux = built
            subs.append(sub)
            demuxes.append(demux)
            pend.append(r)
        if not subs:
            return
        # the SHARED phase: one fused dispatch serves every pending
        # request, timed once and attributed (with a span copy) to
        # each — a recompile of the multi program is tagged distinctly
        # from a cached-executable dispatch
        plan = ("multi", tuple(subs))
        sig = repr(plan)  # multi-KB at high occupancy: once
        kind = _dispatch_kind(sig, b.leaves, b.params)
        try:
            with flight.stage(kind, accs=[r.acc for r in pend],
                              ctx=[r.ctx for r in pend],
                              batch=len(pend), subqueries=len(subs),
                              compile=kind == "compile"):
                # chaos seam: an armed "serving-dispatch" fault fails
                # the fused program exactly like a device-side error,
                # driving every rider onto the per-caller direct
                # fallback
                from pilosa_tpu.obs import faults
                faults.fire("serving-dispatch")
                fn = _compiled(plan, sig=sig)
                # OOM backstop: RESOURCE_EXHAUSTED on the fused
                # program evicts via the ledger + retries once; a
                # persistent OOM falls through to the per-rider direct
                # path, where each solo dispatch carries its own
                # host-fallback ladder — the batch degrades, no
                # rider's query fails
                from pilosa_tpu.memory import pressure
                outs = pressure.guarded(lambda: dispatch_ready(
                    fn, tuple(b.leaves), tuple(b.params)))
        except Exception as e:
            # the fused program failing is a leader-side event the
            # affected callers never see (they silently fall back) —
            # surface it with every rider's trace id
            capture_exception(
                e, where="serving.fused_dispatch", batch=len(pend),
                trace_ids=[r.trace_id for r in pend if r.trace_id])
            for r in pend:
                r.direct = True
            return
        metrics.SERVING_DISPATCH.inc(kind="group")
        for r, demux, out in zip(pend, demuxes, outs):
            try:
                with flight.stage("demux", accs=[r.acc], ctx=r.ctx):
                    r.result = demux(out)
            except Exception:
                r.direct = True
                r.result = None

    def _build_sub(self, b: PlanBuilder, r: _Req, shards: list[int]):
        """(subplan, demux) for one request, or None after setting a
        constant result.  Any exception → solo fallback (which also
        reproduces the user-visible error faithfully)."""
        ex = self.executor
        eng = ex.stacked
        idx = r.idx
        red = eng._reduce_in_program(shards)
        call = r.call
        if r.kind == "count":
            tree = b.build(call.children[0])
            if tree == ("zeros",):
                r.result = [0]
                return None

            def demux_count(out):
                c = np.asarray(out, dtype=np.int64)
                return [int(c) if red else int(c.sum())]
            return ("count", tree, red), demux_count
        if r.kind == "words":
            tree = b.build(call)
            if tree == ("zeros",):
                r.result = [self._row_result(idx, shards, None)]
                return None

            def demux_words(out):
                w = np.asarray(out)[: len(shards)]
                return [self._row_result(idx, shards, w)]
            return ("words", tree), demux_words
        if r.kind == "sum":
            fname = call.arg("_field")
            if fname is None:
                raise Unstackable("Sum without field")
            f = ex._bsi_field(idx, fname)
            planes_i = b._planes_leaf(f)
            tree = None
            if call.children:
                tree = b.build(call.children[0])
                if tree == ("zeros",):
                    r.result = [ValCount(value=f.int_to_value(0), count=0)]
                    return None

            def demux_sum(out):
                cnt, pos, neg = out
                total, count = eng.bsi_sum_host(cnt, pos, neg, red)
                return [ValCount(value=f.int_to_value(total),
                                 count=count)]
            return ("bsi_sum", planes_i, tree, red), demux_sum
        if r.kind == "topn":
            n_key = "n" if call.name == "TopN" else "k"
            prep = ex._topnk_prepare(idx, call, r.shards, {}, n_key)
            if prep[0] == "done":
                r.result = [prep[1]]
                return None
            _, f, views, row_ids, filter_call, n, ids = prep
            est = len(row_ids) * max(len(shards), 1) * (idx.width // 8)
            if est > ex._ROWS_STACK_BUDGET:
                raise Unstackable("TopN row stack over batch budget")
            stack = eng.rows_stack_for(idx, f, tuple(views), row_ids,
                                       tuple(shards))
            rows_i = b._add_leaf(stack)
            tree = (b.build(filter_call)
                    if filter_call is not None else None)
            if tree == ("zeros",):
                pairs = ([Pair(id=rr, count=0) for rr in row_ids]
                         if ids is not None else [])
                r.result = [ex._finish_topn(f, pairs, n, ids)]
                return None

            def demux_topn(out):
                c = np.asarray(out, dtype=np.int64)
                if not red:
                    c = c.sum(axis=1)
                pairs = [Pair(id=rr, count=int(cc))
                         for rr, cc in zip(row_ids, c)
                         if cc > 0 or ids is not None]
                return [ex._finish_topn(f, pairs, n, ids)]
            return ("row_counts", rows_i, tree, red), demux_topn
        if r.kind == "groupby":
            return self._build_groupby_sub(b, r, shards)
        raise Unstackable(f"unbatchable kind {r.kind}")

    def _build_groupby_sub(self, b: PlanBuilder, r: _Req,
                           shards: list[int]):
        """One-pass GroupBy as a batched subplan: the group-code stack
        and BSI planes become shared leaves (PageView pages under the
        ragged program) and the histogram evaluates inside the fused
        device program — a GroupBy rider costs the batch ONE
        single-pass tile walk, not its own dispatch (ISSUE 11)."""
        from pilosa_tpu.executor.stacked import (
            _code_digits,
            _code_space,
            _combo_codes,
            _count_onepass,
            _onepass_plan,
            _onepass_unpack,
            _prunes,
            _walked,
            product_combos,
        )

        ex = self.executor
        eng = ex.stacked
        idx = r.idx
        call = r.call
        if eng.host_only:
            raise Unstackable("groupby batch needs a device program")
        fields, row_lists = [], []
        for rc in call.children:
            fname = rc.arg("_field")
            f = idx.field(fname) if fname else None
            if f is None:
                raise Unstackable("Rows requires a valid field")
            fields.append(f)
            row_lists.append(ex._rows_ids(idx, rc, r.shards))
        if any(not rl for rl in row_lists):
            r.result = [[]]
            return None
        agg_call = call.arg("aggregate")
        agg_field = (ex._bsi_field(idx, agg_call.arg("_field"))
                     if agg_call is not None else None)
        depth = agg_field.bit_depth if agg_field is not None else 0
        fields_rows = list(zip(fields, row_lists))
        if call.arg("filter") is not None and _prunes(fields_rows, depth,
                                                      eng._selects()):
            # the prepass must run before the walk's shape is known
            raise Unstackable("a pruned GroupBy runs solo")
        combos = product_combos([len(rl) for rl in row_lists])
        skey = tuple(shards)
        if not eng._groupby_onepass_ok(
                idx, fields_rows, len(combos), depth,
                agg_field is not None, skey):
            raise Unstackable("groupby shape not one-pass batchable")
        bits, shifts, n_codes = _code_space(fields_rows)
        codes = _combo_codes(shifts, combos)
        digits = _code_digits(fields_rows)
        signed = False
        if agg_field is not None:
            frags = eng._frags(idx, agg_field, agg_field.bsi_view,
                               list(skey))
            signed = any(fr is not None and 1 in fr.row_ids
                         for fr in frags)
        arm, body, passes = _onepass_plan(n_codes, depth, digits, signed)
        if arm != "xla":
            from pilosa_tpu.memory import placement as _placement
            if (eng._n_total_devices() > 1
                    or _placement.mesh_devices() > 1):
                # mirror the solo path's mesh guard: a pallas_call
                # over mesh-sharded leaves inside the fused multi (or
                # shard_map ragged_mesh) program would force a gather
                # (or fail to lower and demote every rider in the
                # batch); the scatter reference shards under GSPMD
                arm = "xla"
        filter_call = call.arg("filter")
        tree = None
        if filter_call is not None:
            tree = b.build(filter_call)
            if tree == ("zeros",):
                r.result = [[]]
                return None
        cg_i = b._groupcode_leaf(fields_rows)
        planes_i = (b._planes_leaf(agg_field)
                    if agg_field is not None else None)
        _count_onepass((arm, body, passes), "batched", depth,
                       _walked(arm, body, digits, n_codes))
        has_planes = agg_field is not None

        def demux_groupby(out):
            counts, nn, pos, neg = _onepass_unpack(
                np.asarray(out), n_codes, depth, has_planes)
            agg_nn = agg_pos = agg_neg = None
            if has_planes:
                agg_nn, agg_pos, agg_neg = nn[codes], pos[codes], \
                    neg[codes]
            return [ex._assemble_groupby(
                fields, row_lists, combos, counts[codes], agg_field,
                "sum", agg_nn, agg_pos, agg_neg, None, None, None,
                None)]
        return (("gb_hist", cg_i, tree, planes_i, n_codes, signed,
                 arm, digits), demux_groupby)

    def _row_result(self, idx, shards: list[int], words) -> RowResult:
        """Mirror Executor._bitmap_result + the translateResults key
        attachment for a fused bitmap query."""
        out = RowResult(idx.width)
        if words is not None:
            for i, shard in enumerate(shards):
                if words[i].any():
                    out.segments[shard] = words[i]
        if idx.keys:
            out.keys = idx.column_translator.translate_ids(out.columns())
        return out

    # -- solo path with cache store ------------------------------------

    def _exec_and_cache(self, index, idx, q, shards, fields, key,
                        snap=None, fl=None):
        """Solo execution with the store protocol: snapshot before,
        execute, store only if the snapshot held.  `snap`, when
        given, must have been taken pre-execution on this path."""
        ex = self.executor
        if self.cache is None or fields is None:
            return ex.execute(index, q, shards)
        sset = _shard_set(shards)
        if snap is None:
            snap = field_snapshot(idx, fields, sset)
        t0 = time.perf_counter()
        results = ex.execute(index, q, shards)
        cost = None
        if _stats.enabled():
            cost = _stats.est_recompute_ms(_fingerprint(key))
            if cost is None:  # cold fingerprint: the run we just paid
                cost = (time.perf_counter() - t0) * 1e3
        # store only if no write raced the execution (a racing write
        # would make the cached value's snapshot provenance unclear)
        if field_snapshot(idx, fields, sset) == snap:
            self.cache.put(key, fields, snap, results, cost_ms=cost)
            # audit tap ONLY on held snapshots: a raced execution has
            # no provable provenance and sampling it could produce a
            # shadow false positive
            results = _audit.tap(self.audit, index, idx, q, shards,
                                 key, fields, snap, "solo", results,
                                 fl)
        return results

    @staticmethod
    def _recompute_cost(key, acc) -> float | None:
        """Recompute-cost hint for a cache entry: the fingerprint
        profile's NON-CACHED estimate (est_recompute_ms — the serve
        EWMA would be talked down to ~0 by the cache's own hits for
        exactly the entries most worth keeping), else the
        leader-attributed phase time of the serve that produced it;
        None (pure LRU) with the statistics catalog disabled."""
        if not _stats.enabled():
            return None
        cost = _stats.est_recompute_ms(_fingerprint(key))
        if cost is None and acc is not None:
            cost = acc.root_s * 1e3
        return cost


def _pure_tree(call: Call) -> bool:
    """True when a bitmap tree uses only calls the PlanBuilder can
    express without per-query precompute or key-dependent leaves."""
    if call.name not in _PURE_BITMAP:
        return False
    if any(isinstance(v, Call) for v in call.args.values()):
        return False
    return all(_pure_tree(c) for c in call.children)
