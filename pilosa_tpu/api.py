"""API facade — every externally visible operation as one method.

Reference: ``API`` struct (api.go:45) — the single entry point the
HTTP/gRPC handlers call into: Query (api.go:209), schema CRUD
(api.go:254-477), imports (api.go:618,1438,1771), status/info, backup
snapshots (api.go:1265).  The TPU build keeps the same facade shape
over Holder + Executor + SQLEngine, plus JSON serialization of every
result type (the handler-side marshaling of http_handler.go).
"""

from __future__ import annotations

import datetime as dt
import os
import threading
import time
from decimal import Decimal

import numpy as np

from pilosa_tpu import __version__
from pilosa_tpu.executor.executor import ExecError, Executor
from pilosa_tpu.executor.results import (
    DistinctValues,
    ExtractedTable,
    GroupCount,
    Pair,
    RowResult,
    SortedRow,
    ValCount,
)
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.index import EXISTENCE_FIELD
from pilosa_tpu.models.schema import FieldOptions
from pilosa_tpu.obs import flight, metrics
from pilosa_tpu.obs.tracing import RecordingTracer, Tracer, start_span
from pilosa_tpu.pql.parser import ParseError
from pilosa_tpu.sql.lexer import SQLError
from pilosa_tpu.sql.engine import SQLEngine


class ApiError(Exception):
    def __init__(self, msg: str, status: int = 400):
        super().__init__(msg)
        self.status = status


class QueryHistoryEntry:
    __slots__ = ("index", "query", "start", "duration")

    def __init__(self, index, query, start, duration):
        self.index = index
        self.query = query
        self.start = start
        self.duration = duration

    def to_dict(self):
        return {"index": self.index, "query": self.query,
                "start": self.start, "runtime_ns": int(self.duration * 1e9)}


class API:
    """Facade over the engine (api.go:45 analog)."""

    def __init__(self, holder: Holder, name: str = "node0"):
        self.holder = holder
        self.name = name
        self.executor = Executor(holder)
        # SQL shares the API's executor (ISSUE 13): one serving
        # layer, one stack/result cache, one HBM ledger client for
        # both query surfaces
        self.sql_engine = SQLEngine(holder, executor=self.executor)
        self.start_time = time.time()
        self._history: list[QueryHistoryEntry] = []
        self._hist_lock = threading.Lock()
        self.history_keep = 100
        # long-query log (server.go:201 OptServerLongQueryTime): any
        # query slower than this (seconds) is logged with its span
        # timings and kept in a ring for /debug/long-queries.
        # 0 disables.
        self.long_query_time: float = 0.0
        self._long_queries: list[dict] = []
        from pilosa_tpu.obs.logger import StderrLogger
        self.logger = StderrLogger()
        # imports serialize per index, the analog of the reference's
        # one-writer-per-shard RBF write transaction (api.go:618 under
        # Qcx write Tx); concurrent ingest still parallelizes batching
        # and key translation outside this lock
        self._import_locks: dict[str, threading.Lock] = {}
        self._import_locks_mu = threading.Lock()
        # cluster-wide exclusive transactions (transaction.go:20);
        # backup holds one while streaming files (ctl/backup.go:30)
        from pilosa_tpu.cluster.txn import TransactionManager
        self.txns = TransactionManager()
        # online-resharding write fence (cluster/rebalance.py
        # FenceTable), installed by ClusterNode; None on plain
        # single-node servers — every check below is a no-op then
        self.fences = None

    def _check_writable(self):
        """Writes are refused while an exclusive transaction is active
        (transaction.go: backup quiesces the cluster)."""
        if self.txns.exclusive_active():
            raise ApiError(
                "cluster is read-only: exclusive transaction active", 409)

    # -- online-resharding fence seams (ISSUE 14) ----------------------

    def _fence_import(self, index: str, cols):
        """Import-path fence admission: MOVED shards raise the typed
        410 redirect (nothing was applied — re-issuing at the new
        owner is safe), FENCING shards wait out the flip, and the
        import registers IN FLIGHT until its finalizer runs — the
        controller's drain ("every write admitted under the old epoch
        finished on the donor") waits on exactly this registration,
        so a write that slipped past the check still lands in the
        delta log before the final chase ships it.  Returns the
        finalizer, or None on non-cluster servers.

        Registration is UNCONDITIONAL on cluster nodes (not gated on
        a fence being armed): a write admitted moments BEFORE the
        fence begins must already be visible to the drain barrier."""
        if self.fences is None:
            return None
        width = self.holder.width
        shards = ({int(c) // width for c in cols}
                  if cols is not None and len(cols) else set())
        tok = self.fences.enter_write(index, shards)
        return lambda: self.fences.exit_write(tok)

    def _fenced_import(self, index: str, cols):
        """Context-manager form of :meth:`_fence_import` — the one
        place the admit/register/finalize protocol lives for every
        import-shaped write surface (a site that skips it silently
        breaks the rebalance drain barrier)."""
        import contextlib

        @contextlib.contextmanager
        def guard():
            done = self._fence_import(index, cols)
            try:
                yield
            finally:
                if done is not None:
                    done()
        return guard()

    def _fence_read_shards(self, index: str, shards):
        """Read-side fence admission: MOVED shards redirect/re-plan,
        and the read registers in flight so RELEASE cannot pop the
        donor's fragments under a running scan (a mid-scan free would
        silently under-count — caught by the concurrent-storm drill).
        Returns the finalizer, or None on non-cluster servers.

        Registration is UNCONDITIONAL on cluster nodes: a read
        admitted BEFORE the fence begins can outlive the whole
        fence→flip→release window on a loaded box, and gating the
        registration on an armed fence made exactly those reads
        invisible to the release drain (reproduced as an undercount
        in the back-to-back join+drain hammer)."""
        if self.fences is None:
            return None
        tok = self.fences.enter_read(index, shards)
        return lambda: self.fences.exit_read(tok)

    def _fence_write_query(self, index: str, pql: str):
        """PQL-write fence guard: admit (blocking out a FENCING flip,
        410-ing MOVED shards) and register the write in flight so the
        controller's drain is a real barrier.  Returns a finalizer,
        or None on non-cluster servers (registration is unconditional
        on cluster nodes — see _fence_import).  With no fence armed
        the write registers as the index WILDCARD (drains always wait
        on wildcards, so the barrier stays exact) instead of paying a
        second PQL parse on every steady-state write."""
        if self.fences is None:
            return None
        shards = set()
        if self.fences.active():
            try:
                from pilosa_tpu.pql import parse
                q = parse(pql) if isinstance(pql, str) else pql
                for c in q.calls:
                    col = c.args.get("_col")
                    if isinstance(col, int) \
                            and not isinstance(col, bool):
                        shards.add(col // self.holder.width)
            except Exception:
                pass  # unparseable -> executor raises its own 400
        tok = self.fences.enter_write(index, shards)
        return lambda: self.fences.exit_write(tok)

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------

    def query(self, index: str, pql: str, shards: list[int] | None = None,
              profile: bool = False, remote: bool = False,
              qos=None) -> dict:
        """PQL query (api.go:209 API.Query).  Returns the full
        QueryResponse dict: {"results": [...]} (+"profile" spans when
        requested, tracing/tracing.go:22-50 behavior).  ``qos``
        (executor/sched.py QoS) carries the request's tenant/priority/
        deadline admission intent from the transport headers."""
        t0 = time.time()
        from pilosa_tpu.pql import is_write_query
        fence_done = None
        # the first parse of the string (memoized: ServingLayer's own
        # `pql.parse` stage then reads the cache)
        with flight.stage("pql.parse"):
            is_write = is_write_query(pql)
        if is_write:
            self._check_writable()
            # online-resharding fence (ISSUE 14): a write to a MOVED
            # shard answers 410 + new owner, a write racing a FENCE
            # flip blocks until the flip resolves, and the write
            # registers in flight so the controller's drain barrier
            # covers it (no-op on unfenced nodes)
            fence_done = self._fence_write_query(index, pql)
        else:
            # reads of a MOVED shard redirect/re-plan instead of
            # serving the donor's released (or stale) copy; live
            # reads register so RELEASE drains them first
            fence_done = self._fence_read_shards(index, shards)
        tracer = None
        # a slow-query threshold records spans for every query so the
        # long-query log can include per-phase timings (server.go:201)
        want_trace = profile or self.long_query_time > 0
        if want_trace:
            from pilosa_tpu.obs import tracing as _tr
            tracer = RecordingTracer()
            prev = _tr.push_thread_tracer(tracer)
        try:
            try:
                # Profile=true rides the serving path too: the query's
                # TraceContext travels into the batch leader, which
                # records the fused device phases (compile / upload /
                # execute, per subquery) back into THIS thread's span
                # tree (obs.tracing.capture_context / span_into) — a
                # profiled query no longer forfeits batching, and its
                # profile shows what the batch actually did.
                results = self.executor.execute_serving(
                    index, pql, shards, remote=remote, qos=qos)
            except (ExecError, ParseError, ValueError, KeyError) as e:
                raise ApiError(str(e), 400)
        finally:
            if want_trace:
                _tr.pop_thread_tracer(prev)
            if fence_done is not None:
                fence_done()
        with flight.stage("result.encode"):
            resp = {"results": [serialize_result(r) for r in results]}
            for r in results:
                if isinstance(r, list) and r and isinstance(r[0], GroupCount):
                    metrics.GROUPBY_REPLY_GROUPS.inc(len(r))
                    flight.note_reply_groups(len(r))
        if profile and tracer.roots:
            resp["profile"] = [s.to_dict() for s in tracer.roots]
        self._record_history(index, pql, t0, tracer)
        return resp

    def sql(self, statement: str, auth_check=None, qos=None) -> dict:
        """SQL query (http_handler.go:1440 /sql).  Returns
        {"schema": {"fields": [...]}, "data": [...]} like the
        reference's SQL response shape.  auth_check, when set, gates
        each statement's table access (Authorizer.sql_check).  ``qos``
        carries the /sql request's tenant/priority/deadline admission
        intent (executor/sched.py QoS); typed shed/deadline errors
        (503/504) propagate to the transport with their status."""
        metrics.SQL_TOTAL.inc()
        t0 = time.time()
        try:
            res = self.sql_engine.query_one(
                statement, auth_check=auth_check,
                write_guard=self._check_writable, qos=qos)
        except (ExecError, SQLError, ParseError, ValueError, KeyError) as e:
            raise ApiError(str(e), 400)
        self._record_history("", statement, t0)
        return {
            "schema": {"fields": [{"name": n, "type": t}
                                  for n, t in res.schema]},
            "data": [[_json_value(v) for v in row] for row in res.rows],
        }

    def _record_history(self, index, query, t0, tracer=None):
        dur = time.time() - t0
        e = QueryHistoryEntry(index, query, t0, dur)
        with self._hist_lock:
            self._history.append(e)
            if len(self._history) > self.history_keep:
                self._history.pop(0)
        if 0 < self.long_query_time <= dur:
            entry = e.to_dict()
            if tracer is not None and tracer.roots:
                entry["spans"] = [s.to_dict() for s in tracer.roots]
            with self._hist_lock:
                self._long_queries.append(entry)
                if len(self._long_queries) > self.history_keep:
                    self._long_queries.pop(0)
            self.logger.warn(
                "long query (%.1fms > %.0fms) index=%r: %s",
                dur * 1e3, self.long_query_time * 1e3, index,
                str(query)[:200])

    def query_history(self) -> list[dict]:
        """Recent queries (http_handler.go:540 /query-history)."""
        with self._hist_lock:
            return [e.to_dict() for e in reversed(self._history)]

    def long_queries(self) -> list[dict]:
        """Slow-query ring with span timings (/debug/long-queries)."""
        with self._hist_lock:
            return list(reversed(self._long_queries))

    # ------------------------------------------------------------------
    # schema (api.go:254-477)
    # ------------------------------------------------------------------

    def schema(self) -> dict:
        return {"indexes": self.holder.schema()}

    def create_index(self, name: str, keys: bool = False,
                     track_existence: bool = True) -> dict:
        _validate_name(name)
        try:
            idx = self.holder.create_index(
                name, keys=keys, track_existence=track_existence)
        except ValueError as e:
            raise ApiError(str(e), 409)
        self.holder.save_schema()
        return idx.to_dict()

    def delete_index(self, name: str):
        if self.holder.index(name) is None:
            raise ApiError(f"index not found: {name}", 404)
        self.holder.delete_index(name)
        self.holder.save_schema()

    def create_field(self, index: str, field: str,
                     options: dict | None = None) -> dict:
        _validate_name(field)
        idx = self._index(index)
        try:
            opts = FieldOptions.from_dict(options or {})
            f = idx.create_field(field, opts)
        except ValueError as e:
            raise ApiError(str(e), 409)
        self.holder.save_schema()
        return f.to_dict()

    def delete_field(self, index: str, field: str):
        idx = self._index(index)
        if idx.field(field) is None:
            raise ApiError(f"field not found: {field}", 404)
        idx.delete_field(field)
        self.holder.save_schema()

    def apply_schema(self, schema: dict):
        """POST /schema (api.go ApplySchema): idempotent bulk create.
        Validated up front so a bad entry can't leave earlier indexes
        half-created."""
        indexes = schema.get("indexes", [])
        try:
            for ix in indexes:
                _validate_name(ix["name"])
                for fd in ix.get("fields", []):
                    _validate_name(fd["name"])
                    FieldOptions.from_dict(fd.get("options", {}))
        except (KeyError, TypeError, ValueError, AttributeError) as e:
            raise ApiError(f"invalid schema: {e!r}", 400)
        for ix in indexes:
            idx = self.holder.create_index(
                ix["name"], keys=ix.get("keys", False),
                track_existence=ix.get("track_existence", True),
                ok_if_exists=True)
            for fd in ix.get("fields", []):
                opts = FieldOptions.from_dict(fd.get("options", {}))
                idx.create_field(fd["name"], opts, ok_if_exists=True)
        self.holder.save_schema()

    # ------------------------------------------------------------------
    # imports (api.go:618 Import, api.go:1438 ImportValue)
    # ------------------------------------------------------------------

    # distinct-shard cap past which an import's sweep falls back to
    # field granularity: _slices_stale is O(fields x views x shards)
    _SWEEP_SHARDS_MAX = 256

    def sweep_import(self, index: str, fields, cols=None,
                     shards: set | None = None,
                     mark_exists: bool = False) -> None:
        """Narrowed import-time result-cache sweep: evict exactly the
        serving-cache entries whose read set intersects the (field,
        shard) slices a bulk import dirtied — the import-path twin of
        the PR 3 point-write ``_write_targets`` narrowing (entries
        over other shards of the same fields keep serving).  No-op
        without an attached serving cache; lazy get-time validation
        still backstops every write path.  ``mark_exists`` folds
        the existence field into the swept set — every import that
        marked columns dirtied it too."""
        serving = getattr(self.executor, "serving", None)
        if serving is None or serving.cache is None:
            return
        idx = self.holder.index(index)
        if idx is None:
            return
        fields = set(fields)
        if mark_exists:
            fields.add(EXISTENCE_FIELD)
        if shards is None and cols is not None and len(cols):
            u = np.unique(np.asarray(cols, dtype=np.int64)
                          // idx.width)
            if u.size <= self._SWEEP_SHARDS_MAX:
                shards = {int(s) for s in u}
        serving.cache.sweep(self.holder, fields, shards)
        metrics.RESULT_CACHE.inc(outcome="write")
        standing = getattr(serving, "standing", None)
        if standing is not None:
            # maintained subscriptions advance off the same landed
            # delta the sweep just declared
            standing.on_write(index, fields, shards)

    def import_bits(self, index: str, field: str, rows=None, cols=None,
                    row_keys=None, col_keys=None, timestamps=None,
                    clear: bool = False,
                    mark_exists: bool = True) -> int:
        self._check_writable()
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        metrics.IMPORT_TOTAL.inc(index=index)
        rows = self._translate_rows(f, rows, row_keys)
        cols = self._translate_cols(idx, cols, col_keys)
        if len(rows) != len(cols):
            raise ApiError("rows and columns length mismatch", 400)
        with self._fenced_import(index, cols), \
                self._import_lock(index):
            if clear:
                n = 0
                for r, c in zip(rows, cols):
                    n += bool(f.clear_bit(int(r), int(c)))
            else:
                f.import_bits(rows, cols, timestamps)
                if mark_exists:
                    idx.mark_columns_exist(cols)
                n = len(cols)
                metrics.IMPORTED_BITS.inc(n, index=index)
        if not clear:
            # statistics catalog: incremental per-field row
            # cardinality + shard-skew maintenance (no-op with
            # PILOSA_TPU_STATS=0).  OUTSIDE the import lock — the
            # note does its own np.unique + flushed tail append, and
            # concurrent importers must not queue behind stats I/O
            from pilosa_tpu.obs import stats as _stats
            _stats.note_ingest(index, field, rows=rows, cols=cols,
                               width=idx.width)
        self.sweep_import(index, {field}, cols,
                          mark_exists=mark_exists and not clear)
        return n

    def import_roaring(self, index: str, field: str, shard: int,
                       rows: dict, clear: bool = False) -> int:
        """Roaring-encoded fragment import (api.go:1771 ImportRoaring;
        fragment.importRoaring fragment.go:2038): one official-format
        roaring blob per row id, columns shard-relative.  Returns the
        number of bits set/cleared."""
        import base64
        from pilosa_tpu.storage import roaring
        self._check_writable()
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        metrics.IMPORT_TOTAL.inc(index=index)
        n = 0
        touched = []
        with self._fenced_import(index, [int(shard) * idx.width]), \
                self._import_lock(index):
            for row_s, blob in rows.items():
                row = int(row_s)
                data = base64.b64decode(blob) \
                    if isinstance(blob, str) else blob
                try:
                    cols = roaring.decode(data)
                except Exception as e:
                    # truncated buffers raise struct.error/ValueError
                    # from the codec internals — all client-input 400s
                    raise ApiError(
                        f"bad roaring data for row {row}: {e}", 400)
                if cols.size and int(cols.max()) >= idx.width:
                    raise ApiError(
                        f"column {int(cols.max())} exceeds shard "
                        f"width", 400)
                abs_cols = cols.astype(np.int64) + shard * idx.width
                if clear:
                    for c in abs_cols:
                        f.clear_bit(row, int(c))
                else:
                    f.import_bits([row] * len(abs_cols), abs_cols)
                    touched.extend(abs_cols.tolist())
                n += int(cols.size)
            if not clear and touched:
                idx.mark_columns_exist(touched)
        metrics.IMPORTED_BITS.inc(n, index=index)
        self.sweep_import(index, {field}, shards={int(shard)},
                          mark_exists=True)
        return n

    def export_roaring(self, index: str, field: str, shard: int,
                       row: int) -> bytes:
        """One row's shard segment as official roaring bytes."""
        from pilosa_tpu.models.view import VIEW_STANDARD
        from pilosa_tpu.storage import roaring
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        v = f.views.get(VIEW_STANDARD)
        frag = v.fragment(shard) if v else None
        if frag is None:
            return roaring.encode([])
        return roaring.encode(roaring.from_words(frag.row_words(row)))

    def _import_lock(self, index: str) -> threading.Lock:
        with self._import_locks_mu:
            lk = self._import_locks.get(index)
            if lk is None:
                lk = self._import_locks[index] = threading.Lock()
            return lk

    def import_values(self, index: str, field: str, cols=None, values=None,
                      col_keys=None, clear: bool = False,
                      mark_exists: bool = True) -> int:
        self._check_writable()
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        metrics.IMPORT_TOTAL.inc(index=index)
        cols = self._translate_cols(idx, cols, col_keys)
        if values is None:
            raise ApiError("values required", 400)
        if len(values) != len(cols):
            raise ApiError("columns and values length mismatch", 400)
        with self._fenced_import(index, cols), \
                self._import_lock(index):
            if clear:
                n = 0
                for c in cols:
                    n += bool(f.clear_value(int(c)))
            else:
                f.import_values(cols, values)
                if mark_exists:
                    idx.mark_columns_exist(cols)
                n = len(cols)
                metrics.IMPORTED_BITS.inc(n, index=index)
        if not clear:
            # statistics catalog: value min/max + shard skew from the
            # BSI ingest path (outside the import lock, see
            # import_bits)
            from pilosa_tpu.obs import stats as _stats
            _stats.note_ingest(index, field, cols=cols,
                               values=values, width=idx.width)
        self.sweep_import(index, {field}, cols,
                          mark_exists=mark_exists and not clear)
        return n

    def mark_columns_exist(self, index: str, cols) -> None:
        """Mark record existence once for a whole columnar batch —
        the per-field imports skip it via mark_exists=False so N
        fields don't re-mark the same ids N times (the ingest
        hotspot measured r04)."""
        with self._fenced_import(index, cols):
            self._index(index).mark_columns_exist(cols)
        self.sweep_import(index, set(), cols, mark_exists=True)

    def clear_field_columns(self, index: str, field: str, cols,
                            mark_exists: bool = True) -> int:
        """Drop EVERY stored bit `field` holds for the given columns,
        across all views — the record-level field clear an explicit
        NULL in an INSERT tuple performs for bool/mutex fields
        (statements.apply_record's clear_field, the reference
        batcher's clear-then-set path).  mark_exists keeps the
        record's existence: (id, NULL) still inserts the record."""
        from pilosa_tpu.ops import bitmap as bm_ops
        self._check_writable()
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        by_shard: dict[int, list[int]] = {}
        for c in cols:
            by_shard.setdefault(int(c) // idx.width, []).append(
                int(c) % idx.width)
        with self._fenced_import(index, cols), \
                self._import_lock(index):
            for shard, local in by_shard.items():
                mask = bm_ops.from_columns(local, idx.width)
                for v in f.views.values():
                    frag = v.fragment(shard)
                    if frag is not None:
                        frag.clear_columns(mask)
            if mark_exists:
                idx.mark_columns_exist(cols)
        self.sweep_import(index, {field}, cols,
                          mark_exists=mark_exists)
        return len(cols)

    def import_columns(self, index: str, cols, bits: dict | None = None,
                       values: dict | None = None,
                       workers: int = 4) -> int:
        """Columnar multi-field import: one shared column-id array,
        `bits` mapping set/mutex field -> row-id array and `values`
        mapping BSI field -> value array, imported with per-field
        THREAD concurrency (the in-process analog of the reference's
        per-ingester clone concurrency, idk/ingest.go:302 — fields
        write disjoint fragments, and the numpy kernels release the
        GIL).  Existence is marked once."""
        from concurrent.futures import ThreadPoolExecutor
        self._check_writable()
        idx = self._index(index)
        jobs = []
        for fname, rows in (bits or {}).items():
            f = idx.field(fname)
            if f is None:
                raise ApiError(f"field not found: {fname}", 404)
            jobs.append((f.import_bits, (rows, cols, None)))
        for fname, vals in (values or {}).items():
            f = idx.field(fname)
            if f is None:
                raise ApiError(f"field not found: {fname}", 404)
            jobs.append((f.import_values, (cols, vals)))
        metrics.IMPORT_TOTAL.inc(index=index)
        with self._fenced_import(index, cols), \
                self._import_lock(index):
            if workers > 1 and len(jobs) > 1:
                with ThreadPoolExecutor(max_workers=workers) as pool:
                    futs = [pool.submit(fn, *args)
                            for fn, args in jobs]
                    for fu in futs:
                        fu.result()
            else:
                for fn, args in jobs:
                    fn(*args)
            idx.mark_columns_exist(cols)
        n = len(cols) * len(jobs)
        metrics.IMPORTED_BITS.inc(n, index=index)
        self.sweep_import(index,
                          set(bits or {}) | set(values or {}),
                          cols, mark_exists=True)
        return n

    def _translate_rows(self, f, rows, row_keys):
        if row_keys is not None:
            if not f.options.keys:
                raise ApiError("field does not use row keys", 400)
            m = f.row_translator.create_keys(*row_keys)
            return [m[k] for k in row_keys]
        return rows if rows is not None else []

    def _translate_cols(self, idx, cols, col_keys):
        if col_keys is not None:
            if not idx.keys:
                raise ApiError("index does not use column keys", 400)
            m = idx.column_translator.create_keys(*col_keys)
            return [m[k] for k in col_keys]
        return cols if cols is not None else []

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def info(self) -> dict:
        import pilosa_tpu.shardwidth as sw
        return {
            "shard_width": sw.SHARD_WIDTH,
            "memory": None,
            "cpu_arch": "tpu",
            "version": __version__,
            "uptime_seconds": int(time.time() - self.start_time),
        }

    def version(self) -> dict:
        return {"version": __version__}

    def status(self) -> dict:
        return {
            "state": "NORMAL",
            "node": {"id": self.name, "is_primary": True},
            "local_id": self.name,
            "cluster_name": "pilosa-tpu",
            "indexes": sorted(self.holder.indexes),
        }

    # ------------------------------------------------------------------
    # transactions (api.go Transactions/StartTransaction; transaction.go)
    # ------------------------------------------------------------------

    def start_transaction(self, id=None, exclusive: bool = False,
                          timeout: float | None = None) -> dict:
        from pilosa_tpu.cluster.txn import TransactionError
        try:
            return self.txns.start(id=id, timeout=timeout,
                                   exclusive=exclusive).to_dict()
        except TransactionError as e:
            raise ApiError(str(e), 409)

    def finish_transaction(self, tid: str) -> dict:
        from pilosa_tpu.cluster.txn import TransactionError
        try:
            return self.txns.finish(tid).to_dict()
        except TransactionError as e:
            raise ApiError(str(e), 404)

    def get_transaction(self, tid: str) -> dict:
        from pilosa_tpu.cluster.txn import TransactionError
        try:
            return self.txns.get(tid).to_dict()
        except TransactionError as e:
            raise ApiError(str(e), 404)

    # ------------------------------------------------------------------
    # backup / restore (ctl/backup.go, ctl/restore.go; RBF files are
    # the checkpoint source of truth — SURVEY §5.4)
    # ------------------------------------------------------------------

    def _safe_rel_path(self, rel: str) -> str:
        if not self.holder.path:
            raise ApiError("node has no data directory", 400)
        base = os.path.abspath(self.holder.path)
        p = os.path.abspath(os.path.normpath(os.path.join(base, rel)))
        if not p.startswith(base + os.sep):
            raise ApiError(f"path escapes data directory: {rel}", 400)
        return p

    def backup_manifest(self) -> dict:
        """Flush + list every data file (schema, RBF shards + WALs,
        translate stores) relative to the data directory."""
        if not self.holder.path:
            raise ApiError("node has no data directory", 400)
        self.holder.sync()
        files = []
        for root, _, fns in os.walk(self.holder.path):
            for fn in fns:
                files.append(os.path.relpath(
                    os.path.join(root, fn), self.holder.path))
        return {"schema": self.schema(), "files": sorted(files)}

    def backup_file(self, rel: str) -> bytes:
        p = self._safe_rel_path(rel)
        if not os.path.isfile(p):
            raise ApiError(f"no such backup file: {rel}", 404)
        with open(p, "rb") as f:
            return f.read()

    def restore_file(self, rel: str, data: bytes):
        p = self._safe_rel_path(rel)
        os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
        with open(p, "wb") as f:
            f.write(data)

    def restore_complete(self):
        """Reload the holder from the restored files (the restore
        analog of ctl/restore.go's post-upload reload)."""
        if not self.holder.path:
            raise ApiError("node has no data directory", 400)
        self.holder.close()
        self.holder.indexes = {}
        self.holder.load_schema()
        return {"indexes": sorted(self.holder.indexes)}

    def shard_max(self) -> dict:
        return {ix.name: (max(ix.available_shards)
                          if ix.available_shards else 0)
                for ix in self.holder.indexes.values()}

    def available_shards(self, index: str) -> list[int]:
        """This node's known shard set for one index (the repair peer
        merges these so a rejoin learns shards created while it was
        down)."""
        return sorted(self._index_or_404(index).available_shards)

    # ------------------------------------------------------------------
    # translation sync + replica repair (holder.go:1488-1715 translate
    # syncer; fragment.go checksum blocks)
    # ------------------------------------------------------------------

    def _index_or_404(self, index: str):
        idx = self.holder.index(index)
        if idx is None:
            raise ApiError(f"index not found: {index}", 404)
        return idx

    def translate_partitions(self, index: str) -> list[int]:
        """Partitions of this index's column-key store holding keys."""
        idx = self._index_or_404(index)
        if not idx.keys:
            raise ApiError(f"index {index} is not keyed", 400)
        return idx.column_translator.nonempty_partitions()

    def translate_partition_snapshot(self, index: str,
                                     partition: int) -> dict:
        idx = self._index_or_404(index)
        if not idx.keys:
            raise ApiError(f"index {index} is not keyed", 400)
        return idx.column_translator.partition_snapshot(int(partition))

    def translate_restore_partition(self, index: str, partition: int,
                                    snap: dict) -> dict:
        idx = self._index_or_404(index)
        if not idx.keys:
            raise ApiError(f"index {index} is not keyed", 400)
        idx.column_translator.restore_partition(int(partition), snap)
        return {"restored": int(partition),
                "entries": len(snap.get("entries", []))}

    def field_translate_snapshot(self, index: str, field: str) -> dict:
        idx = self._index_or_404(index)
        f = idx.field(field)
        if f is None or f.row_translator is None:
            raise ApiError(f"no keyed field {field} in {index}", 404)
        return f.row_translator.snapshot()

    def field_translate_restore(self, index: str, field: str,
                                snap: dict) -> dict:
        idx = self._index_or_404(index)
        f = idx.field(field)
        if f is None or f.row_translator is None:
            raise ApiError(f"no keyed field {field} in {index}", 404)
        f.row_translator.restore_snapshot(snap)
        return {"entries": len(snap.get("entries", []))}

    def _fragment_or_404(self, index, field, view, shard, create=False):
        idx = self._index_or_404(index)
        f = idx.field(field)
        if f is None and create and field == EXISTENCE_FIELD:
            # transfer/repair write path: a fresh recipient has no
            # existence field until its first local mark — create it
            # so shipped _exists fragments land
            f = idx._ensure_existence()
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        v = f.view(view, create=create)
        if v is None:
            raise ApiError(f"view not found: {view}", 404)
        frag = v.fragment(int(shard), create=create)
        if frag is None:
            raise ApiError(f"no fragment shard={shard}", 404)
        return frag

    def fragment_views(self, index: str, field: str) -> list[str]:
        idx = self._index_or_404(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        return sorted(f.views)

    def fragment_checksums(self, index: str, field: str, view: str,
                           shard: int) -> dict:
        """Block digests for divergence detection; {} when the
        fragment does not exist (nothing stored => all-empty)."""
        idx = self._index_or_404(index)
        f = idx.field(field)
        v = f.views.get(view) if f else None
        frag = v.fragment(int(shard)) if v else None
        if frag is None:
            return {}
        return {str(b): d for b, d in frag.block_checksums().items()}

    def fragment_block(self, index: str, field: str, view: str,
                       shard: int, block: int) -> dict:
        """One block's rows as base64(zlib(packed words)); {} when the
        fragment does not exist (all-empty: the repair peer then
        clears its diverged rows)."""
        import base64
        import zlib
        idx = self._index_or_404(index)
        f = idx.field(field)
        v = f.views.get(view) if f else None
        frag = v.fragment(int(shard)) if v else None
        if frag is None:
            return {}
        return {str(r): base64.b64encode(
                    zlib.compress(np.ascontiguousarray(w).tobytes())
                ).decode()
                for r, w in frag.block_rows(int(block)).items()}

    def fragment_set_block(self, index: str, field: str, view: str,
                           shard: int, block: int, payload: dict) -> dict:
        import base64
        import zlib
        frag = self._fragment_or_404(index, field, view, shard,
                                     create=True)
        rows = {}
        for r, b64 in payload.items():
            raw = zlib.decompress(base64.b64decode(b64))
            rows[int(r)] = np.frombuffer(raw, dtype=np.uint32)
        frag.set_block_rows(int(block), rows)
        return {"block": int(block), "rows": len(rows)}

    # ------------------------------------------------------------------
    # online resharding transfer surface (ISSUE 14): SNAPSHOT-COPY
    # resumes on block checksums, DELTA-CHASE replays the PR 3 delta
    # log above the copied version as current row contents
    # ------------------------------------------------------------------

    def _fragment_or_none(self, index, field, view, shard):
        idx = self.holder.index(index)
        f = idx.field(field) if idx is not None else None
        v = f.views.get(view) if f is not None else None
        return v.fragment(int(shard)) if v is not None else None

    def fragment_state(self, index: str, field: str, view: str,
                       shard: int) -> dict:
        """One round-trip COPY bootstrap: the donor fragment's
        (gen, version) captured BEFORE the block reads — so a chase
        from ``version`` covers every write concurrent with the
        copy — plus its block checksums for the resumable diff."""
        frag = self._fragment_or_none(index, field, view, shard)
        if frag is None:
            return {"absent": True}
        gen, version = frag.gen, frag.version
        return {"gen": gen, "version": version,
                "checksums": {str(b): d
                              for b, d in frag.block_checksums().items()}}

    def fragment_deltas(self, index: str, field: str, view: str,
                        shard: int, since: int) -> dict:
        """DELTA-CHASE feed: the current contents of every row the
        delta log names above ``since``.  ``covered=False`` means the
        log cannot prove coverage (overflowed window / version from
        another incarnation) and the caller must fall back to a
        checksum-diff round."""
        frag = self._fragment_or_none(index, field, view, shard)
        if frag is None:
            return {"absent": True}
        gen, version, count, rows = frag.delta_export(int(since))
        if rows is None:
            return {"covered": False, "gen": gen, "version": version}
        import base64
        import zlib
        payload = {str(r): base64.b64encode(
                       zlib.compress(
                           np.ascontiguousarray(w).tobytes())).decode()
                   for r, w in rows.items()}
        return {"covered": True, "gen": gen, "version": version,
                "count": count, "rows": payload}

    def fragment_set_rows(self, index: str, field: str, view: str,
                          shard: int, payload: dict) -> dict:
        """Recipient-side chase apply: replace whole rows with the
        donor's current contents (idempotent, always-forward)."""
        import base64
        import zlib
        frag = self._fragment_or_404(index, field, view, shard,
                                     create=True)
        rows = payload.get("rows", payload)
        for r, b64 in rows.items():
            raw = zlib.decompress(base64.b64decode(b64))
            frag.set_row_words(int(r),
                              np.frombuffer(raw, dtype=np.uint32))
        return {"rows": len(rows)}

    # ------------------------------------------------------------------
    # translation (api.go:929-1038 data streaming analogs)
    # ------------------------------------------------------------------

    def translate_keys(self, index: str, field: str | None, keys: list,
                       create: bool = False) -> list:
        idx = self._index(index)
        if field:
            f = idx.field(field)
            if f is None or not f.options.keys:
                raise ApiError("field not found or not keyed", 400)
            tr = f.row_translator
        else:
            if not idx.keys:
                raise ApiError("index does not use keys", 400)
            tr = idx.column_translator
        if create:
            m = tr.create_keys(*keys)
        else:
            m = tr.find_keys(*keys)
        return [int(m[k]) if k in m else None for k in keys]

    def translate_ids(self, index: str, field: str | None,
                      ids: list) -> list:
        idx = self._index(index)
        if field:
            f = idx.field(field)
            if f is None or not f.options.keys:
                raise ApiError("field not found or not keyed", 400)
            tr = f.row_translator
        else:
            if not idx.keys:
                raise ApiError("index does not use keys", 400)
            tr = idx.column_translator
        return tr.translate_ids(ids)

    def _index(self, name: str):
        idx = self.holder.index(name)
        if idx is None:
            raise ApiError(f"index not found: {name}", 404)
        return idx


_NAME_OK = set("abcdefghijklmnopqrstuvwxyz0123456789-_")


def _validate_name(name: str):
    if not name or name[0] not in "abcdefghijklmnopqrstuvwxyz" or \
            not all(c in _NAME_OK for c in name) or len(name) > 230:
        raise ApiError(f"invalid name: {name!r}", 400)


# ----------------------------------------------------------------------
# result serialization (handler-side marshaling)
# ----------------------------------------------------------------------

def _json_value(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, Decimal):
        # JSON number (reference decimal wire shape); exactness is an
        # engine-level property — the wire is display-precision
        return float(v)
    if isinstance(v, dt.datetime):
        # RFC3339-Z (ns-aware) so wire values round-trip through
        # parse_time_ns and render identically on the far side
        from pilosa_tpu.sql.common import rfc3339
        return rfc3339(v)
    if isinstance(v, np.ndarray):
        return [_json_value(x) for x in v.tolist()]
    if isinstance(v, (list, tuple)):
        return [_json_value(x) for x in v]
    return v


def serialize_result(r) -> object:
    """One PQL result → JSON-able object, mirroring the reference's
    QueryResponse marshaling of each result type."""
    if r is None or isinstance(r, (bool, int, float, str)):
        return _json_value(r)
    if isinstance(r, (np.integer, np.floating)):
        return _json_value(r)
    if isinstance(r, RowResult):
        d = {"columns": [int(c) for c in r.columns()]}
        if r.keys is not None:
            d["keys"] = list(r.keys)
        return d
    if isinstance(r, ValCount):
        return {"value": _json_value(r.value), "count": int(r.count)}
    if isinstance(r, DistinctValues):
        return {"values": [_json_value(v) for v in r.values]}
    if isinstance(r, Pair):
        d = {"id": int(r.id), "count": int(r.count)}
        if r.key is not None:
            d["key"] = r.key
        return d
    if isinstance(r, GroupCount):
        d = {"group": [_json_value(g) if not isinstance(g, dict) else
                       {k: _json_value(v) for k, v in g.items()}
                       for g in r.group],
             "count": int(r.count)}
        if r.agg is not None:
            d["agg"] = _json_value(r.agg)
        if r.agg_count is not None:
            d["agg_count"] = _json_value(r.agg_count)
        return d
    if isinstance(r, SortedRow):
        return {"columns": [int(c) for c in r.columns],
                "values": [_json_value(v) for v in r.values]}
    if isinstance(r, ExtractedTable):
        return {"fields": [_json_value(f) if not isinstance(f, dict) else f
                           for f in r.fields],
                "columns": [{k: _json_value(v) for k, v in c.items()}
                            if isinstance(c, dict) else _json_value(c)
                            for c in r.columns]}
    if isinstance(r, (list, tuple)):
        return [serialize_result(x) for x in r]
    if isinstance(r, dict):
        return {k: serialize_result(v) for k, v in r.items()}
    if isinstance(r, np.ndarray):
        return [_json_value(x) for x in r.tolist()]
    return _json_value(r)
