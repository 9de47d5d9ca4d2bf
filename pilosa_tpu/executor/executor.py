"""Executor — PQL call dispatch and per-shard evaluation.

Behavioral port of the reference executor's read/write call dispatch
(executor.go:634-843) with per-shard hot loops on the device kernels:

- bitmap calls (Row/Union/Intersect/Difference/Xor/Not/Shift/All/
  ConstRow) evaluate to packed word tiles per shard via ops.bitmap;
- BSI condition rows (``Row(x > 5)``) and Sum/Min/Max lower to
  ops.bsi comparator/popcount kernels with plan-time predicate
  scaling (decimal/timestamp → scaled ints, ceil/floor per op) and
  out-of-range short-circuits;
- reductions (Count, Sum, ...) combine per-shard device scalars into
  exact Python ints on the host.

Single-host v0: shards iterate in a Python loop; the mesh executor
(parallel/) stacks shard tiles onto a device mesh instead.
"""

from __future__ import annotations

import contextvars
import datetime as dt
import time
from decimal import Decimal
from fractions import Fraction
from math import ceil, floor

import numpy as np
import jax.numpy as jnp

from pilosa_tpu.executor.results import (
    DistinctValues,
    Pair,
    RowResult,
    ValCount,
)
from pilosa_tpu.models import timeq
from pilosa_tpu.obs import flight, metrics
from pilosa_tpu.obs.tracing import start_span
from pilosa_tpu.models.field import FALSE_ROW, TRUE_ROW, Field
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.index import EXISTENCE_FIELD, Index
from pilosa_tpu.models.schema import FieldType
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.ops import kernels
from pilosa_tpu.pql import ast as past
from pilosa_tpu.pql import parse
from pilosa_tpu.pql.ast import Call, Condition, Query


class ExecError(Exception):
    pass


# Calls that write (pql.Call.IsWrite analog).
_WRITE_CALLS = {"Set", "Clear", "Store", "ClearRow", "Delete"}

# True while serving a node-to-node (Remote=true) request whose ids
# were already translated by the coordinator (executor.go opt.Remote)
_REMOTE = contextvars.ContextVar("pilosa_tpu_remote", default=False)


from pilosa_tpu.executor.advanced import AdvancedOps
from pilosa_tpu.executor.stacked import StackedEngine, Unstackable


class Executor(AdvancedOps):
    def __init__(self, holder: Holder):
        self.holder = holder
        # the mesh-integrated stacked engine (executor/stacked.py):
        # bitmap trees run as ONE jitted program over (S, W) shard
        # stacks — the jitted analog of mapReduce (executor.go:6449).
        # The per-shard Python loop below survives only as the
        # fallback for trees the IR can't express.
        self.stacked = StackedEngine(self)
        self.use_stacked = True
        # the serving front (executor/serving.py): cross-query
        # micro-batching + versioned result cache.  None until a
        # server (or bench) opts in via enable_serving().
        self.serving = None

    def enable_serving(self, window_s: float = 0.001,
                       max_batch: int = 32,
                       cache_bytes: int = 64 << 20,
                       batching: bool = True, **qos_kwargs):
        """Attach the serving layer (executor/serving.py): concurrent
        queries coalesce into one device dispatch per admission window
        (ragged cross-index page-table fusion when possible,
        executor/ragged.py) and repeated reads serve from the
        write-version-guarded result cache.  ``qos_kwargs`` forward to
        the admission scheduler (ragged/admission/heavy_slots/
        queue_max/tenant_weights/default_deadline_ms).  Returns the
        layer for introspection."""
        from pilosa_tpu.executor.serving import ServingLayer
        self.serving = ServingLayer(self, window_s=window_s,
                                    max_batch=max_batch,
                                    cache_bytes=cache_bytes,
                                    batching=batching, **qos_kwargs)
        return self.serving

    def execute_serving(self, index_name: str, query: str | Query,
                        shards: list[int] | None = None,
                        remote: bool = False, qos=None) -> list:
        """Serving-path entry: routes through the admission scheduler
        + micro-batcher + result cache when enabled, else plain
        execute().  ``qos`` (executor/sched.py QoS) carries the
        request's tenant/priority/deadline intent."""
        if self.serving is None:
            return self.execute(index_name, query, shards, remote=remote)
        return self.serving.execute(index_name, query, shards,
                                    remote=remote, qos=qos)

    def set_mesh(self, mesh):
        """Place all shard stacks over a jax.sharding.Mesh; cross-
        shard reductions then lower to ICI collectives."""
        self.stacked.set_mesh(mesh)

    # ------------------------------------------------------------------
    # entry point (executor.Execute analog)
    # ------------------------------------------------------------------

    def execute(self, index_name: str, query: str | Query,
                shards: list[int] | None = None,
                remote: bool = False) -> list:
        """remote=True marks a node-to-node call shipping
        pre-translated ids (executor.go opt.Remote): keyed indexes then
        accept raw column ids instead of rejecting them."""
        tok = _REMOTE.set(remote)
        try:
            return self._execute(index_name, query, shards)
        finally:
            _REMOTE.reset(tok)

    def _execute(self, index_name: str, query: str | Query,
                 shards: list[int] | None = None) -> list:
        t0 = time.perf_counter()
        status = "error"
        idx = self.holder.index(index_name)
        # label only with names of real indexes: arbitrary client
        # strings would grow metric cardinality without bound
        known = idx is not None
        # flight record for the SOLO path (no serving layer in front);
        # begin() returns None when one is already open on this thread
        # — the serving layer's direct fallback must not double-record
        fl = flight.begin(index_name, query)
        try:
            if idx is None:
                raise ExecError(f"index not found: {index_name}")
            q = parse(query) if isinstance(query, str) else query
            out = []
            # tracing.StartSpanFromContext analog (executor.go:6450)
            with start_span("executor.Execute", index=index_name) as sp:
                if fl is not None:
                    sp.set_tag("trace_id", fl["trace_id"])
                for c in q.calls:
                    with start_span(f"executor.execute{c.name}"):
                        res = self._execute_call(idx, c, shards)
                    # translateResults analog (executor.go:7519): attach
                    # column keys to row results on keyed indexes
                    if isinstance(res, RowResult) and idx.keys and \
                            getattr(res, "is_row_ids", False) is False:
                        res.keys = idx.column_translator.translate_ids(
                            res.columns())
                    out.append(res)
            status = "ok"
            return out
        finally:
            metrics.QUERY_TOTAL.inc(
                index=index_name if known else "(unknown)", status=status)
            dur = time.perf_counter() - t0
            metrics.QUERY_DURATION.observe(dur)
            flight.commit(fl, dur, route="solo",
                          error=None if status == "ok" else status)

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    def _execute_call(self, idx: Index, call: Call, shards, pre=None):
        name = call.name
        if pre is None:
            pre = self._precompute_nested(idx, call, shards)
        if name == "Options":
            return self._execute_options(idx, call, shards)
        if name in _WRITE_CALLS:
            return self._execute_write(idx, call, pre)
        if name == "Count":
            return self._reduce_count(idx, self._only_child(call), shards, pre)
        if name == "Sum":
            return self._execute_sum(idx, call, shards, pre)
        if name in ("Min", "Max"):
            return self._execute_minmax(idx, call, shards, name == "Min", pre)
        if name in ("MinRow", "MaxRow"):
            return self._execute_minmax_row(idx, call, shards,
                                            name == "MinRow", pre)
        if name == "FieldValue":
            return self._execute_field_value(idx, call)
        if name == "Distinct":
            return self._execute_distinct(idx, call, shards, pre)
        if name == "Rows":
            return self._execute_rows(idx, call, shards)
        if name == "UnionRows":
            return self._execute_union_rows(idx, call, shards)
        if name == "IncludesColumn":
            return self._execute_includes_column(idx, call, shards, pre)
        if name == "Limit":
            return self._execute_limit(idx, call, shards, pre)
        if name == "TopN":
            return self._execute_topnk(idx, call, shards, pre, "n")
        if name == "TopK":
            return self._execute_topnk(idx, call, shards, pre, "k")
        if name == "GroupBy":
            return self._execute_groupby(idx, call, shards, pre)
        if name == "Percentile":
            return self._execute_percentile(idx, call, shards, pre)
        if name == "Sort":
            return self._execute_sort(idx, call, shards, pre)
        if name == "Extract":
            return self._execute_extract(idx, call, shards, pre)
        # bitmap-producing calls
        return self._bitmap_result(idx, call, shards, pre)

    def _only_child(self, call: Call) -> Call:
        if len(call.children) != 1:
            raise ExecError(f"{call.name} requires exactly one subquery")
        return call.children[0]

    def _shard_list(self, idx: Index, shards) -> list[int]:
        if shards is not None:
            return sorted(shards)
        return sorted(idx.available_shards) or [0]

    def _tree_shards(self, idx: Index, shards, pre) -> list[int]:
        """Shard walk for a bitmap tree: the query's shard set plus any
        shards contributed by precomputed cross-shard results (nested
        Distinct row-id bitmaps can land outside the data shards)."""
        out = set(self._shard_list(idx, shards))
        if shards is None:
            for key, res in pre.items():
                if isinstance(key, tuple):
                    if key[0] == "constrow":  # translated column ids
                        out.update(c // idx.width for c in res)
                    continue
                out.update(res.segments)
        return sorted(out)

    def _precompute_nested(self, idx: Index, call: Call, shards) -> dict:
        """Evaluate nested Distinct calls ONCE per query over the
        query's shard set (the reference executes them as separate
        mapReduce passes, executor.go:1820) and cache by call identity
        for the per-shard tree walk."""
        pre: dict[int, RowResult] = {}

        def walk(c: Call, is_root: bool):
            for ch in c.children:
                walk(ch, False)
            for k, v in c.args.items():
                if not isinstance(v, Call):
                    continue
                # a GroupBy aggregate's Count(Distinct(...)) is not a
                # bitmap operand — the aggregate handler consumes that
                # Distinct node itself (executor.go:3918).  Its filter
                # children ARE bitmap operands and still need their
                # own nested precompute.
                if (c.name == "GroupBy" and k == "aggregate"
                        and v.name == "Count" and v.children
                        and v.children[0].name == "Distinct"):
                    for ch in v.children[0].children:
                        walk(ch, False)
                    continue
                walk(v, False)
            if not is_root and c.name == "Distinct":
                # index= redirects the Distinct to ANOTHER index — the
                # cross-index Distinct join (executor.go:1820;
                # defs_join.go distinctjoin PQL:
                # Intersect(Distinct(Row(price > 10), index=orders,
                # field=userid)))
                didx, dshards, dpre = idx, shards, pre
                iname = c.arg("index")
                if iname and iname != idx.name:
                    didx = self.holder.index(iname)
                    if didx is None:
                        raise ExecError(f"index not found: {iname}")
                    # the foreign field's values become COLUMN ids
                    # here, so only an unkeyed int field is coherent
                    # — anything else would silently join garbage
                    # (decimals dropped, keyed row ids mistaken for
                    # columns)
                    df = didx.field(c.arg("_field") or "")
                    if df is None or \
                            df.options.type != FieldType.INT or \
                            df.options.keys:
                        raise ExecError(
                            "cross-index Distinct requires an "
                            "unkeyed int field")
                    dshards, dpre = None, {}
                res = self._execute_distinct(didx, c, dshards, dpre,
                                             raw=True)
                if isinstance(res, DistinctValues):
                    if didx is idx:
                        raise ExecError("BSI Distinct cannot be "
                                        "nested as a bitmap call")
                    # foreign int values are COLUMN ids here
                    res = RowResult.from_columns(
                        [v for v in res.values
                         if isinstance(v, int) and v >= 0],
                        idx.width)
                pre[id(c)] = res
            elif not is_root and c.name == "UnionRows":
                pre[id(c)] = self._execute_union_rows(idx, c, shards)
            elif c.name == "ConstRow":
                # translate string keys ONCE per query, not once per
                # shard in the tree walk (preTranslate analog)
                pre[("constrow", id(c))] = \
                    self._constrow_cols(idx, c)

        walk(call, True)
        return pre

    # ------------------------------------------------------------------
    # bitmap call tree → per-shard tiles (executeBitmapCallShard analog)
    # ------------------------------------------------------------------

    def _bitmap_result(self, idx: Index, call: Call, shards,
                       pre=None) -> RowResult:
        if pre is None:
            pre = self._precompute_nested(idx, call, shards)
        out = RowResult(idx.width)
        tree_shards = self._tree_shards(idx, shards, pre)
        if self.use_stacked:
            try:
                words = self.stacked.words(idx, call, tree_shards, pre)
                metrics.STACKED_QUERIES.inc(path="stacked")
                if words is not None:
                    for i, shard in enumerate(tree_shards):
                        if words[i].any():
                            out.segments[shard] = words[i]
                return out
            except Unstackable:
                metrics.STACKED_QUERIES.inc(path="loop")
        for shard in tree_shards:
            words = np.asarray(self._bitmap_call_shard(idx, call, shard, pre))
            if words.any():
                out.segments[shard] = words
        return out

    def _bitmap_call_shard(self, idx: Index, call: Call, shard: int, pre):
        """Evaluate a bitmap call for one shard → device words (W,)."""
        name = call.name
        if name in ("Row", "Range"):
            return self._row_shard(idx, call, shard)
        if name == "Union":
            return self._nary(idx, call, shard, pre, bm.union,
                              empty_identity=True)
        if name == "Intersect":
            if not call.children:
                raise ExecError("Intersect requires at least one subquery")
            return self._nary(idx, call, shard, pre, bm.intersect)
        if name == "Difference":
            if not call.children:
                raise ExecError("Difference requires at least one subquery")
            return self._nary(idx, call, shard, pre, bm.difference)
        if name == "Xor":
            return self._nary(idx, call, shard, pre, bm.xor,
                              empty_identity=True)
        if name == "Not":
            child = self._only_child(call)
            return bm.difference(
                self._existence_shard(idx, shard),
                self._bitmap_call_shard(idx, child, shard, pre))
        if name == "All":
            return self._existence_shard(idx, shard)
        if name == "Shift":
            child = self._only_child(call)
            n = int(call.arg("n", 1))
            return bm.shift(
                self._bitmap_call_shard(idx, child, shard, pre), n)
        if name == "ConstRow":
            cols = pre.get(("constrow", id(call))) \
                if pre is not None else None
            if cols is None:
                cols = self._constrow_cols(idx, call)
            in_shard = [c % idx.width for c in cols
                        if c // idx.width == shard]
            return jnp.asarray(bm.from_columns(in_shard, idx.width))
        if name in ("Distinct", "UnionRows"):
            # cross-shard calls materialized once per query in
            # _precompute_nested; served per shard from the cache
            return jnp.asarray(pre[id(call)].shard_words(shard))
        raise ExecError(f"unknown or non-bitmap call: {name}")

    def _nary(self, idx, call, shard, pre, op, empty_identity=False):
        if not call.children:
            if empty_identity:
                return jnp.zeros(idx.width // 32, dtype=jnp.uint32)
            raise ExecError(f"{call.name} requires subqueries")
        acc = self._bitmap_call_shard(idx, call.children[0], shard, pre)
        for c in call.children[1:]:
            acc = op(acc, self._bitmap_call_shard(idx, c, shard, pre))
        return acc

    def _existence_shard(self, idx: Index, shard: int):
        if not idx.track_existence:
            raise ExecError(
                "All()/Not() require existence tracking on the index")
        w = idx.existence_row(shard)
        if w is None:
            return jnp.zeros(idx.width // 32, dtype=jnp.uint32)
        return jnp.asarray(w)

    # -- Row in all its forms ------------------------------------------

    def _row_shard(self, idx: Index, call: Call, shard: int):
        fname, cond = call.condition_field()
        if cond is not None:
            return self._bsi_condition_shard(idx, fname, cond, shard)
        fname, row_val = call.field_arg()
        if fname is None:
            raise ExecError("Row() requires a field argument")
        f = idx.field(fname)
        if f is None:
            raise ExecError(f"field not found: {fname}")
        if f.options.type.is_bsi:
            # Row(bsi=5) is equality on the value
            return self._bsi_condition_shard(
                idx, fname, Condition(past.OP_EQ, row_val), shard)
        row_id = self._row_id_for_value(f, row_val)
        if row_id is None:  # unknown row key → empty row
            return jnp.zeros(idx.width // 32, dtype=jnp.uint32)
        views = f.views_for_range(call.arg("from"), call.arg("to"))
        acc = jnp.zeros(idx.width // 32, dtype=jnp.uint32)
        for vn in views:
            v = f.views.get(vn)
            frag = v.fragment(shard) if v else None
            if frag is not None:
                acc = bm.union(acc, frag.device_row(row_id))
        return acc

    def _row_id_for_value(self, f: Field, val, create: bool = False):
        """Resolve a row value to a row id.  String keys go through the
        field's TranslateStore; on the read path a missing key returns
        None (empty row), matching FindKeys semantics."""
        if isinstance(val, bool):
            if f.options.type != FieldType.BOOL:
                raise ExecError(
                    f"bool row value on non-bool field {f.name}")
            return TRUE_ROW if val else FALSE_ROW
        if isinstance(val, str):
            tr = f.row_translator
            if tr is None:
                raise ExecError(
                    f"field {f.name} does not use string keys")
            if create:
                return tr.create_keys(val)[val]
            return tr.find_keys(val).get(val)
        if val is None:
            raise ExecError("null row value")
        if f.options.keys:
            raise ExecError(
                f"field {f.name} uses row keys; got id {val!r}")
        return int(val)

    # -- BSI predicates -------------------------------------------------

    def _bsi_field(self, idx: Index, fname: str) -> Field:
        f = idx.field(fname)
        if f is None:
            raise ExecError(f"field not found: {fname}")
        if not f.options.type.is_bsi:
            raise ExecError(f"field {fname} is not an int-like field")
        return f

    def _scaled_bound(self, f: Field, v, round_up: bool) -> int:
        """Scale a predicate to stored units, rounding the bound
        outward per the comparison op (exact rational arithmetic).
        String bounds coerce by COLUMN type: timestamps for timestamp
        columns, numerics elsewhere ('1.50' on a decimal column is a
        decimal, not a time literal)."""
        if isinstance(v, str):
            if f.options.type == FieldType.TIMESTAMP:
                try:
                    # ns-exact: parse_time would truncate 7-9 digit
                    # fractions to microseconds and shift predicate
                    # boundaries on timeunit-'ns' columns
                    v = timeq.parse_time_ns(v)
                except ValueError as e:
                    raise ExecError(str(e))
            else:
                try:
                    v = Decimal(v)
                except ArithmeticError:
                    raise ExecError(
                        f"cannot parse numeric bound {v!r}")
                if not v.is_finite():
                    raise ExecError(
                        f"numeric bound must be finite: {v!r}")
        if isinstance(v, dt.datetime):
            if f.options.type != FieldType.TIMESTAMP:
                raise ExecError(
                    f"time predicate on {f.options.type.value} field")
            return f.options.timestamp_to_int(v)
        if isinstance(v, bool):
            raise ExecError("bool predicate on int field")
        scale = f.options.scale if f.options.type == FieldType.DECIMAL else 0
        frac = (Fraction(str(v)) if isinstance(v, float)
                else Fraction(v)) * (10 ** scale)
        return ceil(frac) if round_up else floor(frac)

    def _bsi_condition_shard(self, idx: Index, fname: str, cond: Condition,
                             shard: int):
        f = self._bsi_field(idx, fname)
        depth = f.bit_depth
        v = f.views.get(f.bsi_view)
        frag = v.fragment(shard) if v else None
        zeros = jnp.zeros(idx.width // 32, dtype=jnp.uint32)
        if frag is None:
            if cond.value is None and cond.op == past.OP_EQ:
                return self._existence_shard(idx, shard)
            return zeros
        planes = frag.device_planes(depth)

        # null predicates (pql.Call.FieldEquality isNull)
        if cond.value is None:
            if cond.op == past.OP_EQ:    # field == null: no value stored
                return bm.difference(self._existence_shard(idx, shard),
                                     bsi_ops.not_null(planes))
            if cond.op == past.OP_NEQ:   # field != null: not-null
                return bsi_ops.not_null(planes)
            raise ExecError(f"invalid null comparison {cond.op}")

        max_mag = (1 << depth) - 1

        def masks(up):
            return jnp.asarray(bsi_ops.predicate_masks(up, depth))

        if past.is_between(cond):
            lo_raw, hi_raw = cond.value
            lo = self._scaled_bound(f, lo_raw, round_up=True)
            hi = self._scaled_bound(f, hi_raw, round_up=False)
            if cond.op in (past.OP_BTWN_LT_LT, past.OP_BTWN_LT_LTE):
                lo = max(lo, self._scaled_bound(f, lo_raw, round_up=False) + 1)
            if cond.op in (past.OP_BTWN_LT_LT, past.OP_BTWN_LTE_LT):
                hi = min(hi, self._scaled_bound(f, hi_raw, round_up=True) - 1)
            lo, hi = max(lo, -max_mag), min(hi, max_mag)
            if lo > hi:
                return zeros
            return bsi_ops.range_between(
                planes, masks(abs(lo)), masks(abs(hi)),
                jnp.asarray(lo < 0), jnp.asarray(hi < 0))

        op = cond.op
        if op == past.OP_EQ:
            p_lo = self._scaled_bound(f, cond.value, round_up=False)
            p_hi = self._scaled_bound(f, cond.value, round_up=True)
            if p_lo != p_hi or abs(p_lo) > max_mag:
                return zeros
            return bsi_ops.range_eq(planes, masks(abs(p_lo)),
                                    jnp.asarray(p_lo < 0))
        if op == past.OP_NEQ:
            p_lo = self._scaled_bound(f, cond.value, round_up=False)
            p_hi = self._scaled_bound(f, cond.value, round_up=True)
            if p_lo != p_hi or abs(p_lo) > max_mag:
                return bsi_ops.not_null(planes)
            return bsi_ops.range_neq(planes, masks(abs(p_lo)),
                                     jnp.asarray(p_lo < 0))
        if op in (past.OP_LT, past.OP_LTE):
            allow_eq = op == past.OP_LTE
            p = self._scaled_bound(f, cond.value,
                                   round_up=not allow_eq)
            if p > max_mag:
                return bsi_ops.not_null(planes)
            if p < -max_mag:
                return zeros
            return bsi_ops.range_lt(planes, masks(abs(p)),
                                    jnp.asarray(p < 0), allow_eq=allow_eq)
        if op in (past.OP_GT, past.OP_GTE):
            allow_eq = op == past.OP_GTE
            p = self._scaled_bound(f, cond.value,
                                   round_up=allow_eq)
            if p < -max_mag:
                return bsi_ops.not_null(planes)
            if p > max_mag:
                return zeros
            return bsi_ops.range_gt(planes, masks(abs(p)),
                                    jnp.asarray(p < 0), allow_eq=allow_eq)
        raise ExecError(f"unsupported condition op {op}")

    # ------------------------------------------------------------------
    # aggregates
    # ------------------------------------------------------------------

    def _filter_words(self, idx, call, shard, pre):
        """Optional filter child for Sum/Min/Max/Distinct."""
        if call.children:
            return self._bitmap_call_shard(idx, call.children[0], shard, pre)
        return None

    def _reduce_count(self, idx: Index, call: Call, shards, pre) -> int:
        """Count: the whole tree runs as one stacked device program
        with a single (S,) partials fetch; cross-shard totals are
        summed in exact host ints (SURVEY §7 "Exactness")."""
        tree_shards = self._tree_shards(idx, shards, pre)
        if self.use_stacked:
            try:
                n = self.stacked.count(idx, call, tree_shards, pre)
                metrics.STACKED_QUERIES.inc(path="stacked")
                return n
            except Unstackable:
                metrics.STACKED_QUERIES.inc(path="loop")
        words = [self._bitmap_call_shard(idx, call, shard, pre)
                 for shard in tree_shards]
        if not words:
            return 0
        counts = np.asarray(bm.count(jnp.stack(words)), dtype=np.int64)
        return int(counts.sum())

    def _execute_sum(self, idx: Index, call: Call, shards, pre) -> ValCount:
        fname = call.arg("_field")
        if fname is None:
            raise ExecError("Sum requires field=")
        f = self._bsi_field(idx, fname)
        if self.use_stacked:
            try:
                filter_call = call.children[0] if call.children else None
                total, count = self.stacked.bsi_sum(
                    idx, f, filter_call, self._shard_list(idx, shards), pre)
                metrics.STACKED_QUERIES.inc(path="stacked")
                return ValCount(value=f.int_to_value(total), count=count)
            except Unstackable:
                metrics.STACKED_QUERIES.inc(path="loop")
        # queue every shard's device scan, then fetch all per-plane
        # popcounts in one sync (see _reduce_count)
        parts_per_shard = []
        for shard in self._shard_list(idx, shards):
            v = f.views.get(f.bsi_view)
            frag = v.fragment(shard) if v else None
            if frag is None:
                continue
            planes = frag.device_planes(f.bit_depth)
            filt = self._filter_words(idx, call, shard, pre)
            parts_per_shard.append(bsi_ops.sum_counts(planes, filt))
        total, count = 0, 0
        if parts_per_shard:
            cnt = np.asarray(jnp.stack([p[0] for p in parts_per_shard]))
            pos = np.asarray(jnp.stack([p[1] for p in parts_per_shard]))
            neg = np.asarray(jnp.stack([p[2] for p in parts_per_shard]))
            for i in range(len(parts_per_shard)):
                s, c = bsi_ops.host_sum(cnt[i], pos[i], neg[i])
                total += s
                count += c
        return ValCount(value=f.int_to_value(total), count=count)

    def _execute_minmax(self, idx: Index, call: Call, shards,
                        is_min: bool, pre) -> ValCount:
        fname = call.arg("_field")
        if fname is None:
            raise ExecError(f"{call.name} requires field=")
        f = self._bsi_field(idx, fname)
        if self.use_stacked:
            # fused value-histogram fast path (ISSUE 11 byproduct):
            # one single-pass tile walk over the plane stack instead
            # of a per-shard min/max plane walk each
            try:
                filter_call = (call.children[0] if call.children
                               else None)
                pos, neg = self.stacked.bsi_value_hist(
                    idx, f, filter_call, self._shard_list(idx, shards),
                    pre)
                metrics.STACKED_QUERIES.inc(path="stacked")
                return self._minmax_from_hist(f, pos, neg, is_min)
            except Unstackable:
                metrics.STACKED_QUERIES.inc(path="loop")
        best, count = None, 0
        op = bsi_ops.min_op if is_min else bsi_ops.max_op
        for shard in self._shard_list(idx, shards):
            v = f.views.get(f.bsi_view)
            frag = v.fragment(shard) if v else None
            if frag is None:
                continue
            planes = frag.device_planes(f.bit_depth)
            filt = self._filter_words(idx, call, shard, pre)
            val, c = bsi_ops.host_minmax(*op(planes, filt))
            if c == 0:
                continue
            if best is None or (val < best if is_min else val > best):
                best, count = val, c
            elif val == best:
                count += c
        if best is None:
            return ValCount(value=None, count=0)
        return ValCount(value=f.int_to_value(best), count=count)

    @staticmethod
    def _minmax_from_hist(f, pos, neg, is_min: bool) -> ValCount:
        """Min/Max + attaining count straight out of the fused value
        histogram: the extreme nonzero code, negatives preferred for
        Min / non-negatives for Max (fragment.min/max semantics)."""
        pnz, nnz = np.nonzero(pos)[0], np.nonzero(neg)[0]
        if is_min:
            if nnz.size:
                mag = int(nnz[-1])
                return ValCount(value=f.int_to_value(-mag),
                                count=int(neg[mag]))
            if pnz.size:
                mag = int(pnz[0])
                return ValCount(value=f.int_to_value(mag),
                                count=int(pos[mag]))
        else:
            if pnz.size:
                mag = int(pnz[-1])
                return ValCount(value=f.int_to_value(mag),
                                count=int(pos[mag]))
            if nnz.size:
                mag = int(nnz[0])
                return ValCount(value=f.int_to_value(-mag),
                                count=int(neg[mag]))
        return ValCount(value=None, count=0)

    def _execute_minmax_row(self, idx: Index, call: Call, shards,
                            is_min: bool, pre=None) -> Pair:
        """MinRow/MaxRow (fragment.minRow/maxRow semantics)."""
        fname = call.arg("_field")
        f = idx.field(fname) if fname else None
        if f is None:
            raise ExecError(f"{call.name} requires a field")
        filter_call = call.children[0] if call.children else None
        # per-shard candidate, reduced by row-id preference — counts
        # are NEVER summed across shards (reference reduceFn keeps
        # ONE shard's pair, executor.go:1620), and an UNFILTERED call
        # reports count=1 (a has-value flag, fragment.go:858 minRow:
        # "if filter is nil, it returns minRowID, 1"; defs_keyed.go
        # minrow expects (11, 1) though row 11 spans 3 records).  No
        # stacked fast path: the cross-shard TopN sum would produce
        # the aggregated count the reference never reports.
        best: Pair | None = None
        for shard in self._shard_list(idx, shards):
            v = f.views.get(VIEW_STANDARD)
            frag = v.fragment(shard) if v else None
            if frag is None:
                continue
            rows = sorted(frag.row_ids)
            if not rows:
                continue
            if filter_call is None:
                cand = Pair(id=rows[0] if is_min else rows[-1],
                            count=1)
            else:
                filt = self._bitmap_call_shard(idx, filter_call,
                                               shard, pre)
                cand = None
                for row_id in (rows if is_min else reversed(rows)):
                    c = int(bm.intersection_count(
                        frag.device_row(row_id), filt))
                    if c > 0:
                        cand = Pair(id=row_id, count=c)
                        break
                if cand is None:
                    continue
            if best is None or (cand.id < best.id if is_min
                                else cand.id > best.id):
                best = cand
        return best if best is not None else Pair(id=0, count=0)

    # ------------------------------------------------------------------
    # Distinct / Rows / misc
    # ------------------------------------------------------------------

    def _execute_distinct(self, idx: Index, call: Call, shards,
                          pre=None, raw: bool = False):
        fname = call.arg("_field")
        if fname is None:
            raise ExecError("Distinct requires field=")
        f = idx.field(fname)
        if f is None:
            raise ExecError(f"field not found: {fname}")
        if f.options.type.is_bsi:
            if self.use_stacked and f.bit_depth <= 62:
                try:
                    return self._distinct_bsi_stacked(
                        idx, f, call, shards, pre)
                except Unstackable:
                    pass
            vals: set[int] = set()
            for shard in self._shard_list(idx, shards):
                v = f.views.get(f.bsi_view)
                frag = v.fragment(shard) if v else None
                if frag is None:
                    continue
                filt = self._filter_words(idx, call, shard, pre)
                cols, values = bsi_ops.decode(np.asarray(
                    frag.device_planes(f.bit_depth)))
                if filt is not None:
                    fbits = bsi_ops.unpack_bits_np(np.asarray(filt))
                    values = [val for c, val in zip(cols, values)
                              if fbits[int(c)]]
                vals.update(values)
            return DistinctValues(values=sorted(
                f.int_to_value(v) for v in vals))
        # set-like: distinct row ids with any bit (within filter)
        rows_present: set[int] = set()
        filter_call = call.children[0] if call.children else None
        stacked_done = False
        if self.use_stacked and filter_call is not None:
            # one fused (R, S, W) scan instead of a per-(row, shard)
            # device call each — the TopN candidate machinery reused
            try:
                row_ids = self._all_row_ids(idx, f, shards)
                if row_ids:
                    pairs = self._topnk_stacked(
                        idx, f, row_ids, [VIEW_STANDARD], filter_call,
                        shards, pre, ids=None)
                    rows_present = {p.id for p in pairs}
                stacked_done = True
            except Unstackable:
                pass
        if not stacked_done:
            for shard in self._shard_list(idx, shards):
                v = f.views.get(VIEW_STANDARD)
                frag = v.fragment(shard) if v else None
                if frag is None:
                    continue
                filt = self._filter_words(idx, call, shard, pre)
                for row_id in frag.row_ids:
                    if row_id in rows_present:
                        continue
                    if filt is None:
                        rows_present.add(row_id)
                    elif int(bm.intersection_count(
                            frag.device_row(row_id), filt)) > 0:
                        rows_present.add(row_id)
        res = RowResult.from_columns(rows_present, idx.width)
        res.is_row_ids = True  # row ids, not columns: skip col-key xlate
        if f.options.keys and not raw:
            return DistinctValues(values=sorted(
                k for k in f.row_translator.translate_ids(
                    sorted(rows_present)) if k is not None))
        return res

    def _distinct_bsi_stacked(self, idx: Index, f: Field, call: Call,
                              shards, pre) -> DistinctValues:
        """Distinct over a BSI field on the stacked engine
        (executor.go:2034 re-designed): the fused value histogram
        when the dense value space fits (ISSUE 11 — distinct values
        are the nonzero codes of ONE single-pass tile walk, no
        per-column decode at all), else filter tree as one stacked
        program + the chunked device decode, uniquing in numpy."""
        try:
            pos, neg = self.stacked.bsi_value_hist(
                idx, f, call.children[0] if call.children else None,
                self._shard_list(idx, shards), pre)
            return DistinctValues(values=sorted(
                f.int_to_value(v)
                for v in kernels.distinct_from_hist(pos, neg)))
        except Unstackable:
            pass                      # depth over the dense bound
        skey = tuple(self._shard_list(idx, shards))
        filt_words = None
        if call.children:
            filt_words = self.stacked.words(idx, call.children[0],
                                            list(skey), pre)
            if filt_words is None:      # statically-empty filter
                return DistinctValues(values=[])
        vals: set[int] = set()
        pos = 0
        for chunk_ids, ex, dec in self.stacked.decode_stream(
                idx, f, skey):
            sel = ex
            if filt_words is not None:
                sel = sel & bsi_ops.unpack_bits_np(
                    filt_words[pos:pos + len(chunk_ids)])
            pos += len(chunk_ids)
            if sel.any():
                vals.update(np.unique(dec[sel]).tolist())
        return DistinctValues(values=sorted(
            f.int_to_value(v) for v in vals))

    def _ranged_views(self, f, call: Call) -> list[str]:
        """Views for a Rows/UnionRows call honoring from=/to= time
        bounds (executor.go:4077 executeRowsShard walks the quantum
        views in range)."""
        frm, to = call.arg("from"), call.arg("to")
        try:
            return f.views_for_range(frm, to)
        except ValueError as e:
            raise ExecError(str(e))

    def _rows_ids(self, idx: Index, call: Call, shards) -> list[int]:
        """Rows(field) core returning raw row IDS (executor.
        executeRowsShard basics: column, like, previous, limit)."""
        fname = call.arg("_field")
        f = idx.field(fname) if fname else None
        if f is None:
            raise ExecError("Rows requires a field")
        column = call.arg("column")
        previous = call.arg("previous")
        limit = call.arg("limit")
        if column is not None:
            column = self._col_id(idx, column)
            if column is None:
                return []  # unknown column key matches nothing
        ids: set[int] = set()
        views = self._ranged_views(f, call)  # shard-independent
        for shard in self._shard_list(idx, shards):
            for vn in views:
                v = f.views.get(vn)
                frag = v.fragment(shard) if v else None
                if frag is None:
                    continue
                if column is not None:
                    c = int(column)
                    if c // idx.width != shard:
                        continue
                    ids.update(r for r in frag.row_ids
                               if frag.contains(r, c % idx.width))
                else:
                    ids.update(frag.row_ids)
        like = call.arg("like")
        if like is not None:
            tr = f.row_translator
            if tr is None:
                raise ExecError("Rows(like=) requires a keyed field")
            # PQL Rows(like=) uses the key-filter matcher (like.go);
            # the SQL WHERE planner passes _like_sql for the sql3
            # scalar regex semantics instead
            # (sql3/planner/expression.go:2991)
            from pilosa_tpu.pql.like import like_regex, sql_like_regex
            pat = (sql_like_regex(like) if call.arg("_like_sql")
                   else like_regex(like))
            ids &= set(tr.match(lambda k: pat.match(k) is not None))
        out = sorted(ids)
        if previous is not None:
            prev = previous
            if isinstance(prev, str):
                tr = f.row_translator
                if tr is None:
                    raise ExecError(
                        "string previous= requires a keyed field")
                found = tr.find_keys(prev)
                if prev not in found:
                    raise ExecError(
                        f"previous= key not found: {prev!r}")
                prev = found[prev]
            out = [r for r in out if r > int(prev)]
        if limit is not None:
            out = out[: int(limit)]
        return out

    def _execute_rows(self, idx: Index, call: Call, shards) -> list:
        """Rows(field): row ids, or keys for keyed fields
        (RowIdentifiers.Keys in the reference)."""
        fname = call.arg("_field")
        f = idx.field(fname) if fname else None
        if f is None:
            raise ExecError("Rows requires a field")
        out = self._rows_ids(idx, call, shards)
        if f.options.keys:
            keys = f.row_translator.translate_ids(out)
            return [k if k is not None else r for k, r in zip(keys, out)]
        return out

    def _execute_union_rows(self, idx: Index, call: Call, shards) -> RowResult:
        """UnionRows(Rows(...)): union the row bitmaps named by Rows."""
        out = RowResult(idx.width)
        shard_list = self._shard_list(idx, shards)
        for child in call.children:
            if child.name != "Rows":
                raise ExecError("UnionRows expects Rows() arguments")
            fname = child.arg("_field")
            f = idx.field(fname) if fname else None
            if f is None:
                raise ExecError("Rows requires a field")
            row_ids = self._rows_ids(idx, child, shards)
            views = self._ranged_views(f, child)
            for shard in shard_list:
                acc = jnp.asarray(out.segments.get(
                    shard, bm.empty(idx.width)))
                touched = False
                for vn in views:
                    v = f.views.get(vn)
                    frag = v.fragment(shard) if v else None
                    if frag is None:
                        continue
                    touched = True
                    for r in row_ids:
                        acc = bm.union(acc, frag.device_row(r))
                if not touched:
                    continue
                words = np.asarray(acc)
                if words.any():
                    out.segments[shard] = words
        return out

    def _execute_includes_column(self, idx, call, shards, pre) -> bool:
        col = call.arg("column")
        if col is None:
            raise ExecError("IncludesColumn requires column=")
        col = self._col_id(idx, col)
        if col is None:
            return False
        shard = col // idx.width
        if shards is not None and shard not in set(shards):
            return False
        child = self._only_child(call)
        words = self._bitmap_call_shard(idx, child, shard, pre)
        mask = jnp.asarray(bm.column_bit(col % idx.width, idx.width))
        return bool(bm.any_set(bm.intersect(words, mask)))

    def _execute_limit(self, idx, call, shards, pre) -> RowResult:
        child = self._only_child(call)
        limit = call.arg("limit")
        offset = int(call.arg("offset", 0))
        row = self._bitmap_result(idx, child, shards, pre)
        cols = row.columns()
        end = None if limit is None else offset + int(limit)
        return RowResult.from_columns(cols[offset:end], idx.width)

    def _execute_options(self, idx, call, shards):
        child = self._only_child(call)
        opt_shards = call.arg("shards")
        if opt_shards is not None:
            shards = [int(s) for s in opt_shards]
        return self._execute_call(idx, child, shards)

    # ------------------------------------------------------------------
    # writes (executor.executeSet/executeClear... analogs)
    # ------------------------------------------------------------------

    def _execute_write(self, idx: Index, call: Call, pre=None):
        name = call.name
        if name == "Set":
            return self._execute_set(idx, call)
        if name == "Clear":
            return self._execute_clear(idx, call)
        if name == "Store":
            return self._execute_store(idx, call, pre)
        if name == "ClearRow":
            return self._execute_clear_row(idx, call)
        if name == "Delete":
            return self._execute_delete(idx, call, pre)
        raise ExecError(f"write call not yet supported: {name}")

    def _execute_field_value(self, idx: Index, call: Call) -> ValCount:
        """FieldValue(field=f, column=c): one column's BSI value as
        ValCount(value, 1), count=0 when unset (executor.go:799
        executeFieldValueCall; column keys translate like any read,
        defs_keyed.go fieldvalue)."""
        fname = call.arg("_field") or call.arg("field")
        f = idx.field(fname) if fname else None
        if f is None:
            raise ExecError("FieldValue requires a field")
        if not f.options.type.is_bsi:
            raise ExecError(
                "FieldValue requires an int/decimal/timestamp field")
        col = call.arg("column")
        if col is None:
            raise ExecError("FieldValue requires a column")
        cid = self._col_id(idx, col)
        if cid is None:
            return ValCount(value=None, count=0)
        shard, scol = divmod(int(cid), idx.width)
        v = f.views.get(f.bsi_view)
        frag = v.fragment(shard) if v else None
        if frag is None or not frag.contains(0, scol):
            return ValCount(value=None, count=0)
        mag = sum(1 << i for i in range(f.bit_depth)
                  if frag.contains(2 + i, scol))
        val = f.int_to_value(-mag if frag.contains(1, scol) else mag)
        return ValCount(value=val, count=1)

    def _constrow_cols(self, idx: Index, call: Call) -> list[int]:
        """ConstRow columns with string keys translated (the
        preTranslate analog, executor.go:6814: ConstRow over a keyed
        index takes keys — Extract(ConstRow(columns=['two']), ...),
        defs_keyed.go constrow).  Unknown keys match nothing."""
        out = []
        for c in call.arg("columns", []) or []:
            if isinstance(c, str):
                cid = self._col_id(idx, c)
                if cid is None:
                    continue
                out.append(int(cid))
            else:
                out.append(int(c))
        return out

    def _col_id(self, idx: Index, col, create: bool = False):
        """Resolve a column value (int id or string key) to an id.
        Read path returns None for unknown keys (FindKeys semantics)."""
        if isinstance(col, str):
            tr = idx.column_translator
            if tr is None:
                raise ExecError(
                    f"index {idx.name} does not use column keys")
            if create:
                return tr.create_keys(col)[col]
            return tr.find_keys(col).get(col)
        if idx.keys and not _REMOTE.get():
            raise ExecError(
                f"index {idx.name} uses column keys; got id {col!r}")
        return int(col)

    def _set_col(self, idx: Index, call, create: bool):
        col = call.arg("_col")
        if col is None:
            raise ExecError(f"{call.name} requires a column")
        return self._col_id(idx, col, create)

    def _execute_set(self, idx: Index, call: Call) -> bool:
        col = self._set_col(idx, call, create=True)
        fname, val = call.field_arg()
        if fname is None:
            raise ExecError("Set requires field=value")
        f = idx.field(fname)
        if f is None:
            raise ExecError(f"field not found: {fname}")
        if f.options.type.is_bsi:
            changed = f.set_value(col, val)
        else:
            ts = call.arg("_timestamp")
            changed = f.set_bit(
                self._row_id_for_value(f, val, create=True), col,
                timestamp=timeq.parse_time(ts) if ts else None)
        idx.mark_columns_exist([col])
        return changed

    def _execute_clear(self, idx: Index, call: Call) -> bool:
        col = self._set_col(idx, call, create=False)
        if col is None:
            return False  # unknown column key: nothing to clear
        fname, val = call.field_arg()
        if fname is None:
            raise ExecError("Clear requires field=value")
        f = idx.field(fname)
        if f is None:
            raise ExecError(f"field not found: {fname}")
        if f.options.type.is_bsi:
            return f.clear_value(col)
        row_id = self._row_id_for_value(f, val)
        return False if row_id is None else f.clear_bit(row_id, col)

    def _execute_store(self, idx: Index, call: Call, pre=None) -> bool:
        """Store(Row(...), f=9): write the result bitmap as a row."""
        child = self._only_child(call)
        fname, val = call.field_arg()
        if fname is None:
            raise ExecError("Store requires field=row")
        f = idx.field(fname)
        if f is None:
            f = idx.create_field(fname)
        row_id = self._row_id_for_value(f, val, create=True)
        for shard in self._shard_list(idx, None):
            words = np.asarray(self._bitmap_call_shard(idx, child, shard, pre))
            frag = f.view(VIEW_STANDARD, create=True).fragment(
                shard, create=True)
            frag.set_row_words(row_id, words)
        return True

    def _execute_clear_row(self, idx: Index, call: Call) -> bool:
        fname, val = call.field_arg()
        if fname is None:
            raise ExecError("ClearRow requires field=row")
        f = idx.field(fname)
        if f is None:
            raise ExecError(f"field not found: {fname}")
        row_id = self._row_id_for_value(f, val)
        if row_id is None:
            return False
        changed = False
        for v in f.views.values():
            for frag in v.fragments.values():
                if frag.row_count(row_id):
                    frag.set_row_words(row_id, 0)
                    changed = True
        return changed
