"""Advanced query ops: TopN/TopK, GroupBy, Percentile, Sort, Extract,
Delete.

Reference semantics (behavior, not code):
- TopN/TopK — executor.go:2357-2777, fragment.go:1317-1497.  The
  reference approximates TopN through the per-fragment rank cache
  (cache.go) and merges container iterators per shard; here row
  counts are computed EXACTLY with chunked device batches
  (rows x shards intersection popcounts), which subsumes both calls.
- GroupBy — executor.go:3176-3986, 8617-8940: cartesian product of
  Rows() of each child field, count = intersection count, optional
  filter and Sum aggregate, having on count.
- Percentile — executor.go:1310-1601: binary search on
  Count(Row(field < x)) against desiredLess/desiredGreater.
- Sort — executor.go:9321: columns of a filter ordered by BSI value.
- Extract — executor.go:4758: per-column field values for a filter.
- Delete — removes columns from every field + existence.

All device work is fixed-shape chunked batches; cross-shard and
cross-chunk accumulation happens host-side in exact ints.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np
import jax.numpy as jnp

from pilosa_tpu.executor.results import (
    ExtractedTable,
    GroupCount,
    Pair,
    SortedRow,
    ValCount,
)
from pilosa_tpu.models.field import FALSE_ROW, TRUE_ROW
from pilosa_tpu.models.schema import FieldType
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.executor.stacked import Unstackable
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.pql.ast import Call, Condition

_ROW_CHUNK = 256      # row tiles per device batch in count scans
_SUM_CHUNK = 8        # combo masks per device batch when aggregating BSI


def _trunc_div(a: int, b: int) -> int:
    """Go-style truncating integer division (rounds toward zero)."""
    q = a // b
    if q < 0 and q * b != a:
        q += 1
    return q


class AdvancedOps:
    """Mixin for Executor: the data-dependent query calls."""

    # -- shared helpers -------------------------------------------------

    def _field_views(self, f, from_=None, to=None) -> list[str]:
        if from_ is None and to is None:
            return [VIEW_STANDARD]
        return f.views_for_range(from_, to)

    def _row_tiles(self, f, shard: int, row_ids, views) -> jnp.ndarray:
        """(R, W) stacked tiles for row_ids, unioned across views."""
        acc = None
        for vn in views:
            v = f.views.get(vn)
            frag = v.fragment(shard) if v else None
            if frag is None:
                continue
            tiles = frag.device_rows(list(row_ids))
            acc = tiles if acc is None else bm.union(acc, tiles)
        if acc is None:
            acc = jnp.zeros((len(row_ids), f.width // 32), dtype=jnp.uint32)
        return acc

    def _all_row_ids(self, idx, f, shards) -> list[int]:
        ids: set[int] = set()
        v = f.views.get(VIEW_STANDARD)
        if v is None:
            return []
        for shard in self._shard_list(idx, shards):
            frag = v.fragment(shard)
            if frag is not None:
                ids.update(frag.row_ids)
        return sorted(ids)

    # -- TopN / TopK ----------------------------------------------------

    def _topnk_prepare(self, idx, call: Call, shards, pre, n_key: str):
        """Host half of TopN/TopK: field/view resolution, the rank-
        cache fast paths, and candidate-row selection.  Returns
        ("done", result) when no device scan is needed, else
        ("scan", f, views, row_ids, filter_call, n, ids).  Shared by
        the per-query path below and the cross-query batcher
        (executor/serving.py) so the fused scan stays bit-exact with
        the solo one by construction."""
        fname = call.arg("_field")
        f = idx.field(fname) if fname else None
        if f is None:
            raise self._err(f"{call.name} requires a field")
        n = call.arg(n_key)
        ids = call.arg("ids")
        views = self._field_views(f, call.arg("from"), call.arg("to"))
        filter_call = call.children[0] if call.children else None
        if (ids is None and filter_call is None
                and views == [VIEW_STANDARD]
                and call.name == "TopN"):
            # unfiltered TopN reads counts straight off the per-
            # fragment rank caches — the reference's fragment.top
            # cache path (fragment.go:1317, cache.go) — falling back
            # to the exact scan when any fragment has no cache
            pairs = self._topn_from_caches(idx, f, shards)
            if pairs is not None:
                return ("done", self._finish_topn(f, pairs, n, ids))
        row_ids = ([int(r) for r in ids] if ids is not None
                   else self._all_row_ids(idx, f, shards))
        if (ids is None and call.name == "TopN"
                and views == [VIEW_STANDARD]):
            # ranked caches BOUND the candidate set for the filtered
            # device scan — the reference's entire TopN strategy
            # (fragment.top iterates cache candidates, fragment.go:
            # 1317; cache.go:130): the (R,S,W) scan covers the
            # cache's top rows instead of every row, trading the
            # documented cache approximation for a candidate set
            # independent of field cardinality
            cand = self._candidate_rows_from_caches(idx, f, shards)
            if cand is not None and len(cand) < len(row_ids):
                row_ids = cand
        if not row_ids:
            return ("done", [])
        return ("scan", f, views, row_ids, filter_call, n, ids)

    def _execute_topnk(self, idx, call: Call, shards, pre, n_key: str):
        prep = self._topnk_prepare(idx, call, shards, pre, n_key)
        if prep[0] == "done":
            return prep[1]
        _, f, views, row_ids, filter_call, n, ids = prep
        if getattr(self, "use_stacked", False):
            try:
                pairs = self._topnk_stacked(idx, f, row_ids, views,
                                            filter_call, shards, pre, ids)
            except Unstackable:
                pairs = None
            if pairs is not None:
                return self._finish_topn(f, pairs, n, ids)
        counts = {r: 0 for r in row_ids}
        for shard in self._shard_list(idx, shards):
            filt = (self._bitmap_call_shard(idx, filter_call, shard, pre)
                    if filter_call else None)
            for i in range(0, len(row_ids), _ROW_CHUNK):
                chunk = row_ids[i:i + _ROW_CHUNK]
                tiles = self._row_tiles(f, shard, chunk, views)
                if filt is not None:
                    tiles = bm.intersect(tiles, filt[None, :])
                got = np.asarray(bm.count(tiles), dtype=np.int64)
                for r, c in zip(chunk, got):
                    counts[r] += int(c)
        pairs = [Pair(id=r, count=c) for r, c in counts.items()
                 if c > 0 or ids is not None]
        return self._finish_topn(f, pairs, n, ids)

    # device-batch byte budget for the stacked (R, S, W) row scans.
    # Sized so the design-scale TopN candidate set (16 rows x 954
    # shards x 128 KiB = 2 GiB) runs as ONE device dispatch: every
    # extra chunk is one more dispatch and one more host fetch
    _ROWS_STACK_BUDGET = 1 << 31  # 2 GiB

    def _topnk_stacked(self, idx, f, row_ids, views, filter_call,
                       shards, pre, ids):
        """TopN/TopK candidate scan on the stacked engine: for each
        chunk of candidate rows, ONE fused (R, S, W) AND+popcount
        device pass with the filter tree inlined (executor.go:2750
        topKFilter + mergerator, collapsed into a single program)."""
        eng = self.stacked
        skey = tuple(self._shard_list(idx, shards))
        words = idx.width // 32
        chunk = max(1, self._ROWS_STACK_BUDGET // (max(len(skey), 1)
                                                   * words * 4))
        counts: dict[int, int] = {}
        for i in range(0, len(row_ids), chunk):
            rows = row_ids[i:i + chunk]
            # sparse_raw: on pageable placements the candidate stack
            # arrives as a PageView so an unfiltered scan can serve
            # straight from encode-time lane popcounts (row_counts
            # decodes it per page when a filter tree needs the tiles)
            with eng.sparse_raw():
                stack = eng.rows_stack_for(idx, f, tuple(views), rows,
                                           skey)
            got = eng.row_counts(idx, stack, filter_call, list(skey), pre)
            for r, c in zip(rows, got):
                counts[r] = int(c)
        return [Pair(id=r, count=c) for r, c in counts.items()
                if c > 0 or ids is not None]

    def _topn_from_caches(self, idx, f, shards) -> list | None:
        """Merge per-fragment cache counts; None => no cache, use the
        exact scan."""
        v = f.views.get(VIEW_STANDARD)
        if v is None:
            return []
        counts: dict[int, int] = {}
        for shard in self._shard_list(idx, shards):
            frag = v.fragment(shard)
            if frag is None:
                continue
            cache = frag.row_cache()
            if cache is None:
                return None
            for r, c in cache.top():
                counts[r] = counts.get(r, 0) + c
        return [Pair(id=r, count=c) for r, c in counts.items() if c > 0]

    def _candidate_rows_from_caches(self, idx, f, shards) -> list | None:
        """Union of every shard cache's ranked rows (ascending id for
        deterministic stacking); None when any fragment lacks a
        cache (exact full scan stays)."""
        v = f.views.get(VIEW_STANDARD)
        if v is None:
            return []
        out: set[int] = set()
        for shard in self._shard_list(idx, shards):
            frag = v.fragment(shard)
            if frag is None:
                continue
            cache = frag.row_cache()
            if cache is None:
                return None
            out.update(r for r, _c in cache.top())
        return sorted(out)

    def _finish_topn(self, f, pairs, n, ids):
        pairs.sort(key=lambda p: (-p.count, p.id))
        if n is not None:
            pairs = pairs[: int(n)]
        if f.options.keys:
            keys = f.row_translator.translate_ids([p.id for p in pairs])
            for p, k in zip(pairs, keys):
                p.key = k
        return pairs

    # -- GroupBy --------------------------------------------------------

    def _execute_groupby(self, idx, call: Call, shards, pre):
        rows_calls = [c for c in call.children if c.name == "Rows"]
        if not rows_calls:
            raise self._err("GroupBy requires at least one Rows() child")
        fields, row_lists = [], []
        for rc in rows_calls:
            fname = rc.arg("_field")
            f = idx.field(fname) if fname else None
            if f is None:
                raise self._err("Rows requires a valid field")
            fields.append(f)
            row_lists.append(self._rows_ids(idx, rc, shards))
        if any(not rl for rl in row_lists):
            return []

        filter_call = call.arg("filter")
        agg_call = call.arg("aggregate")
        agg_field = distinct_field = distinct_inner = None
        agg_op = "sum"
        if agg_call is not None:
            if not isinstance(agg_call, Call) or agg_call.name not in (
                    "Sum", "Count", "Min", "Max"):
                raise self._err("GroupBy aggregate must be Sum(...), "
                                "Min(...), Max(...) or "
                                "Count(Distinct(...))")
            if agg_call.name in ("Sum", "Min", "Max"):
                agg_field = self._bsi_field(idx, agg_call.arg("_field"))
                agg_op = agg_call.name.lower()
            else:
                # Count(Distinct(field=D)) (executor.go:3918 aggregate
                # dispatch): per group, the number of distinct values
                # (BSI) or distinct row ids (set-like) of D
                dc = agg_call.children[0] if agg_call.children else None
                if (not isinstance(dc, Call)
                        or dc.name != "Distinct"
                        or dc.arg("_field") is None):
                    raise self._err(
                        "GroupBy Count aggregate requires "
                        "Count(Distinct(field=...))")
                distinct_field = idx.field(dc.arg("_field"))
                if distinct_field is None:
                    raise self._err(
                        f"field not found: {dc.arg('_field')}")
                distinct_inner = (dc.children[0] if dc.children
                                  else None)

        # combo enumeration: the full cartesian product as one (C, nf)
        # index matrix in product order — the same matrix maps 1:1
        # onto the one-pass engine's dense group-code space (each
        # column is a digit, stacked.py/_combo_codes composes the
        # power-of-two strides), so no per-combo Python exists on any
        # path between here and the histogram gather.
        combos = np.indices([len(rl) for rl in row_lists]) \
            .reshape(len(row_lists), -1).T.astype(np.int64)
        shard_list = self._shard_list(idx, shards)

        # previous= paging (executor.go:8617 groupByIterator seek):
        # resume strictly after the given group, in product order —
        # resolved BEFORE any computation so a paged query evaluates
        # only the requested tail of the combo space.  Vectorized
        # lexicographic compare of the id tuples.
        previous = call.arg("previous")
        if previous is not None:
            if len(previous) != len(fields):
                raise self._err(
                    "previous= must have one entry per Rows() child")
            prev_ids = []
            for f, p in zip(fields, previous):
                if isinstance(p, str):
                    tr = f.row_translator
                    if tr is None:
                        raise self._err(
                            "string previous= entry on unkeyed field")
                    found = tr.find_keys(p)
                    if p not in found:
                        raise self._err(f"previous= key not found: {p!r}")
                    p = found[p]
                prev_ids.append(int(p))
            gt = np.zeros(len(combos), dtype=bool)
            eq = np.ones(len(combos), dtype=bool)
            for fi, (rl, pv) in enumerate(zip(row_lists, prev_ids)):
                ids = np.asarray(rl, dtype=np.int64)[combos[:, fi]]
                gt |= eq & (ids > pv)
                eq &= ids == pv
            if not gt.any():
                return []
            combos = combos[int(np.argmax(gt)):]

        counts = agg_nn = agg_pos = agg_neg = agg_vals = None
        if getattr(self, "use_stacked", False) and distinct_field is None:
            try:
                counts, agg = self.stacked.groupby(
                    idx, list(zip(fields, row_lists)), filter_call,
                    agg_field, shard_list, pre, combos, agg_op=agg_op)
                if agg is not None and agg_op in ("min", "max"):
                    agg_nn, agg_vals = agg
                elif agg is not None:
                    agg_nn, agg_pos, agg_neg = agg
            except Unstackable:
                counts = None
        if counts is None:
            if agg_op in ("min", "max"):
                counts, agg_nn, agg_vals = self._groupby_minmax_loop(
                    idx, fields, row_lists, combos, filter_call,
                    agg_field, shard_list, pre, agg_op)
            else:
                counts, agg_nn, agg_pos, agg_neg = self._groupby_loop(
                    idx, fields, row_lists, combos, filter_call,
                    agg_field, shard_list, pre)

        distinct_counts = None
        if distinct_field is not None:
            distinct_counts = self._groupby_count_distinct(
                idx, fields, row_lists, combos, counts, filter_call,
                distinct_inner, distinct_field, shard_list, pre)

        return self._assemble_groupby(
            fields, row_lists, combos, counts, agg_field, agg_op,
            agg_nn, agg_pos, agg_neg, agg_vals, distinct_counts,
            call.arg("having"), call.arg("limit"))

    def _assemble_groupby(self, fields, row_lists, combos, counts,
                          agg_field, agg_op, agg_nn, agg_pos, agg_neg,
                          agg_vals, distinct_counts, having, limit):
        """GroupCount assembly shared by the solo path and the
        serving/ragged batched demux: zero-count combos drop, keys
        translate, aggregates combine (Sum from sign-split plane
        partials; Min/Max from per-group values; Count(Distinct) from
        its own sweep), having/limit apply in combo order."""
        out = []
        for ci, combo in enumerate(combos):
            cnt = int(counts[ci])
            if cnt == 0:
                continue
            group = []
            for f, rl, gi in zip(fields, row_lists, combo):
                entry = {"field": f.name, "row_id": rl[gi]}
                if f.options.keys:
                    entry["row_key"] = f.row_translator.translate_id(rl[gi])
                group.append(entry)
            agg = agg_count = None
            if agg_field is not None and agg_op in ("min", "max"):
                agg_count = int(agg_nn[ci])
                # a group whose columns all lack a value has no
                # min/max (reference fragment.min/max empty scope)
                agg = (agg_field.int_to_value(int(agg_vals[ci]))
                       if agg_count else None)
            elif agg_field is not None:
                total = sum((int(p) - int(g)) << b for b, (p, g) in
                            enumerate(zip(agg_pos[ci], agg_neg[ci])))
                agg = agg_field.int_to_value(total)
                agg_count = int(agg_nn[ci])
            elif distinct_counts is not None:
                agg = agg_count = int(distinct_counts[ci])
            gc = GroupCount(group=group, count=cnt, agg=agg,
                            agg_count=agg_count)
            if having is not None and not self._having_ok(gc, having):
                continue
            out.append(gc)
            if limit is not None and len(out) >= int(limit):
                break
        return out

    def _groupby_loop(self, idx, fields, row_lists, combos, filter_call,
                      agg_field, shard_list, pre):
        """Per-shard fallback for trees the stacked IR can't express."""
        counts = np.zeros(len(combos), dtype=np.int64)
        agg_pos = agg_neg = agg_nn = None
        if agg_field is not None:
            depth = agg_field.bit_depth
            agg_pos = np.zeros((len(combos), depth), dtype=np.int64)
            agg_neg = np.zeros((len(combos), depth), dtype=np.int64)
            agg_nn = np.zeros(len(combos), dtype=np.int64)

        combo_idx = np.array(combos, dtype=np.int64)  # (C, nf)
        for shard in shard_list:
            filt = (self._bitmap_call_shard(idx, filter_call, shard, pre)
                    if filter_call is not None else None)
            tiles_per_field = [
                self._row_tiles(f, shard, rl, [VIEW_STANDARD])
                for f, rl in zip(fields, row_lists)]
            planes = None
            if agg_field is not None:
                v = agg_field.views.get(agg_field.bsi_view)
                frag = v.fragment(shard) if v else None
                if frag is not None:
                    planes = frag.device_planes(agg_field.bit_depth)
            chunk = _SUM_CHUNK if agg_field is not None else _ROW_CHUNK
            for i in range(0, len(combos), chunk):
                sel = combo_idx[i:i + chunk]
                mask = tiles_per_field[0][sel[:, 0]]
                for fi in range(1, len(fields)):
                    mask = bm.intersect(mask, tiles_per_field[fi][sel[:, fi]])
                if filt is not None:
                    mask = bm.intersect(mask, filt[None, :])
                counts[i:i + chunk] += np.asarray(bm.count(mask),
                                                  dtype=np.int64)
                if planes is not None:
                    exists = planes[0][None, :] & mask
                    agg_nn[i:i + chunk] += np.asarray(bm.count(exists),
                                                      dtype=np.int64)
                    sign = planes[1]
                    pos = exists & ~sign[None, :]
                    neg = exists & sign[None, :]
                    mag = planes[2:]
                    # (C, P) per-plane popcounts by sign
                    pos_pc = bm.count(mag[None, :, :] & pos[:, None, :])
                    neg_pc = bm.count(mag[None, :, :] & neg[:, None, :])
                    agg_pos[i:i + chunk] += np.asarray(pos_pc, dtype=np.int64)
                    agg_neg[i:i + chunk] += np.asarray(neg_pc, dtype=np.int64)
        return counts, agg_nn, agg_pos, agg_neg

    def _groupby_minmax_loop(self, idx, fields, row_lists, combos,
                             filter_call, agg_field, shard_list, pre,
                             agg_op: str):
        """Host fallback for GroupBy aggregate=Min/Max — full
        generality (overlapping rows, any depth, any filter tree):
        per shard, decode the BSI values once and reduce each combo's
        member columns in numpy.  The one-pass fused tile walk
        (stacked.groupby agg_op=min/max) is the fast path; this loop
        is the semantics oracle it is pinned against."""
        from pilosa_tpu.ops import bsi as bsi_ops
        counts = np.zeros(len(combos), dtype=np.int64)
        agg_nn = np.zeros(len(combos), dtype=np.int64)
        agg_vals = np.zeros(len(combos), dtype=np.int64)
        reduce_ = np.minimum if agg_op == "min" else np.maximum
        combo_idx = np.array(combos, dtype=np.int64)
        for shard in shard_list:
            filt = (self._bitmap_call_shard(idx, filter_call, shard,
                                            pre)
                    if filter_call is not None else None)
            filt_bits = (bsi_ops.unpack_bits_np(np.asarray(filt))
                         .astype(bool) if filt is not None else None)
            tiles_per_field = [
                self._row_tiles(f, shard, rl, [VIEW_STANDARD])
                for f, rl in zip(fields, row_lists)]
            tile_bits = [bsi_ops.unpack_bits_np(
                np.asarray(t)).astype(bool) for t in tiles_per_field]
            v = agg_field.views.get(agg_field.bsi_view)
            frag = v.fragment(shard) if v else None
            ex = vals = None
            if frag is not None:
                planes = np.asarray(
                    frag.device_planes(agg_field.bit_depth))
                ex = bsi_ops.unpack_bits_np(planes[0]).astype(bool)
                sg = bsi_ops.unpack_bits_np(planes[1]).astype(bool)
                mag = np.zeros(ex.shape, np.int64)
                for p in range(agg_field.bit_depth):
                    mag |= bsi_ops.unpack_bits_np(
                        planes[2 + p]).astype(np.int64) << p
                vals = np.where(sg, -mag, mag)
            for ci in range(len(combos)):
                sel = tile_bits[0][combo_idx[ci, 0]]
                for fi in range(1, len(fields)):
                    sel = sel & tile_bits[fi][combo_idx[ci, fi]]
                if filt_bits is not None:
                    sel = sel & filt_bits
                counts[ci] += int(sel.sum())
                if ex is None:
                    continue
                sele = sel & ex
                n = int(sele.sum())
                if not n:
                    continue
                best = int(reduce_.reduce(vals[sele]))
                agg_vals[ci] = (best if agg_nn[ci] == 0
                                else int(reduce_(agg_vals[ci], best)))
                agg_nn[ci] += n
        return counts, agg_nn, agg_vals

    def _groupby_count_distinct(self, idx, fields, row_lists, combos,
                                counts, filter_call, inner_filter,
                                dfield, shard_list, pre):
        """Count(Distinct(field=D)) per group: distinct BSI values /
        distinct set rows of D among the group's columns, restricted
        by the GroupBy filter AND the Distinct call's own filter child.
        Host numpy over fragment rows + the engine's device-decoded
        value stream (O(shard-chunk) device calls, consumed chunk-by-
        chunk so host memory stays bounded); sets unioned across
        shards.  The caller already trimmed combos to the previous=
        tail, so every nonzero combo here is needed."""
        from pilosa_tpu.ops import bsi as bsi_ops

        nonzero = [ci for ci in range(len(combos))
                   if counts[ci] > 0]
        sets: dict[int, set] = {ci: set() for ci in nonzero}
        is_bsi = dfield.options.type.is_bsi
        if is_bsi and dfield.bit_depth > 62:
            raise self._err("Count(Distinct) unsupported for depth > 62")

        def shard_groups():
            """Yield (shard, ex_row, vals_row) aligned with the decode
            stream's chunking for BSI D; (shard, None, None) otherwise."""
            if not is_bsi:
                for s in shard_list:
                    yield s, None, None
                return
            for chunk_ids, ex, vals in self.stacked.decode_stream(
                    idx, dfield, tuple(shard_list)):
                for i, s in enumerate(chunk_ids):
                    yield s, ex[i], vals[i]

        for shard, ex, vals in shard_groups():
            filt = None
            if filter_call is not None:
                filt = np.asarray(self._bitmap_call_shard(
                    idx, filter_call, shard, pre))
            if inner_filter is not None:
                inner = np.asarray(self._bitmap_call_shard(
                    idx, inner_filter, shard, pre))
                filt = inner if filt is None else filt & inner
            tiles = []
            for f, rl in zip(fields, row_lists):
                v = f.views.get(VIEW_STANDARD)
                frag = v.fragment(shard) if v else None
                tiles.append([
                    frag.row_words(r) if frag is not None
                    else bm.empty(idx.width) for r in rl])
            if not is_bsi:
                v = dfield.views.get(VIEW_STANDARD)
                dfrag = v.fragment(shard) if v else None
                if dfrag is None:
                    continue
                drows = dfrag.row_ids
                dwords = np.stack([dfrag.row_words(r) for r in drows]) \
                    if drows else None
            for ci in nonzero:
                combo = combos[ci]
                mask = tiles[0][combo[0]].copy()
                for fi in range(1, len(fields)):
                    mask &= tiles[fi][combo[fi]]
                if filt is not None:
                    mask &= filt
                if not mask.any():
                    continue
                if is_bsi:
                    bits = bsi_ops.unpack_bits_np(mask) & ex
                    if bits.any():
                        sets[ci].update(np.unique(vals[bits]).tolist())
                else:
                    if dwords is None:
                        continue
                    hit = (dwords & mask[None]).any(axis=1)
                    sets[ci].update(
                        r for r, h in zip(drows, hit) if h)
        out = np.zeros(len(combos), dtype=np.int64)
        for ci, s in sets.items():
            out[ci] = len(s)
        return out

    def _having_ok(self, gc: GroupCount, having) -> bool:
        if not isinstance(having, Call) or having.name != "Condition":
            raise self._err("having must be Condition(...)")
        key, cond = having.condition_field()
        if key not in ("count", "sum"):
            raise self._err(f"having supports count/sum, got {key}")
        val = gc.count if key == "count" else gc.agg
        if val is None:
            raise self._err(
                "having on sum requires aggregate=Sum(field=...)")
        import operator
        from pilosa_tpu.pql import ast as past
        if past.is_between(cond):
            lo, hi = past.between_bounds_inclusive(cond)
            return lo <= val <= hi
        ops = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
               "<=": operator.le, ">": operator.gt, ">=": operator.ge}
        return ops[cond.op](val, cond.value)

    # -- Percentile -----------------------------------------------------

    def _execute_percentile(self, idx, call: Call, shards, pre):
        nth = call.arg("nth")
        if nth is None:
            raise self._err("Percentile(): nth required")
        nth = float(nth)
        if not 0 <= nth <= 100:
            raise self._err("Percentile(): nth must be in [0, 100]")
        fname = call.arg("_field")
        f = self._bsi_field(idx, fname) if fname else None
        if f is None:
            raise self._err("Percentile(): field required")
        filter_call = call.arg("filter")

        def count_cond(op, stored: int) -> int:
            scale = 10 ** (f.options.scale
                           if f.options.type == FieldType.DECIMAL else 0)
            cond = Condition(op, Fraction(stored, scale))
            row = Call("Row", args={f.name: cond})
            tree = (Call("Intersect", children=[row, filter_call])
                    if filter_call is not None else row)
            return self._reduce_count(idx, tree, shards, pre)

        nn_row = Call("Row", args={f.name: Condition("!=", None)})
        total_tree = (Call("Intersect", children=[filter_call, nn_row])
                      if filter_call is not None else nn_row)
        total = self._reduce_count(idx, total_tree, shards, pre)
        if total == 0:
            return None
        desired_less = int(total * nth / 100.0)
        desired_greater = int(total * (100.0 - nth) / 100.0)

        mm_call = Call("Min", args={"_field": f.name},
                       children=[filter_call] if filter_call else [])
        lo_vc = self._execute_minmax(idx, mm_call, shards, True, pre)
        if desired_greater != 0 and desired_less == 0:
            return lo_vc
        mm_call = Call("Max", args={"_field": f.name},
                       children=[filter_call] if filter_call else [])
        hi_vc = self._execute_minmax(idx, mm_call, shards, False, pre)
        if desired_greater == 0:
            return hi_vc

        lo = f.value_to_int(lo_vc.value) if not isinstance(
            lo_vc.value, (int,)) else lo_vc.value
        hi = f.value_to_int(hi_vc.value) if not isinstance(
            hi_vc.value, (int,)) else hi_vc.value
        possible = lo
        broke = False
        while lo < hi:
            # Go-style midpoint without overflow: min/2 + max/2 +
            # (min%2 + max%2)/2 with truncated div/rem
            lo_rem = lo - _trunc_div(lo, 2) * 2
            hi_rem = hi - _trunc_div(hi, 2) * 2
            possible = (_trunc_div(lo, 2) + _trunc_div(hi, 2) +
                        _trunc_div(lo_rem + hi_rem, 2))
            if count_cond("<", possible) > desired_less:
                hi = possible - 1
                continue
            if count_cond(">", possible) > desired_greater:
                lo = possible + 1
                continue
            broke = True
            break
        if not broke:
            # Divergence from the reference: when the search converges
            # without both conditions holding, executor.go:1552 returns
            # the stale last midpoint; we return the converged bound,
            # which is at least as close to the requested percentile.
            possible = lo
        return ValCount(value=f.int_to_value(possible), count=1)

    # -- Sort -----------------------------------------------------------

    def _execute_sort(self, idx, call: Call, shards, pre):
        fname = call.arg("_field") or call.arg("field")
        f = self._bsi_field(idx, fname) if fname else None
        if f is None:
            raise self._err("Sort requires a BSI field")
        desc = bool(call.arg("sort-desc", False))
        filter_call = call.children[0] if call.children else None
        if getattr(self, "use_stacked", False) and f.bit_depth <= 62:
            try:
                return self._sort_stacked(idx, f, desc, filter_call,
                                          call, shards, pre)
            except Unstackable:
                pass
        all_cols, all_vals = [], []
        for shard in self._shard_list(idx, shards):
            v = f.views.get(f.bsi_view)
            frag = v.fragment(shard) if v else None
            if frag is None:
                continue
            cols, vals = bsi_ops.decode(
                np.asarray(frag.device_planes(f.bit_depth)))
            if filter_call is not None:
                filt = np.asarray(self._bitmap_call_shard(
                    idx, filter_call, shard, pre))
                fbits = bsi_ops.unpack_bits_np(filt)
                keep = np.nonzero(fbits[cols])[0]
                cols = cols[keep]
                vals = [vals[i] for i in keep]
            base = shard * idx.width
            all_cols.extend(int(c) + base for c in cols)
            all_vals.extend(vals)
        order = sorted(range(len(all_cols)),
                       key=lambda i: (-all_vals[i] if desc else all_vals[i],
                                      all_cols[i]))
        offset = int(call.arg("offset", 0))
        limit = call.arg("limit")
        end = None if limit is None else offset + int(limit)
        order = order[offset:end]
        return SortedRow(
            columns=[all_cols[i] for i in order],
            values=[f.int_to_value(all_vals[i]) for i in order])

    def _sort_stacked(self, idx, f, desc, filter_call, call, shards, pre):
        """Sort on the stacked engine (executor.go:9321 re-designed):
        the filter tree runs as ONE stacked program, BSI values
        materialize via the chunked device decode (O(shard-chunks)
        device calls), and ordering is one vectorized lexsort — no
        per-column Python anywhere."""
        skey = tuple(self._shard_list(idx, shards))
        filt_words = None
        if filter_call is not None:
            filt_words = self.stacked.words(idx, filter_call,
                                            list(skey), pre)
            if filt_words is None:      # statically-empty filter
                return SortedRow(columns=[], values=[])
        all_cols, all_vals = [], []
        pos = 0
        for chunk_ids, ex, vals in self.stacked.decode_stream(
                idx, f, skey):
            sel = ex
            if filt_words is not None:
                sel = sel & bsi_ops.unpack_bits_np(
                    filt_words[pos:pos + len(chunk_ids)])
            pos += len(chunk_ids)
            si, ci = np.nonzero(sel)
            if si.size:
                bases = np.asarray(chunk_ids, dtype=np.int64)[si] \
                    * idx.width
                all_cols.append(bases + ci)
                all_vals.append(vals[si, ci])
        if not all_cols:
            return SortedRow(columns=[], values=[])
        cols = np.concatenate(all_cols)
        vals_ = np.concatenate(all_vals)
        key = -vals_ if desc else vals_
        order = np.lexsort((cols, key))
        offset = int(call.arg("offset", 0))
        limit = call.arg("limit")
        end = None if limit is None else offset + int(limit)
        order = order[offset:end]
        return SortedRow(
            columns=cols[order].tolist(),
            values=[f.int_to_value(int(x)) for x in vals_[order]])

    # -- Extract --------------------------------------------------------

    def _execute_extract(self, idx, call: Call, shards, pre):
        if not call.children:
            raise self._err("Extract requires a filter call")
        filter_call = call.children[0]
        bad = [c.name for c in call.children[1:] if c.name != "Rows"]
        if bad:
            raise self._err(
                f"Extract children after the filter must be Rows(), got {bad}")
        rows_calls = call.children[1:]
        fnames = []
        for rc in rows_calls:
            fname = rc.arg("_field")
            if fname is None or idx.field(fname) is None:
                raise self._err("Extract Rows() requires a valid field")
            fnames.append(fname)

        if filter_call.name == "Sort":
            # Sort keeps its ordering through Extract (executor.go:4762)
            sorted_row = self._execute_sort(idx, filter_call, shards, pre)
            columns = sorted_row.columns
        else:
            # general dispatch so cross-shard filters (Limit, nested
            # Distinct, ...) work as Extract filters
            row = self._execute_call(idx, filter_call, shards, pre)
            if not hasattr(row, "columns"):
                raise self._err(
                    f"Extract filter must produce a row, got {filter_call.name}")
            columns = row.columns().tolist()

        col_values: dict[int, list] = {c: [] for c in columns}
        # group filter columns by shard once; both branches touch only
        # the shards the filter actually hits
        by_shard: dict[int, list[int]] = {}
        for c in columns:
            by_shard.setdefault(c // idx.width, []).append(c)
        for fname in fnames:
            f = idx.field(fname)
            t = f.options.type
            if t.is_bsi:
                vals = {}
                if getattr(self, "use_stacked", False) \
                        and f.bit_depth <= 62:
                    # chunked device decode + vectorized gather of just
                    # the wanted columns (executor.go:4758 re-designed)
                    skey = tuple(sorted(by_shard))
                    for chunk_ids, ex, dec in self.stacked.decode_stream(
                            idx, f, skey):
                        for i, s in enumerate(chunk_ids):
                            cs = by_shard.get(s)
                            if not cs:
                                continue
                            local = np.asarray(cs, dtype=np.int64) \
                                % idx.width
                            present = ex[i][local]
                            got = dec[i][local]
                            vals.update(
                                (c, f.int_to_value(int(x)) if p else None)
                                for c, p, x in zip(cs, present, got))
                else:
                    v = f.views.get(f.bsi_view)
                    for shard in sorted(by_shard):
                        frag = v.fragment(shard) if v else None
                        if frag is None:
                            continue
                        cols_, values = bsi_ops.decode(
                            np.asarray(frag.device_planes(f.bit_depth)))
                        base = shard * idx.width
                        vals.update((int(c) + base, f.int_to_value(val))
                                    for c, val in zip(cols_, values))
                for c in columns:
                    col_values[c].append(vals.get(c))
            else:
                membership: dict[int, list] = {c: [] for c in columns}
                v = f.views.get(VIEW_STANDARD)
                for shard, cs in sorted(by_shard.items()):
                    frag = v.fragment(shard) if v else None
                    if frag is None:
                        continue
                    local = np.array([c % idx.width for c in cs],
                                     dtype=np.int64)
                    w_i = local >> 5
                    b_i = (local & 31).astype(np.uint32)
                    for r in frag.row_ids:
                        words = frag.row_words(r)
                        hits = ((words[w_i] >> b_i) & 1).astype(bool)
                        for c, h in zip(cs, hits):
                            if h:
                                membership[c].append(r)
                tr = f.row_translator if f.options.keys else None
                for c in columns:
                    rows = membership[c]
                    if t == FieldType.BOOL:
                        col_values[c].append(
                            True if TRUE_ROW in rows else
                            False if FALSE_ROW in rows else None)
                    elif t == FieldType.MUTEX:
                        r = rows[0] if rows else None
                        if tr is not None and r is not None:
                            r = tr.translate_id(r)
                        col_values[c].append(r)
                    elif tr is not None:
                        col_values[c].append(tr.translate_ids(rows))
                    else:
                        col_values[c].append(rows)
        out_cols = []
        col_keys = (idx.column_translator.translate_ids(columns)
                    if idx.keys else None)
        for i, c in enumerate(columns):
            entry = {"column": c, "rows": col_values[c]}
            if col_keys is not None:
                entry["column_key"] = col_keys[i]
            out_cols.append(entry)
        return ExtractedTable(fields=fnames, columns=out_cols)

    # -- Delete ---------------------------------------------------------

    def _execute_delete(self, idx, call: Call, pre):
        """Delete the columns matched by the child bitmap from every
        field (executor.go:9050 delete-records semantics)."""
        child = self._only_child(call)
        changed = False
        for shard in self._shard_list(idx, None):
            words = np.asarray(self._bitmap_call_shard(
                idx, child, shard, pre))
            if not words.any():
                continue
            for f in idx.fields.values():
                for v in f.views.values():
                    frag = v.fragment(shard)
                    if frag is not None:
                        changed |= frag.clear_columns(words)
        return changed

    def _err(self, msg):
        from pilosa_tpu.executor.executor import ExecError
        return ExecError(msg)
