"""Ragged paged dispatch — ONE fused device program for heterogeneous
serving traffic.

The PR 2 batcher fuses only queries over the same (index, shard set):
a mixed batch — point Counts next to TopNs over different indexes and
shard subsets — pays one "multi" dispatch per group, and every group
boundary is a device round trip.  Since PR 5 made device stacks
fixed-size lane-block PAGES, the Ragged Paged Attention trick
(PAPERS.md, arxiv 2604.15464) applies directly: instead of padding
per group, drive one kernel over a *page table* —

- every group's plan is built as usual (the shared ``PlanBuilder``),
  but under ``stacked.raw_pages()`` its stack leaves come back as
  :class:`PageView` handles (the cache's raw page arrays) instead of
  assembled operands;
- each such operand — a *virtual leaf* — owns a static run of the
  program's leaves: its own real pages, each once, in lane order.
  INSIDE the fused program (the "ragged" plan kind in stacked.py) the
  leaf is assembled from that run exactly once, in the shape its
  consumers read (``ops.bitmap.concat_pages``: concatenate, trim the
  last page's padding, reshape; a one-page leaf is the page itself;
  a BSI field's (S, 2+depth, W) leaf that the compare or the sum
  reads comes as its 2+depth planes, each a row gather out of the
  concatenation, and is never reshaped),
  so the per-access assemble dispatch disappears and nothing is
  copied that the query does not read;
- single-leaf Counts — the dominant point-read shape — skip operand
  materialization entirely: the pages of a family's members
  concatenate into one lane block reduced by
  ``ops.bitmap.segment_count`` with one segment id per lane (popcount
  + segment-sum, one pass at raw memory bandwidth — the Buddy-RAM
  bound, arxiv 1611.09988); the padding lanes of a member's last page
  point at a dump segment;
- every other subplan kind (tree counts, words, bsi_sum, row_counts)
  evaluates exactly as in the "multi" plan over the combined
  virtual+direct leaf space, so results are bit-exact by construction.

The plan is a static tuple that names structure only — tree shapes,
each virtual leaf's page count and shape, a family's member runs —
while the pages and segment ids ride as runtime arguments: two batches
with the same structural shape share one compiled executable whatever
rows they read, and the pow2-padded segment count (one dump slot
included) keeps a family's output shape log-bounded.  The mesh
program (``ragged_mesh``) keeps per-device page pools and gather
tables of its own.

Consistency is inherited unchanged from the serving layer: the
post-batch snapshot re-check (executor/serving.py ``_run_batch``)
re-executes any rider whose fragment-version snapshot moved while the
fused program ran.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from pilosa_tpu.executor.stacked import (
    PageView,
    PlanBuilder,
    _compiled,
    _dispatch_kind,
    dispatch_ready,
    raw_pages,
)
from pilosa_tpu.memory import encode, pressure
from pilosa_tpu.obs import flight, metrics
from pilosa_tpu.obs.monitor import capture_exception


class RaggedUnbuildable(Exception):
    """A subplan the ragged program cannot express (falls back to the
    per-group path)."""


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# IR remapping (group-local leaf/param indices -> fused global space)
# ---------------------------------------------------------------------------

def _remap_tree(node, lmap, poff):
    k = node[0]
    if k == "leaf":
        return ("leaf", lmap[node[1]])
    if k == "zeros":
        return node
    if k == "nary":
        return ("nary", node[1],
                tuple(_remap_tree(c, lmap, poff) for c in node[2]))
    if k == "not":
        return ("not", lmap[node[1]], _remap_tree(node[2], lmap, poff))
    if k == "qcover":
        return ("qcover", tuple(lmap[i] for i in node[1]))
    if k == "shift":
        return ("shift", node[1], _remap_tree(node[2], lmap, poff))
    if k == "bsi_cmp":
        return ("bsi_cmp", lmap[node[1]], node[2],
                node[3] + poff, node[4] + poff)
    if k == "bsi_between":
        return ("bsi_between", lmap[node[1]], node[2] + poff,
                node[3] + poff, node[4] + poff, node[5] + poff)
    if k == "bsi_notnull":
        return ("bsi_notnull", lmap[node[1]])
    if k == "bsi_null":
        return ("bsi_null", lmap[node[1]], lmap[node[2]])
    raise RaggedUnbuildable(f"unknown IR node {k}")


def _remap_sub(sub, lmap, poff):
    kind = sub[0]
    if kind == "count":
        return ("count", _remap_tree(sub[1], lmap, poff), sub[2])
    if kind == "words":
        return ("words", _remap_tree(sub[1], lmap, poff))
    if kind == "bsi_sum":
        tree = None if sub[2] is None else _remap_tree(sub[2], lmap,
                                                       poff)
        return ("bsi_sum", lmap[sub[1]], tree, sub[3])
    if kind == "row_counts":
        tree = None if sub[2] is None else _remap_tree(sub[2], lmap,
                                                       poff)
        return ("row_counts", lmap[sub[1]], tree, sub[3])
    if kind == "gb_hist":
        # one-pass GroupBy histogram rider (ISSUE 11): the group-code
        # stack and BSI plane leaves are virtual leaves like every
        # other operand
        tree = None if sub[2] is None else _remap_tree(sub[2], lmap,
                                                       poff)
        planes = None if sub[3] is None else lmap[sub[3]]
        return ("gb_hist", lmap[sub[1]], tree, planes) + sub[4:]
    raise RaggedUnbuildable(f"unraggable sub kind {kind}")


# ---------------------------------------------------------------------------
# program assembly
# ---------------------------------------------------------------------------

class RaggedProgram:
    """Accumulates per-group (PlanBuilder, subplans) contributions and
    finalizes them into ONE ``("ragged", ...)`` plan + leaf/param
    tuples.  Groups stay what they were (one PlanBuilder per
    (index identity, shard set)); the program is what fuses across
    them."""

    # a segment family below this size gains nothing over a plain
    # count subplan (XLA fuses either way); at >= 2 the family shares
    # one popcount pass and its executable survives composition churn
    _SEG_MIN = 2

    def __init__(self, ndev: int = 1):
        # serving-mesh width (memory/placement.py); > 1 puts the
        # program in MESH mode: pages accumulate per owner device and
        # finalize() emits a ("ragged_mesh", ...) plan whose cross-
        # device combines run inside the compiled shard_map program
        self.ndev = int(ndev)
        self.mesh = self.ndev > 1
        # mesh mode only: (page_lanes, width_words) -> per-device page
        # lists (pages stay committed on their placement owner — the
        # pool assembly in _finalize_mesh never moves a byte between
        # devices)
        self.buckets: OrderedDict[tuple, list] = OrderedDict()
        # non-mesh vleaf: ((page_lanes, width_words), pages, n, shape)
        # mesh vleaf:     (bucket_key, pool_row, lane_dev, n, shape,
        #                  shard_axis, group_i)
        self.vleaves: list = []
        self.direct: list = []
        self.params: list = []
        # (entries, lmap, poff) per group; lmap: local leaf index ->
        # ("v", vleaf_i) | ("d", direct_i); an entry is
        # (riders, subplan, demux, slot_key) — riders may be empty
        # for a canonical slot absent from this batch (the sub still
        # evaluates, keeping the plan composition-stable); slot_key
        # feeds the cross-batch program cache's demux table
        self.groups: list = []
        # mesh bookkeeping: per-group shard owner maps (int32 (S,))
        # and the per-device page-encoding mix (flight/roofline
        # attribution of what each chip actually streams)
        self.group_owners: list = []
        self.dev_mix: list = [dict() for _ in range(self.ndev)]

    def add_group(self, builder: PlanBuilder, entries: list,
                  owners=None):
        """`entries`: [(riders, subplan, demux, slot_key), ...] built
        against `builder` (its leaves may be PageView handles —
        raw_pages).  ``owners``: per-shard serving-mesh owner slots
        (int32, len(builder.shards)) — required in mesh mode.
        Raises :class:`RaggedUnbuildable` when the group can't enter
        the mesh program (whole/host-served operands have no device
        layout); the caller degrades those riders to the solo path."""
        poff = len(self.params)
        self.params.extend(builder.params)
        gidx = len(self.groups)
        lmap: dict = {}
        for i, leaf in enumerate(builder.leaves):
            if isinstance(leaf, PageView):
                if self.mesh:
                    lmap[i] = ("v", self._add_mesh_leaf(leaf, gidx))
                    continue
                # per-page decode-to-dense boundary: the program
                # concatenates homogeneous dense pages, so container-
                # encoded pages (memory/encode.py) expand here — page
                # identity and lane order unchanged
                lmap[i] = ("v", len(self.vleaves))
                self.vleaves.append(
                    ((leaf.page_lanes, leaf.width_words),
                     leaf.dense_pages(), leaf.lanes, leaf.shape))
            else:
                if self.mesh:
                    raise RaggedUnbuildable(
                        "direct (whole/host) leaf under mesh")
                lmap[i] = ("d", len(self.direct))
                self.direct.append(leaf)
        self.groups.append((entries, lmap, poff))
        self.group_owners.append(owners)

    def _add_mesh_leaf(self, leaf: PageView, gidx: int) -> int:
        """Accumulate one PageView's pages into per-device bucket
        pools; returns the vleaf index.  ``pool_row[lane]`` is the
        lane's row in its owner device's (pool pages x page_lanes)
        flattened pool — valid after finalize's zero-page padding
        because pad pages append strictly AFTER real ones."""
        if leaf.page_device is None or leaf.shard_axis is None:
            raise RaggedUnbuildable("unplaced PageView under mesh")
        key = (leaf.page_lanes, leaf.width_words)
        per_dev = self.buckets.setdefault(
            key, [[] for _ in range(self.ndev)])
        pages = leaf.dense_pages()   # decode-to-dense ON the owner:
        # jnp ops on device-committed encoded payloads stay committed
        slot = np.empty(len(pages), dtype=np.int64)
        for pi, page in enumerate(pages):
            d = int(leaf.page_device[pi])
            if not 0 <= d < self.ndev:
                raise RaggedUnbuildable("owner slot outside mesh")
            slot[pi] = len(per_dev[d])
            per_dev[d].append(page)
            mk = encode.page_kind(leaf.pages[pi])
            self.dev_mix[d][mk] = self.dev_mix[d].get(mk, 0) + 1
        lane_page = leaf.lane_page.astype(np.int64)
        pool_row = (slot[lane_page] * leaf.page_lanes
                    + leaf.lane_slot.astype(np.int64))
        lane_dev = np.asarray(leaf.page_device,
                              dtype=np.int32)[lane_page]
        self.vleaves.append((key, pool_row, lane_dev, leaf.lanes,
                             leaf.shape, leaf.shard_axis, gidx))
        return len(self.vleaves) - 1

    def _add_mesh_param(self, arr: np.ndarray) -> int:
        """Append one per-device (ndev, X) int32 index param —
        sharded P("dev") into the compiled program, one row per
        device.  X is already pow2-bounded by the callers (local
        shard widths and pool paddings are pow2)."""
        self.params.append(np.ascontiguousarray(arr, dtype=np.int32))
        return len(self.params) - 1

    def finalize(self):
        """(plan, leaves, params, served, table, meshinfo) or None
        when nothing was built.  ``served``: [(req, demux, extract),
        ...] where extract is ("plain", sub_i) or ("seg", sub_i,
        slot); ``table``: slot_key -> (demux, extract) — the cross-
        batch program cache's rider-mapping surface; ``meshinfo``:
        per-device attribution (mesh mode; None otherwise)."""
        if not any(entries for entries, _l, _p in self.groups):
            return None
        # -- segment-count families: single-leaf reduced Counts whose
        # leaf is paged coalesce per (page_lanes, width) class into
        # one segment reduce
        families: OrderedDict[tuple, list] = OrderedDict()
        seg_entry: dict = {}      # id(entry tuple) -> (class, slot)
        for entries, lmap, _poff in self.groups:
            for ent in entries:
                sub = ent[1]
                if (sub[0] == "count" and sub[2]
                        and sub[1][0] == "leaf"
                        and lmap.get(sub[1][1], ("", 0))[0] == "v"):
                    v = self.vleaves[lmap[sub[1][1]][1]]
                    families.setdefault(v[0], []).append((ent, v))
        for vkey, members in list(families.items()):
            if len(members) < self._SEG_MIN:
                del families[vkey]
                continue
            for slot, (ent, _v) in enumerate(members):
                seg_entry[id(ent)] = (vkey, slot)
        # -- keep only the virtual leaves some surviving (non-segment)
        # subplan actually reads: a leaf consumed solely by a segment
        # family never materializes — its lanes reduce straight out of
        # its pages
        def _refs(sub) -> set:
            """LOCAL leaf indices a subplan reads."""
            out: set = set()

            def walk(node):
                k = node[0]
                if k == "leaf":
                    out.add(node[1])
                elif k == "nary":
                    for c in node[2]:
                        walk(c)
                elif k == "not":
                    out.add(node[1])
                    walk(node[2])
                elif k == "qcover":
                    out.update(node[1])
                elif k == "shift":
                    walk(node[2])
                elif k in ("bsi_cmp", "bsi_between", "bsi_notnull"):
                    out.add(node[1])
                elif k == "bsi_null":
                    out.add(node[1])
                    out.add(node[2])
            if sub[0] in ("bsi_sum", "row_counts"):
                out.add(sub[1])
                if sub[2] is not None:
                    walk(sub[2])
            elif sub[0] == "gb_hist":
                out.add(sub[1])
                if sub[2] is not None:
                    walk(sub[2])
                if sub[3] is not None:
                    out.add(sub[3])
            else:
                walk(sub[1])
            return out

        plain: list = []      # (ent, lmap, poff, group_i) batch order
        kept: set[int] = set()
        for gidx, (entries, lmap, poff) in enumerate(self.groups):
            for ent in entries:
                if id(ent) in seg_entry:
                    continue
                plain.append((ent, lmap, poff, gidx))
                for li in _refs(ent[1]):
                    tag, i = lmap[li]
                    if tag == "v":
                        kept.add(i)
        vkeep = sorted(kept)
        vre = {vi: k for k, vi in enumerate(vkeep)}
        if self.mesh:
            return self._finalize_mesh(families, plain, vkeep, vre)
        # -- leaf layout: every virtual leaf a sub or a family reads
        # owns ONE static run of the program's leaves — its real
        # pages, each once — and the direct leaves follow.  A leaf
        # nothing references (a failed subplan build can orphan one)
        # brings no page.
        leaves: list = []
        run_of: dict = {}

        def _run(v) -> tuple:
            """(leaf_start, n_pages) of virtual leaf `v`'s pages."""
            r = run_of.get(id(v))
            if r is None:
                r = run_of[id(v)] = (len(leaves), len(v[1]))
                leaves.extend(v[1])
            return r

        vmeta = tuple(_run(v) + (v[3],)
                      for v in (self.vleaves[vi] for vi in vkeep))
        nv = len(vkeep)
        # -- final lmaps + subs.  Unreferenced virtual leaves map to
        # None: _remap_sub only touches indices a sub actually reads,
        # so a None ever surfacing in a plan is a planner bug that
        # fails loudly at repr/jit time rather than mis-indexing.
        # Identical remapped subplans DEDUPE to one executed sub with
        # several riders: round-robin client mixes put the same query
        # in one batch many times, and without dedupe every
        # multiplicity would be a distinct plan (compile churn) doing
        # duplicate device work.
        subs: list = []
        served: list = []
        table: dict = {}
        sub_ix: dict = {}
        for ent, lmap, poff, _gidx in plain:
            final = {}
            for li, (tag, i) in lmap.items():
                final[li] = vre.get(i) if tag == "v" else nv + i
            riders, sub, demux, slot_key = ent
            rsub = _remap_sub(sub, final, poff)
            i = sub_ix.get(rsub)
            if i is None:
                subs.append(rsub)
                i = sub_ix[rsub] = len(subs) - 1
            if slot_key is not None:
                table[slot_key] = (demux, ("plain", i))
            for r in riders:
                served.append((r, demux, ("plain", i)))
        # -- segment families.  Duplicate calls share one leaf
        # (PlanBuilder dedupe): one segment serves every rider of that
        # call.  A member's lanes past its last real one (the final
        # page's zero padding) point at the dump segment, which also
        # pads the segment count to pow2 so the executable survives
        # composition churn.
        for members in families.values():
            uniq = list({id(v): v for _ent, v in members}.values())
            nseg = len(uniq)
            seg_ids = np.concatenate(
                [np.where(np.arange(len(pages) * page_lanes) < n,
                          slot, nseg)
                 for slot, ((page_lanes, _w), pages, n, _shape)
                 in enumerate(uniq)])
            self.params.append(seg_ids.astype(np.int32))
            subs.append(("segcount", tuple(_run(v) for v in uniq),
                         len(self.params) - 1, _pow2(nseg + 1)))
            slot_of = {id(v): slot for slot, v in enumerate(uniq)}
            for ent, v in members:
                riders, _sub, demux, slot_key = ent
                ext = ("seg", len(subs) - 1, slot_of[id(v)])
                if slot_key is not None:
                    table[slot_key] = (demux, ext)
                for r in riders:
                    served.append((r, demux, ext))
        if not subs:
            return None
        plan = ("ragged", len(leaves), vmeta, tuple(subs))
        return (plan, leaves + self.direct, self.params, served, table,
                None)

    def _finalize_mesh(self, families, plain, vkeep, vre):
        """Emit the ``("ragged_mesh", ...)`` plan: per-device page
        POOLS as mesh-sharded leaves (assembled zero-copy with
        ``make_array_from_single_device_arrays`` — every page is
        already committed on its placement owner), per-device
        gather/scatter index params, and a combine spec per sub —
        psum trees for reduced outputs, dump-row scatter-adds for
        per-shard outputs — so every cross-device combine happens
        INSIDE the compiled program (no host merge phase).  Padded
        local shard positions gather the pool's guaranteed-zero tail
        page; zero shards are harmless for every reduction we run
        (the place_shards invariant — all BSI range arms mask with
        the exists plane)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from pilosa_tpu.memory import placement

        ndev = self.ndev
        n_base = len(self.params)
        used_keys = ({self.vleaves[vi][0] for vi in vkeep}
                     | set(families.keys()))
        devs = placement.devices()
        if len(devs) < ndev:
            raise RaggedUnbuildable("mesh shrank below plan width")
        smesh = placement.serving_mesh()
        bucket_meta: list = []    # (pool_pages, page_lanes, W)
        bucket_id: dict = {}
        zero_row: dict = {}       # bucket key -> all-zero pool row
        leaves: list = []
        dev_bytes = [0] * ndev
        for key, per_dev in self.buckets.items():
            if key not in used_keys:
                continue
            pl, w = key
            # +1 guarantees >= one zero pad page per device: slot
            # p2-1 is all-zero everywhere, the padding gather target
            p2 = _pow2(max(len(pages) for pages in per_dev) + 1)
            pieces = []
            for d in range(ndev):
                blocks = [jax.device_put(p, devs[d])
                          for p in per_dev[d]]
                dev_bytes[d] += len(blocks) * pl * w * 4
                if len(blocks) < p2:
                    z = jax.device_put(
                        np.zeros((pl, w), dtype=np.uint32), devs[d])
                    blocks.extend([z] * (p2 - len(blocks)))
                pieces.append(jnp.stack(blocks)[None])
            glob = jax.make_array_from_single_device_arrays(
                (ndev, p2, pl, w), NamedSharding(smesh, P("dev")),
                pieces)
            bucket_id[key] = len(bucket_meta)
            bucket_meta.append((p2, pl, w))
            zero_row[key] = (p2 - 1) * pl
            leaves.append(glob)
        # -- per-group geometry: each device's owned shard positions,
        # padded to a common pow2 local width
        geo: dict = {}

        def _geometry(gidx):
            g = geo.get(gidx)
            if g is None:
                owners = self.group_owners[gidx]
                if owners is None:
                    raise RaggedUnbuildable("mesh group w/o owners")
                s = int(owners.shape[0])
                owned = [np.flatnonzero(owners == d)
                         for d in range(ndev)]
                s_p = _pow2(max([o.size for o in owned] + [1]))
                sel = np.full((ndev, s_p), s, dtype=np.int64)
                for d in range(ndev):
                    sel[d, :owned[d].size] = owned[d]
                geo[gidx] = g = (s, s_p, sel)
            return g

        # -- virtual leaves: one per-device gather param each.  The
        # local leaf keeps the global lead shape with the shard axis
        # compressed to s_p; the gather grid extends the shard axis
        # by one sentinel slab pointing at the zero pool row.
        vmeta: list = []
        for vi in vkeep:
            key, pool_row, lane_dev, n, shape, sa, gidx = \
                self.vleaves[vi]
            s, s_p, sel = _geometry(gidx)
            lead = shape[:-1]
            if lead[sa] != s:
                raise RaggedUnbuildable("leaf shard axis mismatch")
            grid = np.arange(n, dtype=np.int64).reshape(lead)
            pad_shape = list(lead)
            pad_shape[sa] = 1
            ext = np.concatenate(
                [grid, np.full(pad_shape, n, dtype=np.int64)],
                axis=sa)
            row_ext = np.concatenate(
                [pool_row, np.array([zero_row[key]], np.int64)])
            dev_ext = np.concatenate(
                [lane_dev, np.array([-1], np.int32)])
            gat = []
            for d in range(ndev):
                flat = np.take(ext, sel[d], axis=sa).reshape(-1)
                fd = dev_ext[flat]
                if np.any((fd != d) & (fd != -1)):
                    raise RaggedUnbuildable("placement drift: lane "
                                            "owner != group owner")
                gat.append(row_ext[flat])
            gi = self._add_mesh_param(np.stack(gat))
            lshape = list(lead)
            lshape[sa] = s_p
            vmeta.append((bucket_id[key], gi,
                          tuple(lshape) + (shape[-1],)))
        # -- subs + per-sub combine specs.  spos params (local shard
        # position -> global shard index, padding -> the S dump row)
        # are per group and shared by every scatter sub of the group.
        spos_param: dict = {}

        def _spos(gidx):
            p = spos_param.get(gidx)
            if p is None:
                _s, _sp, sel = _geometry(gidx)
                p = spos_param[gidx] = self._add_mesh_param(sel)
            return p

        subs: list = []
        combines: list = []
        served: list = []
        table: dict = {}
        sub_ix: dict = {}
        for ent, lmap, poff, gidx in plain:
            # no direct leaves in mesh mode (add_group rejects them)
            final = {li: vre.get(i) for li, (_t, i) in lmap.items()}
            riders, sub, demux, slot_key = ent
            rsub = _remap_sub(sub, final, poff)
            if rsub[0] == "gb_hist":
                # pallas arms can't lower inside the shard_map body;
                # the XLA arm is the same math, bit-exact
                rsub = rsub[:6] + ("xla",) + rsub[7:]
            i = sub_ix.get(rsub)
            if i is None:
                s, _sp, _sel = _geometry(gidx)
                k = rsub[0]
                if k == "count":
                    comb = (("psum",) if rsub[2]
                            else ("scatter", _spos(gidx), s, 0))
                elif k == "words":
                    comb = ("scatter", _spos(gidx), s, 0)
                elif k == "bsi_sum":
                    comb = (("psum",) if rsub[3]
                            else ("scatter3", _spos(gidx), s))
                elif k == "row_counts":
                    comb = (("psum",) if rsub[3]
                            else ("scatter", _spos(gidx), s, 1))
                elif k == "gb_hist":
                    comb = ("psum",)
                else:
                    raise RaggedUnbuildable(f"unmeshable sub {k}")
                subs.append(rsub)
                combines.append(comb)
                i = sub_ix[rsub] = len(subs) - 1
            if slot_key is not None:
                table[slot_key] = (demux, ("plain", i))
            for r in riders:
                served.append((r, demux, ("plain", i)))
        # -- segment families: per-device lane/segment id arrays over
        # the device pools; padding points at the zero row + the dump
        # segment, partial per-segment counts psum to the exact total
        for vkey, members in families.items():
            slot_of: dict[int, int] = {}
            uniq: list = []
            member_slots: list = []
            for ent, v in members:
                slt = slot_of.get(id(v[1]))
                if slt is None:
                    slt = slot_of[id(v[1])] = len(uniq)
                    uniq.append((v[1], v[2]))
                member_slots.append((ent, slt))
            nseg = len(uniq)
            npad_seg = _pow2(nseg + 1)   # +1 dump slot for padding
            per_rows = [[] for _ in range(ndev)]
            per_segs = [[] for _ in range(ndev)]
            for slt, (pool_row, lane_dev) in enumerate(uniq):
                for d in range(ndev):
                    m = lane_dev == d
                    per_rows[d].append(pool_row[m])
                    per_segs[d].append(
                        np.full(int(m.sum()), slt, dtype=np.int32))
            lens = [int(sum(a.size for a in per_rows[d]))
                    for d in range(ndev)]
            lpad = _pow2(max(lens + [1]))
            rows = np.full((ndev, lpad), zero_row[vkey],
                           dtype=np.int64)
            segs = np.full((ndev, lpad), nseg, dtype=np.int32)
            for d in range(ndev):
                if lens[d]:
                    rows[d, :lens[d]] = np.concatenate(per_rows[d])
                    segs[d, :lens[d]] = np.concatenate(per_segs[d])
            gi = self._add_mesh_param(rows)
            si = self._add_mesh_param(segs)
            subs.append(("segcount", bucket_id[vkey], gi, si,
                         npad_seg))
            combines.append(("psum",))
            for ent, slt in member_slots:
                riders, _sub, demux, slot_key = ent
                if slot_key is not None:
                    table[slot_key] = (demux,
                                       ("seg", len(subs) - 1, slt))
                for r in riders:
                    served.append((r, demux,
                                   ("seg", len(subs) - 1, slt)))
        if not subs:
            return None
        plan = ("ragged_mesh", ndev, placement.epoch(), n_base,
                tuple(bucket_meta), tuple(vmeta), tuple(subs),
                tuple(combines))
        meshinfo = {"ndev": ndev, "dev_bytes": dev_bytes,
                    "dev_pages": [dict(m) for m in self.dev_mix]}
        return plan, leaves, self.params, served, table, meshinfo


# ---------------------------------------------------------------------------
# canonical composition (composition hysteresis)
# ---------------------------------------------------------------------------
# A fused program compiles per batch COMPOSITION, and free-running
# traffic produces endlessly novel compositions: a fast dispatch
# admits a small random batch, that one-off composition compiles for
# hundreds of milliseconds, the backlog forms a full batch, and the
# system oscillates between "warm full batch" and "novel small batch"
# — compile throughput, not serving.  The fix is hysteresis: the
# layer keeps a CANONICAL slot set of RECURRING (index, shards,
# query) items, LRU-bounded, and every batch dispatches the one
# canonical program.  Present riders demux their slots; absent slots
# still evaluate (their operands are resident cache hits and their
# bulk work is bandwidth-trivial) so the plan tuple — and therefore
# the compiled executable — is IDENTICAL from batch to batch.
# Steady state is literally one fused program, the ROADMAP item 1
# shape; composition changes (a hot query joining, an idle slot
# aging out, a dropped index) recompile exactly once.
#
# PROBATION keeps one-off queries out: a key joins the canonical set
# only after appearing in a SECOND batch within the probation window
# (a random ad-hoc query must not force a full canonical recompile).
# Non-canonical riders ride a separate EXTRAS program — a per-batch
# composition fused like the canonical one, whose compile churn is
# confined to exactly the traffic that churns.

_CANON_MAX = 96        # max canonical slots (absent-slot work bound)
_CANON_IDLE = 64       # batches a slot may sit unused before aging out
_CANON_PROBATION = 32  # window (batches) for the second sighting
_SEEN_MAX = 512        # probation bookkeeping bound


class _Slot:
    __slots__ = ("idx", "index_name", "skey", "shards", "kind",
                 "call", "last_used")

    def __init__(self, r, batch_no):
        self.idx = r.idx
        self.index_name = r.index
        self.skey = r.skey
        self.shards = r.shards
        self.kind = r.kind
        self.call = r.call
        self.last_used = batch_no


class _ShimReq:
    """Stand-in for a canonical slot absent from this batch: just
    enough of the _Req surface for ServingLayer._build_sub."""

    __slots__ = ("idx", "call", "kind", "shards", "skey", "result",
                 "error", "direct", "ctx")

    def __init__(self, slot: _Slot):
        self.idx = slot.idx
        self.call = slot.call
        self.kind = slot.kind
        self.shards = slot.shards
        self.skey = slot.skey
        self.result = None
        self.error = None
        self.direct = False
        self.ctx = None


class CanonicalComposition:
    """The layer's slot set + probation bookkeeping + the lock
    guarding them (concurrent batches overlap under continuous
    batching)."""

    def __init__(self):
        self.slots: OrderedDict[tuple, _Slot] = OrderedDict()
        self.seen: OrderedDict[tuple, int] = OrderedDict()
        self.batch_no = 0
        self.lock = __import__("threading").Lock()
        # cross-batch program cache: (slot fingerprint, mutation
        # epoch, plan, leaves, params, table, consts).  Valid while
        # the slot set AND the global mutation epoch
        # (models/fragment.py) are unchanged — a read-heavy steady
        # state then skips plan building entirely and pays ONE
        # dispatch per batch; any write anywhere invalidates
        # conservatively (the per-fragment stamps remain the precise
        # staleness authority via the post-batch snapshot re-check).
        # Holding `leaves` pins the canonical working set's device
        # pages between batches — bounded by _CANON_MAX slots.
        self.cached = None

    def fold(self, layer, groups: dict) -> list:
        """Register the batch's requests (promoting recurring keys
        out of probation), age out idle/dead slots, and return a
        stable-ordered snapshot of the slot list.  Riders whose key
        is still on probation ride the extras program."""
        holder = layer.executor.holder
        with self.lock:
            self.batch_no += 1
            for reqs in groups.values():
                for r in reqs:
                    key = (id(r.idx), r.skey, r.kind, repr(r.call))
                    slot = self.slots.get(key)
                    if slot is not None:
                        slot.last_used = self.batch_no
                        continue
                    last = self.seen.get(key)
                    if (last is not None
                            and 0 < self.batch_no - last
                            <= _CANON_PROBATION):
                        # second sighting in a different recent
                        # batch: promote — it's recurring traffic
                        self.slots[key] = _Slot(r, self.batch_no)
                        self.seen.pop(key, None)
                    else:
                        self.seen[key] = self.batch_no
                        self.seen.move_to_end(key)
                        while len(self.seen) > _SEEN_MAX:
                            self.seen.popitem(last=False)
            for key, slot in list(self.slots.items()):
                if (self.batch_no - slot.last_used > _CANON_IDLE
                        or holder.index(slot.index_name)
                        is not slot.idx):
                    del self.slots[key]
            while len(self.slots) > _CANON_MAX:
                key = min(self.slots,
                          key=lambda k: self.slots[k].last_used)
                del self.slots[key]
            # stable order: groups by (index name, skey), slots by
            # call repr — identical slot sets build identical plans
            slots = sorted(
                self.slots.values(),
                key=lambda s: (s.index_name, s.skey, s.kind,
                               repr(s.call)))
            fp = tuple(sorted(self.slots))
            return slots, fp

    def drop(self, slot_keys):
        with self.lock:
            for key in slot_keys:
                self.slots.pop(key, None)
            self.cached = None


# ---------------------------------------------------------------------------
# batch execution (called by ServingLayer._run_batch on the leader)
# ---------------------------------------------------------------------------

def _mesh_width(eng) -> int:
    """Serving-mesh width for the fused program: > 1 only when the
    serving mesh (memory/placement.py) is configured AND the engine
    runs the plain paged placement — the legacy GSPMD mesh and
    host_only keep whole-array entries, so there is no page table to
    walk per device."""
    from pilosa_tpu import memory as _mem
    from pilosa_tpu.memory import placement
    if eng.mesh is not None or eng.host_only \
            or not _mem.paged_enabled():
        return 1
    return placement.mesh_devices()


def _note_roofline(nbytes: int, dt, meshinfo, served) -> None:
    """Per-dispatch bandwidth attribution for the fused ragged
    program: the aggregate 'ragged' op family plus — under the mesh —
    a per-device series (each chip's resident pool bytes over the
    same program wall time) and the per-device page-encoding mix on
    every rider's flight record."""
    from pilosa_tpu.obs import roofline
    roofline.note("ragged", nbytes, dt)
    if not meshinfo:
        return
    for d, b in enumerate(meshinfo.get("dev_bytes", ())):
        roofline.note("ragged", b, dt, device=d)
    mix = {f"d{d}:{k}": v
           for d, m in enumerate(meshinfo.get("dev_pages", ()))
           for k, v in m.items()}
    if mix:
        for r, _d, _e in served:
            r.acc.add_pages(mix)


def run_ragged(layer, groups: dict) -> None:
    """Plan, dispatch, and demux EVERY group of the batch through the
    ONE canonical fused program.  Mirrors the per-group leader
    protocol (serving._run_group): per-request plan/build
    attribution, the serving-dispatch chaos seam, the OOM backstop,
    and the mark-direct-on-failure fallback — a failed fused program
    degrades every rider to its caller-thread solo path, never to an
    error."""
    import pilosa_tpu.models.fragment as _frag
    eng = layer.executor.stacked
    canon = getattr(layer, "_ragged_canon", None)
    if canon is None:
        canon = layer._ragged_canon = CanonicalComposition()
    slots, fp = canon.fold(layer, groups)
    # epoch read BEFORE any build/serve decision: a write landing
    # mid-build leaves a stamp older than the live epoch, so the next
    # batch rebuilds (and this batch's riders are covered by the
    # post-batch snapshot re-check either way)
    epoch = _frag.mutation_epoch()
    # riders by slot key, build order canonical within each group
    by_key: OrderedDict[tuple, list] = OrderedDict()
    for reqs in groups.values():
        for r in reqs:
            if r.result is None and r.error is None:
                by_key.setdefault(
                    (id(r.idx), r.skey, r.kind, repr(r.call)),
                    []).append(r)
    # -- canonical program: serve from the cross-batch cache when the
    # slot set and data are unchanged, else rebuild + re-cache ------
    with canon.lock:
        cached = canon.cached
        if cached is not None and (cached[0] != fp
                                   or cached[1] != epoch):
            cached = None
        if cached is not None and cached[2] is not None \
                and cached[2][0] == "ragged_mesh":
            # mesh plans pin topology + placement epoch at build
            # time: a rebalance or mesh resize must rebuild, never
            # replay pools addressed by a dead placement
            from pilosa_tpu.memory import placement as _pl
            if (cached[2][1] != _mesh_width(eng)
                    or cached[2][2] != _pl.epoch()):
                cached = None
                canon.cached = None
        elif cached is not None and cached[2] is not None \
                and _mesh_width(eng) > 1:
            # single-device plan cached before the mesh came up
            cached = None
            canon.cached = None
    if cached is not None:
        _serve_cached(layer, cached, by_key, len(groups))
    else:
        slot_groups: OrderedDict[tuple, list] = OrderedDict()
        for s in slots:
            slot_groups.setdefault((id(s.idx), s.skey), []).append(s)
        work = []
        for (_gid, skey), gslots in slot_groups.items():
            pairs = [(slot, by_key.pop(
                (id(slot.idx), slot.skey, slot.kind, repr(slot.call)),
                [])) for slot in gslots]
            work.append((gslots[0].idx, skey, pairs))
        if work:
            payload = _plan_and_dispatch(layer, eng, work,
                                         len(groups), canon=canon,
                                         program="canonical")
            if payload is not None:
                with canon.lock:
                    # only cache if no slot died during the build
                    # (drop() cleared cached and changed the set)
                    if tuple(sorted(canon.slots)) == fp:
                        canon.cached = (fp, epoch) + payload
    # -- extras program: probation riders (one-off / not-yet-
    # recurring queries) fuse into their own per-batch composition,
    # so their compile churn never touches the canonical executable
    if by_key:
        ework: OrderedDict[tuple, list] = OrderedDict()
        for key, riders in by_key.items():
            if not riders:
                continue
            r0 = riders[0]
            ework.setdefault((id(r0.idx), r0.skey), []).append(
                (_Slot(r0, 0), riders))
        work2 = [(pairs[0][1][0].idx, skey, pairs)
                 for (_gid, skey), pairs in ework.items()]
        if work2:
            _plan_and_dispatch(layer, eng, work2, len(groups),
                               canon=None, program="extras")


def _plan_and_dispatch(layer, eng, work, n_groups: int,
                       canon=None, program: str = "canonical"):
    """Build ONE ragged program over `work` — [(idx, skey,
    [(slot, riders), ...]), ...] in stable order — dispatch it, and
    demux every rider.  `canon` given: a build failure evicts the
    slot from the canonical set, and a successful build returns the
    (plan, leaves, params, table, consts, meshinfo) payload for the
    cross-batch program cache (None otherwise)."""
    from pilosa_tpu.memory import placement as _placement
    ndev = _mesh_width(eng)
    prog = RaggedProgram(ndev=ndev)
    dead_keys: list = []
    consts: dict = {}
    for idx, skey, pairs in work:
        shards = list(skey)
        owners = (_placement.owners(idx.name, shards)
                  if ndev > 1 else None)
        b = PlanBuilder(eng, idx, shards, {})
        entries = []
        for slot, riders in pairs:
            slot_key = ((id(slot.idx), slot.skey, slot.kind,
                         repr(slot.call))
                        if canon is not None else None)
            target = riders[0] if riders else _ShimReq(slot)
            for r in riders:
                r.acc = flight.Acc()
            # one build serves every rider of the slot: one stage,
            # recorded (with the stack fetches inside it) into each
            # rider's Acc and each traced rider's tree
            try:
                with raw_pages(), _plan_stage(riders, slot.kind):
                    built = layer._build_sub(b, target, shards)
            except Exception:
                # unbuildable now (data/schema drift): the slot
                # leaves the canonical set and its riders fall back
                for r in riders:
                    r.direct = True
                if slot_key is not None:
                    dead_keys.append(slot_key)
                continue
            if built is None:
                # constant result: share it across riders (the
                # result cache shares result objects the same way)
                for r in riders[1:]:
                    r.result = target.result
                if slot_key is not None:
                    consts[slot_key] = target.result
                continue
            entries.append((riders, built[0], built[1], slot_key))
        if entries:
            group = [r for riders, _s, _d, _k in entries for r in riders]
            try:
                with _plan_stage(group):
                    prog.add_group(b, entries, owners=owners)
            except RaggedUnbuildable:
                # the group can't enter the mesh program (whole/host
                # operand, unplaced pages): its riders degrade to the
                # solo path, everything else stays fused
                for riders, _s, _d, slot_key in entries:
                    for r in riders:
                        r.direct = True
                    if slot_key is not None:
                        dead_keys.append(slot_key)
    if canon is not None and dead_keys:
        canon.drop(dead_keys)
    cacheable = canon is not None and not dead_keys
    try:
        with _plan_stage([r for _i, _s, pairs in work
                          for _slot, riders in pairs for r in riders]):
            fin = prog.finalize()
    except RaggedUnbuildable as e:
        # finalize-time mesh rejection (placement drift, topology
        # shrink): every rider of the batch degrades, no error
        capture_exception(e, where="serving.ragged_finalize")
        for _idx, _skey, pairs in work:
            for _slot, riders in pairs:
                for r in riders:
                    r.direct = True
        if canon is not None:
            canon.drop([slot_key for _i, _s, pairs in work
                        for slot, _r in pairs
                        for slot_key in [(id(slot.idx), slot.skey,
                                          slot.kind,
                                          repr(slot.call))]])
        return None
    if fin is None:
        # a program of constants alone is still cacheable
        return ((None, None, None, {}, consts, None)
                if cacheable and consts else None)
    plan, leaves, params, served, table, meshinfo = fin
    payload = ((plan, leaves, params, table, consts, meshinfo)
               if cacheable else None)
    if not served:
        # no rider this batch — skip the dispatch but keep the built
        # program for the cache (the next batch serves from it)
        return payload
    outs = _dispatch_served(plan, leaves, params, served,
                            meshinfo, program, n_groups)
    if outs is not None:
        _demux_served(served, outs)
    return payload


def _plan_stage(riders, kind: str = "ragged"):
    """The `plan_build` stage of work the leader does once for
    `riders` (a slot's sub-plan; the page tables and the program's
    assembly), recorded into each of them."""
    return flight.stage(
        "plan_build", accs=[r.acc for r in riders] or [flight.Acc()],
        ctx=[r.ctx for r in riders], kind=kind)


def _dispatch_served(plan, leaves, params, served, meshinfo,
                     program: str, n_groups: int):
    """The ONE fused dispatch of a batch, timed once as the stage
    `kind` ('execute' | 'compile') for every rider it serves: the
    same span in each rider's flight record and trace tree.  The ready
    outputs, or None after marking the riders direct."""
    # the program's signature is a repr of the whole plan and a shape
    # key per page: milliseconds at hundreds of pages, and part of
    # building it
    with _plan_stage([r for r, _d, _e in served]):
        sig = repr(plan)
        kind = _dispatch_kind(sig, leaves, params)
    nsubs = len(plan[3]) if plan[0] == "ragged" else len(plan[6])
    oom0 = metrics.OOM_TOTAL.total(outcome="caught")
    st = flight.stage(
        kind, accs=[r.acc for r, _d, _e in served],
        ctx=[r.ctx for r, _d, _e in served],
        batch=len(served), subqueries=nsubs, ragged=True,
        program=program, groups=n_groups,
        mesh=plan[0] == "ragged_mesh", compile=kind == "compile")
    try:
        with st:
            # same chaos seam + OOM backstop as the per-group dispatch
            from pilosa_tpu.obs import faults
            faults.fire("serving-dispatch")
            fn = _compiled(
                plan, sig=sig,
                name="plan_ragged_extras" if program == "extras"
                and plan[0] == "ragged" else None)
            outs = pressure.guarded(lambda: dispatch_ready(
                fn, tuple(leaves), tuple(params)))
    except Exception as e:
        capture_exception(
            e, where="serving.ragged_dispatch", batch=len(served),
            trace_ids=[r.trace_id for r, _d, _e in served
                       if r.trace_id])
        for r, _d, _e in served:
            r.direct = True
        return None
    metrics.SERVING_DISPATCH.inc(kind=plan[0])
    # one pass over the leaves' sizes (a host attribute, no device
    # read) serves the counter and the roofline note
    nbytes = [int(getattr(a, "nbytes", 0)) for a in leaves]
    if plan[0] == "ragged":
        assembled = sum(nbytes[:plan[1]])
        metrics.RAGGED_ASSEMBLED_BYTES.inc(assembled)
        for r, _d, _e in served:
            r.acc.assembled_bytes += assembled
    if kind == "execute" and \
            metrics.OOM_TOTAL.total(outcome="caught") == oom0:
        _note_roofline(sum(nbytes), st.seconds, meshinfo, served)
    return outs


def _demux_served(served, outs) -> None:
    for r, demux, ext in served:
        out = outs[ext[1]] if ext[0] == "plain" else \
            outs[ext[1]][ext[2]]
        try:
            with flight.stage("demux", accs=[r.acc], ctx=r.ctx):
                r.result = demux(out)
        except Exception:
            r.direct = True
            r.result = None


def _serve_cached(layer, cached, by_key, n_groups: int) -> None:
    """Serve this batch's canonical riders from the cross-batch
    program cache: no plan building, no leaf fetches — map each rider
    to its slot's demux/extract, run the ONE cached fused program,
    demux.  Keys the cache doesn't know stay in `by_key` for the
    extras program."""
    _fp, _epoch, plan, leaves, params, table, consts, meshinfo = \
        cached
    served: list = []
    for key in list(by_key):
        if key in consts:
            for r in by_key.pop(key):
                r.acc = flight.Acc()
                r.result = consts[key]
        elif table and key in table:
            demux, ext = table[key]
            for r in by_key.pop(key):
                r.acc = flight.Acc()
                served.append((r, demux, ext))
    if not served or plan is None:
        return
    outs = _dispatch_served(plan, leaves, params, served,
                            meshinfo, "canonical-cached", n_groups)
    if outs is not None:
        _demux_served(served, outs)
