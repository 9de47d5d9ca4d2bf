"""Ragged paged dispatch tests (executor/ragged.py): one fused
page-table device program serving heterogeneous batches — mixed
indexes, mixed shard subsets, mixed Count/Row/Sum/TopN kinds —
bit-exact vs solo execution, on host and jit engines, under
concurrent writes (the stale-snapshot re-execution path included)."""

import random
import threading

import pytest

from pilosa_tpu import memory
from pilosa_tpu.api import serialize_result
from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.schema import FieldOptions, FieldType
from pilosa_tpu.obs import metrics


def build_mixed_holder() -> Holder:
    """Two indexes with different shard counts and field shapes —
    the heterogeneous-traffic fixture."""
    h = Holder()
    a = h.create_index("alpha", track_existence=True)
    a.create_field("a")
    a.create_field("b")
    a.create_field("v", FieldOptions(type=FieldType.INT,
                                     min=0, max=1000))
    b = h.create_index("beta", track_existence=False)
    b.create_field("c")
    b.create_field("w", FieldOptions(type=FieldType.INT,
                                     min=-50, max=500))
    ex = Executor(h)
    w = a.width
    for i in range(240):
        col = (i * 9973) % (3 * w)          # 3 shards
        ex.execute("alpha", f"Set({col}, a={i % 4})")
        ex.execute("alpha", f"Set({col}, b={i % 6})")
        ex.execute("alpha", f"Set({col}, v={(i * 7) % 97})")
    for i in range(180):
        col = (i * 7919) % (5 * w)          # 5 shards
        ex.execute("beta", f"Set({col}, c={i % 3})")
        ex.execute("beta", f"Set({col}, w={(i * 11) % 300 - 40})")
    return h


@pytest.fixture(scope="module")
def holder():
    return build_mixed_holder()


MIXED = [
    ("alpha", "Count(Row(a=1))", None),
    ("alpha", "Count(Row(b=2))", None),
    ("beta", "Count(Row(c=0))", None),
    ("beta", "Count(Row(c=2))", None),
    ("alpha", "Count(Intersect(Row(a=1), Row(b=2)))", None),
    ("alpha", "Count(Union(Row(a=0), Row(b=5)))", None),
    ("beta", "Count(Union(Row(c=0), Row(c=1)))", None),
    ("alpha", "Row(a=2)", None),
    ("beta", "Row(c=1)", None),
    ("alpha", "Sum(Row(a=1), field=v)", None),
    ("beta", "Sum(field=w)", None),
    ("alpha", "Count(Row(v > 50))", None),
    ("beta", "Count(Row(w > 100))", None),
    ("beta", "Count(Row(w < 0))", None),
    ("alpha", "TopN(a, n=3)", None),
    ("beta", "TopN(c, n=2)", None),
    # explicit shard subsets: same index, different skey -> its own
    # group, fused into the same ragged program
    ("alpha", "Count(Row(a=1))", [0, 1]),
    ("alpha", "Count(Row(a=1))", [2]),
    ("beta", "Count(Row(c=0))", [0, 2, 4]),
    ("alpha", "Count(Not(Row(a=1)))", None),
]


def run_concurrent(srv, items):
    got = {}
    lock = threading.Lock()
    bar = threading.Barrier(len(items))

    def one(k):
        idx, q, shards = k
        bar.wait()
        r = [serialize_result(x)
             for x in srv.execute_serving(idx, q, shards)]
        with lock:
            got[k] = r

    keyed = [(i, q, tuple(s) if s else None) for i, q, s in items]
    ts = [threading.Thread(target=one, args=(k,)) for k in keyed]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return got


def solo_expect(plain, items):
    return {(i, q, tuple(s) if s else None):
            [serialize_result(x) for x in plain.execute(i, q, s)]
            for i, q, s in items}


@pytest.mark.parametrize("host_only", [False, True])
def test_mixed_batch_bit_exact_one_dispatch(holder, host_only):
    """The whole mixed-index batch fuses into ONE ragged dispatch and
    every query demuxes to its exact solo result — on the jit engine
    and the host-only engine."""
    plain = Executor(holder)
    plain.stacked.host_only = host_only
    srv = Executor(holder)
    srv.stacked.host_only = host_only
    layer = srv.enable_serving(window_s=0.05, max_batch=64,
                               cache_bytes=0, admission=False)
    assert layer.ragged
    want = solo_expect(plain, MIXED)
    r0 = metrics.SERVING_DISPATCH.value(kind="ragged")
    got = run_concurrent(srv, MIXED)
    assert got == want
    assert metrics.SERVING_DISPATCH.value(kind="ragged") > r0


def test_ragged_off_matches(holder):
    """A/B sanity: the per-group path serves the same batch
    identically (the bench A/B's control arm)."""
    plain = Executor(holder)
    srv = Executor(holder)
    srv.enable_serving(window_s=0.05, max_batch=64, cache_bytes=0,
                       ragged=False, admission=False)
    g0 = metrics.SERVING_DISPATCH.value(kind="group")
    got = run_concurrent(srv, MIXED)
    assert got == solo_expect(plain, MIXED)
    assert metrics.SERVING_DISPATCH.value(kind="group") > g0


def test_multipage_page_table(holder):
    """Small pages force real multi-page page tables: the fused
    gather must reassemble multi-page operands exactly."""
    prev = memory.page_bytes()
    memory.configure(page_bytes=64 << 10)
    try:
        plain = Executor(holder)
        srv = Executor(holder)
        srv.enable_serving(window_s=0.05, max_batch=64,
                           cache_bytes=0, admission=False)
        got = run_concurrent(srv, MIXED)
        assert got == solo_expect(plain, MIXED)
    finally:
        memory.configure(page_bytes=prev)


def test_segment_ops_bit_exact():
    """ops/bitmap.py ragged primitives: an operand assembled from its
    own pages and the segment popcount reduce match the numpy twins,
    the dump-segment padding contract included."""
    import numpy as np

    from pilosa_tpu.ops import bitmap as bm

    rng = np.random.default_rng(3)
    pages = [rng.integers(0, 1 << 32, size=(4, 8), dtype=np.uint32)
             for _ in range(3)]
    flat = np.concatenate(pages)
    # 10 real lanes: the last page holds two of them
    got = np.asarray(bm.concat_pages(tuple(pages), (5, 2, 8)))
    assert (got == flat[:10].reshape(5, 2, 8)).all()
    assert (np.asarray(bm.concat_pages((pages[0],), (4, 8)))
            == pages[0]).all()
    # a family's lanes: every page of every member, the lanes past a
    # member's last real one pointed at the dump segment (4)
    seg_ids = np.array([0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 4, 4], np.int32)
    counts = np.asarray(bm.segment_count(flat, seg_ids, 8))
    want = bm.segment_count_np(flat, seg_ids, 8)
    assert (counts == want).all()
    assert counts[3] == 0 and (counts[5:] == 0).all()
    assert counts[2] == np.bitwise_count(flat[8:10]).sum()


def _page_view(rng, shape, page_lanes):
    """A PageView over random words, paged as memory/pages.py pages a
    stack: fixed-size lane blocks, the last one zero-padded."""
    import numpy as np

    from pilosa_tpu.executor import stacked as stk

    lanes = int(np.prod(shape[:-1]))
    flat = rng.integers(0, 1 << 32, size=(lanes, shape[-1]),
                        dtype=np.uint32)
    n_pages = -(-lanes // page_lanes)
    padded = np.zeros((n_pages * page_lanes, shape[-1]), np.uint32)
    padded[:lanes] = flat
    pages = [padded[i * page_lanes:(i + 1) * page_lanes]
             for i in range(n_pages)]
    return stk.PageView(shape, lanes, page_lanes, pages)


def _finalize_one_group(leaves, subs):
    """(plan, program leaves, params, outputs) of one group built
    straight on RaggedProgram: `leaves` are the builder's leaves,
    `subs` its sub-plans over them."""
    import types

    from pilosa_tpu.executor import ragged
    from pilosa_tpu.executor import stacked as stk

    prog = ragged.RaggedProgram()
    builder = types.SimpleNamespace(leaves=list(leaves), params=[])
    prog.add_group(builder, [([], sub, None, None) for sub in subs])
    plan, pleaves, params, _served, _table, _mesh = prog.finalize()
    outs = stk._plan_run(plan)(tuple(pleaves), tuple(params))
    return plan, pleaves, params, outs


@pytest.mark.parametrize("shape,page_lanes", [
    ((8, 16), 4),        # multi-page, every page full
    ((3, 3, 16), 4),     # multi-page, one real lane in the last page
    ((3, 16), 4),        # one page, partly filled
    ((4, 16), 4),        # one page, the leaf IS the page
    ((2, 5, 16), 32),    # one page much larger than the leaf
], ids=["multipage", "ragged-tail", "one-page-tail", "one-page-exact",
        "one-page-wide"])
def test_virtual_leaf_equals_assemble_pages(shape, page_lanes):
    """The in-program assembly of a virtual leaf is bm.assemble_pages
    of the same PageView, and the program is handed the leaf's real
    pages and nothing else."""
    import numpy as np

    from pilosa_tpu.ops import bitmap as bm

    pv = _page_view(np.random.default_rng(11), shape, page_lanes)
    want = np.asarray(bm.assemble_pages(tuple(pv.pages), pv.shape))
    plan, pleaves, _params, outs = _finalize_one_group(
        [pv], [("words", ("leaf", 0))])
    assert plan[:3] == ("ragged", len(pv.pages),
                        ((0, len(pv.pages), tuple(shape)),))
    assert [id(p) for p in pleaves] == [id(p) for p in pv.pages]
    assert (np.asarray(outs[0]) == want).all()


@pytest.mark.parametrize("shards,planes,page_lanes", [
    (5, 3, 4),      # 15 lanes: pages cut across shards and planes
    (3, 9, 32),     # one page holds the leaf and padding
    (16, 4, 8),     # whole pages, every page full
    (33, 18, 32),   # deep field, many pages, a ragged tail
], ids=["straddling", "one-page", "whole-pages", "deep"])
def test_bsi_leaf_is_gathered_plane_by_plane(shards, planes, page_lanes):
    """A BSI leaf that only the compare and the sum read is, inside
    the program, its planes, each a row gather out of the page
    concatenation (bm.concat_pages `planes`) — the last page's
    padding, poisoned here, reaches no consumer — and the answers are
    those of the resident (S, P, W) stack; a leaf a GroupBy kernel
    blocks stays that stack."""
    import numpy as np

    from pilosa_tpu.executor import stacked as stk
    from pilosa_tpu.ops import bitmap as bm
    from pilosa_tpu.ops import bsi

    shape = (shards, planes, 16)
    pv = _page_view(np.random.default_rng(14), shape, page_lanes)
    want = np.concatenate(pv.pages)[:pv.lanes].reshape(shape).copy()
    pv.pages[-1][pv.lanes % page_lanes or page_lanes:] = 0xFFFFFFFF
    got = bm.concat_pages(tuple(pv.pages), shape, planes=True)
    assert isinstance(got, tuple) and len(got) == planes
    assert all((np.asarray(g) == want[:, r]).all()
               for r, g in enumerate(got))
    assert (np.asarray(bm.concat_pages(tuple(pv.pages), shape))
            == want).all()
    row = np.random.default_rng(15).integers(
        0, 1 << 32, size=(shards, 16), dtype=np.uint32)
    subs = [("words", ("bsi_notnull", 0)),
            ("words", ("bsi_null", 0, 1)),
            ("bsi_sum", 0, ("leaf", 1), False)]
    xla, kernel = set(), set()
    stk._plane_readers(tuple(subs), xla, kernel)
    assert (xla, kernel) == ({0}, set())
    stk._plane_readers((("gb_hist", 2, None, 0, 4, False, "xla", None),),
                       xla, kernel)
    assert kernel == {0}
    plan, pleaves, _params, outs = _finalize_one_group([pv, row], subs)
    assert plan[2] == ((0, len(pv.pages), shape),)
    assert [id(p) for p in pleaves[:-1]] == [id(p) for p in pv.pages]
    assert (np.asarray(outs[0]) == want[:, 0]).all()
    assert (np.asarray(outs[1]) == row & ~want[:, 0]).all()
    cnt, pos, neg = (np.asarray(o) for o in outs[2])
    for si in range(shards):
        c, p_, n = bsi.sum_counts(want[si], row[si])
        assert cnt[si] == c
        assert (pos[si] == np.asarray(p_)).all()
        assert (neg[si] == np.asarray(n)).all()


def test_virtual_leaf_shared_by_two_subplans_is_assembled_once():
    """Two sub-plans over one leaf read ONE virtual leaf: its pages
    enter the program once, beside the other leaf's and before the
    direct leaf."""
    import numpy as np

    from pilosa_tpu.ops import bitmap as bm

    rng = np.random.default_rng(12)
    a = _page_view(rng, (5, 16), 4)
    b = _page_view(rng, (5, 16), 2)
    direct = rng.integers(0, 1 << 32, size=(5, 16), dtype=np.uint32)
    both = ("nary", "intersect", (("leaf", 0), ("leaf", 1)))
    plan, pleaves, _params, outs = _finalize_one_group(
        [a, b, direct],
        [("words", ("leaf", 0)), ("count", both, False),
         ("count", ("nary", "union", (("leaf", 0), ("leaf", 2))),
          False)])
    assert plan[1] == len(a.pages) + len(b.pages) == 5
    assert plan[2] == ((0, 2, (5, 16)), (2, 3, (5, 16)))
    assert len(pleaves) == plan[1] + 1 and pleaves[-1] is direct
    assert len({id(p) for p in pleaves}) == len(pleaves)
    wa = np.asarray(bm.assemble_pages(tuple(a.pages), a.shape))
    wb = np.asarray(bm.assemble_pages(tuple(b.pages), b.shape))
    assert (np.asarray(outs[0]) == wa).all()
    assert (np.asarray(outs[1])
            == np.bitwise_count(wa & wb).sum(axis=-1)).all()
    assert (np.asarray(outs[2])
            == np.bitwise_count(wa | direct).sum(axis=-1)).all()


def test_segment_family_reduces_its_members_own_pages():
    """A family of single-leaf Counts: each member's pages enter once
    (a member that a plain sub also reads shares its run), the lanes
    of a partly filled last page fall into the dump segment, and
    every count is exact."""
    import numpy as np

    from pilosa_tpu.ops import bitmap as bm

    rng = np.random.default_rng(13)
    views = [_page_view(rng, (5, 16), 2) for _ in range(3)]
    # poison the padding lanes: only the dump segment may see them
    for pv in views:
        pv.pages[-1][1:] = 0xFFFFFFFF
    subs = [("count", ("leaf", i), True) for i in range(3)]
    subs.append(("words", ("leaf", 1)))
    plan, pleaves, params, outs = _finalize_one_group(views, subs)
    seg = plan[3][-1]
    assert seg[0] == "segcount" and seg[3] == 4      # pow2(3 + dump)
    # leaf 1 is also read by the words sub: its run comes first and
    # the family points at the same pages
    assert plan[2] == ((0, 3, (5, 16)),)
    assert seg[1] == ((3, 3), (0, 3), (6, 3))
    assert plan[1] == len(pleaves) == 9
    assert len({id(p) for p in pleaves}) == 9
    seg_ids = params[seg[2]]
    assert seg_ids.shape == (18,) and (seg_ids[5::6] == 3).all()
    got = np.asarray(outs[-1])
    for slot, i in enumerate((0, 1, 2)):
        flat = np.concatenate(views[i].pages)[:5]
        assert got[slot] == np.bitwise_count(flat).sum()
    assert got[3] == 3 * 16 * 32                      # the poison
    assert (np.asarray(outs[0]) == np.asarray(bm.assemble_pages(
        tuple(views[1].pages), (5, 16)))).all()


def test_raw_pages_view(holder):
    """stacked.raw_pages(): a paged stack fetch returns a PageView
    whose pages — decoded at the container boundary, some may be
    packed/run-encoded (memory/encode.py) — concatenate to the
    assembled operand."""
    import numpy as np

    from pilosa_tpu.executor import stacked as stk
    from pilosa_tpu.memory import encode
    from pilosa_tpu.models.view import VIEW_STANDARD

    ex = Executor(holder)
    idx = holder.index("alpha")
    f = idx.field("a")
    skey = tuple(sorted(idx.available_shards))
    whole = np.asarray(ex.stacked.row_stack(
        idx, f, (VIEW_STANDARD,), 1, skey))
    with stk.raw_pages():
        pv = ex.stacked.row_stack(idx, f, (VIEW_STANDARD,), 1, skey)
    assert isinstance(pv, stk.PageView)
    flat = np.concatenate([np.asarray(encode.to_dense(p))
                           for p in pv.pages])
    got = flat[: pv.lanes].reshape(pv.shape)
    assert (got == whole).all()
    # outside the context the same fetch assembles again
    again = np.asarray(ex.stacked.row_stack(
        idx, f, (VIEW_STANDARD,), 1, skey))
    assert (again == whole).all()


def test_property_random_mixed_batches_with_writes():
    """Seeded random mixed-index/mixed-shard batches of
    Count/Row/Sum/TopN stay bit-exact vs solo execution while writes
    interleave between rounds."""
    rng = random.Random(7)
    h = build_mixed_holder()
    plain = Executor(h)
    srv = Executor(h)
    srv.enable_serving(window_s=0.02, max_batch=64, cache_bytes=0,
                       admission=False)
    writer = Executor(h)

    def tree(index, depth):
        fields = ([("a", 4), ("b", 6)] if index == "alpha"
                  else [("c", 3)])
        if depth <= 0 or rng.random() < 0.45:
            if rng.random() < 0.3:
                vf = "v" if index == "alpha" else "w"
                op = rng.choice([">", "<", ">=", "<=", "=="])
                return f"Row({vf} {op} {rng.randrange(-20, 120)})"
            f, r = rng.choice(fields)
            return f"Row({f}={rng.randrange(r)})"
        op = rng.choice(["Union", "Intersect", "Difference", "Xor"])
        kids = ", ".join(tree(index, depth - 1)
                         for _ in range(rng.randrange(2, 4)))
        return f"{op}({kids})"

    def query(index):
        t = tree(index, 2)
        wrap = rng.randrange(5)
        if wrap == 0:
            return f"Count({t})"
        if wrap == 1:
            tf = "a" if index == "alpha" else "c"
            return f"TopN({tf}, {t}, n=3)"
        if wrap == 2:
            vf = "v" if index == "alpha" else "w"
            return f"Sum({t}, field=vf)".replace("vf", vf)
        if wrap == 3:
            return t
        return f"Count({t})"

    n_shards = {"alpha": 3, "beta": 5}
    for round_ in range(5):
        items = []
        for _ in range(10):
            index = rng.choice(["alpha", "beta"])
            shards = None
            if rng.random() < 0.3:
                shards = sorted(rng.sample(
                    range(n_shards[index]),
                    rng.randrange(1, n_shards[index] + 1)))
            items.append((index, query(index), shards))
        # dedupe (same (index, query, shards) twice would race the
        # dict; results identical anyway)
        items = list({(i, q, tuple(s) if s else None): (i, q, s)
                      for i, q, s in items}.values())
        want = solo_expect(plain, items)
        got = run_concurrent(srv, items)
        assert got == want, f"round {round_}"
        for _ in range(6):
            index = rng.choice(["alpha", "beta"])
            col = rng.randrange(n_shards[index] * h.index(index).width)
            f = rng.choice(["a", "b"] if index == "alpha" else ["c"])
            writer.execute(index, f"Set({col}, {f}={rng.randrange(3)})")


def test_monotone_counts_under_concurrent_writes():
    """The stale-snapshot re-execution path: readers hammering the
    ragged serving path while a writer adds bits must never see a
    torn or stale (non-monotone) count."""
    h = build_mixed_holder()
    srv = Executor(h)
    srv.enable_serving(window_s=0.001, max_batch=32, cache_bytes=0,
                       admission=False)
    writer = Executor(h)
    n_writes, n_readers, n_iters = 80, 6, 30
    errs: list = []

    def write():
        try:
            for c in range(n_writes):
                writer.execute("alpha", f"Set({c}, a=9)")
                writer.execute("beta", f"Set({c}, c=9)")
        except Exception as e:  # pragma: no cover
            errs.append(e)

    def read(index, row):
        try:
            prev = -1
            for _ in range(n_iters):
                (n,) = srv.execute_serving(
                    index, f"Count(Row({row}=9))")
                assert n >= prev, (index, n, prev)
                prev = n
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=write)] + [
        threading.Thread(target=read,
                         args=("alpha", "a") if i % 2 else
                         ("beta", "c"))
        for i in range(n_readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    (na,) = Executor(h).execute("alpha", "Count(Row(a=9))")
    (nb,) = Executor(h).execute("beta", "Count(Row(c=9))")
    assert na == n_writes and nb == n_writes


def _run_one_batch(layer, items):
    """Drive ONE deterministic batch through the leader protocol
    (bypassing the timing-dependent admission window)."""
    from pilosa_tpu.pql import parse

    reqs = []
    for index, q, shards in items:
        idx = layer.executor.holder.index(index)
        r = layer._classify(index, idx, parse(q), shards, None,
                            (index, q, None))
        assert r is not None, (index, q)
        reqs.append(r)
    layer._run_batch(reqs)
    out = {}
    for (index, q, shards), r in zip(items, reqs):
        assert r.error is None and not r.direct and \
            r.result is not None, (index, q)
        out[(index, q, tuple(shards) if shards else None)] = [
            serialize_result(x) for x in r.result]
    return out


def test_canonical_composition_stabilizes_executable(holder):
    """Composition hysteresis: once the canonical slot set covers the
    traffic, EVERY batch — whatever subset of the mix it carries —
    dispatches the same fused program.  After the union plan exists,
    re-running either sub-composition compiles nothing new."""
    from pilosa_tpu.executor import stacked as stk

    srv = Executor(holder)
    layer = srv.enable_serving(window_s=0.05, max_batch=64,
                               cache_bytes=0, admission=False)
    plain = Executor(holder)
    batch1 = [("alpha", "Count(Row(a=0))", None),
              ("alpha", "Count(Row(a=1))", None),
              ("beta", "Count(Row(c=0))", None)]
    batch2 = [("alpha", "Count(Row(b=1))", None),
              ("alpha", "Count(Row(b=3))", None),
              ("beta", "Count(Row(c=2))", None)]
    # first sighting rides the extras program (probation); the second
    # sighting promotes into the canonical set
    assert _run_one_batch(layer, batch1) == solo_expect(plain, batch1)
    assert len(layer._ragged_canon.slots) == 0
    assert _run_one_batch(layer, batch1) == solo_expect(plain, batch1)
    assert len(layer._ragged_canon.slots) == 3
    assert _run_one_batch(layer, batch2) == solo_expect(plain, batch2)
    assert _run_one_batch(layer, batch2) == solo_expect(plain, batch2)
    assert len(layer._ragged_canon.slots) == 6
    union_sigs = {s for s in stk._JIT_CACHE
                  if s.startswith("('ragged'")}
    assert union_sigs
    # steady state: both compositions now ride the ONE union plan —
    # no new executable for either sub-composition
    assert _run_one_batch(layer, batch1) == solo_expect(plain, batch1)
    assert _run_one_batch(layer, batch2) == solo_expect(plain, batch2)
    assert {s for s in stk._JIT_CACHE
            if s.startswith("('ragged'")} == union_sigs
    assert len(layer._ragged_canon.slots) == 6


def _spy_dispatches(monkeypatch):
    """Every (plan, leaves, params) the ragged plane dispatches."""
    from pilosa_tpu.executor import ragged

    seen = []
    real = ragged._dispatch_served

    def spy(plan, leaves, params, *rest):
        seen.append((plan, list(leaves), list(params)))
        return real(plan, leaves, params, *rest)
    monkeypatch.setattr(ragged, "_dispatch_served", spy)
    return seen


def _page_runs(plan):
    """Every (leaf_start, n_pages) run a "ragged" plan reads."""
    runs = {(start, n) for start, n, _shape in plan[2]}
    for sub in plan[3]:
        if sub[0] == "segcount":
            runs.update(sub[1])
    return sorted(runs)


def test_program_receives_exactly_the_real_pages(holder, monkeypatch):
    """Small pages, a mixed batch with a segment family: the page
    leaves of every dispatched program are the runs its plan names,
    back to back — no padding page, no page object twice — and the
    answers equal the solo path's."""
    from pilosa_tpu.executor import stacked as stk

    prev = memory.page_bytes()
    memory.configure(page_bytes=256 << 10)   # 2 lanes of 2**15 words
    try:
        plain = Executor(holder)
        srv = Executor(holder)
        layer = srv.enable_serving(window_s=0.05, max_batch=64,
                                   cache_bytes=0, admission=False)
        seen = _spy_dispatches(monkeypatch)
        assert _run_one_batch(layer, MIXED) == solo_expect(plain, MIXED)
    finally:
        memory.configure(page_bytes=prev)
    assert seen
    family_runs = set()
    for plan, leaves, _params in seen:
        assert plan[0] == "ragged"
        cur = 0
        for start, n in _page_runs(plan):
            assert start == cur and n >= 1
            cur += n
        assert cur == plan[1] <= len(leaves)
        pages = leaves[:plan[1]]
        assert len({id(p) for p in pages}) == len(pages)
        assert all(p.ndim == 2 for p in pages)
        assert not any(isinstance(x, stk.PageView) for x in leaves)
        for sub in plan[3]:
            if sub[0] == "segcount":
                family_runs.update(n for _start, n in sub[1])
    # one family of single-leaf Counts spans both indexes: alpha's
    # 3-shard rows (2 pages, half of the second empty) and beta's
    # 2-shard rows (1 page)
    assert family_runs == {1, 2}


def test_same_structure_different_rows_share_one_executable(
        holder, monkeypatch):
    """The plan keys on structure (tree shapes, each leaf's page
    count and shape), never on which rows ride: a later batch of the
    same templates over other rows builds the same plan and runs the
    first batch's executable."""
    from pilosa_tpu.executor import stacked as stk

    srv = Executor(holder)
    layer = srv.enable_serving(window_s=0.05, max_batch=64,
                               cache_bytes=0, admission=False)
    plain = Executor(holder)
    seen = _spy_dispatches(monkeypatch)

    def batch(i):
        # no string twice: a repeat would be promoted into the
        # canonical program, another composition
        return [("alpha", f"Count(Intersect(Row(a={i}), Row(b={i})))",
                 None),
                ("alpha", f"Sum(Row(a={i}), field=v)", None),
                ("alpha", f"TopN(b, Row(a={i}), n=2)", None),
                ("alpha", f"Count(Row(a={i}))", None),
                ("alpha", f"Count(Row(b={i}))", None)]

    assert _run_one_batch(layer, batch(0)) == solo_expect(plain, batch(0))
    (plan, _leaves, _params), = seen
    assert any(sub[0] == "segcount" for sub in plan[3])
    fn = stk._JIT_CACHE[repr(plan)][0]
    n_exec = fn._cache_size()
    for i in (1, 2, 3):
        assert _run_one_batch(layer, batch(i)) \
            == solo_expect(plain, batch(i))
    assert [p for p, _l, _p in seen] == [plan] * 4
    assert stk._JIT_CACHE[repr(plan)][0] is fn
    assert fn._cache_size() == n_exec
