"""A BSI field's planes answer the same wherever a reader finds them.

The plane stack is resident (S, 2+depth, W), lane = shard * (2+depth)
+ plane, in pages.  Its readers take it three ways: the solo engine
assembles the stack (``_expand_view``) and slices a plane per shard;
a ragged program gathers each plane out of its page concatenation
(ops/bitmap.py concat_pages ``planes``) for the compare and the sum,
and hands a GroupBy kernel the stack; the host twin
``plane_stack_np`` is numpy.

One parametrised case per (depth, sign, shard count), shard counts
that are no multiple of a tile and pass one page: all three answer
every read of an int field as the loop executor does; a Set of a new
value, an overwrite and a Clear on the resident stack are each read
back through a patch and never a rebuild; so is the stack after a
page was evicted.
"""

import numpy as np
import pytest

from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.memory import encode
from pilosa_tpu.memory.pages import PagedStack
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.schema import FieldOptions, FieldType
from pilosa_tpu.obs import metrics

WIDTH = 2048                     # 64 words a lane
PAGE_BYTES = 8 * (WIDTH // 32) * 4   # 8 lanes a page


def _holder(depth, signed, n_shards):
    rng = np.random.default_rng(depth * 100 + n_shards + signed)
    hi = (1 << depth) - 1
    lo = -hi if signed else 0
    h = Holder(width=WIDTH)
    idx = h.create_index("i", track_existence=True)
    cols = np.unique(rng.integers(0, WIDTH * n_shards, size=300 * n_shards))
    cols = cols[cols % 7 != 0]                    # columns left free
    g = idx.create_field("g", FieldOptions(type=FieldType.MUTEX))
    g.import_bits(rng.integers(0, 3, size=cols.size), cols)
    f = idx.create_field("f", FieldOptions(type=FieldType.INT,
                                           min=lo, max=hi))
    vals = rng.integers(lo, hi + 1, size=cols.size)
    vals[:4] = (lo, hi, lo, hi)                   # both ends present
    f.import_values(cols, vals.tolist())
    idx.mark_columns_exist([int(c) for c in cols])
    assert f.bit_depth == depth
    return h, lo, hi


def _queries(lo, hi):
    k = (lo + hi) // 2
    a, b = lo + (hi - lo) // 4, hi - (hi - lo) // 4
    agg = "GroupBy(Rows(g), aggregate=%s(field=f))"
    return [f"Count(Row(f > {k}))", f"Row(f > {k})",
            f"Count(Row({a} < f < {b}))", "Sum(field=f)",
            f"Sum(Row(g=1), field=f)", "Min(field=f)", "Max(field=f)",
            f"Max(Row(f < {k}), field=f)", "Distinct(field=f)",
            agg % "Sum", agg % "Min", agg % "Max"]


def _norm(res):
    return [r.columns().tolist() if hasattr(r, "columns") else repr(r)
            for r in res]


def _planes_entry(ex):
    [ps] = [e[1] for k, e in ex.stacked.cache._entries.items()
            if k[0] == "planes"]
    assert isinstance(ps, PagedStack)
    return ps


def _assert_layout(ex, depth, n_shards):
    """The resident stack is (S, 2+depth, W) on the shard axis, in
    pages of at most 8 lanes, and the last page's lanes past the stack
    are zero."""
    ps = _planes_entry(ex)
    assert ps.shape == (n_shards, 2 + depth, WIDTH // 32)
    assert ps.shard_axis == 0
    assert ps.page_lanes <= 8
    assert ps.n_pages == -(-ps.lanes // ps.page_lanes)
    if ps.pages[-1] is not None:
        block = np.asarray(encode.to_dense(ps.pages[-1]))
        assert not block[len(ps.page_lane_ids(ps.n_pages - 1)):].any()


@pytest.mark.parametrize("n_shards", [1, 3, 9, 33])
@pytest.mark.parametrize("signed", [False, True],
                         ids=["unsigned", "signed"])
@pytest.mark.parametrize("depth", [1, 7, 16])
def test_plane_stack_readers(depth, signed, n_shards, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES", str(PAGE_BYTES))
    # the device arm of the one-pass histogram, as a TPU would take it
    # (a CPU would otherwise hand every GroupBy to the host twin)
    monkeypatch.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "xla")
    h, lo, hi = _holder(depth, signed, n_shards)
    queries = _queries(lo, hi)
    loop = Executor(h)
    loop.use_stacked = False
    twin = Executor(h)
    twin.stacked.host_only = True
    solo = Executor(h)                       # _expand_view
    served = Executor(h)                     # the ragged program
    served.enable_serving(cache_bytes=0)
    engines = {"twin": twin.execute, "solo": solo.execute,
               "served": lambda i, q: served.execute_serving(i, q)}

    def check(step, queries=queries):
        for q in queries:
            want = _norm(loop.execute("i", q))
            for name, run in engines.items():
                assert _norm(run("i", q)) == want, (step, name, q)
        for ex in (solo, served):
            _assert_layout(ex, depth, n_shards)

    check("cold")
    assert np.asarray(twin.stacked.plane_stack_np(
        h.index("i"), h.index("i").field("f"),
        tuple(range(n_shards)))).shape == (n_shards, 2 + depth,
                                           WIDTH // 32)
    col = -(-(n_shards - 1) * WIDTH // 7) * 7 + 7   # free, last shard
    writes = [f"Set({col}, f={hi})",          # a new value
              f"Set({col}, f={lo})",          # overwritten
              f"Set(14, f={max(lo, -1)})",
              f"Clear({col}, f={lo})"]
    # one read of each kind is enough to see a write: compare, sum,
    # value histogram, GroupBy
    reads = [queries[0], queries[3], queries[5], queries[9]]
    for w in writes:
        loop.execute("i", w)
        before = {ex: (ex.stacked.cache.patches,
                       ex.stacked.cache.full_rebuilds)
                  for ex in (solo, served)}
        p0 = metrics.STACK_CACHE.value(outcome="patch")
        r0 = metrics.STACK_CACHE.value(outcome="rebuild")
        check(w, reads)
        assert metrics.STACK_CACHE.value(outcome="patch") > p0
        assert metrics.STACK_CACHE.value(outcome="rebuild") == r0
        for ex, (p, r) in before.items():
            assert ex.stacked.cache.patches > p, w
            assert ex.stacked.cache.full_rebuilds == r, w
    for ex in (solo, served):                 # one page evicted
        cache = ex.stacked.cache
        ps = _planes_entry(ex)
        [key] = [k for k in cache._entries if k[0] == "planes"]
        nb = encode.page_nbytes(ps.pages[-1])
        with cache._lock:
            ps.pages[-1] = None
            cache._sync_entry_locked(key, ps)
        cache._client.release(nb)
        n0 = cache.page_rebuilds
        check("evicted", reads)
        assert cache.page_rebuilds == n0 + 1
