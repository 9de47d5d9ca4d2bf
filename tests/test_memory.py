"""HBM residency manager (ISSUE 5): budget ledger, paged device
stacks, cost-aware eviction, prefetch, and the OOM backstop.

Covers the acceptance bar directly: queries stay bit-exact with the
budget clamped below the working set; an injected RESOURCE_EXHAUSTED
is absorbed (evict + retry, then host fallback — never a failed
query); the concurrency satellite (N threads hammering get/reserve
against cross-client reclaim) pins the ledger's core invariant —
accounted bytes never exceed the budget, and accounting drains to
exactly zero.
"""

from __future__ import annotations

import logging
import threading

import numpy as np
import pytest

from pilosa_tpu import memory
from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.executor.serving import ResultCache
from pilosa_tpu.executor.stacked import TileStackCache
from pilosa_tpu.memory import pressure
from pilosa_tpu.memory.ledger import Ledger
from pilosa_tpu.memory.policy import Prefetcher
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import flight, metrics

W = 1 << 15  # small shard width keeps stacks tiny and fast


def _build(n_shards=8, n_rows=8, width=W):
    h = Holder(width=width)
    idx = h.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(5)
    rows = rng.integers(0, n_rows, size=4000)
    cols = rng.integers(0, n_shards * width, size=4000)
    f.import_bits(rows, cols)
    from pilosa_tpu.models.schema import FieldOptions, FieldType
    v = idx.create_field("v", FieldOptions(
        type=FieldType.INT, min=0, max=127))
    v.import_values(cols[:500] % (n_shards * width),
                    (cols[:500] % 97).astype(np.int64))
    return h


@pytest.fixture
def restore_memory():
    """Snapshot/restore the process memory knobs: these tests clamp
    the GLOBAL ledger and toggles, and must leave no trace."""
    led = memory.ledger()
    prev = (memory._paged_default, memory._page_bytes_default,
            pressure.OOM_RETRY, pressure.HOST_FALLBACK)
    yield
    (memory._paged_default, memory._page_bytes_default,
     pressure.OOM_RETRY, pressure.HOST_FALLBACK) = prev
    led.set_budget(None)


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------

def test_ledger_reserve_release_denial():
    led = Ledger(budget_bytes=1000)
    c = led.register("a")
    assert led.budget() == 1000
    assert c.reserve(600)
    assert led.total_bytes == 600
    assert not c.reserve(500)       # would cross the budget, no reclaim
    assert led.total_bytes == 600   # denial leaves accounting untouched
    assert not c.reserve(2000)      # alone exceeds the budget outright
    c.release(600)
    assert led.total_bytes == 0
    assert c.reserve(1000)          # exact fit admitted
    c.release(1000)


def test_ledger_cross_client_reclaim():
    """Pressure in one client sheds cold bytes in another."""
    led = Ledger(budget_bytes=1000)
    state = {"held": 0}

    def reclaim_a(need):
        freed = min(state["held"], need)
        state["held"] -= freed
        a.release(freed)
        return freed

    a = led.register("a", reclaim=reclaim_a, cold_ts=lambda: 1.0)
    b = led.register("b", cold_ts=lambda: 2.0)
    assert a.reserve(900)
    state["held"] = 900
    assert b.reserve(400)           # forces a to shed 300+
    assert led.total_bytes <= 1000
    assert b.bytes == 400
    assert a.bytes <= 600


def test_ledger_env_budget(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_MEMORY_BUDGET_BYTES", "12345")
    assert Ledger().budget() == 12345


def test_ledger_shrink_reclaims():
    led = Ledger(budget_bytes=1000)
    pool = {"held": 800}

    def reclaim(need):
        freed = min(pool["held"], need)
        pool["held"] -= freed
        c.release(freed)
        return freed

    c = led.register("a", reclaim=reclaim)
    assert c.reserve(800)
    led.set_budget(500)
    assert led.total_bytes <= 500


def test_ledger_dead_clients_drop_out():
    led = Ledger(budget_bytes=1000)
    c = led.register("ghost")
    assert c.reserve(700)
    del c
    import gc
    gc.collect()
    assert led.total_bytes == 0     # weakref pruning, no leaked bytes
    c2 = led.register("live")
    assert c2.reserve(1000)


def test_concurrent_reserve_reclaim_race():
    """Satellite: N threads hammer reserve/release while reclaim
    evicts across clients — the accounted total NEVER exceeds the
    budget, and accounting returns to exactly zero after drain."""
    budget = 64 << 10
    led = Ledger(budget_bytes=budget)
    n_threads = 8
    lock = threading.Lock()
    pools: dict[int, int] = {i: 0 for i in range(n_threads)}
    clients = {}

    def make_reclaim(i):
        def reclaim(need):
            with lock:
                freed = min(pools[i], need)
                pools[i] -= freed
            if freed:
                clients[i].release(freed)
            return freed
        return reclaim

    for i in range(n_threads):
        clients[i] = led.register(f"c{i}", reclaim=make_reclaim(i))
    violations = []
    stop = threading.Event()

    def watcher():
        while not stop.is_set():
            t = led.total_bytes
            if t > budget:
                violations.append(t)

    def hammer(i):
        rng = np.random.default_rng(i)
        for _ in range(300):
            n = int(rng.integers(256, 4096))
            if clients[i].reserve(n):
                with lock:
                    pools[i] += n
            if rng.random() < 0.4:
                with lock:
                    give = pools[i] // 2
                    pools[i] -= give
                if give:
                    clients[i].release(give)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    wt = threading.Thread(target=watcher)
    wt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    wt.join()
    assert not violations, f"ledger exceeded budget: {violations[:3]}"
    # drain: release everything still held — accounting must zero out
    for i in range(n_threads):
        with lock:
            n, pools[i] = pools[i], 0
        if n:
            clients[i].release(n)
    assert led.total_bytes == 0


def test_concurrent_stack_cache_under_pressure():
    """Satellite, engine-level: handler threads racing a
    ledger-clamped TileStackCache stay exact and keep accounting
    consistent (no lost or double-counted bytes)."""
    h = _build(n_shards=8)
    ex = Executor(h)
    led = Ledger(budget_bytes=24 << 10)  # far below the working set
    ex.stacked.cache = TileStackCache(ledger=led)
    want = [ex.execute("i", f"Count(Row(f={r}))")[0] for r in range(8)]
    errs = []

    def worker(seed):
        try:
            rng = np.random.default_rng(seed)
            for _ in range(20):
                r = int(rng.integers(0, 8))
                got = ex.execute("i", f"Count(Row(f={r}))")[0]
                assert got == want[r], (r, got, want[r])
        except Exception as e:  # pragma: no cover - failure detail
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    cache = ex.stacked.cache
    assert led.total_bytes <= led.budget()
    with cache._lock:
        assert cache.nbytes == sum(
            e[2] for e in cache._entries.values())
    stack_bytes = cache._client.bytes
    assert stack_bytes == cache.nbytes
    cache.clear()
    assert cache._client.bytes == 0


# ---------------------------------------------------------------------------
# paged residency
# ---------------------------------------------------------------------------

def test_paged_bit_exact_under_budget_clamp(restore_memory):
    """Acceptance: with the budget clamped to HALF the working set,
    the query suite stays bit-exact vs the unbounded run."""
    h = _build(n_shards=8)
    plain = Executor(h)
    queries = ([f"Count(Row(f={r}))" for r in range(8)]
               + ["Count(Intersect(Row(f=1), Row(f=2)))",
                  "TopN(f, n=4)", "Sum(Row(f=1), field=v)",
                  "GroupBy(Rows(f))"])
    want = [repr(plain.execute("i", q)) for q in queries]
    ws = plain.stacked.cache.nbytes
    assert ws > 0
    ex = Executor(h)
    budget = max(ws // 2, 4096)
    led = Ledger(budget_bytes=budget)
    c = ex.stacked.cache = TileStackCache(ledger=led)
    for _ in range(3):
        got = [repr(ex.execute("i", q)) for q in queries]
        assert got == want
        # what is accounted resident never exceeds the clamp
        assert c.nbytes <= budget and led.total_bytes <= budget
    assert c.misses > 0  # the clamp produced genuine pressure


def test_page_eviction_rebuilds_only_missing_pages(monkeypatch):
    """A fresh entry with evicted pages restores ONLY those pages
    (outcome page_rebuild, moved < full size) — the sub-stack
    granularity the whole PR is about.  Pinned dense: the byte
    arithmetic below assumes pages at their fixed dense size (the
    sparse device format's variable-size accounting has its own
    suite, tests/test_sparse_format.py)."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES", "8192")
    h = _build(n_shards=16)
    ex = Executor(h)
    led = Ledger(budget_bytes=1 << 20)
    cache = ex.stacked.cache = TileStackCache(ledger=led)
    want = ex.execute("i", "Count(Row(f=3))")[0]
    [(key, ent)] = [(k, e) for k, e in cache._entries.items()
                    if k[0] == "row" and k[4] == 3]
    from pilosa_tpu.memory.pages import PagedStack
    ps = ent[1]
    assert isinstance(ps, PagedStack) and ps.n_pages > 1
    full = ps.lanes * ps.width_words * 4
    # evict exactly one page
    with cache._lock:
        ps.pages[0] = None
        cache._sync_entry_locked(key, ps)
    cache._client.release(ps.page_nbytes)
    r0 = cache.rebuilt_bytes
    assert ex.execute("i", "Count(Row(f=3))")[0] == want
    assert cache.page_rebuilds == 1
    restacked = cache.rebuilt_bytes - r0
    assert 0 < restacked < full
    assert restacked == ps.page_nbytes


def test_patch_applies_to_single_page(monkeypatch):
    """A point write patches the one page holding its lane.  Pinned
    dense: patched-byte bounds assume the dense word-scatter arm
    (an encoded page rebuilds instead — tests/test_sparse_format.py
    covers that path)."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES", "8192")
    h = _build(n_shards=16)
    ex = Executor(h)
    cache = ex.stacked.cache = TileStackCache(
        ledger=Ledger(budget_bytes=1 << 20))
    before = ex.execute("i", "Count(Row(f=3))")[0]
    free_col = 15 * W + 77
    ex.execute("i", f"Set({free_col}, f=3)")
    p0 = cache.patched_bytes
    assert ex.execute("i", "Count(Row(f=3))")[0] == before + 1
    assert cache.patches == 1
    assert 0 < cache.patched_bytes - p0 <= 8192


def test_broad_scan_does_not_evict_hot_pages(monkeypatch):
    """Admission cap: an entry bigger than half the budget streams
    its tail transiently instead of flushing the hot set.  Geometry:
    16 shards x 4 KiB lanes — hot row stacks 64 KiB each (128 KiB
    total), the TopN candidate block 256 KiB, budget 320 KiB.
    Without the cap the TopN reservation would reclaim a hot stack;
    with it the block retains <= 160 KiB and hot stays resident."""
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES", "8192")
    h = _build(n_shards=16, n_rows=4)
    ex = Executor(h)
    cache = ex.stacked.cache = TileStackCache(
        ledger=Ledger(budget_bytes=320 << 10))
    hot = [f"Count(Row(f={r}))" for r in range(2)]
    want = [ex.execute("i", q)[0] for q in hot]
    top = repr(ex.execute("i", "TopN(f, n=4)"))
    h0 = cache.hits
    for _ in range(3):
        for q, w in zip(hot, want):
            assert ex.execute("i", q)[0] == w
        assert repr(ex.execute("i", "TopN(f, n=4)")) == top
    # the hot row stacks stayed resident through every broad scan
    assert cache.hits - h0 >= 6
    assert cache._client.bytes <= 320 << 10


def test_fully_drained_entries_are_dropped():
    """Eviction that drains every page of an entry must drop the
    entry skeleton too — distinct keys would otherwise accumulate
    zombies forever on a long-lived server."""
    h = _build(n_shards=4)
    ex = Executor(h)
    led = Ledger(budget_bytes=1 << 20)
    cache = ex.stacked.cache = TileStackCache(ledger=led)
    for r in range(8):
        ex.execute("i", f"Count(Row(f={r}))")
    assert len(cache._entries) >= 8
    led.reclaim_frac(1.0, trigger="shrink")
    assert cache.nbytes == 0
    assert len(cache._entries) == 0


def test_prewarm_skips_dropped_field(monkeypatch):
    """A recipe whose field was dropped must not rebuild (and
    budget-reserve) a stack no live query can hit — and the recipe is
    dropped so it stops pinning the dead fragments."""
    h = _build(n_shards=4)
    ex = Executor(h)
    led = Ledger(budget_bytes=1 << 20)
    cache = ex.stacked.cache = TileStackCache(ledger=led)
    ex.execute("i", "Count(Row(f=1))")
    [fp] = [f for f, (k, *_r) in cache._recipes.items()
            if k[0] == "row" and k[4] == 1]
    h.index("i").delete_field("f")
    led.reclaim_frac(1.0, trigger="shrink")
    assert cache.prewarm(fp) is False
    assert fp not in cache._recipes
    assert led.total_bytes == 0  # nothing dead got re-reserved


def test_whole_entries_when_paging_disabled(monkeypatch,
                                            restore_memory):
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGED", "0")
    h = _build(n_shards=4)
    ex = Executor(h)
    want = ex.execute("i", "Count(Row(f=1))")[0]
    from pilosa_tpu.memory.pages import PagedStack
    assert all(not isinstance(e[1], PagedStack)
               for e in ex.stacked.cache._entries.values())
    assert ex.execute("i", "Count(Row(f=1))")[0] == want
    assert ex.stacked.cache.hits >= 1


# ---------------------------------------------------------------------------
# satellites: too-big drop, jit cache counters
# ---------------------------------------------------------------------------

def test_too_big_entry_counted_and_warned_once(caplog):
    c = TileStackCache(max_bytes=64)
    big = np.zeros(1024, dtype=np.uint32)
    t0 = metrics.STACK_CACHE.value(outcome="too_big")
    with caplog.at_level(logging.WARNING, "pilosa_tpu.stacked"):
        for _ in range(3):
            got = c.get(("k", 1), (0,), lambda: big)
            assert got is big
    assert c.nbytes == 0
    assert c.too_big == 3
    assert metrics.STACK_CACHE.value(outcome="too_big") == t0 + 3
    warnings = [r for r in caplog.records
                if "exceeds the device budget" in r.message]
    assert len(warnings) == 1  # once per key, not per access


def test_jit_cache_counters_exported():
    h = _build(n_shards=2)
    Executor(h).execute("i", "Count(Row(f=1))")
    text = metrics.registry.render_text()
    assert 'pilosa_jit_cache_total{cache="plan",event="insert"}' in text
    assert "pilosa_jit_cache_entries" in text
    assert metrics.JIT_CACHE_ENTRIES.value(cache="plan") >= 1


def test_jit_cache_eviction_counted():
    from pilosa_tpu.executor import stacked as stk
    e0 = metrics.JIT_CACHE.value(cache="plan", event="evict")
    with stk._JIT_LOCK:
        n_before = len(stk._JIT_CACHE)
    h = _build(n_shards=2)
    ex = Executor(h)
    # distinct tree shapes force distinct plan signatures
    import random
    rng = random.Random(3)
    for i in range(stk._JIT_CACHE_MAX - n_before + 5):
        depth = [f"Row(f={rng.randrange(8)})" for _ in range(2)]
        ex.execute("i", f"Count(Union({', '.join(depth)}, "
                        f"Row(f={i % 8})))" if i % 2 else
                   f"Count(Intersect({', '.join(depth)}))")
    # shape variety is limited; just assert the counter moved if the
    # cache wrapped, and the bound held either way
    with stk._JIT_LOCK:
        assert len(stk._JIT_CACHE) <= stk._JIT_CACHE_MAX
    assert metrics.JIT_CACHE.value(cache="plan", event="evict") >= e0


# ---------------------------------------------------------------------------
# OOM backstop
# ---------------------------------------------------------------------------

def test_injected_oom_absorbed_by_retry(monkeypatch):
    # pinned dense: under the sparse device format a cached
    # Count(Row) serves from host popcounts with NO device dispatch,
    # so the armed injection would never fire (and would leak into
    # the next test's first guarded call)
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "0")
    h = _build(n_shards=4)
    ex = Executor(h)
    want = ex.execute("i", "Count(Row(f=1))")[0]
    r0 = metrics.OOM_TOTAL.value(outcome="retry_ok")
    pressure.inject_oom(1)
    assert ex.execute("i", "Count(Row(f=1))")[0] == want
    assert metrics.OOM_TOTAL.value(outcome="retry_ok") == r0 + 1


def test_persistent_oom_degrades_to_host():
    h = _build(n_shards=4)
    ex = Executor(h)
    want = repr(ex.execute("i", "Sum(Row(f=1), field=v)"))
    f0 = metrics.OOM_TOTAL.value(outcome="host_fallback")
    r0 = metrics.OOM_TOTAL.value(outcome="raised")
    pressure.inject_oom(2)  # first attempt AND the retry fail
    assert repr(ex.execute("i", "Sum(Row(f=1), field=v)")) == want
    assert metrics.OOM_TOTAL.value(outcome="host_fallback") == f0 + 1
    assert metrics.OOM_TOTAL.value(outcome="raised") == r0


def test_oom_reraises_when_fallback_disabled(restore_memory):
    pressure.OOM_RETRY = False
    pressure.HOST_FALLBACK = False
    h = _build(n_shards=2)
    ex = Executor(h)
    ex.execute("i", "Count(Row(f=1))")
    pressure.inject_oom(1)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        ex.execute("i", "Count(Row(f=2))")


def test_is_oom_matches_xla_shapes():
    assert pressure.is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"))
    assert pressure.is_oom(MemoryError("Out of memory"))
    assert not pressure.is_oom(RuntimeError("INVALID_ARGUMENT: nope"))
    assert not pressure.is_oom(ValueError("unrelated"))
    # how a v5e's runtime refuses to load a program for want of HBM
    assert pressure.is_oom(ValueError(
        "RESOURCE_EXHAUSTED: Error loading program 'jit_plan_ragged_extras'"
        ": Attempting to reserve 2.45G at the bottom of memory. That was "
        "not possible. There are 2.29G free, 0B reserved, and 2.29G "
        "reservable."))


def test_a_program_refused_for_want_of_hbm_is_retried_after_relief():
    calls = []

    def run():
        calls.append(1)
        if len(calls) == 1:
            raise ValueError("RESOURCE_EXHAUSTED: Error loading program "
                             "'jit_plan_ragged_extras': Attempting to "
                             "reserve 2.45G at the bottom of memory.")
        return "ok"
    assert pressure.guarded(run) == "ok" and len(calls) == 2


# ---------------------------------------------------------------------------
# prefetcher
# ---------------------------------------------------------------------------

def test_prefetcher_warms_rebuilt_keys(monkeypatch):
    """Flight records of rebuilt stacks drive a warm pass that makes
    the next access a pure hit."""
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES", "8192")
    prev = (flight.recorder.enabled, flight.recorder._ring.maxlen)
    flight.recorder.configure(enabled=True, keep=256)
    flight.recorder.clear()
    try:
        h = _build(n_shards=16)
        ex = Executor(h)
        led = Ledger(budget_bytes=1 << 20)
        cache = ex.stacked.cache = TileStackCache(ledger=led)
        want = ex.execute("i", "Count(Row(f=2))")[0]
        # drop the entry's pages, as budget pressure would
        led.reclaim_frac(1.0, trigger="shrink")
        assert cache.nbytes == 0
        recs = flight.recorder.recent(16)
        assert any(rec.get("stack_keys") for rec in recs)
        warmed = Prefetcher(cache, ledger=led).step()
        assert warmed >= 1
        assert metrics.PREFETCH_TOTAL.value(outcome="warmed") >= 1
        h0, m0 = cache.hits, cache.misses
        assert ex.execute("i", "Count(Row(f=2))")[0] == want
        assert cache.hits == h0 + 1 and cache.misses == m0
    finally:
        flight.recorder.configure(enabled=prev[0], keep=prev[1])


def test_prewarm_after_write_is_not_stale(monkeypatch):
    """Regression: a prewarm replayed AFTER a later write must patch
    against LIVE fragment versions — a recipe whose delta derivation
    captured its creation-time version tuple would see 'nothing
    changed', stamp the fresh versions onto stale content, and serve
    the stale stack to every later query as a cache hit."""
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES", "8192")
    prev = (flight.recorder.enabled, flight.recorder._ring.maxlen)
    flight.recorder.configure(enabled=True, keep=64)
    flight.recorder.clear()
    try:
        h = _build(n_shards=4)
        ex = Executor(h)
        cache = ex.stacked.cache = TileStackCache(
            ledger=Ledger(budget_bytes=1 << 20))
        before = ex.execute("i", "Count(Row(f=1))")[0]
        free_col = 3 * W + 11
        ex.execute("i", f"Set({free_col}, f=1)")
        # prewarm with the post-write versions, then query
        [fp] = [f for f, (k, *_r) in cache._recipes.items()
                if k[0] == "row" and k[4] == 1]
        cache.prewarm(fp)
        assert ex.execute("i", "Count(Row(f=1))")[0] == before + 1
    finally:
        flight.recorder.configure(enabled=prev[0], keep=prev[1])


def test_prefetcher_skips_under_pressure():
    led = Ledger(budget_bytes=1000)
    c = led.register("x")
    assert c.reserve(900)  # >75% used: no headroom for speculation
    cache = TileStackCache(ledger=led)

    class FakeRecorder:
        def recent(self, n):
            return [{"stack_keys": [("deadbeef", "rebuild")]}]

    warmed = Prefetcher(cache, recorder=FakeRecorder(),
                        ledger=led).step()
    assert warmed == 0


def test_prefetcher_start_stop_idempotent():
    h = _build(n_shards=2)
    ex = Executor(h)
    layer = ex.enable_serving(window_s=0.0, max_batch=2)
    p1 = layer.start_prefetcher(interval_s=10.0)
    p2 = layer.start_prefetcher()
    assert p1 is p2
    layer.stop_prefetcher()
    assert layer.prefetcher is None


# ---------------------------------------------------------------------------
# result cache ledger accounting
# ---------------------------------------------------------------------------

def test_result_cache_ledger_accounting():
    led = Ledger(budget_bytes=1 << 20)
    rc = ResultCache(max_bytes=1 << 16, ledger=led)
    h = _build(n_shards=2)
    idx = h.index("i")
    from pilosa_tpu.executor.serving import field_snapshot
    fields = frozenset({"f"})
    snap = field_snapshot(idx, fields)
    rc.put(("i", "q1", None), fields, snap, [123])
    assert rc.nbytes > 0
    assert led.total_bytes == rc.nbytes
    assert rc.get(idx, ("i", "q1", None)) == [123]
    rc.clear()
    assert led.total_bytes == 0


def test_result_cache_denied_by_ledger_pressure():
    led = Ledger(budget_bytes=128)
    c = led.register("hog")
    assert c.reserve(128)
    rc = ResultCache(max_bytes=1 << 16, ledger=led)
    h = _build(n_shards=2)
    idx = h.index("i")
    from pilosa_tpu.executor.serving import field_snapshot
    fields = frozenset({"f"})
    rc.put(("i", "q", None), fields, field_snapshot(idx, fields), [1])
    assert len(rc) == 0          # denied: served uncached
    assert led.total_bytes == 128


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_apply_memory_settings(restore_memory):
    from pilosa_tpu import config as cfgmod
    cfg = cfgmod.Config(memory_page_bytes=123456, memory_paged=False,
                        memory_oom_retry=False,
                        memory_host_fallback=False)
    cfg.apply_memory_settings()
    assert memory.page_bytes() == 123456
    assert memory.paged_enabled() is False
    assert pressure.OOM_RETRY is False
    assert pressure.HOST_FALLBACK is False


def test_memory_toml_keys(tmp_path):
    from pilosa_tpu import config as cfgmod
    p = tmp_path / "c.toml"
    p.write_text("[memory]\nbudget-bytes = 777\npaged = false\n"
                 "page-bytes = 999\n")
    cfg = cfgmod.load(str(p), env={})
    assert cfg.memory_budget_bytes == 777
    assert cfg.memory_paged is False
    assert cfg.memory_page_bytes == 999
