"""A resident encoded page keeps the dense block its expansion made
(memory/encode.py ``EncodedPage.kept``, executor/stacked.py
``TileStackCache._keep_dense``): later uses hand out that block and run
no expand program; the block is charged to the stack cache's ledger
client on the page's device and leaves with the page; under pressure
kept blocks go before any page; a write replaces the page and its block
with it."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.executor.stacked import TileStackCache, raw_pages
from pilosa_tpu.memory import encode
from pilosa_tpu.memory.ledger import Ledger
from pilosa_tpu.memory.pages import StackRecipe
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.schema import FieldOptions, FieldType
from pilosa_tpu.obs import flight, metrics
from pilosa_tpu.ops import bitmap as bm

W_WORDS = 256          # one lane: 8,192 bits
PAGE_LANES = 8         # one page: 8 lanes, 8 KiB dense
LANES = 16             # two pages a stack
PAGE_BYTES = PAGE_LANES * W_WORDS * 4


@pytest.fixture(autouse=True)
def _small_pages(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_FORMAT", "1")
    monkeypatch.setenv("PILOSA_TPU_MEMORY_PAGE_BYTES", str(PAGE_BYTES))


def _host(kind: str, seed: int = 0) -> np.ndarray:
    """A stack of LANES lanes whose pages encode as `kind`."""
    rng = np.random.default_rng(seed)
    if kind == "dense":
        return rng.integers(0, 1 << 32, size=(LANES, W_WORDS),
                            dtype=np.uint32)
    if kind == "run":
        host = np.full((LANES, W_WORDS), 0xFFFFFFFF, dtype=np.uint32)
        host[:, rng.integers(0, W_WORDS, size=5)] = 0x00F0F000
        return host
    host = np.zeros((LANES, W_WORDS), dtype=np.uint32)
    bits = rng.choice(LANES * W_WORDS * 32, size=LANES * 20,
                      replace=False)
    np.bitwise_or.at(host.reshape(-1), bits // 32,
                     np.uint32(1) << (bits % 32).astype(np.uint32))
    return host


def _fetch(cache, key, host, lane_device=None):
    """One access through the cache's paged path, as the ragged plane
    makes it: the entry's raw PageView."""
    recipe = StackRecipe(
        logical_lead=(host.shape[0],), width_words=host.shape[1],
        lane_words=lambda lane: host[lane], build_host=lambda: host,
        versions_fn=lambda: (1,), lane_device=lane_device)
    with raw_pages():
        return cache.get(key, (1,), recipe.build_host, recipe=recipe)


def _cache(budget: int = 1 << 30):
    led = Ledger(budget_bytes=budget)
    return TileStackCache(ledger=led), led


def _stack(cache, key):
    return cache._entries[key][1]


def _kept_bytes(ps) -> int:
    return sum(int(p.kept.nbytes) for p in ps.pages
               if encode.is_encoded(p) and p.kept is not None)


def _dense(view) -> np.ndarray:
    return np.concatenate([np.asarray(b) for b in view.dense_pages()])


class _Expands:
    """Counts the expand programs dispatched (packed and run), once
    the page shape's warm-up family has run."""

    def __init__(self, monkeypatch):
        encode._warm_expand(PAGE_LANES, W_WORDS)
        self.n = 0
        for name in ("expand_coords", "expand_runs"):
            real = getattr(bm, name)

            def counted(*a, _real=real):
                self.n += 1
                return _real(*a)
            monkeypatch.setattr(bm, name, counted)


def _outcomes():
    return (metrics.STACK_EXPAND.value(outcome="kept"),
            metrics.STACK_EXPAND.value(outcome="expanded"))


@pytest.mark.parametrize("kind", ["packed", "run"])
def test_the_second_dense_use_is_the_kept_block(kind, monkeypatch):
    cache, _led = _cache()
    host = _host(kind)
    view = _fetch(cache, ("t", kind), host)
    assert [encode.page_kind(p) for p in view.pages] == [kind, kind]
    expands = _Expands(monkeypatch)
    k0, e0 = _outcomes()
    first = view.dense_pages()
    assert np.array_equal(_dense(view), host)
    assert _outcomes() == (k0 + 2, e0 + 2)     # two kept, two made
    n = expands.n
    assert n >= 2
    again = _fetch(cache, ("t", kind), host).dense_pages()
    assert expands.n == n                      # nothing dispatched
    assert all(a is b for a, b in zip(first, again))
    assert np.array_equal(
        np.concatenate([np.asarray(b) for b in again]), host)
    assert _outcomes() == (k0 + 4, e0 + 2)


def test_the_flight_record_counts_kept_and_expanded():
    cache, _led = _cache()
    host = _host("packed", seed=4)
    acc = flight.Acc()
    prev = flight.push_acc(acc)
    try:
        _fetch(cache, ("t", "f"), host).dense_pages()
        _fetch(cache, ("t", "f"), host).dense_pages()
    finally:
        flight.pop_acc(prev)
    assert acc.expands == {"expanded": 2, "kept": 2}
    assert acc.pages == {"packed": 4}


def test_the_ledger_charges_the_block_and_takes_it_back():
    """The client grows by exactly the kept blocks' bytes, and each of
    evict, drop and replace gives back the page's block with it."""
    cache, led = _cache()
    keys = [("t", i) for i in range(3)]
    hosts = [_host("packed", seed=i) for i in range(3)]
    for key, host in zip(keys, hosts):
        _fetch(cache, key, host)
    payload = led.total_bytes
    assert payload == cache.nbytes
    for key, host in zip(keys, hosts):
        _fetch(cache, key, host).dense_pages()
    kept = sum(_kept_bytes(_stack(cache, k)) for k in keys)
    assert kept == 3 * 2 * PAGE_BYTES
    assert led.total_bytes == cache.nbytes == payload + kept
    # replace: a write's new page for slot 0 of the first stack
    ps = _stack(cache, keys[0])
    old = ps.pages[0]
    new = encode.encode_block(hosts[0][:PAGE_LANES].copy())
    before = led.total_bytes
    cache._page_replace(keys[0], ps, 0, new)
    assert old.kept is None and old.keeper is None
    assert led.total_bytes == (before - old.payload_nbytes - PAGE_BYTES
                               + new.payload_nbytes)
    assert led.total_bytes == cache.nbytes
    # drop: the whole second stack, its blocks with it
    ps = _stack(cache, keys[1])
    before = led.total_bytes
    gone = ps.resident_bytes()
    assert _kept_bytes(ps) == 2 * PAGE_BYTES
    cache._drop_pages(keys[1], ps)
    assert led.total_bytes == before - gone == cache.nbytes
    # evict: everything left
    left = [p for k in (keys[0], keys[2])
            for p in _stack(cache, k).pages]
    cache._reclaim(led.total_bytes)
    assert led.total_bytes == 0 and cache.nbytes == 0
    assert all(p.kept is None for p in left if encode.is_encoded(p))


def test_pressure_drops_kept_blocks_before_any_page():
    cache, led = _cache()
    keys = [("t", i) for i in range(2)]
    for i, key in enumerate(keys):
        host = _host("packed", seed=10 + i)
        _fetch(cache, key, host).dense_pages()
    stacks = [_stack(cache, k) for k in keys]
    kept = sum(_kept_bytes(ps) for ps in stacks)
    pages = [p for ps in stacks for p in ps.pages]
    assert kept == 4 * PAGE_BYTES
    # a demand a little over one block: two blocks go, no page
    freed = cache._reclaim(PAGE_BYTES + 1)
    assert freed == 2 * PAGE_BYTES
    assert [p for ps in stacks for p in ps.pages] == pages
    # a demand past every kept block: all blocks go before a page
    with cache._lock:
        freed, _dev = cache._shed_locked(2 * PAGE_BYTES + 1)
    assert freed > 2 * PAGE_BYTES
    assert sum(_kept_bytes(ps) for ps in stacks) == 0
    assert sum(p is None for ps in stacks for p in ps.pages) == 1
    cache._release_freed(freed, _dev)
    assert led.total_bytes == cache.nbytes


def _fill(led, leave: int):
    """Another ledger client, with nothing it can give back, takes all
    but `leave` bytes of the budget."""
    filler = led.register("filler")
    assert filler.reserve(led.budget() - led.total_bytes - leave)
    return filler


def test_keeping_a_block_never_costs_a_page():
    """A reservation for a kept block may shed other kept blocks, not a
    resident page."""
    cache, led = _cache(budget=1 << 20)
    a, b = _host("packed", seed=20), _host("packed", seed=21)
    _fetch(cache, "a", a)
    _fetch(cache, "b", b)
    payload = led.total_bytes
    _filler = _fill(led, 2 * PAGE_BYTES)
    assert np.array_equal(_dense(_fetch(cache, "a", a)), a)
    assert _kept_bytes(_stack(cache, "a")) == 2 * PAGE_BYTES
    # b's blocks push out a's, and no page of either stack
    assert np.array_equal(_dense(_fetch(cache, "b", b)), b)
    assert _kept_bytes(_stack(cache, "b")) == 2 * PAGE_BYTES
    assert _kept_bytes(_stack(cache, "a")) == 0
    assert not _stack(cache, "a").missing()
    assert not _stack(cache, "b").missing()
    assert cache.nbytes == payload + 2 * PAGE_BYTES
    assert led.total_bytes == led.budget()


def test_a_denied_block_still_serves_and_is_not_kept(monkeypatch):
    """With nothing to shed but pages, the ledger denies the block: the
    access still gets the right block, and nothing is kept."""
    cache, led = _cache(budget=1 << 20)
    host = _host("packed", seed=30)
    _fetch(cache, "d", host)
    held = cache.nbytes
    _filler = _fill(led, PAGE_BYTES // 2)
    expands = _Expands(monkeypatch)
    k0, e0 = _outcomes()
    for _ in range(2):
        assert np.array_equal(_dense(_fetch(cache, "d", host)), host)
    ps = _stack(cache, "d")
    assert _kept_bytes(ps) == 0 and not ps.missing()
    assert cache.nbytes == held == cache._client.bytes
    assert expands.n == 4
    assert _outcomes() == (k0, e0 + 4)


def test_keeping_a_block_never_sheds_another_clients_bytes():
    """Another client's cold bytes (a jit cache's executables: shed
    one, and its program is compiled and loaded again) are never given
    up for a kept block; with the budget full of them, the block serves
    its access alone."""
    cache, led = _cache(budget=1 << 20)
    host = _host("packed", seed=70)
    _fetch(cache, "e", host)
    asked = []
    other = led.register("programs", cold_ts=lambda: 0.0,
                         reclaim=lambda need: asked.append(need) or 0)
    assert other.reserve(led.budget() - led.total_bytes - PAGE_BYTES // 2)
    assert np.array_equal(_dense(_fetch(cache, "e", host)), host)
    assert asked == []
    assert _kept_bytes(_stack(cache, "e")) == 0
    assert not _stack(cache, "e").missing()


def _device(monkeypatch, led, cache, limit: int, base: int):
    """The device's allocator numbers: `base` bytes besides what the
    cache holds; a test sets `reserved`, `peak_reserved` and `peak` (of
    the bytes in use) in the dict."""
    stats = {"base": base, "reserved": 0, "peak_reserved": 0, "peak": 0}

    def read(_device):
        return {"bytes_limit": limit,
                "bytes_in_use": stats["base"] + cache.nbytes,
                "peak_bytes_in_use": stats["peak"],
                "bytes_reserved": stats["reserved"],
                "peak_bytes_reserved": stats["peak_reserved"]}
    monkeypatch.setattr(led, "_device_stats", read)
    return stats


def test_kept_blocks_leave_the_device_room_for_its_programs(monkeypatch):
    """By the allocator's own numbers, a block is kept only while the
    device keeps the ledger's headroom and room for the largest program
    reservation it has reported, held now or not; short of that, this
    cache's older kept blocks go first, and never a page."""
    cache, led = _cache()
    limit = 100 * PAGE_BYTES                   # headroom: 10 pages
    dev = _device(monkeypatch, led, cache, limit, base=0)
    a, b = _host("packed", seed=71), _host("packed", seed=72)
    _fetch(cache, "a", a)
    _fetch(cache, "b", b)
    pages = cache.nbytes

    def kept():
        return tuple(_kept_bytes(_stack(cache, k)) // PAGE_BYTES
                     for k in ("a", "b"))
    # 80 pages in use: room for a's two blocks
    dev["base"] = 80 * PAGE_BYTES - pages
    assert np.array_equal(_dense(_fetch(cache, "a", a)), a)
    assert kept() == (2, 0)
    # a program reserved 8 pages, since released: 18 pages of room,
    # so b's second block takes one of a's
    dev["peak_reserved"] = 8 * PAGE_BYTES
    assert np.array_equal(_dense(_fetch(cache, "b", b)), b)
    assert kept() == (1, 2)
    assert led._programs_max == 8 * PAGE_BYTES
    # the device reports it no longer: the room is remembered, so a's
    # block takes one of b's (with 10 pages of room it would be kept
    # beside them)
    dev["peak_reserved"] = 0
    assert np.array_equal(_dense(_fetch(cache, "a", a)), a)
    assert kept() == (2, 1)
    # no room at all: every kept block goes, the block serves alone,
    # and every page stays
    dev["base"] += 10 * PAGE_BYTES
    assert np.array_equal(_dense(_fetch(cache, "a", a)), a)
    assert np.array_equal(_dense(_fetch(cache, "b", b)), b)
    assert kept() == (0, 0)
    assert cache.nbytes == pages == led.total_bytes
    assert not _stack(cache, "a").missing()
    assert not _stack(cache, "b").missing()


def test_kept_blocks_leave_room_for_what_programs_allocate(monkeypatch):
    """What a program allocates while it runs (its outputs, an assembled
    operand) comes on top of the bytes in use: the most the device's
    peak has been seen above them is left free as well, and
    remembered."""
    cache, led = _cache()
    limit = 100 * PAGE_BYTES                   # headroom: 10 pages
    dev = _device(monkeypatch, led, cache, limit, base=0)
    a = _host("packed", seed=73)
    _fetch(cache, "a", a)
    dev["base"] = 80 * PAGE_BYTES - cache.nbytes
    dev["peak"] = 91 * PAGE_BYTES              # 11 pages past the 80
    assert np.array_equal(_dense(_fetch(cache, "a", a)), a)
    assert _kept_bytes(_stack(cache, "a")) == 0
    assert led._transient_max == 11 * PAGE_BYTES
    dev["peak"] = 0
    dev["base"] -= 2 * PAGE_BYTES              # 78 + 21 of room: two fit
    assert np.array_equal(_dense(_fetch(cache, "a", a)), a)
    assert _kept_bytes(_stack(cache, "a")) == 2 * PAGE_BYTES


def test_only_a_page_still_in_its_slot_keeps_a_block_once():
    """A view taken before its entry left the cache gets the right
    block and keeps nothing; a block offered twice is kept once."""
    cache, led = _cache()
    host = _host("packed", seed=35)
    gone = _fetch(cache, "g", host)
    cache.clear()
    assert np.array_equal(_dense(gone), host)
    assert led.total_bytes == 0
    assert all(p.kept is None for p in gone.pages)
    view = _fetch(cache, "k", host)
    page = view.pages[0]
    block = page.dense()
    held = led.total_bytes
    assert page.kept is block
    assert not page.keeper(page, page.expand())
    assert page.kept is block and led.total_bytes == held


def test_a_dense_page_is_never_kept():
    cache, led = _cache()
    host = _host("dense", seed=40)
    view = _fetch(cache, "x", host)
    assert [encode.page_kind(p) for p in view.pages] == ["dense"] * 2
    held = led.total_bytes
    k0, e0 = _outcomes()
    out = view.dense_pages()
    assert all(a is b for a, b in zip(out, view.pages))
    assert _outcomes() == (k0, e0)
    assert led.total_bytes == held == 2 * PAGE_BYTES


def test_on_the_mesh_the_block_sits_on_the_pages_owner():
    ndev = 4
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    cache, led = _cache()
    led.set_devices(ndev)
    host = np.concatenate([_host("packed", seed=50 + d)
                           for d in range(2)])
    owner = np.repeat(np.arange(ndev, dtype=np.int32),
                      host.shape[0] // ndev)
    view = _fetch(cache, "m", host, lane_device=owner)
    ps = _stack(cache, "m")
    assert sorted(set(ps.page_device)) == list(range(ndev))
    before = led.device_bytes(ndev)
    blocks = view.dense_pages()
    after = led.device_bytes(ndev)
    for pi, (page, block) in enumerate(zip(ps.pages, blocks)):
        dev = jax.devices()[ps.page_device[pi]]
        assert page.kept is block
        assert block.devices() == {dev}
        lanes = ps.page_lane_ids(pi)
        assert np.array_equal(np.asarray(block)[:lanes.size],
                              host[lanes])
    for slot in range(ndev):
        mine = [p for pi, p in enumerate(ps.pages)
                if ps.page_device[pi] == slot]
        assert after[slot] - before[slot] == sum(
            int(p.kept.nbytes) for p in mine)


# -- through the engine: writes to a row whose packed page is kept --------

W = 1 << 15


def _holder(mutex: bool):
    h = Holder(width=W)
    idx = h.create_index("i")
    opts = FieldOptions(type=FieldType.MUTEX) if mutex else None
    f = idx.create_field("f", opts) if opts else idx.create_field("f")
    rng = np.random.default_rng(7)
    cols = np.sort(rng.choice(4 * W, size=600, replace=False))
    f.import_bits(np.arange(cols.size) % 3, cols)
    return h, cols


def _host_answer(h, q):
    host = Executor(h)
    host.stacked.host_only = True
    return repr(host.execute("i", q))


def _seated(ex):
    return [p for e in ex.stacked.cache._entries.values()
            if hasattr(e[1], "pages") for p in e[1].pages]


@pytest.mark.parametrize("mutex", [False, True],
                         ids=["import_bits", "import_mutex"])
def test_a_write_to_a_kept_row_reads_back_exact(mutex):
    h, cols = _holder(mutex)
    ex = Executor(h)
    queries = ["Row(f=1)", "Count(Not(Row(f=1)))", "Row(f=0)"]
    for q in queries:
        ex.execute("i", q)
    k0, _e0 = _outcomes()
    for q in queries:
        assert repr(ex.execute("i", q)) == _host_answer(h, q)
    assert _outcomes()[0] > k0                 # served from kept blocks
    kept = [p for p in _seated(ex)
            if encode.is_encoded(p) and p.kept is not None]
    assert kept
    # row 1 gains a fresh column in shard 2 and cols[0], which row 0
    # holds (a mutex moves it off row 0)
    fresh = next(c for c in range(2 * W, 3 * W) if c not in set(cols))
    h.index("i").field("f").import_bits(
        np.ones(2, np.int64), np.array([fresh, int(cols[0])]))
    for q in queries:
        assert repr(ex.execute("i", q)) == _host_answer(h, q)
    seated = _seated(ex)
    replaced = [p for p in kept if not any(p is q for q in seated)]
    assert replaced                            # the written pages went
    assert all(p.kept is None and p.keeper is None for p in replaced)


def test_threads_keeping_and_shedding_leave_the_ledger_exact():
    """Callers making pages dense while another sheds under pressure:
    every block is right, and what the ledger holds is exactly what
    the cache's pages and kept blocks hold."""
    import sys
    import threading
    cache, led = _cache(budget=1 << 20)
    hosts = {f"s{i}": _host("packed", seed=60 + i) for i in range(4)}
    for key, host in hosts.items():
        _fetch(cache, key, host)
    _filler = _fill(led, 3 * PAGE_BYTES)
    wrong, done = [], threading.Event()

    def reader(key):
        for _ in range(40):
            if not np.array_equal(_dense(_fetch(cache, key, hosts[key])),
                                  hosts[key]):
                wrong.append(key)

    def shedder():
        while not done.is_set():
            cache._reclaim(PAGE_BYTES)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=reader, args=(k,))
              for k in list(hosts) * 3]
        sh = threading.Thread(target=shedder)
        for t in ts + [sh]:
            t.start()
        for t in ts:
            t.join(timeout=120)
        done.set()
        sh.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts + [sh])
    assert not wrong
    held = sum(e[1].resident_bytes() for e in cache._entries.values())
    assert cache.nbytes == held == cache._client.bytes
    assert led.total_bytes <= led.budget()
