"""Full collections on a served thread leave their survivors frozen
(obs/flight.py ``_on_gc``): the next one walks only what was promoted
since.  Elsewhere, and once the heap has doubled, a full collection
walks the whole heap; the OOM ladder's relief does too
(memory/pressure.py ``relieve``).

Each test collects for real, with the hook installed; the module's
last step is a collection outside any request, which walks — and so
unfreezes — the whole heap for whatever runs next in the process.
"""

import gc
import threading
import weakref

import jax
import jax.numpy as jnp
import pytest

from pilosa_tpu.memory import pressure
from pilosa_tpu.obs import flight, metrics


class Node:
    """A container the collector tracks; ``cycle`` makes it garbage
    only a collection can reclaim."""


def _cycle():
    n = Node()
    n.me = n
    return n


def _full(path, walk):
    flight.flush_metrics()
    return metrics.GC_FULL.value(path=path, walk=walk)


def _walked(obj) -> bool:
    """Whether a collection would walk ``obj``: the frozen objects lie
    outside every generation ``gc.get_objects`` lists."""
    return any(o is obj for o in gc.get_objects())


@pytest.fixture(autouse=True)
def hooked():
    flight.install()
    flight.recorder.configure(enabled=True)
    gc.collect()        # a whole walk: nothing frozen, the blocks noted
    yield
    gc.collect()        # leave the heap as the next test expects it


def test_a_served_full_collection_freezes_what_it_leaves_alive():
    kept = Node()
    assert _walked(kept)
    w0, t0 = _full("served", "whole"), _full("served", "tenured")
    with flight.request():
        gc.collect()        # nothing frozen yet: the whole heap
    assert (_full("served", "whole"), _full("served", "tenured")) \
        == (w0 + 1, t0)
    assert not _walked(kept)
    with flight.request():
        gc.collect()        # walks only what is new since
    assert (_full("served", "whole"), _full("served", "tenured")) \
        == (w0 + 1, t0 + 1)
    assert not _walked(kept)


def test_an_audit_threads_full_collection_freezes_too():
    kept = Node()
    t0 = _full("audit", "tenured")

    def run():
        flight.mark_thread("audit")
        try:
            gc.collect()
            gc.collect()
        finally:
            flight.mark_thread(None)

    t = threading.Thread(target=run)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()
    assert _full("audit", "tenured") == t0 + 1
    assert not _walked(kept)


def test_a_frozen_cycle_outlives_tenured_walks_and_goes_in_a_whole_one():
    c = _cycle()
    ref = weakref.ref(c)
    with flight.request():
        gc.collect()        # c survives, and is frozen
    del c
    with flight.request():
        gc.collect()
    assert ref() is not None    # frozen garbage: no tenured walk sees it
    w0 = _full("background", "whole")
    gc.collect()                # outside a request: the whole heap
    assert ref() is None
    assert _full("background", "whole") == w0 + 1


def test_a_doubled_heap_walks_whole_on_a_served_thread(monkeypatch):
    c = _cycle()
    ref = weakref.ref(c)
    with flight.request():
        gc.collect()
    del c
    # the heap as it stood at the last whole walk: under half of now
    monkeypatch.setattr(flight, "_whole_blocks", 1)
    w0 = _full("served", "whole")
    with flight.request():
        gc.collect()
    assert ref() is None
    assert _full("served", "whole") == w0 + 1
    # that walk noted the heap anew and froze it: the next is tenured
    t0 = _full("served", "tenured")
    with flight.request():
        gc.collect()
    assert _full("served", "tenured") == t0 + 1


def _live(shape) -> int:
    return sum(a.shape == shape for a in jax.live_arrays())


def test_relief_reclaims_a_frozen_cycle_that_holds_a_device_buffer():
    shape = (37, 41)
    c = _cycle()
    c.buf = jnp.ones(shape, jnp.int32) * 7
    jax.block_until_ready(c.buf)
    assert _live(shape) == 1
    ref = weakref.ref(c)
    with flight.request():
        gc.collect()            # frozen, buffer and all
        del c
        gc.collect()
        assert ref() is not None and _live(shape) == 1
        pressure.relieve(0.0)   # on the served thread, as the ladder runs
    assert ref() is None
    assert _live(shape) == 0


def test_young_collections_are_no_full_walks():
    before = {(p, w): _full(p, w) for p in ("served", "background")
              for w in ("tenured", "whole")}
    info = {"generation": 0, "collected": 0, "uncollectable": 0}
    with flight.request():
        flight._on_gc("start", dict(info))
        flight._on_gc("stop", dict(info))
    flight._on_gc("start", dict(info, generation=1))
    flight._on_gc("stop", dict(info, generation=1))
    assert {k: _full(*k) for k in before} == before


def test_pause_counters_and_the_records_gc_phase_read_as_before():
    flight.flush_metrics()
    s0 = metrics.GC_PAUSE_SECONDS.total(path="served")
    n0 = metrics.GC_COLLECTIONS.value(generation="2", path="served")
    with flight.request():
        rec = flight.begin("i", "Count(Row(a=1))")
        gc.collect()
        gc.collect()
        flight.commit(rec, 0.01)
    flight.flush_metrics()
    assert metrics.GC_PAUSE_SECONDS.total(path="served") > s0
    assert metrics.GC_COLLECTIONS.value(generation="2",
                                        path="served") == n0 + 2
    assert rec["phases"]["gc"] > 0
    assert "gc" not in [s[0] for s in rec["spans"]]


def test_the_full_walk_counter_renders_by_path_and_walk():
    with flight.request():
        gc.collect()
        gc.collect()
    flight.flush_metrics()
    text = "\n".join(metrics.GC_FULL.render())
    assert 'pilosa_gc_full_total{path="served",walk="tenured"}' in text
    assert 'pilosa_gc_full_total{path="background",walk="whole"}' in text
