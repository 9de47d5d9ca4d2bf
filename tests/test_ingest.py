"""Ingest tests — batcher, CSV/datagen sources, pipeline semantics
(reference: batch/batch.go, idk/ingest.go loop behaviors)."""

import io

import pytest

from pilosa_tpu.api import API
from pilosa_tpu.ingest import (
    APIImporter,
    Batch,
    CSVSource,
    DatagenSource,
    KafkaSource,
    Pipeline,
    Record,
)
from pilosa_tpu.models.holder import Holder


@pytest.fixture()
def api():
    return API(Holder())


def test_batch_bits_and_values(api):
    api.apply_schema({"indexes": [{"name": "b", "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "n", "options": {"type": "int", "min": 0, "max": 100}},
    ]}]})
    b = Batch(APIImporter(api), "b",
              {"f": {"type": "set"}, "n": {"type": "int"}}, size=3)
    assert not b.add(Record(id=1, values={"f": 7, "n": 10}))
    assert not b.add(Record(id=2, values={"f": [7, 8], "n": 20}))
    assert b.add(Record(id=3, values={"n": None}))  # full at 3
    b.flush()
    [res] = api.query("b", "Count(Row(f=7))")["results"]
    assert res == 2
    [res] = api.query("b", "Sum(field=n)")["results"]
    assert res == {"value": 30, "count": 2}
    # record 3 had no f value and a null n: no bits anywhere
    [res] = api.query("b", "Count(Row(f=8))")["results"]
    assert res == 1


def test_batch_keyed_translation(api):
    api.apply_schema({"indexes": [{"name": "k", "keys": True, "fields": [
        {"name": "color", "options": {"type": "set", "keys": True}},
    ]}]})
    b = Batch(APIImporter(api), "k", {"color": {"type": "set", "keys": True}},
              size=10, index_keys=True)
    b.add(Record(id="alice", values={"color": "red"}))
    b.add(Record(id="bob", values={"color": ["red", "blue"]}))
    b.flush()
    [res] = api.query("k", 'Row(color="red")')["results"]
    assert sorted(res["keys"]) == ["alice", "bob"]


def test_csv_source_and_pipeline(api):
    csv = io.StringIO(
        "_id,segment:id,name:string,qty:int,ok:bool,tags:stringset\n"
        "1,3,aaa,10,true,x;y\n"
        "2,3,bbb,20,false,y\n"
        "3,4,,30,true,\n")
    src = CSVSource(csv)
    assert src.schema["qty"]["type"] == "int"
    assert src.schema["name"]["keys"] is True
    p = Pipeline(src, APIImporter(api), "c")
    assert p.run() == 3
    [res] = api.query("c", "Count(Row(segment=3))")["results"]
    assert res == 2
    [res] = api.query("c", "Sum(field=qty)")["results"]
    assert res == {"value": 60, "count": 3}
    [res] = api.query("c", 'Count(Row(tags="y"))')["results"]
    assert res == 2
    [res] = api.query("c", "Count(Row(ok=true))")["results"]
    assert res == 2
    # record 3's empty name → no bit
    [res] = api.query("c", "Count(Row(segment=4))")["results"]
    assert res == 1


def test_csv_keyed_ids(api):
    csv = io.StringIO("_id:string,seg:id\nuserA,1\nuserB,1\n")
    src = CSVSource(csv)
    p = Pipeline(src, APIImporter(api), "ck")
    assert p.run() == 2
    [res] = api.query("ck", "Row(seg=1)")["results"]
    assert sorted(res["keys"]) == ["userA", "userB"]


def test_csv_bad_header():
    with pytest.raises(ValueError):
        CSVSource(io.StringIO("_id,x:bogustype\n1,2\n"))
    with pytest.raises(ValueError):
        CSVSource(io.StringIO("x:id\n1\n"))  # no _id


def test_datagen_deterministic(api):
    src1 = list(DatagenSource(50, seed=7))
    src2 = list(DatagenSource(50, seed=7))
    assert [r.values for r in src1] == [r.values for r in src2]


def test_pipeline_concurrency_matches_serial(api):
    p1 = Pipeline(DatagenSource(500, seed=3), APIImporter(api), "s1",
                  batch_size=64, concurrency=1)
    p1.run()
    p4 = Pipeline(DatagenSource(500, seed=3), APIImporter(api), "s4",
                  batch_size=64, concurrency=4)
    assert p4.run() == 500
    for q in ("Count(Row(segment=5))", "Sum(field=amount)",
              "Count(Row(active=true))"):
        r1 = api.query("s1", q)["results"]
        r4 = api.query("s4", q)["results"]
        assert r1 == r4, q


def test_pipeline_small_batches_flush_all(api):
    p = Pipeline(DatagenSource(97, seed=1), APIImporter(api), "sb",
                 batch_size=10)
    assert p.run() == 97
    [res] = api.query("sb", "Count(All())")["results"]
    assert res == 97


def test_kafka_gated():
    with pytest.raises(NotImplementedError):
        KafkaSource("broker:9092")


def test_csv_time_field_with_ts(api):
    csv = io.StringIO(
        "_id,ev:time,_ts\n"
        "1,7,2020-03-15T10:00:00\n"
        "2,7,2021-06-01T00:00:00\n")
    src = CSVSource(csv)
    assert src.schema["ev"]["type"] == "time"
    p = Pipeline(src, APIImporter(api), "tv")
    assert p.run() == 2
    [r] = api.query("tv", "Count(Row(ev=7))")["results"]
    assert r == 2
    [r] = api.query(
        "tv", "Count(Row(ev=7, from='2020-01-01T00:00', to='2020-12-31T00:00'))"
    )["results"]
    assert r == 1


def test_pipeline_worker_error_raises_not_hangs(api):
    class BadSource(DatagenSource):
        def __iter__(self):
            for i in range(10000):
                yield Record(id="not-an-int", values={"segment": 1})
    src = BadSource(1)
    src.id_keys = False  # force int(id) failure in every batch
    p = Pipeline(src, APIImporter(api), "bad", batch_size=5, concurrency=3)
    with pytest.raises((ValueError, TypeError)):
        p.run()


def test_columnar_add_matches_record_path(api):
    """Batch.add_columns (the numpy fast path) produces the same index
    state as per-record adds — sets, mutex last-write-wins, string
    keys, int values, NULL cells (batch.go:753 semantics)."""
    import numpy as np
    schema = {"indexes": [{"name": "c", "fields": [
        {"name": "f", "options": {"type": "set"}},
        {"name": "m", "options": {"type": "mutex"}},
        {"name": "s", "options": {"type": "mutex", "keys": True}},
        {"name": "n", "options": {"type": "int", "min": 0,
                                  "max": 1000}},
    ]}]}
    api.apply_schema(schema)
    api2 = API(Holder())
    api2.apply_schema(schema)
    bschema = {"f": {"type": "set"}, "m": {"type": "mutex"},
               "s": {"type": "mutex", "keys": True},
               "n": {"type": "int"}}
    N = 500
    rng = np.random.default_rng(3)
    ids = np.arange(N)
    f = rng.integers(0, 9, size=N)
    m = rng.integers(0, 4, size=N)
    s = np.array([f"k{v}" for v in rng.integers(0, 7, size=N)],
                 dtype=object)
    n = rng.integers(0, 1000, size=N).astype(object)
    n[::7] = None  # NULL cells skip the bit
    colb = Batch(APIImporter(api), "c", bschema)
    colb.add_columns(ids, {"f": f, "m": m, "s": s, "n": n})
    recb = Batch(APIImporter(api2), "c", bschema, size=64)
    for i in range(N):
        recb.add(Record(int(ids[i]), {
            "f": int(f[i]), "m": int(m[i]), "s": str(s[i]),
            "n": None if n[i] is None else int(n[i])}))
        recb.flush()
    from pilosa_tpu.executor import Executor
    e1, e2 = Executor(api.holder), Executor(api2.holder)
    for q in ("Count(Row(f=3))", "Count(Row(m=2))",
              "Count(Row(s='k5'))", "Count(Row(n > 500))",
              "Count(All())"):
        r1 = e1.execute("c", q)[0]
        r2 = e2.execute("c", q)[0]
        assert r1 == r2, (q, r1, r2)


def test_import_columns_api_parallel_and_serial_agree(api):
    """API.import_columns: worker-threaded multi-field import equals
    the serial import, existence marked once."""
    import numpy as np
    schema = {"indexes": [{"name": "p", "fields": [
        {"name": "a", "options": {"type": "set"}},
        {"name": "b", "options": {"type": "set"}},
        {"name": "v", "options": {"type": "int", "min": 0,
                                  "max": 50}},
    ]}]}
    api.apply_schema(schema)
    api2 = API(Holder())
    api2.apply_schema(schema)
    N = 400
    rng = np.random.default_rng(5)
    ids = np.arange(N) * 3
    bits = {"a": rng.integers(0, 5, size=N),
            "b": rng.integers(0, 5, size=N)}
    vals = {"v": rng.integers(0, 50, size=N)}
    api.import_columns("p", ids, bits=bits, values=vals, workers=4)
    api2.import_columns("p", ids, bits=bits, values=vals, workers=1)
    from pilosa_tpu.executor import Executor
    e1, e2 = Executor(api.holder), Executor(api2.holder)
    for q in ("Count(All())", "Count(Row(a=1))", "Count(Row(b=4))",
              "Sum(field=v)"):
        assert e1.execute("p", q)[0] == e2.execute("p", q)[0], q


def _mp_ingest_worker(uri, index, shard_lo, shard_hi, per_shard):
    """Child-process ingester: disjoint shard range -> one server
    (the IDK clone shape, idk/ingest.go:302,319)."""
    import numpy as np

    from pilosa_tpu.ingest.importer import HTTPImporter
    W = 1 << 20
    imp = HTTPImporter(uri)
    total = 0
    for shard in range(shard_lo, shard_hi):
        cols = shard * W + np.arange(per_shard, dtype=np.int64)
        total += imp.import_columns(
            "mp", cols,
            bits={"m": (cols % 7)},
            values={"v": (cols % 1000)})
    return total


def test_multiprocess_sharded_ingest():
    """N importer PROCESSES over disjoint shard ranges into one
    server — the reference's IDK clone concurrency
    (idk/ingest.go:302 m.clone() per ingester).  Validates the
    deployment shape on this host."""
    import multiprocessing as mp

    from pilosa_tpu.server import Server
    srv = Server().start()
    try:
        uri = f"127.0.0.1:{srv.port}"
        from pilosa_tpu.ingest.importer import HTTPImporter
        HTTPImporter(uri).apply_schema({"indexes": [{
            "name": "mp", "fields": [
                {"name": "m", "options": {"type": "mutex"}},
                {"name": "v", "options": {"type": "int", "min": 0,
                                          "max": 1000}}]}]})
        n_workers, shards_per, per_shard = 3, 2, 5000
        ctx = mp.get_context("spawn")
        with ctx.Pool(n_workers) as pool:
            totals = pool.starmap(
                _mp_ingest_worker,
                [(uri, "mp", w * shards_per, (w + 1) * shards_per,
                  per_shard) for w in range(n_workers)])
        assert sum(totals) == n_workers * shards_per * per_shard * 2
        # every shard landed, disjointly owned by its importer
        import http.client
        import json as _json
        c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                       timeout=30)
        c.request("POST", "/index/mp/query",
                  body=_json.dumps({"query": "Count(Row(m=0))"}))
        got = _json.loads(c.getresponse().read())
        c.close()
        want = sum(1 for s in range(n_workers * shards_per)
                   for i in range(per_shard)
                   if (s * (1 << 20) + i) % 7 == 0)
        assert got["results"][0] == want
    finally:
        srv.close()


def test_import_values_int64_min_magnitude():
    """INT64_MIN roundtrips through the bulk BSI import: its magnitude
    2^63 only exists in uint64 (the native kernel's old signed
    negation was UB there, and np.abs is the identity), and the plane
    writes stay inside the declared depth."""
    import numpy as np

    from pilosa_tpu.models.fragment import Fragment
    from pilosa_tpu.ops import bsi as bsi_ops

    int64_min = -(1 << 63)
    depth = 64
    frag = Fragment("i", "v", "bsi", 0, width=1 << 12)
    frag.import_values([5, 9], [int64_min, 3], depth)
    planes = np.stack([frag.row_words(r) for r in range(2 + depth)])
    cols, vals = bsi_ops.decode(planes)
    assert cols.tolist() == [5, 9]
    assert vals == [int64_min, 3]


def test_import_values_numpy_fallback_int64_min(monkeypatch):
    """Same roundtrip with the toolchain absent (numpy scatter)."""
    import numpy as np

    from pilosa_tpu.models.fragment import Fragment
    from pilosa_tpu.ops import bsi as bsi_ops
    from pilosa_tpu.storage import native_ingest as ni

    monkeypatch.setattr(ni, "_lib", None)
    monkeypatch.setattr(ni, "_lib_failed", True)
    int64_min = -(1 << 63)
    frag = Fragment("i", "v", "bsi", 0, width=1 << 12)
    frag.import_values([7], [int64_min], 64)
    planes = np.stack([frag.row_words(r) for r in range(66)])
    cols, vals = bsi_ops.decode(planes)
    assert cols.tolist() == [7] and vals == [int64_min]


def test_import_values_depth_overflow_raises():
    """An out-of-depth magnitude is an unconditional error, not an
    assert that vanishes under python -O: it would otherwise reach the
    native kernel as an out-of-bounds plane index."""
    import pytest as _pytest

    from pilosa_tpu.models.fragment import Fragment

    frag = Fragment("i", "v", "bsi", 0, width=1 << 12)
    with _pytest.raises(ValueError, match="bits"):
        frag.import_values([1], [8], depth=3)
    # INT64_MIN against a too-shallow field must also raise, not wrap
    with _pytest.raises(ValueError, match="bits"):
        frag.import_values([1], [-(1 << 63)], depth=63)
