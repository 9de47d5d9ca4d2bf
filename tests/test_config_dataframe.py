"""Config layering + Arrow dataframe tests."""

import pytest

from pilosa_tpu import config as cfgmod
from pilosa_tpu.models.dataframe import DataframeError, IndexDataframe


def test_config_layering(tmp_path):
    p = tmp_path / "c.toml"
    p.write_text(
        'data-dir = "/var/data"\n'
        'port = 7777\n'
        '[cluster]\nreplicas = 3\n'
        '[auth]\nsecret = "filesec"\n'
        '[tpu]\nkernels = "off"\n')
    # file only
    cfg = cfgmod.load(str(p), env={})
    assert cfg.data_dir == "/var/data"
    assert cfg.port == 7777
    assert cfg.replicas == 3
    assert cfg.auth_secret == "filesec"
    # a key the server no longer knows is ignored, like any unknown key
    assert not [k for k in vars(cfg) if k.startswith("tpu")]
    # env overrides file
    cfg = cfgmod.load(str(p), env={"PILOSA_TPU_PORT": "8888",
                                   "PILOSA_TPU_AUTH_SECRET": "envsec"})
    assert cfg.port == 8888 and cfg.auth_secret == "envsec"
    # flags override env
    cfg = cfgmod.load(str(p), env={"PILOSA_TPU_PORT": "8888"},
                      overrides={"port": 9999, "bind": None})
    assert cfg.port == 9999
    assert cfg.bind == "127.0.0.1"  # None override ignored
    # defaults without file
    assert cfgmod.load(env={}).port == 10101


# every PILOSA_TPU_* name under pilosa_tpu/ (wildcard mentions such as
# PILOSA_TPU_MEMORY_* aside).  A switch can neither come nor go unseen:
# adding one means adding it here, with a caller that needs it; ROADMAP
# C6 shortens the list.
SWITCHES = """
AUDIT AUTH_SECRET BLOB_BACKEND BLOB_ROOT CLUSTER_DEADLINE_S
CLUSTER_HEDGE_MS DAX_BLOB DAX_CHASE_LAG DAX_CHASE_ROUNDS DAX_COOLDOWN_S
DAX_LAZY_HYDRATE DAX_MAX_WORKERS DAX_MIN_WORKERS DAX_PREFETCH
DAX_PRESSURE_HIGH DAX_RECONCILE_INTERVAL_S DAX_SCALE_IN_BURN
DAX_SCALE_OUT_BURN DAX_STANDBY DAX_WORKER_BUDGET_BYTES DELTA_LOG_MAX
FAULT_SPEC FLIGHT GROUPBY_KERNEL GROUPBY_ONEPASS GROUPBY_ONEPASS_ARM
INCIDENTS JIT_ENTRY_EST_BYTES MEMORY_BUDGET_BYTES MEMORY_ENTRY_FRAC
MEMORY_HOST_FALLBACK MEMORY_OOM_RETRY MEMORY_PAGED MEMORY_PAGE_BYTES
MESH_DEVICES PARANOIA PATCH_MAX_FRAC PEAK_GBPS PLACEMENT_PIN QCOVER
REBALANCE_CHASE_LAG REBALANCE_FENCE_TIMEOUT_S REBALANCE_MAX_ROUNDS
ROOFLINE SERVING_ADMISSION SERVING_BATCHING SERVING_CACHE_MB
SERVING_RAGGED SPARSE_FORMAT SQL_PUSHDOWN STACK_PATCH STANDING STATS
TESTHOOK TIMEQ_ROLLUP TIMEQ_WRITE_FINEST TOKEN
TRANSLATE_COMPACT_THRESHOLD WATCHDOG
""".split()


def test_switch_census():
    import pathlib
    import re
    root = pathlib.Path(cfgmod.__file__).parent
    found = set()
    for path in root.rglob("*.py"):
        found.update(re.findall(r"PILOSA_TPU_([A-Z0-9_]*[A-Z0-9])\b",
                                path.read_text(encoding="utf-8")))
    assert sorted(found) == sorted(SWITCHES)


def test_dataframe_rows_and_apply(tmp_path):
    df = IndexDataframe(str(tmp_path))
    df.add_rows([{"_id": 1, "price": 10.0, "qty": 3},
                 {"_id": 2, "price": 2.5, "qty": 8},
                 {"_id": 3, "price": 4.0}])
    assert df.n_rows == 3
    types = {s["name"]: s["type"] for s in df.schema()}
    assert types["price"] == "float" and types["qty"] == "int"
    # ragged column null-filled
    assert df.column("qty").tolist() == [3, 8, None]
    # row-aligned computed column (apply.go Apply capability)
    got = df.apply("price * qty")
    assert got == [30.0, 20.0, 0.0]
    # reducing expression through the whitelisted function table
    assert df.apply("sum(price)") == 16.5
    with pytest.raises(DataframeError):
        df.apply("__import__('os')")
    with pytest.raises(DataframeError):
        df.column("nope")


def test_dataframe_device_aggregate(tmp_path):
    df = IndexDataframe(str(tmp_path))
    df.add_rows([{"_id": i, "v": i * 2} for i in range(100)])
    assert df.aggregate("sum", "v") == 2 * sum(range(100))
    assert df.aggregate("min", "v") == 0
    assert df.aggregate("max", "v") == 198
    assert df.aggregate("count", "v") == 100
    assert df.aggregate("mean", "v") == pytest.approx(99.0)
    with pytest.raises(DataframeError):
        df.aggregate("median", "v")


def test_dataframe_parquet_roundtrip(tmp_path):
    df = IndexDataframe(str(tmp_path))
    df.add_rows([{"_id": 1, "a": "x"}, {"_id": 2, "a": "y"}])
    df.save()
    df2 = IndexDataframe(str(tmp_path))
    assert df2.n_rows == 2
    assert df2.column("a").tolist() == ["x", "y"]
    assert df2.to_arrow().num_rows == 2


def test_dataframe_http_routes():
    from pilosa_tpu.cluster.client import InternalClient, RemoteError
    from pilosa_tpu.server.http import Server

    srv = Server().start()
    uri = f"127.0.0.1:{srv.port}"
    cli = InternalClient()
    try:
        cli._request(uri, "POST", "/index/dfi", {})
        r = cli._request(uri, "POST", "/index/dfi/dataframe", {
            "rows": [{"_id": 1, "x": 5}, {"_id": 2, "x": 7}]})
        assert r["rows"] == 2
        r = cli._request(uri, "GET", "/index/dfi/dataframe")
        assert any(s["name"] == "x" for s in r["schema"])
        r = cli._request(uri, "POST", "/index/dfi/dataframe/apply",
                         {"expr": "x + 1"})
        assert r["result"] == [6, 8]
        r = cli._request(uri, "POST", "/index/dfi/dataframe/apply",
                         {"aggregate": "sum", "column": "x"})
        assert r["result"] == 12
        with pytest.raises(RemoteError) as e:
            cli._request(uri, "POST", "/index/nope/dataframe", {})
        assert e.value.status == 404
    finally:
        srv.close()


def test_float_config_coercion():
    """Float settings (long-query-time) coerce from flags/env/TOML —
    not silently stringified (regression: _coerce lacked a float
    branch)."""
    from pilosa_tpu import config as cfgmod
    cfg = cfgmod.load(overrides={"long_query_time": 0.25})
    assert cfg.long_query_time == 0.25
    cfg = cfgmod.load(env={"PILOSA_TPU_LONG_QUERY_TIME": "1.5"})
    assert cfg.long_query_time == 1.5
