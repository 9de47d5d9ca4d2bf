"""Test config: run JAX on a virtual 8-device CPU mesh.

This is the analog of the reference's in-process multi-node cluster
harness (test/cluster.go:31 MustRunCluster): instead of N server
processes with embedded etcd, we get N XLA host devices so every
sharding/collective path compiles and runs in one process.
"""

import os

# Must be set before jax is imported anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Resource leak auditing (testhook/ analog): must be set before
# pilosa_tpu.obs.testhook is imported anywhere.
os.environ.setdefault("PILOSA_TPU_TESTHOOK", "1")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0x5EED)


@pytest.fixture(autouse=True)
def _fresh_stats_catalog():
    """Per-test isolation of the process statistics catalog
    (obs/stats.py).  Fingerprint profiles are keyed by
    (index, query, shards), and test files reuse the same tiny index
    and query names — a cost profile learned under one test's load
    would reclassify another test's admission (e.g. a point read
    profiled slow earlier would class HEAVY and block forever on a
    deliberately saturated gate).  Clearing the in-memory planes per
    test keeps the stats integration exercised within each test with
    no cross-test order dependence; stats tests that need
    persistence swap in their own catalog."""
    from pilosa_tpu.obs import stats
    stats.get().clear()
    yield


@pytest.fixture(scope="session", autouse=True)
def _leak_audit():
    """Session-end resource audit (testhook/auditor.go): every rbf
    DB, HTTP server, and spill set opened by the suite must have been
    closed."""
    yield
    from pilosa_tpu.obs import testhook
    if not testhook.ENABLED:
        return
    leaks = testhook.audit()
    assert not leaks, (
        f"leaked resources at session end: {leaks}\n"
        "opening stacks:\n"
        + "\n".join("\n".join(v)
                    for v in testhook.audit_stacks().values()))
