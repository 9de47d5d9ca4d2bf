"""chip_smoke.py rehearsed on the CPU, and the compile-cache helper.

The rehearsal runs in a child on a copy of the sources: the smoke
rebuilds ``native/build`` from ``native/*.cc``, which must not pull the
libraries out from under the other test workers.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from pilosa_tpu import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dst = tmp_path_factory.mktemp("checkout")
    skip = shutil.ignore_patterns("__pycache__", "build")
    for d in ("pilosa_tpu", "native"):
        shutil.copytree(os.path.join(ROOT, d), dst / d, ignore=skip)
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), dst)
    return dst


def _smoke(checkout, *args, **env):
    env = {**os.environ, **env}
    for name in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS"):
        env.pop(name, None)     # conftest's eight devices are not the child's
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=checkout, env=env,
        capture_output=True, text=True, timeout=600)


def test_no_tpu_fails_before_building_the_index(checkout):
    out = _smoke(checkout, JAX_PLATFORMS="cpu")
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_cpu_rehearsal_runs_every_phase_and_is_not_a_success(checkout):
    out = _smoke(checkout, "--rehearse-cpu", "--shards", "2",
                 JAX_PLATFORMS="cpu")
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    assert out.returncode == 1, out.stderr[-2000:]
    phases = [ln for ln in lines if "ok" in ln and "phase" in ln]
    assert all(ln["ok"] for ln in phases)
    assert {ln["phase"] for ln in phases} >= {
        "device", "native", "start", "load", "query", "concurrent",
        "write_then_read", "again", "promote", "steady",
        "device_did_the_work"}
    assert {ln["name"] for ln in phases if ln["phase"] == "query"} >= {
        "count_intersect", "topn_filtered", "able_groupby", "groupby_c240",
        "groupby_overlapping", "groupby_min", "sql_group_by"}
    for ln in phases:
        if ln["phase"] in ("again", "steady"):
            assert ln["compiles"] == 0, ln
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    # the copy built its own libraries and kept its own compile cache
    assert (checkout / "native" / "build" / "libingest_tpu.so").exists()
    assert lines[0]["compile_cache"] == str(checkout / ".jax_cache")


def test_four_chip_rehearsal_runs_only_the_mesh_comparison(checkout):
    out = _smoke(checkout, "--rehearse-cpu", "--shards", "4", "--chips", "4",
                 JAX_PLATFORMS="cpu")
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    assert out.returncode == 1, out.stderr[-2000:]
    phases = [ln for ln in lines if "ok" in ln and "phase" in ln]
    assert all(ln["ok"] for ln in phases)
    assert {ln["phase"] for ln in phases} == {
        "device", "native", "start", "load", "mesh", "concurrent",
        "mesh_layout", "mesh1", "mesh_vs_one"}
    layout = next(ln for ln in phases if ln["phase"] == "mesh_layout")
    assert layout["page_devices"] == [0, 1, 2, 3]
    assert all(layout["ledger_device_bytes"])
    assert layout["mesh_dispatches"] > 0
    assert lines[-1]["device"]["count"] == 4 and not lines[-1]["ok"]


@pytest.fixture
def cache_config():
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins_and_sets_nothing(monkeypatch, cache_config):
    cache_config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.place() == "/some/dir"
    assert cache_config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_and_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.place() == want
    assert compile_cache.place() == want
    assert cache_config.jax_compilation_cache_dir == want
