"""The system under test, as an operator gets it, and the harness's view of it.

Copied from ``chip_smoke.py`` (which ran on the chip in PR 21): device
check, compile-cache placing, native build, ``build_server`` with the
default config, schema over HTTP, bulk load through the fragments'
own imports, a small HTTP client, one ``/metrics.json`` scrape as an
object, and the compile counter.

Only this module (and ``run.py``, which calls it) touches JAX and the
program.  The load generator is a child process that imports neither.

**What a deployment brings, and what the harness reads of it.**  A
configuration file names its ``generator`` (``generators/<name>.py``)
and carries ``params``.  Of ``params`` the harness reads ``index``,
``shards``, ``rehearsal_shards`` and the field list
(``harness/fields.py``: ``name``, ``type``, ``options``, ``rows``);
everything else there is the generator's.  The generator exports

- ``make_shard(params, seed, shard) -> (rows, tables)``: one shard's
  data and its additive part of the reference's tables.  ``rows[field]``
  is in the form the generator made it: ``{row id: packed uint32
  words}`` (``Fragment.import_row_words``); ``(row ids, column ids)``
  (``import_mutex`` on a ``mutex`` or ``bool`` field, ``import_bits``
  on any other); or, on an ``int`` field, ``(column ids, values)``
  (``import_values`` at the field's depth).  Column ids are within the
  shard.  An ``int`` field loads into its BSI view, every other into
  the standard view (a ``time`` field's quantum views and a ``keys``
  field's translation are not loaded: no cell asks for them yet).
  Every column of a loaded shard exists, unless ``rows["_exists"]``
  (words, row 0) says which do;
- ``add_tables(total, part)`` and ``drop_columns(tables, part)``: the
  sum over shards, and the sum without one shard (the stale-shard
  control);
- ``Reference(params, tables).answer(call)``: the plain answer to a
  ``harness.pql.Call`` in ``harness.check.canonical``'s form.
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import fields

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(SystemExit):
    """The run cannot give a result: exit code 2, no result line."""

    def __init__(self, what: str):
        print(f"benchmark: {what}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by the name a data file gives."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str) -> dict:
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"no {os.path.join(*parts)}")
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device(chips: int, rehearse_cpu: bool) -> dict:
    """Place the compile cache, then look for the chip.  No TPU, or
    fewer chips than the cell asks for, ends the run with no result."""
    from pilosa_tpu import compile_cache
    compile_cache.place()
    import jax
    # keep every program, also the ones that compile in under a second:
    # a later run of the cell then finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not rehearse_cpu:
        raise BenchError(f"no TPU (jax.devices() = {devs}); the CPU "
                         "rehearsal is asked for with --rehearse-cpu")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = load_json("harness", "peaks.json")
    if dev.platform == "tpu" and dev.device_kind not in peaks:
        raise BenchError(f"device kind {dev.device_kind!r} is not in "
                         "harness/peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def build_native() -> None:
    """native/build/ from native/*.cc when it is not there yet (it is
    in .gitignore, so a fresh checkout builds once and keeps it)."""
    from pilosa_tpu.storage import native_ingest
    if native_ingest.available() or shutil.which("g++") is None:
        return
    subprocess.run(["sh", os.path.join(ROOT, "native", "build.sh")],
                   check=True, capture_output=True)


class Compiles:
    """Counts what JAX compiled and what its persistent cache served."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = self.hits = 0
        self.secs = 0.0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._event)

    def _dur(self, event, secs, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1
            self.secs += secs

    def _event(self, event, **_kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def snap(self) -> dict:
        return {"n": self.n, "cache_hits": self.hits, "seconds": self.secs}


# ---------------------------------------------------------------------------
# HTTP client of the parent: schema, scrapes, warm-up askings
# ---------------------------------------------------------------------------

class Metrics:
    """One /metrics.json scrape: {name: {labels: value}}."""

    def __init__(self, scrape: dict):
        self.scrape = scrape

    def total(self, name: str, label: str = "") -> float:
        """Sum of a counter's series whose labels contain `label`."""
        return sum(v for k, v in self.scrape.get(name, {}).items()
                   if label in k and not isinstance(v, dict))

    def hist(self, name: str) -> dict:
        """A histogram's {"count", "sum"} summed over its series."""
        out = {"count": 0, "sum": 0.0}
        for v in self.scrape.get(name, {}).values():
            if isinstance(v, dict):
                out["count"] += v["count"]
                out["sum"] += v["sum"]
        return out


class Http:
    def __init__(self, port: int, index: str, timeout: float = 900.0):
        self.index = index
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
        resp = self.conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            raise BenchError(f"{method} {path} -> {resp.status}: {raw[:300]!r}")
        return json.loads(raw) if raw else None

    def pql(self, q: str):
        return self.call("POST", f"/index/{self.index}/query",
                         {"query": q})["results"][0]

    def metrics(self) -> Metrics:
        return Metrics(self.call("GET", "/metrics.json"))

    def flights(self, limit: int = 512) -> list[dict]:
        return self.call("GET", f"/debug/queries?limit={limit}")["queries"]

    def errors(self) -> list:
        return self.call("GET", "/debug/errors") or []

    def close(self) -> None:
        self.conn.close()


# ---------------------------------------------------------------------------
# start and load
# ---------------------------------------------------------------------------

def start(config: dict):
    """The server `pilosa-tpu server` starts, default config, on an
    ephemeral port of 127.0.0.1; the configuration's schema over HTTP."""
    from pilosa_tpu import config as cfgmod
    from pilosa_tpu.cli.main import build_server
    cfg = cfgmod.load(None, overrides={"bind": "127.0.0.1", "port": 0})
    srv = build_server(cfg).start()
    params = config["params"]
    http_ = Http(srv.port, params["index"])
    want = fields.schema_fields(params)
    http_.call("POST", "/schema", {"indexes": [
        {"name": params["index"], "fields": want}]})
    got = {f["name"]: f["options"]
           for f in http_.call("GET", "/schema")["indexes"][0]["fields"]}
    for f in want:
        back = got.pop(f["name"], None)
        if back is None or any(back.get(k) != v
                               for k, v in f["options"].items()):
            raise BenchError(f"field {f['name']} was posted as "
                             f"{f['options']} and came back as {back}")
    if got:
        raise BenchError(f"the schema came back with {sorted(got)} besides")
    serving = srv.api.executor.serving
    if not (serving is not None and serving.batching
            and serving.cache is not None):
        raise BenchError("the serving plane is not on")
    return srv, http_


def load(srv, config: dict, generator, seed: int, shards: int,
         skip_shards: frozenset = frozenset()):
    """Generate every shard from the seed on all cores and hand each
    field's rows to its fragment in the form the generator made them
    (the module docstring's three forms; the restore path, nothing
    over HTTP).  Returns the summed reference tables.  Shards in
    `skip_shards` are generated and counted by the reference but never
    reach the server: the control's broken guarantee (an acknowledged
    import that no read sees)."""
    from pilosa_tpu.models.index import EXISTENCE_FIELD
    from pilosa_tpu.models.schema import FieldType
    from pilosa_tpu.models.view import VIEW_STANDARD
    params = config["params"]
    idx = srv.holder.index(params["index"])
    idx._ensure_existence()
    views = {name: f.view(f.bsi_view if f.options.type.is_bsi
                          else VIEW_STANDARD, create=True)
             for name, f in idx.fields.items()}
    every = {0: np.full(idx.width // 32, 0xFFFFFFFF, dtype=np.uint32)}

    def hand_over(shard, name, data):
        frag = views[name].fragment(shard, create=True)
        field = idx.fields[name]
        if isinstance(data, dict):
            for r, w in data.items():
                frag.import_row_words(r, w)
        elif field.options.type.is_bsi:
            frag.import_values(*data, field.bit_depth)
        elif field.options.type in (FieldType.MUTEX, FieldType.BOOL):
            frag.import_mutex(*data)
        else:
            frag.import_bits(*data)

    tables = None
    workers = max(1, min(12, os.cpu_count() or 1))
    with ThreadPoolExecutor(workers) as pool:
        for lo in range(0, shards, 4 * workers):
            chunk = range(lo, min(lo + 4 * workers, shards))
            for shard, (rows, part) in zip(chunk, pool.map(
                    lambda s: generator.make_shard(params, seed, s), chunk)):
                tables = generator.add_tables(tables, part)
                if shard in skip_shards:
                    continue
                for name, data in rows.items():
                    hand_over(shard, name, data)
                if EXISTENCE_FIELD not in rows:
                    hand_over(shard, EXISTENCE_FIELD, every)
    return tables
