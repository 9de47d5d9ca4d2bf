"""The loader's three forms on the fixture deployment (``three_forms``):
what the server holds after ``start`` and ``load`` is what the
generator made, its answers over HTTP are the reference's, the
stale-shard control's are not, and ``start`` refuses a schema that
came back different from the configuration's."""

import copy

import numpy as np
import pytest
import three_forms
from harness import bytes_model, check, pql, server

SEED = 2147483811
QUERIES = [
    "Count(Row(zone=0))",
    "Count(Intersect(Row(seg=1), Row(zone=250)))",
    "Count(Not(Row(seg=2)))",
    "Sum(field=fare)",
    "Sum(Row(tag=7), field=tip)",
    "Sum(Row(fare > 900), field=tip)",
    "TopN(zone, n=12)",
    "TopN(zone, Row(tip < -10))",
    "GroupBy(Rows(seg), Rows(zone), filter=Row(fare > 500), "
    "aggregate=Sum(field=tip))",
]


@pytest.fixture(scope="module", params=["whole", "stale", "missing"])
def world(request):
    config = copy.deepcopy(three_forms.CONFIG)
    if request.param == "missing":
        config["params"]["missing_one_in"] = 16
    skip = frozenset([1]) if request.param == "stale" else frozenset()
    srv, http_ = server.start(config, 1)
    try:
        tables = server.load(srv, config, three_forms, SEED, 2, skip)
        yield (request.param, srv, http_, tables,
               three_forms.Reference(config["params"], tables))
    finally:
        http_.close()
        srv.close()


def _bits(frag, row):
    return np.unpackbits(np.ascontiguousarray(
        frag.row_words(row), dtype=np.uint32).view(np.uint8),
        bitorder="little").astype(bool)


def test_every_row_the_server_holds_is_the_generators(world):
    kind, srv, _http, tables, _ref = world
    fields = srv.holder.index("forms").fields
    shards = [0] if kind == "stale" else [0, 1]
    width = three_forms.SHARD_WIDTH
    sparse = 0
    for shard in shards:
        part = {k: v[..., shard * width:(shard + 1) * width]
                for k, v in tables.items()}
        want = {("_exists", 0): part["exists"]}
        for name in ("seg", "tag"):
            want.update({(name, r): bits
                         for r, bits in enumerate(part[name])})
        want.update({("zone", r): part["zone"] == r
                     for r in range(three_forms.ZONES)})
        for name, depth in (("fare", 10), ("tip", 5)):
            v = part[name]
            want[name, 0] = part["exists"]
            want[name, 1] = v < 0
            want.update({(name, 2 + p): ((np.abs(v) >> p) & 1).astype(bool)
                         for p in range(depth)})
        held = set()
        for name, field in fields.items():
            (view,) = field.views.values()
            frag = view.fragments[shard]
            held.update((name, r) for r in frag.row_ids)
            if name == "zone":
                sparse += frag.sparse_row_count
        assert held == {k for k, bits in want.items() if bits.any()}
        for (name, r), bits in want.items():
            (view,) = fields[name].views.values()
            assert (_bits(view.fragments[shard], r) == bits).all(), (name, r)
    assert sparse > 200 * len(shards)   # zone's tail stayed compressed
    if kind == "stale":
        assert all(1 not in v.fragments for f in fields.values()
                   for v in f.views.values())


@pytest.mark.parametrize("q", QUERIES)
def test_answers_over_http(world, q):
    kind, _srv, http_, _tables, ref = world
    call = pql.parse(q)
    got = check.canonical(call.name, http_.pql(q))
    if kind == "stale":     # the control: the last shard is counted, not served
        assert got != ref.answer(call)
    else:
        assert got == ref.answer(call)


def test_start_refuses_a_field_that_came_back_different(monkeypatch):
    import importlib
    cli = importlib.import_module("pilosa_tpu.cli.main")
    started = []
    real_build, real_call = cli.build_server, server.Http.call

    def build(cfg):
        started.append(real_build(cfg))
        return started[-1]

    def call(self, method, path, body=None):
        out = real_call(self, method, path, body)
        if (method, path) == ("GET", "/schema"):
            tip = next(f for f in out["indexes"][0]["fields"]
                       if f["name"] == "tip")
            tip["options"]["max"] = 31
        return out

    monkeypatch.setattr(cli, "build_server", build)
    monkeypatch.setattr(server.Http, "call", call)
    try:
        with pytest.raises(server.BenchError):
            server.start(three_forms.CONFIG, 1)
    finally:
        for srv in started:
            srv.close()


@pytest.mark.parametrize("q, planes", [
    ("Sum(field=fare)", 2 + 10),
    ("Sum(Row(fare > 900), field=tip)", 2 + 10 + 2 + 5),
    ("Count(Intersect(Row(zone=3), Row(tip < 0)))", 1 + 2 + 5),
    ("GroupBy(Rows(zone))", 9 + 1),
    ("GroupBy(Rows(seg), Rows(zone), filter=Row(fare > 500), "
     "aggregate=Sum(field=tip))", 2 + 9 + 1 + 12 + 7),
    ("TopN(zone, Row(seg=1), n=5)", 300 + 1),
])
def test_necessary_planes_by_field(q, planes):
    params = three_forms.CONFIG["params"]
    assert len(bytes_model.planes(pql.parse(q), params)) == planes
    assert bytes_model.necessary_bytes(pql.parse(q), params, 2) \
        == planes * 2 * 131072
