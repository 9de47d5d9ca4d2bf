"""The main path's programs, compiled for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2).

Interpret mode — what every other kernel test runs — checks neither
Mosaic's block-shape rules nor its VMEM limit: both one-pass GroupBy
kernels passed every test and were refused at lowering.  These cases
compile each kernel at the real shard width (W = 32768 words) and
assert the compiled program carries the kernel (``tpu_custom_call``),
and compile the program the default engine builds for each template
of the benchmark's two traffic mixes: XLA serves the scans, one named
kernel serves GroupBy.  Nothing runs; a compile that passes is not a
chip run.

One file, and the topology is described inside a fixture: only the
xdist worker that is handed this file loads libtpu.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from pilosa_tpu.executor import stacked
from pilosa_tpu.ops import kernels

S = 64
W = 32768


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip — keep it off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # jax.default_backend() is "cpu" here, so the kernels would pick
    # interpret mode — steer them to the Mosaic lowering in the test
    mp.setattr(kernels, "_interpret", lambda: False)
    yield t
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


ABLE = ((3, 6), (1, 2), (3, 5))      # edu, gen, dom: (bits, rows)
ABLE_REG = ABLE + ((2, 4),)           # the 240-group form
TAXI_Q4 = ((4, 10), (3, 8), (6, 60))
# scoped VMEM a v5e kernel may use unless it asks for more
# (CompilerParams(vmem_limit_bytes=): the packed body of groupby_fused
# asks for kernels._PACKED_VMEM_LIMIT on every call, the other kernels
# for nothing)
V5E_SCOPED_VMEM = 16 << 20


def _kernel_calls(text):
    """The compiled program's Pallas calls, one HLO line each."""
    return [ln for ln in text.split("\n")
            if 'custom_call_target="tpu_custom_call"' in ln]


def _fused(n_codes, depth, minmax=False, cb=6, signed=True, digits=None,
           body="packed"):
    """groupby_fused at W = 32768; `body` is the one its shapes must
    take (kernels.fused_body) for the case to mean what its name
    says."""
    if digits is not None:
        cb = sum(b for b, _ in digits)
        n_codes = 1 << cb
    assert kernels.fused_body(digits or ((1, 2),) * cb, depth, signed,
                              minmax) == body

    def fn(cp, va, *planes):
        return kernels.groupby_fused(cp, va, planes[0] if planes else None,
                                     n_codes, signed, minmax=minmax,
                                     digits=digits)
    shapes = [(S, cb, W), (S, W)] + ([(S, 2 + depth, W)] if depth else [])
    return fn, shapes


def _groupby_sum():
    sel = np.stack(np.meshgrid(np.arange(6), np.arange(2), np.arange(5),
                               indexing="ij"), -1).reshape(-1, 3)

    def fn(edu, gen, dom, planes):
        return kernels.groupby_sum([edu, gen, dom], sel, planes)
    return fn, [(6, S, W), (2, S, W), (5, S, W), (S, 10, W)]


OVER_BOUNDS = "groupby_codes_xla_over_bounds"
CASES = {
    "groupby_fused_count": lambda: _fused(64, 0),
    "groupby_fused_sum": lambda: _fused(64, 8),
    "groupby_fused_minmax": lambda: _fused(64, 8, minmax=True,
                                           body="onehot"),
    # what able-1b's Min/Max requests dispatch (age: unsigned, 7 bits)
    "groupby_fused_minmax_able": lambda: _fused(
        0, 7, minmax=True, signed=False, digits=ABLE, body="onehot"),
    # the kernel's own bounds (stacked._ONEPASS_KERNEL_MAX_*): their
    # accumulators do not fit VMEM, the one-hot body serves
    "groupby_fused_sum_bounds": lambda: _fused(4096, 16, cb=12,
                                               body="onehot"),
    "groupby_fused_minmax_bounds": lambda: _fused(4096, 16, minmax=True,
                                                  cb=12, body="onehot"),
    "groupby_sum": _groupby_sum,
    "bsi_value_hist": lambda: (kernels.bsi_value_hist,
                               [(S, 10, W), (S, W)]),
    # past stacked._ONEPASS_KERNEL_MAX_CODES a histogram whose groups
    # the packed body cannot walk takes the XLA scatter (8,192 codes
    # with no digit layout: a value histogram)
    OVER_BOUNDS: lambda: (
        lambda cp, va: kernels.groupby_codes_xla(cp, va, None, 8192),
        [(S, 13, W), (S, W)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == (name != OVER_BOUNDS)


# the able query's forms through the packed body (ISSUE 32): age is an
# unsigned 7-bit int.  "vhist_1024" is a 9-bit int's value histogram:
# every one of 1,024 codes live, 32 upper masks x 32 rows
# (kernels.dense_digits; one vreg a block before PR 40)
PACKED = {
    "able_sum": dict(digits=ABLE, depth=7),
    "able_count": dict(digits=ABLE, depth=0),
    "able_reg_sum": dict(digits=ABLE_REG, depth=7),
    "vhist_1024": dict(digits=kernels.dense_digits(10), depth=0),
    # taxi-1b's Q4 (passenger_count x pickup_year x dist_miles: 8,192
    # codes, 4,800 groups): one walk of 60 rows against 80 upper
    # masks, and with the 9-bit amount summed 12 walks of 5 rows
    "taxi_q4_count": dict(digits=TAXI_Q4, depth=0),
    "taxi_q4_sum": dict(digits=TAXI_Q4, depth=9),
}


def _scoped_vmem(call):
    """Bytes of scoped VMEM a compiled Pallas call uses."""
    import re
    return [int(n) for n in re.findall(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"0","size":"(\d+)"', call)]


@pytest.mark.parametrize("name", list(PACKED))
def test_packed_body_fits_v5e_vmem(one_chip, name):
    """The packed body compiles for the chip, every block 16 vregs
    wide, and the scoped VMEM it uses (accumulators, upper masks,
    operand blocks, Mosaic's own) stays under the limit it asks for
    and within a MiB of what the body counted."""
    case = PACKED[name]
    fn, shapes = _fused(0, signed=False, **case)
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = _kernel_calls(text)
    nv, _fi, _rp, passes = kernels._packed_passes(
        case["digits"], case["depth"], False)
    assert nv == 16
    assert kernels.fused_plan(case["digits"], case["depth"], False) \
        == ("packed", passes)
    assert (passes > 1) == (name == "taxi_q4_sum")
    assert len(calls) == 1 and ("groupby_fused_passes" if passes > 1
                                else "groupby_fused_sum") in calls[0]
    asked = _scoped_vmem(calls[0])
    assert asked and 0 < asked[0] < kernels._PACKED_VMEM_LIMIT, asked
    assert asked[0] <= kernels._PACKED_VMEM_BYTES + (1 << 20), asked
    if name == "taxi_q4_count":
        # one walk of 4,800 accumulators is what the raised limit is
        # for: more than a kernel gets by default
        assert asked[0] > V5E_SCOPED_VMEM


# SSB's pruned GroupBys: the packed body over a selection of
# the group-code stack's rows (kernels.selection, in SMEM), summing a
# 24-bit measure — Q4.3's 2 x 16 x 64 groups in 16 walks, Q3.2's
# 16 x 16 x 8 in 16 walks of 8-vreg blocks, Q2.1's 8 x 64 — and the
# prepass that finds the rows (stacked._groupby_marginals_jit: one
# two-level count-only walk a field)
SELECTED = {
    # name: (the selection's digits, the fields' whole digits)
    "ssb_q4_3": (((3, 2), (8, 16), (10, 64)),
                 ((3, 7), (8, 250), (10, 1000))),
    "ssb_q3_2": (((8, 16), (8, 16), (3, 8)), ((8, 250), (8, 250), (3, 7))),
    "ssb_q2_1": (((3, 8), (10, 64)), ((3, 7), (10, 1000))),
}


@pytest.mark.parametrize("name", list(SELECTED))
def test_ssb_selection_compiles_for_v5e(one_chip, name):
    (digits, whole), depth = SELECTED[name], 24
    cb = sum(b for b, _ in digits)
    sel = kernels.selection(digits, depth, False, [[0]] * len(digits))
    fn = stacked._groupby_selected_jit(True, True, False, digits)

    def sds(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = fn.lower(sds((S, cb + 1, W)), sds((S, W)),
                    sds((S, 2 + depth, W)),
                    sds(sel.shape, jnp.int32)).compile().as_text()
    calls = _kernel_calls(text)
    passes = kernels._packed_passes(digits, depth, False, True)[3]
    assert len(calls) == 1 and ("groupby_fused_passes" if passes > 1
                                else "groupby_fused_sum") in calls[0]
    asked = _scoped_vmem(calls[0])
    assert asked and 0 < asked[0] < kernels._PACKED_VMEM_LIMIT, asked
    prepass = stacked._groupby_marginals_jit("fused", whole, True)
    text = prepass.lower(sds((S, cb + 1, W)), sds((S, W))).compile().as_text()
    assert len(_kernel_calls(text)) == len(digits)


def test_q4_kernel_traces_and_lowers_in_a_second(one_chip):
    """Trace + lowering of taxi-1b's Q4 histogram (4,800 groups, 263
    shards) stays under 2 s here — alone it reads 0.25-0.45 s; the
    compiler's own time is not in it.  No loop over rows or groups
    is unrolled: PR 32's unrolled body cost a served program 10 s of
    Pallas lowering at its first asking."""
    import time
    fn, _ = _fused(0, signed=False, digits=TAXI_Q4, depth=0)
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in ((263, 13, W), (263, W))]
    took = []
    for _ in range(2):
        # a new function object each time: nothing cached is reused
        t0 = time.perf_counter()
        lowered = jax.jit(lambda *a: fn(*a)).lower(*args)
        took.append(time.perf_counter() - t0)
    assert "tpu_custom_call" in lowered.as_text()
    assert min(took) < 2.0, took


@pytest.mark.parametrize("case", ["fused", "xla", "fused_passes"])
def test_onepass_shard_map_compiles_for_four_chips(topo, case):
    """The mesh GroupBy wrapper (stacked._groupby_onepass_shard_map)
    over the four described chips: per-device kernel — past 4,096
    codes in passes where the fields' digits are known (taxi-1b's Q4
    with the amount summed), else the XLA scatter — + psum."""
    cb = {"fused": 6, "xla": 13, "fused_passes": 13}[case]
    arm = case.split("_")[0]
    digits = TAXI_Q4 if case == "fused_passes" else None
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("rows", "shards"))
    flat = ("rows", "shards")

    def sds(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.uint32,
                                    sharding=NamedSharding(mesh, spec))
    fn = stacked._groupby_onepass_shard_map(
        mesh, arm, has_planes=True, has_filter=True, signed=True,
        n_codes=1 << cb, digits=digits)
    compiled = fn.lower(sds((S, cb + 1, W), P(flat, None, None)),
                        sds((S, W), P(flat, None)),
                        sds((S, 10, W), P(flat, None, None))).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (arm == "fused")
    assert "all-reduce" in text


# -- the served programs ------------------------------------------------
#
# benchmark/traffic/points-zipf-solo.json's eight templates and
# groupby60-distinct.json's four forms, as a lone caller sends them.
_R = "Row(a=1), Row(edu=2)"
_G = "GroupBy(Rows(edu), Rows(gen), Rows(dom), filter="
SERVED = {
    "count_intersect": (f"Count(Intersect({_R}))", None),
    "count_union": (f"Count(Union({_R}))", None),
    "count_xor": (f"Count(Xor({_R}))", None),
    "count_difference": (f"Count(Difference({_R}))", None),
    "count_intersect_range": (
        f"Count(Intersect({_R}, Row(age > 40)))", None),
    "count_range": ("Count(Row(age > 40))", None),
    "topn_filtered": (f"TopN(t, Intersect({_R}), n=5)", None),
    "sum_filtered": (f"Sum(Intersect({_R}), field=age)", None),
    "g60_sum_row": (_G + "Row(t=3), aggregate=Sum(field=age))",
                    "groupby_fused_sum"),
    "g60_sum_range": (_G + "Row(age > 40), aggregate=Sum(field=age))",
                      "groupby_fused_sum"),
    "g60_count": (_G + "Row(t=3))", "groupby_fused_sum"),
    "g60_min": (_G + "Row(t=3), aggregate=Min(field=age))",
                "groupby_fused_minmax"),
}


# the point reads of the int field
BSI_POINTS = ("count_intersect_range", "count_range", "sum_filtered")


@pytest.fixture(scope="module")
def served(topo, one_chip):
    """`programs(pql)`: the device programs one request of the default
    serving engine dispatches, each compiled for the described chip.
    able's field shapes (benchmark/configs/able-1b.json) on 3 shards
    of 2^20 columns; every dispatch is caught where the engine makes
    it (dispatch_ready) and answered with zeros of the right shapes,
    so nothing runs here either.  The two GroupBy variables are the
    arms a TPU picks by itself (chip_smoke.py sets the same)."""
    from pilosa_tpu.executor import ragged, serving
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.models.schema import FieldOptions, FieldType

    rng = np.random.default_rng(33)
    h = Holder()
    idx = h.create_index("able", track_existence=True)
    cols = np.arange(0, 3 * idx.width, 997)
    for name, rows in (("a", 2), ("b", 2), ("t", 8), ("edu", 6),
                       ("gen", 2), ("dom", 5), ("reg", 4)):
        idx.create_field(name, FieldOptions(type=FieldType.SET)) \
            .import_bits(rng.integers(0, rows, size=len(cols)), cols)
    idx.create_field(
        "age", FieldOptions(type=FieldType.INT, min=0, max=127)) \
        .import_values(cols, rng.integers(0, 128, size=len(cols)).tolist())
    idx.mark_columns_exist([int(c) for c in cols])
    # taxi-1b's group fields, one filter field and the amount
    taxi = h.create_index("taxi", track_existence=True)
    for name, rows in (("passenger_count", 10), ("pickup_year", 8),
                       ("dist_miles", 60), ("pickup_month", 12)):
        taxi.create_field(name, FieldOptions(type=FieldType.MUTEX)) \
            .import_bits(rng.integers(0, rows, size=len(cols)), cols)
    taxi.create_field("total_amount_dollars", FieldOptions(
        type=FieldType.INT, min=0, max=500)).import_values(
            cols, rng.integers(0, 501, size=len(cols)).tolist())
    taxi.mark_columns_exist([int(c) for c in cols])
    ex = Executor(h)
    ex.enable_serving(cache_bytes=0)

    seen = []

    def catch(fn, *args):
        seen.append((fn.__wrapped__, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one_chip), args)))
        return jax.tree_util.tree_map(
            lambda o: np.zeros(o.shape, o.dtype),
            jax.eval_shape(fn.__wrapped__, *args))

    mp = pytest.MonkeyPatch()
    mp.setenv("PILOSA_TPU_GROUPBY_ONEPASS_ARM", "fused")
    mp.setenv("PILOSA_TPU_GROUPBY_KERNEL", "1")
    for mod in (stacked, ragged, serving):
        mp.setattr(mod, "dispatch_ready", catch)

    def programs(pql, index="able"):
        del seen[:]
        ex.execute_serving(index, pql)
        return [jax.jit(fn).lower(*args).compile().as_text()
                for fn, args in seen]
    yield programs
    mp.undo()


@pytest.mark.parametrize("template", list(SERVED))
def test_served_program_compiles_for_v5e(served, template):
    """One request of each template compiles for the chip; a point
    read's programs hold no Pallas call, a GroupBy's exactly one, the
    fused kernel under its own name.  A point read of the age planes
    (9 of them, 3 shards) gathers each out of the page concatenation:
    it never re-lays the leaf out as the (S, P, W) stack nor slices a
    plane into (1, 128) tiles."""
    import re
    pql, kernel = SERVED[template]
    texts = served(pql)
    assert texts
    assert any("HloModule jit_plan_ragged" in t for t in texts) \
        == (template != "g60_min")       # Min/Max: the solo one-pass
    calls = [ln for t in texts for ln in _kernel_calls(t)]
    assert [re.search(r"%(groupby_fused_[a-z]+)", c).group(1)
            for c in calls] == ([kernel] if kernel else [])
    if template in BSI_POINTS:
        assert not any(re.search(
            r"= u32\[3,9,32768\]\S* (?!bitcast\()", t) for t in texts)
        assert not any(re.search(
            r"= u32\[3,1,32768\]\{[^}]*T\(1,128\)", t) for t in texts)


_T = "GroupBy(Rows(passenger_count), Rows(pickup_year)"
SERVED_TAXI = {
    "q2_amount": ("GroupBy(Rows(passenger_count), filter=Row(pickup_month=3),"
                  " aggregate=Sum(field=total_amount_dollars))",
                  "groupby_fused_sum"),
    "q3_year": (_T + ", filter=Row(pickup_month=3))", "groupby_fused_sum"),
    "q4_dist": (_T + ", Rows(dist_miles), filter=Row(pickup_month=3))",
                "groupby_fused_sum"),
    "q4_dist_range": (_T + ", Rows(dist_miles), filter=Intersect("
                      "Row(pickup_month=3), Row(total_amount_dollars > 9)))",
                      "groupby_fused_sum"),
}


@pytest.mark.parametrize("template", list(SERVED_TAXI))
def test_served_taxi_groupby_compiles_for_v5e(served, template):
    """taxi-1b's GroupBys as a lone caller sends them: one ragged
    program each with exactly one kernel — Q4's 8,192 codes in one
    walk since PR 40, so under the one-walk name."""
    import re
    pql, kernel = SERVED_TAXI[template]
    texts = served(pql, index="taxi")
    assert texts and any("HloModule jit_plan_ragged" in t for t in texts)
    calls = [ln for t in texts for ln in _kernel_calls(t)]
    assert [re.search(r"%(groupby_fused_[a-z]+)", c).group(1)
            for c in calls] == [kernel]
