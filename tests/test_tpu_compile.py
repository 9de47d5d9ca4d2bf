"""The main path's Pallas kernels, compiled for a TPU v5e that is
described, not attached (on-chip-measurement guide, section 2).

Interpret mode — what every other kernel test runs — checks neither
Mosaic's block-shape rules nor its VMEM limit: both one-pass GroupBy
kernels passed every test and were refused at lowering.  These cases
compile each kernel at the real shard width (W = 32768 words) and
assert the compiled program carries the kernel (``tpu_custom_call``).
Nothing runs; a compile that passes is not a chip run.

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
# scoped VMEM a v5e kernel may use unless it asks for more
# (CompilerParams(vmem_limit_bytes=), which groupby_fused does not)
V5E_SCOPED_VMEM = 16 << 20


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
    "groupby_onehot": lambda: (
        lambda cp, va, pl: kernels.groupby_onehot(cp, va, pl, 64, True),
        [(S, 6, W), (S, W), (S, 10, W)]),
    "groupby_sum": _groupby_sum,
    "bsi_value_hist": lambda: (kernels.bsi_value_hist,
                               [(S, 10, W), (S, W)]),
    "popcount_rows": lambda: (kernels.popcount_rows, [(S, W)]),
    "pair_popcount": lambda: (kernels.pair_popcount, [(S, W), (S, W)]),
    "masked_popcount": lambda: (kernels.masked_popcount, [(S, W), (W,)]),
    "bsi_sum_counts": lambda: (kernels.bsi_sum_counts, [(10, W), (W,)]),
    "rows_filter_counts": lambda: (kernels.rows_filter_counts,
                                   [(8, S, W), (S, W)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# the able query's forms through the packed body (ISSUE 32): age is an
# unsigned 7-bit int.  "vhist_1024" is the largest value histogram
# that takes it (a 9-bit int: every one of 1,024 codes live, so the
# mask tree is at its widest and the block at one vreg)
PACKED = {
    "able_sum": dict(digits=ABLE, depth=7),
    "able_count": dict(digits=ABLE, depth=0),
    "able_reg_sum": dict(digits=ABLE_REG, depth=7),
    "vhist_1024": dict(digits=((1, 2),) * 10, depth=0),
}


@pytest.mark.parametrize("name", list(PACKED))
def test_packed_body_fits_v5e_vmem(one_chip, name):
    """The packed body compiles for the chip and the scoped VMEM it
    asks for (accumulators, mask scratch, operand blocks, Mosaic's
    own) stays under what a kernel gets by default."""
    import re
    fn, shapes = _fused(0, signed=False, **PACKED[name])
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "groupby_fused_" in calls[0]
    asked = [int(n) for n in re.findall(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"0","size":"(\d+)"', calls[0])]
    assert asked and 0 < asked[0] < V5E_SCOPED_VMEM, asked
    assert asked[0] <= kernels._PACKED_VMEM_BYTES + (1 << 20), asked


@pytest.mark.parametrize("arm", ["fused", "onehot"])
def test_onepass_shard_map_compiles_for_four_chips(topo, arm):
    """The mesh GroupBy wrapper (stacked._groupby_onepass_shard_map)
    over the four described chips: per-device kernel + psum."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("rows", "shards"))
    flat = ("rows", "shards")

    def sds(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.uint32,
                                    sharding=NamedSharding(mesh, spec))
    fn = stacked._groupby_onepass_shard_map(
        mesh, arm, has_planes=True, has_filter=True, signed=True,
        n_codes=64)
    compiled = fn.lower(sds((S, 7, W), P(flat, None, None)),
                        sds((S, W), P(flat, None)),
                        sds((S, 10, W), P(flat, None, None))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
