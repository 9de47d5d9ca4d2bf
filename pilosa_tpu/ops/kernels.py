"""Pallas TPU kernels: the GroupBy family and the BSI value histogram.

The scans of the served path — Count of set algebra, TopN candidate
counts, BSI Sum and range compares — are plain jnp (`ops.bitmap`,
`ops.bsi`): XLA fuses the AND into the popcount-reduce and the operand's
producer into the scan, which a pallas_call (a fusion barrier) cannot.
What is here is what XLA does badly:

- :func:`groupby_fused` — the one-pass GroupBy histogram over a dense
  group code (counts, BSI Sum partials, Min/Max tables); two bodies,
  chosen from the static shapes by :func:`fused_body`.  The default on
  a TPU inside the executor's bounds.
- :func:`groupby_sum` — the per-combo kernel (scalar-prefetch gather,
  combos innermost) for GroupBy fields whose rows overlap.
- :func:`groupby_codes_xla` — the XLA scatter form of the same
  histogram: the oracle the kernels are tested against, the arm off a
  TPU and past the kernel's bounds, and the mesh shard_map body.
- :func:`bsi_value_hist` — Range / Distinct / Min / Max of an int field
  as one run of the histogram with the value as the group code.
- the `*_hbm_bytes` models the roofline plane notes from.

The XLA GroupBy scan must materialize gathered (C, S, W) combo masks
and re-read them once per BSI plane; the kernels read each operand
stream about once.

All kernels run in interpret mode off a TPU, so the CPU test mesh
(conftest.py) runs the same code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pilosa_tpu.ops import bitmap as bm

_LANES = 128          # TPU lane width (last-dim tile)


def _interpret() -> bool:
    """Pallas interpret mode off-TPU (trace-time decision)."""
    return jax.default_backend() != "tpu"


def _pc(x):
    return jax.lax.convert_element_type(jax.lax.population_count(x),
                                        jnp.int32)


def _pad_axis(x, axis, block):
    """Zero-pad `axis` of x up to a multiple of block (zeros are
    popcount-neutral, so all kernels here tolerate the padding)."""
    n = x.shape[axis]
    pad = (-n) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


# ---------------------------------------------------------------------------
# fused GroupBy + Sum: the whole combo space in one pass
# ---------------------------------------------------------------------------

def _groupby_kernel(nf: int, depth: int, signed: bool, c_dim: int):
    """Kernel body factory: nf field stacks, BSI depth (0 = no
    aggregate), sign-split on/off, c_dim combos.  Outputs are whole
    (·, C) blocks resident in VMEM for the entire grid; each step
    accumulates into its combo's lane via a one-hot (dynamic lane
    stores don't lower on TPU)."""

    def kernel(sel_ref, *refs):
        # refs: nf stack refs [+ planes_ref], then outputs
        # (cnt_ref [, nn_ref, pos_ref, neg_ref])
        stacks = refs[:nf]
        i = nf
        planes_ref = refs[i] if depth else None
        i += 1 if depth else 0
        cnt_ref = refs[i]
        s, w, c = (pl.program_id(0), pl.program_id(1),
                   pl.program_id(2))

        @pl.when((s == 0) & (w == 0) & (c == 0))
        def _init():
            for r in refs[i:]:
                r[...] = jnp.zeros_like(r)

        onehot = (jax.lax.broadcasted_iota(
            jnp.int32, (1, c_dim), 1) == c).astype(jnp.int32)
        m = stacks[0][0]
        for f in range(1, nf):
            m = m & stacks[f][0]                   # (BS, BW)
        cnt_ref[...] += jnp.sum(_pc(m)) * onehot
        if depth:
            nn_ref, pos_ref = refs[i + 1], refs[i + 2]
            exists = planes_ref[:, 0, :]
            em = m & exists
            nn_ref[...] += jnp.sum(_pc(em)) * onehot
            mag = planes_ref[:, 2:, :]             # (BS, depth, BW)
            if signed:
                neg_ref = refs[i + 3]
                sign = planes_ref[:, 1, :]
                pos = em & ~sign
                neg = em & sign
                pos_pc = jnp.sum(_pc(mag & pos[:, None, :]),
                                 axis=(0, 2))      # (depth,)
                neg_pc = jnp.sum(_pc(mag & neg[:, None, :]),
                                 axis=(0, 2))
                pos_ref[...] += pos_pc[:, None] * onehot
                neg_ref[...] += neg_pc[:, None] * onehot
            else:
                pos_pc = jnp.sum(_pc(mag & em[:, None, :]),
                                 axis=(0, 2))
                pos_ref[...] += pos_pc[:, None] * onehot
    return kernel


_GB_SHARD_BLOCK = 8
_GB_WORD_BLOCK = 4096


def groupby_sum(stacks, sel, planes=None, signed=True):
    """Fused GroupBy: every combo's count (+ BSI Sum partials) in ONE
    pass over the field stacks (executor.go:3918 + 8617, collapsed).

    stacks: list of (R_f, S, W) uint32 per GroupBy field;
    sel: (C, nf) int32 combo row indices; planes: (S, P+2, W) or None;
    signed: compute the negative sign-split (skippable when the sign
    plane is empty).  Returns (counts (C,), nn (C,), pos (C, depth),
    neg (C, depth)) int32 — nn/pos/neg None without planes.

    Schedule: grid (S/BS, W/BW, C) with combos INNERMOST and the combo
    row chosen via scalar-prefetched `sel` (the embedding-gather
    pattern) — the plane block loads once per (shard, word) tile and
    is reused by all C combos, so total HBM traffic is ~one read of
    each stack row per referencing combo plus ONE read of the planes,
    instead of the XLA path's per-chunk re-materialization.
    Per-combo totals accumulate across shard tiles in int32 (exact
    below ~2k shards; callers above that use the unreduced XLA path).
    """
    nf = len(stacks)
    c_dim, nf2 = sel.shape
    assert nf2 == nf and nf >= 1
    s_dim, w_dim = stacks[0].shape[1:]
    bs = min(_GB_SHARD_BLOCK, s_dim)
    bw = min(_GB_WORD_BLOCK, w_dim)
    stacks = [_pad_axis(_pad_axis(x, 1, bs), 2, bw) for x in stacks]
    depth = 0
    if planes is not None:
        planes = _pad_axis(_pad_axis(planes, 0, bs), 2, bw)
        depth = planes.shape[1] - 2
    spad, wpad = stacks[0].shape[1:]
    grid = (spad // bs, wpad // bw, c_dim)
    sel = jnp.asarray(sel, dtype=jnp.int32)

    def stack_spec(f):
        return pl.BlockSpec(
            (1, bs, bw), lambda s, w, c, sel_ref: (sel_ref[c, f], s, w))

    in_specs = [stack_spec(f) for f in range(nf)]
    arrays = list(stacks)
    if planes is not None:
        in_specs.append(pl.BlockSpec(
            (bs, 2 + depth, bw), lambda s, w, c, sel_ref: (s, 0, w)))
        arrays.append(planes)
    # outputs live as whole (·, C) VMEM-resident blocks (index_map
    # constant across the grid)
    fixed = lambda s, w, c, sel_ref: (0, 0)
    out_specs = [pl.BlockSpec((1, c_dim), fixed)]
    out_shape = [jax.ShapeDtypeStruct((1, c_dim), jnp.int32)]
    if planes is not None:
        out_specs.append(pl.BlockSpec((1, c_dim), fixed))
        out_shape.append(jax.ShapeDtypeStruct((1, c_dim), jnp.int32))
        n_agg = 2 if signed else 1
        for _ in range(n_agg):
            out_specs.append(pl.BlockSpec((depth, c_dim), fixed))
            out_shape.append(
                jax.ShapeDtypeStruct((depth, c_dim), jnp.int32))
    out = pl.pallas_call(
        _groupby_kernel(nf, depth, signed, c_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        name="groupby_sum",
        interpret=_interpret(),
    )(sel, *arrays)
    if planes is None:
        return out[0][0], None, None, None
    counts, nn = out[0][0], out[1][0]
    pos = out[2].T                                 # (C, depth)
    neg = out[3].T if signed else jnp.zeros_like(pos)
    return counts, nn, pos, neg


# ---------------------------------------------------------------------------
# one-pass GroupBy: combo-independent group-code histogram
# ---------------------------------------------------------------------------
#
# The fused per-combo kernel above re-reads every referenced stack row
# and re-popcounts every BSI plane once PER COMBO — O(C*S*W) traffic.
# The histogram formulation reads every word exactly once regardless of
# combo count: per column, compose a dense group code from packed digit
# planes (ops.bitmap.digit_planes — one digit per disjoint GroupBy
# field), then accumulate counts and BSI sign-split plane partials into
# a (K, G) table indexed by code.  groupby_fused (below) does the
# accumulation in one kernel; groupby_codes_xla is the scatter-add XLA
# form it is cross-checked against (and the mesh shard_map body).
#
# Output layout (shared): rows [counts, nn, pos_plane_0..d-1,
# neg_plane_0..d-1] — identical per-plane sign-split partials to
# groupby_sum / bsi.sum_counts, so host combination (exact Python-int
# shift-add) is byte-for-byte the same across all GroupBy paths.


def _gc_payload_rows(va, ex, sg, mag_bits, depth: int, signed: bool):
    """Per-column 0/1 payload rows [count, nn, pos*depth, neg*depth]
    from unpacked bit vectors (any common shape)."""
    rows = [va]
    if depth:
        rows.append(ex)
        posm = ex * (1 - sg) if signed else ex
        for p in range(depth):
            rows.append(mag_bits[p] * posm)
        if signed:
            negm = ex * sg
            for p in range(depth):
                rows.append(mag_bits[p] * negm)
    return rows


def groupby_codes_xla(code_planes, valid, planes=None, n_codes: int = 1,
                      signed: bool = True, minmax: bool = False):
    """XLA reference for the one-pass GroupBy histogram.

    code_planes: (S, CB, W) uint32 packed group-code bit-planes
    (bitmap.digit_planes of each field, stride-concatenated);
    valid: (S, W) uint32 mask of columns belonging to some combo
    (AND of field unions, AND the filter); planes: (S, 2+depth, W)
    BSI stack or None.  Returns (counts (G,), nn (G,), pos (G, depth),
    neg (G, depth)) int32 over the FULL dense code space G = n_codes —
    every input word is read exactly once, independent of combo count.
    With ``minmax=True`` (requires planes) additionally returns the
    (4, G) [max_mag_pos, min_mag_pos, max_mag_neg, min_mag_neg] table
    via scatter-max/min — the oracle for groupby_fused's
    Min/Max (identities -1 / 1<<depth; see minmax_from_table).
    """
    depth = 0 if planes is None else planes.shape[1] - 2
    assert not (minmax and depth == 0), "minmax requires BSI planes"
    k = _payload_rows(depth, signed)
    big = 1 << depth

    def one_shard(acc, args):
        cp, va_w = args[0], args[1]
        pl_w = args[2] if depth else None
        code = bm.code_from_planes(cp)                # (N,) int32
        va = bm.unpack_bits(va_w)                     # (N,) 0/1
        # invalid columns route to an overflow bucket sliced off below
        seg = jnp.where(va == 1, code, n_codes)
        ex = sg = None
        mag = []
        if depth:
            ex = bm.unpack_bits(pl_w[0]) * va
            sg = bm.unpack_bits(pl_w[1])
            mag = [bm.unpack_bits(pl_w[2 + p]) for p in range(depth)]
        rows = _gc_payload_rows(va, ex, sg, mag, depth, signed)
        outs = [jnp.zeros(n_codes + 1, jnp.int32).at[seg].add(r)
                for r in rows]
        hist_acc = acc[0] if minmax else acc
        hist_acc = hist_acc + jnp.stack(outs)[:, :n_codes]
        if not minmax:
            return hist_acc, None
        mag_val = jnp.zeros_like(code)
        for p in range(depth):
            mag_val = mag_val | (mag[p] << p)
        posm = ex * (1 - sg) if signed else ex
        negm = ex * sg if signed else jnp.zeros_like(ex)

        def side(mask):
            sm = jnp.where(mask == 1, seg, n_codes)
            mx = jnp.full(n_codes + 1, -1, jnp.int32
                          ).at[sm].max(mag_val)[:n_codes]
            mn = jnp.full(n_codes + 1, big, jnp.int32
                          ).at[sm].min(mag_val)[:n_codes]
            return mx, mn

        mxp, mnp_ = side(posm)
        mxn, mnn = side(negm)
        mm = jnp.stack([jnp.maximum(acc[1][0], mxp),
                        jnp.minimum(acc[1][1], mnp_),
                        jnp.maximum(acc[1][2], mxn),
                        jnp.minimum(acc[1][3], mnn)])
        return (hist_acc, mm), None

    init = jnp.zeros((k, n_codes), jnp.int32)
    if minmax:
        mm0 = jnp.stack([jnp.full(n_codes, -1, jnp.int32),
                         jnp.full(n_codes, big, jnp.int32),
                         jnp.full(n_codes, -1, jnp.int32),
                         jnp.full(n_codes, big, jnp.int32)])
        init = (init, mm0)
    args = (code_planes, valid) + ((planes,) if depth else ())
    acc, _ = jax.lax.scan(one_shard, init, args)
    acc, mm = acc if minmax else (acc, None)
    counts = acc[0]
    if depth == 0:
        return counts, None, None, None
    nn = acc[1]
    pos = acc[2:2 + depth].T                          # (G, depth)
    neg = acc[2 + depth:].T if signed else jnp.zeros_like(pos)
    if not minmax:
        return counts, nn, pos, neg
    return counts, nn, pos, neg, mm


def _valid_operand(valid, bw: int):
    """The (S, W) validity mask as a one-pass kernel operand, one
    shard and `bw` words per grid step: (S, 1, W) with a (1, 1, bw)
    block.  Mosaic wants a block's last two dims to be multiples of
    (8, 128) or the whole array dims, which a (1, bw) block of (S, W)
    is not."""
    return (_pad_axis(valid, 1, bw)[:, None, :],
            pl.BlockSpec((1, 1, bw), lambda s, w: (s, 0, w)))


# ---------------------------------------------------------------------------
# fused single-pass GroupBy: per-group masks ANDed and popcounted on
# packed words
# ---------------------------------------------------------------------------
#
# One pass over the operands, and the inner body never leaves the
# packed domain (ISSUE 32; the last level counted where it is formed,
# ISSUE 40).  Per (shard, word block) grid step:
#
#   0. every plane of the block is re-laid out in VMEM so that its
#      words fill whole (8, 128) vregs (the (1, P, BW) block arrives
#      with the planes along sublanes: one sublane of eight per plane);
#   1. the UPPER masks are formed and parked in a VMEM scratch: every
#      field but the widest multiplied out as a tree, each level one
#      array of masks — a row of a field is the AND of its digit
#      planes or their complements, `valid` is ANDed into the first
#      level (2 x 5 = 10 masks for the able query, 10 x 8 = 80 for
#      taxi-1b's Q4);
#   2. the widest field's rows are walked in a rolled loop.  A row's
#      literal is formed once a turn (the row index's bits keep a
#      digit plane or flip it) and kept in a block-wide scratch; then,
#      for a handful of upper masks at a time (_PACKED_INNER, a static
#      inner group inside a second rolled loop), a group's mask is
#      `upper & literal` in registers and its payload rows are
#      popcounts of ANDs off that value — popcount(m),
#      popcount(m & exists), popcount(m & exists [& ~sign | & sign] &
#      plane_p) — folded over the block's vregs in registers and added
#      once per grid step into the group's int32 lane partials.  A
#      (group, vreg) unit of a count is two loads, one AND, one
#      popcount and one add; no group's mask is ever stored.
#
# Kept in VMEM: the accumulators (live groups x payload rows, a vreg
# each), the upper masks and one literal (a block each), the operand
# blocks and their re-laid-out copy.  The lane partials leave the
# kernel as they are, by one copy on the last grid step, and one small
# XLA reduce makes the dense (K, G) table of them; codes whose digit
# exceeds its field's row count are never visited and stay 0.
#
# The accumulators grow with live groups x payload rows.  Where one
# walk's do not fit the VMEM the kernel asks for (_PACKED_VMEM_LIMIT;
# _PACKED_VMEM_BYTES of it are the body's to count) the widest
# field's rows are walked in slices: a leading grid axis, a walk per
# slice, the slice's first row read off the walk index, the
# accumulators flushed into their walk's part of the output
# (taxi-1b's 10 x 8 x 60 = 4,800 groups are one walk; with its 9-bit
# amount summed, 12 walks of 5 rows).  Every walk streams the operands
# and forms the upper masks again; the popcount work is what it was.
# Where not even one row of the widest field fits at the full block
# width, and for Min/Max (which the packed body does not compute:
# ROADMAP A3), the earlier body serves:
# every column unpacked to an int32 code, compared with a 128-lane
# iota into a one-hot and contracted against the 0/1 payload rows on
# the MXU (int8 @ int8 -> int32), Min/Max as masked reductions over
# the same one-hot.  fused_body() is the one place that decides, from
# the static arguments alone.
#
# Exactness: popcounts of {0, 1} columns accumulated in int32 lane
# partials and summed in int32 (callers bound shards like the other
# paths); the one-hot body's per-chunk partial sums are <= bc*BW*32
# < 2^24 terms.

_VREG_WORDS = 8 * _LANES      # one (8, 128) vreg of packed words
# the scoped VMEM every packed call asks Mosaic for
# (CompilerParams(vmem_limit_bytes=): a v5e core has 128 MiB and gives
# a kernel 16 by default), and what the body may count of it — the
# rest is Mosaic's own (taxi-1b's Q4 counts 27.7 MiB and asks 26.7).
# Chosen on the chip (PR 40; Q4's 4,800 groups alone at 263 shards):
# one walk of 16-vreg blocks 0.0297 s; inside 13 MiB five walks of 12
# rows at 16 vregs 0.0346, three of 20 rows at 8 vregs 0.0387
_PACKED_VMEM_LIMIT = 32 << 20
_PACKED_VMEM_BYTES = 29 << 20
_PACKED_BLOCK_VREGS = 16      # vregs of each plane per grid step, at most
_PACKED_WALK_MIN_VREGS = 8    # ... and at least, where a selection walks
                              # in slices (_packed_passes)
# upper masks an inner turn takes: that many independent
# AND -> popcount -> add chains overlap, their partials in registers
# (Q4 alone: 8 a turn 0.0297 s, 4 a turn 0.0321, 16 a turn 0.0308)
_PACKED_INNER = 8
# the code space the one-hot body takes (its lane axis, 128 codes a
# block); stacked's _ONEPASS_KERNEL_MAX_CODES is this number
ONEHOT_MAX_CODES = 4096


def _payload_rows(depth: int, signed: bool) -> int:
    """K of the shared (K, G) layout: counts, then nn and the
    sign-split plane partials when there is a BSI field."""
    return 1 if depth == 0 else 2 + (2 if signed else 1) * depth


def dense_digits(cb: int) -> tuple:
    """The digit layout of a code space whose every code is live (a
    value histogram's): the bits as two fields, so that the packed
    body parks 2^(cb - cb // 2) upper masks and walks 2^(cb // 2)
    rows against them."""
    lo = cb // 2
    return tuple((b, 1 << b) for b in (cb - lo, lo) if b) or ((0, 1),)


def _last_field(digits) -> int:
    """The field whose rows the packed body walks: the widest."""
    return max(range(len(digits)), key=lambda i: digits[i][1])


def _n_upper(digits, fi: int) -> int:
    """Upper masks: the product of every other field's rows."""
    return int(np.prod([rows for i, (_, rows) in enumerate(digits)
                        if i != fi], dtype=np.int64))


def _packed_block_vregs(digits, fi: int, rp: int, depth: int,
                        signed: bool) -> int:
    """Vregs of each plane the packed body takes per grid step when a
    walk holds `rp` rows of field `fi`: the most (a power of two up
    to _PACKED_BLOCK_VREGS) at which what it keeps in VMEM fits
    _PACKED_VMEM_BYTES, 0 when not even one does.  Counted in vregs:
    the accumulators (upper masks x rows of the walk x payload rows),
    and per block vreg the parked upper masks, the row's literal, the
    operand blocks (double-buffered, planes padded to 8 sublanes) and
    their re-laid-out copy."""
    n_upper = _n_upper(digits, fi)
    cb = max(sum(bits for bits, _ in digits), 1)
    groups = [cb, 1] + ([2 + depth] if depth else [])
    acc = n_upper * rp * _payload_rows(depth, signed)
    per_vreg = (n_upper + 1 + sum(groups)
                + 2 * sum(-(-g // 8) * 8 for g in groups))
    nv = _PACKED_BLOCK_VREGS
    while nv and 4 * _VREG_WORDS * (
            acc + nv * per_vreg) > _PACKED_VMEM_BYTES:
        nv //= 2
    return nv


@functools.lru_cache(maxsize=256)
def _packed_passes(digits, depth: int, signed: bool, selected: bool = False):
    """How the packed body walks the live groups of `digits`:
    (block vregs, last field, rows a walk, walks).  The last field is
    the widest; one walk takes all its rows where the accumulators
    fit, else the walks take slices of them, as few as keep the block
    at its full width (_PACKED_BLOCK_VREGS), the rows spread evenly
    over them.  Block vregs 0 where not even one row fits at that
    width: every walk forms the upper masks again, and narrow blocks
    under many walks lose to the scatter (three fields of 60 rows,
    count-only, 263 shards: 60 walks of 1-vreg blocks 8.92 s, the XLA
    scatter 1.91; chip, PR 40).  A walk over a `selected` set of rows
    (selection) keeps the block at the width one row allows down to
    _PACKED_WALK_MIN_VREGS: the deep Sums of a pruned GroupBy (26
    payload rows) fit no row at 16, and their scatter would first
    build a group-code stack of the rows kept."""
    fi = _last_field(digits)
    rows = digits[fi][1]

    def block(rp):
        return _packed_block_vregs(digits, fi, rp, depth, signed)

    nv = block(rows)
    if nv:
        return nv, fi, rows, 1
    nv = block(1)
    least = _PACKED_WALK_MIN_VREGS if selected else _PACKED_BLOCK_VREGS
    if not nv or nv < min(least, _PACKED_BLOCK_VREGS):
        return 0, fi, 0, 1
    rp = 1
    while block(rp + 1) == nv:
        rp += 1
    n_pass = -(-rows // rp)
    return nv, fi, -(-rows // n_pass), n_pass


def _pass_codes(digits, fi: int, rp: int, n_pass: int) -> np.ndarray:
    """Dense codes of the groups the packed body visits, (walks,
    groups a walk) in the order it visits them: the upper fields
    multiply out first (first field fastest), the last field's rows
    last; -1 where a slot's row is past its field's last (the last
    walk of a row count the slice does not divide)."""
    shifts = np.cumsum([0] + [b for b, _ in digits])
    codes = np.zeros(1, np.int64)
    for i, (_bits, rows) in enumerate(digits):
        if i != fi:
            codes = (codes[None, :]
                     | (np.arange(rows)[:, None] << shifts[i])).ravel()
    r = np.arange(n_pass * rp).reshape(n_pass, rp, 1)
    out = codes[None, None, :] | (r << shifts[fi])
    return np.where(r < digits[fi][1], out, -1).reshape(n_pass, -1)


def fused_plan(digits, depth: int, signed: bool = True,
               minmax: bool = False) -> tuple:
    """(body, walks over the operands) of groupby_fused for these
    static arguments: "packed" for counts and Sum where its
    accumulators fit the kernel's VMEM in one walk, or in several
    (_packed_passes) where the code space is past ONEHOT_MAX_CODES —
    else "onehot" in one walk (Min/Max always).
    `digits` is ((bits, rows), ...) per GroupBy field (the _code_space
    layout with each field's row count).  Called at trace time by
    groupby_fused and once a dispatch by stacked._onepass_plan, which
    picks the arm and counts pilosa_groupby_fused_total{body=} and
    the walks from it, so all agree."""
    if not minmax:
        nv, _fi, _rp, n_pass = _packed_passes(tuple(digits), depth, signed)
        # in several walks only past the code space the one-hot body
        # takes: inside it the one-hot was the faster of the two where
        # one walk did not fit (64 x 64 groups, a signed 16-bit Sum, 64
        # shards: 0.193 s against 0.424 in 64 passes; chip, PR 36)
        if nv and (n_pass == 1 or 1 << sum(
                b for b, _ in digits) > ONEHOT_MAX_CODES):
            return "packed", n_pass
    return "onehot", 1


def fused_body(digits, depth: int, signed: bool = True,
               minmax: bool = False) -> str:
    return fused_plan(digits, depth, signed, minmax)[0]


def selected_digits(digits) -> tuple:
    """The code layout of a selection's groups, `digits` being ((bits
    of the operand's digit planes, rows selected), ...) with a power
    of two of rows a field: the selected rows' positions are the
    digits, so the codes are dense over their product."""
    return tuple(((rows - 1).bit_length(), rows) for _bits, rows in digits)


def selection(digits, depth: int, signed: bool, live) -> np.ndarray | None:
    """groupby_fused's `sel` for the rows `live` (per field, the
    digits selected, at most that field's rows of `digits`): one
    segment a field, -1 in the slots past its selection and past the
    last walk's rows.  None where the packed body cannot walk the
    selection (one row of the widest field does not fit)."""
    digits = tuple(digits)
    nv, _fi, rp, n_pass = _packed_passes(digits, depth, signed, True)
    if not nv:
        return None
    n = max(max(rows for _, rows in digits), n_pass * rp)
    sel = np.full((len(digits), n), -1, np.int32)
    for i, rows in enumerate(live):
        sel[i, :len(rows)] = rows
    return sel.ravel()


def _inner_groups(n_upper: int, k: int) -> tuple:
    """(upper masks an inner iteration, whole iterations, masks left
    over): at most _PACKED_INNER x k partials stay in registers, and
    the masks are spread evenly over the iterations."""
    most = max(1, _PACKED_INNER // k)
    u = -(-n_upper // -(-n_upper // most))
    return u, n_upper // u, n_upper % u


def _gb_packed_kernel(digits, depth: int, signed: bool, k: int, nv: int,
                      fi: int, rp: int, n_pass: int, sel_len: int = 0):
    """Packed body factory (see the block comment above): a walk takes
    rows [walk * rp, (walk + 1) * rp) of field `fi`; with more walks
    than one the grid leads with the walk axis.

    `sel_len` > 0: the rows are a selection (groupby_fused's `sel`, the
    first operand, in SMEM): row r of field i is the digit
    ``sel[i * sel_len + r]``, or no column where that is negative, so
    each row's literal is formed from a scalar read at run time, not
    from its static index."""
    cb = sum(bits for bits, _ in digits)
    # lax primitives, not jnp's jitted wrappers (each a nested pjit to
    # trace and lower), and `step` vregs of words to an operation:
    # what Mosaic has to lower stays in the hundreds of operations
    _and, _not = jax.lax.bitwise_and, jax.lax.bitwise_not
    step = next(u for u in (4, 2, 1) if nv % u == 0)
    chunks = [slice(8 * step * c, 8 * step * (c + 1))
              for c in range(nv // step)]
    lead = 1 if n_pass > 1 else 0
    starts = [int(x) for x in np.cumsum([0] + [b for b, _ in digits])]
    last_bits = digits[fi][0]
    n_upper = _n_upper(digits, fi)
    inner, whole, left = _inner_groups(n_upper, k)

    def kernel(*refs):
        sel_ref = None
        if sel_len:
            sel_ref, refs = refs[0], refs[1:]
        cp_ref, va_ref, refs = refs[0], refs[1], refs[2:]
        pl_ref = refs[0] if depth else None

        def digit_lit(rs, start, bits, row):
            """A selection's literal of digit `row` (a scalar read from
            `sel`) over the planes [start, start + bits): each plane
            kept where the row's bit is set, flipped where not; no
            column where the row is negative (a dead slot)."""
            ones = jnp.uint32(0xFFFFFFFF)
            lit = None
            for b in range(bits):
                x = dense_ref[pl.ds(start + b, 1), rs, :]
                flip = jnp.where((row >> b) & 1 == 1, jnp.uint32(0), ones)
                t = jax.lax.bitwise_xor(x, jnp.full_like(x, flip))
                lit = t if lit is None else _and(lit, t)
            alive = jnp.where(row >= 0, ones, jnp.uint32(0))
            return _and(lit, jnp.full_like(lit, alive))
        (out_ref, acc_ref, masks_ref, lit_ref,
         dense_ref) = refs[1 if depth else 0:]
        s, wi = pl.program_id(lead), pl.program_id(lead + 1)

        @pl.when((s == 0) & (wi == 0))
        def _init():
            # slot by slot: one store of the whole table would be
            # unrolled into a store a vreg (4,800 for taxi-1b's Q4)
            def clear(i, carry):
                acc_ref[i] = jnp.zeros(acc_ref.shape[1:], jnp.int32)
                return carry

            jax.lax.fori_loop(0, acc_ref.shape[0], clear, 0)

        # 0. planes along sublanes -> each plane's words in whole vregs
        srcs = [(cp_ref, b) for b in range(cb)] + [(va_ref, 0)] + [
            (pl_ref, p) for p in range(2 + depth if depth else 0)]
        for i, (ref, p) in enumerate(srcs):
            dense_ref[i] = ref[0, p, :].reshape(8 * nv, _LANES)

        # 1. the upper masks, one vreg of words at a time: `valid` and
        # every field but the last multiplied out as a tree.  A level
        # is one array of masks, (n, 8, 128): the operations to lower
        # number the fields' rows, not the groups
        def upper_step(v, carry):
            rs = pl.ds(pl.multiple_of(v * 8, 8), 8)
            masks = dense_ref[pl.ds(cb, 1), rs, :]
            for i, (bits, rows) in enumerate(digits):
                if i == fi or not bits:
                    continue
                level = []
                if sel_len:
                    for r in range(rows):
                        lit = digit_lit(rs, starts[i], bits,
                                        sel_ref[i * sel_len + r])
                        level.append(_and(masks, lit))
                    masks = jax.lax.concatenate(level, 0)
                    continue
                one = [dense_ref[pl.ds(starts[i] + b, 1), rs, :]
                       for b in range(bits)]
                zero = [_not(x) for x in one]
                for r in range(rows):
                    lit = None
                    for b in range(bits):
                        t = one[b] if (r >> b) & 1 else zero[b]
                        lit = t if lit is None else _and(lit, t)
                    level.append(_and(masks, lit))
                masks = jax.lax.concatenate(level, 0)
            masks_ref[:, rs, :] = masks
            return carry

        jax.lax.fori_loop(0, nv, upper_step, 0)

        # 2. the last field's rows, one a turn of a rolled loop.  The
        # row's literal — its digit planes or their complements, as
        # the row index's bits say — is formed once and kept for the
        # turn; a group's mask is an upper mask ANDed with it, in
        # registers, and the payload rows are popcounts of ANDs off
        # that value, `step` vregs of words to an operation, folded
        # over the block in registers: one VMEM add per (group, row)
        first = pl.program_id(0) * rp if lead else 0

        def some(slot, base, n):
            hist = [[None] * k for _ in range(n)]
            for rs in chunks:
                lit = lit_ref[rs, :] if last_bits else None
                ex = sg = mags = None
                if depth:
                    ex = dense_ref[cb + 1, rs, :]
                    mags = [dense_ref[cb + 3 + p, rs, :]
                            for p in range(depth)]
                    if signed:
                        sg = dense_ref[cb + 2, rs, :]
                for u in range(n):
                    m = masks_ref[base + u, rs, :]
                    if lit is not None:
                        m = _and(m, lit)
                    words = [m]
                    if depth:
                        em = _and(m, ex)
                        words.append(em)
                        sides = [em]
                        if signed:
                            sides = [_and(em, _not(sg)), _and(em, sg)]
                        for side in sides:
                            words += [_and(side, mg) for mg in mags]
                    for r, x in enumerate(words):
                        c = _pc(x)
                        if step > 1:
                            c = jnp.sum(c.reshape(step, 8, _LANES), axis=0)
                        hist[u][r] = c if hist[u][r] is None \
                            else jax.lax.add(hist[u][r], c)
            for u in range(n):
                for r in range(k):
                    acc_ref[slot + base + u, r] += hist[u][r]

        def row(j, carry):
            if last_bits and sel_len:
                digit = sel_ref[fi * sel_len + first + j]
                for rs in chunks:
                    lit_ref[rs, :] = digit_lit(rs, starts[fi], last_bits,
                                               digit)[0]
            elif last_bits:
                # bit b of the row keeps plane b or flips it
                flips = [jnp.where(((first + j) >> b) & 1 == 1,
                                   jnp.uint32(0), jnp.uint32(0xFFFFFFFF))
                         for b in range(last_bits)]
                for rs in chunks:
                    lit = None
                    for b in range(last_bits):
                        x = dense_ref[starts[fi] + b, rs, :]
                        t = jax.lax.bitwise_xor(
                            x, jnp.full_like(x, flips[b]))
                        lit = t if lit is None else _and(lit, t)
                    lit_ref[rs, :] = lit
            slot = j * n_upper

            def turn(g, carry):
                some(slot, g * inner, inner)
                return carry

            jax.lax.fori_loop(0, whole, turn, 0)
            if left:
                some(slot, whole * inner, left)
            return carry

        jax.lax.fori_loop(0, rp, row, 0)

        dst = out_ref.at[pl.program_id(0)] if lead else out_ref

        @pl.when((s == pl.num_programs(lead) - 1)
                 & (wi == pl.num_programs(lead + 1) - 1))
        def _flush():
            pltpu.sync_copy(acc_ref, dst)
    return kernel


def _gb_fused_packed(code_planes, valid, planes, digits, depth: int,
                     signed: bool, n_codes: int, sel=None):
    """groupby_fused's packed body: returns the dense (K, G) table —
    with `sel`, over the selection's code space (selected_digits)."""
    s_dim, _cb, w_dim = code_planes.shape
    k = _payload_rows(depth, signed)
    nv, fi, rp, n_pass = _packed_passes(digits, depth, signed,
                                        sel is not None)
    sel_len = 0
    if sel is None:
        codes = _pass_codes(digits, fi, rp, n_pass)
    else:
        sel_len = sel.shape[0] // len(digits)
        codes = _pass_codes(selected_digits(digits), fi, rp, n_pass)
    nv = min(nv, -(-w_dim // _VREG_WORDS))
    bw = nv * _VREG_WORDS
    # zero padding is neutral: valid is ANDed into every mask
    arrays = [_pad_axis(x, 2, bw) for x in (
        code_planes, valid[:, None, :]) + ((planes,) if depth else ())]
    # the accumulators are scratch, so the VMEM asked for is what
    # _packed_block_vregs counted; they leave by one copy at the end
    # (of each walk)
    n_slots = codes.shape[1]
    table = (n_slots, k, 8, _LANES)
    grid = (s_dim, arrays[0].shape[2] // bw)
    at = lambda s, w, *_sel: (s, 0, w)
    if n_pass > 1:
        grid = (n_pass,) + grid
        at = lambda p, s, w, *_sel: (s, 0, w)
    in_specs = [pl.BlockSpec((1, x.shape[1], bw), at) for x in arrays]
    out_specs = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [
        pltpu.VMEM(table, jnp.int32),
        pltpu.VMEM((_n_upper(digits, fi), 8 * nv, _LANES), jnp.uint32),
        pltpu.VMEM((8 * nv, _LANES), jnp.uint32),
        pltpu.VMEM((sum(x.shape[1] for x in arrays), 8 * nv, _LANES),
                   jnp.uint32)]
    if sel is None:
        spec = dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                    scratch_shapes=scratch)
    else:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch))
        arrays = [jnp.asarray(sel, jnp.int32)] + arrays
    out = pl.pallas_call(
        _gb_packed_kernel(digits, depth, signed, k, nv, fi, rp, n_pass,
                          sel_len),
        out_shape=jax.ShapeDtypeStruct(
            table if n_pass == 1 else (n_pass,) + table, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PACKED_VMEM_LIMIT),
        # one factory, two schedules, a name each on the device plane:
        # a reader of "groupby_fused_sum" times one walk over the
        # operands, as it did before there were passes
        name="groupby_fused_sum" if n_pass == 1 else "groupby_fused_passes",
        interpret=_interpret(),
        **spec,
    )(*arrays)
    sums = jnp.sum(out, axis=(-2, -1)).reshape(-1, k)
    live = np.flatnonzero(codes.ravel() >= 0)
    if live.size < codes.size:
        sums = sums[live]
    return jnp.zeros((k, n_codes), jnp.int32).at[
        :, codes.ravel()[live]].set(sums.T)


def _gb_fused_onehot_kernel(cb: int, depth: int, signed: bool, k: int,
                            g_pad: int, bw: int, bc: int, minmax: bool):
    """One-hot body factory.  Per (shard, word-block) grid step the
    32 bit positions are processed in chunks of `bc`; each chunk is
    one flattened (bc*bw,) column axis shared by the int8 payload
    matmul and (when requested) the Min/Max masked reductions."""

    def kernel(cp_ref, va_ref, *refs):
        pl_ref = refs[0] if depth else None
        i = 1 if depth else 0
        out_ref = refs[i]
        mm_ref = refs[i + 1] if minmax else None
        s, wi = pl.program_id(0), pl.program_id(1)

        @pl.when((s == 0) & (wi == 0))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
            if minmax:
                row = jax.lax.broadcasted_iota(jnp.int32, (4, g_pad), 0)
                mm_ref[...] = jnp.where(row % 2 == 0, -1, 1 << depth)

        iota_g = jax.lax.broadcasted_iota(jnp.int32, (1, g_pad), 1)
        big = 1 << depth

        def chunk(c, carry):
            # rolled, so Mosaic sizes its VMEM stack for one chunk's
            # temporaries, not for all of them
            sh = (jax.lax.broadcasted_iota(jnp.uint32, (bc, 1), 0)
                  + (c * bc).astype(jnp.uint32))

            def bits(w):
                # (bw,) uint32 -> (bc*bw,) 0/1 int32 — positions
                # [c*bc, (c+1)*bc) of every word, flattened bit-major
                return ((w[None, :] >> sh)
                        & jnp.uint32(1)).astype(jnp.int32).reshape(-1)

            va = bits(va_ref[0, 0])
            code = jnp.zeros_like(va)
            for b in range(cb):
                code = code | (bits(cp_ref[0, b]) << b)
            ex = sg = None
            mag = []
            if depth:
                ex = bits(pl_ref[0, 0]) * va
                sg = bits(pl_ref[0, 1])
                mag = [bits(pl_ref[0, 2 + p]) for p in range(depth)]
            rows = _gc_payload_rows(va, ex, sg, mag, depth, signed)
            payload = jnp.stack(rows).astype(jnp.int8)   # (K, bc*bw)
            # invalid columns carry all-zero payload (every row has a
            # `va` factor), so their arbitrary code contributes 0
            hit = code[:, None] == iota_g                # (bc*bw, G)
            out_ref[...] += jnp.dot(payload, hit.astype(jnp.int8),
                                    preferred_element_type=jnp.int32)
            if minmax:
                # per-group Min/Max of the column magnitudes on the
                # VPU: one masked max/min over the chunk's one-hot
                # per side.  Invalid columns have ex == 0 and so
                # carry the identity on every side.
                val = mag[0]
                for p in range(1, depth):
                    val = val | (mag[p] << p)
                sides = [(0, ex * (1 - sg) if signed else ex)]
                if signed:
                    sides.append((2, ex * sg))
                for r, mask in sides:
                    hi = jnp.where(mask == 1, val, -1)[:, None]
                    lo = jnp.where(mask == 1, val, big)[:, None]
                    mm_ref[r:r + 1, :] = jnp.maximum(
                        mm_ref[r:r + 1, :],
                        jnp.max(jnp.where(hit, hi, -1), axis=0,
                                keepdims=True))
                    mm_ref[r + 1:r + 2, :] = jnp.minimum(
                        mm_ref[r + 1:r + 2, :],
                        jnp.min(jnp.where(hit, lo, big), axis=0,
                                keepdims=True))
            return carry

        jax.lax.fori_loop(0, 32 // bc, chunk, 0)
    return kernel


def _gb_fused_onehot(code_planes, valid, planes, depth: int,
                     signed: bool, minmax: bool, n_codes: int):
    """groupby_fused's one-hot body: returns the dense (K, G) table
    and the (4, G) Min/Max table (or None)."""
    s_dim, cb, w_dim = code_planes.shape
    k = _payload_rows(depth, signed)
    g_pad = max(-(-int(n_codes) // 128) * 128, 128)
    # word block + bit-chunk sized so the per-chunk int8 one-hot
    # (bc*bw, G) stays ~2 MB (Min/Max selects over it in int32, so a
    # quarter of the columns); bc divides 32 so chunks tile the word
    cols = (1 << 19) if minmax else (1 << 21)
    bw = max(128, min(2048, w_dim))
    if minmax:
        bw = max(128, min(bw, cols // g_pad))
    bc = max(1, min(32, cols // (bw * g_pad)))
    while 32 % bc:
        bc -= 1
    code_planes = _pad_axis(code_planes, 2, bw)
    valid, valid_spec = _valid_operand(valid, bw)
    arrays = [code_planes, valid]
    in_specs = [pl.BlockSpec((1, cb, bw), lambda s, w: (s, 0, w)),
                valid_spec]
    if depth:
        planes = _pad_axis(planes, 2, bw)
        arrays.append(planes)
        in_specs.append(
            pl.BlockSpec((1, 2 + depth, bw), lambda s, w: (s, 0, w)))
    wpad = code_planes.shape[2]
    fixed = lambda s, w: (0, 0)
    out_specs = [pl.BlockSpec((k, g_pad), fixed)]
    out_shape = [jax.ShapeDtypeStruct((k, g_pad), jnp.int32)]
    if minmax:
        out_specs.append(pl.BlockSpec((4, g_pad), fixed))
        out_shape.append(jax.ShapeDtypeStruct((4, g_pad), jnp.int32))
    out = pl.pallas_call(
        _gb_fused_onehot_kernel(cb, depth, signed, k, g_pad, bw, bc,
                                minmax),
        grid=(s_dim, wpad // bw),
        in_specs=in_specs,
        out_specs=out_specs if minmax else out_specs[0],
        out_shape=out_shape if minmax else out_shape[0],
        name=("groupby_fused_minmax" if minmax
              else "groupby_fused_sum"),
        interpret=_interpret(),
    )(*arrays)
    if not minmax:
        return out[:, :n_codes], None
    return out[0][:, :n_codes], out[1][:, :n_codes]


def groupby_fused(code_planes, valid, planes=None, n_codes: int = 1,
                  signed: bool = True, minmax: bool = False,
                  digits=None, sel=None):
    """Fused single-pass GroupBy histogram on packed words.

    Same contract as :func:`groupby_codes_xla` (bit-exact against it
    and against the host twins — the property suite cross-checks all
    of them).  Returns (counts, nn, pos, neg)
    and, with ``minmax=True`` (requires planes), additionally a
    (4, G) int32 table [max_mag_pos, min_mag_pos, max_mag_neg,
    min_mag_neg] with identities (-1 / 1<<depth) marking empty sides —
    combine with :func:`minmax_from_table`.

    ``digits`` is the fields' digit layout, ((bits, rows), ...) in
    code-plane order (stacked._code_space with each field's row
    count): only codes whose every digit is below its field's row
    count are visited, the others stay 0 — which is what they hold
    anyway when `valid` is the AND of the field unions.  Without it
    every code of the CB planes is live (bsi_value_hist:
    dense_digits).

    ``sel`` (:func:`selection`) walks chosen rows only: ``digits``
    then gives each field's bits and the rows selected, a power of
    two, and the table is over the selection's codes
    (:func:`selected_digits`; ``n_codes`` their product), a row's
    position in its field's selection being its digit.  The packed
    body alone walks a selection.

    Schedule: grid (S, W/BW) with NO combo axis — every code plane,
    valid word, and BSI plane word streams through VMEM exactly once
    and the accumulators stay VMEM-resident for the whole walk.  Per
    group the kernel ANDs the group's mask with the payload planes
    and popcounts, 32 columns per word operation (the packed body;
    in several walks where one walk's accumulators would not fit);
    shapes of which not one row of the widest field fits at the full
    block width, and ``minmax``, take the one-hot body instead (see
    the block comment above, and fused_plan).
    """
    s_dim, cb, w_dim = code_planes.shape
    depth = 0 if planes is None else planes.shape[1] - 2
    assert not (minmax and depth == 0), "minmax requires BSI planes"
    if digits is None:
        digits = dense_digits(cb)
    digits = tuple((int(b), int(r)) for b, r in digits)
    assert sum(b for b, _ in digits) == cb, (digits, cb)
    if cb == 0:                        # all fields single-row: code 0
        code_planes = jnp.zeros((s_dim, 1, w_dim), dtype=jnp.uint32)
    if sel is not None or fused_body(digits, depth, signed,
                                     minmax) == "packed":
        hist, mm = _gb_fused_packed(code_planes, valid, planes, digits,
                                    depth, signed, n_codes, sel), None
    else:
        hist, mm = _gb_fused_onehot(code_planes, valid, planes, depth,
                                    signed, minmax, n_codes)
    counts = hist[0]
    if depth == 0:
        return counts, None, None, None
    nn = hist[1]
    pos = hist[2:2 + depth].T                          # (G, depth)
    neg = hist[2 + depth:].T if signed else jnp.zeros_like(pos)
    if not minmax:
        return counts, nn, pos, neg
    return counts, nn, pos, neg, mm


def minmax_from_table(mm, depth: int, op: str):
    """Host combiner for the (4, G) Min/Max magnitude table (fused
    kernel or XLA reference): per group, ``max = max_mag_pos`` when
    any non-negative member exists else ``-min_mag_neg``; ``min =
    -max_mag_neg`` when any negative member exists else
    ``min_mag_pos``.  Returns (values (G,) int64, has (G,) bool)."""
    mm = np.asarray(mm, dtype=np.int64)
    big = 1 << depth
    mxp, mnp_, mxn, mnn = mm[0], mm[1], mm[2], mm[3]
    if op == "max":
        vals = np.where(mxp >= 0, mxp, -mnn)
        has = (mxp >= 0) | (mnn < big)
    else:
        vals = np.where(mxn >= 0, -mxn, mnp_)
        has = (mxn >= 0) | (mnp_ < big)
    return vals, has


def bsi_value_hist(planes, filter_words=None, signed: bool = True,
                   use_kernel: bool = True, gb=None):
    """Fused per-VALUE histogram over a BSI plane stack — the
    Range/Distinct byproduct of the single-pass tile walk.

    planes: (S, 2+depth, W) uint32, filter_words: (S, W) or None.
    Treats the magnitude planes plus the SIGN plane as a group code
    (sign is the top code bit), so one run of the fused GroupBy kernel
    yields counts per signed value: returns (pos (2^depth,) int32,
    neg (2^depth,) int32) — pos[v] = columns with value +v, neg[v] =
    columns with value -v.  Derive Distinct (codes with count > 0),
    Min/Max (extreme nonzero codes), and Range counts
    (:func:`range_count_from_hist`) without decoding a single column.

    This function is the ONE owner of the planes-to-code layout
    (sign plane as the top code bit, exists AND filter as validity);
    `gb` overrides the histogram arm (any groupby_* callable) so the
    executor's arm selection reuses the same transform.  The host
    twin (executor/stacked.py's native arm) mirrors this layout —
    keep them in lockstep.
    """
    depth = planes.shape[1] - 2
    ex = planes[:, 0]
    valid = (ex if filter_words is None
             else jnp.bitwise_and(ex, filter_words))
    cp = jnp.concatenate(
        [planes[:, 2:], planes[:, 1:2]], axis=1)     # (S, depth+1, W)
    n_codes = 1 << (depth + 1)
    if gb is None:
        gb = groupby_fused if use_kernel else groupby_codes_xla
    counts, _, _, _ = gb(cp, valid, None, n_codes, signed)
    return counts[: 1 << depth], counts[1 << depth:]


def range_count_from_hist(pos, neg, lo: int, hi: int) -> int:
    """Columns whose value lies in [lo, hi] — exact, from the fused
    value histogram (pos/neg magnitude counts)."""
    pos = np.asarray(pos, dtype=np.int64)
    neg = np.asarray(neg, dtype=np.int64)
    total = 0
    if hi >= 0:
        total += int(pos[max(lo, 0):hi + 1].sum())
    if lo < 0:
        nlo, nhi = max(-hi, 1), -lo           # magnitudes of negatives
        if nlo <= nhi:
            total += int(neg[nlo:nhi + 1].sum())
    return total


def distinct_from_hist(pos, neg) -> list[int]:
    """Sorted distinct signed values present in the fused value
    histogram.  A -0 cannot occur (the encoder signs only v < 0)."""
    pos = np.asarray(pos)
    neg = np.asarray(neg)
    vals = [-int(v) for v in np.nonzero(neg)[0][::-1] if v > 0]
    vals += [int(v) for v in np.nonzero(pos)[0]]
    return vals


# ---------------------------------------------------------------------------
# HBM traffic models — the roofline plane's bytes-touched source
# ---------------------------------------------------------------------------
#
# pilosa_device_bandwidth_fraction{op=groupby} is only honest if each
# dispatch notes the bytes ITS schedule actually streams: the fused
# single-pass kernel reads every tile once, while the per-combo arms
# re-read stack rows per referencing combo and the XLA scan
# re-materializes gathered combo masks per payload pass.  Crediting
# the one-pass path with the per-combo arms' re-read traffic (or vice
# versa) would inflate (deflate) the fraction.  These models are the
# single source the executor arms note from (ISSUE 11 satellite).


def groupby_onepass_hbm_bytes(n_shards: int, width_words: int,
                              code_bits: int, depth: int = 0,
                              has_filter: bool = False) -> int:
    """Single-pass tile walk: (code planes + valid plane) + BSI stack
    + filter words each cross VMEM exactly once — independent of combo
    count, and counted WITHOUT mesh padding rows."""
    per_shard = (code_bits + 1) + ((2 + depth) if depth else 0) \
        + (1 if has_filter else 0)
    return 4 * n_shards * width_words * per_shard


def groupby_percombo_hbm_bytes(n_shards: int, width_words: int,
                               n_combos: int, nf: int,
                               depth: int = 0) -> int:
    """groupby_sum kernel schedule: each referenced stack row is read
    once per referencing combo (combos innermost in the grid), the
    plane block once per (shard, word) tile — i.e. ONCE total."""
    return 4 * n_shards * width_words * (
        n_combos * nf + ((2 + depth) if depth else 0))


def groupby_scan_hbm_bytes(n_shards: int, width_words: int,
                           n_combos: int, nf: int, depth: int = 0,
                           signed: bool = True,
                           has_filter: bool = False) -> int:
    """XLA per-combo scan traffic: gathered (C, S, W) combo masks
    materialize and are re-read once per payload pass (exists mask +
    one sign-split mask read per magnitude plane) — the multi-pass
    traffic the one-pass kernels exist to remove."""
    w = 4 * n_shards * width_words
    b = n_combos * nf * w + (w if has_filter else 0)
    if depth:
        b += (2 + depth) * w
        b += n_combos * w * (1 + (2 if signed else 1) * depth)
    return b


__all__ = [
    "groupby_sum",
    "groupby_codes_xla",
    "groupby_fused",
    "minmax_from_table",
    "bsi_value_hist",
    "range_count_from_hist",
    "distinct_from_hist",
    "groupby_onepass_hbm_bytes",
    "groupby_percombo_hbm_bytes",
    "groupby_scan_hbm_bytes",
]
