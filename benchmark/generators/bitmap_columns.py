"""Columns first: one shard's packed rows and its reference tables.

A configuration's ``params`` name the fields:

- ``plain``: set fields of 50%-dense uniformly random rows.  A field
  with ``"joint": true`` has one row and takes one bit of the joint
  key; at most one other plain field (the *side* field, e.g. ``t``
  with 8 rows) gets a table per row instead.
- ``categorical``: one digit per column, ``rows`` rows in ``bits``
  bits, uniform over ``1 << bits`` (a digit >= rows is "no value") or
  drawn with ``probs`` (one per row).
- ``bsi``: one unsigned integer field of ``depth`` bits, uniform.

The reference tables are additive over shards: one joint histogram
over (joint plain bits, categorical digits, BSI value) and the same
restricted to each row of the side field.  Every ``Count``, ``Sum``,
``Min``/``Max``, ``TopN`` and filtered ``GroupBy`` whose bitmap
argument is set algebra over those rows, with at most one conjunct on
the side field, has a closed-form answer in them.  ``Reference``
computes it with numpy and imports nothing of the program.

Copied in idea from ``chip_smoke.py:make_shard``/``partial`` (PR 21),
whose per-template partials this one table replaces.
"""

from __future__ import annotations

import itertools

import numpy as np

SHARD_WIDTH = 1 << 20          # the program's default; stated, not imported
WORDS = SHARD_WIDTH // 32


class Unanswerable(ValueError):
    """The reference tables hold no closed form for this query."""


class Layout:
    """Where each field sits in the joint key."""

    def __init__(self, params: dict):
        self.joint = {}         # plain field -> (bit, row id)
        self.side = None        # (field, [row ids])
        bit = 0
        for f in params["plain"]:
            if f.get("joint"):
                if len(f["rows"]) != 1:
                    raise ValueError(f"joint field {f['name']} needs one row")
                self.joint[f["name"]] = (bit, f["rows"][0])
                bit += 1
            elif self.side is None:
                self.side = (f["name"], list(f["rows"]))
            else:
                raise ValueError("only one plain field can be the side field")
        self.cats = {}          # categorical field -> (rows, bits, shift)
        for c in params["categorical"]:
            self.cats[c["name"]] = (c["rows"], c["bits"], bit)
            bit += c["bits"]
        self.key_bits = bit
        self.bsi = params["bsi"]["name"]
        self.depth = params["bsi"]["depth"]
        self.bins = 1 << (self.key_bits + self.depth)


def _pack(bits) -> np.ndarray:
    return np.packbits(bits, bitorder="little").view(np.uint32)


def _unpack(words) -> np.ndarray:
    return np.unpackbits(words.view(np.uint8), bitorder="little")


def make_shard(params: dict, seed: int, shard: int):
    """({field: {row id: packed words}}, additive reference tables)."""
    lay = Layout(params)
    rng = np.random.default_rng([seed, shard])
    rows = {f["name"]: {r: rng.integers(0, 1 << 32, size=WORDS,
                                        dtype=np.uint32) for r in f["rows"]}
            for f in params["plain"]}
    key = np.zeros(SHARD_WIDTH, dtype=np.int32)
    for name, (bit, row) in lay.joint.items():
        key |= _unpack(rows[name][row]).astype(np.int32) << bit
    for c in params["categorical"]:
        n_rows, bits, shift = lay.cats[c["name"]]
        if c.get("probs"):
            d = rng.choice(n_rows, size=SHARD_WIDTH,
                           p=np.asarray(c["probs"], dtype=np.float64)
                           ).astype(np.uint8)
        else:
            d = rng.integers(0, 1 << bits, size=SHARD_WIDTH, dtype=np.uint8)
        rows[c["name"]] = {r + c.get("row_base", 0): _pack(d == r)
                           for r in range(n_rows)}
        key |= d.astype(np.int32) << shift
    value = rng.integers(0, 1 << lay.depth, size=SHARD_WIDTH, dtype=np.uint8)
    every = np.full(WORDS, 0xFFFFFFFF, dtype=np.uint32)
    # BSI rows: 0 not-null, 1 sign, 2 + p the bit planes
    rows[lay.bsi] = {0: every, **{2 + p: _pack((value >> p) & 1)
                                  for p in range(lay.depth)}}
    full = (key << lay.depth) | value
    tables = {"hist": np.bincount(full, minlength=lay.bins).astype(np.int32)}
    if lay.side is not None:
        name, ids = lay.side
        tables["side"] = np.stack([
            np.bincount(full[_unpack(rows[name][r]).astype(bool)],
                        minlength=lay.bins).astype(np.int32) for r in ids])
    return rows, tables


def add_tables(total, part):
    if total is None:
        return {k: v.astype(np.int64) for k, v in part.items()}
    for k, v in part.items():
        total[k] += v
    return total


def drop_columns(tables: dict, part: dict) -> dict:
    """The tables without one shard's part (the control's stale read)."""
    return {k: v - part[k] for k, v in tables.items()}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

_CMP = {">": np.greater, "<": np.less, ">=": np.greater_equal,
        "<=": np.less_equal, "==": np.equal, "!=": np.not_equal}


class Reference:
    """Closed-form answers from the summed tables.

    ``answer(call)`` takes a ``harness.pql.Call`` and returns the
    canonical form ``harness.check.canonical`` gives a response:
    ``Count`` an int; ``Sum``/``Min``/``Max`` ``(value, count)``;
    ``TopN`` ``[(row id, count), ...]``; ``GroupBy``
    ``{(row ids): (count, aggregate or None)}``.
    """

    def __init__(self, params: dict, tables: dict):
        self.lay = lay = Layout(params)
        j, v = 1 << lay.key_bits, 1 << lay.depth
        self.hist = tables["hist"].reshape(j, v)
        self.side = (tables["side"].reshape(-1, j, v)
                     if "side" in tables else None)
        self.keys = np.arange(j)
        self.vals = np.arange(v)
        self.row_base = {c["name"]: c.get("row_base", 0)
                         for c in params["categorical"]}

    # -- bitmap expressions: (mask broadcastable to (J, V), side row) ----

    def _digits(self, name: str) -> np.ndarray:
        _rows, bits, shift = self.lay.cats[name]
        return (self.keys >> shift) & ((1 << bits) - 1)

    def _row(self, call):
        lay = self.lay
        empty = np.zeros((len(self.keys), 1), dtype=bool)
        if call.conds and not call.kwargs and not call.args:
            mask = np.ones((1, len(self.vals)), dtype=bool)
            for name, op, k in call.conds:
                if name != lay.bsi:
                    raise Unanswerable(f"condition on {name}")
                mask &= _CMP[op](self.vals, k)[None, :]
            return mask, None
        if len(call.kwargs) != 1 or call.args or call.conds:
            raise Unanswerable(f"Row with {call.kwargs} {call.args}")
        (name, row), = call.kwargs.items()
        if name in lay.joint:
            bit, has = lay.joint[name]
            if row != has:
                return empty, None
            return (((self.keys >> bit) & 1) == 1)[:, None], None
        if name in lay.cats:
            digit = row - self.row_base[name]
            if not 0 <= digit < lay.cats[name][0]:
                return empty, None
            return (self._digits(name) == digit)[:, None], None
        if lay.side is not None and name == lay.side[0]:
            if row not in lay.side[1]:
                return empty, None
            return np.ones((1, 1), dtype=bool), lay.side[1].index(row)
        raise Unanswerable(f"field {name}")

    def _bitmap(self, call):
        if call.name == "Row":
            return self._row(call)
        if call.name not in ("Intersect", "Union", "Xor", "Difference"):
            raise Unanswerable(f"bitmap call {call.name}")
        parts = [self._bitmap(a) for a in call.args]
        if not parts or call.kwargs or call.conds:
            raise Unanswerable(f"{call.name} with {call.kwargs}")
        sides = {s for _m, s in parts if s is not None}
        if call.name == "Intersect":
            if len(sides) > 1:
                raise Unanswerable("two rows of the side field intersected")
            mask = parts[0][0]
            for m, _s in parts[1:]:
                mask = mask & m
            return mask, (sides.pop() if sides else None)
        if sides:
            raise Unanswerable(f"{call.name} over the side field")
        mask = parts[0][0]
        for m, _s in parts[1:]:
            mask = (mask | m if call.name == "Union" else
                    mask ^ m if call.name == "Xor" else mask & ~m)
        return mask, None

    def _weights(self, call):
        """The (J, V) table of column counts that a bitmap selects;
        `call` None selects every column."""
        if call is None:
            return self.hist
        mask, side = self._bitmap(call)
        table = self.hist if side is None else self.side[side]
        return table * mask

    # -- answers ----------------------------------------------------------

    def answer(self, call):
        how = getattr(self, f"_answer_{call.name.lower()}", None)
        if how is None:
            raise Unanswerable(f"call {call.name}")
        return how(call)

    def _one_filter(self, call, skip: int = 0):
        args = call.args[skip:]
        if len(args) > 1:
            raise Unanswerable(f"{call.name} with {len(args)} bitmaps")
        return args[0] if args else None

    def _answer_count(self, call):
        return int(self._weights(self._one_filter(call)).sum())

    def _valcount(self, call, how: str):
        if call.kwargs.get("field") != self.lay.bsi:
            raise Unanswerable(f"{call.name} of {call.kwargs}")
        h = self._weights(self._one_filter(call)).sum(axis=0)
        return self._aggregate(h, how)

    def _aggregate(self, h, how: str):
        if how == "sum":
            return int((h * self.vals).sum()), int(h.sum())
        if not h.any():
            raise Unanswerable("Min/Max of no column")
        v = int(self.vals[h > 0].min() if how == "min"
                else self.vals[h > 0].max())
        return v, int(h[v])

    def _answer_sum(self, call):
        return self._valcount(call, "sum")

    def _answer_min(self, call):
        return self._valcount(call, "min")

    def _answer_max(self, call):
        return self._valcount(call, "max")

    def _answer_topn(self, call):
        name = call.args[0]
        filt = self._one_filter(call, skip=1)
        lay = self.lay
        if lay.side is not None and name == lay.side[0]:
            mask, side = (self._bitmap(filt) if filt is not None
                          else (np.ones((1, 1), dtype=bool), None))
            if side is not None:
                raise Unanswerable("TopN of the side field filtered by it")
            counts = [(r, int((self.side[i] * mask).sum()))
                      for i, r in enumerate(lay.side[1])]
        elif name in lay.cats:
            per_key = self._weights(filt).sum(axis=1)
            digits = self._digits(name)
            counts = [(d + self.row_base[name], int(per_key[digits == d].sum()))
                      for d in range(lay.cats[name][0])]
        else:
            raise Unanswerable(f"TopN of {name}")
        counts = sorted((c for c in counts if c[1] > 0),
                        key=lambda c: (-c[1], c[0]))
        n = call.kwargs.get("n")
        return counts[:n] if n else counts

    def _answer_groupby(self, call):
        fields = []
        for a in call.args:
            if getattr(a, "name", None) != "Rows" or len(a.args) != 1 \
                    or a.args[0] not in self.lay.cats:
                raise Unanswerable(f"GroupBy over {a}")
            fields.append(a.args[0])
        how = None
        agg = call.kwargs.get("aggregate")
        if agg is not None:
            how = agg.name.lower()
            if how not in ("sum", "min", "max") \
                    or agg.kwargs.get("field") != self.lay.bsi:
                raise Unanswerable(f"aggregate {agg}")
        extra = set(call.kwargs) - {"filter", "aggregate"}
        if extra:
            raise Unanswerable(f"GroupBy with {extra}")
        w = self._weights(call.kwargs.get("filter"))
        digits = [self._digits(f) for f in fields]
        out = {}
        for combo in itertools.product(
                *(range(self.lay.cats[f][0]) for f in fields)):
            sel = np.ones(len(self.keys), dtype=bool)
            for d, r in zip(digits, combo):
                sel &= d == r
            h = w[sel].sum(axis=0)
            count = int(h.sum())
            if not count:
                continue
            ids = tuple(r + self.row_base[f] for f, r in zip(fields, combo))
            out[ids] = (count, self._aggregate(h, how)[0] if how else None)
        return out
