"""A reader for the part of PQL that the traffic files use.

The reference has to know what a query asks without the program's
parser.  ``parse`` turns one call into a ``Call``: positional
arguments (calls or bare names), keyword arguments (integers, names,
double-quoted strings or calls) and conditions (``age > 40``).
Anything else is an error, so a traffic file that leaves this subset
fails before any load.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_TOKEN = re.compile(
    r'\s*([A-Za-z_][A-Za-z0-9_]*|-?\d+|"[^"\\]*"|>=|<=|==|!=|[(),=<>])')
OPS = (">", "<", ">=", "<=", "==", "!=")


class PqlError(ValueError):
    pass


@dataclass
class Call:
    name: str
    args: list = field(default_factory=list)        # Call | str
    kwargs: dict = field(default_factory=dict)      # str -> int | str | Call
    conds: list = field(default_factory=list)       # (field, op, int)


def _tokens(text: str) -> list[str]:
    out, pos = [], 0
    text = text.rstrip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            raise PqlError(f"cannot read {text[pos:pos + 20]!r} in {text!r}")
        out.append(m.group(1))
        pos = m.end()
    return out


def parse(text: str) -> Call:
    toks = _tokens(text)
    call, pos = _call(toks, 0, text)
    if pos != len(toks):
        raise PqlError(f"more than one call in {text!r}")
    return call


def _value(toks, pos, text):
    tok = toks[pos]
    if re.fullmatch(r"-?\d+", tok):
        return int(tok), pos + 1
    if tok[0] == '"':
        return tok[1:-1], pos + 1
    if pos + 1 < len(toks) and toks[pos + 1] == "(":
        return _call(toks, pos, text)
    return tok, pos + 1


def _call(toks, pos, text):
    name = toks[pos]
    if pos + 1 >= len(toks) or toks[pos + 1] != "(":
        raise PqlError(f"{name!r} is not a call in {text!r}")
    call = Call(name)
    pos += 2
    while toks[pos] != ")":
        nxt = toks[pos + 1]
        if nxt == "=":
            call.kwargs[toks[pos]], pos = _value(toks, pos + 2, text)
        elif nxt in OPS:
            call.conds.append((toks[pos], nxt, int(toks[pos + 2])))
            pos += 3
        elif toks[pos][0] == '"':
            raise PqlError(f"a string as an argument in {text!r}")
        else:
            arg, pos = _value(toks, pos, text)
            call.args.append(arg)
        if toks[pos] == ",":
            pos += 1
        elif toks[pos] != ")":
            raise PqlError(f"expected , or ) at {toks[pos]!r} in {text!r}")
    return call, pos + 1
