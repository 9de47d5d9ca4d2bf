"""The one traffic generator: a traffic file's parameters and a seed
give every query string of a run, per client.

A traffic file (``benchmark/traffic/<mix>.json``) holds

- ``loop``: ``"closed"`` (each client sends its next request when the
  last one is answered), ``clients`` and ``start_stagger_s`` (client i
  starts that many seconds times i into the window);
- ``templates``: ``{"name", "count", "pql"}``; a client's schedule is
  made of blocks that hold each template exactly ``count`` times, in
  an order drawn from the seed, so every seed sends the same mix;
- ``params``: what a ``{placeholder}`` in a template draws, each
  occurrence on its own: ``{"uniform": [lo, hi]}`` an integer,
  ``{"choice": [[weight, text], ...]}`` a text that may hold further
  placeholders, ``{"zipf": {"s": s, "values": [...]}}`` a value by a
  Zipf law over a ranking of the values that the seed fixes;
- ``distinct``: ``true`` where no string may be sent twice in a run
  (see ``plans``), so that neither the result cache nor the batcher's
  promotion of recurring queries is what a run measures;
- ``max_requests_per_client_per_s``: how long a schedule is made for a
  window of so many seconds (a client that runs out stops the run);
- ``warmup``: ``sequential`` and ``concurrent_requests`` (after the
  sequential askings, run the closed loop itself until each client
  has sent so many requests of the warm-up stream).
  ``sequential`` is ``true`` (ask every template once with every
  option of the ``choice`` params it names, alone) or a list that
  names each program shape the mix reaches: ``{"t": template,
  "fixed": {param: text}}`` (the other draws from the warm-up stream)
  or ``{"t": template, "q": pql}`` (a literal).

Stream 0 is the window, stream 1 the warm-up: the same templates with
other draws.  Pure functions of (file, seed): no clock, no program.
"""

from __future__ import annotations

import itertools
import math
import random
import re

_SLOT = re.compile(r"\{(\w+)\}")
WINDOW, WARMUP = 0, 1


class Draws:
    def __init__(self, traffic: dict, seed: int, stream: int, client: int):
        self.params = traffic.get("params", {})
        self.rnd = random.Random(f"{seed}/{stream}/{client}")
        self.ranked = {}
        for name, spec in self.params.items():
            if "zipf" in spec:
                values = list(spec["zipf"]["values"])
                # the ranking belongs to the seed, not to the stream
                random.Random(f"{seed}/rank/{name}").shuffle(values)
                s = spec["zipf"]["s"]
                cum = list(itertools.accumulate(
                    1.0 / (r + 1) ** s for r in range(len(values))))
                self.ranked[name] = (values, cum)

    def fill(self, text: str, fixed: dict | None = None) -> str:
        return _SLOT.sub(lambda m: self.draw(m.group(1), fixed), text)

    def draw(self, name: str, fixed: dict | None = None) -> str:
        if fixed and name in fixed:
            return self.fill(fixed[name], fixed)
        spec = self.params[name]
        if "uniform" in spec:
            lo, hi = spec["uniform"]
            return str(self.rnd.randint(lo, hi))
        if "choice" in spec:
            weights, texts = zip(*spec["choice"])
            return self.fill(self.rnd.choices(texts, weights=weights)[0],
                             fixed)
        values, cum = self.ranked[name]
        return self.rnd.choices(values, cum_weights=cum)[0]


def requests_per_client(traffic: dict, seconds: float) -> int:
    block = sum(t["count"] for t in traffic["templates"])
    n = math.ceil(seconds * traffic["max_requests_per_client_per_s"])
    return block * max(1, math.ceil(n / block))


def plans(traffic: dict, seed: int, stream: int, n: int) -> list:
    """The first `n` requests of every client: [[{"t": template,
    "q": pql}]].  Blocks are dealt to the clients in turn, so a longer
    schedule only appends.  With ``"distinct": true`` in the traffic
    file no string is sent twice in a run (nor one that the
    sequential warm-up asked), as long as its parameters leave a new
    one to draw: a template without parameters repeats."""
    clients = range(traffic["clients"])
    draws = [Draws(traffic, seed, stream, c) for c in clients]
    block = [t for t in traffic["templates"] for _ in range(t["count"])]
    seen = None
    if traffic.get("distinct"):
        seen = {w["q"] for w in warm_sequential(traffic, seed)}
    out = [[] for _ in clients]
    while len(out[0]) < n:
        for c in clients:
            draws[c].rnd.shuffle(block)
            for t in block:
                q = draws[c].fill(t["pql"])
                for _retry in range(32):
                    if seen is None or q not in seen:
                        break
                    q = draws[c].fill(t["pql"])
                if seen is not None:
                    seen.add(q)
                out[c].append({"t": t["name"], "q": q})
    return [o[:n] for o in out]


def client_schedule(traffic: dict, seed: int, stream: int, client: int,
                    n: int) -> list[dict]:
    return plans(traffic, seed, stream, n)[client]


def build(traffic: dict, seed: int, stream: int, seconds: float) -> list:
    return plans(traffic, seed, stream, requests_per_client(traffic, seconds))


def _choice_slots(traffic: dict, text: str, seen=()) -> list[str]:
    """The choice params a template reaches, through nested texts."""
    out = []
    for name in _SLOT.findall(text):
        spec = traffic.get("params", {}).get(name, {})
        if "choice" in spec and name not in seen and name not in out:
            out.append(name)
            for _w, sub in spec["choice"]:
                out += [n for n in _choice_slots(traffic, sub,
                                                 (*seen, *out))
                        if n not in out]
    return out


def warm_sequential(traffic: dict, seed: int) -> list[dict]:
    """One asking per template and per option of its choice params
    (the program shapes a mix can reach), other draws from the
    warm-up stream."""
    draws = Draws(traffic, seed, WARMUP, -1)
    out = []
    listed = traffic["warmup"].get("sequential")
    if isinstance(listed, list):
        by_name = {t["name"]: t for t in traffic["templates"]}
        for item in listed:
            q = draws.fill(item.get("q") or by_name[item["t"]]["pql"],
                           item.get("fixed"))
            out.append({"t": item["t"], "q": q})
        return out
    for t in traffic["templates"]:
        slots = _choice_slots(traffic, t["pql"])
        options = [[text for _w, text in traffic["params"][s]["choice"]]
                   for s in slots]
        for combo in itertools.product(*options):
            out.append({"t": t["name"],
                        "q": draws.fill(t["pql"], dict(zip(slots, combo)))})
    return out
