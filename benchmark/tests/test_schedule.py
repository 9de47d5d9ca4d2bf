import collections
import glob
import json
import os

import pytest
from harness import pql, schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))


def _mix(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_schedule_is_a_pure_function_of_the_seed(path):
    mix = _mix(path)
    a = schedule.build(mix, 2147483659, schedule.WINDOW, 3.0)
    b = schedule.build(mix, 2147483659, schedule.WINDOW, 3.0)
    c = schedule.build(mix, 2147483660, schedule.WINDOW, 3.0)
    warm = schedule.build(mix, 2147483659, schedule.WARMUP, 3.0)
    assert a == b
    assert a != c and a != warm
    assert len(a) == mix["clients"]
    # a longer window only appends: the first requests stay the same
    longer = schedule.build(mix, 2147483659, schedule.WINDOW, 9.0)
    assert all(x == y[:len(x)] for x, y in zip(a, longer))


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_seed_sends_the_same_mix(path):
    mix = _mix(path)
    block = sum(t["count"] for t in mix["templates"])
    want = {t["name"]: t["count"] for t in mix["templates"]}
    for seed in (1, 2):
        plan = schedule.client_schedule(mix, seed, schedule.WINDOW, 0,
                                        3 * block)
        for lo in range(0, len(plan), block):
            got = collections.Counter(r["t"] for r in plan[lo:lo + block])
            assert got == want


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_query_is_in_the_reference_subset(path):
    mix = _mix(path)
    for client in schedule.build(mix, 5, schedule.WINDOW, 2.0):
        for item in client[:200]:
            assert "{" not in item["q"]
            pql.parse(item["q"])


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_warm_up_reaches_every_template_and_choice(path):
    mix = _mix(path)
    warm = schedule.warm_sequential(mix, 5)
    assert {w["t"] for w in warm} == {t["name"] for t in mix["templates"]}
    assert all("{" not in w["q"] for w in warm)
    for w in warm:
        pql.parse(w["q"])
    for name, spec in mix["params"].items():
        if "choice" not in spec or isinstance(
                mix["warmup"]["sequential"], list):
            continue
        for t in mix["templates"]:
            if "{" + name + "}" not in t["pql"]:
                continue
            mine = [w["q"] for w in warm if w["t"] == t["name"]]
            assert len(mine) >= len(spec["choice"])


def test_zipf_ranking_belongs_to_the_seed_not_the_stream():
    mix = {"clients": 1, "max_requests_per_client_per_s": 1,
           "templates": [{"name": "x", "count": 1, "pql": "Count({R})"}],
           "params": {"R": {"zipf": {"s": 1.0, "values": [
               f"Row(f={i})" for i in range(20)]}}}}
    tops = []
    for stream in (schedule.WINDOW, schedule.WARMUP):
        plan = schedule.client_schedule(mix, 9, stream, 0, 4000)
        tops.append(collections.Counter(r["q"] for r in plan).most_common(1)
                    [0][0])
    assert tops[0] == tops[1]
    other = schedule.client_schedule(mix, 10, schedule.WINDOW, 0, 4000)
    counts = collections.Counter(r["q"] for r in other)
    # s = 1 over 20 values: the first rank draws about 28%
    assert 0.2 < counts.most_common(1)[0][1] / 4000 < 0.36


def test_the_points_mix_keeps_its_first_6000_requests_at_400_a_second():
    """Raising the points mix's limit from 120 to 400 requests a second
    a client only lengthens the schedule: the 6,000 requests that 120
    gave a 50 s window are the same, byte for byte, for every seed."""
    mix = _mix(os.path.join(BENCH, "traffic", "points-zipf-solo.json"))
    assert mix["max_requests_per_client_per_s"] == 400
    old = dict(mix, max_requests_per_client_per_s=120)
    for seed in (1, 2147483659, 2 ** 31 + 12345):
        was = schedule.build(old, seed, schedule.WINDOW, 50.0)
        now = schedule.build(mix, seed, schedule.WINDOW, 50.0)
        assert len(was[0]) == 6000 and len(now[0]) == 20000
        assert json.dumps(now[0][:6000]).encode() \
            == json.dumps(was[0]).encode()
