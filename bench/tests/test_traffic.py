"""The generator gives every seed the same work in another order."""
import json

import numpy as np

from conftest import BENCH
from harness import traffic


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_same_work_every_seed():
    m = mix("chat")
    segs = [("preroll", 8.0), ("window", 30.0), ("tail", 70.0)]
    a = traffic.schedule(m, 4.0, segs, 1, 1000)
    b = traffic.schedule(m, 4.0, segs, 2 ** 31 + 12345, 1000)
    for seg in ("preroll", "window", "tail"):
        sa = [x for x in a if x.segment == seg]
        sb = [x for x in b if x.segment == seg]
        assert len(sa) == len(sb) == round(4.0 * dict(segs)[seg])
        assert sorted(len(x.prompt) for x in sa) == sorted(
            len(x.prompt) for x in sb)
        assert sorted(x.max_new for x in sa) == sorted(x.max_new for x in sb)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    win = [x.t for x in a if x.segment == "window"]
    assert 8.0 <= min(win) and max(win) < 38.0


def test_long_prompts_spread_through_the_window():
    m = mix("chat")
    arr = traffic.schedule(m, 2.0, [("window", 50.0)], 11, 1000)
    p = np.array([len(x.prompt) for x in arr])
    top = p >= np.quantile(p, 0.9)
    # the longest tenth never arrives back to back
    assert not np.any(top[1:] & top[:-1])
    rank = traffic.spread_order(100, np.random.default_rng(3))
    assert sorted(rank) == list(range(100))


def test_lengths_follow_the_mix():
    m = dict(mix("chat"),
             prompt={"dist": "lognormal", "median": 1024, "sigma": 0.5,
                     "min": 256, "max": 1920},
             output={"dist": "uniform", "min": 8, "max": 64})
    arr = traffic.schedule(m, 2.0, [("window", 100.0)], 7, 32064)
    p = np.array([len(x.prompt) for x in arr])
    o = np.array([x.max_new for x in arr])
    assert p.min() >= 256 and p.max() <= 1920
    assert abs(np.median(p) - 1024) < 30
    assert o.min() >= 8 and o.max() <= 64
    assert traffic.longest_context(m) == 1984
    assert all(0 <= t < 32064 for x in arr for t in x.prompt)


def test_onoff_and_shared_prefixes():
    m = dict(mix("chat"), arrival={"process": "onoff", "on_s": 2.0,
                                   "off_s": 3.0},
             prefix={"share": 0.5, "groups": 2,
                     "length": {"dist": "uniform", "min": 100, "max": 200}})
    arr = traffic.schedule(m, 4.0, [("window", 50.0)], 3, 1000)
    assert len(arr) == 200
    assert all((x.t % 5.0) < 2.0 + 1e-9 for x in arr)
    heads = {tuple(x.prompt[:100]) for x in arr}
    shared = [h for h in heads
              if sum(tuple(x.prompt[:100]) == h for x in arr) > 1]
    assert len(shared) == 2
    assert traffic.longest_context(m) == 200 + 1536 + 512
