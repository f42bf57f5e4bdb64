"""The one traffic generator: reads a mix file's parameters and makes the
arrivals of a run from its seed.

Every seed gets the same work. A segment of ``duration`` seconds at
``rate`` req/s holds round(rate * duration) requests whose prompt and
output lengths are the quantiles (i + 0.5) / n of the mix's
distributions and whose gaps are the quantiles of the exponential
distribution, scaled to fill the segment. The seed only orders them
(and draws the token ids), so two seeds differ in the order of the work
and not in its amount. Gaps, prompt lengths and output lengths are each
laid out in a low-discrepancy order from a seed-drawn offset (position i
takes the rank of frac(u + i * a), with a = sqrt 3 - 1, (sqrt 5 - 1) / 2
and sqrt 2 - 1, so that their triples fill the cube evenly). Long gaps,
long prompts and long answers are spread through the segment instead of
clustering where one seed happens to put them: with some tens of
requests in a window, a tail percentile and the tokens emitted inside
the window are otherwise set by that coincidence (measured: 24% and 4%
interquartile spread over seeds with shuffled orders, against under 3%
between two runs of one seed). Short gaps still come in runs, as the
exponential distribution has many of them.

Mix keys:
  arrival  {"process": "poisson"}, or {"process": "onoff", "on_s": a,
           "off_s": b}: the same requests, sent only in the on phases
  prompt / output  {"dist": "lognormal", "median", "sigma", "min", "max"}
           or {"dist": "uniform", "min", "max"}
  prefix   null, or {"share": p, "groups": g, "length": <dist>}: a share
           p of the requests open with one of g shared prefixes (drawn
           once per run, lengths from <dist>); "prompt" is then the
           length of the part after the prefix
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    t: float                    # seconds after the start of the load
    prompt: List[int]
    max_new: int
    segment: str


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """n integer lengths at the quantiles (i + 0.5) / n of ``dist``."""
    p = (np.arange(n) + 0.5) / max(n, 1)
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = lo + p * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


GAP_STEP = math.sqrt(3.0) - 1.0
PROMPT_STEP = (math.sqrt(5.0) - 1.0) / 2.0
OUTPUT_STEP = math.sqrt(2.0) - 1.0


def spread_order(n: int, rng: np.random.Generator,
                 step: float = PROMPT_STEP) -> np.ndarray:
    """Rank (0..n-1) for each position: neighbours in position sit far
    apart in rank, and the seed sets where the sequence starts."""
    keys = (rng.random() + np.arange(n) * step) % 1.0
    return np.argsort(np.argsort(keys, kind="stable"), kind="stable")


def _times(arrival: Dict, n: int, rate: float, duration: float,
           rng: np.random.Generator) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p) / rate
    gaps = (gaps * (duration / gaps.sum()))[spread_order(n, rng, GAP_STEP)]
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    proc = arrival.get("process", "poisson")
    if proc == "poisson":
        return t
    if proc == "onoff":
        on, off = arrival["on_s"], arrival["off_s"]
        # squeeze the schedule into the on phases, same mean rate
        squeezed = t * on / (on + off)
        cycles = np.floor(squeezed / on)
        return squeezed + cycles * off
    raise ValueError(f"unknown arrival process {proc!r}")


def schedule(mix: Dict, rate: float, segments: List[tuple], seed: int,
             vocab: int) -> List[Arrival]:
    """Arrivals for consecutive (name, seconds) segments, sorted by time."""
    rng = np.random.default_rng(seed)
    pref = mix.get("prefix")
    shared: List[List[int]] = []
    if pref:
        plen = quantiles(pref["length"], pref["groups"])
        shared = [rng.integers(0, vocab, int(n)).tolist() for n in plen]
    out: List[Arrival] = []
    t0 = 0.0
    for name, dur in segments:
        n = max(1, int(round(rate * dur)))
        times = _times(mix.get("arrival", {}), n, rate, dur, rng)
        plens = quantiles(mix["prompt"], n)[spread_order(n, rng)]
        olens = quantiles(mix["output"], n)[spread_order(n, rng,
                                                         OUTPUT_STEP)]
        heads: List[List[int]] = [[] for _ in range(n)]
        if pref:
            k = int(round(pref["share"] * n))
            who = rng.permutation(n)[:k]
            for i, j in enumerate(who):
                heads[j] = shared[i % len(shared)]
        for i in range(n):
            body = rng.integers(0, vocab, int(plens[i])).tolist()
            out.append(Arrival(t0 + float(times[i]), heads[i] + body,
                               int(olens[i]), name))
        t0 += dur
    out.sort(key=lambda a: a.t)
    return out


def longest_context(mix: Dict) -> int:
    """Largest prompt + output a request of this mix can have."""
    head = mix["prefix"]["length"]["max"] if mix.get("prefix") else 0
    return head + mix["prompt"]["max"] + mix["output"]["max"]
