"""The serving engine's host spans and work counters.

Spans (``engine.poll``, ``engine.admit.*``, ``engine.decode.*``) are
``jax.profiler.TraceAnnotation``s whose durations also land in
``Engine.stats``; the children partition ``prefill_s`` and ``decode_s``.
The counters (slot steps, live context, prefill padding, queue waits)
are checked against brute-force counts of what the requests emitted.
"""
import glob
import http.client
import json
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models import transformer as T
from repro.serving.engine import Engine, ServeConfig
from repro.serving.frontend import Frontend, FrontendConfig

ADMIT = ("prep", "group_cache", "prefill", "bind", "sync")
DECODE = ("dispatch", "sync")


@pytest.fixture(scope="module")
def model():
    cfg = get_arch("tinyllama-1.1b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(model, **kw):
    cfg, params = model
    base = dict(max_new_tokens=6, cache_len=64, decode_chunk=6, max_slots=2)
    base.update(kw)
    return Engine(cfg, params, ServeConfig(**base))


def _prompts(cfg, n, shared=12, seed=0):
    """``n`` prompts that share a ``shared``-token prefix."""
    rng = np.random.default_rng(seed)
    head = list(rng.integers(0, cfg.vocab_size, shared))
    return [head + list(rng.integers(0, cfg.vocab_size, int(k)))
            for k in rng.integers(1, 9, n)]


def _serve(eng, prompts, late=0.02):
    """Two prompts up front, the rest one per poll, each stamped as due
    ``late`` seconds before it reached the engine."""
    pending = list(prompts[2:])
    ids = [eng.submit(p) for p in prompts[:2]]

    def poll():
        if pending:
            ids.append(eng.submit(pending.pop(0),
                                  arrival_t=time.perf_counter() - late))

    res = eng.run(poll=poll)
    return [res[i] for i in ids]


def _ctx_by_brute_force(prompts, outs, ring):
    """Decode token i (i >= 1) of a request comes from the query at
    position len(prompt) + i - 1, which attends that many positions (at
    most the ring)."""
    return sum(min(len(p) + i - 1, ring)
               for p, o in zip(prompts, outs) for i in range(1, len(o)))


@pytest.mark.parametrize("prefix", [False, True], ids=["cold", "prefix"])
@pytest.mark.parametrize("drafter", [None, "ngram"], ids=["plain", "spec"])
def test_spans_partition_and_counters_match(model, drafter, prefix):
    cfg, _ = model
    eng = _engine(model, drafter=drafter, draft_k=2, prefix_cache=prefix,
                  prefix_page=4)
    prompts = _prompts(cfg, 6)
    outs = _serve(eng, prompts)
    s = eng.stats
    assert all(len(o) == 6 for o in outs)
    assert s["prefill_s"] == pytest.approx(
        sum(s[f"admit_{k}_s"] for k in ADMIT), abs=1e-6)
    assert s["decode_s"] == pytest.approx(
        sum(s[f"decode_{k}_s"] for k in DECODE), abs=1e-6)
    for k in ADMIT + ("emit",):
        assert s[f"admit_{k}_s"] > 0, k
    for k in DECODE + ("emit",):
        assert s[f"decode_{k}_s"] > 0, k
    assert s["poll_s"] > 0

    assert s["decode_tokens"] == sum(len(o) - 1 for o in outs)
    assert s["decode_slot_steps"] == s["decode_steps"] * 2
    assert s["decode_ring_positions"] == s["decode_slot_steps"] * eng._T
    assert s["decode_steps"] <= s["chunks"] * eng.scfg.decode_chunk
    assert s["decode_ctx_positions"] == _ctx_by_brute_force(
        prompts, outs, eng._T)
    occupancy = s["decode_tokens"] / s["decode_slot_steps"]
    live_share = s["decode_ctx_positions"] / s["decode_ring_positions"]
    assert 0 < occupancy <= 1 and 0 < live_share <= 1

    assert s["prefill_padded_tokens"] >= s["prefill_tokens"] > 0
    assert 0 < s["pre_enqueue_wait_s"] <= s["queue_wait_total_s"]
    if prefix:
        assert s["prefix_hits"] > 0


def test_prefill_padding_counts_bucket_and_group(model):
    """Three prompts (5, 7, 9 tokens) form one group: padded to the
    16-token bucket and to a group of 4 rows, 64 positions for 21."""
    cfg, _ = model
    eng = _engine(model, max_slots=4, prefill_batch=4, prefill_bucket=16,
                  max_new_tokens=2)
    rng = np.random.default_rng(1)
    eng.generate([list(rng.integers(0, cfg.vocab_size, n))
                  for n in (5, 7, 9)])
    assert eng.stats["prefill_groups"] == 1
    assert eng.stats["prefill_tokens"] == 21
    assert eng.stats["prefill_padded_tokens"] == 64


def test_enqueue_stamp_splits_queue_wait(model):
    """``enqueue_t`` is when the engine got the request; ``submit_t``
    stays the arrival. Stats start fresh on every run() cycle."""
    cfg, _ = model
    eng = _engine(model)
    a, b = _prompts(cfg, 2, seed=2)
    t = time.perf_counter()
    ra = eng.submit(a, arrival_t=t - 0.25)
    rb = eng.submit(b)
    queued = {r.id: r for r in eng._queue}
    assert queued[ra].submit_t == t - 0.25 and queued[ra].enqueue_t >= t
    assert queued[rb].enqueue_t == queued[rb].submit_t
    eng.run()
    s = eng.stats
    assert 0.25 <= s["pre_enqueue_wait_s"] < 0.25 + (time.perf_counter() - t)
    assert s["pre_enqueue_wait_s"] < s["queue_wait_total_s"]
    eng.run()                                       # an empty cycle
    assert all(v == 0 for v in eng.stats.values())


def test_spans_nest_in_profiler_trace(model, tmp_path):
    """A CPU profiler trace holds the engine's spans, each child inside
    its parent, with the parent's arguments readable."""
    cfg, _ = model
    eng = _engine(model)
    prompts = _prompts(cfg, 3, seed=3)
    _serve(eng, prompts)                            # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, prompts)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
          for p in pd.planes for ln in p.lines for e in ln.events
          if e.name.startswith("engine.")]
    names = {n for n, *_ in ev}
    assert {"engine.poll", "engine.admit", "engine.decode"} <= names
    assert {f"engine.admit.{k}" for k in ADMIT + ("emit",)} <= names
    assert {f"engine.decode.{k}" for k in DECODE + ("emit",)} <= names
    for parent, kids in (("engine.admit", ADMIT + ("emit",)),
                         ("engine.decode", DECODE + ("emit",))):
        spans = [(a, b) for n, a, b, _ in ev if n == parent]
        for n, a, b, _ in ev:
            if n.startswith(parent + "."):
                assert any(pa <= a and b <= pb for pa, pb in spans), n
        assert sum(n == parent for n, *_ in ev) == sum(
            n == f"{parent}.{kids[0]}" for n, *_ in ev)
    admit = [st for n, _, _, st in ev if n == "engine.admit"]
    assert sum(a["G"] for a in admit) == len(prompts)
    assert all(a["rids"].startswith("[") for a in admit)
    decode = [st for n, _, _, st in ev if n == "engine.decode"]
    assert all(1 <= d["live"] <= 2 for d in decode)


def _http(fe, method, path, payload=None):
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=60)
    conn.request(method, path, payload and json.dumps(payload),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    return resp.status, body


def test_stats_endpoint_serialises_span_keys(model):
    eng = _engine(model, max_slots=1, max_new_tokens=5)
    eng.generate([[3, 1, 4]])                       # compile first
    fe = Frontend(eng, FrontendConfig(model_name="tiny-test")).start()
    try:
        status, _ = _http(fe, "POST", "/v1/completions",
                          dict(prompt=[3, 1, 4, 1], max_tokens=5))
        assert status == 200
        status, body = _http(fe, "GET", "/stats")
    finally:
        fe.close()
    assert status == 200
    engine = body["engine"]
    for k in ("poll_s", "admit_sync_s", "decode_dispatch_s",
              "decode_ctx_positions", "pre_enqueue_wait_s"):
        assert isinstance(engine[k], (int, float)), k
    assert engine["decode_tokens"] == 4
