"""Trace-driven serving bench: latency under load, not a depth sweep.

The e2e bench submits its whole queue up front and drains it -- that
measures throughput at fixed depth, but the ROADMAP north star ("heavy
traffic") is a latency-under-load curve: requests ARRIVE over time, and
the contested metrics are tail TTFT, tail inter-token latency, and
goodput under an SLO. This bench drives the engine with Poisson arrival
traces over two workload mixes and reports exactly those:

* ``chat``: a shared-system-prompt population (60% of requests share a
  24-token system prefix) with short unique suffixes, prefix cache ON --
  the workload the paged KV cache exists for.
* ``mixed``: no shared prefix, broader prompt/output length spread,
  prefix cache OFF -- the cold-path curve.

Arrivals are injected mid-cycle through ``Engine.run(poll=...)``: the
poll hook submits every trace entry whose timestamp has come due, so
requests land between decode chunks exactly as a front-end would inject
them. Per-request we record the arrival-stamped submit wall time, every
token's wall time, and the run()-entry wall time of the cycle that
served the first token -- which lets each row report BOTH the fixed TTFT
(first token - arrival) and the old run-entry-stamped value
(``ttft_runentry_*``). At matched load the fixed value is <= the old one
for every request (run entry always precedes a mid-cycle arrival); the
bench asserts that per request, and ``check_trace`` gates it
structurally, pinning the arrival-time accounting bugfix.

Goodput counts a request iff it completed its full token budget AND met
the TTFT SLO; ``saturation_rps`` per mix is the highest swept offered
rate whose goodput fraction stays above the floor.

Output mirrors e2e_serve: human CSV rows plus one JSON blob;
``--smoke`` runs the reduced sweep CI gates with ``check_trace``
(scripts/check_bench_regression.py) against the committed baseline at
benchmarks/results/trace_serve.json.
"""
import argparse
import collections
import time

import jax
import numpy as np

from repro.configs.base import get_arch
from repro.core.policy import get_policy
from repro.core.qlinear import quantize_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.engine import Engine, ServeConfig
from benchmarks.common import emit, emit_json

MIXES = {
    # shared-system-prompt population: the prefix-cache serving workload
    "chat": dict(shared_frac=0.6, shared_len=24, unique_lo=4,
                 unique_hi=12, out_lo=6, out_hi=14, prefix_cache=True),
    # no sharing, broader length spread: the cold-path curve
    "mixed": dict(shared_frac=0.0, shared_len=0, unique_lo=4,
                  unique_hi=28, out_lo=4, out_hi=16, prefix_cache=False),
}
RATES = (8.0, 32.0, 128.0)       # offered req/s per mix (sweep)
SMOKE_RATES = (8.0, 32.0)        # CI subset (same keys as the baseline)
N_REQUESTS = 48
SMOKE_REQUESTS = 20
SLO_TTFT_S = 0.5                 # TTFT SLO goodput is conditioned on
GOODPUT_FLOOR = 0.9              # goodput_frac >= this => rate "met"
MAX_SLOTS = 8
DECODE_CHUNK = 4                 # short chunks: honest inter-token tails
SEED = 0


def _gen_trace(cfg, mix: str, rate: float, n: int, seed: int):
    """(arrival_s, prompt, out_budget) triples; Poisson arrivals at
    ``rate`` req/s, lengths drawn from the mix. Deterministic per
    (mix, rate, n, seed) so baseline and CI runs replay the same trace."""
    rng = np.random.default_rng(seed)
    m = MIXES[mix]
    shared = ([int(t) for t in rng.integers(0, cfg.vocab_size,
                                            m["shared_len"])]
              if m["shared_len"] else [])
    t, trace = 0.0, []
    for _ in range(n):
        t += float(rng.exponential(1.0 / rate))
        plen = int(rng.integers(m["unique_lo"], m["unique_hi"] + 1))
        head = shared if rng.random() < m["shared_frac"] else []
        prompt = head + [int(x) for x in
                         rng.integers(0, cfg.vocab_size, plen)]
        out = int(rng.integers(m["out_lo"], m["out_hi"] + 1))
        trace.append((t, prompt, out))
    return trace


def _drive(eng: Engine, trace):
    """Replay ``trace`` against a live engine; returns per-request
    records. Arrivals are injected from run(poll=...) so they land
    between decode chunks; when the engine idles ahead of the next
    arrival we sleep the gap out and re-enter run()."""
    state = {}
    pending = collections.deque(trace)
    run_entry = [None]          # wall stamp of the current run() cycle
    t0 = time.perf_counter()

    def on_token(rid, tok):
        st = state[rid]
        if not st["tok_t"]:
            st["run_entry"] = run_entry[0]
        st["tok_t"].append(time.perf_counter())

    def on_done(req):
        state[req.id]["req"] = req

    def poll():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            at, prompt, out = pending.popleft()
            rid = eng.submit(list(prompt), max_new_tokens=out,
                             on_token=on_token, on_done=on_done)
            state[rid] = dict(arrival=at, submit=time.perf_counter(),
                              tok_t=[], run_entry=None, req=None,
                              budget=out)

    while pending or eng._queue:
        now = time.perf_counter() - t0
        if not eng._queue and pending and pending[0][0] > now:
            time.sleep(pending[0][0] - now)
        # the cycle stamp is taken BEFORE run() (and poll() only runs
        # inside it), so run_entry <= submit for every request this cycle
        # serves -- which is why fixed TTFT <= run-entry TTFT per request
        run_entry[0] = time.perf_counter()
        eng.run(poll=poll)
    wall = time.perf_counter() - t0
    return state, wall


def _pct(xs, q):
    return float(np.percentile(xs, q)) if xs else 0.0


def _metrics(state, wall: float, slo_ttft_s: float) -> dict:
    ttfts, old_ttfts, itls, waits = [], [], [], []
    completed = good = 0
    for st in state.values():
        if not st["tok_t"]:
            continue
        ttft = st["tok_t"][0] - st["submit"]
        old = st["tok_t"][0] - st["run_entry"]
        # the arrival-time accounting contract: the fixed stamp can only
        # shrink TTFT relative to the old run()-entry stamp
        assert ttft <= old + 1e-6, (ttft, old)
        ttfts.append(ttft)
        old_ttfts.append(old)
        itls += [b - a for a, b in zip(st["tok_t"], st["tok_t"][1:])]
        req = st["req"]
        if req is not None and req.queue_wait_s is not None:
            waits.append(req.queue_wait_s)
        done_ok = (req is not None and not req.cancelled
                   and len(req.tokens) == st["budget"])
        completed += done_ok
        good += done_ok and ttft <= slo_ttft_s
    n = len(state)
    return dict(
        requests=n, completed=completed, wall_s=round(wall, 4),
        ttft_mean_s=round(float(np.mean(ttfts)), 5) if ttfts else 0.0,
        ttft_p50_s=round(_pct(ttfts, 50), 5),
        ttft_p99_s=round(_pct(ttfts, 99), 5),
        ttft_runentry_p50_s=round(_pct(old_ttfts, 50), 5),
        ttft_runentry_p99_s=round(_pct(old_ttfts, 99), 5),
        itl_p50_s=round(_pct(itls, 50), 6),
        itl_p99_s=round(_pct(itls, 99), 6),
        queue_wait_p99_s=round(_pct(waits, 99), 5),
        slo_ttft_s=slo_ttft_s,
        goodput_frac=round(good / n, 4) if n else 0.0,
        goodput_rps=round(good / wall, 2) if wall > 0 else 0.0,
    )


def _mix_engine(cfg, params, mix: str) -> Engine:
    # prefill_batch=1: one prefill dispatch per admission, so the compile
    # surface is fixed (length buckets only). Grouped admission compiles
    # one program PER GROUP SIZE, and under Poisson arrivals the measured
    # run hits group sizes warmup never saw -- multi-second compiles in
    # the middle of a latency measurement.
    m = MIXES[mix]
    return Engine(cfg, params, ServeConfig(
        max_new_tokens=m["out_hi"], max_slots=MAX_SLOTS,
        decode_chunk=DECODE_CHUNK, cache_len=64, prefill_bucket=16,
        prefill_batch=1, prefix_cache=m["prefix_cache"],
        prefix_page=8))


def _warm(eng: Engine, trace) -> None:
    """Compile the shapes the measured run will hit: one batch drain
    (largest prefill groups + decode chunk) and one one-at-a-time pass
    (size-1 groups per length bucket, the common mid-cycle arrival
    shape). Also pre-populates the chat mix's radix tree, so measured
    runs serve a warm shared-prefix population."""
    prompts = [p for _, p, _ in trace]
    eng.generate(prompts)
    for p in prompts:
        eng.generate([p])


def run(out_path: str = None, smoke: bool = False) -> dict:
    cfg = get_arch("tinyllama-1.1b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    qp, _ = quantize_params(params, get_policy("paper_llama_mix"))
    rates = SMOKE_RATES if smoke else RATES
    n = SMOKE_REQUESTS if smoke else N_REQUESTS

    results = dict(
        benchmark="trace_serve",
        arch="tinyllama-1.1b(reduced)",
        workload=dict(mixes={k: {kk: vv for kk, vv in v.items()}
                             for k, v in MIXES.items()},
                      rates_rps=list(rates), requests_per_rate=n,
                      slo_ttft_s=SLO_TTFT_S,
                      goodput_floor=GOODPUT_FLOOR,
                      max_slots=MAX_SLOTS, decode_chunk=DECODE_CHUNK,
                      seed=SEED, smoke=smoke),
        runs=[], summary={},
    )
    for mix in MIXES:
        eng = _mix_engine(cfg, qp, mix)
        _warm(eng, _gen_trace(cfg, mix, max(rates), n, SEED))
        mix_rows = []
        for rate in rates:
            trace = _gen_trace(cfg, mix, rate, n, SEED)
            state, wall = _drive(eng, trace)
            row = dict(mix=mix, rate_rps=rate,
                       params="fbfq_mixed_q2q3", **_metrics(
                           state, wall, SLO_TTFT_S))
            results["runs"].append(row)
            mix_rows.append(row)
            emit(f"trace_serve_{mix}_r{rate:g}",
                 row["ttft_p99_s"] * 1e6,
                 f"ttft_p50={row['ttft_p50_s']} "
                 f"ttft_p99={row['ttft_p99_s']} "
                 f"itl_p99={row['itl_p99_s']} "
                 f"goodput={row['goodput_frac']} "
                 f"({row['goodput_rps']} rps good)")
        met = [r["rate_rps"] for r in mix_rows
               if r["goodput_frac"] >= GOODPUT_FLOOR]
        results["summary"][mix] = dict(
            saturation_rps=max(met) if met else 0.0,
            rates_met=met, rates_swept=list(rates))
        emit(f"trace_serve_{mix}_saturation",
             results["summary"][mix]["saturation_rps"],
             f"rates_met={met} of {list(rates)} "
             f"(goodput_floor={GOODPUT_FLOOR})")
    emit_json(results, out_path)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="where to persist the JSON blob ('' to skip; "
                         "default: the committed baseline path for the "
                         "full sweep, nowhere for --smoke so a partial "
                         "sweep can never clobber the baseline)")
    ap.add_argument("--smoke", action="store_true",
                    help="quick sweep (CI check_trace gate): rates "
                         f"{SMOKE_RATES} x {SMOKE_REQUESTS} requests "
                         "per mix")
    args = ap.parse_args()
    enable_compile_cache()
    out = args.out
    if out is None:
        out = "" if args.smoke else "benchmarks/results/trace_serve.json"
    run(out or None, smoke=args.smoke)
