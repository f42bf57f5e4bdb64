"""End-to-end serving bench (paper's llama-cli experiment, reduced scale).

Quantizes a TinyLlama-family reduced model with the paper's mixed policy
and drives the continuous-batching engine at queue depths 1 / 4 / 8 / 32
over the paper's workload shape (6-token prompt, 10 new tokens).  Reports
decode tok/s, prefill tok/s, mean time-to-first-token, wall times, and --
the quantity the on-device decode loop exists to minimize -- host syncs
per request.  Prefill runs through the batched chunked admission pipeline
(one fused prefill per group of up to ``max_slots`` requests).

Speculative rows (queue depths 1 / 8 / 32, quantized params, prompt-lookup
drafter over a repetitive prompt) report ``accept_rate`` and
``spec_tok_per_s`` next to the plain columns; speculation-off rows are
unchanged, so the regression gate still sees the plain decode path.

Shared-prefix rows (queue depths 8 / 32) drive the shared-system-prompt
workload -- every request is a 48-token shared prefix plus a unique
suffix -- once with the paged KV prefix cache off (the ttft baseline on
that workload) and once with it on (warm radix tree, suffix-only
prefill), reporting ``prefix_hit_rate``, ``prefix_tokens_reused`` and
``prefix_evictions``.

Tensor-parallel rows (tp=1 vs tp=2 at queue depth 8, quantized params)
report the same decode/prefill/ttft columns under the shard_map TP
engine; they need >= 2 devices, so on a CPU-only box set
REPRO_FORCE_HOST_DEVICES=2 (honored below BEFORE jax initializes) and
they are skipped otherwise (CI's 1-device smoke sweep never produces
them, and the regression gate skips absent rows/metrics).

Disaggregated rows (queue depth 8, quantized params, shared-prefix
workload) pair one monolithic engine against a 1-prefill + 1-decode
worker DisaggEngine at matched depth; the disagg row reports
``migrated_pages`` and the decode workers' ``prefix_hit_rate``, and the
pair rides the same-run ``check_disagg`` structural gate (plus an
in-bench token-identity assert, so a parity break can never publish a
row).

Recurrent rows (mamba2 ssm + zamba2 hybrid, fp32, queue depth 8) drive
ragged distinct-length prompts through the batched fixed-grid chunked
prefill path and report, next to the usual columns, the throughput of
the OLD exact-length prefill (``exact_prefill_tok_per_s``: one freshly
jitted program per prompt length -- the compile-per-length cost that
path actually paid on every new length). A second row per arch runs the
shared-system-prompt workload with the checkpoint-mode prefix cache on
and reports ``prefix_hit_rate``. Both ride the same-run
``check_recurrent_prefill`` structural gate (batched must beat
exact-length; see scripts/check_bench_regression.py) and are part of
the --smoke sweep.

Auto-policy rows (queue depth 4, reduced configs tinyllama / gpt2 /
mobilellama; tinyllama only in --smoke) run the calibrated policy
search (``launch/policy_search.py``) and serve the searched assignment
next to ``default_serve_mix``, reporting quality (teacher-logit ``kl``)
and ``model_bytes`` alongside the usual perf columns, with metric-only
``pure_q2_k`` / ``pure_q6_k`` anchor rows (emitted only for anchor
variants in the sweep's candidate set -- the smoke sweep searches
without q6_k, so it carries just ``pure_q2_k``); they ride the same-run
``check_policy_auto`` structural gate (auto must dominate-or-match the
default on both axes and beat the anchors on quality / size when
present).

Output: human CSV rows (``emit``) plus one machine-readable JSON blob
(``--out`` to persist, default benchmarks/results/e2e_serve.json when run
as a script) so future PRs can track the perf trajectory.  ``--smoke``
runs the reduced sweep CI uses for regression gating -- including one
spec-decode run (see scripts/check_bench_regression.py).
"""
import argparse
import functools
import os
import time

from repro.launch.hostdev import force_host_devices

force_host_devices(os.environ.get("REPRO_FORCE_HOST_DEVICES"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch
from repro.core.policy import get_policy
from repro.core.qlinear import quantize_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.disagg import DisaggEngine
from repro.serving.engine import Engine, ServeConfig
from benchmarks.common import emit, emit_json

PROMPT_LEN = 6            # paper workload
NEW_TOKENS = 10
QUEUE_DEPTHS = (1, 4, 8, 32)     # 4 = the seed benchmark's batch shape
SMOKE_DEPTHS = (4, 8)            # CI regression sweep
SPEC_DEPTHS = (1, 8, 32)         # speculative-decoding sweep
SPEC_SMOKE_DEPTHS = (8,)         # CI spec smoke run
PREFIX_DEPTHS = (8, 32)          # shared-system-prompt sweep
PREFIX_SMOKE_DEPTHS = (8,)       # CI prefix smoke run
TP_DEPTH = 8                     # tensor-parallel row (tp=1 vs tp=2)
DISAGG_DEPTH = 8                 # mono-vs-disagg row pair (1P+1D)
RECURRENT_ARCHS = ("mamba2-2.7b", "zamba2-1.2b")   # ssm + hybrid rows
RECURRENT_DEPTH = 8
# auto-policy quality-at-size rows (--policy auto): searched assignment
# vs default_serve_mix, with pure_q2_k / pure_q6_k anchors, per arch
AUTO_ARCHS = ("tinyllama-1.1b", "gpt2-paper", "mobilellama-1.4b")
AUTO_SMOKE_ARCHS = ("tinyllama-1.1b",)
AUTO_DEPTH = 4
SHARED_PREFIX_LEN = 48           # shared system prompt tokens
UNIQUE_LEN = 6                   # per-request unique suffix tokens
MAX_SLOTS = 8
DRAFT_K = 4


def _bench_one(cfg, params, depth: int, drafter: str = None,
               prefix: bool = None, tp: int = 1,
               tp_matmul: str = "padded") -> dict:
    """One engine sweep. ``prefix`` selects the shared-system-prompt
    workload (every request = SHARED_PREFIX_LEN shared tokens + a unique
    suffix): False runs it with the prefix cache OFF (the ttft baseline),
    True with it ON -- the warm-up generates populate the radix tree, so
    the measured runs hit."""
    slots = min(depth, MAX_SLOTS)
    eng = Engine(cfg, params, ServeConfig(
        max_new_tokens=NEW_TOKENS, max_slots=slots,
        decode_chunk=NEW_TOKENS,
        cache_len=64 if prefix is not None else 32, prefill_bucket=8,
        prefill_batch=slots, drafter=drafter, draft_k=DRAFT_K,
        prefix_cache=bool(prefix), prefix_page=8, tp=tp,
        tp_matmul=tp_matmul))
    rng = np.random.default_rng(0)
    if prefix is not None:
        shared = list(rng.integers(0, cfg.vocab_size, SHARED_PREFIX_LEN))
        prompts = [shared + list(rng.integers(0, cfg.vocab_size, UNIQUE_LEN))
                   for _ in range(depth)]
    elif drafter is None:
        prompts = [list(rng.integers(0, cfg.vocab_size, PROMPT_LEN))
                   for _ in range(depth)]
    else:
        # prompt-lookup's workload: repetitive prompts (cycled 2-grams)
        prompts = [[int(a), int(b)] * (PROMPT_LEN // 2)
                   for a, b in rng.integers(0, cfg.vocab_size, (depth, 2))]
    for _ in range(2):                         # compile + cache-donation warm
        eng.generate(prompts)
    stats = []
    for _ in range(3):
        outs = eng.generate(prompts)
        assert all(len(o) == NEW_TOKENS for o in outs)
        stats.append(dict(eng.stats))
    s = sorted(stats, key=lambda d: d["decode_s"])[1]      # median run
    rec = dict(queue_depth=depth, slots=slots,
               tokens=int(s["tokens"]),
               tok_per_s=round(s["tok_per_s"], 1),
               prefill_tok_per_s=round(s["prefill_tok_per_s"], 1),
               ttft_s=round(s["ttft_s"], 5),
               ttft_p50_s=round(s["ttft_p50_s"], 5),
               ttft_p99_s=round(s["ttft_p99_s"], 5),
               queue_wait_s=round(s["queue_wait_s"], 5),
               prefill_s=round(s["prefill_s"], 4),
               decode_s=round(s["decode_s"], 4),
               host_syncs=int(s["host_syncs"]),
               syncs_per_request=round(s["host_syncs"] / depth, 2),
               prefill_groups=int(s["prefill_groups"]),
               chunks=int(s["chunks"]))
    if drafter is not None:
        rec["drafter"] = drafter
        rec["draft_k"] = DRAFT_K
        rec["accept_rate"] = round(s["accept_rate"], 4)
        rec["spec_tok_per_s"] = rec["tok_per_s"]
        rec["spec_rounds"] = int(s["spec_rounds"])
    if prefix is not None:
        rec["shared_prefix_len"] = SHARED_PREFIX_LEN
        rec["prefix_hit_rate"] = round(s["prefix_hits"] / depth, 4)
        rec["prefix_tokens_reused"] = int(s["prefix_tokens_reused"])
        rec["prefix_evictions"] = int(s["prefix_evictions"])
    return rec


def _bench_disagg(cfg, params, depth: int) -> list:
    """Monolithic-vs-disaggregated row PAIR at matched queue depth over
    the shared-system-prompt workload (SHARED_PREFIX_LEN + unique
    suffix, so KV pages actually migrate prefill-worker -> decode-worker
    on the 1P+1D row). Both rows carry a ``disagg`` field
    (``"mono"`` / ``"1p1d"``) for the same-run structural gate in
    scripts/check_bench_regression.py; the measured outputs are asserted
    token-identical here too, so a parity break can never publish a
    benchmark row."""
    slots = min(depth, MAX_SLOTS)
    scfg = ServeConfig(max_new_tokens=NEW_TOKENS, max_slots=slots,
                       decode_chunk=NEW_TOKENS, cache_len=64,
                       prefill_bucket=8, prefill_batch=slots,
                       prefix_page=8)
    rng = np.random.default_rng(0)
    shared = list(rng.integers(0, cfg.vocab_size, SHARED_PREFIX_LEN))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, UNIQUE_LEN))
               for _ in range(depth)]
    rows, outs_by_tag = [], {}
    for tag, eng in (("mono", Engine(cfg, params, scfg)),
                     ("1p1d", DisaggEngine(cfg, params, scfg,
                                           prefill_workers=1,
                                           decode_workers=1))):
        for _ in range(2):                     # compile + warm radix trees
            eng.generate(prompts)
        stats = []
        for _ in range(3):
            outs = eng.generate(prompts)
            assert all(len(o) == NEW_TOKENS for o in outs)
            stats.append(dict(eng.stats))
        outs_by_tag[tag] = outs
        s = sorted(stats, key=lambda d: d["decode_s"])[1]      # median run
        rec = dict(queue_depth=depth, slots=slots, disagg=tag,
                   tokens=int(s["tokens"]),
                   tok_per_s=round(s["tok_per_s"], 1),
                   prefill_tok_per_s=round(s["prefill_tok_per_s"], 1),
                   ttft_s=round(s["ttft_s"], 5),
                   ttft_p50_s=round(s["ttft_p50_s"], 5),
                   ttft_p99_s=round(s["ttft_p99_s"], 5),
                   prefill_s=round(s["prefill_s"], 4),
                   decode_s=round(s["decode_s"], 4),
                   host_syncs=int(s["host_syncs"]),
                   shared_prefix_len=SHARED_PREFIX_LEN)
        if tag != "mono":
            router = s["router"]
            rec["prefill_workers"] = router["prefill_workers"]
            rec["decode_workers"] = router["decode_workers"]
            # lifetime totals: migration happens on the first (warm-up)
            # pass; measured passes re-hit the decode worker's radix tree
            rec["migrated_pages"] = int(router["migrated_pages_total"])
            rec["prefix_hit_rate"] = round(s["prefix_hits"] / depth, 4)
            rec["prefix_tokens_reused"] = int(s["prefix_tokens_reused"])
        rows.append(rec)
    assert outs_by_tag["1p1d"] == outs_by_tag["mono"], \
        "disaggregated output diverged from monolithic (parity contract)"
    return rows


def _exact_prefill_tok_per_s(cfg, params, prompts) -> float:
    """Throughput of the pre-refactor recurrent prefill: one EXACT-length
    program per prompt, so every new length pays a fresh compile -- the
    cost the old ``_prefill_impl`` paid on first sight of each length (a
    fresh jit wrapper per prompt defeats jax's cache the same way a new
    length did). The batched fixed-grid path amortizes ONE compiled
    (B, C) chunk program over all lengths; this oracle is what the
    ``check_recurrent_prefill`` gate compares it against."""
    total_s, total_tok = 0.0, 0
    for p in prompts:
        L = len(p)
        cache = T.init_cache(cfg, 1, 64)
        fn = jax.jit(functools.partial(
            T.prefill_chunk, params, cfg))            # fresh cache entry
        tok = jnp.asarray([p], jnp.int32)
        lens = jnp.asarray([L], jnp.int32)
        t0 = time.perf_counter()
        out = fn(cache, tokens=tok, start=jnp.int32(0), lengths=lens)
        jax.block_until_ready(out)
        total_s += time.perf_counter() - t0
        total_tok += L
    return total_tok / total_s


def _bench_recurrent(arch: str, depth: int) -> list:
    """Two rows for a recurrent arch (fp32): ragged distinct-length
    prompts through the batched fixed-grid chunked prefill (plus the
    exact-length oracle throughput for the structural gate), and the
    shared-system-prompt workload with the checkpoint-mode prefix cache
    on (hit rate must be total: every measured pass is warm)."""
    cfg = get_arch(arch, reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    slots = min(depth, MAX_SLOTS)
    rng = np.random.default_rng(0)
    # distinct lengths: the exact-length path compiles per length
    lens = list(18 + rng.permutation(30)[:depth])
    shared = list(rng.integers(0, cfg.vocab_size, SHARED_PREFIX_LEN))
    rows = []
    for tag, prefix_on in (("batched", None), ("prefix_on", True)):
        eng = Engine(cfg, params, ServeConfig(
            max_new_tokens=NEW_TOKENS, max_slots=slots,
            decode_chunk=NEW_TOKENS, cache_len=64, prefill_bucket=8,
            prefill_chunk=16, prefill_batch=slots,
            prefix_cache=bool(prefix_on)))
        if prefix_on:
            prompts = [shared + list(rng.integers(0, cfg.vocab_size,
                                                  UNIQUE_LEN))
                       for _ in range(depth)]
        else:
            prompts = [list(rng.integers(0, cfg.vocab_size, int(L)))
                       for L in lens]
        for _ in range(2):                 # compile + warm checkpoint tree
            eng.generate(prompts)
        stats = []
        for _ in range(3):
            outs = eng.generate(prompts)
            assert all(len(o) == NEW_TOKENS for o in outs)
            stats.append(dict(eng.stats))
        s = sorted(stats, key=lambda d: d["decode_s"])[1]      # median run
        rec = dict(queue_depth=depth, slots=slots, arch=arch,
                   family=cfg.family, prefill_mode=tag,
                   tokens=int(s["tokens"]),
                   tok_per_s=round(s["tok_per_s"], 1),
                   prefill_tok_per_s=round(s["prefill_tok_per_s"], 1),
                   ttft_s=round(s["ttft_s"], 5),
                   ttft_p50_s=round(s["ttft_p50_s"], 5),
                   ttft_p99_s=round(s["ttft_p99_s"], 5),
                   prefill_s=round(s["prefill_s"], 4),
                   decode_s=round(s["decode_s"], 4),
                   host_syncs=int(s["host_syncs"]))
        if prefix_on:
            rec["shared_prefix_len"] = SHARED_PREFIX_LEN
            rec["prefix_hit_rate"] = round(s["prefix_hits"] / depth, 4)
            rec["prefix_tokens_reused"] = int(s["prefix_tokens_reused"])
        else:
            rec["exact_prefill_tok_per_s"] = round(
                _exact_prefill_tok_per_s(cfg, params, prompts), 1)
        rows.append(rec)
    return rows


def _bench_policy_auto(smoke: bool) -> list:
    """Auto-policy rows: per arch, run the calibrated policy search and
    serve both the searched assignment and default_serve_mix at matched
    depth; quality (teacher-logit KL) and model bytes ride along from
    the search's own verified evals, with the pure_q2_k / pure_q6_k
    anchors as metric-only rows (anchors exist only for variants the
    sweep searched; smoke drops q6_k). The searched policy dominates-or-
    matches the seed by construction -- check_policy_auto pins that."""
    from repro.core import calibrate as CAL
    from repro.launch.policy_search import search_policy
    archs = AUTO_SMOKE_ARCHS if smoke else AUTO_ARCHS
    candidates = (("q2_k", "q3_k", "none") if smoke else
                  ("q2_k", "q3_k", "q3_k_o", "q4_k", "q6_k", "none"))
    rows = []
    for arch in archs:
        cfg = get_arch(arch, reduced=True)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        stats = CAL.run_calibration(params, cfg,
                                    n_batches=1 if smoke else 2,
                                    seq=32 if smoke else 64)
        policy, info = search_policy(
            cfg, params, arch=arch, candidates=candidates,
            rounds=1 if smoke else 2, stats=stats,
            eval_seq=32 if smoke else 64, verbose=False)
        meta = info["meta"]
        calib = stats.for_paths([p for p, _ in policy.rules])
        qp_auto, _ = quantize_params(params, policy, calib=calib)
        qp_def, _ = quantize_params(params,
                                    get_policy("default_serve_mix"))
        served = (("auto", qp_auto, meta["final"]),
                  ("default_serve_mix", qp_def, meta["seed"]))
        for tag, qp, m in served:
            rec = _bench_one(cfg, qp, AUTO_DEPTH)
            rec["params"] = f"policy_{tag}_{arch}"
            rec["policy"] = tag
            rec["policy_arch"] = arch
            rec["kl"] = round(m["kl"], 6)
            rec["model_bytes"] = int(m["bytes"])
            if "pseudo_ppl" in m:
                rec["pseudo_ppl"] = round(m["pseudo_ppl"], 3)
            rows.append(rec)
        for v, m in meta["anchors"].items():
            rows.append(dict(
                params=f"policy_{v}_{arch}", queue_depth=AUTO_DEPTH,
                policy=v, policy_arch=arch, kl=round(m["kl"], 6),
                model_bytes=int(m["bytes"]),
                pseudo_ppl=round(m["pseudo_ppl"], 3)))
    return rows


def run(out_path: str = None, smoke: bool = False) -> dict:
    cfg = get_arch("tinyllama-1.1b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    qp, _ = quantize_params(params, get_policy("paper_llama_mix"))
    depths = SMOKE_DEPTHS if smoke else QUEUE_DEPTHS
    spec_depths = SPEC_SMOKE_DEPTHS if smoke else SPEC_DEPTHS
    prefix_depths = PREFIX_SMOKE_DEPTHS if smoke else PREFIX_DEPTHS

    results = dict(
        benchmark="e2e_serve",
        arch="tinyllama-1.1b(reduced)",
        workload=dict(prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS,
                      queue_depths=list(depths),
                      spec_queue_depths=list(spec_depths),
                      prefix_queue_depths=list(prefix_depths),
                      shared_prefix_len=SHARED_PREFIX_LEN,
                      unique_len=UNIQUE_LEN, tp_depth=TP_DEPTH,
                      disagg_depth=DISAGG_DEPTH,
                      recurrent_archs=list(RECURRENT_ARCHS),
                      recurrent_depth=RECURRENT_DEPTH,
                      auto_archs=list(AUTO_SMOKE_ARCHS if smoke
                                      else AUTO_ARCHS),
                      auto_depth=AUTO_DEPTH,
                      draft_k=DRAFT_K, max_slots=MAX_SLOTS,
                      smoke=smoke),
        runs=[],
    )
    for tag, p in [("fp32", params), ("fbfq_mixed_q2q3", qp)]:
        for depth in depths:
            rec = _bench_one(cfg, p, depth)
            rec["params"] = tag
            results["runs"].append(rec)
            emit(f"e2e_serve_{tag}_d{depth}",
                 rec["decode_s"] / max(rec["tokens"], 1) * 1e6,
                 f"tok/s={rec['tok_per_s']} "
                 f"prefill_tok/s={rec['prefill_tok_per_s']} "
                 f"ttft_s={rec['ttft_s']} "
                 f"host_syncs={rec['host_syncs']} "
                 f"({rec['syncs_per_request']}/req)")
    for depth in spec_depths:
        rec = _bench_one(cfg, qp, depth, drafter="ngram")
        rec["params"] = "fbfq_mixed_q2q3_spec_ngram"
        results["runs"].append(rec)
        emit(f"e2e_serve_spec_ngram_d{depth}",
             rec["decode_s"] / max(rec["tokens"], 1) * 1e6,
             f"spec_tok/s={rec['spec_tok_per_s']} "
             f"accept_rate={rec['accept_rate']} "
             f"rounds={rec['spec_rounds']} "
             f"ttft_s={rec['ttft_s']}")
    # tensor-parallel rows: same workload/params at tp=1 vs tp=2 under
    # the shard_map engine, one row per (tp, matmul datapath) so
    # baselines compare like-for-like:
    #   padded     -- token-identical output, replicated FLOPs (tracks
    #                 the TP engine's overhead)
    #   sliced     -- lane-sliced gemms, 1/size FLOPs, f32-ulp fidelity
    #   sliced_row -- sliced + row-parallel o-/down-proj (half the
    #                 collectives per layer; activation-ulp fidelity) --
    #                 the throughput datapath
    # Skipped when the backend exposes a single device.
    if not smoke and len(jax.devices()) >= 2:
        tp_rows = [(1, "padded")] + [(2, mm) for mm in
                                     ("padded", "sliced", "sliced_row")]
        for tp, mm in tp_rows:
            rec = _bench_one(cfg, qp, TP_DEPTH, tp=tp, tp_matmul=mm)
            rec["params"] = f"fbfq_mixed_q2q3_tp{tp}_{mm}"
            rec["tp"] = tp
            rec["tp_matmul"] = mm
            results["runs"].append(rec)
            emit(f"e2e_serve_tp{tp}_{mm}_d{TP_DEPTH}",
                 rec["decode_s"] / max(rec["tokens"], 1) * 1e6,
                 f"tok/s={rec['tok_per_s']} "
                 f"prefill_tok/s={rec['prefill_tok_per_s']} "
                 f"ttft_s={rec['ttft_s']}")
    # shared-system-prompt workload: prefix cache off (ttft baseline on
    # the SAME prompts) vs on (warm radix tree -> suffix-only prefill)
    for depth in prefix_depths:
        for tag, on in (("prefix_off", False), ("prefix_on", True)):
            rec = _bench_one(cfg, qp, depth, prefix=on)
            rec["params"] = f"fbfq_mixed_q2q3_{tag}"
            results["runs"].append(rec)
            emit(f"e2e_serve_{tag}_d{depth}",
                 rec["decode_s"] / max(rec["tokens"], 1) * 1e6,
                 f"prefill_tok/s={rec['prefill_tok_per_s']} "
                 f"ttft_s={rec['ttft_s']} "
                 + (f"prefix_hit_rate={rec['prefix_hit_rate']} "
                    f"reused={rec['prefix_tokens_reused']}" if on else ""))
    # recurrent rows (ssm + hybrid, fp32): batched fixed-grid chunked
    # prefill vs the old exact-length oracle, plus a checkpoint-mode
    # prefix-cache row -- both in the smoke sweep for the same-run
    # check_recurrent_prefill structural gate
    for arch in RECURRENT_ARCHS:
        for rec in _bench_recurrent(arch, RECURRENT_DEPTH):
            rec["params"] = f"fp32_{rec['family']}_{rec['prefill_mode']}"
            results["runs"].append(rec)
            fam = rec["family"]
            extra = (f"prefix_hit_rate={rec['prefix_hit_rate']} "
                     f"reused={rec['prefix_tokens_reused']}"
                     if rec["prefill_mode"] == "prefix_on" else
                     f"exact_prefill_tok/s={rec['exact_prefill_tok_per_s']}")
            emit(f"e2e_serve_{fam}_{rec['prefill_mode']}_d{RECURRENT_DEPTH}",
                 rec["decode_s"] / max(rec["tokens"], 1) * 1e6,
                 f"tok/s={rec['tok_per_s']} "
                 f"prefill_tok/s={rec['prefill_tok_per_s']} "
                 f"ttft_s={rec['ttft_s']} {extra}")
    # monolithic-vs-disaggregated pair at matched depth (1 prefill + 1
    # decode worker; shared-prefix workload so pages migrate) -- included
    # in the smoke sweep for the same-run check_disagg structural gate
    for rec in _bench_disagg(cfg, qp, DISAGG_DEPTH):
        rec["params"] = f"fbfq_mixed_q2q3_disagg_{rec['disagg']}" \
            if rec["disagg"] != "mono" else "fbfq_mixed_q2q3_mono"
        results["runs"].append(rec)
        extra = (f"migrated_pages={rec['migrated_pages']} "
                 f"prefix_hit_rate={rec['prefix_hit_rate']}"
                 if rec["disagg"] != "mono" else "")
        emit(f"e2e_serve_disagg_{rec['disagg']}_d{DISAGG_DEPTH}",
             rec["decode_s"] / max(rec["tokens"], 1) * 1e6,
             f"tok/s={rec['tok_per_s']} "
             f"prefill_tok/s={rec['prefill_tok_per_s']} "
             f"ttft_s={rec['ttft_s']} {extra}")
    # auto-policy quality-at-size rows (searched vs default_serve_mix +
    # anchors) -- in the smoke sweep too for the same-run
    # check_policy_auto structural gate
    for rec in _bench_policy_auto(smoke):
        results["runs"].append(rec)
        perf = (f"tok/s={rec['tok_per_s']} ttft_s={rec['ttft_s']} "
                if "tok_per_s" in rec else "")
        emit(f"e2e_serve_{rec['params']}_d{rec['queue_depth']}",
             rec["kl"] * 1e3,
             f"kl={rec['kl']} bytes={rec['model_bytes']} {perf}")
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
                    help="quick sweep (CI regression gate): depths "
                         f"{SMOKE_DEPTHS} plus one spec run at depth "
                         f"{SPEC_SMOKE_DEPTHS[0]}")
    args = ap.parse_args()
    enable_compile_cache()
    out = args.out
    if out is None:
        out = "" if args.smoke else "benchmarks/results/e2e_serve.json"
    run(out or None, smoke=args.smoke)
