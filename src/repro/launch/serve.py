"""Serving launcher: quantize a model with a mixed BFP policy and serve a
queue of requests through the continuous-batching engine -- the llama-cli
analogue of the paper's evaluation.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --policy paper_llama_mix --tokens 32 --requests 8 --slots 4

Disaggregated serving (``--disagg --prefill-workers N --decode-workers
M``) splits the engine into a prefill tier and a decode tier behind a
KV-aware radix router; prompts route to the prefill worker with maximal
prefix-cache overlap and their finished KV pages migrate to a decode
worker (routed output stays token-identical to one monolithic engine).

Tensor-parallel serving (``--tp N``) runs every jitted engine program
through shard_map over a ("model",) mesh; on a CPU-only box add
``--force-host-devices N`` (or XLA_FLAGS=--xla_force_host_platform_
device_count=N) to split the host into N fake devices for testing.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


from repro.launch.hostdev import force_host_devices


def _forced_host_devices():
    """--force-host-devices must take effect BEFORE jax initializes its
    backends, so peek argv (and the env) ahead of the argparse run.
    Prefix matching mirrors argparse's abbreviation rule (no other flag
    starts with --force); non-numeric values are left for argparse's own
    type=int error instead of crashing pre-init."""
    for i, a in enumerate(sys.argv):
        if not a.startswith("--force"):
            continue
        if "=" in a:
            val = a.split("=", 1)[1]
        elif i + 1 < len(sys.argv):
            val = sys.argv[i + 1]
        else:
            continue
        return val if val.lstrip("-").isdigit() else None
    return os.environ.get("REPRO_FORCE_HOST_DEVICES")


force_host_devices(_forced_host_devices())

import jax
import numpy as np

from repro.configs.base import get_arch
from repro.core.policy import get_policy
from repro.core.qlinear import quantize_params, quantized_param_bytes
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.disagg import DisaggEngine
from repro.serving.engine import Engine, ServeConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="default_serve_mix",
                    help="named policy from core.policy.POLICIES, or "
                         "'auto' to load/search a calibrated per-layer "
                         "assignment (see --policy-json)")
    ap.add_argument("--policy-json", default=None,
                    help="searched-policy JSON for --policy auto; if the "
                         "file exists it is loaded, otherwise the search "
                         "runs and writes it (default: "
                         "results/auto_<arch>.json)")
    ap.add_argument("--search-rounds", type=int, default=2,
                    help="refinement rounds for the --policy auto search")
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--requests", type=int, default=4,
                    help="queue depth (may exceed --slots)")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent batch slots")
    ap.add_argument("--chunk", type=int, default=0,
                    help="decode steps per host sync (0 = --tokens)")
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--prefill-batch", type=int, default=8,
                    help="max requests per batched prefill group")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="tokens per prefill chunk (long prompts stream "
                         "through one fixed-shape compiled program)")
    ap.add_argument("--prefill-bucket", type=int, default=16,
                    help="prompt pad granularity (compilations are "
                         "O(#buckets), not O(#prompt lengths))")
    ap.add_argument("--prompt-len", type=int, default=6)   # paper: 6 tokens
    ap.add_argument("--tokens", type=int, default=10)      # paper: 10 tokens
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are emitted")
    ap.add_argument("--drafter", default=None,
                    choices=("ngram", "self"),
                    help="enable speculative decoding with this drafter "
                         "(greedy output stays bit-identical to plain "
                         "decode)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="drafted tokens per verify round")
    ap.add_argument("--draft-layers", type=int, default=2,
                    help="self-drafter: how many leading target-model "
                         "layers draft (same quantized weights)")
    ap.add_argument("--draft-ngram", type=int, default=2,
                    help="ngram drafter: match gram length")
    ap.add_argument("--draft-verify", default="scan",
                    choices=("scan", "batched"),
                    help="verify datapath: 'scan' is bit-exact vs plain "
                         "decode, 'batched' scores the whole draft block "
                         "in one masked forward")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the prefix cache: admission reuses the "
                         "longest cached token prefix and prefills only "
                         "the suffix (output stays token-identical to a "
                         "cold prefill). KV families page the ring; "
                         "recurrent families (ssm/hybrid) checkpoint "
                         "conv/SSM state at prefill-chunk boundaries")
    ap.add_argument("--prefix-page", type=int, default=16,
                    help="positions per KV page (clamped to a divisor of "
                         "the ring length; recurrent families pin the "
                         "page to --prefill-chunk instead)")
    ap.add_argument("--prefix-bytes", type=int, default=64 << 20,
                    help="device byte budget for the page pool (LRU "
                         "eviction of zero-ref pages beyond it)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every request (the prefix-cache workload)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: split into prefill-"
                         "worker and decode-worker engine instances "
                         "behind a KV-aware radix router; finished "
                         "prefill KV pages migrate to the decode tier "
                         "(routed output stays token-identical to one "
                         "monolithic engine)")
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="prefill-tier engine instances (--disagg)")
    ap.add_argument("--decode-workers", type=int, default=1,
                    help="decode-tier engine instances (--disagg)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: run the engine's jitted "
                         "programs via shard_map over a ('model',) mesh "
                         "of this many devices (lane-only sharding; "
                         "greedy output stays token-identical to --tp 1)")
    ap.add_argument("--tp-matmul", default="padded",
                    choices=("padded", "sliced", "sliced_row"),
                    help="TP projection datapath: 'padded' keeps the "
                         "single-device gemm shape per shard (bit-exact "
                         "parity; weights/KV still sharded), 'sliced' "
                         "runs true lane-sliced gemms (1/N FLOPs per "
                         "shard, equal to within an f32 ulp), "
                         "'sliced_row' adds row-parallel o-/down-"
                         "projections (half the collectives per layer; "
                         "equal to within ~a few activation-dtype ulps)")
    ap.add_argument("--no-tp-ep", dest="tp_ep", action="store_false",
                    help="disable expert parallelism under --tp for MoE "
                         "archs (by default expert stacks shard over the "
                         "model axis when n_experts divides the mesh; "
                         "outputs are bit-identical either way)")
    ap.add_argument("--force-host-devices", type=int, default=None,
                    help="split the host platform into this many fake "
                         "devices for CPU TP testing (applied before "
                         "jax init; also honored from the "
                         "REPRO_FORCE_HOST_DEVICES env var)")
    ap.add_argument("--http", action="store_true",
                    help="serve an OpenAI-compatible HTTP front-end "
                         "(POST /v1/completions with a token-id prompt, "
                         "SSE streaming, per-request priority/deadline_s/"
                         "timeout_s; GET /health, /v1/models, /stats) "
                         "instead of draining a synthetic queue")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--http listen address")
    ap.add_argument("--port", type=int, default=8000,
                    help="--http listen port (0 = ephemeral)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: submit()/HTTP requests "
                         "beyond this depth are rejected with a "
                         "structured reason (HTTP 429); 0 = unbounded")
    ap.add_argument("--preempt", action="store_true",
                    help="allow a strictly-higher-priority queued request "
                         "to preempt the lowest-priority running slot "
                         "(the victim keeps its streamed tokens)")
    ap.add_argument("--request-timeout", type=float, default=120.0,
                    help="--http default per-request wall ceiling in "
                         "seconds (overridable per request via "
                         "timeout_s)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch, reduced=args.reduced)
    if not cfg.embed_input:
        raise SystemExit(f"{args.arch} has a stub modality frontend; "
                         "serve driver needs token inputs")
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    if args.no_quant:
        qp = params
        print("serving UNQUANTIZED (baseline)")
    else:
        t0 = time.time()
        calib = None
        if args.policy == "auto":
            from repro.core import calibrate as CAL
            from repro.core.policy import load_policy
            from repro.launch.policy_search import (search_policy,
                                                    save_searched_policy)
            path = args.policy_json or f"results/auto_{args.arch}.json"
            if os.path.exists(path):
                policy = load_policy(path)
                print(f"loaded searched policy from {path}")
                if any(v == "q3_k_o" for _, v in policy.rules):
                    # q3_k_o weighs outliers by activation absmax; redo
                    # the (cheap, deterministic) calibration pass
                    stats = CAL.run_calibration(params, cfg)
                    calib = stats.for_paths(
                        [p for p, _ in policy.rules])
            else:
                policy, info = search_policy(
                    cfg, params, arch=args.arch,
                    rounds=args.search_rounds)
                save_searched_policy(path, policy, info)
                print(f"searched policy written to {path}")
                # pack with the same activation stats the search's
                # verified evals used -- q3_k_o outlier selection must
                # match the assignment the search validated, not fall
                # back to weight-magnitude-only selection
                calib = info["stats"].for_paths(
                    [p for p, _ in policy.rules])
        else:
            policy = get_policy(args.policy)
        qp, report = quantize_params(params, policy, calib=calib)
        counts = {}
        for v in report.values():
            if v:
                counts[v] = counts.get(v, 0) + 1
        sizes = quantized_param_bytes(qp)
        print(f"quantized with policy {args.policy} in {time.time()-t0:.1f}s:"
              f" {counts}; packed {sizes['packed']/2**20:.1f} MiB + residual "
              f"{sizes['unpacked']/2**20:.1f} MiB")

    decode_chunk = args.chunk or args.tokens
    if args.drafter is not None:
        decode_chunk = max(decode_chunk, args.draft_k + 1)
    if args.tp > 1:
        print(f"tensor-parallel: tp={args.tp} ({args.tp_matmul} matmul) "
              f"over {len(jax.devices())} visible devices")
    scfg = ServeConfig(
        max_new_tokens=args.tokens, temperature=args.temperature,
        eos_id=args.eos_id, cache_len=args.cache_len, seed=args.seed,
        max_slots=args.slots, decode_chunk=decode_chunk,
        prefill_batch=args.prefill_batch, prefill_chunk=args.prefill_chunk,
        prefill_bucket=args.prefill_bucket,
        drafter=args.drafter, draft_k=args.draft_k,
        draft_layers=args.draft_layers, draft_ngram=args.draft_ngram,
        draft_verify=args.draft_verify,
        prefix_cache=args.prefix_cache, prefix_page=args.prefix_page,
        prefix_bytes=args.prefix_bytes,
        max_queue=args.max_queue, preempt=args.preempt,
        tp=args.tp, tp_matmul=args.tp_matmul, tp_ep=args.tp_ep)
    if args.disagg:
        print(f"disaggregated: {args.prefill_workers} prefill + "
              f"{args.decode_workers} decode worker(s), KV-aware router")
        engine = DisaggEngine(cfg, qp, scfg,
                              prefill_workers=args.prefill_workers,
                              decode_workers=args.decode_workers)
    else:
        engine = Engine(cfg, qp, scfg)

    if args.http:
        from repro.serving.frontend import FrontendConfig, serve_forever
        serve_forever(engine, FrontendConfig(
            host=args.host, port=args.port, model_name=args.arch,
            request_timeout_s=args.request_timeout,
            max_tokens_default=args.tokens))
        return

    on_token = None
    if args.stream:
        on_token = lambda rid, tok: print(f"  [req {rid}] += {tok}")
    rng = np.random.default_rng(args.seed)
    shared = list(rng.integers(0, cfg.vocab_size, args.shared_prefix))
    ids = [engine.submit(shared + list(rng.integers(0, cfg.vocab_size,
                                                    args.prompt_len)),
                         on_token=on_token)
           for _ in range(args.requests)]
    results = engine.run()
    for rid in ids[:4]:
        print(f"req {rid}: {results[rid]}")

    # rates print 0 on empty denominators (a queue whose every request is
    # cancelled from its on_token callback never decodes; spec_rounds may
    # be 0): the engine's _finalize_stats carries the same guards, and
    # every ratio derived HERE goes through _rate too
    _rate = lambda n, d: n / d if d else 0.0
    s = engine.stats
    spec = ""
    if args.drafter is not None:
        spec = (f", spec accept {s['accept_rate']:.0%} "
                f"({s['draft_accepted']:.0f}/{s['draft_tokens']:.0f} "
                f"drafts over {s['spec_rounds']:.0f} rounds)")
    disagg = ""
    if args.disagg:
        rt = s["router"]
        disagg = (f", router: {rt['migrated_pages_total']} pages migrated, "
                  f"prefill hit rates {rt['prefill_hit_rate']}, "
                  f"{rt['direct_decode']} direct-to-decode, peak depths "
                  f"P{rt['prefill_peak_depth']}/D{rt['decode_peak_depth']}")
    prefix = ""
    if args.prefix_cache:
        prefix = (f", prefix hits {_rate(s['prefix_hits'], s['admissions']):.0%} "
                  f"({s['prefix_tokens_reused']:.0f} tokens reused, "
                  f"{s['prefix_evictions']:.0f} evictions)")
    print(f"prefill {s['prefill_s']:.3f}s "
          f"({s['prefill_tok_per_s']:.1f} tok/s, "
          f"{s['prefill_groups']:.0f} fused groups, "
          f"mean ttft {s['ttft_s'] * 1e3:.1f}ms), "
          f"decode {s['decode_s']:.3f}s, "
          f"{s['tok_per_s']:.1f} tok/s ({s['tokens']} tokens, "
          f"{s['host_syncs']} host syncs / {s['requests']} requests, "
          f"{_rate(s['host_syncs'], s['requests']):.1f}/req, "
          f"{s['chunks']} fused chunks{spec}{prefix}{disagg})")


if __name__ == "__main__":
    main()
