"""Disaggregated prefill/decode serving: N prefill-worker and M
decode-worker engine instances behind a KV-aware router.

Monolithic serving makes prefill and decode compete for the same device
steps: every admission stalls the decode loop for a full chunked-prefill
group. Disaggregation splits the phases onto separate engine instances --
prefill workers only ever run admission-shaped programs, decode workers
only ever see prompts whose KV is already resident -- which is the
architectural unlock for serving at depth (ROADMAP item 1; the
vllm/triton-distributed prefill/decode split, in-process).

The hand-off protocol rides the paged prefix cache end to end:

1. **route**: the router (serving/router.py) scores the prompt against
   every prefill worker's radix tree and routes to maximal overlap, so a
   shared system prompt concentrates on the worker that already holds
   its pages (warm prefill = suffix-only compute).
2. **prefill**: the chosen worker runs the prompt through its ordinary
   batched chunked admission with a 1-token budget -- pure prefill; the
   sampled token is discarded (the decode worker re-derives it, see
   below) -- and its prefix cache inserts the prompt's full KV pages
   into its page pool.
3. **migrate**: ``Engine.export_kv_pages`` copies those pool pages to
   host memory bit-for-bit (int8-KV scales included);
   ``Engine.import_kv_pages`` scatters them into the routed decode
   worker's pool and radix tree. In-process this is one device->host and
   one host->device copy; the same protocol shape extends to a wire.
4. **decode**: the request is submitted to the decode worker, whose
   ordinary prefix-cache admission matches the imported pages, scatters
   them into its ring, prefills ONLY the remaining tail (the last token
   plus any partial page -- where the first sampled token comes from),
   and decodes continuously.

**The parity contract.** The decode worker samples every token,
including the first, from its own PRNG stream with the same per-request
key-split discipline a monolithic engine uses, and warm-prefix admission
is already pinned token-identical to cold prefill (tests/
test_prefix_cache.py, greedy AND temperature). So with 1 decode worker,
routed output is TOKEN-IDENTICAL to one monolithic engine with the same
ServeConfig -- greedy and temperature, across causal/window/int8-KV,
with speculation and the prefix cache live on the workers
(tests/test_disagg.py). With M decode workers, greedy output stays
token-identical (greedy sampling is schedule-independent and admission
isolation is pinned); temperature splits into per-worker streams.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.serving.engine import (_KV_FAMILIES, Engine, EngineSaturated,
                                  Request, ServeConfig)
from repro.serving.router import KVRouter


class DisaggEngine:
    """N prefill + M decode engine instances, one router, page migration
    through host memory. Public surface mirrors ``Engine``:
    submit/cancel/run/generate and a ``stats`` dict (aggregated across
    workers, plus router fields)."""

    def __init__(self, cfg: ModelConfig, params, serve_cfg: ServeConfig,
                 prefill_workers: int = 1, decode_workers: int = 1):
        if prefill_workers < 1 or decode_workers < 1:
            raise ValueError(
                f"need >= 1 prefill and >= 1 decode worker, got "
                f"{prefill_workers}P + {decode_workers}D")
        if cfg.family not in _KV_FAMILIES:
            raise ValueError(
                f"disaggregated serving needs a KV-ring family (got "
                f"{cfg.family!r}): recurrent state is not positional and "
                "cannot be handed off as pages")
        self.cfg = cfg
        self.scfg = serve_cfg
        # decode workers ARE the serving engines: same config (same seed,
        # slots, drafter, sampling -- the parity contract), prefix cache
        # forced on because imported pages land in it
        dcfg = dataclasses.replace(serve_cfg, prefix_cache=True)
        # prefill workers never decode (1-token budgets finish at the
        # first sampled token), so drafters are dead weight there; the
        # prefix cache doubles as the router's scoring state and the
        # export source
        pcfg = dataclasses.replace(serve_cfg, prefix_cache=True,
                                   drafter=None)
        self.decode_engines = [Engine(cfg, params, dcfg)
                               for _ in range(decode_workers)]
        self.prefill_engines = [Engine(cfg, params, pcfg)
                                for _ in range(prefill_workers)]
        self.router = KVRouter(self.prefill_engines, self.decode_engines)
        self._page = self.prefill_engines[0]._page
        self._T = self.decode_engines[0]._T
        self._queue: collections.deque = collections.deque()
        self._results: Dict[int, Request] = {}
        self._handoff: Dict[Any, Request] = {}   # (worker, worker_req_id)
        self._next_id = 0
        self._run_t0: Optional[float] = None
        self.stats: Dict[str, Any] = self._fresh_stats()

    # -- stats --------------------------------------------------------------
    @staticmethod
    def _fresh_stats() -> Dict[str, Any]:
        s = Engine._fresh_stats()
        s.update(migrated_pages=0, migrated_requests=0,
                 prefill_prefix_hits=0, prefill_prefix_tokens_reused=0,
                 router={})
        return s

    def _absorb(self, ws: Dict[str, float], decode: bool) -> None:
        """Fold one worker's per-cycle stats into the aggregate. Both
        tiers contribute prefill-side counters (decode workers still
        prefill each request's uncached tail); only decode workers
        contribute decode/token/spec/prefix-serving counters -- a prefill
        worker's discarded first tokens are not served output, and its
        radix activity is reported separately (it measures routing
        locality, not serving reuse)."""
        for k in ("prefill_s", "prefill_tokens", "prefill_groups",
                  "host_syncs", "admit_prep_s", "admit_group_cache_s",
                  "admit_prefill_s", "admit_bind_s", "admit_sync_s",
                  "admit_emit_s", "prefill_padded_tokens",
                  "queue_wait_total_s", "pre_enqueue_wait_s"):
            self.stats[k] += ws[k]
        if decode:
            for k in ("decode_s", "tokens", "chunks", "admissions",
                      "draft_tokens", "draft_accepted", "spec_rounds",
                      "prefix_hits", "prefix_tokens_reused",
                      "prefix_evictions", "prefix_insert_drops",
                      "decode_dispatch_s", "decode_sync_s",
                      "decode_emit_s", "decode_steps", "decode_slot_steps",
                      "decode_tokens", "decode_ctx_positions",
                      "decode_ring_positions", "poll_s"):
                self.stats[k] += ws[k]
        else:
            self.stats["prefill_prefix_hits"] += ws["prefix_hits"]
            self.stats["prefill_prefix_tokens_reused"] += \
                ws["prefix_tokens_reused"]
            self.stats["prefix_insert_drops"] += ws["prefix_insert_drops"]

    # -- submission ---------------------------------------------------------
    def submit(self, prompt: List[int],
               max_new_tokens: Optional[int] = None,
               on_token: Optional[Callable[[int, int], None]] = None,
               speculate: Optional[bool] = None,
               priority: int = 0,
               deadline_s: Optional[float] = None,
               on_done: Optional[Callable[[Request], None]] = None,
               arrival_t: Optional[float] = None) -> int:
        """Queue a request; same contract as ``Engine.submit`` (including
        the KV-ring bound and the SLO fields), validated eagerly so a bad
        request fails at submission, not mid-hand-off. The arrival stamp
        taken here survives the prefill->decode hand-off: the decode-tier
        submit receives it via ``arrival_t``, so TTFT measured by the
        decode worker still counts from the request's true arrival."""
        if not prompt:
            raise ValueError("empty prompt")
        budget = (self.scfg.max_new_tokens if max_new_tokens is None
                  else max_new_tokens)
        if budget < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {budget}")
        if speculate and self.scfg.drafter is None:
            raise ValueError("speculate=True needs ServeConfig.drafter")
        if (not self.cfg.sliding_window
                and len(prompt) + budget > self._T):
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({budget}) "
                f"exceeds cache_len {self._T}; raise ServeConfig.cache_len")
        if (self.scfg.max_queue > 0
                and len(self._queue) >= self.scfg.max_queue):
            raise EngineSaturated(
                "queue_full",
                f"queue holds {len(self._queue)} requests "
                f"(ServeConfig.max_queue={self.scfg.max_queue})")
        req = Request(id=self._next_id, prompt=list(prompt),
                      max_new_tokens=budget, on_token=on_token,
                      speculate=speculate, priority=int(priority),
                      deadline_s=deadline_s, on_done=on_done,
                      submit_t=(time.perf_counter() if arrival_t is None
                                else arrival_t))
        req._route = None               # (prefill worker, decode worker)
        self._next_id += 1
        self._queue.append(req)
        return req.id

    def _complete(self, req: Request) -> None:
        """Disagg-level completion point: records the result and fires the
        request's ``on_done`` exactly once (mirrors Engine._finish)."""
        already = req.done
        req.done = True
        self._results[req.id] = req
        if req.on_done is not None and not already:
            req.on_done(req)

    def cancel(self, request_id: int) -> bool:
        """Cancel a request: still queued here -> it never routes; already
        handed to a decode worker -> delegated to that worker (its slot
        frees at the next chunk boundary, streamed tokens are kept)."""
        for req in self._queue:
            if req.id == request_id:
                self._queue.remove(req)
                req.cancelled = True
                self._complete(req)
                return True
        for (dw, wid), req in self._handoff.items():
            if req.id == request_id and not req.done:
                if self.decode_engines[dw].cancel(wid):
                    req.cancelled = True
                    return True
        return False

    # -- the serving loop ---------------------------------------------------
    def _prefill_route(self, req: Request) -> Optional[int]:
        """Pick a prefill worker, or None when prefill can't help: a
        prompt without one full page exports nothing, and a prompt longer
        than the ring (windowed archs) skips insertion -- both go
        straight to a decode worker, which cold-prefills them."""
        if len(req.prompt) < self._page or len(req.prompt) > self._T:
            self.router.note_direct_decode()
            return None
        return self.router.pick_prefill(req.prompt)

    def _emit_cb(self, req: Request):
        """Wrap the user's on_token: stamp disagg-level ttft on the first
        token and re-key the callback to the DisaggEngine request id."""
        def cb(_wid: int, tok: int) -> None:
            if req.ttft_s is None:
                # measured from the request's ARRIVAL at the DisaggEngine
                # (the stamp the decode-tier submit also inherits via
                # arrival_t), not from run() entry -- same bugfix as
                # Engine._note_first_token
                if req.submit_t is not None:
                    req.ttft_s = time.perf_counter() - req.submit_t
                elif self._run_t0 is not None:
                    req.ttft_s = time.perf_counter() - self._run_t0
            if req.on_token is not None:
                req.on_token(req.id, tok)
        return cb

    def _copy_back_cb(self, req: Request):
        """on_done hook for the decode-tier request: copy the worker's
        queue-wait / deadline verdict back onto the disagg-level request
        (its ttft is already arrival-correct because the worker measured
        from the handed-off arrival_t)."""
        def cb(wreq: Request) -> None:
            req.queue_wait_s = wreq.queue_wait_s
            req.deadline_missed = wreq.deadline_missed
        return cb

    def run(self, poll: Optional[Callable[[], None]] = None
            ) -> Dict[int, List[int]]:
        """Drain the queue in waves: route -> prefill -> migrate ->
        decode. Requests submitted from ``on_token`` callbacks mid-wave
        join the next wave (same observable contract as ``Engine.run``).
        ``poll``, when given, is called once per wave so a front-end can
        inject arrivals between waves. Returns {request_id: tokens} for
        THIS cycle; stats cover this cycle only."""
        self.stats = self._fresh_stats()
        self._run_t0 = time.perf_counter()
        while True:
            if poll is not None:
                poll()
            if not self._queue:
                break
            wave = list(self._queue)
            self._queue.clear()
            # -- phase 1: route + prefill (per-worker batched admission)
            assigned: Dict[int, List[Request]] = {}
            for req in wave:
                pw = self._prefill_route(req)
                req._route = pw
                if pw is not None:
                    assigned.setdefault(pw, []).append(req)
            for pw, reqs in assigned.items():
                eng = self.prefill_engines[pw]
                for req in reqs:
                    eng.submit(list(req.prompt), max_new_tokens=1)
                eng.run()               # pure prefill: budget-1 requests
                self._absorb(eng.stats, decode=False)
                for _ in reqs:
                    self.router.note_prefill_done(pw)
            # -- phase 2: migrate + hand off, in submission order (with
            # one decode worker this preserves the exact admission order
            # a monolithic engine would see -- the temperature-parity leg)
            batches: Dict[int, List[int]] = {}
            for req in wave:
                if req.cancelled:
                    self._complete(req)
                    continue
                dw = self.router.pick_decode()
                deng = self.decode_engines[dw]
                if req._route is not None:
                    kv = self.prefill_engines[req._route].export_kv_pages(
                        req.prompt)
                    n = deng.import_kv_pages(kv)
                    self.router.note_migrated(dw, n)
                    self.stats["migrated_pages"] += n
                    self.stats["migrated_requests"] += n > 0
                # arrival_t hands the original arrival stamp across the
                # tier boundary: the decode worker's TTFT/queue-wait clock
                # keeps counting from when the user submitted, not from
                # when the hand-off happened
                wid = deng.submit(list(req.prompt),
                                  max_new_tokens=req.max_new_tokens,
                                  on_token=self._emit_cb(req),
                                  speculate=req.speculate,
                                  priority=req.priority,
                                  deadline_s=req.deadline_s,
                                  on_done=self._copy_back_cb(req),
                                  arrival_t=req.submit_t)
                self._handoff[(dw, wid)] = req
                batches.setdefault(dw, []).append(wid)
            # -- phase 3: decode (continuous batching inside each worker)
            for dw, wids in batches.items():
                deng = self.decode_engines[dw]
                res = deng.run()
                self._absorb(deng.stats, decode=True)
                self.stats["deadline_misses"] += \
                    deng.stats["deadline_misses"]
                self.stats["preemptions"] += deng.stats["preemptions"]
                for wid in wids:
                    req = self._handoff.pop((dw, wid))
                    req.tokens = list(res.get(wid, []))
                    self._complete(req)
                    self.router.note_decode_done(dw)
        done = {rid: req.tokens for rid, req in self._results.items()}
        self._finalize_stats(done)
        self._results = {}
        self._run_t0 = None
        return done

    def _finalize_stats(self, done: Dict[int, List[int]]) -> None:
        s = self.stats
        s["requests"] = s["admissions"]
        s["tokens"] = sum(len(t) for t in done.values())
        s["tok_per_s"] = (s["tokens"] / s["decode_s"]
                          if s["decode_s"] > 0 else 0.0)
        s["prefill_tok_per_s"] = (s["prefill_tokens"] / s["prefill_s"]
                                  if s["prefill_s"] > 0 else 0.0)
        ttfts = [r.ttft_s for r in self._results.values()
                 if r.ttft_s is not None]
        s["ttft_s"] = sum(ttfts) / len(ttfts) if ttfts else 0.0
        s["ttft_p50_s"] = float(np.percentile(ttfts, 50)) if ttfts else 0.0
        s["ttft_p99_s"] = float(np.percentile(ttfts, 99)) if ttfts else 0.0
        waits = [r.queue_wait_s for r in self._results.values()
                 if r.queue_wait_s is not None]
        s["queue_wait_s"] = sum(waits) / len(waits) if waits else 0.0
        s["accept_rate"] = (s["draft_accepted"] / s["draft_tokens"]
                            if s["draft_tokens"] > 0 else 0.0)
        s["router"] = self.router.snapshot()

    # -- public API ---------------------------------------------------------
    def generate(self, prompts: List[List[int]]) -> List[List[int]]:
        """Generate completions for a batch of prompts through the
        disaggregated path. Resets every worker's scheduler/PRNG state
        (call-to-call determinism, and the exact discipline under which
        routed output is token-identical to ``Engine.generate`` with the
        same ServeConfig); radix trees and page pools persist, so repeat
        workloads stay warm."""
        if self._queue:
            raise RuntimeError(
                f"{len(self._queue)} submitted request(s) pending; call "
                "run() to drain them before generate() (which resets)")
        for eng in self.prefill_engines + self.decode_engines:
            eng._reset()
        ids = [self.submit(list(p)) for p in prompts]
        res = self.run()
        return [res[i] for i in ids]
