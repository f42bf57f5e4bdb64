"""Continuous-batching serving engine with a fully on-device decode loop
and a batched, chunked prefill pipeline.

The paper's end-to-end number is serving throughput, and at that scale the
bottleneck is not the MatMul but host round-trips and under-filled batches
(LlamaF, arXiv:2409.11424).  This engine therefore keeps both phases busy:

* ``decode chunk``: one jitted program runs up to ``decode_chunk`` decode
  steps inside a ``jax.lax.while_loop`` -- sampling, EOS masking, per-slot
  token-budget accounting and position bookkeeping are all arrays in the
  loop carry.  The host sees one sync per *chunk*, not per token, so host
  syncs per generated sequence are O(1).
* ``batched chunked prefill``: at each chunk boundary the scheduler drains
  up to ``prefill_batch`` queued requests into the free slots at once,
  right-pads their prompts to a shared bucketed length, and feeds them
  through ONE jitted ``transformer.prefill_chunk`` program per fixed
  (group, chunk) shape.  A length mask keeps padding out of the KV ring
  and out of the sampled first token; prompts longer than
  ``prefill_chunk`` stream through the same program chunk by chunk, so
  prefill compilations are O(#buckets), not O(#distinct prompt lengths).
  All resulting caches scatter into their slots in a single
  ``transformer.cache_set_slots`` call.  Recurrent families (ssm/hybrid)
  ride the SAME batched path: padding columns are identity on the
  conv/SSM state (``transformer._recurrent_chunk`` zeroes their dt and
  gathers each row's conv tail at its last real column), and the chunk
  grid is FIXED (``prefill_chunk`` clamped down to divide the ring) so
  every prompt sees the same absolute chunk boundaries -- which makes
  batched admission bit-identical to sequential admission and lets ONE
  compiled (group, chunk) program serve every prompt length.
* ``continuous batching``: when a sequence finishes (EOS, budget, or
  ``cancel``), its slot is freed and queued requests are admitted between
  chunks -- no recompilation.  Dead slots still run the math (static
  shapes) but a live mask keeps them from touching their cache
  (``decode_step(live=)``).
* ``streaming``: each request may carry an ``on_token`` callback; tokens
  are delivered after every chunk (and the first token at admission).
* ``speculative decoding``: with a ``Drafter`` configured (drafters.py:
  n-gram prompt lookup, or a truncated-layer self-draft over the same
  quantized weights), the decode chunk becomes draft -> verify -> accept
  rounds: k drafted tokens are scored per slot in one fused verify pass,
  the longest correct prefix is accepted (greedy) or rejection-sampled
  (temperature), and the ring rows written for rejected drafts are
  restored from a pre-verify snapshot (``cache_ring_rewind``). All of it
  rides the jitted while_loop carry -- still ONE host sync per chunk --
  and every decision is per-slot, so a continuous batch freely mixes
  speculative and plain sequences (``submit(speculate=...)``).
  ``draft_verify="scan"`` (default) replays decode_step per column and
  makes greedy speculative output BIT-identical to plain decode;
  ``"batched"`` scores the block in one masked prefill-style forward
  (throughput datapath, equal to within float rounding).
* ``tensor parallelism`` (``tp=N``): every jitted program above runs via
  ``shard_map`` over a ("model",) mesh. Weights shard lane-only (packed
  QTensor payload lanes / attention heads / the ffn hidden; K rows stay
  whole per shard so super-blocks never straddle devices), the KV cache
  and prefix-cache page pool shard over kv_heads, and each projection
  pays ONE collective (a tiled lane all-gather of disjoint blocks --
  exact). The default "padded" matmul datapath keeps every
  gemm at the single-device program shape (off-shard lanes zero-embedded
  -- exact), so serving output is TOKEN-IDENTICAL across tp degrees, in
  fp32 and quantized, with speculation and the prefix cache on
  (tests/test_tp_serving.py); "sliced" trades that bitwise parity for
  1/N per-shard FLOPs. Host-side scheduling is mesh-oblivious; the
  ``generate_reference``/``generate_spec_reference`` oracles run their
  plain jitted programs over the sharded params via GSPMD (correct, but
  compare them at tp=1 where they are the pinned bitwise oracle).
* ``prefix caching`` (``prefix_cache=True``): a host-side radix tree over
  token-ID prefixes maps to a refcounted device page pool
  (serving/prefix_cache.py). Admission matches each queued request's
  longest cached prefix, scatters those pages into its group-cache row
  (bit-for-bit KV copies, copy-on-write for partial-page hits) and runs
  chunked prefill only over the uncached suffix; freshly computed prompt
  pages are inserted back, with LRU eviction of zero-ref (childless)
  pages under a byte budget. Greedy output is token-identical to running
  with the cache off, and admission still costs ONE host sync per group.
  Recurrent families cache CHECKPOINTS instead of positional pages: the
  page size is pinned to the prefill chunk, each pool page stores the
  whole conv/SSM state after its last token (the inter-chunk carry the
  chunk loop already materializes -- zero extra compute, bit-identical
  to cold by construction), and a warm admission restores the checkpoint
  at the group's shared full-page horizon and prefills only the suffix
  (hybrid additionally scatters the ring pages below that horizon).
* ``family adapters`` (models/state.py): which family supports which
  feature lives in ONE capability table (``FamilyCaps``) checked by ONE
  validation pass (``validate_serve_features``) at construction, and the
  engine drives every cache operation through a ``DecodeState`` adapter
  instead of ad-hoc ``cfg.family`` string checks.

``generate_reference`` keeps the pre-rewrite host-driven loop (one jitted
step per token, same math) for parity tests and as readable documentation
of the device loop's semantics; ``generate_spec_reference`` does the same
for the speculative path with the acceptance/rollback bookkeeping
re-implemented in numpy (the rejection-sampling oracle).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.distributed import sharding as SH
from repro.models import transformer as T
from repro.models.state import (DecodeState, KV_FAMILIES,
                                validate_serve_features)
from repro.serving.drafters import make_drafter
from repro.serving.prefix_cache import PrefixCache

# re-export: the KV-ring family set now lives in the capability table
# (models/state.py); serving/disagg.py and tests import it from here
_KV_FAMILIES = KV_FAMILIES


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32            # per-request default token budget
    temperature: float = 0.0            # 0 -> greedy
    eos_id: Optional[int] = None
    cache_len: int = 256                # KV ring length (fixed at compile)
    seed: int = 0
    max_slots: int = 4                  # concurrent batch slots
    decode_chunk: int = 32              # device-loop steps per host sync
    prefill_bucket: int = 16            # prompt pad granularity (attention)
    prefill_batch: int = 8              # max requests per prefill group
    prefill_chunk: int = 64             # tokens per prefill chunk
    # speculative decoding (None = off; "ngram" | "self", drafters.py)
    drafter: Optional[str] = None
    draft_k: int = 4                    # drafted tokens per verify round
    draft_layers: int = 2               # "self": target-model prefix depth
    draft_ngram: int = 2                # "ngram": match gram length
    draft_hist: int = 64                # "ngram": history ring length
    draft_verify: str = "scan"          # "scan" (bit-exact vs plain decode)
                                        # | "batched" (one masked forward)
    # prefix cache (radix tree over token-ID prefixes; admission reuses
    # the longest cached prefix and prefills only the suffix -- greedy
    # output stays token-identical to a cold prefill). KV families page
    # the ring; recurrent families checkpoint conv/SSM state at prefill
    # chunk boundaries (page size == prefill_chunk there).
    prefix_cache: bool = False
    prefix_page: int = 16               # positions per page (clamped to a
                                        # divisor of the KV ring length;
                                        # recurrent families override it
                                        # with the prefill chunk)
    prefix_bytes: int = 64 << 20        # device byte budget for the pool
    # tensor parallelism: run every jitted serving program via shard_map
    # over a ("model",) mesh of this many devices. Lane-only sharding
    # (packed payload lanes / heads / ffn hidden over the mesh, K rows
    # whole per shard) + one exact lane all-gather per projection
    # keeps greedy output token-identical across tp degrees (see
    # distributed/sharding.py). CPU testing: export
    # XLA_FLAGS=--xla_force_host_platform_device_count=N first.
    # SLO-aware admission: with max_queue > 0, submit() rejects instead of
    # growing the queue without bound (EngineSaturated, reason
    # "queue_full"); with the prefix cache on it also rejects when the
    # queued prompts' combined KV-page demand exceeds the whole page pool
    # ("page_pool_saturated" -- admission would thrash the pool). 0 keeps
    # the historical unbounded-queue behavior.
    max_queue: int = 0
    # preempt-by-slot: when every slot is busy and the queue head has
    # STRICTLY higher priority than some running request, cancel the
    # lowest-priority (then youngest) victim to free its slot. Equal
    # priorities never preempt, so single-priority workloads (the parity-
    # pinned default) are unaffected.
    preempt: bool = False
    tp: int = 1
    tp_matmul: str = "padded"           # "padded" (bit-exact vs tp=1: the
                                        # gemm keeps the single-device
                                        # shape; weights/cache sharded,
                                        # FLOPs replicated) | "sliced"
                                        # (1/size FLOPs per shard, equal
                                        # to within an f32 ulp) |
                                        # "sliced_row" (sliced + row-
                                        # parallel o-/down-proj: half the
                                        # collectives per layer, equal to
                                        # within ~a few activation-dtype
                                        # ulps -- f32-ulp when the model
                                        # runs f32; the fast path on
                                        # collective-bound meshes)
    tp_ep: bool = True                  # MoE expert parallelism under tp:
                                        # shard the expert stacks over the
                                        # model axis when n_experts
                                        # divides tp (bit-identical to the
                                        # replicated path; see
                                        # distributed/sharding.py). False
                                        # forces replicated experts.


@dataclasses.dataclass
class KVPages:
    """Host-memory snapshot of a prompt's full KV pages, the hand-off
    unit for disaggregated serving (serving/disagg.py): bit-for-bit
    copies of page-pool rows (all layers, int8 scales included), plus
    the token ids they cover so the receiving engine can re-key them in
    its own radix tree. ``payload`` entries are pool-layout arrays with
    the page axis at dim 1 -- e.g. ``k``: (L, n_pages, page, KH, Dh) --
    where page q covers positions [q*page, (q+1)*page)."""
    page: int
    tokens: List[int]
    payload: Dict[str, np.ndarray]

    @property
    def n_pages(self) -> int:
        return len(self.tokens) // self.page


class EngineSaturated(RuntimeError):
    """submit() backpressure rejection (ServeConfig.max_queue > 0).

    ``reason`` is machine-readable -- "queue_full" (the bounded queue is
    at capacity) or "page_pool_saturated" (the queued prompts' combined
    KV-page demand already exceeds the prefix-cache pool, so admitting
    more would only thrash it) -- and ``detail`` is the human-readable
    explanation. Front-ends map this to a structured 429."""

    def __init__(self, reason: str, detail: str):
        super().__init__(f"{reason}: {detail}")
        self.reason = reason
        self.detail = detail


@dataclasses.dataclass
class Request:
    id: int
    prompt: List[int]
    max_new_tokens: int
    on_token: Optional[Callable[[int, int], None]] = None
    speculate: bool = False
    priority: int = 0                   # higher drains first
    deadline_s: Optional[float] = None  # TTFT SLO, relative to submit_t
    on_done: Optional[Callable[["Request"], None]] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    preempted: bool = False             # cancelled to free its slot for a
                                        # strictly-higher-priority request
    deadline_missed: bool = False       # first token landed past deadline
    submit_t: Optional[float] = None    # perf_counter at submit() -- the
                                        # arrival stamp TTFT is measured
                                        # from (survives the disagg
                                        # prefill->decode hand-off via
                                        # submit(arrival_t=))
    enqueue_t: Optional[float] = None   # perf_counter inside submit():
                                        # when this engine got the request
                                        # (after submit_t with arrival_t)
    ttft_s: Optional[float] = None      # first token - submit_t
    queue_wait_s: Optional[float] = None  # submit -> prefill start

    def _emit(self, tok: int) -> None:
        self.tokens.append(tok)
        if self.on_token is not None:
            self.on_token(self.id, tok)


class _Span:
    """A named host span of the engine and its consecutive children.

    The span and each child are ``jax.profiler.TraceAnnotation``s: the
    profiler records them while it traces, on the clock of the device
    planes, and otherwise they cost about a microsecond. ``phase`` ends
    the open child and starts the next at ONE clock read and returns
    that read, so children cover the span's extent without gaps. Each
    child adds its duration to ``stats[<key>]``, where the key is its
    name without ``engine.``, dots as underscores, plus ``_s``
    (``engine.admit.sync`` -> ``admit_sync_s``); a span made with
    ``timed=True`` adds its own duration the same way on exit."""

    def __init__(self, stats: Dict[str, float], name: str,
                 timed: bool = False):
        self._stats, self._name, self._timed = stats, name, timed
        self._child = None

    @staticmethod
    def _key(name: str) -> str:
        return name[len("engine."):].replace(".", "_") + "_s"

    @staticmethod
    def recording() -> bool:
        """True while the profiler traces: build span arguments only
        then. An id list renders as "[1, 2]" (the trace reader takes a
        bare "1,2" for the number 1)."""
        return jax.profiler.TraceAnnotation.is_enabled()

    def args(self, **kw) -> None:
        self._ann.set_metadata(**kw)

    def __enter__(self) -> "_Span":
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = self._t = time.perf_counter()
        return self

    def phase(self, child: Optional[str] = None) -> float:
        """End the open child, start ``<name>.<child>`` (none when
        ``child`` is None); returns the clock read between the two."""
        now = time.perf_counter()
        if self._child is not None:
            ann, key = self._child
            ann.__exit__(None, None, None)
            self._stats[key] += now - self._t
        self._t, self._child = now, None
        if child is not None:
            name = f"{self._name}.{child}"
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            self._child = (ann, self._key(name))
        return now

    def __exit__(self, *exc) -> None:
        now = self.phase()
        if self._timed:
            self._stats[self._key(self._name)] += now - self._t0
        self._ann.__exit__(*exc)


class Engine:
    def __init__(self, cfg: ModelConfig, params, serve_cfg: ServeConfig):
        for field in ("max_slots", "decode_chunk", "max_new_tokens",
                      "cache_len", "prefill_batch", "prefill_chunk", "tp"):
            if getattr(serve_cfg, field) < 1:
                raise ValueError(f"ServeConfig.{field} must be >= 1, got "
                                 f"{getattr(serve_cfg, field)}")
        self.cfg = cfg
        self.params = params
        self.scfg = serve_cfg
        self._B = serve_cfg.max_slots
        # ring length must match init_cache's clamp or slot scatter would
        # write a cache_len-long update into a window-long ring
        self._T = T.attn_cache_len(cfg, serve_cfg.cache_len)
        # ONE validation pass over the family x feature matrix replaces
        # the old scattered per-feature "needs a KV-ring family" gates
        self._caps = validate_serve_features(
            cfg, tp=serve_cfg.tp, drafter=serve_cfg.drafter is not None,
            prefix_cache=serve_cfg.prefix_cache)
        self._state = DecodeState(cfg)
        self._kv_family = self._caps.kv_ring
        # prefill chunk length. Recurrent families pin a FIXED chunk grid
        # (clamped down to a divisor of the ring): the SSD scan's numerics
        # depend on chunk-boundary placement, so a shared absolute grid is
        # what makes batched prefill bit-identical to sequential admission
        # and warm (checkpoint) admission bit-identical to cold -- and it
        # means ONE compiled program serves every prompt length
        chunk = max(1, min(serve_cfg.prefill_chunk, self._T))
        if self._caps.recurrent:
            while self._T % chunk:
                chunk -= 1
        self._chunk = chunk
        # -- tensor parallelism: a ("model",) mesh every jitted serving
        # program runs over via shard_map. Weights lane-shard (K whole
        # per shard -- packed super-blocks never straddle devices), the
        # KV cache shards over kv_heads, and each projection's output is
        # assembled by one exact lane all-gather, so greedy output
        # stays token-identical across tp degrees.
        self._mesh = None
        self._plan = SH.make_serve_tp_plan(cfg, 1,
                                           matmul=serve_cfg.tp_matmul)
        if serve_cfg.tp > 1:
            devs = jax.devices()
            if len(devs) < serve_cfg.tp:
                raise ValueError(
                    f"tp={serve_cfg.tp} needs {serve_cfg.tp} devices but "
                    f"jax sees {len(devs)}; on CPU export XLA_FLAGS="
                    "--xla_force_host_platform_device_count="
                    f"{serve_cfg.tp} before importing jax")
            self._plan = SH.make_serve_tp_plan(cfg, serve_cfg.tp,
                                               matmul=serve_cfg.tp_matmul,
                                               params=params,
                                               ep=serve_cfg.tp_ep)
            self._mesh = Mesh(np.asarray(devs[:serve_cfg.tp]),
                              (self._plan.axis,))
            self._pspecs = SH.serve_param_specs(params, self._plan)
            self.params = jax.device_put(
                params, SH.named(self._pspecs, self._mesh))
            ctmpl = jax.eval_shape(
                lambda: T.init_cache(cfg, self._B, self._T))
            self._cspecs = SH.serve_cache_specs(ctmpl, self._plan)
        self._drafter = None
        if serve_cfg.drafter is not None:
            if serve_cfg.draft_k < 1:
                raise ValueError("draft_k must be >= 1")
            if serve_cfg.draft_k + 1 > serve_cfg.decode_chunk:
                raise ValueError(
                    f"decode_chunk ({serve_cfg.decode_chunk}) must fit a "
                    f"whole verify round (draft_k + 1 = "
                    f"{serve_cfg.draft_k + 1}) or speculating slots can "
                    "never emit")
            if serve_cfg.draft_k + 1 > self._T:
                raise ValueError(
                    f"draft_k + 1 ({serve_cfg.draft_k + 1}) exceeds the KV "
                    f"ring ({self._T}); draft positions must map to "
                    "distinct ring rows")
            if serve_cfg.draft_verify not in ("scan", "batched"):
                raise ValueError(
                    f"draft_verify must be 'scan' or 'batched', got "
                    f"{serve_cfg.draft_verify!r}")
            self._drafter = make_drafter(serve_cfg.drafter, cfg, serve_cfg)
            P0 = jax.sharding.PartitionSpec()
            dspec = jax.tree.map(lambda _: P0,
                                 self._drafter.init_state_np(self._B))
            self._spec_chunk = self._tp_jit(
                self._spec_chunk_impl,
                rest_in=("cache",) + (P0,) * 7 + (dspec,),
                out_specs=("cache",) + (P0,) * 6 + (dspec,) + (P0,) * 3,
                donate=(1,))
            self._verify = jax.jit(self._verify_impl)
            self._propose_ref = jax.jit(
                lambda params, cache, ds, tok, pos, act:
                self._drafter.propose(params, self.cfg, cache, ds, tok,
                                      pos, act))
        self._prefix: Optional[PrefixCache] = None
        self._page: Optional[int] = None
        if serve_cfg.prefix_cache:
            if serve_cfg.prefix_page < 1:
                raise ValueError("prefix_page must be >= 1")
            if self._caps.prefix_mode == "checkpoints":
                # recurrent checkpoint pages: one pool row holds the
                # WHOLE conv/SSM state after the page's last token.
                # Pinning the page to the prefill chunk makes every
                # checkpoint exactly the inter-chunk carry the chunk
                # loop materializes anyway -- zero extra compute, and
                # warm restore is bit-identical to cold by construction
                page = self._chunk
            else:
                # pages must tile the ring exactly so a page never wraps
                # internally (position p % T stays page-contiguous)
                page = max(1, min(serve_cfg.prefix_page, self._T))
                while self._T % page:
                    page -= 1
            self._page = page
            cap = max(2, int(serve_cfg.prefix_bytes)
                      // self._state.page_bytes(page))
            self._prefix = PrefixCache(page, cap)
            self._pool = None           # device pool, allocated on 1st use
            self._prefix_scatter = jax.jit(self._prefix_scatter_impl,
                                           donate_argnums=(0,))
            self._prefix_insert = jax.jit(self._prefix_insert_impl,
                                          donate_argnums=(0,))
            if self._caps.prefix_mode == "checkpoints":
                self._state_scatter = jax.jit(self._state_scatter_impl,
                                              donate_argnums=(0,))
                self._state_insert = jax.jit(self._state_insert_impl,
                                             donate_argnums=(0,))
            # cross-engine page hand-off (export_kv_pages/import_kv_pages):
            # the same pool-copy programs, pointed at host memory
            self._pool_export = jax.jit(self._pool_export_impl)
            self._pool_import = jax.jit(self._pool_import_impl,
                                        donate_argnums=(0,))
        # (the group cache is NOT donated here: its (L,G,T,..) buffers can
        # never alias the (L,B,T,..) output, they'd just warn)
        self._admit_caches = jax.jit(self._admit_caches_impl,
                                     donate_argnums=(0,))
        P0 = jax.sharding.PartitionSpec()
        self._prefill_chunk = self._tp_jit(
            self._prefill_chunk_impl, rest_in=("cache",) + (P0,) * 5,
            out_specs=("cache", P0), donate=(1, 5))
        self._sample_first = jax.jit(self._sample_first_impl)
        self._bind_slots = jax.jit(self._bind_slots_impl)
        self._decode_chunk = self._tp_jit(
            self._decode_chunk_impl, rest_in=("cache",) + (P0,) * 6,
            out_specs=("cache",) + (P0,) * 6, donate=(1,))
        self._ref_step = jax.jit(self._ref_step_impl)
        self._cache = None
        self.stats: Dict[str, float] = {}
        self._reset()

    def _tp_jit(self, fn, rest_in, out_specs, donate=()):
        """jit, or jit(shard_map(...)) when a TP mesh is configured.

        ``fn`` must take ``params`` first; ``rest_in``/``out_specs`` are
        PartitionSpec pytrees for the remaining args/outputs, with the
        sentinel string "cache" standing for the decode-cache spec tree.
        Inside the shard the params pytree holds lane-local views
        (QTensor aux shapes relocalized to the lanes this shard owns) and
        the serve-TP plan is active, so layer code slices its local head
        counts and places the per-projection lane gathers."""
        if self._mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        plan, pspecs = self._plan, self._pspecs
        sub = lambda s: self._cspecs if isinstance(s, str) else s
        rest_in = tuple(sub(s) for s in rest_in)
        out_specs = tuple(sub(s) for s in out_specs)

        def body(params, *rest):
            params = SH.localize_serve_params(params, pspecs, plan.size)
            with SH.serve_tp(plan):
                return fn(params, *rest)

        return jax.jit(
            jax.shard_map(body, mesh=self._mesh,
                          in_specs=(pspecs,) + rest_in,
                          out_specs=out_specs, check_vma=False),
            donate_argnums=donate)

    def _new_cache(self, B: int):
        """Fresh decode cache for ``B`` slots, placed with the TP cache
        sharding (KV payloads over kv_heads) when a mesh is configured so
        donation aliases shard-to-shard instead of warning."""
        cache = self._state.init(B, self._T)
        if self._mesh is not None:
            cache = jax.device_put(cache,
                                   SH.named(self._cspecs, self._mesh))
        return cache

    # -- jitted internals ----------------------------------------------------
    def _sample(self, logits, key):
        """logits (B,V) -> token ids (B,) int32."""
        if self.scfg.temperature > 0:
            return jax.random.categorical(
                key, logits / self.scfg.temperature).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _admit_caches_impl(self, cache, group_cache, indices):
        return self._state.set_slots(cache, group_cache, indices)

    def _prefix_scatter_impl(self, gcache, pool, idx, rows, cols,
                             positions):
        """Copy pool pages ``idx`` (n,) into group-cache rows ``rows`` at
        ring slots ``cols`` (n, page), stamping ``positions``. Entries
        with cols >= T drop (batch padding / partial-page tails). Only
        ring-payload pool entries scatter here; a recurrent pool's
        conv/state checkpoints go through _state_scatter_impl instead
        (they are whole-state rows, not positional pages)."""
        keys = set(T._PAGE_KEYS)
        pages = {k: v[:, idx] for k, v in pool.items() if k in keys}
        return self._state.scatter_pages(gcache, pages, rows, cols,
                                         positions)

    def _prefix_insert_impl(self, pool, gcache, idx, rows, cols):
        """Copy freshly prefilled pages out of the group cache into pool
        rows ``idx`` (n,); idx >= capacity drops (batch padding). Pool
        entries the ring gather does not produce (a recurrent pool's
        conv/state checkpoints) pass through untouched."""
        pages = self._state.gather_pages(gcache, rows, cols)
        return {k: (pool[k].at[:, idx].set(pages[k], mode="drop")
                    if k in pages else pool[k])
                for k in pool}

    def _state_scatter_impl(self, gcache, pool, idx, rows):
        """Restore recurrent checkpoints: pool page rows ``idx`` (n,)
        into group-cache batch rows ``rows`` (n,); rows >= G drop."""
        return self._state.scatter_checkpoints(gcache, pool, idx, rows)

    def _state_insert_impl(self, pool, gcache, rows, idx):
        """Record recurrent checkpoints: group-cache batch rows ``rows``
        (n,) into pool page rows ``idx`` (n,); idx >= capacity drops."""
        return self._state.insert_checkpoints(pool, gcache, rows, idx)

    def _pool_export_impl(self, pool, idx):
        """Gather pool pages ``idx`` (n,) for a cross-engine hand-off --
        pure data movement, the export half of the disaggregation page
        migration (the host copy happens in export_kv_pages)."""
        return {k: v[:, idx] for k, v in pool.items()}

    def _pool_import_impl(self, pool, pages, idx):
        """Scatter imported pages into pool rows ``idx`` (n,) -- the
        cross-engine twin of _prefix_insert_impl, sourced from another
        engine's exported pages instead of a local group cache."""
        return {k: (pool[k].at[:, idx].set(pages[k].astype(pool[k].dtype))
                    if k in pages else pool[k])
                for k in pool}

    def _prefill_chunk_impl(self, params, gcache, tokens, start, lengths,
                            last_logits, cached):
        """One (G, C) prefill chunk + ragged last-token logit capture.

        ``start`` is traced, so every chunk index reuses one compilation.
        ``last_logits`` accumulates each row's logits at its true last
        prompt token (rows whose last token is not in this chunk pass
        through); the LM head runs on ONE gathered row per sequence, never
        on the full (G, C, V) block. ``cached`` (G,) marks each row's
        prefix-cache horizon: columns below it are already resident in
        the ring and are masked out of compute exactly like padding."""
        C = tokens.shape[1]
        h, gcache = T.prefill_chunk(params, self.cfg, gcache, tokens=tokens,
                                    start=start, lengths=lengths,
                                    cached_lengths=cached)
        last = lengths - 1
        off = jnp.clip(last - start, 0, C - 1)
        hr = jnp.take_along_axis(h, off[:, None, None], axis=1)[:, 0]
        logits = T.lm_logits(params, self.cfg, hr)          # (G, V) f32
        sel = (last >= start) & (last < start + C)
        return gcache, jnp.where(sel[:, None], logits, last_logits)

    def _sample_first_impl(self, last_logits, keys):
        """Per-row first-token sampling with per-request keys: row i uses
        the key the sequential path would have split for request i, so
        batched admission is token-identical to one-at-a-time admission."""
        samp = lambda lg, key: self._sample(lg[None], key)[0]
        return jax.vmap(samp)(last_logits, keys)

    def _bind_slots_impl(self, first, budgets, free_arr):
        """Device-side slot binding for a prefill group: rows that already
        finished at their first token (budget 1 / instant EOS; dummy rows
        carry budget 0) take NO slot, and survivors pack into ``free_arr``
        in group order -- the exact layout one-at-a-time admission yields
        (a slot's row index feeds the shared decode sampling key, so
        layout parity is what keeps batched admission token-identical
        under temperature). Returns scatter indices, out-of-range where
        unbound. On device so the cache scatter can be dispatched BEFORE
        the host syncs on the first tokens."""
        fin = budgets <= 1
        if self.scfg.eos_id is not None:
            fin = fin | (first == self.scfg.eos_id)
        alive = ~fin
        rank = jnp.cumsum(alive.astype(jnp.int32)) - 1
        nfree = free_arr.shape[0]
        return jnp.where(alive, free_arr[jnp.clip(rank, 0, nfree - 1)],
                         self._B)

    def _decode_chunk_impl(self, params, cache, tok, pos, live, n_gen,
                           budget, key):
        """Run up to ``decode_chunk`` decode steps on device.

        Carry: (step, cache, tok (B,), pos (B,), live (B,) bool,
        n_gen (B,), out (B,C), key).  Exits early once every slot is dead.
        ``out`` holds the tokens emitted this chunk, -1 where a slot was
        already dead at that step (so each row is a dense prefix).
        """
        C = self.scfg.decode_chunk
        B = tok.shape[0]
        out0 = jnp.full((B, C), -1, jnp.int32)

        def cond(st):
            step, _, _, _, live_, _, _, _ = st
            return (step < C) & jnp.any(live_)

        def body(st):
            step, cache_, tok_, pos_, live_, n_gen_, out_, key_ = st
            logits, cache_ = T.decode_step(params, self.cfg, cache_,
                                           tokens=tok_, position=pos_,
                                           live=live_)
            with jax.named_scope("sample"):
                key_, sub = jax.random.split(key_)
                nxt = self._sample(logits, sub)
                nxt = jnp.where(live_, nxt, tok_)
            out_ = out_.at[:, step].set(jnp.where(live_, nxt, -1))
            n_gen_ = n_gen_ + live_.astype(jnp.int32)
            new_live = live_ & (n_gen_ < budget)
            if self.scfg.eos_id is not None:
                new_live = new_live & (nxt != self.scfg.eos_id)
            pos_ = pos_ + live_.astype(jnp.int32)
            return step + 1, cache_, nxt, pos_, new_live, n_gen_, out_, key_

        st = (jnp.zeros((), jnp.int32), cache, tok, pos, live, n_gen,
              out0, key)
        _, cache, tok, pos, live, n_gen, out, key = jax.lax.while_loop(
            cond, body, st)
        return cache, out, tok, pos, live, n_gen, key

    # -- speculative decode (draft -> verify -> accept -> rewind) ------------
    def _verify_impl(self, params, cache, tokens, positions, valid):
        """One verify pass over a (B, k+1) block -> (logits, cache).

        ``draft_verify="scan"`` (default) replays decode_step per column
        -- bit-identical numbers to plain decode, the basis of the greedy
        parity guarantee. ``"batched"`` scores the block in one masked
        prefill-style forward -- the throughput datapath, equal to within
        float rounding (a greedy argmax can flip on a near-tie)."""
        if self.scfg.draft_verify == "scan":
            return T.verify_scan(params, self.cfg, cache, tokens=tokens,
                                 positions=positions, valid=valid)
        h, cache = T.verify_chunk(params, self.cfg, cache, tokens=tokens,
                                  positions=positions, valid=valid)
        return T.lm_logits(params, self.cfg, h), cache

    def _accept_impl(self, logits, drafts, spec_eff, k_u, k_fin):
        """Per-slot draft acceptance. logits (B, k+1, V) scored over
        [cur_tok, d_1..d_k]; drafts (B, k); spec_eff (B,) marks slots that
        actually speculated this round (others accept 0 drafts and their
        "final" token is a plain col-0 sample/argmax).

        Greedy: accept the longest prefix where d_j == argmax; final token
        is the argmax after the last accepted draft (replacement on first
        mismatch, bonus when all k accepted) -- exactly the token chain
        plain greedy decode would emit, which is the parity guarantee.

        Temperature: rejection sampling against the point-mass draft
        distribution: accept d_j with prob p_j(d_j); on first rejection
        sample from p with the rejected draft's mass removed
        (renormalized); on full acceptance sample the bonus from p_k."""
        B, S, V = logits.shape
        k = S - 1
        if self.scfg.temperature > 0:
            lt = (logits / self.scfg.temperature).astype(jnp.float32)
            p = jax.nn.softmax(lt[:, :k], axis=-1)          # (B, k, V)
            pd = jnp.take_along_axis(p, drafts[:, :, None], 2)[..., 0]
            u = jax.random.uniform(k_u, (B, k))
            ok = (u < pd) & spec_eff[:, None]
            acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), 1), 1)
            pl = jnp.take_along_axis(lt, acc[:, None, None], 1)[:, 0]
            pcol = jax.nn.softmax(pl, axis=-1)              # (B, V)
            dcol = jnp.take_along_axis(
                drafts, jnp.clip(acc, 0, k - 1)[:, None], 1)[:, 0]
            rejected = spec_eff & (acc < k)
            onehot = jnp.arange(V)[None] == dcol[:, None]
            resid = jnp.where(rejected[:, None] & onehot, 0.0, pcol)
            lr = jnp.where(resid > 0, jnp.log(resid), -jnp.inf)
            fin = jax.random.categorical(k_fin, lr).astype(jnp.int32)
            # degenerate guard: p put (numerically) ALL mass on the draft
            fin = jnp.where(jnp.any(resid > 0, -1), fin, dcol)
            return acc, fin
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, S)
        ok = (drafts == g[:, :k]) & spec_eff[:, None]
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), 1), 1)
        fin = jnp.take_along_axis(g, acc[:, None], 1)[:, 0]
        return acc, fin

    def _spec_chunk_impl(self, params, cache, tok, pos, live, spec, n_gen,
                         budget, key, dstate):
        """Speculative decode chunk: verify rounds inside one device loop.

        Each round drafts k tokens per speculating slot, snapshots the
        ring rows the draft block will write, scores [cur, d_1..d_k] in
        ONE masked verify forward, accepts a per-slot prefix, rewinds the
        rejected writes, and scatters the accepted tokens into the
        per-slot output at that slot's own cursor. Non-speculating live
        slots ride the same program as 1-column plain decode steps, so a
        continuous batch freely mixes speculative and plain sequences.
        The host still sees ONE sync per chunk.

        ``out`` rows are dense prefixes (-1 beyond each cursor); a slot
        pauses (stays live, stops emitting) when a whole verify round no
        longer fits its remaining chunk capacity."""
        C = self.scfg.decode_chunk
        k = self.scfg.draft_k
        S = k + 1
        B = tok.shape[0]
        Tring = self._T
        eos = self.scfg.eos_id
        cols = jnp.arange(S, dtype=jnp.int32)[None]
        bidx = jnp.arange(B)[:, None]
        out0 = jnp.full((B, C), -1, jnp.int32)
        zero = jnp.zeros((), jnp.int32)

        def spec_now(pos_):
            # full-attention archs must not let draft positions wrap the
            # ring (overwritten rows are still needed); slots within k of
            # the ring end fall back to plain steps for their last tokens
            return (spec if self.cfg.sliding_window
                    else spec & (pos_ + k < Tring))

        def active(live_, nout_, pos_):
            need = jnp.where(spec_now(pos_), S, 1)
            return live_ & (nout_ + need <= C)

        def cond(st):
            _, _, pos_, live_, _, _, nout_, _, _, _, _, _ = st
            return jnp.any(active(live_, nout_, pos_))

        def body(st):
            (cache_, tok_, pos_, live_, n_gen_, out_, nout_, key_, ds_,
             dtot_, dacc_, rounds_) = st
            act = active(live_, nout_, pos_)
            spec_ok = spec_now(pos_)
            spec_eff = act & spec_ok
            key_, k_u, k_fin = jax.random.split(key_, 3)
            drafts, ds_ = self._drafter.propose(
                params, self.cfg, cache_, ds_, tok_, pos_, spec_eff)
            x = jnp.concatenate([tok_[:, None], drafts], axis=1)   # (B,S)
            positions = pos_[:, None] + cols
            valid = act[:, None] & ((cols == 0) | spec_eff[:, None])
            slots = positions % Tring
            snap = self._state.ring_snapshot(cache_, slots)
            logits, cache_ = self._verify_impl(params, cache_, x,
                                               positions, valid)
            acc, fin = self._accept_impl(logits, drafts, spec_eff,
                                         k_u, k_fin)
            # emitted block: accepted drafts then the final token
            draftsp = jnp.concatenate([drafts, drafts[:, -1:]], 1)
            emit = jnp.where(cols < acc[:, None], draftsp, fin[:, None])
            e = jnp.minimum(acc + 1, budget - n_gen_)
            if eos is not None:
                hit = (emit == eos) & (cols < e[:, None])
                has = jnp.any(hit, axis=1)
                first = jnp.argmax(hit, axis=1).astype(jnp.int32)
                e = jnp.where(has, jnp.minimum(e, first + 1), e)
            e = jnp.where(act, e, 0)
            # per-slot scatter at each row's own cursor
            osel = jnp.where(cols < e[:, None], nout_[:, None] + cols, C)
            out_ = out_.at[bidx, osel].set(emit, mode="drop")
            # un-write rejected draft entries (t0 + acc accepted ones stay)
            keep = jnp.where(act, 1 + acc, 0)
            cache_ = self._state.ring_rewind(cache_, snap, slots, keep)
            n_gen_ = n_gen_ + e
            pos_ = pos_ + e
            last = jnp.take_along_axis(
                emit, jnp.clip(e - 1, 0, S - 1)[:, None], 1)[:, 0]
            tok_ = jnp.where(e > 0, last, tok_)
            died = n_gen_ >= budget
            if eos is not None:
                died = died | jnp.any((emit == eos) & (cols < e[:, None]),
                                      axis=1)
            live_ = jnp.where(act, live_ & ~died, live_)
            nout_ = nout_ + e
            ds_ = self._drafter.update(ds_, emit, e)
            dtot_ = dtot_ + jnp.sum(jnp.where(spec_eff, k, 0))
            dacc_ = dacc_ + jnp.sum(jnp.where(spec_eff, acc, 0))
            return (cache_, tok_, pos_, live_, n_gen_, out_, nout_, key_,
                    ds_, dtot_, dacc_, rounds_ + 1)

        st = (cache, tok, pos, live, n_gen, out0,
              jnp.zeros((B,), jnp.int32), key, dstate, zero, zero, zero)
        (cache, tok, pos, live, n_gen, out, _, key, dstate, dtot, dacc,
         rounds) = jax.lax.while_loop(cond, body, st)
        return (cache, out, tok, pos, live, n_gen, key, dstate, dtot,
                dacc, rounds)

    def _ref_step_impl(self, params, cache, tok, pos, live, key):
        """One host-driven decode step (reference path)."""
        logits, cache = T.decode_step(params, self.cfg, cache, tokens=tok,
                                      position=pos, live=live)
        nxt = self._sample(logits, key)
        return jnp.where(live, nxt, tok), cache

    # -- host-side scheduler -------------------------------------------------
    def _reset(self) -> None:
        B = self._B
        self._queue: collections.deque = collections.deque()
        self._slots: List[Optional[Request]] = [None] * B
        self._admitting: List[Request] = []
        self._results: Dict[int, Request] = {}
        self._next_id = 0
        self._key = jax.random.PRNGKey(self.scfg.seed)
        self._tok = np.zeros(B, np.int32)
        self._pos = np.zeros(B, np.int32)
        self._live = np.zeros(B, bool)
        self._ngen = np.zeros(B, np.int32)
        self._budget = np.full(B, self.scfg.max_new_tokens, np.int32)
        self._spec = np.zeros(B, bool)
        self._dstate: Dict[str, np.ndarray] = (
            self._drafter.init_state_np(B) if self._drafter else {})
        self._run_t0: Optional[float] = None
        self.stats = self._fresh_stats()

    @staticmethod
    def _fresh_stats() -> Dict[str, float]:
        return dict(prefill_s=0.0, decode_s=0.0, tokens=0, tok_per_s=0.0,
                    host_syncs=0, admissions=0, chunks=0,
                    requests=0, prefill_groups=0, prefill_tokens=0,
                    prefill_tok_per_s=0.0, ttft_s=0.0,
                    ttft_p50_s=0.0, ttft_p99_s=0.0, queue_wait_s=0.0,
                    deadline_misses=0, preemptions=0,
                    draft_tokens=0, draft_accepted=0, accept_rate=0.0,
                    spec_rounds=0, prefix_hits=0, prefix_tokens_reused=0,
                    prefix_evictions=0, prefix_insert_drops=0,
                    # span seconds (_Span): admit_{prep,group_cache,
                    # prefill,bind,sync}_s partition prefill_s and
                    # decode_{dispatch,sync}_s partition decode_s
                    poll_s=0.0, admit_prep_s=0.0, admit_group_cache_s=0.0,
                    admit_prefill_s=0.0, admit_bind_s=0.0,
                    admit_sync_s=0.0, admit_emit_s=0.0,
                    decode_dispatch_s=0.0, decode_sync_s=0.0,
                    decode_emit_s=0.0,
                    # work counters: useful work beside what the device ran
                    decode_steps=0, decode_slot_steps=0, decode_tokens=0,
                    decode_ctx_positions=0, decode_ring_positions=0,
                    prefill_padded_tokens=0,
                    queue_wait_total_s=0.0, pre_enqueue_wait_s=0.0)

    def submit(self, prompt: List[int],
               max_new_tokens: Optional[int] = None,
               on_token: Optional[Callable[[int, int], None]] = None,
               speculate: Optional[bool] = None,
               priority: int = 0,
               deadline_s: Optional[float] = None,
               on_done: Optional[Callable[[Request], None]] = None,
               arrival_t: Optional[float] = None) -> int:
        """Queue a request; returns its id. Tokens stream via ``on_token``
        (called as on_token(request_id, token)) if given. ``speculate``
        toggles speculative decoding per request (default: on whenever the
        engine has a drafter configured).

        SLO fields: ``priority`` (higher drains first; strictly-higher
        priority may preempt under ServeConfig.preempt), ``deadline_s``
        (TTFT deadline relative to arrival -- orders the queue within a
        priority stratum and feeds the ``deadline_misses`` stat),
        ``on_done`` (called exactly once with the Request when it
        finishes, is cancelled, or is preempted). ``arrival_t`` overrides
        the arrival stamp (perf_counter clock) so a hand-off between
        engines -- disaggregated prefill->decode -- preserves the
        original arrival time instead of restarting the TTFT clock.
        Raises EngineSaturated when ServeConfig.max_queue > 0 and the
        queue (or the prefix-cache page pool) is saturated."""
        if not prompt:
            raise ValueError("empty prompt")
        budget = (self.scfg.max_new_tokens if max_new_tokens is None
                  else max_new_tokens)
        if budget < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {budget}")
        if speculate is None:
            speculate = self._drafter is not None
        elif speculate and self._drafter is None:
            raise ValueError("speculate=True needs ServeConfig.drafter")
        if (self._caps.ring_bounded_context and not self.cfg.sliding_window
                and len(prompt) + budget > self._T):
            # full-attention archs must not wrap the KV ring (that would
            # silently truncate context); windowed archs wrap by design
            # (the ring IS the window) and take prompts of any length
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({budget}) "
                f"exceeds cache_len {self._T}; raise ServeConfig.cache_len")
        if self.scfg.max_queue > 0:
            if len(self._queue) >= self.scfg.max_queue:
                raise EngineSaturated(
                    "queue_full",
                    f"queue holds {len(self._queue)} requests "
                    f"(ServeConfig.max_queue={self.scfg.max_queue})")
            if self._prefix is not None:
                pages = lambda n: -(-n // self._page)
                demand = pages(len(prompt)) + sum(
                    pages(len(r.prompt)) for r in self._queue)
                if demand > self._prefix.capacity:
                    raise EngineSaturated(
                        "page_pool_saturated",
                        f"queued prompts need {demand} KV pages, pool "
                        f"capacity is {self._prefix.capacity} "
                        "(raise ServeConfig.prefix_bytes or shed load)")
        now = time.perf_counter()
        req = Request(id=self._next_id, prompt=list(prompt),
                      max_new_tokens=budget, on_token=on_token,
                      speculate=speculate, priority=int(priority),
                      deadline_s=deadline_s, on_done=on_done,
                      submit_t=now if arrival_t is None else arrival_t,
                      enqueue_t=now)
        self._next_id += 1
        self._queue.append(req)
        return req.id

    def cancel(self, request_id: int) -> bool:
        """Cancel a request. Still queued: it never runs. Already in a
        slot: the slot is freed at the next chunk boundary and tokens
        emitted so far are kept. Either way the request shows up in this
        cycle's results with ``cancelled=True``. Returns False for ids
        that are unknown or already finished."""
        for req in self._queue:
            if req.id == request_id:
                self._queue.remove(req)
                self._finish(req, cancelled=True)
                return True
        for i, req in enumerate(self._slots):
            if req is not None and req.id == request_id:
                self._live[i] = False
                self._slots[i] = None
                self._finish(req, cancelled=True)
                return True
        # mid-admission: a group-mate's first-token callback cancels a
        # request whose prefill already ran but whose slot is not bound
        # yet -- it never binds and never emits (same observable result as
        # cancelling it while queued)
        for req in self._admitting:
            if req.id == request_id and not req.done:
                self._finish(req, cancelled=True)
                return True
        return False

    def _finish(self, req: Request, cancelled: bool = False) -> None:
        """Single completion point -- normal finish, cancel, and
        preemption all land here, so ``on_done`` fires exactly once."""
        if req.done:
            return
        req.done = True
        if cancelled:
            req.cancelled = True
        self._results[req.id] = req
        if req.on_done is not None:
            req.on_done(req)

    def _note_first_token(self, req: Request) -> None:
        # TTFT is measured from the request's ARRIVAL (submit_t), not from
        # run() entry: the old run()-entry stamp inflated every mid-cycle
        # arrival's TTFT by its queue position in the cycle and made
        # latency-under-load curves unmeasurable. _run_t0 remains only as
        # a fallback for requests that never went through submit().
        now = time.perf_counter()
        if req.submit_t is not None:
            req.ttft_s = now - req.submit_t
        elif self._run_t0 is not None:
            req.ttft_s = now - self._run_t0
        if (req.deadline_s is not None and req.submit_t is not None
                and now - req.submit_t > req.deadline_s):
            req.deadline_missed = True
            self.stats["deadline_misses"] += 1

    def _start_slot(self, slot: int, req: Request, first_tok: int,
                    prompt_len: int) -> bool:
        """Record a freshly prefilled request; returns True if the slot
        ended up free (finished at its first token, or cancelled from its
        own first-token callback). The slot is bound BEFORE the token is
        emitted so cancel() called inside on_token can find and free it."""
        self._note_first_token(req)
        self._slots[slot] = req
        self._tok[slot] = first_tok
        self._pos[slot] = prompt_len
        self._live[slot] = True
        self._ngen[slot] = 1
        self._budget[slot] = req.max_new_tokens
        self._spec[slot] = req.speculate
        if self._drafter is not None:
            # drafter history covers prompt + first token for EVERY slot
            # (cheap, and per-request speculation toggles stay honest)
            self._drafter.admit_np(self._dstate, slot,
                                   req.prompt + [first_tok])
        req._emit(first_tok)
        if self._slots[slot] is not req:        # cancelled during emit
            return True
        if req.max_new_tokens <= 1 or (
                self.scfg.eos_id is not None
                and first_tok == self.scfg.eos_id):
            self._live[slot] = False
            self._slots[slot] = None
            self._finish(req)
            return True
        return False

    # -- admission: batched chunked prefill (KV-cache families) --------------
    def _group_shape(self, lens: List[int]):
        """(padded len P, chunk len C, padded group size Gp).

        P is the group max rounded up to ``prefill_bucket`` (one compiled
        shape per bucket) and, past ``prefill_chunk``, to a multiple of the
        chunk length (ONE compiled shape covers every longer prompt).
        Group size pads to a power of two capped at ``prefill_batch``.

        Recurrent families never shrink the chunk to the bucket: their
        chunk grid is FIXED (self._chunk, a divisor of the ring) so every
        prompt -- batched or sequential, warm or cold -- sees the same
        absolute chunk boundaries, which the SSD scan's numerics (and the
        checkpoint page identity) depend on."""
        b = max(self.scfg.prefill_bucket, 1)
        maxb = max(-(-n // b) * b for n in lens)
        C = self._chunk
        if self._caps.recurrent or maxb > C:
            P = -(-maxb // C) * C
        else:
            P = C = maxb
        Gp = 1 << max(len(lens) - 1, 0).bit_length()
        return P, C, min(max(Gp, 1), max(self.scfg.prefill_batch, 1))

    def _match_prefixes(self, reqs: List[Request]):
        """Radix-match every request's longest cached prefix. Returns
        (per-request matched lengths, page-scatter jobs) where each job is
        (group_row, pool_idx, start_pos, take): rows [0, take) of that
        page land in the ring (take < page is a partial-page hit)."""
        matches, jobs = [], []
        for i, r in enumerate(reqs):
            m, pages = self._prefix.match(r.prompt)
            # insertion is gated at prompt <= ring length, so a match can
            # never exceed the ring: every matched position has a live
            # slot and the batched scatter's destinations stay distinct
            assert m <= self._T, (m, self._T)
            matches.append(m)
            if not m:
                continue
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += m
            for pidx, p0, take in pages:
                jobs.append((i, pidx, p0, take))
        return matches, jobs

    def _scatter_prefix_pages(self, gcache, jobs):
        """One batched gather->scatter of matched pool pages into the
        group cache (the copy in copy-on-write: slot rings only ever hold
        page copies, so later suffix writes never touch the pool)."""
        self._ensure_pool()
        page = self._page
        n = 1 << max(len(jobs) - 1, 0).bit_length()     # bucketed shapes
        idx = np.full(n, self._prefix.capacity, np.int32)
        rows = np.zeros(n, np.int32)
        cols = np.full((n, page), self._T, np.int32)    # T = drop
        pos = np.zeros((n, page), np.int32)
        ar = np.arange(page)
        for j, (row, pidx, p0, take) in enumerate(jobs):
            idx[j], rows[j] = pidx, row
            cols[j] = np.where(ar < take, (p0 + ar) % self._T, self._T)
            pos[j] = p0 + ar
        return self._prefix_scatter(gcache, self._pool, jnp.asarray(idx),
                                    jnp.asarray(rows), jnp.asarray(cols),
                                    jnp.asarray(pos))

    def _insert_prefix_pages(self, gcache, reqs, lens) -> None:
        """Record every request's full prompt pages in the radix tree and
        copy newly allocated ones out of the freshly prefilled group
        cache (async dispatch -- no host sync). Prompts longer than the
        ring skip insertion: their early pages were already overwritten
        by ring wrap."""
        ev0 = self._prefix.evictions
        dr0 = self._prefix.insert_drops
        jobs = []
        protect: set = set()        # shared across the group: one request's
        for i, r in enumerate(reqs):  # eviction must not recycle a pool
            if lens[i] <= self._T:    # index a group-mate just allocated
                jobs += [(i, pidx, p0)
                         for pidx, p0 in self._prefix.insert(r.prompt,
                                                             protect)]
        self.stats["prefix_evictions"] += self._prefix.evictions - ev0
        # a pool too small for the workload drops page insertions
        # silently (no behavior change: matching just misses later);
        # surface the count so saturated-pool runs are diagnosable
        self.stats["prefix_insert_drops"] += (self._prefix.insert_drops
                                              - dr0)
        if not jobs:
            return
        self._ensure_pool()
        page = self._page
        n = 1 << max(len(jobs) - 1, 0).bit_length()
        idx = np.full(n, self._prefix.capacity, np.int32)   # cap = drop
        rows = np.zeros(n, np.int32)
        cols = np.zeros((n, page), np.int32)
        ar = np.arange(page)
        for j, (row, pidx, p0) in enumerate(jobs):
            idx[j], rows[j] = pidx, row
            cols[j] = p0 + ar           # full in-ring pages never wrap
        self._pool = self._prefix_insert(self._pool, gcache,
                                         jnp.asarray(idx),
                                         jnp.asarray(rows),
                                         jnp.asarray(cols))

    # -- prefix cache, recurrent families: checkpoint pages ------------------
    def _match_checkpoints(self, reqs: List[Request]):
        """Checkpoint matching (recurrent families): only FULL pages count
        (a checkpoint is the state after a whole page of tokens), and the
        group shares ONE reuse horizon s0 = min over rows' full-page
        matches -- the chunk grid is group-wide, so a single cold row pins
        s0 to 0 and the whole group runs cold (shared-prefix traffic
        tends to arrive in groups, so the common case still reuses).
        Returns (s0, per-row full-page match lengths, hybrid ring-page
        scatter jobs covering [0, s0), checkpoint restore jobs
        (row, pool_idx) for each row's page ending at s0)."""
        page = self._page
        raw = [self._prefix.match(r.prompt) for r in reqs]
        fulls = [(m // page) * page for m, _ in raw]
        s0 = min(fulls)
        if s0 == 0:
            return 0, fulls, [], []
        pjobs, ckpt_jobs = [], []
        # of the recurrent families only hybrid carries an attention ring
        # (the capability that also makes its context ring-bounded)
        has_ring = self._caps.ring_bounded_context
        for i, (m, pages) in enumerate(raw):
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += s0
            for pidx, p0, take in pages:
                if take != page or p0 + page > s0:
                    continue            # partial page, or past the horizon
                if has_ring:
                    pjobs.append((i, pidx, p0, take))
                if p0 + page == s0:
                    ckpt_jobs.append((i, pidx))
        return s0, fulls, pjobs, ckpt_jobs

    def _scatter_checkpoints(self, gcache, jobs, Gp: int):
        """One batched device copy restoring each warm row's conv/SSM
        state from the checkpoint of the page ending at the group horizon
        (bucketed shapes; row pads point at Gp = drop)."""
        self._ensure_pool()
        n = 1 << max(len(jobs) - 1, 0).bit_length()
        idx = np.zeros(n, np.int32)
        rows = np.full(n, Gp, np.int32)
        for j, (row, pidx) in enumerate(jobs):
            idx[j], rows[j] = pidx, row
        return self._state_scatter(gcache, self._pool, jnp.asarray(idx),
                                   jnp.asarray(rows))

    def _plan_checkpoint_inserts(self, reqs, lens, fulls, s0: int):
        """Record the group's prompt pages in the radix tree BEFORE the
        chunk loop runs. A recurrent page's payload is an inter-chunk
        state snapshot that exists only transiently (the next chunk call
        donates the group cache), so each new page's checkpoint copy must
        be dispatched right after the chunk that produces it. Returns
        ({chunk index -> [(row, pool_idx)]}, hybrid ring-payload gather
        jobs (row, pool_idx, start_pos) for the same new pages)."""
        page = self._page
        ev0 = self._prefix.evictions
        dr0 = self._prefix.insert_drops
        protect: set = set()
        # protect pass: walk every row's matched chain into the shared
        # protect set first, so an earlier row's insert can never evict a
        # page a group-mate matched -- a re-inserted pre-horizon page
        # would have no checkpoint source in this run's chunk grid
        for i, r in enumerate(reqs):
            if fulls[i]:
                self._prefix.insert(r.prompt[:fulls[i]], protect)
        by_chunk: Dict[int, list] = {}
        kv_jobs: List = []
        has_ring = self._caps.ring_bounded_context
        for i, r in enumerate(reqs):
            if has_ring and lens[i] > self._T:
                continue    # hybrid: ring wrap clobbered the early pages
            for pidx, p0 in self._prefix.insert(r.prompt, protect):
                j = (p0 - s0) // page   # grid chunk whose output is the
                by_chunk.setdefault(j, []).append((i, pidx))  # checkpoint
                if has_ring:
                    kv_jobs.append((i, pidx, p0))
        self.stats["prefix_evictions"] += self._prefix.evictions - ev0
        self.stats["prefix_insert_drops"] += (self._prefix.insert_drops
                                              - dr0)
        return by_chunk, kv_jobs

    def _insert_checkpoints(self, gcache, jobs) -> None:
        """Copy inter-chunk conv/SSM state into pool checkpoint rows.
        Async dispatch that MUST precede the next chunk call (which
        donates the group cache the snapshot is read from)."""
        self._ensure_pool()
        n = 1 << max(len(jobs) - 1, 0).bit_length()
        idx = np.full(n, self._prefix.capacity, np.int32)   # cap = drop
        rows = np.zeros(n, np.int32)
        for j, (row, pidx) in enumerate(jobs):
            idx[j], rows[j] = pidx, row
        self._pool = self._state_insert(self._pool, gcache,
                                        jnp.asarray(rows),
                                        jnp.asarray(idx))

    def _insert_ring_pages(self, gcache, jobs) -> None:
        """Copy the ring payload of freshly recorded hybrid pages out of
        the prefilled group cache. The radix insert already ran in
        _plan_checkpoint_inserts -- this is only the KV half of each new
        page (its checkpoint half landed chunk by chunk)."""
        self._ensure_pool()
        page = self._page
        n = 1 << max(len(jobs) - 1, 0).bit_length()
        idx = np.full(n, self._prefix.capacity, np.int32)   # cap = drop
        rows = np.zeros(n, np.int32)
        cols = np.zeros((n, page), np.int32)
        ar = np.arange(page)
        for j, (row, pidx, p0) in enumerate(jobs):
            idx[j], rows[j] = pidx, row
            cols[j] = p0 + ar           # full in-ring pages never wrap
        self._pool = self._prefix_insert(self._pool, gcache,
                                         jnp.asarray(idx),
                                         jnp.asarray(rows),
                                         jnp.asarray(cols))

    def _ensure_pool(self) -> None:
        if self._pool is None:
            self._pool = self._state.page_pool(self._prefix.capacity,
                                               self._page)
            if self._mesh is not None:
                # page payloads co-shard with the ring (kv_heads axis) so
                # page gather/scatter stays collective-free under GSPMD
                pspec = SH.serve_cache_specs(self._pool, self._plan)
                self._pool = jax.device_put(
                    self._pool, SH.named(pspec, self._mesh))

    # -- cross-engine KV hand-off (disaggregated serving) --------------------
    @property
    def prefix_page(self) -> Optional[int]:
        """Positions per KV page (None when the prefix cache is off)."""
        return self._page if self._prefix is not None else None

    def prefix_match_len(self, tokens: List[int]) -> int:
        """Router probe: how many leading tokens of ``tokens`` this
        engine's radix tree already holds (0 with the cache off). Pure
        host state, no LRU side effects -- a KV-aware router scores every
        worker with this before routing (serving/router.py)."""
        if self._prefix is None:
            return 0
        return self._prefix.match_len(list(tokens))

    def export_kv_pages(self, tokens: List[int]) -> KVPages:
        """Copy the full KV pages this engine has cached for ``tokens``
        out to host memory, page-granular and bit-for-bit (int8-KV scales
        included). The chain covers whole pages from position 0 up to the
        first miss; a prompt this engine just prefilled (with the prefix
        cache on) exports every full page of itself. This is the sending
        half of the disaggregation hand-off: the pages land in another
        engine via ``import_kv_pages`` and are reused through its
        ordinary (parity-pinned) prefix-cache admission."""
        if self._prefix is None:
            raise RuntimeError(
                "export_kv_pages needs ServeConfig.prefix_cache=True: the "
                "page pool is the export source")
        tokens = list(tokens)
        chain = self._prefix.page_chain(tokens)
        if not chain:
            return KVPages(page=self._page, tokens=[], payload={})
        self._ensure_pool()
        idx = jnp.asarray(np.array([i for i, _ in chain], np.int32))
        got = jax.device_get(self._pool_export(self._pool, idx))
        return KVPages(page=self._page,
                       tokens=tokens[:len(chain) * self._page],
                       payload={k: np.asarray(v) for k, v in got.items()})

    def import_kv_pages(self, kv: KVPages) -> int:
        """Adopt another engine's exported pages: record their token
        chain in this engine's radix tree and copy the payloads into its
        page pool (one async device scatter -- no host sync). Returns the
        number of pages actually imported; pages whose chain prefix is
        already resident are deduplicated (their bits are identical by
        construction: same params, same tokens, same prefill math), and a
        saturated pool drops the tail exactly like a local insert (the
        drop count rides the ``prefix_insert_drops`` stat). After an
        import, admitting a request with that prompt hits the prefix
        cache as if this engine had prefilled it itself -- which is the
        disaggregation parity argument in one sentence."""
        if self._prefix is None:
            raise RuntimeError(
                "import_kv_pages needs ServeConfig.prefix_cache=True: the "
                "page pool is the import destination")
        if kv.page != self._page:
            raise ValueError(
                f"page geometry mismatch: exported pages hold {kv.page} "
                f"positions, this engine's pool holds {self._page}")
        n = kv.n_pages
        if n == 0 or len(kv.tokens) > self._T:
            # mirror of the local insertion gate: prompts longer than the
            # ring would have had their early pages overwritten by wrap
            return 0
        drops0 = self._prefix.insert_drops
        new = self._prefix.insert(list(kv.tokens[:n * self._page]))
        self.stats["prefix_insert_drops"] += (self._prefix.insert_drops
                                              - drops0)
        if not new:
            return 0
        self._ensure_pool()
        src = np.array([p0 // self._page for _, p0 in new], np.int32)
        dst = np.array([i for i, _ in new], np.int32)
        pages = {k: jnp.asarray(v[:, src]) for k, v in kv.payload.items()}
        self._pool = self._pool_import(self._pool, pages,
                                       jnp.asarray(dst))
        return len(new)

    def _admit_group(self, slots: List[int], reqs: List[Request]) -> None:
        """Prefill ``reqs`` as one right-padded batch and scatter all their
        caches into ``slots`` with a single cache_set_slots call.

        With the prefix cache enabled, each request's longest cached
        prefix is scattered into its group-cache row page by page and the
        chunked prefill covers only [min cached length, padded max): the
        chunk grid starts at the group-wide reuse horizon, rows whose own
        horizon lies further right mask the overlap columns out of
        compute (``cached_lengths``), and the suffix length (not the full
        prompt) picks the bucketed chunk shape -- so shared-prefix groups
        skip most of their MatMul work while still emitting bit-identical
        KV rows and logits.

        Recurrent families run the same batched path on their FIXED chunk
        grid: warm rows restore the conv/SSM checkpoint at the group's
        shared full-page horizon s0 (hybrid also scatters the ring pages
        below it), the chunk loop starts at s0, and freshly recorded
        pages capture their checkpoints chunk by chunk (the inter-chunk
        carry, copied before the next chunk donates it)."""
        with _Span(self.stats, "engine.admit") as span:
            t0 = span.phase("prep")
            for r in reqs:
                if r.submit_t is not None:
                    r.queue_wait_s = t0 - r.submit_t
                    self.stats["queue_wait_total_s"] += r.queue_wait_s
                    self.stats["pre_enqueue_wait_s"] += (r.enqueue_t
                                                         - r.submit_t)
            G = len(reqs)
            lens = [len(r.prompt) for r in reqs]
            pjobs: List = []
            ckpt_jobs: List = []
            ins_by_chunk: Dict[int, List] = {}
            kv_ins_jobs: List = []
            if self._prefix is None:
                matches, s0 = [0] * G, 0
            elif self._kv_family:
                matches, pjobs = self._match_prefixes(reqs)
                s0 = min(matches)
            else:
                # recurrent: whole-state checkpoints, full pages only, one
                # shared horizon; the per-column cached mask stays 0 (the
                # restored checkpoint replaces masking -- the chunk GRID
                # starts at s0 instead)
                s0, fulls, pjobs, ckpt_jobs = self._match_checkpoints(reqs)
                matches = [0] * G
                ins_by_chunk, kv_ins_jobs = self._plan_checkpoint_inserts(
                    reqs, lens, fulls, s0)
            P, C, Gp = self._group_shape([n - s0 for n in lens])
            if span.recording():
                span.args(G=G, Gp=Gp, P=P, C=C, s0=s0,
                          rids=str([r.id for r in reqs]))
            toks = np.zeros((Gp, s0 + P), np.int32)
            lengths = np.zeros(Gp, np.int32)        # dummy rows: length 0
            cached = np.zeros(Gp, np.int32)
            for i, r in enumerate(reqs):
                toks[i, :lens[i]] = r.prompt
                lengths[i] = lens[i]
                cached[i] = matches[i]
            # split one key per request IN QUEUE ORDER -- exactly the
            # stream a sequential (prefill_batch=1) admission loop would
            # consume, so the two schedules sample identical first tokens
            subs = []
            for _ in range(G):
                self._key, sub = jax.random.split(self._key)
                subs.append(sub)
            subs += [subs[-1]] * (Gp - G)           # dummies: never emitted
            if self._cache is None:
                self._cache = self._new_cache(self._B)
            span.phase("group_cache")
            gcache = self._new_cache(Gp)
            if ckpt_jobs:
                gcache = self._scatter_checkpoints(gcache, ckpt_jobs, Gp)
            if pjobs:
                gcache = self._scatter_prefix_pages(gcache, pjobs)
            span.phase("prefill")
            last_logits = jnp.zeros((Gp, self.cfg.vocab_size), jnp.float32)
            lengths_d = jnp.asarray(lengths)
            cached_d = jnp.asarray(cached)
            for j in range(P // C):
                start = s0 + j * C
                gcache, last_logits = self._prefill_chunk(
                    self.params, gcache,
                    jnp.asarray(toks[:, start:start + C]),
                    jnp.asarray(start, jnp.int32), lengths_d, last_logits,
                    cached_d)
                if j in ins_by_chunk:
                    # checkpoint copies ride the device queue here, BEFORE
                    # the next chunk call donates (and so invalidates) the
                    # group-cache buffers they read from
                    self._insert_checkpoints(gcache, ins_by_chunk[j])
            span.phase("bind")
            first_d = self._sample_first(last_logits, jnp.stack(subs))
            budgets = np.zeros(Gp, np.int32)        # dummies: 0 -> unbound
            budgets[:G] = [r.max_new_tokens for r in reqs]
            # free list padded to Gp so compiled shapes track the group
            # BUCKET, not the exact group size (pad entries are never
            # read: survivor ranks stay < G)
            free_arr = np.full(Gp, self._B, np.int32)
            free_arr[:G] = slots
            idx_d = self._bind_slots(first_d, jnp.asarray(budgets),
                                     jnp.asarray(free_arr))
            self._cache = self._admit_caches(self._cache, gcache, idx_d)
            if self._prefix is not None:
                if self._kv_family:
                    # record this group's prompt pages (async dispatch,
                    # rides the same device queue -- admission stays one
                    # host sync)
                    self._insert_prefix_pages(gcache, reqs, lens)
                elif kv_ins_jobs:
                    # hybrid: ring payload of the pages recorded pre-loop
                    self._insert_ring_pages(gcache, kv_ins_jobs)
            span.phase("sync")
            firsts = np.asarray(jax.device_get(first_d))   # 1 sync / GROUP
            # host-side mirror of _bind_slots_impl for the bookkeeping
            free_iter = iter(slots)
            bound = [None if (req.max_new_tokens <= 1
                              or (self.scfg.eos_id is not None
                                  and int(firsts[i]) == self.scfg.eos_id))
                     else next(free_iter) for i, req in enumerate(reqs)]
            self.stats["host_syncs"] += 1
            self.stats["prefill_groups"] += 1
            self.stats["admissions"] += G
            self.stats["prefill_tokens"] += sum(lens)
            self.stats["prefill_padded_tokens"] += Gp * (s0 + P)
            self.stats["prefill_s"] += span.phase("emit") - t0
            self._admitting = reqs
            for i, req in enumerate(reqs):
                if req.cancelled:
                    # cancelled from a group-mate's on_token callback
                    # after its prefill but before its slot bound: never
                    # binds, never emits (its scattered cache row is
                    # inert garbage)
                    continue
                if bound[i] is None:
                    self._note_first_token(req)
                    req._emit(int(firsts[i]))
                    self._finish(req)
                else:
                    self._start_slot(bound[i], req, int(firsts[i]),
                                     lens[i])
            self._admitting = []

    @staticmethod
    def _admit_key(req: Request):
        """Queue drain order: priority strata (higher first), earliest
        absolute TTFT deadline within a stratum, then submission order --
        a queue with uniform priority and no deadlines therefore drains
        exactly FIFO, which is what keeps the parity-pinned default
        schedule (and its PRNG key-split order) unchanged."""
        dl = (req.submit_t + req.deadline_s
              if req.deadline_s is not None and req.submit_t is not None
              else float("inf"))
        return (-req.priority, dl, req.id)

    def _pop_pending(self, n: int) -> List[Request]:
        picked = sorted(self._queue, key=self._admit_key)[:n]
        for r in picked:
            self._queue.remove(r)
        return picked

    def _preempt_for(self, head: Request) -> bool:
        """Free one slot for ``head`` by cancelling the lowest-priority
        (then youngest) running request -- only when head's priority is
        STRICTLY higher, so equal-priority work never preempts and the
        single-priority default can never trigger this. The victim keeps
        its emitted tokens and completes with cancelled=True,
        preempted=True (the ordinary cancel contract)."""
        victims = [(req.priority, -req.id, i)
                   for i, req in enumerate(self._slots) if req is not None]
        if not victims:
            return False
        prio, _, i = min(victims)
        if head.priority <= prio:
            return False
        victim = self._slots[i]
        self._live[i] = False
        self._slots[i] = None
        victim.preempted = True
        self.stats["preemptions"] += 1
        self._finish(victim, cancelled=True)
        return True

    def _admit_pending(self) -> None:
        while self._queue:
            free = [i for i in range(self._B) if self._slots[i] is None]
            if not free:
                if not self.scfg.preempt:
                    return
                head = min(self._queue, key=self._admit_key)
                if not self._preempt_for(head):
                    return
                free = [i for i in range(self._B)
                        if self._slots[i] is None]
            n = min(len(free), max(self.scfg.prefill_batch, 1),
                    len(self._queue))
            self._admit_group(free[:n], self._pop_pending(n))

    def _run_chunk(self) -> None:
        with _Span(self.stats, "engine.decode") as span:
            if span.recording():
                rids = [r.id for i, r in enumerate(self._slots)
                        if r is not None and self._live[i]]
                span.args(live=len(rids), rids=str(rids))
            pos0 = self._pos
            t0 = span.phase("dispatch")
            if self._drafter is not None:
                dstate_d = {k: jnp.asarray(v)
                            for k, v in self._dstate.items()}
                (self._cache, out_d, tok_d, pos_d, live_d, ngen_d,
                 self._key, ds_d, dtot_d, dacc_d, rounds_d) = \
                    self._spec_chunk(
                        self.params, self._cache, jnp.asarray(self._tok),
                        jnp.asarray(self._pos), jnp.asarray(self._live),
                        jnp.asarray(self._spec), jnp.asarray(self._ngen),
                        jnp.asarray(self._budget), self._key, dstate_d)
                span.phase("sync")
                out, tok, pos, live, ngen, ds, dtot, dacc, rounds = \
                    jax.device_get((out_d, tok_d, pos_d, live_d, ngen_d,
                                    ds_d, dtot_d, dacc_d,
                                    rounds_d))          # THE sync
                self._dstate = {k: np.array(v) for k, v in ds.items()}
                self.stats["draft_tokens"] += int(dtot)
                self.stats["draft_accepted"] += int(dacc)
                self.stats["spec_rounds"] += int(rounds)
            else:
                (self._cache, out_d, tok_d, pos_d, live_d, ngen_d,
                 self._key) = self._decode_chunk(
                    self.params, self._cache, jnp.asarray(self._tok),
                    jnp.asarray(self._pos), jnp.asarray(self._live),
                    jnp.asarray(self._ngen), jnp.asarray(self._budget),
                    self._key)
                span.phase("sync")
                out, tok, pos, live, ngen = jax.device_get(
                    (out_d, tok_d, pos_d, live_d, ngen_d))  # THE sync
            # device_get hands back read-only buffers; admission mutates
            # these
            self._tok, self._pos = np.array(tok), np.array(pos)
            self._live, self._ngen = np.array(live), np.array(ngen)
            self.stats["host_syncs"] += 1
            self.stats["chunks"] += 1
            self._count_chunk(out, pos0)
            self.stats["decode_s"] += span.phase("emit") - t0
            self._emit_chunk(out)

    def _count_chunk(self, out: np.ndarray, pos0: np.ndarray) -> None:
        """Useful decode work beside what the device ran. The chunk ran
        as many steps as its busiest slot emitted tokens, each over all
        ``max_slots`` slots and the whole ring; a slot at position p that
        emitted k tokens attended k*p + k(k-1)/2 live positions (each
        step capped at the ring length)."""
        n = (out >= 0).sum(axis=1)
        steps = int(n.max())
        j = np.arange(out.shape[1])
        ctx = np.minimum(pos0[:, None] + j, self._T) * (j < n[:, None])
        s = self.stats
        s["decode_steps"] += steps
        s["decode_slot_steps"] += steps * self._B
        s["decode_tokens"] += int(n.sum())
        s["decode_ctx_positions"] += int(ctx.sum())
        s["decode_ring_positions"] += steps * self._B * self._T

    def _emit_chunk(self, out: np.ndarray) -> None:
        """Stream each slot's dense token prefix; free finished slots."""
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            for tok in out[i][out[i] >= 0].tolist():
                req._emit(tok)
                if self._slots[i] is None:      # on_token cancelled us
                    break
            if self._slots[i] is not None and not self._live[i]:
                self._finish(req)
                self._slots[i] = None               # slot freed -> eviction

    def _finalize_stats(self, done: Dict[int, List[int]]) -> None:
        """Derive rate stats with explicit zero-denominator guards: a run
        whose every request is cancelled from an ``on_token`` callback at
        admission never decodes (decode_s == 0 with tokens > 0 -- the old
        ``max(x, 1e-9)`` guard reported absurd rates there), and
        spec_rounds == 0 leaves draft_tokens at 0. All rates report 0.0
        in those cases.

        ``requests`` counts admissions over the whole cycle, not the
        queue length at run() entry: a request submitted from an
        ``on_token`` callback mid-cycle is served by this cycle and must
        be counted by it (the old entry-time stamp missed every one of
        them); a request cancelled while still queued is never admitted
        and is not a served request."""
        self.stats["requests"] = self.stats["admissions"]
        ntok = sum(len(t) for t in done.values())
        self.stats["tokens"] = ntok
        self.stats["tok_per_s"] = (
            ntok / self.stats["decode_s"]
            if self.stats["decode_s"] > 0 else 0.0)
        self.stats["prefill_tok_per_s"] = (
            self.stats["prefill_tokens"] / self.stats["prefill_s"]
            if self.stats["prefill_s"] > 0 else 0.0)
        ttfts = [r.ttft_s for r in self._results.values()
                 if r.ttft_s is not None]
        self.stats["ttft_s"] = sum(ttfts) / len(ttfts) if ttfts else 0.0
        # tail latency is the contested serving metric -- a mean hides the
        # queue-position tail entirely (every depth>1 row used to look
        # identical at p50 and p99 because both were the same mean)
        self.stats["ttft_p50_s"] = (
            float(np.percentile(ttfts, 50)) if ttfts else 0.0)
        self.stats["ttft_p99_s"] = (
            float(np.percentile(ttfts, 99)) if ttfts else 0.0)
        waits = [r.queue_wait_s for r in self._results.values()
                 if r.queue_wait_s is not None]
        self.stats["queue_wait_s"] = (
            sum(waits) / len(waits) if waits else 0.0)
        self.stats["accept_rate"] = (
            self.stats["draft_accepted"] / self.stats["draft_tokens"]
            if self.stats["draft_tokens"] > 0 else 0.0)

    def run(self, poll: Optional[Callable[[], None]] = None
            ) -> Dict[int, List[int]]:
        """Drive batched admission + fused decode chunks until queue and
        slots are drained. Returns {request_id: tokens} for THIS cycle;
        stats cover this cycle only (slots are always empty between run()
        calls, so resetting the counters here is safe).

        ``poll``, when given, is called once per scheduler iteration
        (before the drain check): a front-end or trace-driven load
        generator injects mid-cycle submits/cancels there -- arrivals land
        between chunks without any engine-side threading."""
        self.stats = self._fresh_stats()
        self._run_t0 = time.perf_counter()
        while True:
            if poll is not None:
                with _Span(self.stats, "engine.poll", timed=True):
                    poll()
            if not (self._queue or any(r is not None
                                       for r in self._slots)):
                break
            self._admit_pending()
            if not self._live.any():
                continue
            self._run_chunk()
        done = {rid: req.tokens for rid, req in self._results.items()}
        self._finalize_stats(done)
        self._results = {}                  # next submit/run cycle is fresh
        self._run_t0 = None
        return done

    # -- public API ----------------------------------------------------------
    def generate(self, prompts: List[List[int]]) -> List[List[int]]:
        """Generate completions for a batch of prompts. Prompts beyond
        ``max_slots`` are continuously batched into freed slots. Resets
        engine state (fresh PRNG seed) for call-to-call determinism."""
        if self._queue:
            raise RuntimeError(
                f"{len(self._queue)} submitted request(s) pending; call "
                "run() to drain them before generate() (which resets)")
        self._reset()
        ids = [self.submit(list(p)) for p in prompts]
        res = self.run()
        return [res[i] for i in ids]

    def generate_reference(self,
                           prompts: List[List[int]]) -> List[List[int]]:
        """Pre-rewrite reference: same admission/prefill/sampling math but
        one host round-trip per token. O(tokens) syncs -- parity oracle
        for the on-device loop, not a serving path."""
        if len(prompts) > self._B:
            raise ValueError("reference path has no queue; "
                             f"need <= {self._B} prompts")
        if self._queue:
            raise RuntimeError(
                f"{len(self._queue)} submitted request(s) pending; call "
                "run() to drain them before generate_reference()")
        self._reset()
        ids = [self.submit(list(p)) for p in prompts]
        self._run_t0 = time.perf_counter()
        self._admit_pending()
        t0 = time.perf_counter()
        while self._live.any():
            self._key, sub = jax.random.split(self._key)
            nxt_d, self._cache = self._ref_step(
                self.params, self._cache, jnp.asarray(self._tok),
                jnp.asarray(self._pos), jnp.asarray(self._live), sub)
            nxt = np.asarray(jax.device_get(nxt_d))
            self.stats["host_syncs"] += 1
            for i, req in enumerate(self._slots):
                if req is None or not self._live[i]:
                    continue
                tok = int(nxt[i])
                req._emit(tok)
                self._ngen[i] += 1
                self._pos[i] += 1
                self._tok[i] = tok
                if (self._ngen[i] >= self._budget[i]
                        or (self.scfg.eos_id is not None
                            and tok == self.scfg.eos_id)):
                    self._live[i] = False
                    self._finish(req)
                    self._slots[i] = None
        self.stats["decode_s"] += time.perf_counter() - t0
        res = {rid: req.tokens for rid, req in self._results.items()}
        self._finalize_stats(res)
        self._results = {}
        self._run_t0 = None
        return [res[i] for i in ids]

    def generate_spec_reference(self,
                                prompts: List[List[int]]) -> List[List[int]]:
        """Host-driven speculative oracle: one verify ROUND per host trip,
        with acceptance, rejection sampling, truncation and rollback
        bookkeeping re-implemented in numpy against the raw logits. Same
        key-split discipline as the fused loop, so the two must agree
        token-for-token -- this is the validation target for temperature
        mode, where plain decode is no longer a token-level oracle.
        O(tokens) syncs; a parity tool, not a serving path."""
        if self._drafter is None:
            raise RuntimeError("generate_spec_reference needs a drafter")
        if len(prompts) > self._B:
            raise ValueError("reference path has no queue; "
                             f"need <= {self._B} prompts")
        if self._queue:
            raise RuntimeError(
                f"{len(self._queue)} submitted request(s) pending; call "
                "run() to drain them before generate_spec_reference()")
        self._reset()
        ids = [self.submit(list(p)) for p in prompts]
        self._run_t0 = time.perf_counter()
        self._admit_pending()
        C = self.scfg.decode_chunk
        k = self.scfg.draft_k
        S = k + 1
        B = self._B
        eos = self.scfg.eos_id
        temp = self.scfg.temperature
        cols = np.arange(S)[None]
        t0 = time.perf_counter()
        while self._live.any():
            nout = np.zeros(B, np.int32)            # fresh chunk capacity
            progressed = False
            while True:
                spec_ok = (self._spec if self.cfg.sliding_window
                           else self._spec & (self._pos + k < self._T))
                need = np.where(spec_ok, S, 1)
                act = self._live & (nout + need <= C)
                if not act.any():
                    break
                progressed = True
                spec_eff = act & spec_ok
                self._key, k_u, k_fin = jax.random.split(self._key, 3)
                ds_d = {kk: jnp.asarray(v)
                        for kk, v in self._dstate.items()}
                drafts_d, ds_d = self._propose_ref(
                    self.params, self._cache, ds_d,
                    jnp.asarray(self._tok), jnp.asarray(self._pos),
                    jnp.asarray(spec_eff))
                drafts = np.asarray(jax.device_get(drafts_d))
                x = np.concatenate([self._tok[:, None], drafts], axis=1)
                positions = self._pos[:, None] + cols
                valid = act[:, None] & ((cols == 0) | spec_eff[:, None])
                slots_d = jnp.asarray(positions % self._T)
                snap = T.cache_ring_snapshot(self._cache, slots_d)
                logits_d, self._cache = self._verify(
                    self.params, self._cache, jnp.asarray(x),
                    jnp.asarray(positions), jnp.asarray(valid))
                logits = np.asarray(jax.device_get(logits_d), np.float32)
                self.stats["host_syncs"] += 1
                # -- host acceptance (independent numpy re-implementation)
                if temp > 0:
                    lt = logits / temp
                    pm = np.exp(lt[:, :k]
                                - lt[:, :k].max(-1, keepdims=True))
                    pm = pm / pm.sum(-1, keepdims=True)
                    pd = np.take_along_axis(
                        pm, drafts[:, :, None], 2)[..., 0]
                    u = np.asarray(jax.random.uniform(k_u, (B, k)))
                    ok = (u < pd) & spec_eff[:, None]
                    acc = np.cumprod(ok, axis=1).sum(axis=1).astype(np.int32)
                    pl = np.take_along_axis(lt, acc[:, None, None], 1)[:, 0]
                    pcol = np.exp(pl - pl.max(-1, keepdims=True))
                    pcol = pcol / pcol.sum(-1, keepdims=True)
                    dcol = np.take_along_axis(
                        drafts, np.clip(acc, 0, k - 1)[:, None], 1)[:, 0]
                    rejected = spec_eff & (acc < k)
                    resid = pcol.copy()
                    resid[np.arange(B), dcol] = np.where(
                        rejected, 0.0, resid[np.arange(B), dcol])
                    with np.errstate(divide="ignore"):
                        lr = np.where(resid > 0, np.log(resid), -np.inf)
                    fin = np.asarray(jax.random.categorical(
                        k_fin, jnp.asarray(lr))).astype(np.int32)
                    fin = np.where((resid > 0).any(-1), fin, dcol)
                else:
                    g = logits.argmax(-1).astype(np.int32)
                    ok = (drafts == g[:, :k]) & spec_eff[:, None]
                    acc = np.cumprod(ok, axis=1).sum(axis=1).astype(np.int32)
                    fin = np.take_along_axis(g, acc[:, None], 1)[:, 0]
                draftsp = np.concatenate([drafts, drafts[:, -1:]], axis=1)
                emit = np.where(cols < acc[:, None], draftsp, fin[:, None])
                e = np.minimum(acc + 1, self._budget - self._ngen)
                if eos is not None:
                    hit = (emit == eos) & (cols < e[:, None])
                    has = hit.any(1)
                    first = hit.argmax(1).astype(np.int32)
                    e = np.where(has, np.minimum(e, first + 1), e)
                e = np.where(act, e, 0)
                keep = np.where(act, 1 + acc, 0)
                self._cache = T.cache_ring_rewind(
                    self._cache, snap, slots_d, jnp.asarray(keep))
                ds_d = self._drafter.update(ds_d, jnp.asarray(emit),
                                            jnp.asarray(e))
                self._dstate = {kk: np.array(v) for kk, v in
                                jax.device_get(ds_d).items()}
                self.stats["draft_tokens"] += int(spec_eff.sum()) * k
                self.stats["draft_accepted"] += int(acc[spec_eff].sum())
                self.stats["spec_rounds"] += 1
                for i, req in enumerate(self._slots):
                    if req is None or e[i] == 0:
                        continue
                    for t in emit[i, :e[i]].tolist():
                        req._emit(int(t))
                    self._ngen[i] += int(e[i])
                    self._pos[i] += int(e[i])
                    self._tok[i] = int(emit[i, e[i] - 1])
                    died = self._ngen[i] >= self._budget[i]
                    if eos is not None:
                        died = died or eos in emit[i, :e[i]].tolist()
                    if died:
                        self._live[i] = False
                        self._finish(req)
                        self._slots[i] = None
                nout = nout + e
            if not progressed:
                break
        self.stats["decode_s"] += time.perf_counter() - t0
        res = {rid: req.tokens for rid, req in self._results.items()}
        self._finalize_stats(res)
        self._results = {}
        self._run_t0 = None
        return [res[i] for i in ids]
