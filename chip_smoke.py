#!/usr/bin/env python3
"""Bring-up smoke test: the quantized serving path, run on a TPU.

    python3 chip_smoke.py [--seed 0]     # one chip: device, kernels, serve
    python3 chip_smoke.py --chips 4      # four chips: tp=1 vs tp=4 only

One process; the HTTP front end runs on threads of it. Phases, in order,
each exiting non-zero on failure:

  device   jax.devices()[0] must be a TPU. There is no CPU fallback.
  kernels  compiled ``bfp_matmul_pallas`` for every registered weight
           format at the tinyllama-1.1b projection shapes, M = 8 (decode
           slots) and 512 (prefill group x chunk), and
           ``prefill_attn_fused`` at the engine's prefill shape, each
           against its float32 reference.
  serve    full-width tinyllama-1.1b, weights drawn from ``--seed`` and
           packed with paper_llama_mix, served by ``Engine`` behind
           ``Frontend`` on localhost: a concurrent burst must answer in
           full, and one-at-a-time HTTP answers must equal
           ``Engine.generate`` of the same prompt; the prefill and first
           decode logits must agree with the kernel_impl="xla"
           (dequantize-then-dot) datapath.
  tp       (``--chips 4`` only, and then the only phase) tp=1 against
           tp=4 for the padded, sliced and sliced_row datapaths.

Each phase prints its XLA compile seconds, wall seconds and the device's
peak bytes in use: smoke facts, not benchmark numbers. The last stdout
line is the JSON verdict, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "tinyllama-1.1b"
POLICY = "paper_llama_mix"
# (K, N) of every projection the engine runs for tinyllama-1.1b:
# wq/wo, wk/wv, w_gate/w_up, w_down, lm_head
SHAPES = ((2048, 2048), (2048, 256), (2048, 5632), (5632, 2048),
          (2048, 32000))
ROWS = (8, 512)                 # decode slots; prefill group 8 x chunk 64
NEW_TOKENS = 32
CACHE_LEN = 1024
SLOTS = 8

# Kernel vs float32 reference, as max|y - ref| / max|ref|. Both sides see
# the same bf16 activations; the kernel rounds each dequantized weight to
# bf16 for the MXU (2^-9 relative), which summed over K with random signs
# stays near 1e-3 of the output scale. A wrong tile, scale or unpack
# gives O(1).
KERNEL_TOL = 1e-2
# Fused prefill attention vs float32 naive attention at HIGHEST
# precision: the kernel returns q's dtype (bf16, 2^-9 relative rounding),
# and q, k, v are bf16-valued, so at most the probability x value product
# loses bf16 precision besides.
ATTN_TOL = 1e-2
# Logits of two datapaths of the same bf16 model, as max|a - b| /
# max|b|: every projection output rounds to bf16 (eps 2^-8); a different
# f32 accumulation order flips single roundings by one ulp, and those
# travel through 22 residual layers. A few ulps of the logit scale is
# expected; a broken datapath gives O(1).
LOGIT_TOL = 5e-2

_COMPILE_S = [0.0]


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _count_compiles(event: str, secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


class Phase:
    """Prints a phase's wall seconds, XLA compile seconds and the first
    device's peak bytes in use when it ends without an exception."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"== phase {self.name}", flush=True)
        self.t0, self.c0 = time.perf_counter(), _COMPILE_S[0]
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            import jax
            stats = jax.devices()[0].memory_stats() or {}
            print(f"[{self.name}] ok: wall_s="
                  f"{time.perf_counter() - self.t0} xla_compile_s="
                  f"{_COMPILE_S[0] - self.c0} peak_bytes_in_use="
                  f"{stats.get('peak_bytes_in_use', 'not reported')}",
                  flush=True)
        return False


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def phase_kernels(seed: int, shapes=SHAPES, rows=ROWS,
                  interpret: bool = False) -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.formats import WEIGHT_VARIANTS
    from repro.core.quantize import quantize
    from repro.kernels.bfp_matmul import bfp_matmul_pallas
    from repro.kernels.ref import matmul_ref

    def kernel(xs, t):
        return [bfp_matmul_pallas(x, t, out_dtype=jnp.float32,
                                  interpret=interpret) for x in xs]

    kernel = jax.jit(kernel)
    reference = jax.jit(lambda xs, t: [matmul_ref(x, t) for x in xs])
    quant = {v: jax.jit(functools.partial(quantize, v))
             for v in WEIGHT_VARIANTS}
    key = jax.random.PRNGKey(seed)
    worst = {v: 0.0 for v in WEIGHT_VARIANTS}
    for K, N in shapes:
        key, kw, kx = jax.random.split(key, 3)
        w = jax.random.normal(kw, (K, N), jnp.float32) * 0.02
        xs = [jax.random.normal(k, (m, K), jnp.float32).astype(jnp.bfloat16)
              for k, m in zip(jax.random.split(kx, len(rows)), rows)]
        for v in WEIGHT_VARIANTS:
            t = quant[v](w)
            check(t.variant == v, f"{v} at K={K} packed as {t.variant}")
            got = kernel(xs, t)
            with jax.default_matmul_precision("highest"):
                want = reference(xs, t)
            for m, g, r in zip(rows, got, want):
                err = rel_err(g, r)
                worst[v] = max(worst[v], err)
                check(err <= KERNEL_TOL,
                      f"bfp_matmul_pallas {v} M={m} K={K} N={N}: max rel "
                      f"err {err} > {KERNEL_TOL}")
    for v in WEIGHT_VARIANTS:
        print(f"  bfp_matmul_pallas {v}: max rel err {worst[v]} over "
              f"{len(shapes)} shapes x M={list(rows)} (tol {KERNEL_TOL})")
    attn_check(seed, interpret=interpret)


def attn_check(seed: int, B=8, C=64, H=32, KH=4, D=64,
               T=CACHE_LEN + 64, interpret: bool = False) -> None:
    """prefill_attn_fused at the engine's prefill shape: a chunk of C
    queries against a ring of CACHE_LEN slots plus the chunk's own keys,
    with empty (-1) ring slots past the prompt."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.prefill_attn import prefill_attn_fused
    from repro.models.layers import naive_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q = jax.random.normal(kq, (B, C, H, D), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, KH, D), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, KH, D), jnp.float32).astype(jnp.bfloat16)
    start = T // 3
    q_pos = jnp.broadcast_to(start + jnp.arange(C, dtype=jnp.int32), (B, C))
    slots = jnp.arange(T, dtype=jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.where(slots < start + C, slots, -1), (B, T))
    got = jax.jit(lambda *a: prefill_attn_fused(*a, interpret=interpret))(
        q, k, v, q_pos, kv_pos)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v, qp, kp: naive_attention(
            q.astype(jnp.float32), k, v, q_positions=qp,
            kv_positions=kp))(q, k, v, q_pos, kv_pos)
    err = rel_err(got, want)
    print(f"  prefill_attn_fused B{B} C{C} H{H}/KH{KH} D{D} T{T}: max rel "
          f"err {err} (tol {ATTN_TOL})")
    check(err <= ATTN_TOL, f"prefill_attn_fused max rel err {err}")


# ---------------------------------------------------------------------------
# model, engine probes
# ---------------------------------------------------------------------------

def build_model(cfg, seed: int):
    """Seeded random weights packed with POLICY, built in one jitted
    program so the float32 weights never all sit on the device at once
    as separate buffers."""
    import jax

    from repro.core.policy import get_policy
    from repro.core.qlinear import quantize_params
    from repro.models import transformer as T

    policy = get_policy(POLICY)
    build = jax.jit(lambda key: quantize_params(T.init_params(cfg, key),
                                                policy)[0])
    return jax.block_until_ready(build(jax.random.PRNGKey(seed)))


def count_formats(qp) -> dict:
    """Packed matmuls per format, stacked layers counted one by one."""
    import jax

    from repro.core.quantize import QTensor

    counts: dict = {}
    for leaf in jax.tree.leaves(qp, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            n = next(iter(leaf.data.values())).shape[:-2]
            counts[leaf.variant] = counts.get(leaf.variant, 0) + (
                int(n[0]) if n else 1)
    return counts


def make_prompts(vocab: int, seed: int, long_lens=(333, 701),
                 shared=320, short=6, n_short=5):
    """The paper's 6-token prompts, two long ones that prefill in several
    chunks, and a shared-prefix pair (last, so that the engine admits its
    second member after the first one's pages are cached)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    draw = lambda n: [int(t) for t in rng.integers(0, vocab, n)]
    prefix = draw(shared)
    return ([draw(short) for _ in range(n_short)]
            + [draw(n) for n in long_lens]
            + [prefix + draw(11), prefix + draw(23)])


def prefill_logits(eng, prompts):
    """Last-position logits of ``prompts`` through the engine's own
    prefill-chunk program, and the group cache it wrote."""
    import jax.numpy as jnp
    import numpy as np

    C = eng._chunk
    lens = np.array([len(p) for p in prompts], np.int32)
    P = -(-int(lens.max()) // C) * C
    toks = np.zeros((len(prompts), P), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    cache = eng._new_cache(len(prompts))
    last = jnp.zeros((len(prompts), eng.cfg.vocab_size), jnp.float32)
    zeros = jnp.zeros(len(prompts), jnp.int32)
    for j in range(P // C):
        cache, last = eng._prefill_chunk(
            eng.params, cache, jnp.asarray(toks[:, j * C:(j + 1) * C]),
            jnp.asarray(j * C, jnp.int32), jnp.asarray(lens), last, zeros)
    return last, cache, lens


def decode_logits(cfg, params, cache, tokens, positions):
    """Logits of one ``decode_step`` -- the step the engine's decode
    loop runs -- from a prefilled group cache."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    step = jax.jit(lambda p, c, t, pos: T.decode_step(
        p, cfg, c, tokens=t, position=pos)[0])
    return step(params, cache, jnp.asarray(tokens), jnp.asarray(positions))


def _post_all(port: int, prompts, max_tokens: int):
    """POST every prompt concurrently; returns the token lists in order."""
    out: list = [None] * len(prompts)

    def one(i, prompt):
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=900)
            conn.request("POST", "/v1/completions",
                         json.dumps(dict(prompt=prompt,
                                         max_tokens=max_tokens)),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out[i] = (resp.status, json.loads(resp.read()))
            conn.close()
        except Exception as e:          # re-raised on the main thread
            out[i] = e

    threads = [threading.Thread(target=one, args=(i, p))
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    toks = []
    for i, r in enumerate(out):
        if isinstance(r, Exception):
            raise r
        status, body = r
        check(status == 200, f"HTTP request {i}: status {status}: {body}")
        toks.append(body["choices"][0]["token_ids"])
    return toks


def phase_serve(cfg, seed: int, new_tokens: int = NEW_TOKENS,
                cache_len: int = CACHE_LEN, slots: int = SLOTS) -> None:
    import numpy as np

    from repro.serving.engine import Engine, ServeConfig
    from repro.serving.frontend import Frontend, FrontendConfig

    with Phase("build"):
        qp = build_model(cfg, seed)
        counts = count_formats(qp)
        print(f"  {cfg.name} packed with {POLICY}: {counts}")
        check(counts.get("q2_k") == 2 * cfg.n_layers + 1
              and counts.get("q3_k") == 5 * cfg.n_layers,
              f"{POLICY} packed {counts}; expected 2L+1 q2_k, 5L q3_k")

    prompts = make_prompts(cfg.vocab_size, seed)
    scfg = ServeConfig(max_new_tokens=new_tokens, cache_len=cache_len,
                       max_slots=slots, prefix_cache=True, seed=seed)
    eng = Engine(cfg, qp, scfg)
    with Phase("serve/generate"):
        want = eng.generate(prompts)
        print(f"  {len(prompts)} prompts of lengths "
              f"{[len(p) for p in prompts]}: prefix hits "
              f"{eng.stats['prefix_hits']}, prefill groups "
              f"{eng.stats['prefill_groups']}, decode chunks "
              f"{eng.stats['chunks']}")
        check(all(len(t) == new_tokens for t in want),
              f"generate lengths {[len(t) for t in want]}")
        check(eng.stats["prefix_hits"] > 0,
              "the shared-prefix pair made no prefix-cache hit")

    # Greedy tokens are reproducible only under the same admission group
    # and prefix-cache state: a different batch or a warm prefix changes
    # the chunk layout of prefill attention and so the float rounding of
    # its sums, which can flip a greedy near-tie (seen on a v5e). So the
    # concurrent burst checks the front end under load, and the exact
    # check sends one request at a time and compares it with
    # Engine.generate of that prompt alone, in the same cache state.
    with Phase("serve/http"):
        fe = Frontend(eng, FrontendConfig(
            model_name=cfg.name, request_timeout_s=900.0,
            max_tokens_default=new_tokens)).start()
        try:
            burst = _post_all(fe.port, prompts, new_tokens)
            one_by_one = [_post_all(fe.port, [p], new_tokens)[0]
                          for p in prompts]
        finally:
            fe.close()
        check(all(len(t) == new_tokens for t in burst),
              f"concurrent HTTP answer lengths {[len(t) for t in burst]}")
        alone = [eng.generate([p])[0] for p in prompts]
        for i, (g, w) in enumerate(zip(one_by_one, alone)):
            check(g == w, f"HTTP answer {i} differs from Engine.generate:"
                          f"\n  http     {g}\n  generate {w}")
        same = sum(b == w for b, w in zip(burst, want))
        print(f"  {len(burst)} concurrent HTTP answers of {new_tokens} "
              f"tokens, {same} equal to the batched cold generate; "
              f"{len(one_by_one)} one-at-a-time HTTP answers equal "
              "Engine.generate of the same prompt")

    with Phase("serve/xla-datapath"):
        probe = [prompts[0], prompts[6]]
        xcfg = cfg.replace(kernel_impl="xla")
        xeng = Engine(xcfg, qp, ServeConfig(max_new_tokens=new_tokens,
                                            cache_len=cache_len,
                                            max_slots=slots, seed=seed))
        lp, cp, lens = prefill_logits(eng, probe)
        lx, cx, _ = prefill_logits(xeng, probe)
        nxt = np.asarray(lp).argmax(-1).astype(np.int32)
        dp = decode_logits(cfg, eng.params, cp, nxt, lens)
        dx = decode_logits(xcfg, xeng.params, cx, nxt, lens)
        for name, a, b in (("prefill", lp, lx), ("decode", dp, dx)):
            err = rel_err(a, b)
            print(f"  {name} logits, pallas vs xla, prompts of "
                  f"{lens.tolist()} tokens: max rel err {err} "
                  f"(tol {LOGIT_TOL})")
            check(err <= LOGIT_TOL, f"{name} logits differ from the xla "
                                    f"datapath: {err}")


def phase_tp(cfg, seed: int, tp: int = 4, new_tokens: int = NEW_TOKENS,
             cache_len: int = CACHE_LEN, slots: int = SLOTS) -> None:
    import gc

    import jax

    from repro.serving.engine import Engine, ServeConfig

    with Phase("build"):
        qp = build_model(cfg, seed)
    prompts = make_prompts(cfg.vocab_size, seed, long_lens=(333,),
                           n_short=2)[:3]
    mk = lambda **kw: Engine(cfg, qp, ServeConfig(
        max_new_tokens=new_tokens, cache_len=cache_len, max_slots=slots,
        seed=seed, **kw))
    with Phase("tp1"):
        eng = mk()
        want = eng.generate(prompts)
        ref = prefill_logits(eng, prompts)[0]
        del eng
    devs = jax.devices()[:tp]
    failures = []           # every datapath runs; the phase fails after
    for mm in ("padded", "sliced", "sliced_row"):
        with Phase(f"tp{tp}/{mm}"):
            eng = mk(tp=tp, tp_matmul=mm)
            check(list(eng._mesh.devices.flat) == devs,
                  f"mesh devices {eng._mesh.devices} != {devs}")
            leaves = jax.tree.leaves(eng.params)
            placed = {d for a in leaves for d in a.sharding.device_set}
            split = sum(a.addressable_shards[0].data.shape != a.shape
                        for a in leaves)
            check(placed == set(devs) and split > 0,
                  f"params on {placed}, {split} leaves split")
            got = eng.generate(prompts)
            err = rel_err(prefill_logits(eng, prompts)[0], ref)
            same = got == want
            print(f"  tp={tp} {mm}: params over {len(placed)} devices, "
                  f"{split}/{len(leaves)} leaves split; prefill logits "
                  f"max rel err vs tp=1 {err}; tokens equal tp=1: {same}")
            if mm == "padded" and not same:
                failures.append(f"padded tp={tp} tokens differ from tp=1")
            if mm != "padded" and err > LOGIT_TOL:
                failures.append(f"{mm} logits vs tp=1: {err} > {LOGIT_TOL}")
            del eng
            gc.collect()
    check(not failures, "; ".join(failures))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tp=1 vs tp=4 phase")
    args = ap.parse_args()

    import jax

    from repro.configs.base import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0].platform is "
              f"{devs[0].platform!r}); this smoke test runs only on the "
              "chip", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compiles)
    print(f"jax {jax.__version__}, device_kind {devs[0].device_kind!r}, "
          f"{len(devs)} device(s), compile cache {cache_dir}", flush=True)
    cfg = get_arch(ARCH)
    try:
        if args.chips == 4:
            check(len(devs) >= 4, f"--chips 4 needs 4 devices, "
                                  f"jax sees {len(devs)}")
            phase_tp(cfg, args.seed)
        else:
            with Phase("kernels"):
                phase_kernels(args.seed)
            phase_serve(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
