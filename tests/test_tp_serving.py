"""Tensor-parallel serving suite: shard_map TP pinned by multi-device
parity plus host-side sharding-rule unit tests.

THE oracle: greedy serving output is TOKEN-IDENTICAL across mesh shapes
{1, 2, 4} -- across causal / sliding-window / int8-KV attention, with
speculative decoding and the paged prefix cache riding on top, in fp32
AND with a packed quantized policy. The guarantee is by construction,
not luck: weights lane-shard (K rows whole per shard, so packed
super-blocks never straddle devices), the KV cache shards over kv_heads
(slicing a BATCH dim keeps each head's attention sub-problem the same
shape), and the default "padded" matmul datapath zero-embeds each
shard's lanes so every gemm keeps the single-device program shape --
CPU gemms round shape-dependently (pinned below), so same-shape is the
only road to bitwise parity. The "sliced" datapath (true lane-sliced
gemm, 1/size FLOPs per shard) is equal to within an f32 ulp only and is
tested at a documented logit tolerance, same caveat class as
test_spec_decode's batched verify.

Multi-device tests need forced host devices BEFORE jax initializes:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m pytest -x -q tests/test_tp_serving.py

Under the plain tier-1 run (1 device) those tests skip, and a subprocess
test still proves the acceptance core (fp32 parity {1,2,4} with spec +
prefix cache enabled) by forcing 4 devices in a fresh interpreter.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_arch
from repro.core.policy import get_policy
from repro.core.qlinear import quantize_params
from repro.core import quantize as Q
from repro.distributed import sharding as SH
from repro.models import transformer as T
from repro.serving.engine import Engine, ServeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NDEV = len(jax.devices())
needs4 = pytest.mark.skipif(
    NDEV < 4, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                     "device_count=4 (set before jax initializes)")
needs2 = pytest.mark.skipif(
    NDEV < 2, reason="needs >= 2 devices (force host devices via "
                     "XLA_FLAGS)")

BASE = dict(max_new_tokens=6, cache_len=64, decode_chunk=8, max_slots=3,
            prefill_bucket=4, prefill_chunk=8, prefill_batch=3)


def _prompts(cfg, n, seed=0, lo=2, hi=30, shared=0):
    """Ragged prompts (multi-chunk lengths included); ``shared`` prepends
    a common system prefix (the prefix-cache workload)."""
    rng = np.random.default_rng(seed)
    pre = list(rng.integers(0, cfg.vocab_size, shared))
    return [pre + list(rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(lo, hi))))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# host-side unit tests (no extra devices needed)
# ---------------------------------------------------------------------------

def test_serve_tp_plan_divisibility_fallbacks():
    cfg = get_arch("tinyllama-1.1b", reduced=True)       # H=4 KH=2 ff=512
    p1 = SH.make_serve_tp_plan(cfg, 1)
    assert p1.size == 1 and not p1.attn and not p1.mlp
    p2 = SH.make_serve_tp_plan(cfg, 2)
    assert p2.attn and p2.mlp
    # KH=2 not divisible by 4 -> attention falls back to replication,
    # the mlp (ff=512, d=256) still shards
    p4 = SH.make_serve_tp_plan(cfg, 4)
    assert not p4.attn and p4.mlp
    # fused-qkv layouts interleave q/k/v lanes -> attention never shards
    g = get_arch("gpt2-paper", reduced=True)
    assert not SH.make_serve_tp_plan(g, 2).attn
    # moe expert stacks stay replicated at serve time
    m = get_arch("olmoe-1b-7b", reduced=True)
    assert not SH.make_serve_tp_plan(m, 2).mlp
    with pytest.raises(ValueError, match="padded.*sliced"):
        SH.make_serve_tp_plan(cfg, 2, matmul="megatron")


def test_serve_param_specs_lane_only():
    """Serve weights shard lanes ONLY -- in particular the row-parallel
    (in the training rules) w_down keeps its K rows whole per shard."""
    cfg = get_arch("tinyllama-1.1b", reduced=True)
    params = jax.eval_shape(lambda: T.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    plan = SH.make_serve_tp_plan(cfg, 2)
    specs = SH.serve_param_specs(params, plan)
    lay = specs["layers"]
    assert lay["attn"]["wq"] == P(None, None, "model")
    assert lay["attn"]["wo"] == P(None, None, "model")    # lanes, NOT K
    assert lay["mlp"]["w_down"] == P(None, None, "model")
    assert lay["ln1"]["w"] == P()
    assert specs["wte"] == P()                            # replicated head
    # quantized: payload arrays shard their lane (last) axis
    qp, _ = quantize_params(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params),
        get_policy("paper_llama_mix"))
    qspecs = SH.serve_param_specs(qp, plan)
    qt = qspecs["layers"]["mlp"]["w_down"]
    assert isinstance(qt, Q.QTensor)
    assert all(len(sp) and sp[-1] == "model" for sp in qt.data.values())
    # attention fallback (tp=4, KH=2): attn replicated, mlp sharded
    specs4 = SH.serve_param_specs(params, SH.make_serve_tp_plan(cfg, 4))
    assert specs4["layers"]["attn"]["wq"] == P()
    assert specs4["layers"]["mlp"]["w_up"] == P(None, None, "model")


def test_serve_cache_specs_kv_heads():
    cfg = get_arch("llama3.2-1b", reduced=True).replace(
        kv_cache_quant=True, dtype="float32")
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 4, 64))
    plan = SH.make_serve_tp_plan(cfg, 2)
    specs = SH.serve_cache_specs(cache, plan)
    assert specs["k"] == P(None, None, None, "model", None)
    assert specs["k_scale"] == P(None, None, None, "model")  # co-sharded
    assert specs["pos"] == P()
    # page pools co-shard on the same axis
    pool = jax.eval_shape(lambda: T.cache_page_pool(cfg, 8, 8))
    pspecs = SH.serve_cache_specs(pool, plan)
    assert pspecs["v"] == P(None, None, None, "model", None)
    # attention fallback -> fully replicated cache
    nodiv = SH.serve_cache_specs(cache, SH.make_serve_tp_plan(cfg, 8))
    assert nodiv["k"] == P()


def test_lane_shard_and_localize_qtensor():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 128)) * 0.2
    t = Q.quantize("q2_k", w)
    s0 = SH.lane_shard_qtensor(t, 0, 2)
    assert s0.shape == (256, 64)
    assert all(v.shape[-1] * 2 == t.data[k].shape[-1]
               for k, v in s0.data.items())
    with pytest.raises(ValueError, match="divisible"):
        SH.lane_shard_qtensor(t, 0, 3)
    # localize rewrites only lane-sharded QTensor aux shapes
    params = {"a": t, "b": jnp.ones((4, 4))}
    plan = SH.ServeTPPlan(size=2, attn=True, mlp=True)
    specs = {"a": Q.QTensor(t.variant, t.shape,
                            {k: P(None, "model") for k in t.data}),
             "b": P()}
    loc = SH.localize_serve_params(params, specs, 2)
    assert loc["a"].shape == (256, 64)
    rep = {"a": Q.QTensor(t.variant, t.shape, {k: P() for k in t.data}),
           "b": P()}
    assert SH.localize_serve_params(params, rep, 2)["a"].shape == (256, 128)


def test_tp_validation_errors():
    ssm = get_arch("mamba2-2.7b", reduced=True)
    sp = T.init_params(ssm, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="KV-ring family"):
        Engine(ssm, sp, ServeConfig(tp=2, **BASE))
    cfg = get_arch("tinyllama-1.1b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="device"):
        Engine(cfg, params, ServeConfig(tp=NDEV + 1, **BASE))
    with pytest.raises(ValueError, match="tp"):
        Engine(cfg, params, ServeConfig(tp=0, **BASE))


def test_padded_gemm_column_independence():
    """THE property the padded TP datapath rests on: zeroing the
    off-shard columns of a weight (same gemm shape) never perturbs the
    on-shard columns' bits -- gemm rounding is per-output-column, so a
    shard computing dot(x, zero_embed(w_lanes)) reproduces the
    single-device dot's columns exactly, at every tp degree. Asserted
    bitwise over the engine's own projection shapes, including the
    (24, 256, 256) case where the lane-SLICED dot demonstrably rounds
    differently on CPU XLA (which is why sliced mode only promises
    ulp-level agreement; see test_sliced_datapath_logit_tolerance)."""
    for seed, (M, K, N) in enumerate([(24, 256, 256), (24, 256, 512),
                                      (24, 512, 256), (3, 256, 512)]):
        kx, kw = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, (M, K), jnp.float32)
        w = jax.random.normal(kw, (K, N), jnp.float32)
        full = np.asarray(jax.jit(jnp.dot)(x, w))
        for S in (2, 4):
            n = N // S
            for i in range(S):
                wz = np.zeros((K, N), np.float32)
                wz[:, i * n:(i + 1) * n] = np.asarray(w[:, i * n:(i + 1) * n])
                emb = np.asarray(jax.jit(jnp.dot)(x, jnp.asarray(wz)))
                np.testing.assert_array_equal(
                    emb[:, i * n:(i + 1) * n], full[:, i * n:(i + 1) * n])
                np.testing.assert_array_equal(
                    emb[:, :i * n], 0.0)
                np.testing.assert_array_equal(emb[:, (i + 1) * n:], 0.0)


# ---------------------------------------------------------------------------
# multi-device parity (forced host devices)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def causal():
    # n_kv_heads=4 so tp=4 shards attention too (stock reduced KH=2
    # exercises the fallback instead, covered by test_greedy_parity_fallback)
    cfg = get_arch("tinyllama-1.1b", reduced=True).replace(n_kv_heads=4)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def windowed():
    cfg = get_arch("h2o-danube-1.8b", reduced=True)      # window = 64
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def int8kv():
    cfg = get_arch("llama3.2-1b", reduced=True).replace(
        kv_cache_quant=True, dtype="float32")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _parity(model, meshes, prompts=None, runs=1, **kw):
    """Generate with identical queues at every tp degree; all outputs
    (and a second warm run, for prefix workloads) must be token-identical
    to the tp=1 engine's."""
    cfg, params = model
    prompts = prompts or _prompts(cfg, 5, seed=1)
    outs, engines = {}, {}
    for tp in meshes:
        eng = Engine(cfg, params, ServeConfig(tp=tp, **BASE, **kw))
        outs[tp] = [eng.generate(prompts) for _ in range(runs)]
        engines[tp] = eng
    for tp in meshes[1:]:
        assert outs[tp] == outs[meshes[0]], f"tp={tp} diverged"
    return engines


@needs4
@pytest.mark.parametrize("spec,prefix", [(False, False), (False, True),
                                         (True, False), (True, True)])
def test_greedy_parity_causal_meshes_1_2_4(causal, spec, prefix):
    """fp32 greedy, mesh {1,2,4}: bitwise token parity across the full
    spec x prefix matrix -- cold AND warm (radix re-hit) cycles."""
    kw = {}
    if spec:
        kw.update(drafter="ngram", draft_k=3)
    if prefix:
        kw.update(prefix_cache=True, prefix_page=8)
    prompts = _prompts(causal[0], 5, seed=2, shared=24 if prefix else 0,
                       lo=2, hi=8 if prefix else 30)
    engines = _parity(causal, (1, 2, 4), prompts=prompts, runs=2, **kw)
    assert engines[2]._plan.attn and engines[4]._plan.attn
    if prefix:     # warm cycle really hit, identically at every degree
        hits = {tp: e.stats["prefix_hits"] for tp, e in engines.items()}
        assert hits[1] > 0 and hits[1] == hits[2] == hits[4]
    if spec:       # bitwise-equal accept decisions, not just tokens
        acc = {tp: (e.stats["draft_tokens"], e.stats["draft_accepted"])
               for tp, e in engines.items()}
        assert acc[1] == acc[2] == acc[4]


@needs4
def test_greedy_parity_fallback_config(causal):
    """Stock reduced tinyllama (KH=2): tp=4 falls back to replicated
    attention + sharded mlp and must STILL be token-identical."""
    cfg = get_arch("tinyllama-1.1b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engines = _parity((cfg, params), (1, 4))
    assert not engines[4]._plan.attn and engines[4]._plan.mlp


@needs4
def test_greedy_parity_sliding_window(windowed):
    """Ring wrap under TP: prompts longer than the window, budgets that
    decode across the wrap point."""
    cfg, _ = windowed
    prompts = _prompts(cfg, 4, seed=3, lo=40, hi=90)     # > window = 64
    _parity(windowed, (1, 2), prompts=prompts)


@needs4
def test_greedy_parity_int8_kv(int8kv):
    """int8 KV quantization per (token, head): head-sliced quantize is
    elementwise across kv_heads, so the sharded cache holds bit-equal
    payloads AND scales."""
    engines = _parity(int8kv, (1, 2))
    assert engines[2]._cspecs["k_scale"] == P(None, None, None, "model")


@needs4
def test_greedy_parity_self_drafter(causal):
    """Truncated-layer self-drafting reuses the sharded packed weights
    inside the TP decode loop (draft cache carved from the sharded
    ring)."""
    _parity(causal, (1, 2), drafter="self", draft_k=2, draft_layers=1)


@needs4
def test_temperature_parity_meshes(causal):
    """Sampling: logits are replicated bit-identically, PRNG keys split
    identically on every shard, so temperature sampling is ALSO
    token-identical across tp degrees (padded datapath)."""
    _parity(causal, (1, 2, 4), temperature=0.8, seed=7)


@needs4
def test_quantized_padded_token_parity(causal):
    """Packed q2/q3 weights, padded datapath: dequantization is
    lane-elementwise and the gemm keeps the single-device shape, so even
    the QUANTIZED pipeline is token-identical across meshes."""
    cfg, params = causal
    qp, _ = quantize_params(params, get_policy("paper_llama_mix"))
    _parity((cfg, qp), (1, 2), prompts=_prompts(cfg, 4, seed=5, hi=14))


@needs2
def test_greedy_parity_gpt2_gelu(int8kv):
    """gpt2 family under TP: fused-qkv attention replicates (lane slices
    would interleave q/k/v), the gelu mlp shards with its LANE-SHARDED
    b_fc added to the still-local hidden and replicated b_proj added
    after the output gather -- the one bias-placement path no other
    config exercises. LayerNorm + learned positions ride along."""
    cfg = get_arch("gpt2-paper", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engines = _parity((cfg, params), (1, 2),
                      prompts=_prompts(cfg, 4, seed=13, hi=14))
    assert not engines[2]._plan.attn and engines[2]._plan.mlp


@needs2
def test_sliced_datapath_logit_tolerance(causal):
    """The throughput ("sliced") datapath: true lane-sliced gemms. CPU
    gemms round shape-dependently, so logits match the tp=1 program only
    to ~an f32 ulp of the accumulation (documented tolerance; greedy
    tokens may flip on near-ties, same caveat as test_spec_decode's
    batched verify -- so this test pins LOGITS, not tokens)."""
    cfg, params = causal
    lens = [14, 9, 11]
    rng = np.random.default_rng(11)
    toks = np.zeros((3, 16), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, cfg.vocab_size, n)
    lengths = jnp.asarray(lens, jnp.int32)
    cached = jnp.zeros(3, jnp.int32)
    logits = {}
    for tp, mm in ((1, "padded"), (2, "sliced")):
        eng = Engine(cfg, params, ServeConfig(tp=tp, tp_matmul=mm, **BASE))
        gcache = eng._new_cache(3)
        last = jnp.zeros((3, cfg.vocab_size), jnp.float32)
        for j in range(2):
            gcache, last = eng._prefill_chunk(
                eng.params, gcache, jnp.asarray(toks[:, j * 8:(j + 1) * 8]),
                jnp.asarray(j * 8, jnp.int32), lengths, last, cached)
        logits[tp] = np.asarray(jax.device_get(last))
    np.testing.assert_allclose(
        logits[2], logits[1], rtol=1e-4,
        atol=1e-4 * np.abs(logits[1]).max())


def _chunked_logits(cfg, params, tp, mm):
    """Logits after two prefill chunks of a 3-request ragged group under
    one (tp, tp_matmul) engine -- the shared probe for the sliced-family
    tolerance tests."""
    lens = [14, 9, 11]
    rng = np.random.default_rng(11)
    toks = np.zeros((3, 16), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, cfg.vocab_size, n)
    lengths = jnp.asarray(lens, jnp.int32)
    cached = jnp.zeros(3, jnp.int32)
    eng = Engine(cfg, params, ServeConfig(tp=tp, tp_matmul=mm, **BASE))
    gcache = eng._new_cache(3)
    last = jnp.zeros((3, cfg.vocab_size), jnp.float32)
    for j in range(2):
        gcache, last = eng._prefill_chunk(
            eng.params, gcache, jnp.asarray(toks[:, j * 8:(j + 1) * 8]),
            jnp.asarray(j * 8, jnp.int32), lengths, last, cached)
    return np.asarray(jax.device_get(last)), eng


@needs2
def test_sliced_row_logit_tolerance_bf16(causal):
    """The "sliced_row" datapath (row-parallel o-/down-proj, fp32
    partials psummed then rounded once): splitting the K reduction
    across shards cannot bit-match the full-K dot once activations
    round to bf16 at layer boundaries, so with the default bf16
    activations the contract is ~a few BF16 ulps of the logits
    (measured ~5e-3 rel on CPU XLA; eps_bf16 = 7.8e-3), not the f32
    envelope the lane-only "sliced" datapath keeps."""
    cfg, params = causal
    ref, _ = _chunked_logits(cfg, params, 1, "padded")
    got, eng = _chunked_logits(cfg, params, 2, "sliced_row")
    # unquantized fixture: plain wo/w_down K-rows divide -> "packed"
    assert eng._plan.attn_row == "packed" and eng._plan.mlp_row == "packed"
    np.testing.assert_allclose(got, ref, rtol=2e-2,
                               atol=2e-2 * np.abs(ref).max())
    assert np.abs(got - ref).max() <= 1.5e-2 * np.abs(ref).max()


@needs2
def test_sliced_row_logit_tolerance_f32(causal):
    """With fp32 activations the ONLY divergence left in "sliced_row" is
    the K-reduction split itself, so the logits sit inside the same
    f32-ulp envelope as the lane-only "sliced" datapath (measured
    ~2e-5 rel)."""
    cfg, _ = causal
    cfg = cfg.replace(dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ref, _ = _chunked_logits(cfg, params, 1, "padded")
    got, _ = _chunked_logits(cfg, params, 2, "sliced_row")
    np.testing.assert_allclose(got, ref, rtol=1e-4,
                               atol=1e-4 * np.abs(ref).max())


@needs2
def test_sliced_row_quantized_row_modes(causal):
    """Quantized params pick per-leaf row modes: the causal fixture's
    wo (K=256, one q3_k super-block) cannot K-shard and falls back to
    "dequant" (replicated payload, per-shard row slice), while w_down
    (K=512) shards whole super-blocks ("packed"). Logits stay inside
    the activation-ulp envelope either way."""
    cfg, params = causal
    qp, _ = quantize_params(params, get_policy("paper_llama_mix"))
    ref, _ = _chunked_logits(cfg, qp, 1, "padded")
    got, eng = _chunked_logits(cfg, qp, 2, "sliced_row")
    assert eng._plan.attn_row == "dequant"
    assert eng._plan.mlp_row == "packed"
    np.testing.assert_allclose(got, ref, rtol=2e-2,
                               atol=2e-2 * np.abs(ref).max())


@needs2
def test_ring_collective_matmul_parity():
    """layers.tp_ring_dense -- the collective-matmul fallback that
    "sliced_row" full-output projections use when no row-parallel mode
    applies: lane-sharded input chunks accumulate against the local
    lane slice of the weight in an fp32 carry while ppermute forwards
    them around the ring. Must match the plain full matmul within the
    activation-ulp contract, for a packed QTensor and a plain weight."""
    from jax.sharding import Mesh
    from repro.models import layers as L
    size = 2
    K, N, M = 512, 256, 8
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(kw, (K, N), jnp.float32) * 0.2
    t = Q.quantize("q3_k", w)
    plan = SH.ServeTPPlan(size=size, attn=True, mlp=True,
                          matmul="sliced_row")
    mesh = Mesh(np.asarray(jax.devices()[:size]), ("model",))
    for weight, wspec in (
            (t, Q.QTensor(t.variant, t.shape,
                          {k: P(None, "model") for k in t.data})),
            (w.astype(jnp.bfloat16), P(None, "model"))):
        def body(xl, wl):
            wl = SH.localize_serve_params(
                wl, jax.tree.map(lambda _: wspec, wl,
                                 is_leaf=lambda q: isinstance(q, Q.QTensor)),
                size) if isinstance(wl, Q.QTensor) else wl
            with SH.serve_tp(plan):
                return L.tp_ring_dense(xl, wl)
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, "model"), wspec),
                          out_specs=P(), check_vma=False)
        got = np.asarray(jax.jit(f)(x, weight), np.float32)
        wf = Q.dequantize(t, dtype=jnp.bfloat16) if isinstance(
            weight, Q.QTensor) else w.astype(jnp.bfloat16)
        ref = np.asarray(jnp.dot(x, wf).astype(x.dtype), np.float32)
        np.testing.assert_allclose(got, ref, rtol=2e-2,
                                   atol=2e-2 * np.abs(ref).max())


@needs2
def test_cancel_midstream_under_tp(causal):
    """In-flight cancel from an on_token callback behaves identically at
    tp=2 (host scheduler state is mesh-oblivious)."""
    cfg, params = causal
    prompts = _prompts(cfg, 3, seed=9, hi=12)

    def run(tp):
        eng = Engine(cfg, params, ServeConfig(tp=tp, **BASE))
        ids, seen = [], {}

        def cb(rid, tok):
            seen[rid] = seen.get(rid, 0) + 1
            if rid == ids[0] and seen[rid] == 2:
                eng.cancel(ids[1])
        for p in prompts:
            ids.append(eng.submit(p, on_token=cb))
        res = eng.run()
        return [res[i] for i in ids]

    assert run(1) == run(2)


# ---------------------------------------------------------------------------
# acceptance core under the plain tier-1 run: subprocess forces 4 host
# devices in a fresh interpreter (XLA_FLAGS must precede jax init)
# ---------------------------------------------------------------------------

TP_SNIPPET = r"""
import sys
sys.path.insert(0, "src")
from repro.launch.hostdev import force_host_devices
force_host_devices(4)
import jax
import numpy as np
from repro.configs.base import get_arch
from repro.models import transformer as T
from repro.serving.engine import Engine, ServeConfig

cfg = get_arch("tinyllama-1.1b", reduced=True).replace(n_kv_heads=4)
params = T.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(1)
shared = list(rng.integers(0, cfg.vocab_size, 24))
prompts = [shared + list(rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(2, 8))))
           for _ in range(5)]
outs = {}
for tp in (1, 2, 4):
    eng = Engine(cfg, params, ServeConfig(
        max_new_tokens=6, cache_len=64, decode_chunk=8, max_slots=3,
        prefill_bucket=4, prefill_chunk=8, prefill_batch=3,
        tp=tp, drafter="ngram", draft_k=3,
        prefix_cache=True, prefix_page=8))
    cold = eng.generate(prompts)
    warm = eng.generate(prompts)
    assert eng.stats["prefix_hits"] == len(prompts), eng.stats
    outs[tp] = (cold, warm)
assert outs[1] == outs[2] == outs[4], outs
print("SUBPROCESS_TP_PARITY_OK")
"""


@pytest.mark.slow
def test_subprocess_forced4_spec_prefix_parity():
    """fp32 greedy + ngram speculation + warm prefix cache: token
    parity across meshes {1, 2, 4} -- the acceptance core, provable even
    when this pytest process only sees one device."""
    out = subprocess.run([sys.executable, "-c", TP_SNIPPET], cwd=REPO,
                         capture_output=True, text=True, timeout=1200)
    assert "SUBPROCESS_TP_PARITY_OK" in out.stdout, out.stdout + out.stderr
