"""Disaggregated prefill/decode serving suite.

THE oracle: routed output through DisaggEngine (prefill workers + KV
page migration + decode workers behind the radix router) must be
TOKEN-IDENTICAL to one monolithic Engine with the same ServeConfig --
greedy across causal / sliding-window / int8-KV, against monolithic
references with the prefix cache off AND on, with speculation riding on
the decode tier, and under temperature sampling (1P+1D). The guarantee
composes from already-pinned pieces: exported pages are bit-for-bit pool
copies (tested in isolation below, int8 scales included), imports land
in the decode worker's ordinary prefix cache, and warm-prefix admission
is parity-pinned in test_prefix_cache -- so the only NEW thing to trust
is the hand-off, which is why export/import gets its own bit-identity
tests before the router ever composes them.

Router behavior (overlap-first placement, spreading, direct-to-decode
for sub-page prompts) and API validation ride along.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models import transformer as T
from repro.serving.disagg import DisaggEngine
from repro.serving.engine import Engine, ServeConfig
from repro.serving.router import KVRouter


@pytest.fixture(scope="module")
def causal():
    cfg = get_arch("tinyllama-1.1b", reduced=True)
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


_BASE = dict(max_new_tokens=5, cache_len=64, decode_chunk=5, max_slots=2,
             prefill_bucket=4, prefill_chunk=16, prefix_page=8)


def _scfg(**kw):
    base = dict(_BASE)
    base.update(kw)
    return ServeConfig(**base)


def _prompts(cfg, n, shared_len=24, uniq=(3, 9), seed=0):
    """Shared-system-prompt queue plus one sub-page prompt (exercises the
    router's direct-to-decode path in every parity run)."""
    rng = np.random.default_rng(seed)
    shared = list(rng.integers(0, cfg.vocab_size, shared_len))
    ps = [shared + list(rng.integers(0, cfg.vocab_size,
                                     int(rng.integers(*uniq))))
          for _ in range(n)]
    ps.append(list(rng.integers(0, cfg.vocab_size, 4)))  # < one page
    return ps


# ---------------------------------------------------------------------------
# the parity matrix: arch family x mono-prefix on/off x spec on/off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", ["causal", "windowed", "int8kv"])
@pytest.mark.parametrize("spec", [False, True])
def test_greedy_parity_matrix(fixture, spec, request):
    """1P+1D routed output == monolithic engine, token for token, against
    BOTH monolithic references (prefix cache off and on), with pages
    actually migrating and the decode tier actually reusing them. With
    ``spec`` the drafter rides on the decode workers (prefill workers
    never decode, so speculation there is moot)."""
    model = request.getfixturevalue(fixture)
    cfg, params = model
    kw = dict(drafter="ngram", draft_k=4) if spec else {}
    prompts = _prompts(cfg, 4, seed=1)
    mono_off = Engine(cfg, params, _scfg(**kw))
    mono_on = Engine(cfg, params, _scfg(prefix_cache=True, **kw))
    dis = DisaggEngine(cfg, params, _scfg(**kw),
                       prefill_workers=1, decode_workers=1)
    expect = mono_off.generate(prompts)
    assert mono_on.generate(prompts) == expect
    assert dis.generate(prompts) == expect
    assert dis.stats["migrated_pages"] > 0
    assert dis.stats["prefix_hits"] > 0          # decode tier reused them
    assert dis.stats["router"]["direct_decode"] == 1   # the sub-page prompt
    # repeat runs stay warm AND identical (radix state survives generate)
    assert dis.generate(prompts) == expect
    assert dis.stats["router"]["migrated_pages_total"] > 0


def test_temperature_parity_1p1d(causal):
    """Same ServeConfig seed + same submission order => the decode worker
    replicates the monolithic engine's per-request key-split discipline
    exactly, so even SAMPLED output is token-identical through the
    disaggregated path (1 decode worker; multi-worker temperature runs
    split into per-worker streams by design)."""
    cfg, params = causal
    prompts = _prompts(cfg, 4, seed=2)
    mono = Engine(cfg, params, _scfg(temperature=0.7, seed=3))
    dis = DisaggEngine(cfg, params, _scfg(temperature=0.7, seed=3),
                       prefill_workers=1, decode_workers=1)
    expect = mono.generate(prompts)
    assert dis.generate(prompts) == expect
    assert dis.stats["migrated_pages"] > 0


def test_multiworker_greedy_parity(causal):
    """2P+2D: greedy sampling is schedule-independent and per-slot
    admission is isolation-pinned, so output stays token-identical to one
    monolithic engine even with requests spread over two decode
    workers -- and the router must actually spread them."""
    cfg, params = causal
    prompts = _prompts(cfg, 5, seed=4)
    mono = Engine(cfg, params, _scfg())
    dis = DisaggEngine(cfg, params, _scfg(),
                       prefill_workers=2, decode_workers=2)
    assert dis.generate(prompts) == mono.generate(prompts)
    rt = dis.stats["router"]
    assert all(n > 0 for n in rt["decode_requests"])     # both workers used
    assert rt["migrated_pages_total"] > 0


# ---------------------------------------------------------------------------
# router placement
# ---------------------------------------------------------------------------

def test_router_prefers_prefix_overlap(causal):
    """Two prefill workers: after worker 0 caches family-A pages, a
    second wave routes the A-prefixed request to worker 0 (overlap) and
    the unrelated request to worker 1 (tie on score 0 -> shallowest
    queue), concentrating prefix reuse where the KV lives."""
    cfg, params = causal
    rng = np.random.default_rng(5)
    A = list(rng.integers(0, cfg.vocab_size, 24))
    dis = DisaggEngine(cfg, params, _scfg(),
                       prefill_workers=2, decode_workers=1)
    dis.generate([A + list(rng.integers(0, cfg.vocab_size, 5))])
    rt = dis.stats["router"]
    assert rt["prefill_requests"] == [1, 0]      # cold tie -> worker 0
    A2 = A + list(rng.integers(0, cfg.vocab_size, 6))
    B = list(rng.integers(0, cfg.vocab_size, 30))
    dis.generate([A2, B])
    rt = dis.stats["router"]
    assert rt["prefill_requests"] == [2, 1]      # A2 -> 0 (overlap), B -> 1
    assert rt["prefill_overlap_hits"][0] == 1
    assert rt["prefill_overlap_tokens"][0] >= 24 - _BASE["prefix_page"]
    assert rt["prefill_hit_rate"][0] == 0.5


def test_router_scoring_no_lru_distortion():
    """prefix_match_len (the router probe) must not touch LRU stamps:
    scoring a request against every worker's tree cannot reorder
    eviction on the workers that lose the vote. Checked host-side on the
    raw radix tree."""
    from repro.serving.prefix_cache import PrefixCache
    pc = PrefixCache(page=2, capacity=2)
    pc.insert([1, 2, 3, 4])                      # two pages
    stamps = {id(c): c.stamp for c in pc._root.children.values()}
    assert pc.match_len([1, 2, 3, 4, 9]) == 4
    assert {id(c): c.stamp
            for c in pc._root.children.values()} == stamps
    m, _ = pc.match([1, 2, 3, 4, 9])             # match() DOES touch
    assert m == 4
    assert {id(c): c.stamp
            for c in pc._root.children.values()} != stamps


# ---------------------------------------------------------------------------
# export/import in isolation: the hand-off must be bit-identical before
# the router ever composes it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", ["causal", "int8kv"])
def test_export_import_bit_identical(fixture, request):
    """Pages exported from one engine and imported into another come back
    out bit-for-bit -- int8-KV payloads AND their f32 scales -- and the
    importer's radix tree then matches the prompt as if it had prefilled
    it itself. Re-import dedupes to zero."""
    model = request.getfixturevalue(fixture)
    cfg, params = model
    rng = np.random.default_rng(6)
    P = list(rng.integers(0, cfg.vocab_size, 21))        # 2 full pages + 5
    src = Engine(cfg, params, _scfg(prefix_cache=True))
    dst = Engine(cfg, params, _scfg(prefix_cache=True))
    src.generate([P])
    kv = src.export_kv_pages(P)
    assert kv.n_pages == 2 and kv.tokens == P[:16]
    if cfg.kv_cache_quant:
        assert kv.payload["k"].dtype == np.int8
        assert set(kv.payload) == {"k", "v", "k_scale", "v_scale"}
        assert kv.payload["k_scale"].dtype == np.float32
    assert dst.import_kv_pages(kv) == 2
    assert dst.prefix_match_len(P) == 16
    back = dst.export_kv_pages(P)
    assert back.tokens == kv.tokens
    for k in kv.payload:
        np.testing.assert_array_equal(np.asarray(back.payload[k]),
                                      np.asarray(kv.payload[k]))
    assert dst.import_kv_pages(kv) == 0                  # dedup
    # and the imported pages SERVE: dst decodes P identically to src
    assert dst.generate([P]) == src.generate([P])
    assert dst.stats["prefix_hits"] == 1


def test_page_roundtrip_ring_wrap(windowed):
    """The page primitives themselves through a sliding-window ring wrap:
    gather pages whose positions straddle the wrap boundary out of one
    ring, scatter them into a second engine's fresh ring, and the
    destination rows/positions must equal the source bit-for-bit (cols
    are position % T on both sides)."""
    cfg, _ = windowed
    Tr = T.attn_cache_len(cfg, 64)
    assert Tr == 64
    page = 8
    key = jax.random.PRNGKey(7)
    ring = T.init_cache(cfg, 2, 64)
    ring = {k: (jax.random.normal(key, v.shape).astype(v.dtype)
                if v.dtype != jnp.int32 else v)
            for k, v in ring.items()}
    # positions 60..75 on slot 1: pages [60..67], [68..75] wrap the ring
    positions = np.arange(60, 76)
    cols = (positions % Tr).reshape(2, page)
    rows = np.array([1, 1])
    pages = T.cache_gather_pages(ring, jnp.asarray(rows),
                                 jnp.asarray(cols))
    ring2 = T.init_cache(cfg, 2, 64)
    ring2 = T.cache_scatter_pages(
        ring2, pages, jnp.asarray(np.array([0, 0])), jnp.asarray(cols),
        jnp.asarray(positions.reshape(2, page)))
    for k, pg in pages.items():
        got = np.asarray(ring2[k][:, 0])[:, cols.ravel()]
        want = np.asarray(ring[k][:, 1])[:, cols.ravel()]
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(ring2["pos"][0])[cols.ravel()], positions)


def test_import_rejects_mismatched_page(causal):
    cfg, params = causal
    rng = np.random.default_rng(8)
    P = list(rng.integers(0, cfg.vocab_size, 17))
    src = Engine(cfg, params, _scfg(prefix_cache=True))
    dst = Engine(cfg, params, _scfg(prefix_cache=True, prefix_page=4))
    src.generate([P])
    with pytest.raises(ValueError, match="page geometry"):
        dst.import_kv_pages(src.export_kv_pages(P))


def test_export_requires_prefix_cache(causal):
    cfg, params = causal
    eng = Engine(cfg, params, _scfg())
    with pytest.raises(RuntimeError, match="prefix_cache"):
        eng.export_kv_pages([1, 2, 3])
    with pytest.raises(RuntimeError, match="prefix_cache"):
        eng.import_kv_pages(None)
    assert eng.prefix_page is None
    assert eng.prefix_match_len([1, 2, 3]) == 0


def test_export_unknown_prompt_is_empty(causal):
    cfg, params = causal
    eng = Engine(cfg, params, _scfg(prefix_cache=True))
    kv = eng.export_kv_pages([5, 6, 7, 8, 9, 10, 11, 12, 13])
    assert kv.n_pages == 0 and kv.payload == {}


# ---------------------------------------------------------------------------
# API validation
# ---------------------------------------------------------------------------

def test_disagg_validation(causal):
    cfg, params = causal
    with pytest.raises(ValueError, match="worker"):
        DisaggEngine(cfg, params, _scfg(), prefill_workers=0)
    ssm = cfg.replace(family="ssm")
    with pytest.raises(ValueError, match="KV-ring"):
        DisaggEngine(ssm, params, _scfg())
    dis = DisaggEngine(cfg, params, _scfg())
    with pytest.raises(ValueError, match="empty"):
        dis.submit([])
    with pytest.raises(ValueError, match="max_new_tokens"):
        dis.submit([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError, match="drafter"):
        dis.submit([1, 2], speculate=True)
    with pytest.raises(ValueError, match="cache_len"):
        dis.submit(list(range(64)))
    dis.submit([1, 2, 3])
    with pytest.raises(RuntimeError, match="pending"):
        dis.generate([[1, 2]])


def test_router_validation():
    with pytest.raises(ValueError, match="router needs"):
        KVRouter([], [object()])


def test_router_depth_clamps_at_zero():
    """A double-done (or a done with no matching pick) must not drive a
    queue depth negative: a negative depth makes that worker look
    permanently shallower than every honest worker, so least-loaded
    placement routes to it forever after. Depths clamp at 0 and the
    stray calls are counted in snapshot()["depth_underflows"]."""
    class _W:                            # router probes prefix_match_len
        def prefix_match_len(self, prompt):
            return 0
    r = KVRouter([_W(), _W()], [object(), object()])
    w = r.pick_prefill([1, 2, 3])
    r.note_prefill_done(w)
    r.note_prefill_done(w)               # double-done: clamped, counted
    r.note_decode_done(1)                # done without pick: ditto
    snap = r.snapshot()
    assert snap["prefill_queue_depth"] == [0, 0]
    assert snap["decode_queue_depth"] == [0, 0]
    assert snap["depth_underflows"] == 2
    # placement is still unbiased: the clamped worker does not win every
    # least-loaded tie-break with a phantom negative depth
    d = r.pick_decode()
    assert d == 0                        # lowest index, not the clamped 1
    r.note_decode_done(d)
    assert r.snapshot()["depth_underflows"] == 2


def test_disagg_cancel_queued(causal):
    cfg, params = causal
    dis = DisaggEngine(cfg, params, _scfg())
    rid = dis.submit([1, 2, 3])
    keep = dis.submit([4, 5, 6, 7])
    assert dis.cancel(rid)
    assert not dis.cancel(999)
    res = dis.run()
    assert res[rid] == []
    assert len(res[keep]) == _BASE["max_new_tokens"]


def test_disagg_folds_span_and_work_counters(causal):
    """The engine's span seconds and work counters reach the aggregate:
    prefill-side keys from both tiers, decode-side keys from the decode
    workers alone (one wave, so each worker's stats are that wave's)."""
    cfg, params = causal
    dis = DisaggEngine(cfg, params, _scfg(),
                       prefill_workers=1, decode_workers=1)
    outs = dis.generate(_prompts(cfg, 3, seed=5))
    s = dis.stats
    tiers = dis.prefill_engines + dis.decode_engines
    for k in ("admit_prep_s", "admit_group_cache_s", "admit_prefill_s",
              "admit_bind_s", "admit_sync_s", "admit_emit_s",
              "prefill_padded_tokens", "queue_wait_total_s",
              "pre_enqueue_wait_s"):
        assert s[k] == pytest.approx(sum(e.stats[k] for e in tiers)), k
    for k in ("decode_dispatch_s", "decode_sync_s", "decode_emit_s",
              "decode_steps", "decode_slot_steps", "decode_tokens",
              "decode_ctx_positions", "decode_ring_positions", "poll_s"):
        assert s[k] == pytest.approx(
            sum(e.stats[k] for e in dis.decode_engines)), k
    assert s["prefill_padded_tokens"] > dis.prefill_engines[0].stats[
        "prefill_padded_tokens"] > 0
    assert s["decode_tokens"] == sum(len(o) - 1 for o in outs)
    assert s["decode_ring_positions"] > 0 and s["queue_wait_total_s"] > 0
    assert s["prefill_s"] == pytest.approx(sum(
        s[f"admit_{k}_s"] for k in ("prep", "group_cache", "prefill",
                                    "bind", "sync")), abs=1e-6)
    assert s["decode_s"] == pytest.approx(
        s["decode_dispatch_s"] + s["decode_sync_s"], abs=1e-6)
