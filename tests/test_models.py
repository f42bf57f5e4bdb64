"""Per-arch smoke tests (reduced configs): forward + train step on CPU,
shape/NaN assertions -- one per assigned architecture + paper models."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_arch
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.training import steps as S

ALL_ARCHS = list(ARCH_IDS)


def _batch(cfg, key, B=2, S=16):
    ks = jax.random.split(key, 3)
    batch = {"labels": jax.random.randint(ks[0], (B, S), 0,
                                          cfg.vocab_size)}
    if cfg.embed_input:
        batch["tokens"] = jax.random.randint(ks[1], (B, S), 0,
                                             cfg.vocab_size)
    else:
        batch["embeds"] = jax.random.normal(ks[1], (B, S, cfg.d_model),
                                            jnp.float32)
    if cfg.pos_emb == "mrope":
        batch["positions"] = jnp.broadcast_to(jnp.arange(S)[None, None],
                                              (3, B, S)).astype(jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_no_nans(arch):
    cfg = get_arch(arch, reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, aux, _ = T.forward_seq(
        params, cfg, tokens=batch.get("tokens"),
        embeds=batch.get("embeds"), positions=batch.get("positions"))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step(arch):
    cfg = get_arch(arch, reduced=True)
    opt = AdamWConfig(warmup_steps=1, total_steps=10)
    state = S.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(S.make_train_step(cfg, opt))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    state2, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(state2["step"]) == 1
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda p, q: float(jnp.abs(p - q).max()),
                     state["params"], state2["params"]))
    assert delta > 0


@pytest.mark.parametrize("arch", ["llama3.2-1b", "zamba2-1.2b",
                                  "mamba2-2.7b", "olmoe-1b-7b",
                                  "gpt2-paper", "h2o-danube-1.8b"])
def test_decode_matches_full_forward(arch):
    # fp32 compute: the decode and full-sequence paths reduce in different
    # orders, which is bit-visible at bf16 but not a semantic difference
    cfg = get_arch(arch, reduced=True).replace(dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    B, S_pre, n_new = 2, 16, 4
    S_tot = S_pre + n_new
    key = jax.random.PRNGKey(1)
    if cfg.embed_input:
        toks = jax.random.randint(key, (B, S_tot), 0, cfg.vocab_size)
        fkw = dict(tokens=toks)
        pkw = dict(tokens=toks[:, :S_pre])
    else:
        em = jax.random.normal(key, (B, S_tot, cfg.d_model), jnp.float32)
        fkw = dict(embeds=em)
        pkw = dict(embeds=em[:, :S_pre])
    logits_full, _, _ = T.forward_seq(params, cfg, **fkw)
    logits_pre, _, caches = T.forward_seq(params, cfg, want_cache=True,
                                          **pkw)
    cache = T.cache_from_prefill(cfg, caches, S_pre,
                                 cache_len=T.attn_cache_len(cfg, S_tot),
                                 dtype=jnp.float32)
    errs = [float(jnp.abs(logits_pre[:, -1]
                          - logits_full[:, S_pre - 1]).max())]
    for t in range(n_new):
        pos = jnp.full((B,), S_pre + t, jnp.int32)
        skw = (dict(tokens=toks[:, S_pre + t]) if cfg.embed_input
               else dict(embeds=em[:, S_pre + t]))
        lg, cache = T.decode_step(params, cfg, cache, position=pos, **skw)
        errs.append(float(jnp.abs(lg - logits_full[:, S_pre + t]).max()))
    scale = float(jnp.abs(logits_full).max()) + 1e-9
    # MoE: router logits differ by ~1 ulp between the two paths, which can
    # flip near-tied top-k choices -- an inherent (documented) property of
    # capacity routing, not a cache bug
    tol = 5e-3 if cfg.family == "moe" else 2e-4
    assert max(errs) / scale < tol, errs


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_decode_live_mask_leaves_dead_slots_untouched(kv_quant):
    """decode_step(live=) writes each live slot's new K/V row (and, on the
    int8 ring, its scales) in place at (layer, slot, position % T) and
    nothing else: dead slots' rings and positions stay bitwise as they
    were, and so does every other row of the live slots."""
    cfg = get_arch("qwen3-1.7b", reduced=True).replace(
        dtype="bfloat16", kv_cache_quant=kv_quant)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    B, Tn = 4, 16
    cache = T.init_cache(cfg, B, Tn)
    ks = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for name in ("k", "v"):
        x = jax.random.normal(next(ks), cache[name].shape, jnp.float32)
        cache[name] = ((x * 40).astype(jnp.int8) if kv_quant
                       else x.astype(cache[name].dtype))
        if kv_quant:
            cache[name + "_scale"] = jax.random.uniform(
                next(ks), cache[name + "_scale"].shape, jnp.float32)
    position = jnp.array([5, 9, 21, 3], jnp.int32)   # slot 2 has wrapped
    cache["pos"] = jnp.where(jnp.arange(Tn)[None] < position[:, None] % Tn,
                             jnp.arange(Tn)[None], -1).astype(jnp.int32)
    live = jnp.array([True, False, True, False])
    before = jax.tree.map(np.asarray, cache)
    _, after = jax.jit(lambda c: T.decode_step(
        params, cfg, c, tokens=jnp.array([1, 2, 3, 4], jnp.int32),
        position=position, live=live))(cache)
    after = jax.tree.map(np.asarray, after)
    slot = np.asarray(position) % Tn
    written = np.zeros((B, Tn), bool)
    written[[0, 2], slot[[0, 2]]] = True
    for name in before:
        changed = before[name] != after[name]
        if name != "pos":       # (L, B, T, ...) -> which (slot, row) moved
            changed = changed.any(axis=tuple(
                i for i in range(changed.ndim) if i not in (1, 2)))
        assert not (changed & ~written).any(), (
            name, np.argwhere(changed & ~written))
        assert changed[0, slot[0]] and changed[2, slot[2]], name
    np.testing.assert_array_equal(after["pos"][[0, 2], slot[[0, 2]]],
                                  np.asarray(position)[[0, 2]])


def test_swa_ring_buffer_decode():
    """Sliding-window arch: decode far past the window with a ring cache
    must equal the full forward."""
    # fp32 compute: ring-buffer slot order permutes the softmax summation
    # order at wrap, which is bit-visible at bf16 but not a correctness bug
    cfg = get_arch("h2o-danube-1.8b", reduced=True).replace(
        sliding_window=8, dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    B, S_tot = 1, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S_tot), 0,
                              cfg.vocab_size)
    logits_full, _, _ = T.forward_seq(params, cfg, tokens=toks)
    # prefill 4, then decode 28 steps through a ring cache of size 8
    S_pre = 4
    _, _, caches = T.forward_seq(params, cfg, want_cache=True,
                                 tokens=toks[:, :S_pre])
    cache = T.cache_from_prefill(cfg, caches, S_pre, cache_len=8,
                                 dtype=jnp.float32)
    errs = []
    for t in range(S_pre, S_tot):
        pos = jnp.full((B,), t, jnp.int32)
        lg, cache = T.decode_step(params, cfg, cache, position=pos,
                                  tokens=toks[:, t])
        errs.append(float(jnp.abs(lg - logits_full[:, t]).max()))
    scale = float(jnp.abs(logits_full).max())
    assert max(errs) / scale < 2e-4, max(errs)


def test_blockwise_attention_equals_naive():
    from repro.models import layers as L
    key = jax.random.PRNGKey(0)
    B, S, H, KH, D = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KH, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KH, D))
    for window in (None, 24):
        o1 = L.naive_attention(q, k, v, causal=True, window=window)
        o2 = L.blockwise_attention(q, k, v, causal=True, window=window,
                                   q_chunk=16, kv_chunk=16)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-5, atol=2e-5)


def test_ssd_chunked_equals_recurrence():
    from repro.models.mamba2 import _ssd_chunk_scan, naive_recurrence
    key = jax.random.PRNGKey(3)
    B, S, H, P, N = 2, 48, 4, 8, 16
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    s0 = jax.random.normal(key, (B, H, P, N)) * 0.1
    y1, st1 = _ssd_chunk_scan(x, dt, A, Bm, Cm, s0, 16)   # S % 16 == 0
    y2, st2 = naive_recurrence(x, dt, A, Bm, Cm, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=1e-4)
    # non-divisible S -> padded path
    y3, st3 = _ssd_chunk_scan(x[:, :40], dt[:, :40], A, Bm[:, :40],
                              Cm[:, :40], s0, 16)
    y4, st4 = naive_recurrence(x[:, :40], dt[:, :40], A, Bm[:, :40],
                               Cm[:, :40], s0)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y4), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st3), np.asarray(st4), atol=1e-4)


def test_moe_routing_properties():
    from repro.models.moe import moe_block
    cfg = get_arch("olmoe-1b-7b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe_block(x, lp, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(aux) > 0.5          # Switch aux loss ~1 for random routing
    # zero input -> zero output (experts are linear+silu with no bias)
    y0, _ = moe_block(jnp.zeros_like(x), lp, cfg)
    assert float(jnp.abs(y0).max()) < 1e-5


def test_mrope_sections():
    from repro.models.layers import rope_cos_sin, apply_rope
    B, S, D = 2, 8, 32
    pos3 = jnp.stack([jnp.arange(S)[None].repeat(B, 0)] * 3)
    cos3, sin3 = rope_cos_sin(pos3, D, 1e4, mrope_sections=(4, 6, 6))
    cos1, sin1 = rope_cos_sin(pos3[0], D, 1e4)
    # equal position streams -> M-RoPE == standard RoPE
    np.testing.assert_allclose(np.asarray(cos3), np.asarray(cos1),
                               rtol=1e-6)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, 2, D))
    np.testing.assert_allclose(np.asarray(apply_rope(q, cos3, sin3)),
                               np.asarray(apply_rope(q, cos1, sin1)),
                               rtol=1e-5, atol=1e-5)
