"""Decode-attention Pallas kernel (interpret mode) vs the XLA reference.

The kernel reads layer ``li`` of the stacked (L, B, T, KH, D) ring in
place and promises f32-rounding-level agreement with the XLA reference run
on ``ring[li]``: the same position mask (-1 empty, <= the query position,
sliding window), softcap and GQA grouping. Every case stacks three layers
of different data, so a kernel that read the wrong layer would fail, and
tiles the ring into several grid steps, so the online softmax runs across
tiles. Rows of an entirely empty slot are compared too: both paths average
that head's values over the ring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import decode_attn
from repro.models import layers as L

TOL = 5e-6     # f32 accumulation-order noise at these shapes
LAYERS = 3

CASES = {
    # name: (G, KH, T, block_t, ring dtype, q dtype, window, softcap, layer)
    "bf16_ring_g1": (1, 2, 32, 8, jnp.bfloat16, jnp.float32, None, None, 0),
    "bf16_ring_g2": (2, 2, 32, 8, jnp.bfloat16, jnp.float32, None, None, 1),
    "bf16_ring_g4": (4, 2, 32, 8, jnp.bfloat16, jnp.float32, None, None, 2),
    "bf16_ring_bf16_q": (2, 4, 32, 16, jnp.bfloat16, jnp.bfloat16, None,
                         None, 1),
    "window_wrapped": (2, 2, 16, 4, jnp.bfloat16, jnp.float32, 12, None, 2),
    "softcap": (2, 2, 32, 8, jnp.bfloat16, jnp.float32, None, 5.0, 1),
    "f32_ring": (2, 2, 24, 8, jnp.float32, jnp.float32, None, None, 1),
}


def _positions(T):
    """(B, T) ring positions and (B,) query positions for four slots: a
    ring still filling (empty tail), a ring written past its end (a
    sliding window that wrapped), a slot with empty gaps, and an entirely
    empty slot."""
    t = np.arange(T)
    filling = np.where(t <= 9, t, -1)
    q_wrap = 3 * T + 5                       # the ring has wrapped 3 times
    wrapped = q_wrap - ((q_wrap - t) % T)    # position held by slot t
    gaps = np.where(t % 3 == 1, -1, t)
    empty = np.full(T, -1)
    sp = np.stack([filling, wrapped, gaps, empty]).astype(np.int32)
    qp = np.array([9, q_wrap, T - 1, 0], np.int32)
    return jnp.asarray(sp), jnp.asarray(qp)


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_attn_matches_reference(case):
    G, KH, T, bt, rdt, qdt, window, softcap, li = CASES[case]
    B, D = 4, 64
    ks = jax.random.split(jax.random.PRNGKey(sorted(CASES).index(case)), 3)
    q = jax.random.normal(ks[0], (B, 1, KH * G, D), jnp.float32).astype(qdt)
    k = jax.random.normal(ks[1], (LAYERS, B, T, KH, D),
                          jnp.float32).astype(rdt)
    v = jax.random.normal(ks[2], (LAYERS, B, T, KH, D),
                          jnp.float32).astype(rdt)
    sp, qp = _positions(T)
    ref = L.decode_attention(q, k[li], v[li], sp, qp, window=window,
                             softcap=softcap)
    out = decode_attn(q, k, v, jnp.int32(li), sp, qp, window=window,
                      softcap=softcap, block_t=bt, interpret=True)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    a, b = np.asarray(ref, np.float32), np.asarray(out, np.float32)
    # a bf16 output is the f32 result rounded once: one bf16 ulp apart at
    # most where the two f32 values straddle a rounding boundary
    tol = TOL if qdt == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(b, a, rtol=tol,
                               atol=tol * (np.abs(a).max() + 1e-9))
    # another layer's data gives another answer: the layer index is read
    other = L.decode_attention(q, k[(li + 1) % LAYERS], v[(li + 1) % LAYERS],
                               sp, qp, window=window, softcap=softcap)
    assert np.abs(np.asarray(other, np.float32)[:3] - b[:3]).max() > 1e-2


def test_ring_entry_selects_by_platform():
    """``layers.decode_attention_ring`` lowered for the CPU runs the XLA
    reference on the sliced layer: bit-identical to it."""
    B, T, KH, G, D, li = 2, 16, 2, 2, 32, 1
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, 1, KH * G, D), jnp.float32)
    k = jax.random.normal(ks[1], (LAYERS, B, T, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (LAYERS, B, T, KH, D), jnp.float32)
    sp, qp = _positions(T)
    sp, qp = sp[:B], qp[:B]
    got = jax.jit(L.decode_attention_ring)(q, k, v, jnp.int32(li), sp, qp)
    want = jax.jit(L.decode_attention)(q, k[li], v[li], sp, qp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
