"""Single-query decode attention over one layer of the stacked KV ring.

The decode step keeps every layer's K and V ring in one (L, B, T, KH, D)
array that rides the layer scan's carry. Slicing layer ``li`` out of it
for ``layers.decode_attention`` makes XLA copy the whole per-layer ring
(B x T x KH x D, twice per layer and step); this kernel instead reads the
layer's tiles where they lie: the layer index is a scalar-prefetch
argument of the BlockSpec index maps, so the only HBM traffic is the one
read of the ring that attention needs anyway.

Layout. A grid step holds one slot's (bt, KH, D) tile of K and of V, seen
as a (bt * KH, D) matrix whose row ``t * KH + h`` is position t of kv
head h. All KH * G query rows of the slot (row ``h * G + g``) score every
row of the tile in ONE matmul; scores across heads (row head != column
head) are masked out. That spends KH x the MXU outputs of a per-head
product but feeds each K/V element through the MXU once, like the
per-head form, and keeps the DMA one contiguous tile per step.

Numerics match ``layers.decode_attention`` to f32 rounding: scores,
softmax statistics and the value accumulation are float32. Operands reach
the MXU in bfloat16 without loss: a bf16 ring is exact, and each f32
operand (a float32 q, the softmax weights) is split into three bf16 terms
whose sum is the f32 value to 2^-24; an f32 ring runs f32 dots at
HIGHEST precision. Masking is by position (``slot_pos``: -1 empty, <= the
query position, inside the sliding window), as in the XLA path, with a
fully masked slot averaging its head's values the same way.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# cross-head scores sit below NEG_INF, so a slot whose positions are all
# masked still weights only its own head's values (as the XLA path does)
CROSS_HEAD = 2 * NEG_INF
# K + V tile bytes per grid step (each double-buffered)
_TILE_BYTES = 2 * 1024 * 1024


def _split3(x):
    """f32 -> three bf16 terms whose f32 sum is x (to 2^-24 relative)."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _kernel(li_ref, qpos_ref, q_ref, k_ref, v_ref, kp_ref, o_ref, m_ref,
            l_ref, *, scale, window, softcap, G, KH, nq, nt, exact):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    R = KH * G
    bt, D = k_ref.shape[0], k_ref.shape[2]
    N = bt * KH
    k = k_ref[...].reshape(N, D)
    v = v_ref[...].reshape(N, D)
    nt_dims = (((1,), (1,)), ((), ()))
    if exact:
        s = jax.lax.dot_general(q_ref[...], k, nt_dims,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    else:
        sq = jax.lax.dot_general(q_ref[...], k, nt_dims,
                                 preferred_element_type=jnp.float32)
        s = sq[:R]
        for i in range(1, nq):
            s = s + sq[i * R:(i + 1) * R]
    s = s * scale                                        # (R, N)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qp = qpos_ref[b]
    kp = kp_ref[0]                                       # (1, N)
    ok = (kp >= 0) & (kp <= qp)
    if window:
        ok &= kp > qp - window
    rows = jax.lax.broadcasted_iota(jnp.int32, (R, N), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, N), 1)
    same = rows // G == cols % KH
    s = jnp.where(same, jnp.where(ok, s, NEG_INF), CROSS_HEAD)

    m_prev = m_ref[...]                                  # (R, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                               # (R, N)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    if exact:
        pv = jnp.dot(p, v, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    else:
        pv3 = jnp.dot(jnp.concatenate(_split3(p), axis=0), v,
                      preferred_element_type=jnp.float32)
        pv = pv3[:R] + pv3[R:2 * R] + pv3[2 * R:]
    o_ref[...] = o_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(j == nt - 1)
    def _finish():
        o_ref[...] = o_ref[...] / l_ref[...]


def _block_t(T: int, row_bytes: int) -> int:
    """Largest divisor of T whose K + V tiles fit ``_TILE_BYTES``."""
    cap = max(1, _TILE_BYTES // (2 * row_bytes))
    return max(d for d in range(1, min(T, cap) + 1) if T % d == 0)


def decode_attn(q, k_ring, v_ring, layer, slot_pos, q_pos, *, window=None,
                softcap=None, block_t=None, interpret: bool = False):
    """q: (B,1,H,D); k_ring/v_ring: (L,B,T,KH,D) stacked rings; layer: ()
    int32 index into L; slot_pos: (B,T) absolute position per ring slot
    (-1 empty); q_pos: (B,) query positions.

    Returns (B,1,H,D) in q.dtype, equal to f32 rounding to
    ``layers.decode_attention(q, k_ring[layer], v_ring[layer], slot_pos,
    q_pos, ...)`` without slicing the ring. ``block_t`` (a divisor of T)
    sets the ring positions per grid step; by default the largest whose
    K and V tiles fit 2 MiB."""
    B, _, H, D = q.shape
    T, KH = k_ring.shape[2], k_ring.shape[3]
    G = H // KH
    R = KH * G
    exact = k_ring.dtype == jnp.float32
    bt = block_t or _block_t(T, KH * D * k_ring.dtype.itemsize)
    assert T % bt == 0, (T, bt)
    nt = T // bt
    N = bt * KH

    qr = q.reshape(B, R, D)
    if exact:
        qr, nq = qr.astype(jnp.float32), 1
    elif q.dtype == jnp.bfloat16:
        nq = 1
    else:
        qr, nq = jnp.concatenate(_split3(qr.astype(jnp.float32)), axis=1), 3
    # ring positions repeated per kv head: column t * KH + h of a tile
    kp = jnp.repeat(slot_pos.astype(jnp.int32), KH, axis=1)
    kp = kp.reshape(B, nt, 1, N)
    li = jnp.asarray(layer, jnp.int32).reshape(1)

    ring_spec = pl.BlockSpec((None, None, bt, KH, D),
                             lambda b, j, li, qp: (li[0], b, j, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D), window=window,
                          softcap=softcap, G=G, KH=KH, nq=nq, nt=nt,
                          exact=exact),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nt),
            in_specs=[
                pl.BlockSpec((None, nq * R, D),
                             lambda b, j, li, qp: (b, 0, 0)),
                ring_spec, ring_spec,
                pl.BlockSpec((None, None, 1, N),
                             lambda b, j, li, qp: (b, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, R, D),
                                   lambda b, j, li, qp: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, R, D), jnp.float32),
        name="decode_attn",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(li, q_pos.astype(jnp.int32), qr, k_ring, v_ring, kp)
    return out.reshape(B, 1, H, D).astype(q.dtype)
