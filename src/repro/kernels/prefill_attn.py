"""Fused prefill-attention Pallas kernel (flash-style online softmax).

The chunked-prefill attention pattern (``layers.prefill_attention``) is a
masked cross-attention: a (B, C) prompt chunk's queries attend the decode
ring *plus* the chunk's own keys, with validity decided purely by
POSITION arrays (absolute query positions vs. per-slot kv positions,
``-1`` marking empty slots) rather than by a dense mask. The naive path
materializes the full (C, T) score matrix per head in f32; this kernel
streams KV tiles through VMEM with the canonical online-softmax
recurrence instead, so peak memory per grid step is one (bq, bk) score
tile and the (bq, D) output accumulator -- the same output-stationary
discipline as the fused dequant-matmul kernel (K innermost,
"arbitrary"; running max/denominator in VMEM scratch).

GQA is folded in the wrapper: heads collapse onto their KV group
((B, KH) becomes the outer grid axis, the G query heads of a group ride
along the row axis), so the kernel body is a plain single-head attention
over (rows, D) x (T, D) with per-row / per-column position operands.

Numerics match ``layers.naive_attention`` to f32 rounding: scores,
softmax statistics and the value accumulation all run in f32, with one
cast back to the query dtype at the end. Rows whose every column is
masked (right-padding / empty slots) produce garbage by the same
convention as the naive path -- callers discard them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bfp_matmul import _round_up

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, m_ref, l_ref, *,
            scale: float, window, softcap, nt: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, D)
    k = k_ref[0].astype(jnp.float32)                 # (bk, D)
    v = v_ref[0].astype(jnp.float32)                 # (bk, D)
    qp = qp_ref[0].reshape(-1, 1)                    # (bq, 1) int32
    kp = kp_ref[0]                                   # (1, bk) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    msk = (kp >= 0) & (kp <= qp)
    if window:
        msk &= kp > qp - window
    s = jnp.where(msk, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                           # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                   # (bq, 1)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    o_ref[0] = o_ref[0] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nt - 1)
    def _finish():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-30)


def prefill_attn_fused(q, k, v, q_pos, kv_pos, *, window=None, scale=None,
                       softcap=None, block_q: int = 128,
                       block_k: int = 256,
                       interpret: bool = False) -> jnp.ndarray:
    """q: (B,C,H,D); k/v: (B,T,KH,D); q_pos: (B,C); kv_pos: (B,T).

    Returns (B,C,H,D) in q.dtype: causal position-masked attention
    identical (to f32 rounding) to ``layers.naive_attention`` with the
    same position operands. kv_pos == -1 marks empty slots; right-padded
    query rows (q_pos past the prompt) yield garbage the caller ignores,
    same convention as the naive path."""
    B, C, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale or (1.0 / math.sqrt(D))

    # fold GQA: (B,C,H,D) -> (B*KH, C*G, D); row r <-> (c = r // G, g)
    qg = q.reshape(B, C, KH, G, D).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B * KH, C * G, D)
    k2 = k.transpose(0, 2, 1, 3).reshape(B * KH, T, D)
    v2 = v.transpose(0, 2, 1, 3).reshape(B * KH, T, D)
    qp = jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)       # (B, C*G)
    qp = jnp.repeat(qp[:, None], KH, axis=1).reshape(B * KH, C * G)
    kp = jnp.repeat(kv_pos.astype(jnp.int32)[:, None], KH,
                    axis=1).reshape(B * KH, T)

    M = C * G
    bq = min(block_q, _round_up(M, 8))
    bk = min(block_k, _round_up(T, 128))
    Mp, Tp = _round_up(M, bq), _round_up(T, bk)
    if Mp != M:
        qg = jnp.pad(qg, ((0, 0), (0, Mp - M), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, Mp - M)), constant_values=-1)
    if Tp != T:
        k2 = jnp.pad(k2, ((0, 0), (0, Tp - T), (0, 0)))
        v2 = jnp.pad(v2, ((0, 0), (0, Tp - T), (0, 0)))
        kp = jnp.pad(kp, ((0, 0), (0, Tp - T)), constant_values=-1)
    # positions ride as (rows, 1, len): a (1, bq) block of a 2-D array is
    # no legal tile, while the unit middle dim is the array's full extent
    qp, kp = qp[:, None], kp[:, None]

    grid = (B * KH, Mp // bq, Tp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          softcap=softcap, nt=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KH, Mp, D), jnp.float32),
        name="prefill_attn",
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, k2, v2, qp, kp)

    out = out[:, :M].reshape(B, KH, C, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, H, D).astype(q.dtype)
