"""Model building blocks: norms, rotary embeddings, attention, MLPs.

Everything is functional (params are pytrees) and serve-aware: any weight
matrix may be a packed ``QTensor``, in which case the matmul dispatches to
the fused BFP kernel path (``kernels/ops.bfp_matmul``) -- the per-layer
variant switch that is the paper's headline feature.

Attention implementations:
  * ``naive``      -- materializes (…, S, T) scores; tiny shapes/tests only.
  * ``blockwise``  -- exact online-softmax over KV chunks with a python loop
    over Q chunks and a ``lax.scan`` over exactly the causally-needed KV
    chunks (static per Q chunk), so HLO FLOPs stay ~triangular and peak
    memory is one (cq x ck) score block. This is the dry-run/long-seq path.
  * decode         -- single-token query against a (possibly ring-buffer)
    cache with per-slot absolute positions; ``decode_attention_ring``
    reads one layer of the stacked ring in place (a Pallas kernel on TPU).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import calibrate as CAL
from repro.core.quantize import QTensor, dequantize
from repro.distributed.sharding import constrain, serve_tp_plan
from repro.kernels import ops as kops
from repro.kernels.decode_attn import decode_attn
from repro.kernels.prefill_attn import prefill_attn_fused

NEG_INF = -1e30


def dense(x: jnp.ndarray, w, *, impl: str = "auto",
          interpret: bool = False) -> jnp.ndarray:
    """MatMul against either a plain array or a packed QTensor."""
    if isinstance(w, QTensor):
        return kops.bfp_matmul(x, w, impl=impl, interpret=interpret)
    return jnp.dot(x, w.astype(x.dtype))


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def layernorm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm(x, p: Dict, kind: str, eps: float):
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"], eps)
    return layernorm(x, p["w"], p["b"], eps)


# ---------------------------------------------------------------------------
# position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def rope_cos_sin(positions: jnp.ndarray, d_head: int, theta: float,
                 mrope_sections: Optional[Tuple[int, int, int]] = None):
    """positions: (B, S) or (3, B, S) for M-RoPE. Returns cos/sin (B, S, D/2).

    M-RoPE (Qwen2-VL): the D/2 rotary frequencies are split into
    (temporal, height, width) sections; each section rotates by its own
    position stream. For text tokens the three streams coincide.
    """
    inv = rope_freqs(d_head, theta)                       # (D/2,)
    if positions.ndim == 2:
        ang = positions[..., None].astype(jnp.float32) * inv  # (B,S,D/2)
    else:
        assert mrope_sections is not None
        ang3 = positions[..., None].astype(jnp.float32) * inv  # (3,B,S,D/2)
        sect = []
        for i, n in enumerate(mrope_sections):
            sect.append(jnp.full((n,), i, jnp.int32))
        sel = jnp.concatenate(sect)                        # (D/2,)
        ang = jnp.take_along_axis(
            jnp.moveaxis(ang3, 0, -1), sel[None, None, :, None], axis=-1
        )[..., 0]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: (B, S, H, D); cos/sin: (B, S, D/2). Split-half (llama) convention."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1, x2 = xf[..., :d2], xf[..., d2:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def sincos_pos_emb(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """(B, S) -> (B, S, d_model) fixed sinusoidal embedding (musicgen)."""
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _split_gqa(q, n_kv: int):
    """(B, S, H, D) -> (B, S, KH, G, D)."""
    B, S, H, D = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, D)


def naive_attention(q, k, v, *, causal=True, window=None, scale=None,
                    softcap=None, q_positions=None, kv_positions=None):
    """q: (B,S,H,D), k/v: (B,T,KH,D) -> (B,S,H,D). Materializes scores."""
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    scale = scale or (1.0 / math.sqrt(D))
    qg = _split_gqa(q, KH)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qp = q_positions if q_positions is not None else jnp.arange(S)[None]
    kp = kv_positions if kv_positions is not None else jnp.arange(T)[None]
    mask = jnp.ones((B, S, T), bool)
    if causal:
        mask &= kp[:, None, :] <= qp[:, :, None]
    if window:
        mask &= kp[:, None, :] > qp[:, :, None] - window
    mask &= kp[:, None, :] >= 0              # invalid cache slots carry -1
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, D).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal=True, window=None, scale=None,
                        softcap=None, q_chunk=1024, kv_chunk=1024,
                        unroll=1):
    """Exact chunked online-softmax attention, triangular FLOPs.

    Requires S % q_chunk == 0 and T % kv_chunk == 0 (callers pad); assumes
    q/k positions are 0..S-1 aligned (self-attention over a full sequence).
    """
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale or (1.0 / math.sqrt(D))
    cq = min(q_chunk, S)
    ck = min(kv_chunk, T)
    assert S % cq == 0 and T % ck == 0, (S, cq, T, ck)
    nq = S // cq
    out = []
    for i in range(nq):
        q0 = i * cq
        qi = _split_gqa(q[:, q0:q0 + cq], KH).astype(jnp.float32)  # (B,cq,KH,G,D)
        # causally-needed kv chunk range for this q chunk (static)
        hi = (q0 + cq + ck - 1) // ck if causal else T // ck
        lo = 0
        if window is not None:
            lo = max(0, (q0 - window + 1) // ck)
        nkv = hi - lo
        ks = jax.lax.slice_in_dim(k, lo * ck, hi * ck, axis=1)
        vs = jax.lax.slice_in_dim(v, lo * ck, hi * ck, axis=1)
        ks = ks.reshape(B, nkv, ck, KH, D)
        vs = vs.reshape(B, nkv, ck, KH, D)
        qpos = q0 + jnp.arange(cq)

        def step(carry, inp):
            m, l, acc = carry
            kc, vc, j = inp
            s = jnp.einsum("bqkgd,btkd->bkgqt", qi, kc.astype(jnp.float32))
            s = s * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            kpos = (lo + j) * ck + jnp.arange(ck)
            msk = jnp.ones((cq, ck), bool)
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window:
                msk &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KH, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, cq), jnp.float32)
        a0 = jnp.zeros((B, KH, G, cq, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, a0),
            (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0),
             jnp.arange(nkv)), unroll=unroll)
        o = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,KH,G,cq,D)
        out.append(jnp.moveaxis(o, 3, 1).reshape(B, cq, H, D))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


def prefill_attention(q, k_cache, v_cache, slot_pos, k_new, v_new,
                      positions, valid, *, window=None, scale=None,
                      softcap=None, impl="naive", interpret=False):
    """Chunked-prefill attention: one prompt chunk against cache + itself.

    q: (B,C,H,D) chunk queries; k_cache/v_cache: (B,T,KH,D) ring *before*
    this chunk's writes (an entry a later in-chunk token will overwrite is
    still a real past token for earlier queries -- attending the pre-write
    ring plus the explicit in-chunk keys reproduces exact causal/ring
    semantics, including sliding-window wrap); slot_pos: (B,T) absolute
    positions per ring slot (-1 empty); k_new/v_new: (B,C,KH,D) this
    chunk's keys/values; positions: (B,C) absolute; valid: (B,C) False on
    right-padding (those keys never win attention; their query outputs are
    garbage the caller must ignore).

    ``impl="fused"`` routes the concatenated problem through the Pallas
    flash kernel (``kernels.prefill_attn``, f32-rounding-identical online
    softmax, no (C, T) score materialization; interpret=True runs it on
    CPU); the default materializing ``naive_attention`` path is the
    reference."""
    kv_pos_new = jnp.where(valid, positions, -1)
    k_all = jnp.concatenate([k_cache, k_new.astype(k_cache.dtype)], axis=1)
    v_all = jnp.concatenate([v_cache, v_new.astype(v_cache.dtype)], axis=1)
    kv_pos = jnp.concatenate([slot_pos, kv_pos_new], axis=1)
    if impl == "fused":
        return prefill_attn_fused(q, k_all, v_all, positions, kv_pos,
                                  window=window, scale=scale,
                                  softcap=softcap, interpret=interpret)
    return naive_attention(q, k_all, v_all, causal=True, window=window,
                           scale=scale, softcap=softcap,
                           q_positions=positions, kv_positions=kv_pos)


def verify_attention(q, k_cache, v_cache, slot_pos, k_new, v_new,
                     positions, valid, *, window=None, scale=None,
                     softcap=None):
    """Draft-block verify attention (speculative decoding): BIT-identical
    to running ``decode_attention`` once per token.

    ``prefill_attention`` would be semantically correct here, but it sums
    the block's own keys at the END of the concatenated KV axis, while
    plain decode sums each new key in-place at its ring slot -- a
    different f32 accumulation order, i.e. logits that differ by an ulp
    and can flip a greedy argmax on a near-tie. Greedy speculative decode
    promises *bit-identical* output to plain decode, so verify replays
    decode's exact dataflow instead: scan the block's columns, write each
    valid column's K/V into the ring carry at ``position % T``, then run
    the very same ``decode_attention`` program on the updated ring. Every
    column sees the ring laid out exactly as plain decode would have laid
    it out at that step (accepted drafts resident at their slots, not
    appended), column shapes match decode's (B, 1, H, D), and the
    summation order is identical -- so the scores are too.

    q: (B, S, H, D); k_new/v_new: (B, S, KH, D) at ring dtype semantics
    (caller pre-rounds / pre-dequantizes exactly like the decode write
    path); positions (B, S) per-row absolute; valid (B, S) marks columns
    that run (col 0 only for a plain decode step riding the program).
    Invalid columns leave the ring untouched and their outputs are
    garbage the caller must ignore. Requires S <= T (distinct slots).
    Returns (B, S, H, D)."""
    B, S, H, D = q.shape
    T = k_cache.shape[1]
    bidx = jnp.arange(B)

    def step(carry, xs):
        kc, vc, sp = carry
        qj, kj, vj, pj, okj = xs            # (B,H,D) (B,KH,D) ... (B,) (B,)
        slot = pj % T
        kw = jnp.where(okj[:, None, None], kj.astype(kc.dtype),
                       kc[bidx, slot])
        vw = jnp.where(okj[:, None, None], vj.astype(vc.dtype),
                       vc[bidx, slot])
        pw = jnp.where(okj, pj, sp[bidx, slot])
        kc = kc.at[bidx, slot].set(kw)
        vc = vc.at[bidx, slot].set(vw)
        sp = sp.at[bidx, slot].set(pw)
        o = decode_attention(qj[:, None], kc, vc, sp, pj, window=window,
                             scale=scale, softcap=softcap)
        return (kc, vc, sp), o[:, 0]

    _, outs = jax.lax.scan(
        step, (k_cache, v_cache, slot_pos),
        (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k_new, 1, 0),
         jnp.moveaxis(v_new, 1, 0), positions.T, valid.T))
    return jnp.moveaxis(outs, 0, 1)


def decode_attention(q, k_cache, v_cache, slot_pos, q_pos, *,
                     window=None, scale=None, softcap=None):
    """Single-step decode. q: (B,1,H,D); caches: (B,T,KH,D);
    slot_pos: (B,T) absolute positions per cache slot (-1 = empty);
    q_pos: (B,) current position."""
    B, _, H, D = q.shape
    T, KH = k_cache.shape[1], k_cache.shape[2]
    scale = scale or (1.0 / math.sqrt(D))
    qg = _split_gqa(q, KH).astype(jnp.float32)[:, 0]        # (B,KH,G,D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg,
                   k_cache.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    msk = (slot_pos >= 0) & (slot_pos <= q_pos[:, None])
    if window:
        msk &= slot_pos > (q_pos[:, None] - window)
    s = jnp.where(msk[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


def decode_attention_ring(q, k_ring, v_ring, layer, slot_pos, q_pos, *,
                          window=None, softcap=None):
    """``decode_attention`` against layer ``layer`` of the stacked
    (L, B, T, KH, D) rings. Lowered for a TPU, a bf16 / f32 ring is read
    in place by the ``decode_attn`` Pallas kernel; elsewhere the layer is
    sliced out and ``decode_attention`` (the reference) runs on it."""
    def xla():
        at = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0,
                                                    keepdims=False)
        return decode_attention(q, at(k_ring), at(v_ring), slot_pos, q_pos,
                                window=window, softcap=softcap)

    if k_ring.dtype not in (jnp.bfloat16, jnp.float32):
        return xla()
    return jax.lax.platform_dependent(
        tpu=lambda: decode_attn(q, k_ring, v_ring, layer, slot_pos, q_pos,
                                window=window, softcap=softcap),
        default=xla)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def _tp_mlp_active() -> bool:
    """Serve-TP lane sharding for the ffn block (shard_map body only)."""
    plan = serve_tp_plan()
    return plan is not None and plan.size > 1 and plan.mlp


def tp_lane_dense(x, w, out: str, *, impl="auto", interpret=False):
    """Serve-TP projection against a lane-sharded weight (``w`` is this
    shard's (..., K, N/size) lane slice; K rows are whole, so every
    output column is a full-K dot).

    ``out="local"``: return this shard's lane block, NO collective --
    q/k/v (the block IS this shard's heads) and gate/up/fc (the ffn
    hidden stays sharded through the elementwise activation).
    ``out="full"``: replicated full output via ONE collective -- o-proj
    and down-proj, whose consumers (residual adds, norms) need the
    replicated activation.

    Datapath per ServeTPPlan.matmul: "padded" zero-embeds the slice and
    runs the single-device gemm shape (bit-identical columns by
    construction -- the parity default); "sliced" runs the true
    lane-sliced gemm (FLOPs and packed HBM traffic 1/size per shard,
    equal to within an f32 ulp: CPU gemms round shape-dependently)."""
    plan = serve_tp_plan()
    if plan is None or plan.size == 1:
        return dense(x, w, impl=impl, interpret=interpret)
    if plan.matmul == "padded":
        y = kops.tp_local_lanes(
            dense(x, kops.tp_embed_lanes(w), impl=impl, interpret=interpret))
    else:
        y = dense(x, w, impl=impl, interpret=interpret)
    return y if out == "local" else kops.tp_gather_lanes(y)


def tp_ring_dense(x, w, *, impl="auto", interpret=False):
    """Ring collective-matmul: a full-output serve-TP projection whose
    input lives lane-sharded (``x`` is this shard's K-chunk) and whose
    weight lives lane-sharded too (``w`` is (..., K, N/size)), computed
    WITHOUT ever materializing the gathered input. Each of the ``size``
    steps multiplies the chunk currently in hand against its matching
    K-rows of the local weight while ``ppermute`` forwards the chunk one
    hop around the ring -- so the all-gather's wire time hides behind
    the gemms (the collective-matmul overlap; on a real mesh each hop's
    DMA runs concurrently with the current chunk's dot). The final lane
    outputs are assembled by the usual tiled all-gather.

    K accumulates chunk-at-a-time in an fp32 carry (single rounding at
    the end), so the result carries the same activation-ulp contract as
    the rest of the "sliced_row" datapath -- tp_lane_dense routes here
    for full-output projections when no row-parallel mode applies.
    Packed weights dequantize their local lane slice ONCE (1/size of the
    full weight traffic), outside the ring loop; the per-step K-row
    slice then needs no super-block alignment."""
    plan = serve_tp_plan()
    if plan is None or plan.size == 1:
        return dense(x, w, impl=impl, interpret=interpret)
    size, axis = plan.size, plan.axis
    kl = x.shape[-1]
    i = jax.lax.axis_index(axis)
    perm = [(s, (s + 1) % size) for s in range(size)]
    if isinstance(w, QTensor):
        wf = dequantize(w, dtype=jnp.bfloat16)
    else:
        wf = w.astype(x.dtype)

    chunk, acc = x, None
    for s in range(size):
        # the chunk in hand at step s started at shard (i - s): that is
        # its K offset into the (full-K, local-lane) weight
        j = (i - s) % size
        rows = jax.lax.dynamic_slice_in_dim(wf, j * kl, kl, 0)
        part = jnp.dot(chunk.astype(wf.dtype), rows,
                       preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
        if s + 1 < size:
            chunk = jax.lax.ppermute(chunk, axis, perm)
    y = acc.astype(x.dtype)
    return kops.tp_gather_lanes(y)


def tp_row_dense(x, w, mode: str, *, impl="auto", interpret=False):
    """Serve-TP row-parallel projection (sliced datapath): ``x`` is this
    shard's K-slice of the projection input -- its own head outputs (the
    o-proj) or ffn lanes (the down-proj) -- fed straight into a
    partial-K gemm, and ONE ``psum`` assembles the replicated output.
    Pairs with the column-parallel projections upstream exactly as in
    Megatron, replacing TWO per-layer collectives (the input's lane
    gather + the output's gather) with one, at narrower wire width.

    ``mode`` (from ServeTPPlan.attn_row / mlp_row):
      "packed"  -- ``w`` is this shard's K-row slice (whole super-blocks,
        aux already localized), so the plain fused/XLA gemm applies.
      "dequant" -- ``w`` is the full replicated packed tensor; each shard
        dequantizes and slices its K rows (kops.tp_row_local_matmul).

    Partials emit fp32 and the psum runs at fp32 width, rounding to the
    activation dtype once AFTER the reduce (see tp_row_local_matmul) --
    so the only divergence from the lane dataflow is the K-reduction
    order across shards. That reorder cannot bit-match a full-K dot
    once activations round to bf16 at layer boundaries, which is why
    this path is its own datapath value ("sliced_row") with an
    activation-ulp tolerance contract -- f32 models stay inside the
    f32-ulp envelope ("padded"/"sliced" never route here)."""
    plan = serve_tp_plan()
    if plan is None or plan.size == 1:
        return dense(x, w, impl=impl, interpret=interpret)
    if isinstance(w, QTensor):
        y = kops.tp_row_local_matmul(x, w, mode, impl=impl,
                                     interpret=interpret)
    else:
        # plain weights row-shard whenever K divides, so shard_map has
        # already handed over this shard's (K/size, N) rows
        y = jnp.dot(x, w.astype(x.dtype),
                    preferred_element_type=jnp.float32)
    return jax.lax.psum(y, plan.axis).astype(x.dtype)


def swiglu_mlp(x, p: Dict, *, impl="auto", interpret=False):
    if _tp_mlp_active():
        plan = serve_tp_plan()
        # serve TP (shard_map): gate/up emit this shard's ffn lanes and
        # the activation stays local. Row-parallel plans feed those lanes
        # straight into the down-proj and psum once (tp_row_dense);
        # otherwise ONE exact all-gather assembles the hidden (w_down
        # keeps its K rows whole per shard) and one more gathers the down
        # output -- see tp_lane_dense
        g = tp_lane_dense(x, p["w_gate"], "local", impl=impl,
                          interpret=interpret)
        u = tp_lane_dense(x, p["w_up"], "local", impl=impl,
                          interpret=interpret)
        h = jax.nn.silu(g) * u
        if plan.mlp_row:
            return tp_row_dense(h, p["w_down"], plan.mlp_row, impl=impl,
                                interpret=interpret)
        if plan.matmul == "sliced_row":
            # no row layout for w_down (plan built without params):
            # ring collective-matmul hides the hidden's gather behind
            # the chunked down-proj gemms
            return tp_ring_dense(h, p["w_down"], impl=impl,
                                 interpret=interpret)
        h = kops.tp_gather_lanes(h)
        return tp_lane_dense(h, p["w_down"], "full", impl=impl,
                             interpret=interpret)
    CAL.tap(("mlp/w_gate", "mlp/w_up"), x)
    g = dense(x, p["w_gate"], impl=impl, interpret=interpret)
    u = dense(x, p["w_up"], impl=impl, interpret=interpret)
    # Megatron-style TP: ffn hidden sharded over model on the ff dim;
    # the row-parallel down-proj output is constrained replicated-on-d so
    # the TP all-reduce happens HERE, in bf16, not inside the next norm's
    # f32 upcast (GSPMD would otherwise sink it there at 2x width)
    h = constrain(jax.nn.silu(g) * u, "dp", None, "model")
    CAL.tap("mlp/w_down", h)
    return constrain(dense(h, p["w_down"], impl=impl, interpret=interpret),
                     "dp", None, None)


def gelu_mlp(x, p: Dict, *, impl="auto", interpret=False):
    if _tp_mlp_active():
        plan = serve_tp_plan()
        h = tp_lane_dense(x, p["c_fc"], "local", impl=impl,
                          interpret=interpret)
        if "b_fc" in p:
            # b_fc is lane-sharded with c_fc, so the add stays local;
            # b_proj adds after the output psum/gather and is replicated
            h = h + p["b_fc"].astype(h.dtype)
        h = jax.nn.gelu(h, approximate=True)
        if plan.mlp_row:
            o = tp_row_dense(h, p["c_proj"], plan.mlp_row, impl=impl,
                             interpret=interpret)
        elif plan.matmul == "sliced_row":
            o = tp_ring_dense(h, p["c_proj"], impl=impl,
                              interpret=interpret)
        else:
            h = kops.tp_gather_lanes(h)
            o = tp_lane_dense(h, p["c_proj"], "full", impl=impl,
                              interpret=interpret)
        if "b_proj" in p:
            o = o + p["b_proj"].astype(o.dtype)
        return o
    CAL.tap("mlp/c_fc", x)
    h = dense(x, p["c_fc"], impl=impl, interpret=interpret)
    if "b_fc" in p:
        h = h + p["b_fc"].astype(h.dtype)
    h = constrain(jax.nn.gelu(h, approximate=True), "dp", None, "model")
    CAL.tap("mlp/c_proj", h)
    o = constrain(dense(h, p["c_proj"], impl=impl, interpret=interpret),
                  "dp", None, None)
    if "b_proj" in p:
        o = o + p["b_proj"].astype(o.dtype)
    return o
