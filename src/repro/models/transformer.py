"""Model assembly: init + train/prefill/decode for every supported family.

Families (see configs/base.py): dense (llama-style), gpt2 (fused-qkv,
LayerNorm/GELU, learned positions), moe (GShard-style top-k capacity
routing), ssm (Mamba2), hybrid (Zamba2: Mamba2 backbone + shared attention
block every k layers), vlm (dense + M-RoPE + stub patch embeddings), audio
(dense + sincos positions + stub frame embeddings).

All stacks scan over layers with stacked params (HLO size O(1) in depth).
Serve-mode params may contain packed ``QTensor`` leaves (mixed per-layer
variants -- the paper's flexible BFP execution); ``layers.dense`` dispatches
them to the fused kernel path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import calibrate as CAL
from repro.core.quantize import QTensor, dequantize
from repro.distributed import sharding as SH
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dense_init(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))).astype(dtype)


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict[str, Any]:
    keys = iter(jax.random.split(key, 64))
    nk = lambda: next(keys)
    d, Lc, V = cfg.d_model, cfg.n_layers, cfg.vocab_size
    H, KH, Dh, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff
    p: Dict[str, Any] = {}

    def norm_p(width, stacked=True):
        shape = (Lc, width) if stacked else (width,)
        out = {"w": jnp.ones(shape, dtype)}
        if cfg.norm_type == "layernorm":
            out["b"] = jnp.zeros(shape, dtype)
        return out

    if cfg.embed_input:
        # 1/sqrt(d) scale keeps tied-head logits O(1)
        p["wte"] = _dense_init(nk(), (V, d), d, dtype)
    if cfg.pos_emb == "learned":
        p["wpe"] = _dense_init(nk(), (cfg.max_position, d), 1.0, dtype) * 0.02

    if cfg.family in ("dense", "vlm", "audio", "moe", "gpt2"):
        attn: Dict[str, Any] = {}
        if cfg.fused_qkv:
            attn["c_attn"] = _dense_init(nk(), (Lc, d, 3 * d), d, dtype)
            attn["b_attn"] = jnp.zeros((Lc, 3 * d), dtype)
            attn["c_proj"] = _dense_init(nk(), (Lc, d, d), d, dtype)
            attn["b_proj"] = jnp.zeros((Lc, d), dtype)
        else:
            attn["wq"] = _dense_init(nk(), (Lc, d, H * Dh), d, dtype)
            attn["wk"] = _dense_init(nk(), (Lc, d, KH * Dh), d, dtype)
            attn["wv"] = _dense_init(nk(), (Lc, d, KH * Dh), d, dtype)
            attn["wo"] = _dense_init(nk(), (Lc, H * Dh, d), H * Dh, dtype)
            if cfg.qk_norm:
                attn["q_norm"] = jnp.ones((Lc, Dh), dtype)
                attn["k_norm"] = jnp.ones((Lc, Dh), dtype)
        blk: Dict[str, Any] = {"ln1": norm_p(d), "ln2": norm_p(d),
                               "attn": attn}
        if cfg.family == "moe":
            fe = cfg.moe_d_ff
            E = cfg.n_experts
            blk["moe"] = {
                "router": _dense_init(nk(), (Lc, d, E), d, dtype),
                "w_gate": _dense_init(nk(), (Lc, E, d, fe), d, dtype),
                "w_up": _dense_init(nk(), (Lc, E, d, fe), d, dtype),
                "w_down": _dense_init(nk(), (Lc, E, fe, d), fe, dtype),
            }
        elif cfg.act == "gelu":
            blk["mlp"] = {
                "c_fc": _dense_init(nk(), (Lc, d, f), d, dtype),
                "b_fc": jnp.zeros((Lc, f), dtype),
                "c_proj": _dense_init(nk(), (Lc, f, d), f, dtype),
                "b_proj": jnp.zeros((Lc, d), dtype),
            }
        else:
            blk["mlp"] = {
                "w_gate": _dense_init(nk(), (Lc, d, f), d, dtype),
                "w_up": _dense_init(nk(), (Lc, d, f), d, dtype),
                "w_down": _dense_init(nk(), (Lc, f, d), f, dtype),
            }
        p["layers"] = blk

    elif cfg.family in ("ssm", "hybrid"):
        dd = M2.ssm_dims(cfg)
        p["layers"] = {
            "ln1": norm_p(d),
            "ssm": {
                "in_proj": _dense_init(nk(), (Lc, d, dd["d_proj"]), d, dtype),
                "out_proj": _dense_init(nk(), (Lc, dd["d_inner"], d),
                                        dd["d_inner"], dtype),
                "conv_w": _dense_init(nk(), (Lc, cfg.ssm_conv_width,
                                             dd["conv_ch"]), 4.0, dtype),
                "conv_b": jnp.zeros((Lc, dd["conv_ch"]), dtype),
                "A_log": jnp.zeros((Lc, dd["n_heads"]), jnp.float32),
                "D": jnp.ones((Lc, dd["n_heads"]), jnp.float32),
                "dt_bias": jnp.zeros((Lc, dd["n_heads"]), jnp.float32),
                "norm_w": jnp.ones((Lc, dd["d_inner"]), dtype),
            },
        }
        if cfg.family == "hybrid":
            d2 = 2 * d
            fh = cfg.hybrid_attn_d_ff or cfg.d_ff
            Dh2 = d2 // cfg.n_heads
            p["shared"] = {
                "ln1": {"w": jnp.ones((d2,), dtype)},
                "ln2": {"w": jnp.ones((d2,), dtype)},
                "attn": {
                    "wq": _dense_init(nk(), (d2, H * Dh2), d2, dtype),
                    "wk": _dense_init(nk(), (d2, KH * Dh2), d2, dtype),
                    "wv": _dense_init(nk(), (d2, KH * Dh2), d2, dtype),
                    "wo": _dense_init(nk(), (H * Dh2, d2), H * Dh2, dtype),
                },
                "mlp": {
                    "w_gate": _dense_init(nk(), (d2, fh), d2, dtype),
                    "w_up": _dense_init(nk(), (d2, fh), d2, dtype),
                    "w_down": _dense_init(nk(), (fh, d2), fh, dtype),
                },
                "proj_out": _dense_init(nk(), (d2, d), d2, dtype),
            }
    else:
        raise ValueError(cfg.family)

    p["ln_f"] = norm_p(d, stacked=False)
    if not cfg.tie_embeddings:
        p["lm_head"] = _dense_init(nk(), (d, V), d, dtype)
    return p


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _maybe_dequant(w):
    return dequantize(w, dtype=jnp.bfloat16) if isinstance(w, QTensor) else w


def _embed(params, cfg: ModelConfig, tokens=None, embeds=None,
           positions=None):
    if embeds is not None:
        h = embeds
    else:
        wte = _maybe_dequant(params["wte"])
        h = wte[tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.pos_emb == "learned":
        wpe = params["wpe"]
        h = h + wpe[positions].astype(h.dtype)
    elif cfg.pos_emb == "sincos":
        h = h + L.sincos_pos_emb(positions, cfg.d_model).astype(h.dtype)
    return h


def _logits(params, cfg: ModelConfig, h, impl="auto", interpret=False):
    if cfg.tie_embeddings:
        wte = _maybe_dequant(params["wte"])
        return jnp.einsum("...d,vd->...v", h.astype(jnp.float32),
                          wte.astype(jnp.float32))
    CAL.tap("lm_head", h)
    out = L.dense(h, params["lm_head"], impl=impl, interpret=interpret)
    return out.astype(jnp.float32)


def _tp_attn_shards(cfg: ModelConfig) -> int:
    """Serve-TP shard count over attention heads (1 when inactive)."""
    plan = SH.serve_tp_plan()
    return plan.size if (plan is not None and plan.attn) else 1


def _qkv(a_in, lp, cfg: ModelConfig, impl, interpret):
    B, S, _ = a_in.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    # serve TP (shard_map): wq/wk/wv are lane-sharded, so this shard's
    # projection output block IS its contiguous run of whole heads -- no
    # collective here; the KV cache co-shards over kv_heads and attention
    # below runs shape-generically on the local head counts (slicing the
    # head BATCH dim keeps each head's sub-problem the same shape, so
    # per-head attention math is bit-identical across tp degrees)
    s = _tp_attn_shards(cfg)
    H, KH = H // s, KH // s
    attn = lp["attn"]
    if cfg.fused_qkv:
        CAL.tap("attn/c_attn", a_in)
        qkv = L.dense(a_in, attn["c_attn"], impl=impl, interpret=interpret)
        qkv = qkv + attn["b_attn"].astype(qkv.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    elif s > 1:
        q = L.tp_lane_dense(a_in, attn["wq"], "local", impl=impl,
                            interpret=interpret)
        k = L.tp_lane_dense(a_in, attn["wk"], "local", impl=impl,
                            interpret=interpret)
        v = L.tp_lane_dense(a_in, attn["wv"], "local", impl=impl,
                            interpret=interpret)
    else:
        CAL.tap(("attn/wq", "attn/wk", "attn/wv"), a_in)
        q = L.dense(a_in, attn["wq"], impl=impl, interpret=interpret)
        k = L.dense(a_in, attn["wk"], impl=impl, interpret=interpret)
        v = L.dense(a_in, attn["wv"], impl=impl, interpret=interpret)
    q = SH.constrain(q.reshape(B, S, H, Dh), "dp", None, "model", None)
    k = SH.constrain(k.reshape(B, S, KH, Dh), "dp", None, "model", None)
    v = SH.constrain(v.reshape(B, S, KH, Dh), "dp", None, "model", None)
    if cfg.qk_norm:
        q = L.rmsnorm(q, attn["q_norm"], cfg.norm_eps)
        k = L.rmsnorm(k, attn["k_norm"], cfg.norm_eps)
    return q, k, v


def _attn_out(o, lp, cfg, impl, interpret):
    B, S = o.shape[:2]
    o = SH.constrain(o, "dp", None, "model", None)
    o = o.reshape(B, S, o.shape[2] * o.shape[3])    # local heads * Dh
    attn = lp["attn"]
    CAL.tap("attn/c_proj" if cfg.fused_qkv else "attn/wo", o)
    if _tp_attn_shards(cfg) > 1:
        plan = SH.serve_tp_plan()
        if plan is not None and plan.attn_row:
            # row-parallel sliced path: this shard's contiguous heads ARE
            # a contiguous K-row slice of wo, so the local head outputs
            # feed wo's partial gemm directly and ONE psum assembles the
            # d_model output -- no head gather, no lane gather
            out = L.tp_row_dense(o, attn["wo"], plan.attn_row, impl=impl,
                                 interpret=interpret)
            return SH.constrain(out, "dp", None, None)
        if plan is not None and plan.matmul == "sliced_row":
            # no row layout for wo (plan built without params): ring
            # collective-matmul hides the head gather behind the chunked
            # o-proj gemms
            out = L.tp_ring_dense(o, attn["wo"], impl=impl,
                                  interpret=interpret)
            return SH.constrain(out, "dp", None, None)
        # lane path: wo keeps its K rows (all heads) whole per shard, so
        # gather the head outputs (exact tiled all-gather), then one
        # more gather assembles wo's d_model lanes
        o = kops.tp_gather_lanes(o)
        out = L.tp_lane_dense(o, attn["wo"], "full", impl=impl,
                              interpret=interpret)
        return SH.constrain(out, "dp", None, None)
    if cfg.fused_qkv:
        out = L.dense(o, attn["c_proj"], impl=impl, interpret=interpret)
        out = SH.constrain(out, "dp", None, None)
        return out + attn["b_proj"].astype(out.dtype)
    out = L.dense(o, attn["wo"], impl=impl, interpret=interpret)
    return SH.constrain(out, "dp", None, None)


def _seq_attention(q, k, v, cfg: ModelConfig, S: int,
                   interpret: bool = False):
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "naive" if S <= 2048 else "blockwise"
    if impl == "naive":
        return L.naive_attention(q, k, v, causal=True,
                                 window=cfg.sliding_window,
                                 softcap=cfg.attn_logit_softcap)
    if impl == "fused":
        B, S2 = q.shape[0], q.shape[1]
        pos = jnp.broadcast_to(jnp.arange(S2, dtype=jnp.int32)[None],
                               (B, S2))
        return L.prefill_attn_fused(q, k, v, pos, pos,
                                    window=cfg.sliding_window,
                                    softcap=cfg.attn_logit_softcap,
                                    interpret=interpret)
    return L.blockwise_attention(q, k, v, causal=True,
                                 window=cfg.sliding_window,
                                 softcap=cfg.attn_logit_softcap,
                                 q_chunk=cfg.attn_q_chunk,
                                 kv_chunk=cfg.attn_kv_chunk,
                                 unroll=_unroll(cfg))


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _attn_layer_seq(h, lp, cfg: ModelConfig, cos_sin, *, impl, interpret,
                    want_cache: bool):
    B, S, _ = h.shape
    a_in = L.norm(h, lp["ln1"], cfg.norm_type, cfg.norm_eps)
    q, k, v = _qkv(a_in, lp, cfg, impl, interpret)
    if cos_sin is not None:
        cos, sin = cos_sin
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    o = _seq_attention(q, k, v, cfg, S, interpret)
    h = h + _attn_out(o, lp, cfg, impl, interpret)
    m_in = L.norm(h, lp["ln2"], cfg.norm_type, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe":
        mo, aux = MOE.moe_block(m_in, lp["moe"], cfg, impl=impl,
                                interpret=interpret)
        h = h + mo
    elif cfg.act == "gelu":
        h = h + L.gelu_mlp(m_in, lp["mlp"], impl=impl, interpret=interpret)
    else:
        h = h + L.swiglu_mlp(m_in, lp["mlp"], impl=impl, interpret=interpret)
    kv = (k, v) if want_cache else None
    return h, aux, kv


def _unroll(cfg):
    return True if cfg.scan_unroll else 1


def forward_seq(params, cfg: ModelConfig, *, tokens=None, embeds=None,
                positions=None, want_cache: bool = False,
                return_hidden: bool = False,
                interpret: bool = False):
    """Full-sequence forward. Returns (logits f32 (B,S,V), aux_loss, kv_list).

    return_hidden: return final-norm hidden states instead of logits (the
    chunked vocab-sharded loss computes its own head matmul; see
    training/steps.py).
    kv_list (if want_cache): per-family cache payload of the whole sequence.
    """
    impl = cfg.kernel_impl
    if tokens is not None:
        B, S = tokens.shape
    else:
        B, S = embeds.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.pos_emb == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, B, S))
    pos2d = positions[0] if positions.ndim == 3 else positions
    h = _embed(params, cfg, tokens=tokens, embeds=embeds, positions=pos2d)

    cos_sin = None
    if cfg.pos_emb in ("rope", "mrope"):
        cos_sin = L.rope_cos_sin(
            positions if cfg.pos_emb == "mrope" else pos2d,
            cfg.d_head, cfg.rope_theta,
            cfg.mrope_sections if cfg.pos_emb == "mrope" else None)

    aux_total = jnp.zeros((), jnp.float32)
    caches: Any = None

    if cfg.family in ("dense", "vlm", "audio", "moe", "gpt2"):
        def body(carry, lp):
            hh, aux = carry
            hh, a, kv = _attn_layer_seq(hh, lp, cfg, cos_sin, impl=impl,
                                        interpret=interpret,
                                        want_cache=want_cache)
            return (hh, aux + a), kv
        body_fn = jax.checkpoint(body) if cfg.remat else body
        (h, aux_total), kvs = jax.lax.scan(body_fn, (h, aux_total),
                                           params["layers"],
                                           unroll=_unroll(cfg))
        caches = kvs                     # (k (L,B,S,KH,Dh), v (...)) or None

    elif cfg.family == "ssm":
        def body(carry, lp):
            hh = carry
            a_in = L.norm(hh, lp["ln1"], cfg.norm_type, cfg.norm_eps)
            out, (cstate, sstate) = M2.mamba2_forward(
                a_in, lp["ssm"], cfg, impl=impl, interpret=interpret)
            return hh + out, (cstate, sstate)
        body_fn = jax.checkpoint(body) if cfg.remat else body
        h, states = jax.lax.scan(body_fn, h, params["layers"],
                                 unroll=_unroll(cfg))
        caches = states                  # (conv (L,B,W-1,C), ssm (L,B,H,P,N))

    elif cfg.family == "hybrid":
        h, caches = _hybrid_forward_seq(params, cfg, h, want_cache,
                                        impl, interpret)
    else:
        raise ValueError(cfg.family)

    h = L.norm(h, params["ln_f"], cfg.norm_type, cfg.norm_eps)
    if return_hidden:
        return h, aux_total, caches
    logits = _logits(params, cfg, h, impl=impl, interpret=interpret)
    return logits, aux_total, caches


def _shared_block_seq(h, emb0, sp, cfg: ModelConfig, *, impl, interpret,
                      want_cache):
    """Zamba2 shared attention block over (h ++ initial-embedding)."""
    B, S, d = h.shape
    u = jnp.concatenate([h, emb0], axis=-1)                 # (B,S,2d)
    a_in = L.rmsnorm(u, sp["ln1"]["w"], cfg.norm_eps)
    Dh2 = 2 * d // cfg.n_heads
    q = L.dense(a_in, sp["attn"]["wq"], impl=impl, interpret=interpret)
    k = L.dense(a_in, sp["attn"]["wk"], impl=impl, interpret=interpret)
    v = L.dense(a_in, sp["attn"]["wv"], impl=impl, interpret=interpret)
    q = q.reshape(B, S, cfg.n_heads, Dh2)
    k = k.reshape(B, S, cfg.n_kv_heads, Dh2)
    v = v.reshape(B, S, cfg.n_kv_heads, Dh2)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cos, sin = L.rope_cos_sin(pos, Dh2, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    o = _seq_attention(q, k, v, cfg, S, interpret)
    o = o.reshape(B, S, cfg.n_heads * Dh2)
    u = u + L.dense(o, sp["attn"]["wo"], impl=impl, interpret=interpret)
    m_in = L.rmsnorm(u, sp["ln2"]["w"], cfg.norm_eps)
    u = u + L.swiglu_mlp(m_in, sp["mlp"], impl=impl, interpret=interpret)
    out = L.dense(u, sp["proj_out"], impl=impl, interpret=interpret)
    kv = (k, v) if want_cache else None
    return h + out, kv


def _hybrid_groups(cfg: ModelConfig):
    """Layer-group sizes between shared-block applications."""
    k = cfg.hybrid_attn_every
    n = cfg.n_layers
    groups = []
    while n > 0:
        groups.append(min(k, n))
        n -= k
    return groups


def _hybrid_forward_seq(params, cfg, h, want_cache, impl, interpret):
    emb0 = h
    groups = _hybrid_groups(cfg)
    conv_states, ssm_states, shared_kvs = [], [], []
    i0 = 0
    for gi, g in enumerate(groups):
        lp = jax.tree.map(lambda a: a[i0:i0 + g], params["layers"])
        i0 += g

        def body(carry, lpl):
            hh = carry
            a_in = L.norm(hh, lpl["ln1"], cfg.norm_type, cfg.norm_eps)
            out, (cs, ss) = M2.mamba2_forward(a_in, lpl["ssm"], cfg,
                                              impl=impl, interpret=interpret)
            return hh + out, (cs, ss)
        body_fn = jax.checkpoint(body) if cfg.remat else body
        h, (cs, ss) = jax.lax.scan(body_fn, h, lp, unroll=_unroll(cfg))
        conv_states.append(cs)
        ssm_states.append(ss)
        if g == cfg.hybrid_attn_every:    # full group -> shared block
            h, kv = _shared_block_seq(h, emb0, params["shared"], cfg,
                                      impl=impl, interpret=interpret,
                                      want_cache=want_cache)
            if want_cache:
                shared_kvs.append(kv)
    caches = (jnp.concatenate(conv_states, 0),
              jnp.concatenate(ssm_states, 0),
              (jnp.stack([k for k, _ in shared_kvs]),
               jnp.stack([v for _, v in shared_kvs])) if shared_kvs and
              want_cache else None)
    return h, caches


# ---------------------------------------------------------------------------
# decode (single new token against a cache)
# ---------------------------------------------------------------------------

def attn_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Ring-buffer length: sliding-window archs only keep the window."""
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def init_cache(cfg: ModelConfig, B: int, seq_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Zero/empty decode cache sized for contexts up to ``seq_len``."""
    Lc = cfg.n_layers
    cache: Dict[str, Any] = {}
    if cfg.family in ("dense", "vlm", "audio", "moe", "gpt2"):
        T = attn_cache_len(cfg, seq_len)
        KH, Dh = cfg.n_kv_heads, cfg.d_head
        kdt = jnp.int8 if cfg.kv_cache_quant else dtype
        cache["k"] = jnp.zeros((Lc, B, T, KH, Dh), kdt)
        cache["v"] = jnp.zeros((Lc, B, T, KH, Dh), kdt)
        if cfg.kv_cache_quant:
            cache["k_scale"] = jnp.zeros((Lc, B, T, KH), jnp.float32)
            cache["v_scale"] = jnp.zeros((Lc, B, T, KH), jnp.float32)
        cache["pos"] = jnp.full((B, T), -1, jnp.int32)
    elif cfg.family == "ssm":
        dd = M2.ssm_dims(cfg)
        cache["conv"] = jnp.zeros((Lc, B, cfg.ssm_conv_width - 1,
                                   dd["conv_ch"]), dtype)
        cache["state"] = jnp.zeros((Lc, B, dd["n_heads"], dd["head_dim"],
                                    dd["state"]), jnp.float32)
    elif cfg.family == "hybrid":
        dd = M2.ssm_dims(cfg)
        cache["conv"] = jnp.zeros((Lc, B, cfg.ssm_conv_width - 1,
                                   dd["conv_ch"]), dtype)
        cache["state"] = jnp.zeros((Lc, B, dd["n_heads"], dd["head_dim"],
                                    dd["state"]), jnp.float32)
        napp = sum(1 for g in _hybrid_groups(cfg)
                   if g == cfg.hybrid_attn_every)
        T = attn_cache_len(cfg, seq_len)
        Dh2 = 2 * cfg.d_model // cfg.n_heads
        cache["k"] = jnp.zeros((napp, B, T, cfg.n_kv_heads, Dh2), dtype)
        cache["v"] = jnp.zeros((napp, B, T, cfg.n_kv_heads, Dh2), dtype)
        cache["pos"] = jnp.full((B, T), -1, jnp.int32)
    else:
        raise ValueError(cfg.family)
    return cache


def _quantize_kv(x):
    """x: (B, KH, Dh) -> (int8 values, per-(B,KH) scale)."""
    amax = jnp.abs(x.astype(jnp.float32)).max(axis=-1)
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * inv[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _attn_layer_decode(h, lp, li, kall, vall, ksall, vsall, slot_pos,
                       position, slot_w, cfg, cos_sin, impl, interpret):
    """h: (B,1,d); kall/vall: (L,B,T,KH,Dh) stacked rings, this layer at
    ``li``; position/slot_w: (B,). ksall/vsall: (L,B,T,KH) int8-ring
    scales when cfg.kv_cache_quant. The new token's K/V row is written in
    place at (li, b, slot_w[b]); a slot_w of T (a dead slot) drops the
    write, so the ring is never copied and dead slots keep theirs."""
    B = h.shape[0]
    a_in = L.norm(h, lp["ln1"], cfg.norm_type, cfg.norm_eps)
    q, k, v = _qkv(a_in, lp, cfg, impl, interpret)
    if cos_sin is not None:
        cos, sin = cos_sin
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    bidx = jnp.arange(B)
    put = lambda a, x: a.at[li, bidx, slot_w].set(x, mode="drop")
    with jax.named_scope("ring_write"):
        if cfg.kv_cache_quant:
            kq, kscale = _quantize_kv(k[:, 0])
            vq, vscale = _quantize_kv(v[:, 0])
            kall, vall = put(kall, kq), put(vall, vq)
            ksall, vsall = put(ksall, kscale), put(vsall, vscale)
        else:
            kall = put(kall, k[:, 0].astype(kall.dtype))
            vall = put(vall, v[:, 0].astype(vall.dtype))
    with jax.named_scope("decode_attn"):
        if cfg.kv_cache_quant:
            # the int8 ring is dequantized whole and read by XLA
            at = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0,
                                                        keepdims=False)
            k_eff = at(kall).astype(jnp.float32) * at(ksall)[..., None]
            v_eff = at(vall).astype(jnp.float32) * at(vsall)[..., None]
            o = L.decode_attention(q, k_eff, v_eff, slot_pos, position,
                                   window=cfg.sliding_window,
                                   softcap=cfg.attn_logit_softcap)
        else:
            o = L.decode_attention_ring(q, kall, vall, li, slot_pos,
                                        position, window=cfg.sliding_window,
                                        softcap=cfg.attn_logit_softcap)
    h = h + _attn_out(o, lp, cfg, impl, interpret)
    m_in = L.norm(h, lp["ln2"], cfg.norm_type, cfg.norm_eps)
    if cfg.family == "moe":
        mo, _ = MOE.moe_block(m_in, lp["moe"], cfg, impl=impl,
                              interpret=interpret)
        h = h + mo
    elif cfg.act == "gelu":
        h = h + L.gelu_mlp(m_in, lp["mlp"], impl=impl, interpret=interpret)
    else:
        h = h + L.swiglu_mlp(m_in, lp["mlp"], impl=impl, interpret=interpret)
    return h, kall, vall, ksall, vsall


def decode_step(params, cfg: ModelConfig, cache: Dict[str, Any], *,
                tokens=None, embeds=None, position=None, live=None,
                interpret: bool = False):
    """One decode step. tokens: (B,) int32 or embeds: (B, d); position: (B,)
    absolute per-slot position of the new token. Returns
    (logits (B,V) f32, cache).

    live: optional (B,) bool slot mask for continuous batching -- dead
    slots run the math (static shapes) but do NOT mutate their cache or
    position book-keeping, so a freed slot can be re-admitted later
    without stale-state leakage. Logits of dead slots are undefined."""
    impl = cfg.kernel_impl
    B = tokens.shape[0] if tokens is not None else embeds.shape[0]
    h = _embed(params, cfg, tokens=tokens, embeds=embeds, positions=position)
    h = h[:, None, :] if h.ndim == 2 else h                 # (B,1,d)

    cos_sin = None
    if cfg.pos_emb in ("rope", "mrope"):
        pos_r = position[:, None]                           # (B,1)
        if cfg.pos_emb == "mrope":
            pos_r = jnp.broadcast_to(pos_r[None], (3, B, 1))
        cos_sin = L.rope_cos_sin(
            pos_r, cfg.d_head, cfg.rope_theta,
            cfg.mrope_sections if cfg.pos_emb == "mrope" else None)

    new_cache = dict(cache)
    Lc = cfg.n_layers
    lidx = jnp.arange(Lc)
    if cfg.family in ("dense", "vlm", "audio", "moe", "gpt2"):
        T = cache["k"].shape[2]
        slot_w = position % T
        if live is not None:            # T is out of range: the write drops
            slot_w = jnp.where(live, slot_w, T)
        slot_pos = cache["pos"].at[jnp.arange(B), slot_w].set(
            position, mode="drop")
        new_cache["pos"] = slot_pos

        # the stacked rings ride the scan carry; each layer writes its new
        # rows into them in place and attention reads its layer where it
        # lies, so no per-layer ring is sliced out or written back
        def body(carry, xs):
            lp, li = xs
            return _attn_layer_decode(carry[0], lp, li, *carry[1:], slot_pos,
                                      position, slot_w, cfg, cos_sin, impl,
                                      interpret), None

        zero = jnp.zeros((), jnp.float32)
        (h, knew, vnew, ksnew, vsnew), _ = jax.lax.scan(
            body, (h, cache["k"], cache["v"],
                   cache.get("k_scale", zero), cache.get("v_scale", zero)),
            (params["layers"], lidx), unroll=_unroll(cfg))
        new_cache["k"], new_cache["v"] = knew, vnew
        if cfg.kv_cache_quant:
            new_cache["k_scale"], new_cache["v_scale"] = ksnew, vsnew

    elif cfg.family == "ssm":
        def body(carry, xs):
            hh, call, sall = carry
            lp, li = xs
            cs = jax.lax.dynamic_index_in_dim(call, li, 0, keepdims=False)
            ss = jax.lax.dynamic_index_in_dim(sall, li, 0, keepdims=False)
            a_in = L.norm(hh, lp["ln1"], cfg.norm_type, cfg.norm_eps)
            out, (cs2, ss2) = M2.mamba2_decode(a_in[:, 0], lp["ssm"], cfg,
                                               cs, ss, impl=impl,
                                               interpret=interpret)
            if live is not None:
                cs2 = jnp.where(live[:, None, None], cs2, cs)
                ss2 = jnp.where(live[:, None, None, None], ss2, ss)
            call = jax.lax.dynamic_update_index_in_dim(call, cs2.astype(
                call.dtype), li, 0)
            sall = jax.lax.dynamic_update_index_in_dim(sall, ss2, li, 0)
            return (hh + out[:, None], call, sall), None

        (h, cnew, snew), _ = jax.lax.scan(
            body, (h, cache["conv"], cache["state"]),
            (params["layers"], lidx), unroll=_unroll(cfg))
        new_cache["conv"], new_cache["state"] = cnew, snew

    elif cfg.family == "hybrid":
        h, new_cache = _hybrid_decode(params, cfg, h, cache, position,
                                      impl, interpret, live=live)
    else:
        raise ValueError(cfg.family)

    h = L.norm(h, params["ln_f"], cfg.norm_type, cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = _logits(params, cfg, h[:, 0], impl=impl,
                         interpret=interpret)
    return logits, new_cache


def lm_logits(params, cfg: ModelConfig, h, *, interpret: bool = False):
    """LM head on final-norm hidden states h (..., d) -> logits f32.

    Public so schedulers can gather the few hidden rows they need (e.g.
    each sequence's last prompt token) and run the vocab matmul on just
    those, instead of materializing (B, S, V) logits."""
    return _logits(params, cfg, h, impl=cfg.kernel_impl, interpret=interpret)


def prefill_chunk(params, cfg: ModelConfig, cache: Dict[str, Any], *,
                  tokens, start, lengths, cached_lengths=None,
                  interpret: bool = False):
    """One batched prefill chunk against a decode cache (attention families).

    tokens: (B, C) int32, right-padded; start: () int32 absolute position of
    column 0 (same for every row -- the scheduler pads the batch to a shared
    bucketed length); lengths: (B,) true prompt lengths. Columns at
    positions >= lengths are padding: they run the math (static shapes) but
    never write the KV ring and never win attention (write index driven out
    of range -> scatter drop). A row with length 0 is a group-padding dummy.

    cached_lengths: optional (B,) -- row ``b``'s positions below
    ``cached_lengths[b]`` are ALREADY resident in the ring (scattered from
    a prefix cache, bit-for-bit the values a cold prefill would have
    written). Those columns are masked out exactly like padding: they
    neither rewrite the ring nor act as in-chunk keys, while the suffix's
    queries still attend them through the ring -- the same dataflow a
    later chunk of a cold multi-chunk prefill uses for earlier chunks'
    keys, which is what keeps warm prefill token-identical to cold.

    Feeding a prompt through successive chunks is exact: each chunk's
    queries attend the pre-chunk ring plus the chunk's own keys (see
    ``layers.prefill_attention``), then the chunk's K/V land in the ring at
    ``position % T`` -- identical semantics to running ``decode_step`` once
    per token, but with MatMul-shaped batches. Requires C <= ring length
    (in-chunk positions must map to distinct slots).

    Recurrent families (ssm / hybrid) run the same masked-chunk contract
    through ``_recurrent_chunk``: invalid columns are identity on the
    conv/SSM state (dt zeroed, conv tail gathered at each row's last valid
    column), so trailing pads never pollute recurrent state and one
    compiled (B, C) program serves every prompt length. ``cached_lengths``
    is ignored there: recurrent state is positional, so a warm prefix
    admission restores a checkpoint and starts the chunk GRID at the
    cached horizon instead of masking per-row.

    Returns (final-norm hidden (B, C, d), new cache). Callers that only
    need logits for some rows/offsets should gather from the hidden states
    and apply ``lm_logits`` there.
    """
    B, C = tokens.shape
    positions = jnp.broadcast_to(
        start + jnp.arange(C, dtype=jnp.int32)[None], (B, C))
    valid = positions < lengths[:, None]
    if cfg.family in ("ssm", "hybrid"):
        return _recurrent_chunk(params, cfg, cache, tokens, positions,
                                valid, interpret)
    if cached_lengths is not None:
        valid = valid & (positions >= cached_lengths[:, None])
    attn_fn = L.prefill_attention
    if cfg.attn_impl == "fused":
        # flash-style Pallas kernel for the chunk-vs-ring attention
        # (interpret mode runs it on CPU); verify_chunk keeps its scan
        attn_fn = functools.partial(L.prefill_attention, impl="fused",
                                    interpret=interpret)
    return _masked_chunk(params, cfg, cache, tokens, positions, valid,
                         attn_fn, interpret)


def verify_chunk(params, cfg: ModelConfig, cache: Dict[str, Any], *,
                 tokens, positions, valid, interpret: bool = False):
    """Score a per-slot block of tokens against the decode cache in ONE
    batched forward (the speculative-decoding verify pass).

    Same masked program shape as ``prefill_chunk`` but ``positions``
    (B, C) is explicit and per-row: slot b's block starts at its own
    absolute position (its draft block), so a continuous batch can verify
    k drafted tokens per speculating slot while plain slots run a 1-column
    decode step through the same program. ``valid`` (B, C) masks the
    columns that really run; invalid columns never write the KV ring and
    never win attention. Writes for positions that later turn out to be
    rejected drafts are un-done by ``cache_ring_rewind``."""
    return _masked_chunk(params, cfg, cache, tokens, positions, valid,
                         L.verify_attention, interpret)


def verify_scan(params, cfg: ModelConfig, cache: Dict[str, Any], *,
                tokens, positions, valid, interpret: bool = False):
    """Bit-exact verify: scan ``decode_step`` over the block's columns.

    Same signature/semantics as ``verify_chunk`` but returns per-column
    LOGITS (B, S, V) directly and guarantees each column's numbers are
    BIT-identical to plain decode's: every column runs the very same
    (B,)-shaped decode_step graph plain decode runs, so XLA makes the
    same fusion/rounding choices. The batched ``verify_chunk`` scores the
    whole block in one masked forward -- higher arithmetic intensity, but
    a differently-shaped program whose logits can differ from decode's by
    a float ulp and flip a greedy argmax on a near-tie. Scan mode is what
    backs the engine's greedy-parity guarantee; batched mode is the
    throughput path. Both stay inside one jitted program per chunk."""
    def body(c, xs):
        tk, po, ok = xs
        logits, c = decode_step(params, cfg, c, tokens=tk, position=po,
                                live=ok, interpret=interpret)
        return c, logits

    cache, lgs = jax.lax.scan(body, cache,
                              (tokens.T, positions.T, valid.T))
    return jnp.moveaxis(lgs, 0, 1), cache


def _masked_chunk(params, cfg: ModelConfig, cache, tokens, positions,
                  valid, attn_fn, interpret):
    """Shared body of prefill_chunk / verify_chunk: one (B, C) masked
    chunk forward against the ring, writing valid columns at
    ``positions % T``."""
    if cfg.family not in ("dense", "vlm", "audio", "moe", "gpt2"):
        raise NotImplementedError(
            f"the ring-masked chunk body is KV-cache-only; family "
            f"{cfg.family!r} prefills through _recurrent_chunk and cannot "
            f"verify drafts (a dense recurrent state has no ring rewind)")
    impl = cfg.kernel_impl
    B, C = tokens.shape
    T = cache["k"].shape[2]
    assert C <= T, (C, T)
    h = _embed(params, cfg, tokens=tokens, positions=positions)

    cos_sin = None
    if cfg.pos_emb in ("rope", "mrope"):
        pos_r = positions
        if cfg.pos_emb == "mrope":
            pos_r = jnp.broadcast_to(positions[None], (3, B, C))
        cos_sin = L.rope_cos_sin(
            pos_r, cfg.d_head, cfg.rope_theta,
            cfg.mrope_sections if cfg.pos_emb == "mrope" else None)

    bidx = jnp.arange(B)[:, None]
    slot_w = jnp.where(valid, positions % T, T)     # T = out of range: drop
    old_pos = cache["pos"]
    new_cache = dict(cache)
    new_cache["pos"] = old_pos.at[bidx, slot_w].set(positions, mode="drop")
    quant = cfg.kv_cache_quant
    lidx = jnp.arange(cfg.n_layers)

    def body(carry, xs):
        hh, kall, vall, ksall, vsall = carry
        lp, li = xs
        idx = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)
        upd = lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, li, 0)
        kc, vc = idx(kall), idx(vall)
        a_in = L.norm(hh, lp["ln1"], cfg.norm_type, cfg.norm_eps)
        q, k, v = _qkv(a_in, lp, cfg, impl, interpret)
        if cos_sin is not None:
            cos, sin = cos_sin
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        if quant:
            ks, vs = idx(ksall), idx(vsall)
            kq, kscale = _quantize_kv(k)            # (B,C,KH,Dh)/(B,C,KH)
            vq, vscale = _quantize_kv(v)
            kc_eff = kc.astype(jnp.float32) * ks[..., None]
            vc_eff = vc.astype(jnp.float32) * vs[..., None]
            # attend the quantized reconstruction of the chunk's own keys
            # so results do not depend on where chunk boundaries fall
            k_chunk = kq.astype(jnp.float32) * kscale[..., None]
            v_chunk = vq.astype(jnp.float32) * vscale[..., None]
        else:
            kc_eff, vc_eff = kc, vc
            k_chunk = k.astype(kc.dtype)            # ring-dtype rounding,
            v_chunk = v.astype(vc.dtype)            # same reason as above
        o = attn_fn(q, kc_eff, vc_eff, old_pos, k_chunk,
                    v_chunk, positions, valid,
                    window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap)
        if quant:
            kall = upd(kall, kc.at[bidx, slot_w].set(kq, mode="drop"))
            vall = upd(vall, vc.at[bidx, slot_w].set(vq, mode="drop"))
            ksall = upd(ksall, ks.at[bidx, slot_w].set(kscale, mode="drop"))
            vsall = upd(vsall, vs.at[bidx, slot_w].set(vscale, mode="drop"))
        else:
            kall = upd(kall, kc.at[bidx, slot_w].set(k_chunk, mode="drop"))
            vall = upd(vall, vc.at[bidx, slot_w].set(v_chunk, mode="drop"))
        hh = hh + _attn_out(o, lp, cfg, impl, interpret)
        m_in = L.norm(hh, lp["ln2"], cfg.norm_type, cfg.norm_eps)
        if cfg.family == "moe":
            mo, _ = MOE.moe_block(m_in, lp["moe"], cfg, impl=impl,
                                  interpret=interpret)
            hh = hh + mo
        elif cfg.act == "gelu":
            hh = hh + L.gelu_mlp(m_in, lp["mlp"], impl=impl,
                                 interpret=interpret)
        else:
            hh = hh + L.swiglu_mlp(m_in, lp["mlp"], impl=impl,
                                   interpret=interpret)
        return (hh, kall, vall, ksall, vsall), None

    zero = jnp.zeros((), jnp.float32)
    (h, knew, vnew, ksnew, vsnew), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"],
               cache.get("k_scale", zero), cache.get("v_scale", zero)),
        (params["layers"], lidx), unroll=_unroll(cfg))
    new_cache["k"], new_cache["v"] = knew, vnew
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = ksnew, vsnew
    h = L.norm(h, params["ln_f"], cfg.norm_type, cfg.norm_eps)
    return h, new_cache


def _recurrent_chunk(params, cfg: ModelConfig, cache, tokens, positions,
                     valid, interpret):
    """Masked (B, C) prefill chunk for the recurrent families (ssm /
    hybrid): the batched, length-bucketed counterpart of the KV families'
    ``_masked_chunk``.

    ``valid`` is a contiguous per-row prefix (positions < lengths).
    Invalid columns run the math (static shapes) but are IDENTITY on the
    recurrent state: ``mamba2_forward(valid=...)`` zeroes dt post-softplus
    (decay exp(0)=1, zero input contribution) and gathers the conv tail at
    each row's last valid column, so a row whose prompt ended mid-chunk --
    or a group-padding dummy with length 0 -- carries exactly the state of
    an exact-length run. Because every per-position op is row-independent
    and the scheduler keeps the chunk grid at fixed absolute boundaries,
    batched prefill is token-identical to sequential admission.

    Hybrid additionally runs its shared attention block with the KV-ring
    chunk semantics of ``_masked_chunk``: queries attend the pre-chunk
    ring plus the chunk's own (ring-dtype-rounded) keys, then valid
    columns land in the ring at ``position % T``."""
    impl = cfg.kernel_impl
    B, C = tokens.shape
    h = _embed(params, cfg, tokens=tokens, positions=positions)
    new_cache = dict(cache)

    if cfg.family == "ssm":
        lidx = jnp.arange(cfg.n_layers)

        def body(carry, xs):
            hh, call, sall = carry
            lp, li = xs
            cs = jax.lax.dynamic_index_in_dim(call, li, 0, keepdims=False)
            ss = jax.lax.dynamic_index_in_dim(sall, li, 0, keepdims=False)
            a_in = L.norm(hh, lp["ln1"], cfg.norm_type, cfg.norm_eps)
            out, (cs2, ss2) = M2.mamba2_forward(
                a_in, lp["ssm"], cfg, conv_state=cs, ssm_state=ss,
                valid=valid, impl=impl, interpret=interpret)
            call = jax.lax.dynamic_update_index_in_dim(
                call, cs2.astype(call.dtype), li, 0)
            sall = jax.lax.dynamic_update_index_in_dim(sall, ss2, li, 0)
            return (hh + out, call, sall), None

        (h, cnew, snew), _ = jax.lax.scan(
            body, (h, cache["conv"], cache["state"]),
            (params["layers"], lidx), unroll=_unroll(cfg))
        new_cache["conv"], new_cache["state"] = cnew, snew

    else:                                                    # hybrid
        emb0 = h
        T = cache["k"].shape[2]
        assert C <= T, (C, T)
        bidx = jnp.arange(B)[:, None]
        slot_w = jnp.where(valid, positions % T, T)  # T = out of range: drop
        old_pos = cache["pos"]
        new_cache["pos"] = old_pos.at[bidx, slot_w].set(positions,
                                                        mode="drop")
        groups = _hybrid_groups(cfg)
        conv_parts, state_parts = [], []
        knew, vnew = cache["k"], cache["v"]
        i0 = 0
        app = 0
        for g in groups:
            lp = jax.tree.map(lambda a: a[i0:i0 + g], params["layers"])
            cs = cache["conv"][i0:i0 + g]
            ss = cache["state"][i0:i0 + g]
            i0 += g

            def body(hh, xs):
                lpl, c1, s1 = xs
                a_in = L.norm(hh, lpl["ln1"], cfg.norm_type, cfg.norm_eps)
                out, (c2, s2) = M2.mamba2_forward(
                    a_in, lpl["ssm"], cfg, conv_state=c1, ssm_state=s1,
                    valid=valid, impl=impl, interpret=interpret)
                return hh + out, (c2.astype(c1.dtype), s2)

            h, (cn, sn) = jax.lax.scan(body, h, (lp, cs, ss),
                                       unroll=_unroll(cfg))
            conv_parts.append(cn)
            state_parts.append(sn)
            if g == cfg.hybrid_attn_every:
                h, kc, vc = _shared_block_chunk(
                    h, emb0, params["shared"], cfg, knew[app], vnew[app],
                    old_pos, positions, valid, slot_w, impl, interpret)
                knew = knew.at[app].set(kc)
                vnew = vnew.at[app].set(vc)
                app += 1
        new_cache["conv"] = jnp.concatenate(conv_parts, 0)
        new_cache["state"] = jnp.concatenate(state_parts, 0)
        new_cache["k"], new_cache["v"] = knew, vnew

    h = L.norm(h, params["ln_f"], cfg.norm_type, cfg.norm_eps)
    return h, new_cache


def _shared_block_chunk(h, emb0, sp, cfg, kc, vc, old_pos, positions, valid,
                        slot_w, impl, interpret):
    """Chunked-prefill counterpart of ``_shared_block_decode``: the
    chunk's queries attend the pre-chunk ring plus the chunk's own masked
    keys, then valid columns' K/V land in the ring at ``position % T``
    (same dataflow as the KV families' chunk body)."""
    B, C, d = h.shape
    u = jnp.concatenate([h, emb0], axis=-1)                 # (B,C,2d)
    a_in = L.rmsnorm(u, sp["ln1"]["w"], cfg.norm_eps)
    Dh2 = 2 * d // cfg.n_heads
    q = L.dense(a_in, sp["attn"]["wq"], impl=impl, interpret=interpret)
    k = L.dense(a_in, sp["attn"]["wk"], impl=impl, interpret=interpret)
    v = L.dense(a_in, sp["attn"]["wv"], impl=impl, interpret=interpret)
    q = q.reshape(B, C, cfg.n_heads, Dh2)
    k = k.reshape(B, C, cfg.n_kv_heads, Dh2)
    v = v.reshape(B, C, cfg.n_kv_heads, Dh2)
    cos, sin = L.rope_cos_sin(positions, Dh2, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    k_chunk = k.astype(kc.dtype)        # ring-dtype rounding: results do
    v_chunk = v.astype(vc.dtype)        # not depend on chunk boundaries
    o = L.prefill_attention(q, kc, vc, old_pos, k_chunk, v_chunk,
                            positions, valid, window=cfg.sliding_window)
    bidx = jnp.arange(B)[:, None]
    kc = kc.at[bidx, slot_w].set(k_chunk, mode="drop")
    vc = vc.at[bidx, slot_w].set(v_chunk, mode="drop")
    o = o.reshape(B, C, cfg.n_heads * Dh2)
    u = u + L.dense(o, sp["attn"]["wo"], impl=impl, interpret=interpret)
    m_in = L.rmsnorm(u, sp["ln2"]["w"], cfg.norm_eps)
    u = u + L.swiglu_mlp(m_in, sp["mlp"], impl=impl, interpret=interpret)
    out = L.dense(u, sp["proj_out"], impl=impl, interpret=interpret)
    return h + out, kc, vc


def _shared_block_decode(h, emb0, sp, cfg, kc, vc, slot_pos, position, slot,
                         impl, interpret, live=None):
    """h/emb0: (B,1,d); kc/vc: (B,T,KH,Dh2)."""
    B, _, d = h.shape
    u = jnp.concatenate([h, emb0], axis=-1)
    a_in = L.rmsnorm(u, sp["ln1"]["w"], cfg.norm_eps)
    Dh2 = 2 * d // cfg.n_heads
    q = L.dense(a_in, sp["attn"]["wq"], impl=impl, interpret=interpret)
    k = L.dense(a_in, sp["attn"]["wk"], impl=impl, interpret=interpret)
    v = L.dense(a_in, sp["attn"]["wv"], impl=impl, interpret=interpret)
    q = q.reshape(B, 1, cfg.n_heads, Dh2)
    k = k.reshape(B, 1, cfg.n_kv_heads, Dh2)
    v = v.reshape(B, 1, cfg.n_kv_heads, Dh2)
    cos, sin = L.rope_cos_sin(position[:, None], Dh2, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    bidx = jnp.arange(B)
    k_new, v_new = k[:, 0].astype(kc.dtype), v[:, 0].astype(vc.dtype)
    if live is not None:
        k_new = jnp.where(live[:, None, None], k_new, kc[bidx, slot])
        v_new = jnp.where(live[:, None, None], v_new, vc[bidx, slot])
    kc = kc.at[bidx, slot].set(k_new)
    vc = vc.at[bidx, slot].set(v_new)
    o = L.decode_attention(q, kc, vc, slot_pos, position,
                           window=cfg.sliding_window)
    o = o.reshape(B, 1, cfg.n_heads * Dh2)
    u = u + L.dense(o, sp["attn"]["wo"], impl=impl, interpret=interpret)
    m_in = L.rmsnorm(u, sp["ln2"]["w"], cfg.norm_eps)
    u = u + L.swiglu_mlp(m_in, sp["mlp"], impl=impl, interpret=interpret)
    out = L.dense(u, sp["proj_out"], impl=impl, interpret=interpret)
    return h + out, kc, vc


def _hybrid_decode(params, cfg, h, cache, position, impl, interpret,
                   live=None):
    emb0 = h
    B = h.shape[0]
    T = cache["k"].shape[2]
    slot = position % T
    pos_new = position if live is None else jnp.where(
        live, position, cache["pos"][jnp.arange(B), slot])
    slot_pos = cache["pos"].at[jnp.arange(B), slot].set(pos_new)
    new_cache = dict(cache)
    new_cache["pos"] = slot_pos
    groups = _hybrid_groups(cfg)
    conv_parts, state_parts = [], []
    knew = cache["k"]
    vnew = cache["v"]
    i0 = 0
    app = 0
    for g in groups:
        lp = jax.tree.map(lambda a: a[i0:i0 + g], params["layers"])
        cs = cache["conv"][i0:i0 + g]
        ss = cache["state"][i0:i0 + g]
        i0 += g

        def body(hh, xs):
            lpl, c1, s1 = xs
            a_in = L.norm(hh, lpl["ln1"], cfg.norm_type, cfg.norm_eps)
            out, (c2, s2) = M2.mamba2_decode(a_in[:, 0], lpl["ssm"], cfg,
                                             c1, s1, impl=impl,
                                             interpret=interpret)
            if live is not None:
                c2 = jnp.where(live[:, None, None], c2, c1)
                s2 = jnp.where(live[:, None, None, None], s2, s1)
            return hh + out[:, None], (c2, s2)

        h, (cn, sn) = jax.lax.scan(body, h, (lp, cs, ss),
                                   unroll=_unroll(cfg))
        conv_parts.append(cn)
        state_parts.append(sn)
        if g == cfg.hybrid_attn_every:
            h, kc, vc = _shared_block_decode(
                h, emb0, params["shared"], cfg, knew[app], vnew[app],
                slot_pos, position, slot, impl, interpret, live=live)
            knew = knew.at[app].set(kc)
            vnew = vnew.at[app].set(vc)
            app += 1
    new_cache["conv"] = jnp.concatenate(conv_parts, 0)
    new_cache["state"] = jnp.concatenate(state_parts, 0)
    new_cache["k"], new_cache["v"] = knew, vnew
    return h, new_cache


def cache_from_prefill(cfg: ModelConfig, caches, seq_len: int,
                       cache_len: Optional[int] = None,
                       dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Convert forward_seq(want_cache=True) payload into a decode cache."""
    T = cache_len or attn_cache_len(cfg, seq_len)
    if cfg.family in ("dense", "vlm", "audio", "moe", "gpt2"):
        k, v = caches                                       # (L,B,S,KH,Dh)
        Lc, B, S = k.shape[:3]
        if S >= T:                                          # keep last T
            k, v = k[:, :, S - T:], v[:, :, S - T:]
            pos = jnp.broadcast_to(jnp.arange(S - T, S)[None], (B, T))
            # ring alignment: slot for position p is p % T
            roll = -((S - T) % T)
            k = jnp.roll(k, roll, axis=2)
            v = jnp.roll(v, roll, axis=2)
            pos = jnp.roll(pos, roll, axis=1)
        else:
            pad = T - S
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            pos = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
                 jnp.full((B, pad), -1, jnp.int32)], axis=1)
        if cfg.kv_cache_quant:
            def qfull(x):
                amax = jnp.abs(x.astype(jnp.float32)).max(axis=-1)
                scale = amax / 127.0
                inv = jnp.where(scale > 0,
                                1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
                q = jnp.clip(jnp.round(x.astype(jnp.float32)
                                       * inv[..., None]), -127, 127)
                return q.astype(jnp.int8), scale
            kq, ksc = qfull(k)
            vq, vsc = qfull(v)
            return {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc,
                    "pos": pos.astype(jnp.int32)}
        return {"k": k.astype(dtype), "v": v.astype(dtype),
                "pos": pos.astype(jnp.int32)}
    if cfg.family == "ssm":
        conv, state = caches
        return {"conv": conv.astype(dtype), "state": state}
    if cfg.family == "hybrid":
        conv, state, kv = caches
        k, v = kv                                           # (napp,B,S,KH,Dh2)
        napp, B, S = k.shape[:3]
        pad = T - S
        assert pad >= 0, "hybrid prefill longer than cache"
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
             jnp.full((B, pad), -1, jnp.int32)], axis=1)
        return {"conv": conv.astype(dtype), "state": state,
                "k": k.astype(dtype), "v": v.astype(dtype), "pos": pos}
    raise ValueError(cfg.family)


def cache_batch_axis(key: str) -> int:
    """Axis of the batch-slot dimension for each decode-cache entry.

    Every family stacks layers (or shared-block applications) at axis 0
    except the per-slot position ring ``pos`` which is (B, T)."""
    return 0 if key == "pos" else 1


def cache_set_slots(cache: Dict[str, Any], group_cache: Dict[str, Any],
                    indices) -> Dict[str, Any]:
    """Scatter a G-request cache batch into batch slots ``indices`` (G,)
    of a multi-slot decode cache in ONE program. ``indices`` may be traced,
    so a single compilation serves every slot assignment (batched
    continuous-batching admission). An index >= B drops that row -- the
    scheduler pads admission groups to a bucketed size with dummy rows and
    points them out of range instead of wasting a real slot on them."""
    out = {}
    for k, v in cache.items():
        upd = group_cache[k].astype(v.dtype)
        if cache_batch_axis(k) == 0:
            out[k] = v.at[indices].set(upd, mode="drop")
        else:
            out[k] = v.at[:, indices].set(upd, mode="drop")
    return out


def cache_set_slot(cache: Dict[str, Any], slot_cache: Dict[str, Any],
                   index) -> Dict[str, Any]:
    """Single-request admission: scatter a batch-dim-1 cache into slot
    ``index``. Thin wrapper over ``cache_set_slots`` (kept for the
    recurrent-family exact-length prefill path and external callers)."""
    return cache_set_slots(cache, slot_cache,
                           jnp.asarray(index, jnp.int32)[None])


def _ring_axis(key: str) -> int:
    """Axis of the ring (cache position) dimension per cache entry: the
    position ring ``pos`` is (B, T); every KV payload stacks layers first
    (L, B, T, ...)."""
    return 1 if key == "pos" else 2


def cache_ring_snapshot(cache: Dict[str, Any],
                        slots: jnp.ndarray) -> Dict[str, Any]:
    """Snapshot ring rows ``slots`` (B, S) of every ring-indexed cache
    entry (k/v, int8 scales, pos) before a speculative verify pass writes
    them. Recurrent entries (conv/state) have no ring and are excluded --
    speculation is a KV-cache-family feature (a dense recurrent state
    cannot be rolled back by re-pointing positions)."""
    return {k: kops.ring_gather(v, slots, ring_axis=_ring_axis(k))
            for k, v in cache.items() if k not in ("conv", "state")}


# ---------------------------------------------------------------------------
# page-granular cache copy (prefix cache)
# ---------------------------------------------------------------------------

# ring-payload entries a KV page carries (``pos`` is derived from the
# page's start position at scatter time, never stored)
_PAGE_KEYS = ("k", "v", "k_scale", "v_scale")
# recurrent checkpoint payload: one pool row holds the WHOLE conv/SSM
# state after the page's last token (not per-position data), so a warm
# admission restores it and recomputes only the suffix
_STATE_KEYS = ("conv", "state")


def cache_page_keys(cfg: ModelConfig) -> Tuple[str, ...]:
    """Pool entries a prefix-cache page carries for this family: ring
    payloads for KV families, plus whole-state checkpoints for the
    recurrent ones (hybrid pages both its shared-block KV ring and its
    conv/SSM checkpoints)."""
    keys = _PAGE_KEYS
    if cfg.family in ("ssm", "hybrid"):
        keys = keys + _STATE_KEYS
    return keys


def cache_page_pool(cfg: ModelConfig, n_pages: int, page: int,
                    dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Device page pool for the prefix cache: every ring-payload cache
    entry with the batch-slot axis reinterpreted as a page index and the
    ring axis shortened to ``page`` rows -- e.g. ``k``:
    (L, n_pages, page, KH, Dh). Same dtypes as the live ring (int8 + f32
    scales under kv_cache_quant), so page copies are bit-for-bit.
    Recurrent families add per-page checkpoint entries ``conv``
    (L, n_pages, W-1, C) / ``state`` (L, n_pages, H, P, N): the state
    AFTER the page's last token, indexed by page like a one-row batch."""
    tmpl = init_cache(cfg, n_pages, page, dtype=dtype)
    keys = cache_page_keys(cfg)
    return {k: v for k, v in tmpl.items() if k in keys}


def cache_page_bytes(cfg: ModelConfig, page: int) -> int:
    """Device bytes one KV page occupies (all payload arrays, all layers)."""
    shapes = jax.eval_shape(lambda: cache_page_pool(cfg, 1, page))
    return sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
               for v in shapes.values())


def cache_gather_pages(cache: Dict[str, Any], rows: jnp.ndarray,
                       cols: jnp.ndarray) -> Dict[str, Any]:
    """Copy page-shaped row blocks out of a decode cache: ``rows`` (n,)
    batch slots, ``cols`` (n, page) ring slots (position % T, so pages
    sitting across a sliding-window wrap read their true rows). Returns
    pool-layout payloads (the per-entry (batch, ring) dims become
    (n, page))."""
    return {k: kops.page_gather(cache[k], rows, cols,
                                ring_axis=_ring_axis(k))
            for k in _PAGE_KEYS if k in cache}


def cache_scatter_pages(cache: Dict[str, Any], pages: Dict[str, Any],
                        rows: jnp.ndarray, cols: jnp.ndarray,
                        positions: jnp.ndarray) -> Dict[str, Any]:
    """Scatter pool pages into a decode cache and stamp their absolute
    positions into the ``pos`` ring. ``cols`` entries >= T drop that
    element -- batch padding, and the copy-on-write path: a partial-page
    hit scatters only its matched leading rows, the suffix prefill then
    recomputes (overwrites) the divergent tail in the ring while the
    source pool page stays intact. Exact through ring wrap and int8-KV
    scale payloads (all entries are copied bit-for-bit)."""
    new = dict(cache)
    for k, pg in pages.items():
        if k in cache:
            new[k] = kops.page_scatter(cache[k], pg, rows, cols,
                                       ring_axis=_ring_axis(k))
    new["pos"] = kops.page_scatter(cache["pos"], positions, rows, cols,
                                   ring_axis=1)
    return new


def cache_scatter_checkpoints(cache: Dict[str, Any], pool: Dict[str, Any],
                              idx: jnp.ndarray,
                              rows: jnp.ndarray) -> Dict[str, Any]:
    """Restore recurrent checkpoints: copy pool page rows ``idx`` (n,)
    into batch rows ``rows`` (n,) of a decode cache's conv/state entries
    (whole-state row copies -- checkpoints are not positional pages). A
    row >= B drops that element (bucketed-job padding); the corresponding
    pad ``idx`` may be out of range (the gather clamps, the scatter
    drops)."""
    new = dict(cache)
    for k in _STATE_KEYS:
        if k in cache:
            new[k] = cache[k].at[:, rows].set(
                pool[k][:, idx].astype(cache[k].dtype), mode="drop")
    return new


def cache_insert_checkpoints(pool: Dict[str, Any], cache: Dict[str, Any],
                             rows: jnp.ndarray,
                             idx: jnp.ndarray) -> Dict[str, Any]:
    """Record recurrent checkpoints: copy decode-cache batch rows ``rows``
    (n,) conv/state into pool page rows ``idx`` (n,). The source is the
    inter-chunk state the scheduler's chunk loop already materializes, so
    a checkpoint is bit-for-bit the state a cold run carries at that page
    boundary -- zero extra compute. ``idx`` >= n_pages drops (padding)."""
    new = dict(pool)
    for k in _STATE_KEYS:
        if k in pool:
            new[k] = pool[k].at[:, idx].set(
                cache[k][:, rows].astype(pool[k].dtype), mode="drop")
    return new


def cache_ring_rewind(cache: Dict[str, Any], snapshot: Dict[str, Any],
                      slots: jnp.ndarray, keep) -> Dict[str, Any]:
    """Un-write rejected speculative entries: restore snapshot column j
    into ring row ``slots[b, j]`` for every j >= keep[b] (columns below
    ``keep`` hold accepted tokens and stay). ``keep`` (B,) is traced, so
    one compiled program serves every acceptance pattern. Exact for ring
    wrap too: a rejected draft that overwrote a still-in-window entry gets
    that entry back, so sliding-window decode after a rollback is
    bit-identical to never having speculated."""
    new = dict(cache)
    for k, snap in snapshot.items():
        new[k] = kops.ring_restore(cache[k], snap, slots, keep,
                                   ring_axis=_ring_axis(k))
    return new
