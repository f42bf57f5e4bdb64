"""Quantize / dequantize for the GGUF k-quant family (pure JAX, jittable).

Dequantization is llama.cpp-exact in *semantics* (same reconstruction
formulas, same field widths). Quantization is a vectorized one-shot
min/max (affine formats) or absmax (symmetric formats) fit with the block
scales themselves re-quantized to their narrow fields against a per-SB
super-scale, exactly mirroring the two-level scheme of the paper's Fig. 2 --
but without llama.cpp's iterative `make_qkx2_quants` refinement search (the
paper's contribution is executing pre-quantized models, not the quantizer;
see DESIGN.md §7).

Weights: shape (K, N), quantized along K (the reduction axis), N on lanes.
Activations (Q8_K): shape (..., K), quantized along the trailing axis.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import formats as F
from repro.core.formats import slab_pack, slab_unpack


# ---------------------------------------------------------------------------
# QTensor: packed quantized weight tensor (registered pytree)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """A (K, N) weight matrix in packed BFP form.

    ``data`` holds the payload arrays named per ``formats.FORMATS[variant]``;
    ``variant``/``shape`` are static (pytree aux) so jitted code can dispatch
    per-variant without retracing on values.
    """
    variant: str
    shape: Tuple[int, int]      # logical (K, N)
    data: Dict[str, jnp.ndarray]

    def tree_flatten(self):
        keys = tuple(sorted(self.data))
        return tuple(self.data[k] for k in keys), (self.variant, self.shape, keys)

    @classmethod
    def tree_unflatten(cls, aux, children):
        variant, shape, keys = aux
        return cls(variant, shape, dict(zip(keys, children)))

    @property
    def nbytes(self) -> int:
        total = 0
        for v in self.data.values():
            # works for ShapeDtypeStruct stand-ins too
            import numpy as _np
            total += int(_np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
        return total

    @property
    def bits_per_weight(self) -> float:
        K, N = self.shape
        return self.nbytes * 8.0 / (K * N)

    def astuple(self):
        return tuple(self.data[k] for k in sorted(self.data))


def _nearest(x):
    # round-half-away like llama.cpp's nearest_int on the values we produce
    return jnp.round(x)


def _safe_inv(x):
    return jnp.where(x > 0, 1.0 / jnp.where(x > 0, x, 1.0), 0.0)


def _f32(a):
    """Payload field -> f32. Integer fields widen through int32: Mosaic
    (the Pallas TPU compiler) has no direct uint8 -> float32 cast, and the
    fused kernel runs these same dequantize bodies on its VMEM tiles."""
    if jnp.issubdtype(a.dtype, jnp.integer):
        a = a.astype(jnp.int32)
    return a.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Q2_K
# ---------------------------------------------------------------------------

def quantize_q2_k(w: jnp.ndarray) -> QTensor:
    K, N = w.shape
    assert K % 256 == 0, K
    nsb = K // 256
    x = w.astype(jnp.float32).reshape(nsb, 16, 16, N)       # (sb, blk, in, N)
    bmax = x.max(axis=2)
    bmin = x.min(axis=2)
    min_f = jnp.maximum(0.0, -bmin)                          # (sb, 16, N) >= 0
    scale_f = jnp.maximum(bmax + min_f, 0.0) / 3.0
    d = scale_f.max(axis=1) / 15.0                           # (sb, N)
    dmin = min_f.max(axis=1) / 15.0
    sc_q = jnp.clip(_nearest(scale_f * _safe_inv(d)[:, None]), 0, 15)
    m_q = jnp.clip(_nearest(min_f * _safe_inv(dmin)[:, None]), 0, 15)
    eff_sc = d[:, None] * sc_q                               # (sb, 16, N)
    eff_mn = dmin[:, None] * m_q
    q = jnp.clip(_nearest((x + eff_mn[:, :, None]) * _safe_inv(eff_sc)[:, :, None]),
                 0, 3)
    qs = slab_pack(q.reshape(K, N), 2, 256)
    scales = (sc_q.astype(jnp.uint8) | (m_q.astype(jnp.uint8) << 4)).reshape(K // 16, N)
    return QTensor("q2_k", (K, N), dict(
        qs=qs, scales=scales,
        d=d.astype(jnp.float16), dmin=dmin.astype(jnp.float16)))


def dequantize_q2_k(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    K, N = t.shape
    nsb = K // 256
    q = _f32(slab_unpack(t.data["qs"], 2, 256)).reshape(nsb, 16, 16, N)
    scales = t.data["scales"].astype(jnp.int32)
    sc = _f32(scales & 0xF).reshape(nsb, 16, N)
    mn = _f32(scales >> 4).reshape(nsb, 16, N)
    d = _f32(t.data["d"])[:, None]                           # (sb, 1, N)
    dmin = _f32(t.data["dmin"])[:, None]
    w = (d * sc)[:, :, None] * q - (dmin * mn)[:, :, None]
    return w.reshape(K, N).astype(dtype)


# ---------------------------------------------------------------------------
# Q3_K
# ---------------------------------------------------------------------------

def quantize_q3_k(w: jnp.ndarray) -> QTensor:
    K, N = w.shape
    assert K % 256 == 0, K
    nsb = K // 256
    x = w.astype(jnp.float32).reshape(nsb, 16, 16, N)
    amax = jnp.abs(x).max(axis=2)                            # (sb, 16, N)
    scale_f = amax / 4.0
    d = scale_f.max(axis=1) / 31.0                           # (sb, N)
    sc_q = jnp.clip(_nearest(scale_f * _safe_inv(d)[:, None]), 0, 31)
    eff = d[:, None] * sc_q
    q = jnp.clip(_nearest(x * _safe_inv(eff)[:, :, None]), -4, 3) + 4  # [0,7]
    q = q.reshape(K, N)
    qs = slab_pack(q.astype(jnp.uint8) & 3, 2, 256)
    hmask = slab_pack(q.astype(jnp.uint8) >> 2, 1, 256)
    scales = (sc_q + 32).astype(jnp.uint8).reshape(K // 16, N)  # stored 0..63
    return QTensor("q3_k", (K, N), dict(
        qs=qs, hmask=hmask, scales=scales, d=d.astype(jnp.float16)))


def dequantize_q3_k(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    K, N = t.shape
    nsb = K // 256
    lo = slab_unpack(t.data["qs"], 2, 256)
    hi = slab_unpack(t.data["hmask"], 1, 256)
    q = _f32(lo + (hi << 2)) - 4.0                           # [-4, 3]
    q = q.reshape(nsb, 16, 16, N)
    sc = _f32(t.data["scales"]).reshape(nsb, 16, N) - 32.0
    d = _f32(t.data["d"])[:, None]
    w = (d * sc)[:, :, None] * q
    return w.reshape(K, N).astype(dtype)


# ---------------------------------------------------------------------------
# Q3_K_O (beyond-paper): q3_k base + fp16 outlier sidecar.
#
# Per 256-row super-block and per output column, the OUTLIERS_PER_SB rows
# with the largest (activation-weighted) magnitude are stored exactly in an
# fp16 sidecar (local row index + value) and zeroed before the q3_k fit, so
# the narrow 3-bit grid is spent on the well-behaved bulk. ``act_absmax``
# comes from core/calibrate.py (per-K-column activation abs-max); without it
# the selection falls back to weight magnitude alone.
# ---------------------------------------------------------------------------

OUTLIERS_PER_SB = 8


def quantize_q3_k_o(w: jnp.ndarray, act_absmax=None) -> QTensor:
    K, N = w.shape
    assert K % 256 == 0, K
    nsb = K // 256
    no = OUTLIERS_PER_SB
    x = w.astype(jnp.float32).reshape(nsb, 256, N)
    score = jnp.abs(x)
    if act_absmax is not None:
        a = jnp.asarray(act_absmax, jnp.float32).reshape(nsb, 256)
        score = score * a[:, :, None]
    # top-`no` rows per (super-block, column); top_k works on the last axis
    _, idx = jax.lax.top_k(jnp.swapaxes(score, 1, 2), no)   # (nsb, N, no)
    idx = jnp.swapaxes(idx, 1, 2)                           # (nsb, no, N)
    ovals = jnp.take_along_axis(x, idx, axis=1)             # (nsb, no, N)
    rows = jax.lax.broadcasted_iota(jnp.int32, (nsb, 256, N), 1)
    mask = jnp.zeros((nsb, 256, N), bool)
    for j in range(no):
        mask = mask | (rows == idx[:, j][:, None, :])
    base = jnp.where(mask, 0.0, x).reshape(K, N)
    qt = quantize_q3_k(base)
    return QTensor("q3_k_o", (K, N), dict(
        qt.data,
        oidx=idx.astype(jnp.uint8).reshape(K // 32, N),
        ovals=ovals.astype(jnp.float16).reshape(K // 32, N)))


def dequantize_q3_k_o(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    K, N = t.shape
    nsb = K // 256
    no = OUTLIERS_PER_SB
    base = dequantize_q3_k(
        QTensor("q3_k", (K, N),
                {k: t.data[k] for k in ("qs", "hmask", "scales", "d")}),
        dtype=jnp.float32)
    idx = t.data["oidx"].astype(jnp.int32).reshape(nsb, no, N)
    vals = _f32(t.data["ovals"]).reshape(nsb, no, N)
    w = base.reshape(nsb, 256, N)
    rows = jax.lax.broadcasted_iota(jnp.int32, (nsb, 256, N), 1)
    # scatter-by-comparison: VPU-friendly inside the Pallas kernel (no
    # gathers); top_k indices are distinct so `where` never double-writes
    for j in range(no):
        sel = rows == idx[:, j][:, None, :]
        w = jnp.where(sel, vals[:, j][:, None, :], w)
    return w.reshape(K, N).astype(dtype)


# ---------------------------------------------------------------------------
# Q4_K / Q5_K (affine, 32-blocks, 6-bit scales+mins)
# ---------------------------------------------------------------------------

def _quantize_q45_common(w, qmax, with_high):
    K, N = w.shape
    assert K % 256 == 0, K
    nsb = K // 256
    x = w.astype(jnp.float32).reshape(nsb, 8, 32, N)
    bmax = x.max(axis=2)
    bmin = x.min(axis=2)
    min_f = jnp.maximum(0.0, -bmin)
    scale_f = jnp.maximum(bmax + min_f, 0.0) / qmax
    d = scale_f.max(axis=1) / 63.0
    dmin = min_f.max(axis=1) / 63.0
    sc_q = jnp.clip(_nearest(scale_f * _safe_inv(d)[:, None]), 0, 63)
    m_q = jnp.clip(_nearest(min_f * _safe_inv(dmin)[:, None]), 0, 63)
    eff_sc = d[:, None] * sc_q
    eff_mn = dmin[:, None] * m_q
    q = jnp.clip(_nearest((x + eff_mn[:, :, None]) * _safe_inv(eff_sc)[:, :, None]),
                 0, qmax).astype(jnp.uint8).reshape(K, N)
    data = dict(
        qs=slab_pack(q & 15, 4, 256),
        scales=sc_q.astype(jnp.uint8).reshape(K // 32, N),
        mins=m_q.astype(jnp.uint8).reshape(K // 32, N),
        d=d.astype(jnp.float16), dmin=dmin.astype(jnp.float16))
    if with_high:
        data["qh"] = slab_pack(q >> 4, 1, 256)
    return data, (K, N)


def quantize_q4_k(w):
    data, shape = _quantize_q45_common(w, 15, with_high=False)
    return QTensor("q4_k", shape, data)


def quantize_q5_k(w):
    data, shape = _quantize_q45_common(w, 31, with_high=True)
    return QTensor("q5_k", shape, data)


def _dequantize_q45_common(t, dtype):
    K, N = t.shape
    nsb = K // 256
    q = slab_unpack(t.data["qs"], 4, 256)
    if "qh" in t.data:
        q = q + (slab_unpack(t.data["qh"], 1, 256) << 4)
    q = _f32(q).reshape(nsb, 8, 32, N)
    sc = _f32(t.data["scales"]).reshape(nsb, 8, N)
    mn = _f32(t.data["mins"]).reshape(nsb, 8, N)
    d = _f32(t.data["d"])[:, None]
    dmin = _f32(t.data["dmin"])[:, None]
    w = (d * sc)[:, :, None] * q - (dmin * mn)[:, :, None]
    return w.reshape(K, N).astype(dtype)


def dequantize_q4_k(t, dtype=jnp.float32):
    return _dequantize_q45_common(t, dtype)


def dequantize_q5_k(t, dtype=jnp.float32):
    return _dequantize_q45_common(t, dtype)


# ---------------------------------------------------------------------------
# Q6_K (symmetric, 16-blocks, int8 block scales)
# ---------------------------------------------------------------------------

def quantize_q6_k(w: jnp.ndarray) -> QTensor:
    K, N = w.shape
    assert K % 256 == 0, K
    nsb = K // 256
    x = w.astype(jnp.float32).reshape(nsb, 16, 16, N)
    amax = jnp.abs(x).max(axis=2)
    scale_f = amax / 32.0
    d = scale_f.max(axis=1) / 127.0
    sc_q = jnp.clip(_nearest(scale_f * _safe_inv(d)[:, None]), -128, 127)
    eff = d[:, None] * sc_q
    q = jnp.clip(_nearest(x * _safe_inv(eff)[:, :, None]), -32, 31) + 32
    q = q.astype(jnp.uint8).reshape(K, N)                    # [0, 63]
    return QTensor("q6_k", (K, N), dict(
        ql=slab_pack(q & 15, 4, 256),
        qh=slab_pack(q >> 4, 2, 256),
        scales=sc_q.astype(jnp.int8).reshape(K // 16, N),
        d=d.astype(jnp.float16)))


def dequantize_q6_k(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    K, N = t.shape
    nsb = K // 256
    q = _f32(slab_unpack(t.data["ql"], 4, 256)
             + (slab_unpack(t.data["qh"], 2, 256) << 4)) - 32.0
    q = q.reshape(nsb, 16, 16, N)
    sc = _f32(t.data["scales"]).reshape(nsb, 16, N)
    d = _f32(t.data["d"])[:, None]
    w = (d * sc)[:, :, None] * q
    return w.reshape(K, N).astype(dtype)


# ---------------------------------------------------------------------------
# Q4_0 (classic 32-block symmetric 4-bit, fp16 scale)
# ---------------------------------------------------------------------------

def quantize_q4_0(w: jnp.ndarray) -> QTensor:
    """llama.cpp sign convention: d = (signed abs-max element) / -8, so
    the extreme value maps exactly to code 0 (-8 on the grid) and the
    grid's asymmetric [-8, 7] range points toward it."""
    K, N = w.shape
    assert K % 32 == 0, K
    x = w.astype(jnp.float32).reshape(K // 32, 32, N)
    imax = jnp.argmax(jnp.abs(x), axis=1)                    # (K//32, N)
    mval = jnp.take_along_axis(x, imax[:, None], axis=1)[:, 0]
    d = mval / -8.0
    inv = jnp.where(d != 0, 1.0 / jnp.where(d != 0, d, 1.0), 0.0)
    q = jnp.clip(_nearest(x * inv[:, None]) + 8, 0, 15)
    q = q.astype(jnp.uint8).reshape(K, N)
    return QTensor("q4_0", (K, N), dict(
        qs=slab_pack(q, 4, 32), d=d.astype(jnp.float16)))


def dequantize_q4_0(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    K, N = t.shape
    q = _f32(slab_unpack(t.data["qs"], 4, 32)) - 8.0
    d = _f32(t.data["d"])[:, None]                           # (K//32, 1, N)
    w = d * q.reshape(K // 32, 32, N)
    return w.reshape(K, N).astype(dtype)


# ---------------------------------------------------------------------------
# Q8_0 (fallback for K % 256 != 0; blocks of 32, fp16 scale)
# ---------------------------------------------------------------------------

def quantize_q8_0(w: jnp.ndarray) -> QTensor:
    K, N = w.shape
    assert K % 32 == 0, K
    x = w.astype(jnp.float32).reshape(K // 32, 32, N)
    amax = jnp.abs(x).max(axis=1)                            # (K//32, N)
    d = amax / 127.0
    q = jnp.clip(_nearest(x * _safe_inv(d)[:, None]), -127, 127)
    return QTensor("q8_0", (K, N), dict(
        qs=q.astype(jnp.int8).reshape(K, N), d=d.astype(jnp.float16)))


def dequantize_q8_0(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    K, N = t.shape
    q = _f32(t.data["qs"]).reshape(K // 32, 32, N)
    d = _f32(t.data["d"])[:, None]
    return (d * q).reshape(K, N).astype(dtype)


# ---------------------------------------------------------------------------
# Q8_K activations: x (..., K) -> dict(qs int8, d f32, bsums int16)
# ---------------------------------------------------------------------------

def quantize_q8_k(x: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    K = x.shape[-1]
    assert K % 256 == 0, K
    lead = x.shape[:-1]
    xf = x.astype(jnp.float32).reshape(lead + (K // 256, 256))
    amax = jnp.abs(xf).max(axis=-1)                          # (..., nsb)
    d = amax / 127.0
    q = jnp.clip(_nearest(xf * _safe_inv(d)[..., None]), -127, 127)
    q = q.astype(jnp.int8)
    bsums = q.astype(jnp.int32).reshape(lead + (K // 256, 16, 16)).sum(-1)
    return dict(qs=q.reshape(lead + (K,)),
                d=d,
                bsums=bsums.astype(jnp.int16).reshape(lead + (K // 16,)))


def dequantize_q8_k(qx: Dict[str, jnp.ndarray], dtype=jnp.float32) -> jnp.ndarray:
    qs = qx["qs"]
    K = qs.shape[-1]
    lead = qs.shape[:-1]
    q = qs.astype(jnp.float32).reshape(lead + (K // 256, 256))
    x = q * qx["d"][..., None]
    return x.reshape(lead + (K,)).astype(dtype)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

_QUANTIZE = {
    "q2_k": quantize_q2_k, "q3_k": quantize_q3_k,
    "q3_k_o": quantize_q3_k_o, "q4_0": quantize_q4_0,
    "q4_k": quantize_q4_k, "q5_k": quantize_q5_k, "q6_k": quantize_q6_k,
    "q8_0": quantize_q8_0,
}
_DEQUANTIZE = {
    "q2_k": dequantize_q2_k, "q3_k": dequantize_q3_k,
    "q3_k_o": dequantize_q3_k_o,
    "q4_0": dequantize_q4_0, "q4_k": dequantize_q4_k,
    "q5_k": dequantize_q5_k, "q6_k": dequantize_q6_k,
    "q8_0": dequantize_q8_0,
}


def quantize(variant: str, w: jnp.ndarray) -> QTensor:
    """Quantize weight matrix w (K, N) along K. Applies the llama.cpp
    fallback rule (K % 256 != 0 -> q8_0)."""
    variant = F.pick_fallback(variant, w.shape[0])
    return _QUANTIZE[variant](w)


def dequantize(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    return _DEQUANTIZE[t.variant](t, dtype=dtype)


def qtensor_spec(variant: str, K: int, N: int) -> QTensor:
    """ShapeDtypeStruct stand-in QTensor (for dry-run lowering)."""
    variant = F.pick_fallback(variant, K)
    fmt = F.get_format(variant)
    data = {name: jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
            for name, (shape, dt) in fmt.array_shapes(K, N).items()}
    return QTensor(variant, (K, N), data)
