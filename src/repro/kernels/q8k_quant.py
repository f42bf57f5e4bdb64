"""Pallas kernel: quantize activations to Q8_K (per-256 super-block int8).

The paper's driver quantizes input tensors to Q8_K before streaming them to
the accelerator (llama.cpp does the same on CPU). On TPU this is a cheap
VPU pass: per 256-value super-block, absmax -> scale -> round, plus the
16-block partial sums ("bsums") that the Q2_K min-correction term consumes.

Batched callers of the *integer* (Q8_K) datapath -- ``ref.matmul_q8k_ref``
and the ISA simulator; the fused serving kernels take float activations
directly -- hand this kernel a right-padded (G, P, K) batch flattened to
M = G*P rows, where trailing rows of each request are padding. The
optional ``valid`` row mask zeroes those rows' payloads (qs/d/bsums all
exactly 0) inside the kernel, so the integer dot products see inert
padding without a separate masking pass over the (M, K) activations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, valid_ref, qs_ref, d_ref, bs_ref):
    x = x_ref[...].astype(jnp.float32)              # (bm, K)
    if valid_ref is not None:
        x = x * valid_ref[...].astype(jnp.float32)  # (bm, 1) row mask
    bm, K = x.shape
    nsb = K // 256
    xs = x.reshape(bm, nsb, 256)
    amax = jnp.abs(xs).max(axis=-1)                  # (bm, nsb)
    d = amax / 127.0
    inv = jnp.where(d > 0, 1.0 / jnp.where(d > 0, d, 1.0), 0.0)
    q = jnp.clip(jnp.round(xs * inv[..., None]), -127, 127)
    qi = q.astype(jnp.int32)
    bsums = qi.reshape(bm, nsb, 16, 16).sum(axis=-1)
    qs_ref[...] = qi.reshape(bm, K).astype(jnp.int8)
    d_ref[...] = d
    bs_ref[...] = bsums.reshape(bm, K // 16).astype(jnp.int16)


def q8k_quantize_pallas(x: jnp.ndarray, *, valid: jnp.ndarray = None,
                        block_m: int = 8, interpret: bool = False):
    """x: (M, K), K % 256 == 0 -> dict(qs int8 (M,K), d f32 (M,K/256),
    bsums int16 (M,K/16)). ``valid``: optional (M,) bool/0-1 row mask --
    False rows (batch padding) quantize to all-zero payloads."""
    M, K = x.shape
    assert K % 256 == 0, K
    bm = min(block_m, M)
    Mp = (M + bm - 1) // bm * bm
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    grid = (Mp // bm,)
    in_specs = [pl.BlockSpec((bm, K), lambda i: (i, 0))]
    args = [x]
    if valid is not None:
        v2 = jnp.asarray(valid).astype(jnp.float32).reshape(M, 1)
        if Mp != M:
            v2 = jnp.pad(v2, ((0, Mp - M), (0, 0)))
        in_specs.append(pl.BlockSpec((bm, 1), lambda i: (i, 0)))
        args.append(v2)
        kernel = _kernel
    else:
        def kernel(x_ref, qs_ref, d_ref, bs_ref):
            _kernel(x_ref, None, qs_ref, d_ref, bs_ref)

    qs, d, bs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((bm, K // 256), lambda i: (i, 0)),
            pl.BlockSpec((bm, K // 16), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, K), jnp.int8),
            jax.ShapeDtypeStruct((Mp, K // 256), jnp.float32),
            jax.ShapeDtypeStruct((Mp, K // 16), jnp.int16),
        ],
        name="q8k_quantize",
        interpret=interpret,
    )(*args)
    return dict(qs=qs[:M], d=d[:M], bsums=bs[:M])
