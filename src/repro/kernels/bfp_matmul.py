"""Fused BFP dequant-matmul Pallas TPU kernel -- the DSBP, TPU-native.

The paper's Dynamic Super-Block Processor streams packed super-blocks from
main memory through a bit-slicer/data-mapper into BRAM caches, runs a shared
integer vector engine, and applies variant-specific scaling. On TPU the same
dataflow becomes:

  HBM (packed SoA arrays)  --BlockSpec DMA-->  VMEM tiles
  bit-slicer/data-mapper    = vectorized shift/mask slab unpack (VPU)
  shared vector engine      = MXU ``jnp.dot`` with fp32 accumulation
  Q2/Q3 scalar units + mux  = variant-specific two-level scale fold,
                              selected statically per layer (one compiled
                              program holds both variants; switching per
                              layer needs no reconfiguration)

Output-stationary tiling (paper §III-C): grid (M/bm, N/bn, K/bk) with the
K dimension innermost/"arbitrary"; the output tile stays resident in VMEM
across the K sweep, exactly like the paper's accumulator register file.

HBM traffic per output tile is the *packed* operand bytes -- the entire
point of BFP quantization (2.625-3.5625 bits/weight instead of 16).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import get_format
from repro.core.quantize import QTensor, dequantize

def _choose_block_k(K: int, sb: int, target: int = 512) -> int:
    """Largest bk <= target with bk % sb == 0 and K % bk == 0.

    Falls back to bk = sb when no super-block-aligned divisor of K exists
    at or below the target (e.g. K = 1792 with target 384): K is always a
    super-block multiple for packed tensors, so sb itself always tiles --
    a smaller-than-asked tile, never an error. A target below sb gets the
    same fallback."""
    if K % sb:
        raise ValueError(f"K={K} is not a multiple of super-block {sb}; "
                         "not a packable shape")
    if K <= target:
        return K
    bk = target - target % sb
    while bk >= sb:
        if K % bk == 0:
            return bk
        bk -= sb
    return sb


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _tile_rows(full: jnp.ndarray, start, rows: int) -> jnp.ndarray:
    """Rows [start, start + rows) of a VMEM-resident whole-K payload block.

    A payload with fewer than 8 rows per K tile (one value per super-block,
    e.g. q2_k's d at bk 512 is 2 rows) has no legal tile of its own, so the
    wrapper hands the kernel all of K and this picks the tile's rows by a
    masked sublane reduction: Mosaic only slices dynamically at multiples
    of 8 rows. Exact: every output row sums one selected value and zeros."""
    R, bn = full.shape
    shape = (rows, R, bn)
    hit = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
           == jax.lax.broadcasted_iota(jnp.int32, shape, 0) + start)
    return jnp.sum(jnp.where(hit, full[None], jnp.zeros((), full.dtype)),
                   axis=1)


def _kernel(x_ref, *rest, variant: str, names: Tuple[str, ...],
            whole_k: Tuple[int, ...], block_shape: Tuple[int, int],
            compute_dtype):
    """rest = (*weight_refs, out_ref). ``whole_k[i]`` is 0 for a payload
    tiled along K, else its rows per K tile (its block holds all of K)."""
    w_refs, o_ref = rest[:-1], rest[-1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # "bit-slicer + data mapper": unpack/dequantize this VMEM tile.
    data = {}
    for name, ref, rows in zip(names, w_refs, whole_k):
        a = ref[...]
        if rows:
            if jnp.issubdtype(a.dtype, jnp.integer):
                a = a.astype(jnp.int32)
            a = _tile_rows(a, k * rows, rows)
        data[name] = a
    qt = QTensor(variant, block_shape, data)
    w = dequantize(qt, dtype=compute_dtype)          # (bk, bn)
    x = x_ref[...].astype(compute_dtype)             # (bm, bk)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


def bfp_matmul_pallas(x: jnp.ndarray, t: QTensor, *,
                      block_m: int = 128, block_n: int = 256,
                      block_k: int = 512,
                      compute_dtype=jnp.bfloat16,
                      out_dtype=None,
                      interpret: bool = False) -> jnp.ndarray:
    """x: (M, K) float; t: packed (K, N). Returns (M, N) in ``out_dtype``.

    M / N are padded to block multiples inside (packed arrays pad with
    zeros along lanes => zero weights, numerically inert).
    """
    M, K = x.shape
    Kt, N = t.shape
    assert K == Kt, (K, Kt)
    fmt = get_format(t.variant)
    for name, arr in t.data.items():
        # lane (last-axis) width must match the logical N: a QTensor whose
        # payloads were lane-sharded (serving TP slices lanes per shard;
        # K rows stay whole) but whose static aux shape still claims the
        # global N would otherwise fail deep inside the unpack reshapes --
        # shard_map callers must relocalize via
        # distributed.sharding.localize_serve_params first
        if arr.shape[-1] != N:
            raise ValueError(
                f"QTensor({t.variant}) payload {name!r} carries "
                f"{arr.shape[-1]} lanes but aux shape says N={N}; "
                "lane-sharded payloads need localize_serve_params")
    out_dtype = out_dtype or x.dtype

    bk = _choose_block_k(K, fmt.super_block, block_k)
    bm = min(block_m, _round_up(M, 8))
    bn = min(block_n, _round_up(N, 128))
    Mp, Np = _round_up(M, bm), _round_up(N, bn)

    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    # Mosaic cannot load f16 vectors: the f16 scale payloads (d/dmin, one
    # row per super-block; q4_0/q8_0's d and q3_k_o's ovals, one per 32
    # rows) widen to f32 here. The stored format stays f16.
    data = {k2: v.astype(jnp.float32) if v.dtype == jnp.float16 else v
            for k2, v in t.data.items()}
    if Np != N:
        data = {k2: jnp.pad(v, ((0, 0), (0, Np - N))) for k2, v in data.items()}

    names = tuple(sorted(data))
    kdiv = {a.name: a.k_div for a in fmt.arrays}
    grid = (Mp // bm, Np // bn, K // bk)

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))]
    whole_k = []
    for name in names:
        rows = bk // kdiv[name]
        if rows % 8 == 0 or rows == data[name].shape[0]:
            whole_k.append(0)
            in_specs.append(pl.BlockSpec((rows, bn), lambda i, j, k: (k, j)))
        else:
            # no legal (rows, bn) tile: hold all of K, which stays in VMEM
            # across the K sweep (same block index), see _tile_rows
            whole_k.append(rows)
            in_specs.append(pl.BlockSpec((data[name].shape[0], bn),
                                         lambda i, j, k: (0, j)))

    out = pl.pallas_call(
        functools.partial(_kernel, variant=t.variant, names=names,
                          whole_k=tuple(whole_k), block_shape=(bk, bn),
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        name="bfp_matmul",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, *[data[n] for n in names])
    return out[:M, :N].astype(out_dtype)


def vmem_bytes(variant: str, block_m: int, block_n: int, block_k: int,
               x_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16) -> dict:
    """Static VMEM budget of one grid step (Table II analogue)."""
    fmt = get_format(variant)
    w_packed = sum((block_k // a.k_div) * block_n * jnp.dtype(a.dtype).itemsize
                   for a in fmt.arrays)
    return dict(
        x_tile=block_m * block_k * jnp.dtype(x_dtype).itemsize,
        w_packed_tile=w_packed,
        w_dequant_tile=block_k * block_n * jnp.dtype(compute_dtype).itemsize,
        acc_tile=block_m * block_n * 4,
        total=(block_m * block_k * jnp.dtype(x_dtype).itemsize + w_packed
               + block_k * block_n * jnp.dtype(compute_dtype).itemsize
               + block_m * block_n * 4),
    )
