"""BFP (block floating point) quantization format descriptors.

Implements the llama.cpp / GGUF "k-quant" family used by the F-BFQ paper:

  * Q2_K, Q3_K   -- the two variants the paper's accelerator executes
  * Q4_K, Q5_K, Q6_K, Q8_0 -- the paper's stated future work ("support
                    Q4_K-Q8_K"), implemented here as beyond-paper variants
  * Q8_K         -- activation format (int8 per 256-value super-block)

Packed layout is TPU-native structure-of-arrays (SoA): for a weight matrix
``W`` of shape ``(K, N)`` quantized along the reduction axis ``K``, every
payload array keeps ``N`` on the minor (128-lane) dimension and packs
sub-byte fields along ``K`` in *slab order*:

    within each super-block of ``R`` rows, the packed array has ``R // F``
    rows (``F`` fields per byte); bit-field ``j`` (shift ``j * bits``) of
    packed row ``p`` holds original row ``j * (R // F) + p``.

Unpacking is therefore ``concat([(q >> bits*j) & mask for j in range(F)])``
over whole ``(R//F, N)`` slabs -- vectorizable on the TPU VPU with no
sub-lane shuffles (this is the kernel-side analogue of the paper's
"bit-slicer + data mapper").
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax.numpy as jnp
import numpy as np

SUPER_BLOCK = 256   # weights per super-block (SB) for k-quants
BLOCK16 = 16        # Q2_K/Q3_K/Q6_K sub-block
BLOCK32 = 32        # Q4_K/Q5_K sub-block, Q8_0 block


@dataclasses.dataclass(frozen=True)
class ArraySpec:
    """Shape/dtype of one packed payload array, as a function of (K, N)."""
    name: str
    # divisor along K (packed rows = K // k_div); 0 means shape (K//256, N)
    k_div: int
    dtype: str

    def shape(self, K: int, N: int) -> Tuple[int, int]:
        return (K // self.k_div, N)


@dataclasses.dataclass(frozen=True)
class QuantFormat:
    name: str
    # effective bits per weight of THIS implementation's packed layout
    bits_per_weight: float
    # llama.cpp reference bits per weight (for honesty in reports)
    bits_per_weight_gguf: float
    block: int                 # sub-block size (per-block scale granularity)
    super_block: int           # rows per super-block along K
    arrays: Tuple[ArraySpec, ...]
    is_weight_format: bool = True

    def array_shapes(self, K: int, N: int) -> Dict[str, Tuple[Tuple[int, int], str]]:
        if K % self.super_block:
            raise ValueError(
                f"{self.name}: K={K} not divisible by super-block "
                f"{self.super_block}")
        return {a.name: (a.shape(K, N), a.dtype) for a in self.arrays}

    def nbytes(self, K: int, N: int) -> int:
        total = 0
        for a in self.arrays:
            shp = a.shape(K, N)
            total += int(np.prod(shp)) * np.dtype(a.dtype).itemsize
        return total


# --------------------------------------------------------------------------
# Format registry.
#
# bits/weight bookkeeping (ours vs llama.cpp GGUF):
#   Q2_K : 2 + 8/16 + 2*16/256                  = 2.625   (gguf: 2.625, exact)
#   Q3_K : 2 + 1 + 8/16 + 16/256                = 3.5625  (gguf: 3.4375; we
#           store the 6-bit block scales byte-aligned for lane-conflict-free
#           access -- +0.125 b/w)
#   Q3_K_O: q3_k + 8*(8+16)/256 outlier sidecar = 4.3125  (gguf: 4.1875)
#   Q4_0 : 4 + 16/32                            = 4.5     (gguf: 4.5, exact)
#   Q4_K : 4 + 2*8/32 + 2*16/256                = 4.625   (gguf: 4.5)
#   Q5_K : 5 + 2*8/32 + 2*16/256                = 5.625   (gguf: 5.5)
#   Q6_K : 4 + 2 + 8/16 + 16/256                = 6.5625  (gguf: 6.5625, exact)
#   Q8_0 : 8 + 16/32                            = 8.5     (gguf: 8.5, exact)
#   Q8_K : 8 + 32/256 + 16*16/256 (bsums)       = 9.125   (activation format)
# --------------------------------------------------------------------------

Q2_K = QuantFormat(
    name="q2_k", bits_per_weight=2.625, bits_per_weight_gguf=2.625,
    block=BLOCK16, super_block=SUPER_BLOCK,
    arrays=(
        ArraySpec("qs", 4, "uint8"),       # 4 x 2-bit quants per byte
        ArraySpec("scales", 16, "uint8"),  # lo nibble: scale, hi nibble: min
        ArraySpec("d", 256, "float16"),    # SB super-scale for scales
        ArraySpec("dmin", 256, "float16"), # SB super-scale for mins
    ))

Q3_K = QuantFormat(
    name="q3_k", bits_per_weight=3.5625, bits_per_weight_gguf=3.4375,
    block=BLOCK16, super_block=SUPER_BLOCK,
    arrays=(
        ArraySpec("qs", 4, "uint8"),       # low 2 bits
        ArraySpec("hmask", 8, "uint8"),    # high bit
        ArraySpec("scales", 16, "uint8"),  # 6-bit scale, stored 0..63
        ArraySpec("d", 256, "float16"),
    ))

Q3_K_O = QuantFormat(
    # beyond-paper outlier-aware variant (d-Matrix-style outlier blocks,
    # PAPERS.md): q3_k base plus an fp16 sidecar holding, per 256-row
    # super-block and per output column, the 8 most activation-sensitive
    # weight rows at full fp16 (local row index + value). The base q3_k
    # payload stores 0 at those positions; dequant scatters the sidecar
    # back. 8*(8+16)/256 = 0.75 extra bits/weight over q3_k.
    name="q3_k_o", bits_per_weight=4.3125, bits_per_weight_gguf=4.1875,
    block=BLOCK16, super_block=SUPER_BLOCK,
    arrays=(
        ArraySpec("qs", 4, "uint8"),       # low 2 bits (as q3_k)
        ArraySpec("hmask", 8, "uint8"),    # high bit (as q3_k)
        ArraySpec("scales", 16, "uint8"),  # 6-bit scale, stored 0..63
        ArraySpec("d", 256, "float16"),
        ArraySpec("oidx", 32, "uint8"),    # 8 outlier row idx per SB (local)
        ArraySpec("ovals", 32, "float16"), # their fp16 values
    ))

Q4_K = QuantFormat(
    name="q4_k", bits_per_weight=4.625, bits_per_weight_gguf=4.5,
    block=BLOCK32, super_block=SUPER_BLOCK,
    arrays=(
        ArraySpec("qs", 2, "uint8"),       # 2 x 4-bit per byte
        ArraySpec("scales", 32, "uint8"),  # 6-bit scale, 0..63
        ArraySpec("mins", 32, "uint8"),    # 6-bit min, 0..63
        ArraySpec("d", 256, "float16"),
        ArraySpec("dmin", 256, "float16"),
    ))

Q5_K = QuantFormat(
    name="q5_k", bits_per_weight=5.625, bits_per_weight_gguf=5.5,
    block=BLOCK32, super_block=SUPER_BLOCK,
    arrays=(
        ArraySpec("qs", 2, "uint8"),       # low 4 bits
        ArraySpec("qh", 8, "uint8"),       # high bit
        ArraySpec("scales", 32, "uint8"),
        ArraySpec("mins", 32, "uint8"),
        ArraySpec("d", 256, "float16"),
        ArraySpec("dmin", 256, "float16"),
    ))

Q6_K = QuantFormat(
    name="q6_k", bits_per_weight=6.5625, bits_per_weight_gguf=6.5625,
    block=BLOCK16, super_block=SUPER_BLOCK,
    arrays=(
        ArraySpec("ql", 2, "uint8"),       # low 4 bits
        ArraySpec("qh", 4, "uint8"),       # high 2 bits
        ArraySpec("scales", 16, "int8"),   # signed 8-bit block scales
        ArraySpec("d", 256, "float16"),
    ))

Q4_0 = QuantFormat(
    # llama.cpp's classic 32-block symmetric 4-bit format: one fp16 scale
    # per 32 values, d pinned by the abs-max element mapping to code 0
    name="q4_0", bits_per_weight=4.5, bits_per_weight_gguf=4.5,
    block=BLOCK32, super_block=BLOCK32,
    arrays=(
        ArraySpec("qs", 2, "uint8"),       # 2 x 4-bit per byte
        ArraySpec("d", 32, "float16"),
    ))

Q8_0 = QuantFormat(
    # llama.cpp fallback for tensors whose K is not a multiple of 256
    name="q8_0", bits_per_weight=8.5, bits_per_weight_gguf=8.5,
    block=BLOCK32, super_block=BLOCK32,
    arrays=(
        ArraySpec("qs", 1, "int8"),
        ArraySpec("d", 32, "float16"),
    ))

Q8_K = QuantFormat(
    # activation format: int8 per 256-value SB + fp32 scale + 16-block sums
    name="q8_k", bits_per_weight=9.125, bits_per_weight_gguf=9.125,
    block=BLOCK16, super_block=SUPER_BLOCK,
    arrays=(
        ArraySpec("qs", 1, "int8"),
        ArraySpec("d", 256, "float32"),
        ArraySpec("bsums", 16, "int16"),
    ),
    is_weight_format=False)

FORMATS: Dict[str, QuantFormat] = {
    f.name: f for f in (Q2_K, Q3_K, Q3_K_O, Q4_0, Q4_K, Q5_K, Q6_K, Q8_0,
                        Q8_K)
}

# variants the paper's accelerator supports natively
PAPER_VARIANTS = ("q2_k", "q3_k")
# variants listed as the paper's future work, implemented here (q3_k_o is
# our beyond-paper outlier-sidecar variant used by `--policy auto`)
EXTENDED_VARIANTS = ("q3_k_o", "q4_0", "q4_k", "q5_k", "q6_k", "q8_0")
WEIGHT_VARIANTS = PAPER_VARIANTS + EXTENDED_VARIANTS


def get_format(name: str) -> QuantFormat:
    try:
        return FORMATS[name]
    except KeyError:
        raise KeyError(f"unknown quant format {name!r}; "
                       f"known: {sorted(FORMATS)}") from None


def pick_fallback(name: str, K: int) -> str:
    """llama.cpp behaviour: k-quants need K % 256 == 0; otherwise the tensor
    falls back to a 32-block format (Q8_0 here)."""
    fmt = get_format(name)
    if K % fmt.super_block == 0:
        return name
    if K % 32 == 0:
        return "q8_0"
    raise ValueError(f"K={K} not quantizable (needs K % 32 == 0)")


# ---------------------------------------------------------------------------
# slab pack/unpack primitives (shared by quantize.py, kernels, tests)
# ---------------------------------------------------------------------------

def slab_pack(q: jnp.ndarray, bits: int, sb_rows: int) -> jnp.ndarray:
    """Pack integer array q (K, N), values in [0, 2^bits), into bytes.

    F = 8 // bits fields per byte; within each super-block of ``sb_rows``
    rows, field j of packed row p holds original row ``j * (sb_rows//F) + p``.
    """
    F = 8 // bits
    K, N = q.shape
    assert K % sb_rows == 0, (K, sb_rows)
    slab = sb_rows // F
    qq = q.astype(jnp.uint8).reshape(K // sb_rows, F, slab, N)
    out = jnp.zeros((K // sb_rows, slab, N), jnp.uint8)
    for j in range(F):
        out = out | (qq[:, j] << (bits * j))
    return out.reshape(K // F, N)


def slab_unpack(packed: jnp.ndarray, bits: int, sb_rows: int) -> jnp.ndarray:
    """Inverse of slab_pack: (K//F, N) bytes -> (K, N) int32 in [0, 2^bits).

    The bytes widen to int32 before any shift: Mosaic lowers 32-bit
    vector shifts and int32 -> f32 casts, not their uint8 forms."""
    F = 8 // bits
    Kp, N = packed.shape
    slab = sb_rows // F
    assert Kp % slab == 0, (Kp, sb_rows)
    p = packed.astype(jnp.int32).reshape(Kp // slab, slab, N)
    mask = (1 << bits) - 1
    slabs = [((p >> (bits * j)) & mask) for j in range(F)]
    return jnp.concatenate(slabs, axis=1).reshape(Kp * F, N)
