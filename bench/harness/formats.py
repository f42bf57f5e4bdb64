"""The packed weight formats the benchmark's configurations use, written
from their definition: seeded payloads, the decode the reference runs,
and the stored bytes the roofline counts.

Layout (structure of arrays, N on the lanes, K packed in slab order):
within each 256-row super-block, field j of packed row p of a b-bit
array holds row j * (256 // (8 // b)) + p.

  q2_k  w = d * sc * q - dmin * m     q in 0..3, sc/m the low/high
        nibbles of ``scales`` (one byte per 16 rows), d/dmin float16
        per super-block
  q3_k  w = d * (s - 32) * (q - 4)    q = qs (2 bits) + 4 * hmask (1 bit),
        s = ``scales`` (one byte per 16 rows, 0..63), d float16 per
        super-block
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SB = 256

# payload arrays: name -> (rows of K per stored row, dtype)
LAYOUT = {
    "q2_k": {"qs": (4, jnp.uint8), "scales": (16, jnp.uint8),
             "d": (SB, jnp.float16), "dmin": (SB, jnp.float16)},
    "q3_k": {"qs": (4, jnp.uint8), "hmask": (8, jnp.uint8),
             "scales": (16, jnp.uint8), "d": (SB, jnp.float16)},
}


@jax.tree_util.register_pytree_node_class
class Packed:
    """One packed (K, N) weight (with any stacked leading axes)."""

    def __init__(self, variant: str, K: int, N: int, arrays: dict):
        self.variant, self.K, self.N, self.arrays = variant, K, N, arrays

    def tree_flatten(self):
        names = tuple(sorted(self.arrays))
        return (tuple(self.arrays[n] for n in names),
                (self.variant, self.K, self.N, names))

    @classmethod
    def tree_unflatten(cls, aux, children):
        variant, K, N, names = aux
        return cls(variant, K, N, dict(zip(names, children)))


def packed_bytes(variant: str, K: int, N: int) -> int:
    """Stored bytes of one (K, N) weight at its unpadded shape."""
    return sum((K // rows) * N * jnp.dtype(dt).itemsize
               for rows, dt in LAYOUT[variant].values())


# Var(w) / d^2 of the seeded payloads below (codes and the jitter u of |d|
# uniform, E[u^2] = 13/12), so d can be drawn for a chosen std of w
_VAR_PER_D2 = {
    # w/d = sc*q - 1.5*m: E = 0, E[(.)^2] = 271.25 - 253.125 + 174.375
    "q2_k": 192.5 * 13 / 12,
    # w/d = sc*(q-4), sc in 0..31, q-4 in -4..3: 325.5 * 5.5
    "q3_k": 1790.25 * 13 / 12,
}


def random_payload(variant: str, key, lead, K: int, N: int,
                   std: float) -> Packed:
    """Uniform codes and scales, float16 super-scales of random sign,
    drawn so the decoded weights have mean 0 and standard deviation
    ``std``. Runs under jit."""
    lead = tuple(lead)
    ks = jax.random.split(key, 4)
    nsb = K // SB
    mag = std / math.sqrt(_VAR_PER_D2[variant])
    u = jax.random.uniform(ks[0], lead + (nsb, N), jnp.float32, 0.5, 1.5)
    sign = jnp.where(jax.random.bernoulli(ks[1], 0.5, lead + (nsb, N)),
                     1.0, -1.0)
    d = sign * u * mag
    byte = lambda k, rows: jax.random.bits(k, lead + (K // rows, N),
                                           jnp.uint8)
    if variant == "q2_k":
        arrays = dict(qs=byte(ks[2], 4), scales=byte(ks[3], 16),
                      d=d.astype(jnp.float16),
                      dmin=(1.5 * d).astype(jnp.float16))
    elif variant == "q3_k":
        k2, k3 = jax.random.split(ks[3])
        arrays = dict(qs=byte(ks[2], 4), hmask=byte(k2, 8),
                      scales=32 + byte(k3, 16) % 32,
                      d=d.astype(jnp.float16))
    else:
        raise ValueError(f"no seeded payload for {variant!r}")
    return Packed(variant, K, N, arrays)


def _unslab(a, bits: int):
    """(..., K // F, N) bytes -> (..., K, N) int32 codes, slab order."""
    F = 8 // bits
    *lead, Kp, N = a.shape
    slab = SB // F
    a = a.astype(jnp.int32).reshape(*lead, Kp // slab, 1, slab, N)
    j = jnp.arange(F, dtype=jnp.int32).reshape(F, 1, 1)
    fields = (a >> (bits * j)) & ((1 << bits) - 1)  # (..., nsb, F, slab, N)
    return fields.reshape(*lead, Kp * F, N)


def decode(w: Packed):
    """Float32 weights (..., K, N) of a packed leaf."""
    K, N, a = w.K, w.N, w.arrays
    lead = a["qs"].shape[:-2]
    nsb = K // SB
    blk = lambda x: x.astype(jnp.float32).reshape(*lead, nsb, 16, 1, N)
    sbw = lambda x: x.astype(jnp.float32).reshape(*lead, nsb, 1, 1, N)
    if w.variant == "q2_k":
        q = _unslab(a["qs"], 2).astype(jnp.float32)
        q = q.reshape(*lead, nsb, 16, 16, N)
        s = a["scales"].astype(jnp.int32)
        out = (sbw(a["d"]) * blk(s & 15) * q
               - sbw(a["dmin"]) * blk(s >> 4))
    elif w.variant == "q3_k":
        q = (_unslab(a["qs"], 2) + 4 * _unslab(a["hmask"], 1) - 4)
        q = q.astype(jnp.float32).reshape(*lead, nsb, 16, 16, N)
        out = sbw(a["d"]) * (blk(a["scales"]) - 32.0) * q
    else:
        raise ValueError(f"no decode for {w.variant!r}")
    return out.reshape(*lead, K, N)
