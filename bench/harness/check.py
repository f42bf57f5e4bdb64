"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample of
the requests due in the window that finished, drawn from the seed and
holding the one with the most served tokens, goes through the plain
float32 reference of the configuration's family: one full causal
forward over prompt + served tokens per request. The number compared is
the widest gap by which a served token's reference logit lies below the
reference's best logit at that position. It is 0 where every served
token is the reference's own greedy choice, and a near-tie flipped by
rounding reads about as much as the rounding moved the logits.

The control puts the reference, computed in float8 (e4m3, one scale per
row of each matmul operand, the step below the configuration's
bfloat16), in the program's place: at each position of the same prompts
and served tokens it reads the gap of the token the float8 forward puts
first, and the cell's limits judge it as they judge the program.
``tools/calibrate.py`` reads it on the chip; the benchmark's own runs do
not.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness.load import Record


def sample(records: List[Record], min_tokens: int,
           rng: np.random.Generator) -> List[Record]:
    """The finished window request with the most served tokens, then
    others in a seeded order until ``min_tokens`` served tokens."""
    done = [r for r in records if r.in_window and r.complete]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.rid))
    out, rest = [done[0]], done[1:]
    for i in rng.permutation(len(rest)):
        if sum(len(r.tokens) for r in out) >= min_tokens:
            break
        out.append(rest[int(i)])
    return out


def e4m3(x, axis):
    """Round to float8 e4m3 (3 mantissa bits, exponents from -6, max 448)
    with one scale per slice along ``axis``, the operand's contraction
    axis, and return the result in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -9)))
    step = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)
    return jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * s


class Reference:
    """Compiled reference (and control) forwards at one padded length."""

    def __init__(self, family, conf: Dict, seq_len: int, n_out: int):
        self.seq_len, self.n_out = seq_len, n_out

        def gaps(w, toks, start, served):
            lg = family.reference_logits(conf, w, toks, start, n_out)
            best = lg.max(-1)
            return best - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]

        def control_gaps(w, toks, start):
            lg = family.reference_logits(conf, w, toks, start, n_out)
            low = family.reference_logits(conf, w, toks, start, n_out,
                                          rnd=e4m3)
            pick = jnp.argmax(low, -1)
            return lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]

        self._gaps = jax.jit(gaps)
        self._control = jax.jit(control_gaps)

    def _inputs(self, r: Record):
        seq = list(r.arrival.prompt) + list(r.tokens[:-1])
        n = len(r.tokens)
        if len(seq) > self.seq_len or n > self.n_out:
            raise ValueError(f"request of {len(seq)} + {n} exceeds the "
                             f"reference's {self.seq_len} / {self.n_out}")
        toks = np.zeros(self.seq_len, np.int32)
        toks[:len(seq)] = seq
        served = np.zeros(self.n_out, np.int32)
        served[:n] = r.tokens
        return (jnp.asarray(toks), jnp.asarray(len(r.arrival.prompt) - 1,
                                               jnp.int32),
                jnp.asarray(served), n)

    def widest_gap(self, weights, recs: List[Record]) -> float:
        """Program: widest gap of a served token below the reference's best."""
        worst = 0.0
        for r in recs:
            toks, start, served, n = self._inputs(r)
            g = np.asarray(self._gaps(weights, toks, start, served))[:n]
            worst = max(worst, float(g.max()))
        return worst

    def control_widest_gap(self, weights, recs: List[Record]) -> float:
        """Control: widest gap of the float8 forward's first choice."""
        worst = 0.0
        for r in recs:
            toks, start, _, n = self._inputs(r)
            g = np.asarray(self._control(weights, toks, start))[:n]
            worst = max(worst, float(g.max()))
        return worst


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)


def report(numbers: Dict[str, float], limits: Dict[str, float],
           say: Callable[[str], None]) -> Dict[str, Dict[str, float]]:
    out = {}
    for k in limits:
        say(f"check {k} {numbers[k]!r} limit {limits[k]!r}")
        out[k] = {"value": numbers[k], "limit": limits[k]}
    return out
