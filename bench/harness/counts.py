"""The work the algorithm needs, counted from shapes: model FLOPs per
token, and the FLOPs and bytes of the packed projections (bfp_matmul).

Counts are of what any implementation must do, not of what today's
program does: packed weights at their stored size and unpadded N, live
rows only, attention over the live context only. A later change that
removes waste therefore raises a share towards 100% and never past it.
The attention term follows the program's own model-FLOPs convention
(launch/flops.py): 4 * heads * head_dim FLOPs per (query, key) pair and
layer."""
from __future__ import annotations

from typing import Dict, List, Tuple

from harness.formats import packed_bytes

ACT_BYTES = 2                       # bf16 activations in and out


class Counts:
    def __init__(self, family, conf: Dict):
        m = family.dims(conf)
        self.L, self.H, self.Dh = m["L"], m["H"], m["Dh"]
        projs = family.projections(conf)
        head = [p for p in projs if p[0] == "lm_head"]
        body = [p for p in projs if p[0] != "lm_head"]
        # (K, N, stored bytes) of each matmul a token goes through
        self.layer_mm: List[Tuple[int, int, int]] = [
            (K, N, packed_bytes(v, K, N)) for _, K, N, v, n in body
            for _ in range(n)]
        self.head_mm = [(K, N, packed_bytes(v, K, N))
                        for _, K, N, v, _ in head]
        self.layer_params = sum(K * N for K, N, _ in self.layer_mm)
        self.head_params = sum(K * N for K, N, _ in self.head_mm)

    def attn_pair_flops(self) -> float:
        return 4.0 * self.L * self.H * self.Dh

    # -- model FLOPs --------------------------------------------------------
    def prefill_flops(self, prompt_len: int) -> float:
        """One prompt: every token through the layers, causal attention
        over the live prefix, the LM head on the last token only."""
        S = prompt_len
        return (2.0 * self.layer_params * S + 2.0 * self.head_params
                + self.attn_pair_flops() * S * (S + 1) / 2.0)

    def decode_flops(self, context: int) -> float:
        """One decoded token whose query sees ``context`` positions."""
        return (2.0 * (self.layer_params + self.head_params)
                + self.attn_pair_flops() * context)

    # -- bfp_matmul: least time of a batch of projections --------------------
    @staticmethod
    def least_s(mms, rows: float, peak_flops: float,
                peak_bw: float) -> float:
        """Least device time of ``rows`` activation rows through the
        matmuls ``mms``, each weight read once: per matmul the larger of
        FLOPs over peak and bytes over bandwidth."""
        t = 0.0
        for K, N, wbytes in mms:
            flops = 2.0 * rows * K * N
            nbytes = wbytes + rows * (K + N) * ACT_BYTES
            t += max(flops / peak_flops, nbytes / peak_bw)
        return t
