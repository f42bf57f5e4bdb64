"""What a per-layer metric reader gets: the traced window's requests,
the engine's counters at the window's edges, the reduced device trace,
the cell's counts and the device peaks. Each reader in ``bench/metrics``
takes a ``Context`` and returns a number, or None where it finds
nothing to read."""
from __future__ import annotations

import math
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from harness import tracing

# program names in the trace (jit of the engine's bound methods)
PREFILL_PROGRAM = r"_prefill_chunk_impl"
DECODE_PROGRAM = r"_decode_chunk_impl"
# HLO opcode of the Pallas bfp_matmul kernel's op events: the serving path
# runs no other Pallas kernel and no other custom call
BFP_KERNEL = "custom-call"


class Context:
    def __init__(self, cell, counts, peaks: Dict, records, stats: List[Dict],
                 edges: List[float], trace_dir: Path, n_devices: int):
        self.cell, self.counts, self.peaks = cell, counts, peaks
        self.records = records
        self.stats0, self.stats1 = stats
        self.t0, self.t1 = edges
        self.prof = tracing.load(tracing.xplane_file(trace_dir), n_devices)
        self.dev = self.prof.devices[0]
        self.window = self.prof.window

    # -- host side: what the engine did inside the traced window ------------
    def delta(self, key: str) -> float:
        return self.stats1[key] - self.stats0[key]

    def admitted(self):
        """Requests whose prefill ran inside the window."""
        return [r for r in self.records if r.queue_wait_s is not None
                and self.t0 <= r.due + r.queue_wait_s < self.t1]

    def prefill_groups(self) -> List[List[int]]:
        """Prompt lengths of each prefill group run in the window (one
        admission call stamps one start time on all its requests)."""
        groups: Dict[float, List[int]] = {}
        for r in self.admitted():
            key = round(r.due + r.queue_wait_s, 6)
            groups.setdefault(key, []).append(len(r.arrival.prompt))
        return list(groups.values())

    def decode_chunks(self) -> List[Tuple[int, int]]:
        """(steps, live rows summed over the steps) of each decode chunk
        run in the window: a chunk runs as many steps as its busiest slot
        emitted tokens."""
        lo, hi = self.stats0["chunks"], self.stats1["chunks"]
        per: Dict[int, Dict[int, int]] = {}
        for r in self.records:
            for i, c in enumerate(r.tok_chunk):
                if i > 0 and lo < c <= hi:
                    per.setdefault(c, {}).setdefault(r.rid, 0)
                    per[c][r.rid] += 1
        return [(max(v.values()), sum(v.values())) for v in per.values()]

    def decode_contexts(self) -> List[int]:
        """Positions each decode query in the window attends."""
        lo, hi = self.stats0["chunks"], self.stats1["chunks"]
        out = []
        for r in self.records:
            P = len(r.arrival.prompt)
            out += [P + i for i, c in enumerate(r.tok_chunk)
                    if i > 0 and lo < c <= hi]
        return out

    # -- device side -----------------------------------------------------------
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        return float(np.mean([tracing.busy_s(d, self.window)
                              for d in self.prof.devices]))

    def program_s(self, pattern: str) -> float:
        return tracing.module_s(self.dev, self.window, pattern)

    def kernel_s(self, op_pattern: str, program: str) -> float:
        return tracing.op_s(self.dev, self.window, op_pattern, program)

    def breakdown(self) -> Dict:
        return {"device_ops": tracing.top_ops(self.dev, self.window),
                "idle_gaps": tracing.top_gaps(self.prof, self.dev)}


def share(num: float, den: float):
    """A share in percent, or None where the denominator is empty."""
    return 100.0 * num / den if den > 0 and math.isfinite(num) else None
