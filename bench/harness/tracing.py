"""The profiler trace of a traced window, reduced to what the per-layer
metrics read: device op and program intervals, the benchmark's own host
spans, busy and idle time, and time by program and by kernel.

Read with ``jax.profiler.ProfileData``; all events of one trace share one
clock (nanoseconds). A device plane's "XLA Ops" line holds the ops that
ran on it and its "XLA Modules" line the compiled programs around them.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float                    # ns
    end: float                      # ns
    module: str = ""                # program the op ran in
    opcode: str = ""                # HLO opcode of a device op

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Profile:
    devices: List[Device]
    spans: List[Event]              # host spans named bench.*
    window: Tuple[float, float]     # the bench.window span


def xplane_file(logdir: Path) -> Path:
    files = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


# a device op's event name is its HLO instruction:
#   "%closed_call.44 = f32[32,2048]{...} custom-call(bf16[...] ...), ..."
_HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def _op_events(line) -> List[Event]:
    """Device ops named by their instruction (numeric suffix folded),
    with their HLO opcode (e.g. custom-call for a Pallas kernel)."""
    out = []
    for e in line.events:
        full = e.name
        m, c = _HLO_NAME.match(full), _HLO_OPCODE.search(full)
        name = re.sub(r"\.\d+$", "", m.group(1)) if m else full[:80]
        opcode = c.group(1) if (m and c) else ""
        out.append(Event(name, e.start_ns, e.start_ns + e.duration_ns,
                         opcode=opcode))
    return out


def _module_base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def load(path: Path, device_count: int) -> Profile:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(str(path)), device_count)


def from_profile(pd, device_count: int) -> Profile:
    """Device planes ("/device:*" with an "XLA Ops" line), the first
    ``device_count`` of them by name, and the bench.* host spans."""
    devices, spans = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            ops = sorted(_op_events(lines[OPS_LINE]), key=lambda e: e.start)
            mods = (sorted(_events(lines[MODULES_LINE]),
                           key=lambda e: e.start)
                    if MODULES_LINE in lines else [])
            _assign_modules(ops, mods)
            devices.append(Device(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in _events(ln)
                          if e.name.startswith(SPAN_PREFIX)]
    devices = sorted(devices, key=lambda d: d.name)[:device_count]
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not devices or not win:
        raise ValueError(f"the trace has no device ops or no "
                         f"{WINDOW_SPAN} span")
    return Profile(devices, spans, (win[0].start, win[0].end))


def _assign_modules(ops: List[Event], mods: List[Event]) -> None:
    starts = [m.start for m in mods]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and mods[i].end >= op.start:
            op.module = _module_base(mods[i].name)


def clip(events: List[Event], lo: float, hi: float) -> List[Tuple]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_s(dev: Device, window: Tuple[float, float]) -> float:
    """Seconds of the window in which some op ran on the device."""
    return sum(b - a for a, b in union(clip(dev.ops, *window))) * 1e-9


def idle_gaps(dev: Device, window: Tuple[float, float]) -> List[Tuple]:
    """(start, end) of every stretch of the window with no op running."""
    lo, hi = window
    gaps, t = [], lo
    for a, b in union(clip(dev.ops, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_activity(spans: List[Event], a: float, b: float) -> str:
    """The innermost bench.* span covering most of [a, b]; "engine"
    (the program's own host code) where none does."""
    best, best_len = "engine", float("inf")
    for s in spans:
        if s.name in (WINDOW_SPAN, "bench.engine_run"):
            continue
        cover = min(s.end, b) - max(s.start, a)
        if cover > 0.5 * (b - a) and s.dur < best_len:
            best, best_len = s.name, s.dur
    return best


def module_s(dev: Device, window, pattern: str) -> float:
    """Seconds of programs whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(b - a for a, b in clip(
        [m for m in dev.modules if rx.search(m.name)], *window)) * 1e-9


def op_s(dev: Device, window, opcode: str,
         module_pattern: Optional[str] = None) -> float:
    """Seconds of ops with HLO opcode ``opcode``, in programs matching
    ``module_pattern`` when given."""
    mx = re.compile(module_pattern) if module_pattern else None
    sel = [e for e in dev.ops if e.opcode == opcode
           and (mx is None or mx.search(e.module))]
    return sum(b - a for a, b in clip(sel, *window)) * 1e-9


CONTAINERS = ("while", "conditional", "call")


def top_ops(dev: Device, window, n: int = 10) -> List[List]:
    """The n ops (by instruction name, numeric suffix folded, with their
    opcode and program) with the most device time; loops and calls that
    hold other ops are left out."""
    tot: Dict[str, float] = {}
    for e in dev.ops:
        a, b = max(e.start, window[0]), min(e.end, window[1])
        if b > a and e.opcode not in CONTAINERS:
            key = f"{e.module}/{e.name} ({e.opcode})"
            tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(prof: Profile, dev: Device, n: int = 10) -> List[List]:
    """Idle time by what the host was doing, largest first."""
    tot: Dict[str, float] = {}
    for a, b in idle_gaps(dev, prof.window):
        k = host_activity(prof.spans, a, b)
        tot[k] = tot.get(k, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
