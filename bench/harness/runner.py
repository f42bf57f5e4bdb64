"""One run of one cell: set-up, the measured window, the check, the
result line. ``run.py`` is its command line."""
from __future__ import annotations

import contextlib
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from harness import check, spec, traffic
from harness.counts import Counts
from harness.load import OpenLoop

CACHE_DIR = ".bench_cache"          # inside the checkout, fixed path


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_chip(chips: int):
    """The devices this cell runs on; exits without a result where JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        say(f"needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
        raise SystemExit(2)
    return devs[:chips]


def configure_jax(root: Path) -> None:
    """The compile cache and the TPU runtime's logs inside the checkout
    (fixed paths: the cache's path is part of its key). Runs before the
    first call that starts the TPU runtime."""
    import os

    import jax

    os.environ.setdefault("TPU_LOG_DIR", str(root / CACHE_DIR / "tpu_logs"))
    jax.config.update("jax_compilation_cache_dir",
                      str(root / CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts traces and backend compiles from JAX's monitoring events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


def percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else math.nan


def seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for the weights and the engine from any
    whole-number ``--seed``."""
    rng = np.random.default_rng(seed)
    w, e = rng.integers(0, 2 ** 31 - 1, 2)
    return {"weights": int(w), "engine": int(e)}


def end_to_end(records, w0: float, w1: float, setup_s: float,
               peak_bytes: int) -> Dict[str, float]:
    win = [r for r in records if r.in_window]
    inf = math.inf
    ttft = [(r.tok_t[0] - r.due) if r.tok_t else inf for r in win]
    tpot = [(r.tok_t[-1] - r.tok_t[0]) / (len(r.tok_t) - 1)
            if r.complete else inf
            for r in win if r.arrival.max_new > 1]
    out_tok = sum(1 for r in records for t in r.tok_t if w0 <= t < w1)
    return {
        "ttft_p95_ms": percentile(ttft, 95) * 1e3,
        "tpot_p95_ms": percentile(tpot, 95) * 1e3,
        "output_tok_s": out_tok / (w1 - w0),
        "peak_hbm_gb": peak_bytes / 1e9,
        "setup_s": setup_s,
    }


def peak_bytes(devs) -> int:
    stats = [d.memory_stats() or {} for d in devs]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class Window:
    """Called from every poll: marks the measured span's edges (times,
    the engine's counters, the compile count) and, in a traced run,
    starts and stops the profiler there. The span opens at the window's
    start and lasts ``span_s``: the whole window, or in a traced run its
    first ``trace_s`` seconds (writing a trace out stalls the host)."""

    def __init__(self, eng, counter: CompileCounter, span_s: float,
                 trace_dir: Optional[Path]):
        self.eng, self.counter, self.span_s = eng, counter, span_s
        self.trace_dir = trace_dir
        self.t: List[float] = []
        self.stats: List[Dict] = []
        self.compiles: List[int] = []
        self._ann = None

    def _mark(self) -> None:
        self.t.append(time.perf_counter())
        self.stats.append(dict(self.eng.stats))
        self.compiles.append(self.counter.n)

    def __call__(self, now: float, loop) -> None:
        import jax

        if not self.t and now >= loop.w0:
            if self.trace_dir is not None:
                jax.profiler.start_trace(str(self.trace_dir))
                self._ann = jax.profiler.TraceAnnotation("bench.window")
                self._ann.__enter__()
            self._mark()
        elif len(self.t) == 1 and now >= loop.w0 + self.span_s:
            self._mark()
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                jax.profiler.stop_trace()


class Session:
    """One cell's system under test in one process: devices, peaks, the
    program's config, the engine (built once, warmed once) and the
    compiled reference. ``run`` makes one run of it; a tool may make
    several, one per seed, without building the programs again."""

    def __init__(self, cell_name: str, root: Path = spec.ROOT,
                 bench_dir: Path = spec.BENCH, chips_ok=require_chip,
                 kind: Optional[str] = None):
        from harness import model

        self.root = root
        self.cell = spec.load_cell(cell_name, root, bench_dir)
        configure_jax(root)
        self.devs = chips_ok(self.cell.chips)
        self.kind = kind or self.devs[0].device_kind
        self.peaks = spec.peaks(self.kind, bench_dir)
        self.counter = CompileCounter()
        self.fam = self.cell.family()
        self.mc = model.model_config(self.cell)
        self.longest = traffic.longest_context(self.cell.traffic)
        if self.longest > self.cell.serve["cache_len"]:
            raise ValueError(f"the mix reaches {self.longest} positions; "
                             f"the cell's cache holds "
                             f"{self.cell.serve['cache_len']}")
        self.eng = None
        self.ref = None

    def _serve(self, weights, seed: int, arrivals) -> None:
        from harness import model

        params = model.program_params(weights)
        if self.eng is not None:
            self.eng.params = params            # same shapes: no compile
            return
        self.eng = model.make_engine(self.mc, params, self.cell,
                                     seeds(seed)["engine"])
        shapes = model.prefill_shapes(self.cell.serve,
                                      [len(a.prompt) for a in arrivals])
        model.warm_up(self.eng, shapes, self.cell.config["vocab_size"])
        say(f"{self.cell.name}: warmed {len(shapes)} prefill shapes "
            f"{sorted(shapes)}")

    def run(self, seed: int, seconds: float, trace: bool, t_start: float,
            rate: Optional[float] = None, keep_engine: bool = False,
            keep_trace: Optional[Path] = None,
            control: bool = False) -> Dict:
        import jax

        cell, st = self.cell, self.cell.settings
        rate = rate or st["rate_rps"]
        segs = [("preroll", st["preroll_s"]), ("window", seconds),
                ("tail", st["drain_s"] + 10.0)]
        arrivals = traffic.schedule(cell.traffic, rate, segs, seed,
                                    cell.config["vocab_size"])
        weights = self.fam.build_weights(
            cell.config, jax.random.PRNGKey(seeds(seed)["weights"]))
        jax.block_until_ready(weights)
        self._serve(weights, seed, arrivals)

        trace_dir = ((keep_trace or self.root / CACHE_DIR / "trace")
                     if trace else None)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        span_s = min(seconds, st["trace_s"]) if trace else seconds
        win = Window(self.eng, self.counter, span_s, trace_dir)
        span = ((lambda name: jax.profiler.TraceAnnotation(name)) if trace
                else (lambda name: contextlib.nullcontext()))
        loop = OpenLoop(self.eng, arrivals, st["preroll_s"], seconds,
                        st["drain_s"], on_poll=win, span=span)
        loop.run()
        if len(win.t) != 2:
            raise RuntimeError("the load ended before the window closed")
        w0, w1 = win.t
        peak = peak_bytes(self.devs)
        late = [r.submit - r.due for r in loop.records if r.in_window]
        say(f"{len(late)} requests due in the window at {rate} req/s; "
            f"compiles in window: {win.compiles[1] - win.compiles[0]}; "
            f"generator late p50 {percentile(late, 50) * 1e3:.3f} ms, "
            f"p95 {percentile(late, 95) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms")

        # the program's state goes before the reference runs
        loop.eng = win.eng = None
        if keep_engine:
            self.eng._cache = None              # the KV ring, rebuilt on use
        else:
            self.eng = None
        gc.collect()

        records = loop.records
        self.last = (records, w0, w1)
        window = [r for r in records if r.in_window]
        failed = sum(not r.complete for r in window)
        if self.ref is None:
            self.ref = check.Reference(self.fam, cell.config,
                                       -(-self.longest // 128) * 128,
                                       cell.traffic["output"]["max"])
        picked = check.sample(records, st["check"]["min_tokens"],
                              np.random.default_rng(seed))
        t_ref = time.perf_counter()
        numbers = {"max_logit_gap": (self.ref.widest_gap(weights, picked)
                                     if picked else math.inf),
                   "unanswered": float(failed)}
        limits = dict(st["check"]["limits"], unanswered=0.0)
        say(f"checked {len(picked)} requests, "
            f"{sum(len(r.tokens) for r in picked)} served tokens, "
            f"reference {time.perf_counter() - t_ref:.1f} s")

        device = {"platform": self.devs[0].platform, "kind": self.kind,
                  "count": len(self.devs), "memory_peak_bytes": peak}
        result = {"correct": check.verdict(numbers, limits),
                  "attempted": len(window), "failed": failed}
        if control:
            ctrl = dict(numbers, max_logit_gap=(
                self.ref.control_widest_gap(weights, picked)
                if picked else math.inf))
            result["control_max_logit_gap"] = ctrl["max_logit_gap"]
            result["control_correct"] = check.verdict(ctrl, limits)
        units = {m["name"]: m["unit"]
                 for m in cell.end_to_end + cell.per_layer}
        if not trace:
            vals = end_to_end(records, w0, w1, w0 - t_start, peak)
            metrics = {m["name"]: vals[m["name"]] for m in cell.end_to_end}
        else:
            from harness import layers

            ctx = layers.Context(cell, Counts(self.fam, cell.config),
                                 self.peaks, records, win.stats, win.t,
                                 trace_dir, len(self.devs))
            metrics = {}
            for m in cell.per_layer:
                v = cell.metric_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = v
            device["busy_s"] = ctx.busy_s()
            device["window_s"] = ctx.window_s()
            result["breakdown"] = ctx.breakdown()
            if keep_trace is None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = {k: {"value": _num(v), "unit": units[k]}
                             for k, v in metrics.items()}
        result["device"] = device
        result["checks"] = check.report(numbers, limits, say)
        return result


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, **kw) -> Dict:
    """One run of a cell in a fresh process."""
    opts = {k: kw.pop(k) for k in ("root", "bench_dir", "chips_ok", "kind")
            if k in kw}
    return Session(cell_name, **opts).run(seed, seconds, trace, t_start, **kw)


def _num(v: float):
    return v if math.isfinite(v) else None


def emit(result: Dict) -> None:
    print(json.dumps(result), flush=True)
