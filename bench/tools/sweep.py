"""Find a cell's knee once, on the chip: serve the cell's traffic at
several offered rates in one process and report, per rate, the rates
achieved, the latency tails and whether the backlog grew over the window
(the queue wait's slope against due time, and the requests due in the
window still queued when it closed).

    python3 bench/tools/sweep.py --workload <cell> --rates 2,4,6,8 \
        --seconds 20 --out chiprun_out/sweep.jsonl
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def backlog(records, w0: float, w1: float) -> dict:
    win = [r for r in records if r.in_window and r.queue_wait_s is not None]
    due = np.array([r.due - w0 for r in win])
    wait = np.array([r.queue_wait_s for r in win])
    slope = float(np.polyfit(due, wait, 1)[0]) if len(win) > 2 else 0.0
    waiting = sum(1 for r in records if r.in_window
                  and (r.queue_wait_s is None or r.due + r.queue_wait_s > w1))
    return {"queue_wait_slope_s_per_s": slope,
            "queue_wait_p50_ms": float(np.median(wait)) * 1e3,
            "queue_wait_max_ms": float(wait.max()) * 1e3,
            "queued_at_close": waiting}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default="chiprun_out/sweep.jsonl")
    args = ap.parse_args()

    from harness import runner

    ses = runner.Session(args.workload)
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    with open(out_path, "a") as out:
        for rate in [float(x) for x in args.rates.split(",")]:
            res = ses.run(args.seed, args.seconds, False, T_START,
                          rate=rate, keep_engine=True)
            row = {"workload": args.workload, "rate_rps": rate,
                   "correct": res["correct"], "attempted": res["attempted"],
                   **{k: v["value"] for k, v in res["metrics"].items()},
                   **backlog(*ses.last)}
            print(json.dumps(row), flush=True)
            print(json.dumps(row), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
