"""Readings that a cell's correctness limit is set from, on the chip, in
one process: the program's widest logit gap on each of many seeds, and
the control's (the float8 reference in the program's place) on some of
them, each over a short window at the cell's own load. The control's
``correct`` is the cell's own verdict on its numbers: it has to be false.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 101-112 \
        --control 101,102,103 --seconds 5 --out chiprun_out/cal.jsonl
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def seed_list(text: str):
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default="chiprun_out/calibrate.jsonl")
    args = ap.parse_args()

    from harness import runner

    ses = runner.Session(args.workload)
    control = set(seed_list(args.control))
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    with open(out_path, "a") as out:
        for seed in seed_list(args.seeds):
            t = time.perf_counter()
            res = ses.run(seed, args.seconds, False, T_START,
                          keep_engine=True, control=seed in control)
            row = {"workload": args.workload, "seed": seed,
                   "correct": res["correct"], "failed": res["failed"],
                   "max_logit_gap": res["checks"]["max_logit_gap"]["value"],
                   "control_max_logit_gap": res.get("control_max_logit_gap"),
                   "control_correct": res.get("control_correct"),
                   "wall_s": time.perf_counter() - t}
            print(json.dumps(row), flush=True)
            print(json.dumps(row), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
