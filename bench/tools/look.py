"""Look at one trace by hand: run a cell traced, keep the trace, and
write what it holds (planes, lines, the ops with the most device time,
their programs and stats) to a text file.

    python3 bench/tools/look.py --workload <cell> --seed <n> --seconds <s> \
        --out chiprun_out/look.txt
"""
import argparse
import sys
import time
from collections import defaultdict
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def summarize(path: Path, out) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name}: {len(lines)} lines", file=out)
        for ln in lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r}: {len(evs)} events", file=out)
            if not evs:
                continue
            tot, cnt, ex = defaultdict(float), defaultdict(int), {}
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
                ex.setdefault(e.name, e)
            top = sorted(tot, key=lambda k: -tot[k])[:40]
            for k in top:
                e = ex[k]
                stats = {s: str(v)[:160] for s, v in dict(e.stats).items()}
                print(f"    {tot[k] * 1e-6:12.3f} ms x{cnt[k]:6d}  {k[:300]}"
                      f"  start={e.start_ns:.0f} {stats}", file=out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default="chiprun_out/look.txt")
    args = ap.parse_args()

    from harness import runner, tracing

    keep = BENCH.parent / runner.CACHE_DIR / "look"
    result = runner.run(args.workload, args.seed, args.seconds, True,
                        T_START, keep_trace=keep)
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    with open(out_path, "w") as out:
        print(result, file=out)
        summarize(tracing.xplane_file(keep), out)
    runner.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
