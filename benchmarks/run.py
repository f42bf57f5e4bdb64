"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Roofline terms for the full
(arch x shape) grid come from the dry-run artifacts (launch/dryrun.py);
benches here are self-contained CPU-runnable reproductions.
"""
import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig1_distribution, table2_kernel_resources,
                            table3_models, table4_speedup,
                            kernel_microbench, e2e_serve)
    print("name,us_per_call,derived")
    failed = 0
    for mod in (fig1_distribution, table2_kernel_resources, table3_models,
                table4_speedup, kernel_microbench, e2e_serve):
        try:
            mod.run()
        except Exception:
            failed += 1
            print(f"{mod.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
