"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus each module's own
detail rows prefixed by their table).
    PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""
import argparse
import sys
import traceback

MODULES = [
    "fig4_balanced",
    "table1_basic",
    "table23_ultra",
    "table4_groupsearch",
    "fig5_groupsize",
    "memory_fig7",
    "serve_bench",
    "roofline_report",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    mods = [args.only] if args.only else MODULES
    failures = []
    for name in mods:
        print(f"\n===== {name} =====", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED: {failures}", file=sys.stderr)
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == '__main__':
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
