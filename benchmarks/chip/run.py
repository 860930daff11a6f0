"""Chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload phi3m.decode-mixed \
        --seed 7 --seconds 51 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for; off a TPU it exits non-zero and prints no result. The
last line of standard output is the result object; the compared numbers,
each beside its limit, are the last lines of standard error.
"""
import os
import sys
import time

T_PROC0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

if __name__ == "__main__":
    from chipbench.harness import main
    sys.exit(main(t_proc0=T_PROC0))
