"""Tables 2-3 reproduction: ultra-high compression (32x..256x).

The paper's key result: pushing alpha alone (m=1) collapses accuracy, but
holding alpha at its safe value and growing m (Separate Quantization's
storage decomposition) keeps accuracy flat while the ratio multiplies —
DeltaDQ(m=8) at 128x == DeltaDQ(m=1) at 32x, while DARE/Magnitude/
DeltaZip degrade or die (paper Tables 2 and 3).
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import csv_row, get_models, task_accuracy
from benchmarks.table1_basic import apply_dense_delta, compress_with_baseline, pick_hg
from repro.core import DeltaDQSpec, compress

# ratio -> list of (label, spec); mirrors the paper's rows
ROWS = [
    (32, [("DeltaDQ(m=1)", DeltaDQSpec(alpha=8, k_bits=4, m=1))]),
    (64, [("DeltaDQ(m=1)", DeltaDQSpec(alpha=16, k_bits=4, m=1)),
          ("DeltaDQ(m=4)", DeltaDQSpec(alpha=8, k_bits=4, m=4))]),
    (128, [("DeltaDQ(m=1)", DeltaDQSpec(alpha=32, k_bits=4, m=1)),
           ("DeltaDQ(m=8)", DeltaDQSpec(alpha=8, k_bits=4, m=8))]),
]


def main():
    t0 = time.time()
    cfg, base, ft = get_models()
    rng = jax.random.PRNGKey(1)
    acc_orig = task_accuracy(cfg, ft)
    print(f"# original(ft) acc={acc_orig:.3f}")
    print("method,ratio,accuracy")

    flat_acc = {}
    for ratio, entries in ROWS:
        for label, spec in entries:
            hg = pick_hg(cfg, base, ft, spec)
            spec = DeltaDQSpec(alpha=spec.alpha, k_bits=spec.k_bits, m=spec.m, h_g=hg)
            assert abs(spec.ratio() - ratio) < 1e-6, (spec, ratio)
            deltas, _ = compress(base, ft, spec)
            acc = task_accuracy(cfg, base, deltas=deltas)
            flat_acc[(label, ratio)] = acc
            print(f"{label},{ratio},{acc:.3f}")
        for method in ("magnitude", "dare", "deltazip"):
            dd = compress_with_baseline(base, ft, method, float(ratio), rng)
            acc = task_accuracy(cfg, apply_dense_delta(base, dd))
            flat_acc[(method, ratio)] = acc
            print(f"{method},{ratio},{acc:.3f}")

    # the paper's signature pattern: m>1 at 128x matches m=1 at 32x
    a32 = flat_acc[("DeltaDQ(m=1)", 32)]
    a128m8 = flat_acc[("DeltaDQ(m=8)", 128)]
    us = (time.time() - t0) * 1e6
    csv_row("table23_ultra", us,
            f"acc32x={a32:.3f};acc128x_m8={a128m8:.3f};identical={abs(a32 - a128m8) < 1e-9}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
