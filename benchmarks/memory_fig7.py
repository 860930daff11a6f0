"""Fig. 7 reproduction: Separate Quantization's memory/accuracy vs m.

Two claims: (1) growing m adds only negligible memory (group offsets +
offset coefficients) at fixed FINAL storage bit-width; (2) at ultra-low
final bits (2-bit, 1-bit storage), accuracy improves dramatically with m
because code resolution is k = final_bits + log2(m). Recomputed for TPU
v5e HBM (16 GiB/chip) instead of the paper's V100/A100.
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import csv_row, get_models, task_accuracy
from repro.core import DeltaDQSpec, compress
from repro.core.pack import PackedDelta, to_storage_parts
from repro.utils import flatten_with_paths

V5E_HBM = 16 * 2**30


def storage_bytes(deltas) -> tuple[float, float]:
    """(paper-convention value bytes, honest bytes incl indices+offsets)."""
    vals = honest = 0.0
    flat = flatten_with_paths(deltas, is_leaf=lambda x: isinstance(x, PackedDelta))
    for d in flat.values():
        if d is None:
            continue
        import numpy as np
        stack = int(np.prod(d.stack_shape())) if d.stack_shape() else 1
        vals += d.value_bits() * stack / 8
        honest += (d.value_bits() + d.index_bits()) * stack / 8
        # group offsets: one int per (group,col) per part (paper's CSR rows)
        honest += d.m * d.n_groups * d.h_out * stack * 4 / 64  # amortized 64-entry offsets
    return vals, honest


def main():
    t0 = time.time()
    cfg, base, ft = get_models()
    alpha = 8.0

    print("final_bits,m,k_codes,ratio,value_bytes,honest_bytes,accuracy")
    rows = {}
    # fixed FINAL storage bits, growing m -> k = bits + log2(m) resolution
    for final_bits in (2, 1):
        for m in (1, 2, 4, 8):
            import math
            k = final_bits + int(math.log2(m))
            if k > 8:
                continue
            spec = DeltaDQSpec(alpha=alpha, k_bits=k, m=m, h_g=64)
            deltas, _ = compress(base, ft, spec)
            vb, hb = storage_bytes(deltas)
            acc = task_accuracy(cfg, base, deltas=deltas, n_batches=2)
            rows[(final_bits, m)] = (vb, acc)
            print(f"{final_bits},{m},{k},{spec.ratio():.0f},{vb:.0f},{hb:.0f},{acc:.3f}")

    # memory constant in m at fixed final bits; accuracy grows with m
    (v1, a1), (v8, a8) = rows[(1, 1)], rows[(1, 8)]
    us = (time.time() - t0) * 1e6
    csv_row("memory_fig7", us,
            f"mem_growth_m8={v8 / v1:.3f}x;acc_1bit_m1={a1:.3f};acc_1bit_m8={a8:.3f}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
