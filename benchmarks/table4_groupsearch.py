"""Table 4 reproduction: group-size selection — Direct vs Proxy.

Direct: compress the whole model at each candidate h_g and score full task
accuracy. Proxy: layer-1 attention error on ~1% calibration data (Eq. 5).
The paper's claim: proxy finds the same h_g* ~3x faster.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, get_models, task, task_accuracy
from repro.core import DeltaDQSpec, candidate_group_sizes, compress, search_direct, search_proxy
from repro.models import lm


def main():
    cfg, base, ft = get_models()
    batch = task().batch_at(0)
    x = lm.embed_tokens(cfg, base, jnp.asarray(batch["tokens"][:1])).reshape(-1, cfg.d_model)

    print("alpha,method,seconds,h_g_star")
    results = {}
    for alpha in (2, 4, 8):
        spec = DeltaDQSpec(alpha=float(alpha), k_bits=None)

        t0 = time.time()

        def direct_score(hg):
            s = DeltaDQSpec(alpha=float(alpha), k_bits=None, h_g=hg)
            deltas, _ = compress(base, ft, s)
            return -task_accuracy(cfg, base, deltas=deltas, n_batches=1)

        direct = search_direct(direct_score, cfg.d_model, spec)
        t_direct = time.time() - t0

        proxy = search_proxy(x.astype(jnp.float32),
                             base["attn"]["wq"][0].astype(jnp.float32),
                             base["attn"]["wk"][0].astype(jnp.float32),
                             ft["attn"]["wq"][0].astype(jnp.float32),
                             ft["attn"]["wk"][0].astype(jnp.float32), spec)
        print(f"{alpha},direct,{t_direct:.2f},{direct.h_g_star}")
        print(f"{alpha},proxy,{proxy.seconds:.2f},{proxy.h_g_star}")
        results[alpha] = (t_direct, proxy.seconds, direct.h_g_star, proxy.h_g_star)

    speedups = [d / max(p, 1e-9) for d, p, *_ in results.values()]
    us = sum(d + p for d, p, *_ in results.values()) * 1e6
    csv_row("table4_groupsearch", us,
            f"median_speedup={sorted(speedups)[1]:.1f}x;"
            f"agree={sum(int(a == b) for *_, a, b in results.values())}/3")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
