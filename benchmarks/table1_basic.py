"""Table 1 reproduction: accuracy vs baselines at 2/4/8/16x compression.

Protocol mirrors the paper at tiny scale: the REAL SFT delta of the bench
model is compressed by each method at each ratio; exact-match task accuracy
is measured through the serving engine. DeltaDQ uses Group-wise Dropout
(h_g from the proxy search) for 2-8x and adds quantization at 16x, exactly
like the paper.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import csv_row, get_models, layer_l2, task_accuracy
from repro.core import DeltaDQSpec, baselines, compress
from repro.core.pack import PackedDelta
from repro.utils import flatten_with_paths, map_with_paths


def compress_with_baseline(base, ft, method: str, alpha: float, rng):
    """Dense-compressed delta trees for baseline methods (uniform API)."""
    from repro.core.compress import is_compressible
    import jax.numpy as jnp

    def fn(path, b, f):
        if not is_compressible(path, b):
            return None
        d = f.astype(jnp.float32) - b.astype(jnp.float32)
        lead = d.shape[:-2]
        flatd = d.reshape((-1, *d.shape[-2:]))
        outs = [baselines.METHODS[method](jax.random.fold_in(rng, i), flatd[i], alpha=alpha)
                for i in range(flatd.shape[0])] if lead else \
               [baselines.METHODS[method](rng, d, alpha=alpha)]
        out = jnp.stack(outs).reshape(d.shape) if lead else outs[0]
        return out

    return map_with_paths(fn, base, ft)


def apply_dense_delta(base, dense_deltas):
    import jax.numpy as jnp
    return map_with_paths(
        lambda p, b, d: b if d is None else (b.astype(jnp.float32) + d).astype(b.dtype),
        base, dense_deltas)


DELTADQ_BY_ALPHA = {
    2: DeltaDQSpec(alpha=2.0, k_bits=None),
    4: DeltaDQSpec(alpha=4.0, k_bits=None),
    8: DeltaDQSpec(alpha=8.0, k_bits=None),
    16: DeltaDQSpec(alpha=8.0, k_bits=8, m=1),   # paper: quantization at 16x
}


def pick_hg(cfg, base, ft, spec):
    """Proxy search on layer-1 Q/K (paper §3.3)."""
    import jax.numpy as jnp
    from repro.core import search_proxy
    from repro.models import lm as lmod
    from benchmarks.common import task
    batch = task().batch_at(0)
    x = lmod.embed_tokens(cfg, base, jnp.asarray(batch["tokens"][:2])).reshape(-1, cfg.d_model)
    res = search_proxy(x.astype(jnp.float32),
                       base["attn"]["wq"][0].astype(jnp.float32),
                       base["attn"]["wk"][0].astype(jnp.float32),
                       ft["attn"]["wq"][0].astype(jnp.float32),
                       ft["attn"]["wk"][0].astype(jnp.float32), spec)
    return res.h_g_star


def main():
    t0 = time.time()
    cfg, base, ft = get_models()
    rng = jax.random.PRNGKey(0)
    acc_orig = task_accuracy(cfg, ft)
    acc_base = task_accuracy(cfg, base)
    print(f"# original(ft) acc={acc_orig:.3f}  raw base acc={acc_base:.3f}")
    print("method,ratio,accuracy,layer_l2")

    rows = {}
    for alpha in (2, 4, 8, 16):
        spec = DELTADQ_BY_ALPHA[alpha]
        hg = pick_hg(cfg, base, ft, spec)
        spec = DeltaDQSpec(alpha=spec.alpha, k_bits=spec.k_bits, m=spec.m, h_g=hg)
        deltas, rep = compress(base, ft, spec)
        acc = task_accuracy(cfg, base, deltas=deltas)
        l2 = layer_l2(cfg, base, ft, deltas)
        rows[("deltadq", alpha)] = acc
        print(f"DeltaDQ(h_g={hg}),{alpha},{acc:.3f},{l2:.3e}")

        for method in ("magnitude", "dare", "deltazip"):
            dd = compress_with_baseline(base, ft, method, float(alpha), rng)
            merged = apply_dense_delta(base, dd)
            acc_m = task_accuracy(cfg, merged)
            rows[(method, alpha)] = acc_m
            print(f"{method},{alpha},{acc_m:.3f},-")

    us = (time.time() - t0) * 1e6
    win16 = rows[("deltadq", 16)] - max(rows[(m, 16)] for m in ("magnitude", "dare", "deltazip"))
    csv_row("table1_basic", us,
            f"acc_orig={acc_orig:.3f};deltadq16x={rows[('deltadq', 16)]:.3f};margin16x={win16:+.3f}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
