"""Fig. 4 reproduction: Balanced Intermediate Results.

For each output element a_pq = sum_j x_pj * w_qj, compare the variance and
min-max range of the per-j intermediate products for the DELTA weight vs
the FINE-TUNED weight. The paper's observation: delta products are orders
of magnitude more balanced — the property that makes random dropping
near-lossless.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, get_models, task
from repro.models import lm
from repro.utils import flatten_with_paths


def intermediate_stats(x: jnp.ndarray, w: jnp.ndarray, n_out: int = 32):
    """x [t, h_in]; w [h_in, h_out] -> per-(p,q) variance and range of the
    h_in intermediate products, averaged."""
    prods = x[:, :, None] * w[None, :, :n_out]        # [t, h_in, n_out]
    var = jnp.var(prods, axis=1)
    rng = jnp.max(prods, axis=1) - jnp.min(prods, axis=1)
    return float(jnp.mean(var)), float(jnp.mean(rng))


def main():
    t0 = time.time()
    cfg, base, ft = get_models()
    fb = flatten_with_paths(base)
    ff = flatten_with_paths(ft)
    batch = task().batch_at(0)
    x = lm.embed_tokens(cfg, base, jnp.asarray(batch["tokens"][:2])).reshape(-1, cfg.d_model)
    x = x.astype(jnp.float32)

    print("layer,var_ft,var_delta,range_ft,range_delta,var_ratio,range_ratio")
    ratios = []
    for key in ("attn/wq", "attn/wk", "mlp/wi"):
        wf = ff[key][0].astype(jnp.float32)           # layer 0
        wb = fb[key][0].astype(jnp.float32)
        d = wf - wb
        v_ft, r_ft = intermediate_stats(x, wf)
        v_d, r_d = intermediate_stats(x, d)
        ratios.append(v_ft / max(v_d, 1e-20))
        print(f"{key},{v_ft:.3e},{v_d:.3e},{r_ft:.3e},{r_d:.3e},"
              f"{v_ft / max(v_d, 1e-20):.1f},{r_ft / max(r_d, 1e-20):.1f}")

    us = (time.time() - t0) * 1e6
    csv_row("fig4_balanced", us, f"median_var_ratio={np.median(ratios):.1f}x")
    assert np.median(ratios) > 3, "delta products should be more balanced"


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
