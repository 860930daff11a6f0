"""Fig. 5 reproduction: impact of group size on accuracy at fixed ratio.

Sweeps h_g for the bench model's SFT delta at alpha=8 and reports task
accuracy + the attention-proxy error per candidate. The paper's finding:
the optimum is an interior h_g* (smaller is not monotonically better),
unlike group-wise quantization.
"""
from __future__ import annotations

import time

import jax.numpy as jnp

from benchmarks.common import csv_row, get_models, task, task_accuracy
from repro.core import DeltaDQSpec, candidate_group_sizes, compress
from repro.core.groupsearch import attention_proxy_error
from repro.models import lm
import jax


def main():
    t0 = time.time()
    cfg, base, ft = get_models()
    alpha = 8.0
    batch = task().batch_at(0)
    x = lm.embed_tokens(cfg, base, jnp.asarray(batch["tokens"][:2])).reshape(-1, cfg.d_model)
    x = x.astype(jnp.float32)

    print("h_g,accuracy,proxy_error")
    accs = {}
    for hg in candidate_group_sizes(cfg.d_model, alpha):
        spec = DeltaDQSpec(alpha=alpha, k_bits=None, h_g=hg)
        deltas, _ = compress(base, ft, spec)
        acc = task_accuracy(cfg, base, deltas=deltas, n_batches=2)
        err = float(attention_proxy_error(
            x, base["attn"]["wq"][0].astype(jnp.float32),
            base["attn"]["wk"][0].astype(jnp.float32),
            ft["attn"]["wq"][0].astype(jnp.float32),
            ft["attn"]["wk"][0].astype(jnp.float32),
            hg, spec, jax.random.PRNGKey(hg)))
        accs[hg] = acc
        print(f"{hg},{acc:.3f},{err:.4e}")

    best = max(accs, key=accs.get)
    us = (time.time() - t0) * 1e6
    csv_row("fig5_groupsize", us, f"best_hg={best};spread={max(accs.values()) - min(accs.values()):.3f}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
