"""Per-codec compression benchmark on wizard-llama2-7b (smoke) shapes.

For every registered delta codec, compresses the same synthetic
(base, ft) pair and records:

* ``ratio_paper`` / ``ratio_honest`` — storage accounting (deterministic,
  compared EXACTLY by ``--check``),
* ``rel_error`` — mean relative Frobenius reconstruction error over the
  compressed leaves (deterministic given the pinned seeds),
* ``decode_us`` — wall-clock of the XLA fallback correction at a
  decode-sized token count on the largest compressed leaf's RUNTIME form
  (every codec serves through the same PackedDelta machinery, so this is
  the per-codec serving cost, not a format-specific path),

plus an ``auto`` row (``codec="auto"``, the default 2.0 bits/element
budget) that must report ``budget_met`` — the auto-picker provably fits
the budget on this config.

Writes ``BENCH_compress.json`` at the repo root. CI regression gate::

    python -m benchmarks.compress_bench --out BENCH_compress.fresh.json \
        --check BENCH_compress.json --tolerance 3.0

Ratios gate exactly; ``rel_error`` may not grow past 1.05x the baseline
(it is deterministic — the headroom only covers BLAS/libm drift across
runner images); ``decode_us`` gates at the wall-clock tolerance.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.configs import get_smoke_config
from repro.core import DeltaDQSpec, compress
from repro.core.codecs import (
    BitDeltaSpec,
    LowRankSpec,
    codec_of_leaf,
    is_codec_leaf,
    reconstruct_dense_any,
    runtime_packed_leaf,
)
from repro.kernels import fallback
from repro.models import lm
from repro.utils import flatten_with_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one spec per codec: DeltaDQ at the launcher's 128x deployment point
CODEC_SPECS = {
    "deltadq": DeltaDQSpec(alpha=8.0, k_bits=4, m=8, h_g=16),
    "bitdelta": BitDeltaSpec(),
    "lowrank": LowRankSpec(rank=8, k_bits=4),
}
AUTO_BUDGET_BITS = 2.0
DECODE_T = 4                       # decode-sized token count


def _models():
    cfg = get_smoke_config("wizard-llama2-7b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    ft = jax.tree.map(
        lambda p: p + 0.02 * jax.random.normal(
            jax.random.PRNGKey(1), p.shape, jnp.float32).astype(p.dtype)
        if p.ndim >= 2 else p, base)
    return cfg, base, ft


def _time_decode(leaf) -> float:
    """us per fallback correction call on the leaf's runtime form."""
    d = runtime_packed_leaf(leaf)
    if d.stack_shape():
        d = d.index(0)
    x = jax.random.normal(jax.random.PRNGKey(2), (DECODE_T, d.h_in))
    fn = jax.jit(lambda x: fallback.correction_nd(x, d))
    jax.block_until_ready(fn(x))   # compile
    t0 = time.perf_counter()
    n = 50
    for _ in range(n):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def _rel_error(base, ft, deltas) -> float:
    fb = flatten_with_paths(base)
    ff = flatten_with_paths(ft)
    fd = flatten_with_paths(deltas, is_leaf=is_codec_leaf)
    errs = []
    for k, d in fd.items():
        if d is None:
            continue
        delta = np.asarray(ff[k], np.float32) - np.asarray(fb[k], np.float32)
        recon = np.asarray(reconstruct_dense_any(d), np.float32)
        errs.append(float(np.linalg.norm(recon - delta))
                    / max(float(np.linalg.norm(delta)), 1e-12))
    return float(np.mean(errs))


def _largest_leaf(deltas):
    leaves = [l for l in jax.tree.leaves(deltas, is_leaf=is_codec_leaf)
              if is_codec_leaf(l)]
    return max(leaves, key=lambda l: l.h_in * l.h_out)


def codec_row(name: str, base, ft) -> dict:
    deltas, report = compress(base, ft, CODEC_SPECS[name])
    row = {
        "codec": name,
        "spec": repr(CODEC_SPECS[name]),
        "n_compressed": report.n_compressed,
        "ratio_paper": report.ratio_paper,
        "ratio_honest": report.ratio_honest,
        "rel_error": _rel_error(base, ft, deltas),
        "decode_us": _time_decode(_largest_leaf(deltas)),
    }
    print(f"{name}: paper {row['ratio_paper']:.1f}x honest "
          f"{row['ratio_honest']:.1f}x rel_err {row['rel_error']:.3f} "
          f"decode {row['decode_us']:.0f}us")
    return row


def auto_row(base, ft) -> dict:
    deltas, report = compress(base, ft, codec="auto",
                              budget_bits=AUTO_BUDGET_BITS)
    picks: dict[str, int] = {}
    for ch in report.auto_choices.values():
        picks[ch["codec"]] = picks.get(ch["codec"], 0) + 1
    row = {
        "budget_bits": AUTO_BUDGET_BITS,
        "budget_met": report.budget_met,
        "ratio_honest": report.ratio_honest,
        "rel_error": _rel_error(base, ft, deltas),
        "picks": picks,
        "max_bits_per_element": max(
            ch["bits_per_element"] for ch in report.auto_choices.values()),
    }
    print(f"auto(budget={AUTO_BUDGET_BITS}): met={row['budget_met']} "
          f"honest {row['ratio_honest']:.1f}x picks={picks}")
    return row


def compare_against(fresh: dict, baseline_path: str, tolerance: float) -> list:
    with open(baseline_path) as f:
        baseline = json.load(f)
    fails = []
    base_rows = {r["codec"]: r for r in baseline.get("codecs", [])}
    for r in fresh.get("codecs", []):
        b = base_rows.get(r["codec"])
        if not b or b.get("spec") != r.get("spec"):
            continue
        for key in ("ratio_paper", "ratio_honest"):
            if abs(r[key] - b[key]) > 1e-6:
                fails.append(f"{r['codec']} {key} {r[key]:.4f} != "
                             f"baseline {b[key]:.4f} (exact gate)")
        if r["rel_error"] > b["rel_error"] * 1.05:
            fails.append(f"{r['codec']} rel_error {r['rel_error']:.4f} > "
                         f"1.05x baseline {b['rel_error']:.4f}")
        if r["decode_us"] > b["decode_us"] * tolerance:
            fails.append(f"{r['codec']} decode_us {r['decode_us']:.0f} > "
                         f"{tolerance}x baseline {b['decode_us']:.0f}")
    auto = fresh.get("auto")
    if auto and not auto.get("budget_met"):
        fails.append(f"auto-picker failed its {auto.get('budget_bits')} "
                     f"bits/element budget (max "
                     f"{auto.get('max_bits_per_element'):.2f})")
    return fails


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_compress.json"))
    ap.add_argument("--check", default=None, metavar="BASELINE_JSON",
                    help="fail (exit 1) on regression vs this baseline")
    ap.add_argument("--tolerance", type=float, default=3.0,
                    help="wall-clock tolerance for decode_us")
    args = ap.parse_args()

    cfg, base, ft = _models()
    report = {"arch": cfg.name,
              "codecs": [codec_row(n, base, ft) for n in sorted(CODEC_SPECS)],
              "auto": auto_row(base, ft)}

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {args.out}")

    dq = next(r for r in report["codecs"] if r["codec"] == "deltadq")
    csv_row("compress_bench", dq["decode_us"],
            f"deltadq_honest={dq['ratio_honest']:.1f}x;"
            f"auto_met={report['auto']['budget_met']}")

    if args.check:
        fails = compare_against(report, args.check, args.tolerance)
        if fails:
            for f_ in fails:
                print(f"REGRESSION: {f_}", file=sys.stderr)
            sys.exit(1)
        print(f"# compress bench regression check vs {args.check}: OK")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
