"""Aggregate results/dryrun/*.json into the §Roofline table (markdown+CSV)."""
from __future__ import annotations

import glob
import json
import os
import time

from benchmarks.common import RESULTS, csv_row

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load_cells(mesh: str = "16x16"):
    """Post-perf-pass cells (results/dryrun2) preferred; cells whose v2
    recompile did not finish fall back to the v1 baseline (marked)."""
    cells = {}
    for p in sorted(glob.glob(os.path.join(RESULTS, "dryrun", f"*__{mesh}.json"))):
        with open(p) as f:
            d = json.load(f)
        d["_version"] = "v1-baseline"
        cells[(d["arch"], d["shape"])] = d
    for p in sorted(glob.glob(os.path.join(RESULTS, "dryrun2", f"*__{mesh}.json"))):
        with open(p) as f:
            d = json.load(f)
        d["_version"] = "v2"
        cells[(d["arch"], d["shape"])] = d
    return cells


def fmt_row(d: dict) -> str:
    if d.get("skip_reason"):
        return f"| {d['arch']} | {d['shape']} | skip | — | — | — | — | — | {d['skip_reason']} |"
    if not d.get("ok"):
        return f"| {d['arch']} | {d['shape']} | FAIL | — | — | — | — | — | {str(d.get('error'))[:60]} |"
    r = d["roofline"]
    note = f"mem_frac={r.get('memory_frac'):.2f}" if r.get("memory_frac") is not None else "—"
    if d.get("_version") == "v1-baseline":
        note += " (v1 baseline)"
    return ("| {arch} | {shape} | {bn} | {tc:.2e} | {tm:.2e} | {tl:.2e} | "
            "{uf:.2f} | {rf:.3f} | {note} |").format(
        arch=d["arch"], shape=d["shape"], bn=r["bottleneck"],
        tc=r["t_compute_s"], tm=r["t_memory_s"], tl=r["t_collective_s"],
        uf=r["useful_flops_frac"], rf=r["roofline_frac"], note=note)


def main():
    t0 = time.time()
    cells = load_cells()
    print("| arch | shape | bottleneck | t_compute | t_memory | t_collective "
          "| useful_flops | roofline_frac | notes |")
    print("|---|---|---|---|---|---|---|---|---|")
    n_ok = n_skip = n_fail = 0
    for (arch, shape) in sorted(cells, key=lambda k: (k[0], SHAPE_ORDER.index(k[1]))):
        d = cells[(arch, shape)]
        print(fmt_row(d))
        n_ok += bool(d.get("ok") and not d.get("skip_reason"))
        n_skip += bool(d.get("skip_reason"))
        n_fail += bool(not d.get("ok"))
    us = (time.time() - t0) * 1e6
    csv_row("roofline_report", us, f"cells_ok={n_ok};skips={n_skip};fails={n_fail}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
