"""One run of one cell, driven by ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration (``configs/``)
and a traffic mix (``traffic/<traffic>.json``); its correctness limits
are ``limits/<cell>.json``. Each metric is read by ``metrics/<name>.py``,
whose ``read(run)`` returns a number, or None where it finds nothing to
read (the metric is then left out of the line). ``--trace 0`` reads the
cell's end-to-end metrics, ``--trace 1`` its per-layer ones, from a run
traced by the JAX profiler. A later PR adds a cell, a mix, a
configuration or a metric by adding files: nothing here names one.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from chipbench import costs, refcheck, trace_reduce
from chipbench.serving import Cell, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str):
    """(workload entry, configuration, mix, limits) of a cell."""
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    conf = load_json(ROOT, conf_entry["file"])
    mix = load_json(HERE, "traffic", wl["traffic"] + ".json")
    limits = load_json(HERE, "limits", workload + ".json")
    return wl, conf, mix, limits


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end ones untraced, its
    per-layer ones traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chip(n_chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < n_chips:
        raise NoChip(f"needs {n_chips} chips; JAX found {len(devs)}")
    return devs


@dataclass
class RunData:
    """What a metric reader reads."""
    window: Window
    arch: dict
    mix: dict
    device_kind: str
    setup_s: float
    engine_metrics: Any            # repro.serve.metrics.Metrics of the window
    delta_shapes: Dict[str, tuple]
    trace: Optional[trace_reduce.Summary] = None

    @property
    def peaks(self) -> dict:
        return costs.peaks(self.device_kind)


def _peak_bytes(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, **kw) -> dict:
    """Set up, measure and check one cell of ``bench``."""
    _, conf, mix, limits = cell_spec(bench, workload)
    return measure(conf, mix, limits, cell_metrics(bench, workload, trace),
                   seed, seconds, trace, **kw)


def measure(conf: dict, mix: dict, limits: dict, metric_entries: List[dict],
            seed: int, seconds: float, trace: bool, *, t_proc0: float,
            devices: list, log: Callable[[str], None],
            control: bool = False) -> dict:
    """Set up, measure, check; returns the result object."""
    import jax
    from repro.analysis import CompileGuard

    span = jax.profiler.TraceAnnotation if trace else None
    cell = Cell(conf, mix, seed, log=log, span=span)
    cell.setup()
    setup_s = time.perf_counter() - t_proc0
    log(f"setup_s {setup_s!r}")

    guard = CompileGuard(cell.engine)
    guard.snapshot()
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                window = cell.run_window(seconds)
            jax.profiler.stop_trace()
            summary = trace_reduce.summarize(trace_reduce.load(tdir))
        else:
            window = cell.run_window(seconds)
            summary = None
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    new = {k: guard.new_compiles(k) for k in guard.sizes()}
    log(f"window {window.seconds!r}s, {len(window.steps)} steps; compiles "
        f"inside the window {new}")
    peak = _peak_bytes(devices)
    engine_metrics = cell.engine.metrics
    attempted = len(window.records)
    run = RunData(window=window, arch=conf["arch"], mix=mix,
                  device_kind=devices[0].device_kind, setup_s=setup_s,
                  engine_metrics=engine_metrics,
                  delta_shapes=cell.delta_shapes, trace=summary)
    metrics = {}
    for m in metric_entries:
        v = load_reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    # the program's state goes before the reference runs
    base = cell.base
    del cell.engine
    gc.collect()
    t = time.perf_counter()
    got = refcheck.compare(conf, mix, base, cell.names, seed, window.records,
                           control=control)
    log(f"reference over {got['requests']} requests, {got['tokens']} served "
        f"tokens, owners {got['owners']}: {time.perf_counter() - t:.3f}s")
    checks = {k: {"value": got[k], "limit": float(limits[k])}
              for k in limits if not k.startswith("_")}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": [list(g) for g in
                                          summary.idle_gaps[:10]]}
    out["checks"] = checks
    return out


def main(argv=None, *, t_proc0: Optional[float] = None) -> int:
    import argparse
    t_proc0 = time.perf_counter() if t_proc0 is None else t_proc0
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the int8 reference in the program's place in "
                         "the check; such a run must come out not correct "
                         "(calibration only; benchmark runs leave it off)")
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    bench = load_json(ROOT, "BENCHMARK.json")
    wl, _, _, _ = cell_spec(bench, args.workload)
    try:
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        raise SystemExit(f"the program is not importable: {e}")
    devices = require_chip(int(wl["chips"]))
    from repro.utils import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    # cache every program, however quickly it compiled, so that only the
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device {devices[0].device_kind!r} x{len(devices)}; compile cache "
        f"{cache}")
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_proc0=t_proc0, devices=devices,
                   log=log, control=args.control)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0
