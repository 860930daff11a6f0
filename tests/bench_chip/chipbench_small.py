"""Cells of the chip benchmark cut to a size a CPU test run can hold."""
import json
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def small_conf(name):
    """The cell's configuration file at a size a test run can hold."""
    c = _load("configs", name + ".json")
    if c["arch"]["family"] == "ssm":
        c["arch"].update(d_model=64, n_heads=8, n_kv=8, head_dim=16,
                         vocab=512, n_layers=2,
                         ssm={"d_state": 16, "head_dim": 16, "expand": 2,
                              "conv_width": 4, "chunk": 16, "n_groups": 1})
    else:
        c["arch"].update(d_model=64, n_heads=4, n_kv=2, head_dim=16,
                         d_ff=128, vocab=512, n_layers=1)
    return c


def cell_mix(name):
    """A traffic mix as committed."""
    return _load("traffic", name + ".json")


def small_mix(name):
    """The mix with two tenants (one per codec group), two clients and
    short lengths."""
    m = cell_mix(name)
    m["fleet"]["tenants"] = m["fleet"]["tenants"][:2]
    m["clients"] = m["slots"] = 2
    m["check_requests"] = 6
    m["prompt_len"].update(min=8, max=16, median=12, levels=2)
    if m["output_len"]["max"] > 1:
        m["output_len"].update(min=4, max=8, median=6, levels=2)
    return m


def cell_limits(cell):
    """The limits a benchmark cell commits (``limits/<cell>.json``)."""
    return _load("limits", cell + ".json")


def run_small(conf, mix, limits=None, seconds=0.5, seed=11, control=False,
              log=lambda m: None):
    return harness.measure(conf, mix, limits or {"gap_max": 0.05}, [], seed,
                           seconds, False, t_proc0=time.perf_counter(),
                           devices=jax.devices(), log=log, control=control)


CELLS = [("phi3-medium-14b", "decode-mixed"),
         ("phi3-medium-14b", "prefill-score"),
         ("mamba2-370m", "decode-mixed")]
