"""The host-time readers (``host_ms_per_step.decode``,
``host_ms_per_req.prefill``) over the engine's phase times, by hand and
in a small run of each Phi-3 cell on the CPU."""
import json
import os
import sys
import time
from types import SimpleNamespace

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402
from chipbench_small import small_conf, small_mix  # noqa: E402

from repro.serve import Metrics  # noqa: E402

READERS = [("host_ms_per_step.decode", "engine.decode", "decode-mixed"),
           ("host_ms_per_req.prefill", "engine.prefill", "prefill-score")]

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def _run(metrics):
    return SimpleNamespace(engine_metrics=metrics, trace=None)


@pytest.mark.parametrize("name,span,_", READERS)
def test_reader_is_span_less_wait_per_span(name, span, _):
    m = Metrics(n_slots=2)
    for dur, wait in ((0.100, 0.090), (0.200, 0.180), (0.300, 0.300)):
        m.phases.add(span, dur)
        m.phases.add(span + ".wait", wait)
    m.phases.add(span + ".prep", 0.005)        # children do not add up
    read = harness.load_reader(name).read
    assert read(_run(m)) == pytest.approx((0.010 + 0.020 + 0.0) / 3 * 1e3)


@pytest.mark.parametrize("name,span,_", READERS)
def test_reader_finds_nothing_without_phase_times(name, span, _):
    read = harness.load_reader(name).read
    assert read(_run(Metrics(n_slots=2))) is None     # no span closed
    # a program whose metrics keep no phase times at all
    assert read(_run(SimpleNamespace(n_decode_steps=3))) is None


@pytest.mark.parametrize("name,span,mix_name", READERS)
def test_reader_in_a_small_run(name, span, mix_name):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    out = harness.measure(small_conf("phi3-medium-14b"), small_mix(mix_name),
                          {"gap_max": 0.05}, [entry], 11, 0.5, False,
                          t_proc0=time.perf_counter(), devices=jax.devices(),
                          log=lambda m: None)
    assert out["correct"], out["checks"]
    got = out["metrics"][name]
    assert got["unit"] == "ms" and got["value"] > 0.0
