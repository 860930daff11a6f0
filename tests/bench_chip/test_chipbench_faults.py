"""Whole runs of the chip benchmark's harness at a small size on the CPU
(the look for a chip skipped) with the timed path broken underneath:
each must come out not correct, as must the int8 control put in the
program's place."""
import os
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from chipbench_small import (  # noqa: E402
    CELLS, cell_limits, cell_mix, run_small, small_conf, small_mix)


@pytest.mark.parametrize("conf_name,mix_name", CELLS)
def test_a_token_altered_where_produced_is_caught(conf_name, mix_name,
                                                  monkeypatch):
    from repro.serve.scheduler import Request
    emit = Request.emit

    def altered(self, token):
        return emit(self, (token + 1) % 512)

    monkeypatch.setattr(Request, "emit", altered)
    out = run_small(small_conf(conf_name), small_mix(mix_name))
    assert not out["correct"], out["checks"]


def test_a_dropped_decode_correction_is_caught(monkeypatch):
    """The comparison covers the served correction, not one easy part."""
    from repro.kernels import fallback
    real = fallback.segment_correction

    def dropped(x2, d, *a, **k):
        return jnp.zeros_like(real(x2, d, *a, **k))

    monkeypatch.setattr(fallback, "segment_correction", dropped)
    mix = small_mix("decode-mixed")
    mix["fleet"]["base_share"] = 0.0
    out = run_small(small_conf("phi3-medium-14b"), mix)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell,mix_name", [
    ("phi3m.decode-mixed", "decode-mixed"),
    ("phi3m.prefill-score", "prefill-score")])
def test_the_int8_control_is_not_correct(cell, mix_name):
    """The int8 reference in the program's place goes through the same
    decision, under the cell's committed limits, and fails it. At this
    width int8 moves the logits less than at the cell's, so the run keeps
    the cell's clients and slots and compares every served token."""
    mix = small_mix(mix_name)
    mix["clients"], mix["slots"] = (cell_mix(mix_name)[k]
                                    for k in ("clients", "slots"))
    mix["check_requests"] = 1 << 20
    out = run_small(small_conf("phi3-medium-14b"), mix,
                    limits=cell_limits(cell), seconds=2.0, seed=13,
                    control=True)
    assert not out["correct"], out["checks"]
    assert list(out["checks"]) == ["gap_max"]
