"""The chip benchmark's correctness comparison: its own DeltaDQ and its
plain references against the program, and sound whole runs of the
harness at a small size on the CPU (the look for a chip skipped)."""
import ast
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import model, refcheck  # noqa: E402
from chipbench_small import (  # noqa: E402
    CELLS, run_small, small_conf, small_mix)


@pytest.mark.parametrize("k_bits,m", [(8, 1), (4, 8), (None, 1)])
def test_own_deltadq_equals_the_program_packing(k_bits, m):
    from repro.core.dropout import groupwise_dropout_pack
    from repro.core.pack import reconstruct_dense
    key = jax.random.PRNGKey(3)
    delta = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 48)) * 0.01
    packed = groupwise_dropout_pack(key, delta, h_g=16, alpha=8.0,
                                    k_bits=k_bits, m=m)
    ours = refcheck.deltadq_dense(key, delta, alpha=8.0, k_bits=k_bits,
                                  h_g=16)
    np.testing.assert_array_equal(np.asarray(ours),
                                  np.asarray(reconstruct_dense(packed)))


@pytest.mark.parametrize("name", ["phi3-medium-14b", "mamba2-370m"])
def test_reference_equals_the_program_forward(name):
    """Each plain reference, fed the program's layout, gives the logits
    of the program's own float32 forward pass."""
    from repro.models import lm
    conf = small_conf(name)
    cfg = model.arch_config(conf)
    params = jax.tree.map(lambda w: w.astype(jnp.float32),
                          model.make_params(conf, cfg, 5))
    ref = refcheck.load_reference(conf)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, 48),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = lm.forward(cfg.replace(param_dtype="float32"), params,
                          {"tokens": toks[None]})[0]
        got = ref.logits_at(conf["arch"], params, toks, jnp.arange(48))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("conf_name,mix_name", CELLS)
def test_a_sound_run_is_correct(conf_name, mix_name):
    out = run_small(small_conf(conf_name), small_mix(mix_name))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0


@pytest.mark.parametrize("conf_name,mix_name", CELLS)
def test_the_window_compiles_nothing(conf_name, mix_name):
    """Set-up warms every program the window runs, also where requests
    turn over in it and new prompts reach shapes a fill did not."""
    mix = small_mix(mix_name)
    lines = []
    out = run_small(small_conf(conf_name), mix, log=lines.append)
    assert out["attempted"] > mix["clients"]      # requests turned over
    window = [m for m in lines if m.startswith("window ")]
    assert len(window) == 1, lines
    new = ast.literal_eval(window[0].split("compiles inside the window ")[1])
    assert new and not any(new.values()), window[0]
