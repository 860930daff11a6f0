import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import PretrainMixture
from repro.models import lm
from repro.optim import adamw, schedule
from repro.optim.adamw import AdamWConfig
from repro.train import make_train_step


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3.2-1b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    data = PretrainMixture(vocab=cfg.vocab, seq_len=32, batch=8)
    return cfg, params, data


def test_loss_decreases(setup):
    cfg, params, data = setup
    opt_cfg = AdamWConfig(lr=5e-3, schedule=schedule.cosine_with_warmup(3, 40))
    opt = adamw.init(params)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    losses = []
    for i in range(15):
        params, opt, m = step(params, opt, data.batch_at(i), jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_microbatch_equivalence(setup):
    """n_micro=1 vs n_micro=4 give (nearly) identical updates."""
    cfg, params, data = setup
    opt_cfg = AdamWConfig(lr=1e-3)
    batch = data.batch_at(0)
    outs = []
    for nm in (1, 4):
        opt = adamw.init(params)
        step = jax.jit(make_train_step(cfg, opt_cfg, n_micro=nm))
        p2, _, m = step(params, opt, batch, jax.random.PRNGKey(0))
        outs.append((p2, float(m["loss"])))
    # loss of n_micro=4 is the mean over chunks of per-chunk losses; grads equal
    flat1 = jax.tree.leaves(outs[0][0])
    flat4 = jax.tree.leaves(outs[1][0])
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=5e-3)


@pytest.mark.slow  # full grad trace through every delta site (~27s)
def test_loss_differentiable_through_delta_path(setup):
    """grad through deltas= must work through the fusion-pinning barriers
    in apply_linear and the slot dispatch (optimization_barrier is an
    identity with a differentiation rule)."""
    from repro.core import DeltaDQSpec, compress
    cfg, params, data = setup
    ft = jax.tree.map(lambda p: p * 1.01 if p.ndim >= 2 else p, params)
    deltas, _ = compress(params, ft, DeltaDQSpec(alpha=4.0, k_bits=8, h_g=16))
    batch = data.batch_at(0)
    g = jax.grad(lambda p: lm.loss_fn(cfg, p, batch, deltas=deltas)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0.0


def test_schedules():
    s = schedule.cosine_with_warmup(10, 100)
    assert float(s(jnp.int32(0))) == 0.0
    assert float(s(jnp.int32(10))) == pytest.approx(1.0)
    assert float(s(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)
    inv = schedule.inverse_sqrt(16)
    assert float(inv(jnp.int32(4))) == pytest.approx(0.25)
    assert float(inv(jnp.int32(64))) == pytest.approx(0.5)


def test_grad_clip():
    g = {"a": jnp.ones((4,)) * 100.0}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_weight_decay_mask():
    from repro.optim.adamw import _decay_mask
    assert _decay_mask("attn/wq") == 1.0
    assert _decay_mask("attn/ln1") == 0.0
    assert _decay_mask("final_norm/scale") == 0.0
