"""Fault tolerance: checkpoint/restart, failure simulation, elastic re-mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.configs import get_smoke_config
from repro.data import PretrainMixture
from repro.models import lm
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.train import make_train_step


def _train(cfg, params, opt, data, step_fn, start, n):
    ms = None
    for i in range(start, start + n):
        params, opt, ms = step_fn(params, opt, data.batch_at(i), jax.random.PRNGKey(i))
    return params, opt, ms


def test_failure_restart_bitexact(tmp_path):
    """Kill mid-training, restore, continue: bitwise identical to no-failure."""
    cfg = get_smoke_config("llama3.2-1b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    data = PretrainMixture(vocab=cfg.vocab, seq_len=16, batch=4)
    step_fn = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))

    # uninterrupted run: 6 steps
    p_ref, o_ref, _ = _train(cfg, params, adamw.init(params), data, step_fn, 0, 6)

    # interrupted: 3 steps -> checkpoint -> "crash" -> restore -> 3 more
    ck = Checkpointer(str(tmp_path / "ck"))
    p1, o1, _ = _train(cfg, params, adamw.init(params), data, step_fn, 0, 3)
    ck.save(3, {"params": p1, "opt": o1}, extra={"data_step": 3})
    del p1, o1  # crash
    state, manifest = ck.restore({"params": params, "opt": adamw.init(params)})
    assert manifest["extra"]["data_step"] == 3
    p2, o2, _ = _train(cfg, state["params"], state["opt"], data, step_fn,
                       manifest["extra"]["data_step"], 3)

    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save(tmp_path):
    ck = Checkpointer(str(tmp_path / "ck"))
    state = {"w": jnp.arange(1000, dtype=jnp.float32)}
    ck.save(1, state, blocking=False)
    ck.wait()
    restored, _ = ck.restore(state)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))


def test_latest_step_and_multiple(tmp_path):
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(1, {"x": jnp.ones(3)})
    ck.save(7, {"x": jnp.ones(3) * 7})
    assert ck.latest_step() == 7
    r, _ = ck.restore({"x": jnp.zeros(3)})
    assert float(r["x"][0]) == 7.0
    r1, _ = ck.restore({"x": jnp.zeros(3)}, step=1)
    assert float(r1["x"][0]) == 1.0


@pytest.mark.slow  # three short training runs across meshes in subprocesses
def test_elastic_remesh_restore(subproc):
    """Save on a (2,2) mesh, restore on (4,1) AND on (1,1): training continues
    with identical loss trajectory — the elastic-rescale path."""
    out = subproc("""
    import jax, numpy as np, jax.numpy as jnp, tempfile, os
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import Checkpointer
    from repro.configs import get_smoke_config
    from repro.data import PretrainMixture
    from repro.models import lm
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig
    from repro.train import make_train_step
    from repro.dist import ShardingRules, tree_shardings
    from repro.launch.mesh import make_mesh

    cfg = get_smoke_config('llama3.2-1b')
    data = PretrainMixture(vocab=cfg.vocab, seq_len=16, batch=4)
    step_fn = make_train_step(cfg, AdamWConfig(lr=1e-3))

    def run(mesh_shape, restore_dir=None, start=0, n=3, save_dir=None):
        mesh = make_mesh(mesh_shape, ('data', 'model'))
        rules = ShardingRules(mesh)
        p_specs, p_axes = lm.param_specs(cfg), lm.param_axes(cfg)
        p_sh = tree_shardings(rules, p_specs, p_axes)
        with mesh:
            params = lm.init_params(cfg, jax.random.PRNGKey(0))
            params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, p_sh)
            opt = adamw.init(params)
            if restore_dir:
                ck = Checkpointer(restore_dir)
                state, man = ck.restore({'params': params, 'opt': opt})
                params, opt = state['params'], state['opt']
                start = man['extra']['data_step']
            sf = jax.jit(step_fn)
            loss = None
            for i in range(start, start + n):
                params, opt, m = sf(params, opt, data.batch_at(i), jax.random.PRNGKey(i))
                loss = float(m['loss'])
            if save_dir:
                Checkpointer(save_dir).save(start + n, {'params': params, 'opt': opt},
                                            extra={'data_step': start + n})
            return params, loss

    d = tempfile.mkdtemp()
    # reference: 6 steps on (2,2)
    _, ref_loss = run((2, 2), n=6)
    # elastic: 3 steps on (2,2) -> save -> restore on (4,1) -> 3 more
    run((2, 2), n=3, save_dir=d)
    _, el_loss = run((4, 1), restore_dir=d, n=3)
    # and restore on a single device mesh
    _, sd_loss = run((1, 1), restore_dir=d, n=3)
    print('REF', ref_loss, 'EL', el_loss, 'SD', sd_loss)
    assert abs(ref_loss - el_loss) < 2e-3, (ref_loss, el_loss)
    assert abs(ref_loss - sd_loss) < 2e-3, (ref_loss, sd_loss)
    print('OK')
    """, n_devices=8, timeout=900)
    assert "OK" in out
