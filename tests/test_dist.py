"""Distribution-layer tests: sharding rules, ZeRO-1, compressed all-reduce."""
import numpy as np
import pytest


def test_sharding_rules_and_fallback(subproc):
    out = subproc("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist import ShardingRules, tree_shardings, zero1_shardings
    from repro.dist.sharding import TRAIN_OVERRIDES

    mesh = jax.make_mesh((2, 4), ('data', 'model'))
    rules = ShardingRules(mesh)
    # heads divisible by model -> sharded
    assert rules.spec_for(('embed', 'heads'), (64, 32), 'wq') == P(None, 'model')
    # 3 heads not divisible by 4 -> replicated + fallback recorded
    assert rules.spec_for(('embed', 'heads'), (64, 3), 'wq3') == P(None, None)
    assert any(p == 'wq3' for p, _, _ in rules.fallbacks)
    # batch over (pod,data): pod absent -> data only
    assert rules.spec_for(('batch', 'seq'), (8, 16), 'tok') == P('data', None)
    # train profile: FSDP on embed
    tr = rules.with_overrides(**TRAIN_OVERRIDES)
    assert tr.spec_for(('embed', 'mlp'), (64, 128), 'wi') == P('data', 'model')
    # same mesh axis never used twice in one spec
    assert tr.spec_for(('mlp', 'mlp'), (128, 128), 'ww') == P('model', None)
    print('OK')
    """, n_devices=8)
    assert "OK" in out


def test_zero1_adds_shard_on_free_dim(subproc):
    out = subproc("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist import ShardingRules, zero1_shardings
    mesh = jax.make_mesh((2, 4), ('data', 'model'))
    rules = ShardingRules(mesh)
    specs = {'w': jax.ShapeDtypeStruct((64, 128), 'float32')}
    axes = {'w': ('embed', 'mlp')}
    sh = zero1_shardings(rules, specs, axes, zero_axes=('data',))
    assert sh['w'].spec == P('data', 'model'), sh['w'].spec
    # when embed already took data (train profile) -> no double use
    rules2 = ShardingRules(mesh, dict(rules.rules, embed=('data',)))
    sh2 = zero1_shardings(rules2, specs, axes, zero_axes=('data',))
    assert sh2['w'].spec == P('data', 'model')
    print('OK')
    """, n_devices=8)
    assert "OK" in out


def test_compressed_allreduce_matches_mean(subproc):
    out = subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import make_compressed_allreduce
    mesh = jax.make_mesh((4,), ('data',))
    tr = make_compressed_allreduce(mesh, 'data')

    # per-device distinct values, replicated container: emulate by shard_map
    # over a [4, n] array where row i is device i's local gradient
    rng = np.random.default_rng(0)
    local = rng.normal(size=(4, 1000)).astype(np.float32)
    want = local.mean(0)

    def per_device(v):  # v: this device's row [1, n] -> replicated mean
        from repro.dist.grad_compress import _compressed_psum_flat
        return _compressed_psum_flat(v[0], 'data', 4)[None]

    got = jax.shard_map(per_device, mesh=mesh, in_specs=P('data'),
                        out_specs=P('data'), check_vma=False)(jnp.asarray(local))
    got = np.asarray(got)
    # every device row holds the same reduced result
    for i in range(4):
        np.testing.assert_allclose(got[i], got[0], atol=1e-6)
    # int8 two-phase quantization error is bounded (~1% of range)
    err = np.abs(got[0] - want).max()
    rng_ = np.abs(want).max()
    assert err < 0.05 * rng_ + 0.05, (err, rng_)
    print('ERR', err, 'OK')
    """, n_devices=8)
    assert "OK" in out


def test_error_feedback_converges(subproc):
    """With error feedback, repeated compressed reductions of the SAME
    gradient converge to the true value (residual correction)."""
    out = subproc("""
    import jax.numpy as jnp, numpy as np
    from repro.dist import ErrorFeedback
    g = {'w': jnp.asarray(np.random.default_rng(1).normal(size=512).astype(np.float32))}
    res = ErrorFeedback.init(g)
    acc = jnp.zeros(512)
    n = 30
    for _ in range(n):
        sent, res = ErrorFeedback.apply(g, res)
        acc = acc + sent['w']
    # average of sent == true gradient despite int8 rounding each round
    err = float(jnp.max(jnp.abs(acc / n - g['w'])))
    assert err < 2e-3, err
    print('OK', err)
    """, n_devices=4)
    assert "OK" in out


def test_cache_axes_shapes():
    import jax
    from repro.configs import get_smoke_config
    from repro.dist import cache_axes
    from repro.models import lm
    for arch in ("llama3.2-1b", "mamba2-370m", "recurrentgemma-9b"):
        cfg = get_smoke_config(arch)
        cache = lm.cache_specs(cfg, batch=2, max_seq=16)
        axes = cache_axes(cache)
        assert len(axes) == len(cache)
        flat_c = jax.tree.leaves(cache)
        # axes leaves are tuples of axis names; NamedTuple states must still
        # be descended into, so only stop at pure name tuples
        is_ax = lambda x: (isinstance(x, tuple) and not hasattr(x, "_fields")
                           and all(e is None or isinstance(e, str) for e in x))
        flat_a = jax.tree.leaves(axes, is_leaf=is_ax)
        assert len(flat_c) == len(flat_a)
        for c, a in zip(flat_c, flat_a):
            assert len(a) == len(c.shape)
