"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Sweeps shapes/dtypes per the deliverable; hypothesis drives random
envelope-internal configurations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import groupwise_dropout_pack
from repro.kernels import ops, ref

# hypothesis is optional: only the property-based test needs it, the
# deterministic parity sweeps must run everywhere (they are the only
# validation of the Pallas kernels on CPU containers)
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

SWEEP = [
    # (T, h_in, h_out, h_g, alpha, k_bits); h_g inside the kernel
    # envelope: a multiple of 128 lanes, or all of h_in
    (64, 256, 128, 128, 8, 4),
    (32, 512, 256, 128, 4, 8),
    (128, 256, 384, 128, 2, 2),
    (16, 128, 128, 128, 8, 1),
    (8, 64, 96, 64, 4, None),
    (100, 256, 96, 256, 16, 4),     # padding path (T not multiple of tile)
    (1, 128, 64, 128, 4, 4),        # decode shape (T=1)
]


def _pack(h_in, h_out, h_g, alpha, k, seed=0, scale=0.01):
    rng = jax.random.PRNGKey(seed)
    d = jax.random.normal(rng, (h_in, h_out)) * scale
    return groupwise_dropout_pack(rng, d, h_g=h_g, alpha=alpha, k_bits=k)


@pytest.mark.parametrize("T,h_in,h_out,h_g,alpha,k", SWEEP)
def test_delta_spmm_vs_ref(T, h_in, h_out, h_g, alpha, k):
    p = _pack(h_in, h_out, h_g, alpha, k)
    assert ops.kernel_supported(p), ops.kernel_refusal(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, h_in))
    np.testing.assert_allclose(np.asarray(ops.delta_spmm(x, p, interpret=True)),
                               np.asarray(ref.delta_spmm_ref(x, p)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,h_in,h_out,h_g,alpha,k", SWEEP[:5])
def test_fused_base_delta_vs_ref(T, h_in, h_out, h_g, alpha, k):
    p = _pack(h_in, h_out, h_g, alpha, k)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, h_in))
    w = jax.random.normal(jax.random.PRNGKey(2), (h_in, h_out)) * 0.05
    np.testing.assert_allclose(np.asarray(ops.fused_base_delta(x, w, p, interpret=True)),
                               np.asarray(ref.fused_base_delta_ref(x, w, p)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,h_in,h_out,h_g,alpha,k", SWEEP[:5])
def test_dequant_vs_ref(T, h_in, h_out, h_g, alpha, k):
    p = _pack(h_in, h_out, h_g, alpha, k)
    np.testing.assert_allclose(np.asarray(ops.dequant(p, interpret=True)),
                               np.asarray(ref.dequant_tile_ref(p)),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(dtype):
    p = _pack(256, 128, 128, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 256)).astype(dtype)
    got = ops.delta_spmm(x, p, interpret=True)
    want = ref.delta_spmm_ref(x.astype(jnp.float32), p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=0.05 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=0.05 if dtype == jnp.bfloat16 else 1e-4)


def test_import_leaves_the_backend_alone(subproc):
    """Interpret mode is decided per call: importing the kernels (or the
    serving stack above them) must not initialise a JAX backend, which
    on a TPU host would claim the chip."""
    out = subproc("""
    from jax._src import xla_bridge
    import repro.kernels.ops, repro.serve, repro.launch.serve  # noqa: F401
    print(xla_bridge.backends_are_initialized())
    """, n_devices=1)
    assert out.strip() == "False"


@pytest.mark.parametrize("h_in,h_g", [
    (1024, 1024),     # h_g > MAX_HG
    (128, 64),        # not lane-aligned: the x block (tb, 64) cannot lower
])
def test_fallback_outside_envelope(h_in, h_g):
    # routes to the XLA fallback, says why, and still matches the oracle
    from repro.serve.trace import attribution
    p = _pack(h_in, 32, h_g, 8, 4)
    assert not ops.kernel_supported(p)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, h_in))
    with attribution() as notes:
        got = ops.delta_spmm(x, p, interpret=True)
    assert {"site": "delta_spmm",
            "kernel_refused": ops.kernel_refusal(p)} in notes
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.delta_spmm_ref(x, p)),
                               atol=1e-4, rtol=1e-4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(
        t_exp=st.integers(0, 6),
        g_exp=st.integers(0, 3),
        hg_exp=st.integers(4, 8),
        alpha=st.sampled_from([2, 4, 8, 16]),
        k=st.sampled_from([1, 2, 4, 8, None]),
        ho_mult=st.integers(1, 3),
    )
    def test_kernel_hypothesis(t_exp, g_exp, hg_exp, alpha, k, ho_mult):
        h_g = 2 ** hg_exp
        if h_g < alpha:
            h_g = alpha
        h_in = h_g * (2 ** g_exp)
        h_out = 64 * ho_mult
        T = 2 ** t_exp
        p = _pack(h_in, h_out, h_g, alpha, k, seed=t_exp + hg_exp)
        x = jax.random.normal(jax.random.PRNGKey(5), (T, h_in))
        np.testing.assert_allclose(
            np.asarray(ops.delta_spmm(x, p, interpret=True)),
            np.asarray(ref.delta_spmm_ref(x, p)),
            atol=1e-3, rtol=1e-3)
