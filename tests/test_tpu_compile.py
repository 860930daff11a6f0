"""Compile the correction path for a TPU v5e, without one attached.

The TPU compiler is installed here and compiles for a described chip:
what it refuses (block tiling, unlowerable primitives, VMEM overruns) is
refused here at no chip time. Nothing runs, so these tests say nothing
about results or speed.

Widths are Llama-3.2-1B's (h_in 2048 -> h_out 512 / 2048 / 8192: the
k/v, q/o and MLP projections) at the lane-aligned group sizes the
kernels accept, for an 8-slot decode batch of 4-bit codes; the served
XLA correction also at the group sizes its in-group select takes.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under several pytest
workers every worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.pack import PackedDelta
from repro.kernels import fallback, ops

H_IN = 2048
H_OUTS = (512, 2048, 8192)
H_GS = (128, 256)
T = 8             # decode rows: one per engine slot
TENANT_ROWS = 5   # tenant stack incl. the zero row
K_BITS = 4
ALPHA = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed(h_g, h_out, sharding, stack=()):
    """Shapes of a 4-bit PackedDelta (optionally tenant-stacked)."""
    keep = h_g // ALPHA
    kp = -(-keep // (8 // K_BITS))
    G = H_IN // h_g
    return PackedDelta(
        _sds(stack + (G, keep, h_out), jnp.uint8, sharding),
        _sds(stack + (G, kp, h_out), jnp.uint8, sharding),
        _sds(stack, jnp.float32, sharding), _sds(stack, jnp.int32, sharding),
        H_IN, h_out, h_g, keep, float(ALPHA), K_BITS, 1)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


CASES = [(h_g, h_out) for h_g in H_GS for h_out in H_OUTS]


@pytest.mark.parametrize("h_g,h_out", CASES)
def test_delta_spmm_compiles(one_chip, h_g, h_out):
    p = _packed(h_g, h_out, one_chip)
    assert ops.kernel_supported(p)
    x = _sds((T, H_IN), jnp.float32, one_chip)
    c = _compile(lambda x, p: ops.delta_spmm(x, p, interpret=False), x, p)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("h_g,h_out", CASES)
def test_fused_base_delta_compiles(one_chip, h_g, h_out):
    p = _packed(h_g, h_out, one_chip)
    x = _sds((T, H_IN), jnp.float32, one_chip)
    w = _sds((H_IN, h_out), jnp.bfloat16, one_chip)
    c = _compile(lambda x, w, p: ops.fused_base_delta(x, w, p,
                                                      interpret=False),
                 x, w, p)
    assert "tpu_custom_call" in c.as_text()


def _segment_args(h_g, h_out, sharding):
    stk = _packed(h_g, h_out, sharding, stack=(TENANT_ROWS,))
    x = _sds((T, H_IN), jnp.float32, sharding)
    seg_rows = _sds((T,), jnp.int32, sharding)
    seg_offsets = _sds((T + 1,), jnp.int32, sharding)
    return x, stk, seg_rows, seg_offsets


@pytest.mark.parametrize("h_g,h_out", CASES)
def test_delta_spmm_segments_compiles(one_chip, h_g, h_out):
    c = _compile(lambda x, d, sr, so: ops.delta_spmm_segments(
        x, d, sr, so, interpret=False), *_segment_args(h_g, h_out, one_chip))
    assert "tpu_custom_call" in c.as_text()


def _gathers_reading(hlo: str, n_elems: int) -> list:
    """Compiled-HLO gathers whose operand is an f32 array of n_elems
    (instruction names are unique in a module, so a name gives a type)."""
    types = dict(re.findall(r"%(\S+) = (\w+\[[0-9,]*\])", hlo))
    found = []
    for name, operand in re.findall(r"%(\S+) = \S+ gather\(%([^,\s]+)", hlo):
        dtype, dims = types.get(operand, "?[]").rstrip("]").split("[")
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        if dtype == "f32" and size == n_elems:
            found.append(name)
    return found


# group sizes the in-group select serves (the fleet's h_g 16 among them,
# and 128) and one above SELECT_MAX_HG, which keeps the flat gather
SERVED_CASES = [(h_g, h_out) for h_g in (16, 64) + H_GS for h_out in H_OUTS]


@pytest.mark.parametrize("h_g,h_out", SERVED_CASES)
def test_served_segment_correction_compiles(one_chip, h_g, h_out):
    """The XLA formulation the engine serves with today. Up to
    SELECT_MAX_HG no gather reads the [T, h_in] activations; above it
    the flat gather does, which shows the probe finds one."""
    c = _compile(fallback.segment_correction,
                 *_segment_args(h_g, h_out, one_chip))
    text = c.as_text()
    assert "tpu_custom_call" not in text
    fed = _gathers_reading(text, T * H_IN)
    assert (fed == []) == (h_g <= fallback.SELECT_MAX_HG), fed
