"""Parity tests for the mixed-tenant segment dispatch and the XLA
fallback formulations.

Three layers are checked against the dense-reconstruct oracle:

* ``kernels.fallback`` — gather / per-row / segment formulations (the
  CPU serving hot path), including the bitwise-stability property the
  token-identity contract depends on;
* ``kernels.ops.delta_spmm_segments`` — the batched slot Pallas kernel
  in interpret mode (+ the scan fallback);
* ``core.apply.slot_delta_matmul`` — the dispatch seam the engine uses,
  in both "segments" and "per_row" modes.

The slow-marked sweep covers the full supported envelope
(h_g x keep x k_bits); the fast subset runs per-PR.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import groupwise_dropout_pack
from repro.core.apply import (
    get_slot_dispatch,
    set_slot_dispatch,
    stack_tenant_deltas,
    slot_delta_matmul,
    wrap_slot_deltas,
)
from repro.core.pack import PackedDelta, reconstruct_dense
from repro.kernels import fallback, ops
from repro.serve.scheduler import tenant_segments


def _pack(h_in, h_out, h_g, alpha, k, seed=0, scale=0.01):
    rng = jax.random.PRNGKey(seed)
    d = jax.random.normal(rng, (h_in, h_out)) * scale
    return groupwise_dropout_pack(rng, d, h_g=h_g, alpha=alpha, k_bits=k)


def _stacked(n, h_in=128, h_out=256, h_g=64, alpha=8, k=4):
    ps = [_pack(h_in, h_out, h_g, alpha, k, seed=s) for s in range(n)]
    return stack_tenant_deltas([{"w": p} for p in ps])["w"], ps


def _segments(rows):
    return jax.tree.map(jnp.asarray, tenant_segments(np.asarray(rows)))


# ---------------------------------------------------------------------------
# XLA fallback formulations
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,h_in,h_out,h_g,alpha,k", [
    (1, 128, 256, 64, 8, 4),
    (8, 128, 96, 32, 4, 2),
    (200, 256, 128, 64, 8, None),
])
def test_gather_vs_dense_correction(T, h_in, h_out, h_g, alpha, k):
    p = _pack(h_in, h_out, h_g, alpha, k)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, h_in))
    want = np.asarray(x @ reconstruct_dense(p))
    np.testing.assert_allclose(np.asarray(fallback.gather_correction(x, p)),
                               want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fallback.dense_correction(x, p)),
                               want, atol=1e-6, rtol=1e-6)


def test_gather_correction_batch_extent_bit_stable():
    """The token-identity contract: a row's correction must be the same
    bits whether computed alone, in a group, or in a full slot batch."""
    p = _pack(128, 256, 64, 8, 4, scale=0.5)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 128)) * 2.0
    full = np.asarray(jax.jit(lambda x: fallback.gather_correction(x, p))(x))
    for sl in (slice(0, 1), slice(2, 5), slice(3, 8)):
        part = np.asarray(
            jax.jit(lambda x: fallback.gather_correction(x, p))(x[sl]))
        np.testing.assert_array_equal(part, full[sl])


def test_rows_vs_shared_vals_bit_identical():
    """Per-row gather with every row on the same tenant must bit-match
    the shared-tenant gather (what makes per_row == per-tenant exact)."""
    p = _pack(128, 256, 64, 8, 4, scale=0.5)
    B = 4
    rows = np.zeros(B, np.int32)
    stk, _ = _stacked(1)
    gat = PackedDelta(stk.idx[rows], stk.codes[rows],
                      jnp.asarray(stk.scale)[rows],
                      jnp.asarray(stk.zero)[rows],
                      stk.h_in, stk.h_out, stk.h_g, stk.keep,
                      stk.alpha, stk.k_bits, stk.m)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, 128))
    y_rows = np.asarray(jax.jit(
        lambda x: fallback.gather_correction_rows(x[:, None, :], gat))(x))[:, 0]
    y_shared = np.asarray(jax.jit(
        lambda x: fallback.gather_correction(x, stk.index(0)))(x))
    np.testing.assert_array_equal(y_rows, y_shared)


def test_gather_rows_no_dense_materialization_parity():
    """The slots fallback must match per-row dense without ever building
    the [B, h_in, h_out] stack (which blew up memory when rows shared a
    tenant)."""
    stk, ps = _stacked(2)
    rows = np.array([1, 1, 1, 0, 1, 1], np.int32)   # dup-heavy batch
    gat = PackedDelta(stk.idx[rows], stk.codes[rows],
                      jnp.asarray(stk.scale)[rows],
                      jnp.asarray(stk.zero)[rows],
                      stk.h_in, stk.h_out, stk.h_g, stk.keep,
                      stk.alpha, stk.k_bits, stk.m)
    x = jax.random.normal(jax.random.PRNGKey(4), (len(rows), 1, 128))
    want = jnp.einsum("b...d,bdf->b...f", x, reconstruct_dense(stk)[rows])
    got = ops.delta_spmm_slots(x, gat, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# How the fallback picks kept activations: in-group select or flat gather
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("h_g", [8, 16, 32, 64, 128])
def test_select_pick_bit_equal_to_flat_index_oracle(h_g):
    """The select copies x[n, g*h_g + idx] exactly: every bit, -0.0,
    +-inf and NaN included, as a gather by flat index does."""
    assert h_g <= fallback.SELECT_MAX_HG
    N, G, K, O = 3, 5, max(h_g // 8, 2), 128
    rng = np.random.default_rng(h_g)
    x = rng.standard_normal((N, G * h_g)).astype(np.float32)
    specials = np.array([-0.0, np.inf, -np.inf, np.nan], np.float32)
    x.flat[rng.choice(x.size, 4 * len(specials), replace=False)] = \
        np.repeat(specials, 4)
    idx = rng.integers(0, h_g, (N, G, K, O)).astype(np.uint8)
    idx[0, 0, 0, :h_g] = np.arange(h_g)        # every column picked once
    flat = idx.astype(np.int64) + (np.arange(G) * h_g)[:, None, None]
    want = np.take_along_axis(x, flat.reshape(N, -1), axis=1)
    got = np.asarray(jax.jit(fallback._select_in_group)(
        jnp.asarray(x).reshape(N, G, h_g), jnp.asarray(idx)))
    np.testing.assert_array_equal(got.reshape(N, -1).view(np.uint32),
                                  want.view(np.uint32))


def _attributed_forms(h_g):
    """Formulations the correction and the segment dispatch report at h_g."""
    from repro.serve.trace import attribution
    p = _pack(256, 96, h_g, 8, 4)
    stk = stack_tenant_deltas([{"w": p}, {"w": p}])["w"]
    seg = _segments([1, 0, 1])
    with attribution() as notes:
        fallback.correction(jnp.ones((3, 256)), p, gather_max_t=64)
        fallback.segment_correction(jnp.ones((3, 256)), stk, seg.seg_rows,
                                    seg.seg_offsets)
    return {n["site"]: n["formulation"] for n in notes}


@pytest.mark.parametrize("h_g,want", [
    (16, {"correction": "xla-select",
          "segment_correction": "segments-xla-select"}),
    (2 * fallback.SELECT_MAX_HG, {"correction": "xla-gather",
                                  "segment_correction": "segments-xla"}),
])
def test_pick_attribution_by_group_size(h_g, want):
    """Up to SELECT_MAX_HG the attribution reports the select; above it,
    the flat gather."""
    assert _attributed_forms(h_g) == want


def _activation_gathers(stablehlo: str, n_elems: int) -> list:
    """StableHLO gathers whose operand is an f32 block of n_elems."""
    found = []
    for line in stablehlo.splitlines():
        if '"stablehlo.gather"' not in line:
            continue
        operand = re.search(r": \(tensor<([0-9x]+)xf32>", line)
        if operand and np.prod([int(d) for d in
                                operand.group(1).split("x")]) == n_elems:
            found.append(line.strip())
    return found


@pytest.mark.parametrize("h_g", [16, 2 * fallback.SELECT_MAX_HG])
def test_segment_correction_lowers_no_activation_gather(h_g):
    """At h_g 16 no gather reads the activation block: the pick is made
    of static slices and selects. The gather path above SELECT_MAX_HG is
    the control that the probe finds such a gather."""
    T, h_in = 4, 256
    stk = stack_tenant_deltas([{"w": _pack(h_in, 96, h_g, 8, 4, seed=s)}
                               for s in range(3)])["w"]
    seg = _segments([2, 0, 1, 2])
    text = jax.jit(fallback.segment_correction).lower(
        jnp.zeros((T, h_in)), stk, seg.seg_rows, seg.seg_offsets).as_text()
    found = _activation_gathers(text, T * h_in)
    if h_g <= fallback.SELECT_MAX_HG:
        assert found == []
    else:
        assert found


# ---------------------------------------------------------------------------
# Segment dispatch (fallback scan + Pallas kernel, interpret mode)
# ---------------------------------------------------------------------------
def _segment_oracle(x, stk, rows):
    dense = reconstruct_dense(stk)                   # [R, h_in, h_out]
    return jnp.einsum("b...d,bdf->b...f", x, dense[np.asarray(rows)])


@pytest.mark.parametrize("rows", [
    [0, 0, 0, 0],              # single tenant
    [2, 0, 2, 1, 0, 2, 1, 0],  # mixed, duplicates
    [1, 2, 0],                 # all distinct
])
def test_segment_fallback_parity(rows):
    stk, _ = _stacked(3)
    B = len(rows)
    x = jax.random.normal(jax.random.PRNGKey(5), (B, 128))
    seg = _segments(rows)
    xs = jnp.take(x, seg.order, axis=0)
    y = fallback.segment_correction(xs, stk, seg.seg_rows, seg.seg_offsets)
    y = jnp.take(y, seg.inv_order, axis=0)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_segment_oracle(x, stk, rows)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("h_out", [256, 96, 251])
def test_segment_kernel_interpret_parity(h_out):
    stk, _ = _stacked(3, h_out=h_out, h_g=128)
    assert ops.kernel_supported(stk.index(0))
    rows = [2, 0, 2, 1, 0, 2, 1, 0]
    B = len(rows)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, 128))
    seg = _segments(rows)
    xs = jnp.take(x, seg.order, axis=0)
    y = ops.delta_spmm_segments(xs, stk, seg.seg_rows, seg.seg_offsets,
                                interpret=True)
    y = jnp.take(y, seg.inv_order, axis=0)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_segment_oracle(x, stk, rows)),
                               atol=1e-4, rtol=1e-4)


def test_segment_kernel_multi_row_blocks():
    """T spanning several row tiles: segment/tile overlap logic."""
    stk, _ = _stacked(2, h_in=64, h_out=128, h_g=64, alpha=4)
    assert ops.kernel_supported(stk.index(0))
    rows = [0] * 5 + [1] * 11          # 16 rows, tb forced to 8
    B = len(rows)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, 64))
    seg = _segments(rows)
    xs = jnp.take(x, seg.order, axis=0)
    y = ops.delta_spmm_segments(xs, stk, seg.seg_rows, seg.seg_offsets,
                                tb=8, interpret=True)
    y = jnp.take(y, seg.inv_order, axis=0)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_segment_oracle(x, stk, rows)),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# apply-level dispatch seam
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["segments", "per_row"])
def test_slot_delta_matmul_modes(mode):
    stk_tree, _ = _stacked(3)
    rows = np.array([2, 0, 2, 1, 0, 1], np.int32)
    B = len(rows)
    x = jax.random.normal(jax.random.PRNGKey(8), (B, 1, 128))
    sd = wrap_slot_deltas({"w": stk_tree}, jnp.asarray(rows),
                          segments=_segments(rows))["w"]
    want = _segment_oracle(x, stk_tree, rows)
    prev = get_slot_dispatch()
    try:
        set_slot_dispatch(mode)
        got = jax.jit(lambda x, sd: slot_delta_matmul(x, sd))(x, sd)
    finally:
        set_slot_dispatch(prev)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_values_path_bit_identical_to_packed():
    """The pre-decoded residency path (values + res_map on the
    SlotDelta) must produce the EXACT bits of the packed segment
    dispatch — decode-ahead-of-time is the same elementwise math as
    decode-in-step, and the contraction is shared. Includes a permuted
    res_map (residency rows need not align with tenant rows)."""
    from repro.core.apply import SlotDelta
    from repro.core.pack import decode_values

    stk_tree, _ = _stacked(3)
    rows = np.array([2, 0, 2, 1, 0, 1], np.int32)
    B = len(rows)
    x = jax.random.normal(jax.random.PRNGKey(9), (B, 1, 128))
    seg = _segments(rows)
    packed = SlotDelta(stk_tree, jnp.asarray(rows), seg)
    want = np.asarray(jax.jit(slot_delta_matmul)(x, packed))

    # identity res_map: residency row == tenant row
    vals = decode_values(stk_tree)
    ident = SlotDelta(stk_tree, jnp.asarray(rows), seg, vals,
                      jnp.arange(vals.shape[0], dtype=jnp.int32))
    got = np.asarray(jax.jit(slot_delta_matmul)(x, ident))
    np.testing.assert_array_equal(got, want)

    # permuted residency buffer: slot order differs from tenant order
    perm = np.array([2, 0, 1], np.int32)       # residency slot -> tenant row
    buf = jnp.asarray(np.asarray(vals)[perm])
    res_map = np.zeros(vals.shape[0], np.int32)
    for slot, row in enumerate(perm):
        res_map[row] = slot
    permd = SlotDelta(stk_tree, jnp.asarray(rows), seg, buf,
                      jnp.asarray(res_map))
    got = np.asarray(jax.jit(slot_delta_matmul)(x, permd))
    np.testing.assert_array_equal(got, want)


def test_segments_layout_shapes_static():
    """Different tenant mixes must produce identical array shapes (one
    decode jit compilation regardless of the batch's tenant diversity)."""
    shapes = set()
    for rows in ([0, 0, 0, 0], [1, 2, 3, 0], [2, 2, 1, 1]):
        seg = tenant_segments(np.asarray(rows, np.int32))
        shapes.add((seg.order.shape, seg.inv_order.shape,
                    seg.seg_rows.shape, seg.seg_offsets.shape))
    assert len(shapes) == 1


def test_segments_layout_contents():
    seg = tenant_segments(np.array([2, 0, 2, 1], np.int32))
    np.testing.assert_array_equal(seg.order, [1, 3, 0, 2])
    np.testing.assert_array_equal(seg.seg_rows, [0, 1, 2, 0])
    np.testing.assert_array_equal(seg.seg_offsets, [0, 1, 2, 4, 4])
    np.testing.assert_array_equal(
        np.asarray(seg.order)[np.asarray(seg.inv_order)], np.arange(4))


# ---------------------------------------------------------------------------
# Full-envelope sweep (slow; fast subset above runs per-PR)
# ---------------------------------------------------------------------------
def _envelope_points():
    pts = []
    for h_g in (16, 64, 256):
        for keep in (1, 16, 128):
            if keep > h_g or h_g % keep:
                continue
            for k_bits in (None, 1, 2, 4, 8):
                pts.append((h_g, keep, k_bits))
    return pts


@pytest.mark.slow
@pytest.mark.parametrize("h_g,keep,k_bits", _envelope_points())
def test_kernel_envelope_sweep(h_g, keep, k_bits):
    """delta_spmm / fused / segments (interpret) vs the dense oracle
    across the whole supported envelope."""
    alpha = h_g // keep
    # inside the kernel envelope: h_g lane-aligned, or all of h_in
    h_in, h_out = (h_g if h_g % ops.LANES else 2 * h_g), 128
    p = _pack(h_in, h_out, h_g, alpha, k_bits, seed=h_g + keep)
    assert ops.kernel_supported(p), ops.kernel_refusal(p)
    x = jax.random.normal(jax.random.PRNGKey(9), (16, h_in))
    dense = reconstruct_dense(p)
    want = np.asarray(x @ dense)
    np.testing.assert_allclose(
        np.asarray(ops.delta_spmm(x, p, interpret=True)), want,
        atol=1e-3, rtol=1e-3)
    w = jax.random.normal(jax.random.PRNGKey(10), (h_in, h_out)) * 0.05
    np.testing.assert_allclose(
        np.asarray(ops.fused_base_delta(x, w, p, interpret=True)),
        np.asarray(x @ (w + dense)), atol=1e-3, rtol=1e-3)
    # 2-tenant stack through the segments kernel
    p2 = _pack(h_in, h_out, h_g, alpha, k_bits, seed=h_g + keep + 1)
    stk = stack_tenant_deltas([{"w": p}, {"w": p2}])["w"]
    rows = [1, 0, 1, 1]
    seg = _segments(rows)
    xs = jnp.take(x[:4], seg.order, axis=0)
    y = ops.delta_spmm_segments(xs, stk, seg.seg_rows, seg.seg_offsets,
                                interpret=True)
    y = jnp.take(y, seg.inv_order, axis=0)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_segment_oracle(x[:4], stk, rows)),
        atol=1e-3, rtol=1e-3)
