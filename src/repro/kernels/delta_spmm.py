"""Pallas TPU kernels for the DeltaDQ hot path.

TPU adaptation of the paper's CSR SpMM (DESIGN.md §3): the packed,
quantized, *structured*-sparse delta streams HBM->VMEM at compressed
width; inside VMEM each (group x out-tile) block is dequantized and
scattered to a dense [h_g, Ob] tile via the one-hot-compare idiom (TPU's
scatter), which then feeds the MXU as a regular dense matmul. HBM traffic
is compressed bytes only; the dense tile never leaves VMEM.

Kernels
    delta_spmm_kernel           y = x @ dequant(delta)
    fused_base_delta_kernel     y = x @ (W_base + dequant(delta))  (x read once)
    delta_spmm_segments_kernel  mixed-tenant decode: rows sorted by tenant,
                                each tenant's tile decoded ONCE per segment
    dequant_kernel              dense delta tile materialization

Grid: (T/Tb, O/Ob, G) with the group axis innermost ("arbitrary") so the
output tile accumulates in VMEM across groups; the segments kernel adds a
segment axis next to G (both "arbitrary", consecutive for a fixed output
block) and scalar-prefetches the tenant-segment layout so BlockSpec index
maps can route each segment to its tenant's compressed bytes. Supported
envelope (checked by ops.py, XLA fallback otherwise): h_g in {128, 256}
or h_g == h_in <= 256, keep <= 128. The x block is (Tb, h_g), and the
TPU lowering needs a block's last dim to be a multiple of 128 lanes or
the whole array, so the paper's smaller h_g* (16..64, Table 4) is the
fallback's job on the chip, as is row-wise h_g == h_in past 256.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# default kept-values-per-chunk for the in-VMEM scatter loop; bounds the
# one-hot working set to KC * h_g * Ob * 4B (= 1 MiB at 8 x 256 x 128).
# Autotune (kernels/autotune.py) can override per envelope point.
_KC = 8

# the per-tensor quant scalars (scale, zero) live whole in SMEM: a (1, 1)
# VMEM block over an [R, 1] array breaks the (8, 128) block-tiling rule
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _unpack_codes(codes, k_bits: int, keep: int):
    """[Kp, Ob] uint8 -> [keep, Ob] int32 codes (w = physical pack width)."""
    codes = codes.astype(jnp.int32)
    w = 1 if k_bits <= 1 else 2 if k_bits <= 2 else 4 if k_bits <= 4 else 8
    if w == 8:
        return codes
    per = 8 // w
    mask = 2**w - 1
    cols = [(codes >> (i * w)) & mask for i in range(per)]
    q = jnp.stack(cols, axis=1)                      # [Kp, per, Ob]
    q = q.reshape(codes.shape[0] * per, codes.shape[1])
    return q[:keep]


def _scatter_dense(idx, vals, h_g: int, keep: int, kc: int = _KC):
    """Build the dense [h_g, Ob] tile from (idx, vals) [keep, Ob] in VMEM.

    One-hot-compare scatter, chunked over `keep` (chunk size ``kc``) to
    bound the working set. The chunk loop is unrolled with static slice
    bounds: Mosaic has no lowering for a dynamic slice of a loaded value.
    """
    Ob = idx.shape[-1]
    iota_h = jax.lax.broadcasted_iota(jnp.int32, (1, h_g, Ob), 1)
    dense = jnp.zeros((h_g, Ob), jnp.float32)
    for c0 in range(0, keep, kc):
        sel_i = idx[c0:c0 + kc][:, None, :]          # [<=KC, 1, Ob]
        sel_v = vals[c0:c0 + kc][:, None, :]
        dense = dense + jnp.sum(jnp.where(sel_i == iota_h, sel_v, 0.0),
                                axis=0)
    return dense


def _decode_arrays(idx, codes, scale, zero, *, k_bits, keep, h_g, kc=_KC):
    """(idx [keep, Ob], codes [Kp|keep, Ob], scalars) -> dense [h_g, Ob]."""
    idx = idx.astype(jnp.int32)
    if k_bits is None:
        vals = codes.astype(jnp.float32)
    else:
        q = _unpack_codes(codes, k_bits, keep)
        vals = (q.astype(jnp.float32) - zero.astype(jnp.float32)) * scale
    return _scatter_dense(idx, vals, h_g, keep, kc)


def _decode_tile(idx_ref, codes_ref, scale_ref, zero_ref, *, k_bits, keep,
                 h_g, kc=_KC):
    return _decode_arrays(idx_ref[0], codes_ref[0], scale_ref[0, 0],
                          zero_ref[0, 0], k_bits=k_bits, keep=keep, h_g=h_g,
                          kc=kc)


# ---------------------------------------------------------------------------
# y = x @ dequant(delta)
# ---------------------------------------------------------------------------
def _spmm_body(x_ref, idx_ref, codes_ref, scale_ref, zero_ref, o_ref, *,
               k_bits, keep, h_g, kc):
    gi = pl.program_id(2)

    @pl.when(gi == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    dense = _decode_tile(idx_ref, codes_ref, scale_ref, zero_ref,
                         k_bits=k_bits, keep=keep, h_g=h_g, kc=kc)
    x = x_ref[...].astype(jnp.float32)               # [Tb, h_g]
    o_ref[...] += jnp.dot(x, dense, preferred_element_type=jnp.float32)


def delta_spmm_kernel(x, idx, codes, scale, zero, *, h_g: int, keep: int,
                      k_bits: Optional[int], h_out: int,
                      tb: int = 128, ob: int = 128, kc: int = _KC,
                      interpret: bool = False):
    """x [T, h_in]; idx [G, keep, O]; codes [G, Kp|keep, O]; -> [T, O] f32."""
    T, h_in = x.shape
    G = h_in // h_g
    Kp = codes.shape[1]
    tb = min(tb, T)
    ob = min(ob, h_out)
    if T % tb or h_out % ob:
        raise ValueError(
            f"kernel tiles must divide extents: T={T} %% tb={tb} and "
            f"h_out={h_out} %% ob={ob} must both be 0")
    grid = (T // tb, h_out // ob, G)
    return pl.pallas_call(
        functools.partial(_spmm_body, k_bits=k_bits, keep=keep, h_g=h_g, kc=kc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, h_g), lambda t, o, g: (t, g)),
            pl.BlockSpec((1, keep, ob), lambda t, o, g: (g, 0, o)),
            pl.BlockSpec((1, Kp, ob), lambda t, o, g: (g, 0, o)),
            _SMEM,
            _SMEM,
        ],
        out_specs=pl.BlockSpec((tb, ob), lambda t, o, g: (t, o)),
        out_shape=jax.ShapeDtypeStruct((T, h_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, idx, codes, scale, zero)


# ---------------------------------------------------------------------------
# y = x @ (W + dequant(delta))  — separate computation fused into one pass
# ---------------------------------------------------------------------------
def _fused_body(x_ref, w_ref, idx_ref, codes_ref, scale_ref, zero_ref, o_ref, *,
                k_bits, keep, h_g, kc):
    gi = pl.program_id(2)

    @pl.when(gi == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    dense = _decode_tile(idx_ref, codes_ref, scale_ref, zero_ref,
                         k_bits=k_bits, keep=keep, h_g=h_g, kc=kc)
    w = w_ref[...].astype(jnp.float32)               # [h_g, Ob]
    x = x_ref[...].astype(jnp.float32)               # [Tb, h_g]
    o_ref[...] += jnp.dot(x, w + dense, preferred_element_type=jnp.float32)


def fused_base_delta_kernel(x, w, idx, codes, scale, zero, *, h_g: int, keep: int,
                            k_bits: Optional[int],
                            tb: int = 128, ob: int = 128, kc: int = _KC,
                            interpret: bool = False):
    """x [T, h_in]; w [h_in, h_out]; packed delta -> [T, h_out] f32."""
    T, h_in = x.shape
    h_out = w.shape[1]
    G = h_in // h_g
    Kp = codes.shape[1]
    tb = min(tb, T)
    ob = min(ob, h_out)
    if T % tb or h_out % ob:
        raise ValueError(
            f"kernel tiles must divide extents: T={T} %% tb={tb} and "
            f"h_out={h_out} %% ob={ob} must both be 0")
    grid = (T // tb, h_out // ob, G)
    return pl.pallas_call(
        functools.partial(_fused_body, k_bits=k_bits, keep=keep, h_g=h_g, kc=kc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, h_g), lambda t, o, g: (t, g)),
            pl.BlockSpec((h_g, ob), lambda t, o, g: (g, o)),
            pl.BlockSpec((1, keep, ob), lambda t, o, g: (g, 0, o)),
            pl.BlockSpec((1, Kp, ob), lambda t, o, g: (g, 0, o)),
            _SMEM,
            _SMEM,
        ],
        out_specs=pl.BlockSpec((tb, ob), lambda t, o, g: (t, o)),
        out_shape=jax.ShapeDtypeStruct((T, h_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, idx, codes, scale, zero)


# ---------------------------------------------------------------------------
# mixed-tenant decode: batched slot kernel over tenant segments
# ---------------------------------------------------------------------------
def _segments_body(seg_rows_ref, seg_offs_ref, x_ref, idx_ref, codes_ref,
                   scale_ref, zero_ref, o_ref, *, k_bits, keep, h_g, tb, kc):
    t = pl.program_id(0)
    s = pl.program_id(2)
    gi = pl.program_id(3)

    @pl.when((s == 0) & (gi == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    start = seg_offs_ref[s]
    end = seg_offs_ref[s + 1]
    row0 = t * tb

    # skip empty segments and segments disjoint from this row block — the
    # decode work for each tenant happens once per (segment, tile), not
    # once per batch row
    @pl.when((end > start) & (start < row0 + tb) & (end > row0))
    def _():
        r = seg_rows_ref[s]
        dense = _decode_arrays(idx_ref[0, 0], codes_ref[0, 0],
                               scale_ref[r, 0], zero_ref[r, 0],
                               k_bits=k_bits, keep=keep, h_g=h_g, kc=kc)
        x = x_ref[...].astype(jnp.float32)            # [tb, h_g]
        y = jnp.dot(x, dense, preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
        mask = (rows >= start) & (rows < end)
        o_ref[...] += jnp.where(mask, y, 0.0)


def delta_spmm_segments_kernel(x, idx, codes, scale, zero, seg_rows,
                               seg_offsets, *, h_g: int, keep: int,
                               k_bits: Optional[int], h_out: int,
                               tb: int = 128, ob: int = 128, kc: int = _KC,
                               interpret: bool = False):
    """Mixed-tenant matmul with per-segment tile reuse.

    x [T, h_in] rows **sorted by tenant**; idx [R, G, keep, O] /
    codes [R, G, Kp, O] / scale,zero [R, 1] are the tenant-stacked packed
    delta; seg_rows [S] int32 maps segment -> tenant row; seg_offsets
    [S+1] int32 gives each segment's half-open row range (empty segments
    have equal offsets). Output [T, h_out] f32 where row r gets
    ``x[r] @ dequant(delta[tenant_of(r)])``.

    Grid: (T/Tb, O/Ob, S, G) — the segment and group axes are innermost
    and consecutive for a fixed output block, so the [Tb, Ob] accumulator
    stays in VMEM across every (segment, group) visit and each tenant's
    [h_g, Ob] tile is decoded exactly once per (segment, tile) instead of
    once per batch row. seg_rows/seg_offsets are scalar-prefetched so the
    idx/codes BlockSpec index maps can select the segment's tenant row.
    """
    T, h_in = x.shape
    G = h_in // h_g
    Kp = codes.shape[2]
    S = seg_rows.shape[0]
    tb = min(tb, T)
    ob = min(ob, h_out)
    if T % tb or h_out % ob:
        raise ValueError(
            f"kernel tiles must divide extents: T={T} %% tb={tb} and "
            f"h_out={h_out} %% ob={ob} must both be 0")
    if seg_offsets.shape[0] != S + 1:
        raise ValueError(
            f"seg_offsets has {seg_offsets.shape[0]} entries for {S} "
            f"segments (needs S+1={S + 1} fenceposts)")
    grid = (T // tb, h_out // ob, S, G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, h_g), lambda t, o, s, g, sr, so: (t, g)),
            pl.BlockSpec((1, 1, keep, ob),
                         lambda t, o, s, g, sr, so: (sr[s], g, 0, o)),
            pl.BlockSpec((1, 1, Kp, ob),
                         lambda t, o, s, g, sr, so: (sr[s], g, 0, o)),
            _SMEM,
            _SMEM,
        ],
        out_specs=pl.BlockSpec((tb, ob), lambda t, o, s, g, sr, so: (t, o)),
    )
    return pl.pallas_call(
        functools.partial(_segments_body, k_bits=k_bits, keep=keep, h_g=h_g,
                          tb=tb, kc=kc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, h_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(seg_rows, seg_offsets, x, idx, codes, scale, zero)


# ---------------------------------------------------------------------------
# dense delta materialization (merge / eval path)
# ---------------------------------------------------------------------------
def _dequant_body(idx_ref, codes_ref, scale_ref, zero_ref, o_ref, *,
                  k_bits, keep, h_g, kc):
    o_ref[...] = _decode_tile(idx_ref, codes_ref, scale_ref, zero_ref,
                              k_bits=k_bits, keep=keep, h_g=h_g, kc=kc)


def dequant_kernel(idx, codes, scale, zero, *, h_g: int, keep: int,
                   k_bits: Optional[int], h_out: int,
                   ob: int = 128, kc: int = _KC, interpret: bool = False):
    """Packed delta -> dense [h_in, h_out] f32."""
    G = idx.shape[0]
    Kp = codes.shape[1]
    ob = min(ob, h_out)
    if h_out % ob:
        raise ValueError(f"ob={ob} must divide h_out={h_out}")
    grid = (G, h_out // ob)
    return pl.pallas_call(
        functools.partial(_dequant_body, k_bits=k_bits, keep=keep, h_g=h_g,
                          kc=kc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, keep, ob), lambda g, o: (g, 0, o)),
            pl.BlockSpec((1, Kp, ob), lambda g, o: (g, 0, o)),
            _SMEM,
            _SMEM,
        ],
        out_specs=pl.BlockSpec((h_g, ob), lambda g, o: (g, o)),
        out_shape=jax.ShapeDtypeStruct((G * h_g, h_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel")),
        interpret=interpret,
    )(idx, codes, scale, zero)
