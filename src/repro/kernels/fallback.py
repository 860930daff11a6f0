"""Pure-XLA delta-correction formulations (the non-Pallas hot path).

On hosts without a TPU (CPU CI, the bench host) the delta correction is
plain XLA, and its formulation dominates the decode-path overhead. Two
mathematically identical formulations with opposite scaling:

* :func:`dense_correction` — scatter the packed delta to a dense
  ``[h_in, h_out]`` matrix, then one dense matmul. The scatter cost is
  paid once regardless of T, so it wins for prefill-sized token counts.
* :func:`gather_correction` — never materialize the dense delta: pick
  each kept element's activation by its in-group index and contract
  against the dequantized values directly
  (``y[t,o] = sum_{g,k} x[t, g*h_g + idx[g,k,o]] * val[g,k,o]``).
  Work is ``T * nnz`` instead of ``nnz`` scatter + ``T * h_in * h_out``
  matmul — at decode shapes (T = a handful of slots) this is 5-20x
  faster and is what collapses the serve-time delta overhead.

:func:`correction` picks between them by token count; the crossover is
the autotuned ``gather_max_t`` (kernels/autotune.py).

How a kept element's activation is picked depends on the group size.
A DeltaDQ index is local to its dropout group, ``idx[g,k,o] < h_g``, so
the activation is one of the h_g values ``x[t, g*h_g : (g+1)*h_g]``.
For ``h_g <= SELECT_MAX_HG`` the pick is a select tree over the index's
bits on those h_g columns (static slices): vector-unit work, where the
TPU's elementwise gather costs ~14 cycles an element. Above it the pick
is a gather by the flat ``h_in`` index: the select's work and compile
time grow with h_g, the gather's do not. A select moves its operand's
bits (-0.0 and NaN included), so both picks hand the same
multiply-reduce the same values.

Mixed-tenant decode adds two more:

* :func:`gather_correction_rows` — per-row deltas (a row-gathered
  ``[B]`` stack): the same gather contraction with per-row values. This
  replaces the old ``[B, h_in, h_out]`` dense reconstruction, whose
  memory blew up B-fold even when every row shared one tenant.
* :func:`segment_correction` — the unique-tenant dispatch: rows sorted
  by tenant, a scan over (statically shaped, possibly empty) tenant
  segments that dequantizes each *unique* delta once and applies it to
  the whole batch with rows outside the segment masked. The per-segment
  contraction is the exact same ``gather_correction`` primitive the
  single-tenant path uses, which keeps mixed-stream decode bit-identical
  to the per-tenant reference engine.

Bit-identity note: the gather contraction is written as an elementwise
multiply followed by ``sum`` over one merged (group, keep) axis — NOT a
dot_general/einsum — because XLA's dot reduction order varies with the
batch extent, while the reduce op's per-(row, column) inner loop does
not. The token-identity contract (mixed-slot decode == per-tenant
reference decode, exact) depends on this: the same row correction must
produce the same bits whether the row is decoded alone, in a tenant
group, or in a mixed slot batch.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.pack import PackedDelta, decode_values, reconstruct_dense


def _note(site: str, **attrs) -> None:
    """Report the chosen formulation to an open trace context (no-op
    otherwise). Lazy import: the serve package's __init__ imports the
    engine, which imports this module."""
    from repro.serve.trace import note_path
    note_path(site, **attrs)


# Largest dropout group whose kept activations are picked by an in-group
# select; larger groups take the flat-index gather (module docstring).
# On a v5e (N 8, h_in 5120, h_out 17920) the select took 4.8 / 2.8 / 6.1 ms
# at h_g 16 / 64 / 128 against the gather's ~866 ms at each; compiling it
# for a v5e (on a CPU host) took 3.0 s at 64, 9.5 s at 128 and 88 s at
# 256, so it stops at 128.
SELECT_MAX_HG = 128


def _pick(h_g: int) -> str:
    """Which way :func:`_rows_core` picks activations at group size h_g."""
    return "select" if h_g <= SELECT_MAX_HG else "gather"


def dense_correction(x2: jnp.ndarray, d: PackedDelta) -> jnp.ndarray:
    """x2 [T, h_in] @ dense(delta) -> [T, h_out] f32 (reconstruct path).

    Computed transposed, with the output columns as the product's rows:
    XLA:CPU gives a row of a matmul the same bits for any row count, but
    not a column for any column count. So the mesh path's per-shard
    column slice bit-matches the replicated product. The barrier keeps
    the simplifier from folding the transposes back into ``x @ dense``.
    """
    dense_t, x_t = jax.lax.optimization_barrier(
        (reconstruct_dense(d).T, x2.astype(jnp.float32).T))
    return (dense_t @ x_t).T


def gather_correction(x2: jnp.ndarray, d: PackedDelta) -> jnp.ndarray:
    """x2 [T, h_in] -> [T, h_out] f32 without materializing the dense delta."""
    vals = decode_values(d)                          # [G, K, O] f32
    G, K, O = vals.shape
    T = x2.shape[0]
    # the per-row paths' contraction, every row on this one delta: one
    # pick + reduce shape for shared and per-row deltas, one set of bits
    return _rows_core(x2, jnp.broadcast_to(d.idx, (T, G, K, O)),
                      jnp.broadcast_to(vals.reshape(1, G * K, O),
                                       (T, G * K, O)))


def correction(x2: jnp.ndarray, d: PackedDelta, *,
               gather_max_t: int = 64) -> jnp.ndarray:
    """Formulation chooser: gather for decode-sized T, dense otherwise."""
    if x2.shape[0] <= gather_max_t:
        _note("correction", formulation=f"xla-{_pick(d.h_g)}", codec=d.codec,
              T=int(x2.shape[0]), gather_max_t=int(gather_max_t))
        return gather_correction(x2, d)
    _note("correction", formulation="xla-dense", codec=d.codec,
          T=int(x2.shape[0]), gather_max_t=int(gather_max_t))
    return dense_correction(x2, d)


def correction_nd(x: jnp.ndarray, d: PackedDelta, *,
                  gather_max_t: Optional[int] = None) -> jnp.ndarray:
    """x [..., h_in] -> [..., h_out] f32: flatten leading dims, choose the
    formulation, restore shape.

    The ONE entry point for every XLA-fallback correction site
    (replicated apply path, out-of-envelope ops path, sharded shard_map
    body) — the token-identity contract requires all of them to choose
    the same formulation with the same autotune key, so the lookup lives
    here. Pass ``gather_max_t`` to pin the decision externally (the
    sharded path decides on the GLOBAL envelope, then applies it to the
    local column slice).
    """
    if gather_max_t is None:
        from repro.kernels import autotune
        gather_max_t = autotune.lookup(
            d.h_g, d.keep, d.k_bits, d.h_in, d.h_out,
            t=x.size // x.shape[-1])["gather_max_t"]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d.h_in)
    y = correction(x2, d, gather_max_t=gather_max_t)
    return y.reshape(*lead, d.h_out)


def _select_in_group(x3: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """x3 [N, G, h_g], idx [N, G, K, O] in-group indices -> [N, G, K, O]
    with ``out[n,g,k,o] = x3[n, g, idx[n,g,k,o]]``.

    A select tree over idx's bits: level b pairs the surviving columns
    that differ in bit b, so h_g columns take h_g - 1 selects and
    log2(h_g) bit tests per element. Columns are static slices
    (``lax.index_in_dim`` lowers to a slice; ``jnp.take`` would lower to
    a gather), padded to a power of two with columns no index reaches.
    """
    h_g = x3.shape[2]
    idx = idx.astype(jnp.int32)
    cols = [jax.lax.index_in_dim(x3, j, axis=2, keepdims=False)[..., None, None]
            for j in range(h_g)]
    cols += cols[-1:] * ((1 << (h_g - 1).bit_length()) - h_g)
    bit = 1
    while len(cols) > 1:
        hi = (idx & bit) != 0
        cols = [jnp.where(hi, c1, c0) for c0, c1 in zip(cols[0::2], cols[1::2])]
        bit <<= 1
    return jnp.broadcast_to(cols[0], idx.shape)


def _rows_core(x_rows: jnp.ndarray, idx: jnp.ndarray,
               vals: jnp.ndarray) -> jnp.ndarray:
    """Shared per-row contraction: x_rows [N, h_in], idx [N, G, K, O]
    in-group indices, vals [N, G*K, O] -> [N, O] f32.

    Every per-row path (row-gathered stack, segment dispatch) funnels
    through this one function so the pick + reduce shapes — and
    therefore the bits — are identical across dispatch modes. The pick
    is :func:`_select_in_group` up to ``SELECT_MAX_HG``, else a gather by
    flat ``h_in`` index; either copies the same activations.
    """
    N, h_in = x_rows.shape
    _, G, K, O = idx.shape
    h_g = h_in // G
    x = x_rows.astype(jnp.float32)
    if _pick(h_g) == "select":
        # one pick + reduce whatever built the operands: XLA:CPU fuses the
        # selects into a reduce it vectorizes with reassociation, and
        # without the barrier that order followed the callers' structure
        # (constants, broadcasts, row gathers), not just the shapes
        x, idx, vals = jax.lax.optimization_barrier((x, idx, vals))
        sel = _select_in_group(x.reshape(N, G, h_g), idx).reshape(
            N, G * K, O)
    else:
        base = (jnp.arange(G, dtype=jnp.int32) * h_g)[:, None, None]
        gidx = (idx.astype(jnp.int32) + base).reshape(N, G * K * O)
        sel = jnp.take_along_axis(x, gidx, axis=1).reshape(N, G * K, O)
    return (sel * vals).sum(axis=1)


def gather_correction_rows(x: jnp.ndarray, d: PackedDelta,
                           values: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Per-row deltas: x [B, ..., h_in], d row-stacked [B] -> [B, ..., h_out].

    Peak extra memory is ``B * nnz`` floats (the picked activations),
    not ``B * h_in * h_out`` — rows sharing a tenant no longer multiply a
    dense reconstruction.

    ``values`` (optional f32 [B, G, K, O]) supplies pre-decoded kept
    values and skips the in-graph code unpack — the residency fast
    path. The decode is elementwise (``(q - z) * s`` after a bit
    unpack), so values decoded ahead of time are bit-identical to
    values decoded in-step, and the contraction below is unchanged —
    which is what lets the residency tier keep the token-identity
    contract.
    """
    B = x.shape[0]
    vals = decode_values(d) if values is None else values   # [B, G, K, O]
    _, G, K, O = vals.shape
    x2 = x.astype(jnp.float32).reshape(B, -1, d.h_in)
    T = x2.shape[1]
    # flatten (row, token) so the reduce shape matches gather_correction's
    # [rows, G*K, O] exactly — same bits as the shared-tenant path
    x_rows = x2.reshape(B * T, d.h_in)
    idx_rows = jnp.broadcast_to(
        d.idx[:, None], (B, T, G, K, O)).reshape(B * T, G, K, O)
    vals_rows = jnp.broadcast_to(
        vals.reshape(B, 1, G * K, O), (B, T, G * K, O)).reshape(B * T, G * K, O)
    y = _rows_core(x_rows, idx_rows, vals_rows)
    return y.reshape(*x.shape[:-1], d.h_out)


def segment_correction(x2: jnp.ndarray, d: PackedDelta,
                       seg_rows: jnp.ndarray,
                       seg_offsets: jnp.ndarray,
                       values: Optional[jnp.ndarray] = None,
                       res_map: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Unique-tenant dispatch: x2 [T, h_in] rows sorted by tenant.

    ``d`` is the tenant-stacked packed delta [R, ...]; ``seg_rows`` [S]
    maps segment -> tenant row and ``seg_offsets`` [S+1] gives each
    segment's half-open row range (S is a static shape — padding
    segments are empty). The packed (still-compressed) bytes are routed
    to rows through the segment map and contracted by the same
    :func:`_rows_core` the per-row path uses — identical pick/reduce
    shapes, identical bits.

    ``values``/``res_map`` (optional) select the pre-decoded residency
    tier: ``values`` f32 [C, G, K, O] holds decoded kept values for C
    resident tenant rows and ``res_map`` int32 [R] maps tenant row ->
    residency row. The per-step code unpack is skipped entirely — the
    dequant happened once at promotion time with the same elementwise
    math, so the bits entering :func:`_rows_core` are unchanged (the
    residency tier preserves the token-identity contract).

    Note on CPU economics: XLA has no cross-row tile reuse, so the
    unique-tenant *compute* dedup does not pay here on the packed path —
    gathering f32 dequantized values per unique tenant costs more than
    re-unpacking the (8x smaller) packed codes per row. This fallback
    therefore matches the per-row path's work; the genuine dedup lives
    in (a) the Pallas segments kernel, which decodes each [h_g, Ob]
    VMEM tile once per segment instead of once per row (gated by
    kernel_bench), and (b) the residency values path above, which
    removes the unpack from the step altogether.
    """
    T = x2.shape[0]
    form = "segments-xla-select" if _pick(d.h_g) == "select" else "segments-xla"
    _note("segment_correction", formulation=form, codec=d.codec,
          residency="values" if values is not None else "packed", T=int(T))
    # map each (sorted) row to its segment: count of segment ends <= row
    rows_iota = jnp.arange(T, dtype=jnp.int32)
    row_seg = (rows_iota[:, None] >= seg_offsets[None, 1:]).sum(axis=1)
    tenant_rows = seg_rows[row_seg]                  # [T]
    dl = PackedDelta(
        d.idx[tenant_rows], d.codes[tenant_rows],
        jnp.asarray(d.scale, jnp.float32)[tenant_rows],
        jnp.asarray(d.zero, jnp.int32)[tenant_rows],
        d.h_in, d.h_out, d.h_g, d.keep, d.alpha, d.k_bits, d.m, d.codec)
    vals = None
    if values is not None:
        vals = values[res_map[tenant_rows]]          # [T, G, K, O] f32
    return gather_correction_rows(x2[:, None, :], dl, values=vals)[:, 0]
