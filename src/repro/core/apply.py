"""Delta application — the paper's separate-computation scheme (§3.1, Fig. 3).

Every linear site in the model zoo routes through :func:`apply_linear`:

    y = x @ W_base            (+ x @ dequant(packed delta)   if delta given)

On TPU hot paths the correction term is the Pallas ``delta_spmm`` kernel
(scatter-to-dense in VMEM + MXU); under SPMD dry-runs and CPU tests the
mathematically identical XLA fallback below is used (config
``use_pallas_kernels``). Both share the pure-jnp oracle in
``repro/kernels/ref.py`` for tests.

Multi-tenant slot dispatch: the continuous-batching engine serves one
decode step whose batch rows belong to *different* tenants. For that it
stacks every tenant's :class:`PackedDelta` along a new leading axis
(:func:`stack_tenant_deltas`) and wraps each leaf in a :class:`SlotDelta`
carrying the per-row tenant index, so ``apply_linear`` gathers each row's
delta before applying the correction.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.pack import PackedDelta, reconstruct_dense

# Global switch flipped by serving/launch configs. The Pallas path only
# lowers on real TPUs; everything else uses the XLA fallback.
_USE_PALLAS = False

# Mixed-tenant decode dispatch mode. "segments" (default) groups batch
# rows by tenant so each unique delta is dequantized once per step
# (requires the SlotDelta to carry a TenantSegments layout — built host-
# side by serve.scheduler.tenant_segments). "per_row" is the legacy
# path: gather a per-row delta stack and reconstruct/apply per row.
_SLOT_DISPATCH = "segments"

# The named scope every delta correction runs under (apply_linear,
# apply_linear_batched). It lands in each correction op's HLO ``op_name``
# metadata, whatever the formulation (gather, dense, segments, Pallas,
# sharded), so a profile attributes device time to the correction by
# scope. The optimization barrier at the same site keeps correction ops
# out of the base matmul's fusions. Metadata only: bits, fusion and the
# number of compiles are unchanged.
CORRECTION_SCOPE = "delta_correction"

# Active serving mesh (set by mesh-mode engines/launchers). When a mesh
# with a >1 `model` axis is installed, every delta correction routes
# through the shard_map'd output-column-partitioned path in
# ``kernels.ops.delta_correction_sharded`` — each shard touches only its
# own slice of the compressed bytes. One mesh per process.
_MESH = None


def _note(site: str, **attrs) -> None:
    """Report the chosen dispatch to an open trace context (no-op
    otherwise). Lazy import: serve's __init__ imports the engine, which
    imports this module."""
    from repro.serve.trace import note_path
    note_path(site, **attrs)


def set_use_pallas(flag: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = flag


def get_use_pallas() -> bool:
    return _USE_PALLAS


def set_slot_dispatch(mode: str) -> None:
    """Select the mixed-tenant decode dispatch: "segments" | "per_row"."""
    if mode not in ("segments", "per_row"):
        raise ValueError(
            f"slot_dispatch mode {mode!r} not in ('segments', 'per_row')")
    global _SLOT_DISPATCH
    _SLOT_DISPATCH = mode


def get_slot_dispatch() -> str:
    return _SLOT_DISPATCH


def set_mesh(mesh) -> None:
    """Install (or clear, with None) the process-wide serving mesh."""
    global _MESH
    _MESH = mesh


def get_mesh():
    return _MESH


def _sharded_correction(x: jnp.ndarray, d: PackedDelta):
    """Mesh-partitioned delta correction, or None if it doesn't apply."""
    if _MESH is None:
        return None
    from repro.kernels import ops
    return ops.delta_correction_sharded(x, d, _MESH, use_pallas=_USE_PALLAS)


def _pinned(c: Any) -> Any:
    """Pin a fusion boundary: ``optimization_barrier`` over a pytree.

    Used for the correction's boundary (bit-identity across mesh
    layouts, see apply_linear) and to tie a layer's delta decode to its
    activations (see _segment_dispatch). The barrier is an identity
    function and jax differentiates it, so ``deltas=`` forwards stay
    differentiable.
    """
    return jax.lax.optimization_barrier(c)


def _replicated(t: jnp.ndarray) -> jnp.ndarray:
    """Pin an activation replicated over the serving mesh.

    The serve layout is column-parallel only: weights shard their output
    axis, never the contraction axis, and activations are gathered back
    to replicated after every linear site. Every matmul then reduces
    over the full contraction locally — in the same order as a single
    device — which is what makes sharded decode bit-identical to the
    single-device engine (the CI token-identity check). At decode batch
    sizes the gathered activations are tiny; the multi-GB object (the
    base) stays sharded in HBM.
    """
    if _MESH is None:
        return t
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(_MESH, PartitionSpec()))


@jax.tree_util.register_pytree_node_class
@dataclass
class TenantSegments:
    """Static-shape tenant-segment layout for a mixed decode batch.

    Built host-side (``serve.scheduler.tenant_segments``) from the
    per-slot tenant rows: batch rows are sorted (stably) by tenant so
    each unique tenant occupies one contiguous segment. All arrays have
    shapes that depend only on the slot count B, so the decode step
    still compiles exactly once:

      order       int32 [B]    row permutation (sorted by tenant row)
      inv_order   int32 [B]    inverse permutation (unsort the output)
      seg_rows    int32 [B]    tenant row per segment (padding rows 0)
      seg_offsets int32 [B+1]  half-open row ranges; empty segments have
                               equal offsets and are skipped at runtime

    A :class:`ShardedTenantSegments` flattened with
    ``global_order()``/``global_segments()`` is also a valid instance
    of this layout: rows sorted by tenant only within each contiguous
    shard pool, each pool contributing its own segment run (a tenant on
    two shards gets two segments). Nothing downstream changes —
    segments are consumed only as (tenant row, contiguous range) pairs,
    so the same envelope and the same jit signature serve data=1 and
    data=N — but because the permutation never crosses a pool boundary,
    the sorted batch partitions over the mesh ``data`` axis exactly
    like the slot rows, and every segment's work stays on the shard
    hosting its rows.
    """
    order: jnp.ndarray
    inv_order: jnp.ndarray
    seg_rows: jnp.ndarray
    seg_offsets: jnp.ndarray

    def tree_flatten(self):
        return (self.order, self.inv_order, self.seg_rows,
                self.seg_offsets), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclass
class ShardedTenantSegments:
    """Per-data-shard tenant-segment layout (``data > 1`` decode).

    Built host-side by ``serve.scheduler.tenant_segments_sharded`` from
    the per-slot tenant rows: each contiguous shard pool of
    B_s = B / D slots sorts its own rows by tenant and carries its own
    (pool-local) segment list. All arrays are [D, B_s]-shaped — the
    static global envelope — so one jit signature serves every step:

      order       int32 [D, B_s]    pool-LOCAL row permutation
      inv_order   int32 [D, B_s]    its inverse (also pool-local)
      seg_rows    int32 [D, B_s]    tenant row per segment (padding 0)
      seg_offsets int32 [D, B_s+1]  pool-local half-open ranges

    The leading D axis partitions over the mesh ``data`` axis inside the
    shard_map'd correction: each device shard receives exactly its
    pool's rows and its pool's segment list, so it dequantizes only the
    tenants it actually hosts. :meth:`global_order` /
    :meth:`global_segments` flatten to the equivalent single-pool
    layout (block-diagonal permutation, concatenated segment runs) for
    the unsharded execution paths — bit-identical by construction.
    """
    order: jnp.ndarray
    inv_order: jnp.ndarray
    seg_rows: jnp.ndarray
    seg_offsets: jnp.ndarray

    def tree_flatten(self):
        return (self.order, self.inv_order, self.seg_rows,
                self.seg_offsets), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def data_shards(self) -> int:
        return self.order.shape[0]

    def global_order(self):
        """Flatten to global [B] (order, inv_order). The permutation is
        block-diagonal (never crosses a pool), so the global inverse is
        the per-pool inverse shifted by each pool's base offset."""
        D, Bs = self.order.shape
        base = (jnp.arange(D, dtype=jnp.int32) * Bs)[:, None]
        return ((jnp.asarray(self.order) + base).reshape(D * Bs),
                (jnp.asarray(self.inv_order) + base).reshape(D * Bs))

    def global_segments(self):
        """Flatten to the global [B] seg_rows / [B+1] seg_offsets form
        (each pool's padding segments collapse onto its end boundary, so
        offsets stay monotone and segments never cross a pool)."""
        D, Bs = self.seg_rows.shape
        B = D * Bs
        base = (jnp.arange(D, dtype=jnp.int32) * Bs)[:, None]
        sr = jnp.asarray(self.seg_rows).reshape(B)
        so = jnp.concatenate([
            (jnp.asarray(self.seg_offsets)[:, :Bs] + base).reshape(B),
            jnp.full((1,), B, jnp.int32)])
        return sr, so


@jax.tree_util.register_pytree_node_class
@dataclass
class SlotDelta:
    """A tenant-stacked :class:`PackedDelta` plus per-batch-row tenant ids.

    ``delta`` arrays carry a leading tenant axis T (then, optionally, the
    per-kind layer stack): idx/codes [T, *lead, G, K, O], scale/zero
    [T, *lead]. ``slots`` is int32 [B] mapping each batch row to a tenant
    row; row 0 is conventionally the zero delta (base model).
    ``segments`` (optional) carries the sorted tenant-segment layout
    consumed by the unique-tenant dispatch — either the single-pool
    :class:`TenantSegments` or, for ``data > 1`` serving, the per-shard
    :class:`ShardedTenantSegments`.

    ``values``/``res_map`` (optional, only with ``segments``) carry the
    pre-decoded delta residency tier (``serve.engine.DeltaResidency``):
    ``values`` f32 [C, *lead, G, K, O] holds ``pack.decode_values``
    output for C *resident* tenant rows, ``res_map`` int32 [T] maps a
    tenant row to its residency row (rows the engine did not make
    resident this step map to 0 and are never referenced by a live
    segment). When present, the segment dispatch skips the per-step
    code unpack and reads the decoded values directly; the packed
    arrays still ride along for the index gather, and every path
    without values decodes the codes as before (the always-correct
    fallback).
    """
    delta: PackedDelta
    slots: jnp.ndarray
    segments: Optional[Any] = None
    values: Optional[jnp.ndarray] = None
    res_map: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return (self.delta, self.slots, self.segments, self.values,
                self.res_map), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def index(self, i) -> "SlotDelta":
        """Slice the *layer* stack (axis 1, after the tenant axis)."""
        d = self.delta
        return SlotDelta(PackedDelta(
            d.idx[:, i], d.codes[:, i],
            d.scale[:, i] if jnp.ndim(d.scale) >= 2 else d.scale,
            d.zero[:, i] if jnp.ndim(d.zero) >= 2 else d.zero,
            d.h_in, d.h_out, d.h_g, d.keep, d.alpha, d.k_bits, d.m, d.codec),
            self.slots, self.segments,
            self.values[:, i] if self.values is not None else None,
            self.res_map)

    def gather(self) -> PackedDelta:
        """Per-row delta: [B, G, K, O] gathered from the tenant stack."""
        d = self.delta
        s = self.slots
        return PackedDelta(
            d.idx[s], d.codes[s],
            jnp.asarray(d.scale, jnp.float32)[s],
            jnp.asarray(d.zero, jnp.int32)[s],
            d.h_in, d.h_out, d.h_g, d.keep, d.alpha, d.k_bits, d.m, d.codec)


@jax.tree_util.register_pytree_node_class
@dataclass
class MultiSlotDelta:
    """Mixed-codec decode: one :class:`SlotDelta` part per codec group.

    The engine cannot stack tenants whose runtime packings differ (codec,
    group size, quantization width...), so it stacks each compatible
    *group* separately and routes every group's rows through that group's
    own segment layout. Rows a group does not own map to its row 0 — the
    zero delta — so the per-leaf correction is simply the SUM of the
    parts' corrections: exactly one part contributes the row's real
    correction and every other part contributes an exact 0.0, keeping
    mixed-codec decode token-identical to serving each tenant alone.
    """
    parts: tuple

    def tree_flatten(self):
        return tuple(self.parts), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children))

    def index(self, i) -> "MultiSlotDelta":
        return MultiSlotDelta(tuple(p.index(i) for p in self.parts))


def combine_slot_deltas(wrapped: list) -> Any:
    """Merge per-group slot-wrapped trees (see ``wrap_slot_deltas``) into
    one tree of :class:`MultiSlotDelta` leaves (identity for one group)."""
    if len(wrapped) == 1:
        return wrapped[0]
    return jax.tree.map(lambda *ls: MultiSlotDelta(ls), *wrapped,
                        is_leaf=lambda x: isinstance(x, SlotDelta))


def _row_sharded(t: jnp.ndarray) -> jnp.ndarray:
    """Pin a [rows, ...] array's leading axis over the mesh ``data`` axis.

    Used inside the segment dispatch when the active mesh has a ``data``
    axis > 1: the slot-sorted batch (whose permutation never crosses a
    shard-pool boundary — see TenantSegments) then partitions over
    ``data`` like the KV slot rows do, so each shard's segment
    corrections read and write only local rows. No-op without a mesh,
    with data=1, or when the row count doesn't divide (batch-1 prefill).
    """
    if _MESH is None or _MESH.shape.get("data", 1) <= 1 \
            or t.shape[0] % _MESH.shape["data"]:
        return t
    from jax.sharding import NamedSharding, PartitionSpec
    spec = PartitionSpec(*(["data"] + [None] * (t.ndim - 1)))
    return jax.lax.with_sharding_constraint(t, NamedSharding(_MESH, spec))


def _segment_dispatch(x: jnp.ndarray, sd: SlotDelta) -> jnp.ndarray:
    """Unique-tenant correction: sort rows by tenant, dequantize each
    unique delta once, apply per segment, unsort. x [B, ..., h_in].

    With a :class:`ShardedTenantSegments` layout the mesh path hands the
    per-shard [D, B_s] arrays straight to the shard_map'd correction
    (each data shard processes its own pool's rows and segments); every
    other path runs the flattened global-envelope equivalent, which is
    the same permutation and the same per-row bits.
    """
    seg = sd.segments
    # Tie the packed delta to this layer's activations. Nothing below
    # reads x before the contraction, so without the tie XLA hoists
    # every layer's per-row delta gather and decode to the start of the
    # step and keeps all of them live at once: for an 8-slot decode at
    # Llama-3.2-1B widths, 9.6 GB of temporaries in a v5e compile
    # against 2.1 GB with the tie.
    x, d, values = _pinned((x, sd.delta, sd.values))
    sd = SlotDelta(d, sd.slots, seg, values, sd.res_map)
    B = x.shape[0]
    lead = x.shape[1:-1]
    tokens_per_row = 1
    for n in lead:
        tokens_per_row *= n
    sharded = isinstance(seg, ShardedTenantSegments)
    order, inv_order = seg.global_order() if sharded \
        else (seg.order, seg.inv_order)
    xs = jnp.take(x, order, axis=0)
    x2 = _row_sharded(xs.reshape(B * tokens_per_row, d.h_in))
    y2 = None
    if _MESH is not None:
        from repro.kernels import ops
        # ranges (pool-local [D, B_s+1] or global [B+1]) scale with the
        # tokens folded out of each batch row; ops detects the per-shard
        # form by its 2-D seg_rows
        y2 = ops.delta_correction_sharded(
            x2, d, _MESH, use_pallas=_USE_PALLAS,
            segments=(seg.seg_rows, seg.seg_offsets * tokens_per_row),
            values=sd.values, res_map=sd.res_map)
    if y2 is None:
        sr, so = seg.global_segments() if sharded \
            else (seg.seg_rows, seg.seg_offsets)
        # row ranges scale with the tokens folded out of each batch row
        y2 = _segment_local(x2, d, sr, so * tokens_per_row,
                            sd.values, sd.res_map)
    # same dtype round-trip as every other path (no-op for f32)
    y = y2.reshape(B, *lead, d.h_out).astype(x.dtype)
    return jnp.take(y, inv_order, axis=0)


def _segment_local(x2, d, seg_rows, seg_offsets, values=None, res_map=None):
    from repro.kernels import fallback, ops
    if _USE_PALLAS:
        return ops.delta_spmm_segments(x2, d, seg_rows, seg_offsets,
                                       values=values, res_map=res_map)
    return fallback.segment_correction(x2, d, seg_rows, seg_offsets,
                                       values=values, res_map=res_map)


def slot_delta_matmul(x: jnp.ndarray, sd: SlotDelta) -> jnp.ndarray:
    """Mixed-tenant correction: x [B, S, h_in] with row b using tenant
    slots[b].

    Default ("segments" dispatch, when the SlotDelta carries a
    TenantSegments layout): rows are grouped by tenant so each *unique*
    delta is dequantized once per step. Fallback ("per_row" dispatch, or
    no layout attached): gather each row's packed delta (tiny vs dense)
    then contract per row; on TPU hot paths the gathered stack routes
    through the vmapped Pallas kernel. The per-row path is the legacy
    behavior, kept selectable via :func:`set_slot_dispatch`.
    """
    if sd.segments is not None and _SLOT_DISPATCH == "segments":
        _note("slot_dispatch", dispatch="segments")
        return _segment_dispatch(x, sd)
    _note("slot_dispatch", dispatch="per_row")
    g = sd.gather()
    y = _sharded_correction(x, g)
    if y is not None:
        return y
    from repro.kernels import fallback, ops
    if _USE_PALLAS:
        return ops.delta_spmm_slots(x, g)
    # per-row gather: never materializes the dense [B, h_in, h_out]
    # stack, and bit-matches the shared-tenant gather formulation
    _note("slot_dispatch", formulation="per-row-gather")
    return fallback.gather_correction_rows(x, g).astype(x.dtype)


def delta_matmul(x: jnp.ndarray, d) -> jnp.ndarray:
    """x [..., h_in] @ dequant(delta) [h_in, h_out] -> [..., h_out]."""
    if isinstance(d, MultiSlotDelta):
        # mixed-codec groups: sum the per-group corrections in f32. Each
        # row is owned by exactly one group; the others map it to the
        # zero-delta row, contributing an exact 0.0 (scale and codes are
        # all zero), so the sum preserves the token-identity contract.
        y = slot_delta_matmul(x, d.parts[0]).astype(jnp.float32)
        for p in d.parts[1:]:
            y = y + slot_delta_matmul(x, p).astype(jnp.float32)
        return y.astype(x.dtype)
    if isinstance(d, SlotDelta):
        return slot_delta_matmul(x, d)
    if not d.stack_shape():
        y = _sharded_correction(x, d)
        if y is not None:
            return y
        if _USE_PALLAS:
            from repro.kernels import ops
            return ops.delta_spmm(x, d)
        # XLA fallback: the gather formulation at decode-sized token
        # counts, dense reconstruction at prefill-sized ones. The same
        # primitive (same contraction shape) backs the segment dispatch,
        # which is what keeps mixed-stream decode token-identical to
        # this per-tenant reference path.
        from repro.kernels import fallback
        return fallback.correction_nd(x, d).astype(x.dtype)
    dense = reconstruct_dense(d, dtype=x.dtype)
    return x @ dense


def matmul_rows(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x [..., h_in] @ w [h_in, h_out]; on the CPU a lone row is computed
    beside a zero row.

    XLA:CPU gives a one-row float32 product a matrix-vector kernel whose
    bits differ from those of the same row in a larger product, and a
    batch-1 decode has to match its row of a slot batch bit for bit.
    Elsewhere this is ``x @ w``: on a TPU v5e the padded row changed the
    one-row product's bits and took the batch-1 reference further from
    the slot batch, not closer.
    """
    rows = x.reshape(-1, x.shape[-1])
    if rows.shape[0] != 1 or jax.default_backend() != "cpu":
        return x @ w
    y = (jnp.concatenate([rows, jnp.zeros_like(rows)]) @ w)[:1]
    return y.reshape(*x.shape[:-1], w.shape[-1])


def apply_linear(x: jnp.ndarray, w: jnp.ndarray, d: Optional[PackedDelta] = None) -> jnp.ndarray:
    """Base matmul plus (optionally) the tenant's delta correction.

    The correction is computed behind an ``optimization_barrier`` and
    added in f32 with ONE explicit final rounding. Without the barrier
    XLA fuses the correction into its consumers at fusion-dependent
    precision, and the fusion decisions shift when the shard_map'd
    sharded-correction region is present — sharded and single-device
    decode then drift by an ulp, enough to flip greedy argmax near
    ties. The pinned boundary + fixed-precision add keep the hot path
    bit-identical across mesh layouts (the CI token-identity check).
    """
    x = _replicated(x)
    y = matmul_rows(x, w)
    if d is not None:
        with jax.named_scope(CORRECTION_SCOPE):
            c = _pinned(delta_matmul(x, d).astype(jnp.float32))
        y = (y.astype(jnp.float32) + c).astype(y.dtype)
    return _replicated(y)


def apply_linear_batched(x: jnp.ndarray, w: jnp.ndarray,
                         d: Optional[PackedDelta] = None) -> jnp.ndarray:
    """Batched over a leading stack dim (e.g. MoE experts):
    x [E, ..., h_in], w [E, h_in, h_out], delta stacked [E, ...]."""
    if isinstance(d, (SlotDelta, MultiSlotDelta)):
        # Expert buffers mix tokens from many slots; a per-row gather has no
        # meaning here. The serving engine must group such archs per tenant.
        raise NotImplementedError(
            "slot-dispatched deltas are not supported at expert-batched "
            "linear sites (MoE); serve these tenants via per-tenant grouping")
    x = _replicated(x)
    # deltalint: allow[DL001] audited MoE expert-batched base matmul: no
    # per-row identity contract at this site (tenants are served grouped,
    # never mixed-batch through expert buffers — see the raise above)
    y = jnp.einsum("e...d,edf->e...f", x, w)
    if d is not None:
        with jax.named_scope(CORRECTION_SCOPE):
            dense = reconstruct_dense(d, dtype=x.dtype)  # [E, h_in, h_out]
            # same fusion pin + fixed-precision add as apply_linear, so MoE
            # expert-site corrections keep the mesh bit-identity contract
            # deltalint: allow[DL001] audited MoE correction: grouped-per-
            # tenant serving only, so batch extent is fixed per tenant group
            c = _pinned(jnp.einsum("e...d,edf->e...f", x, dense)
                        .astype(jnp.float32))
        y = (y.astype(jnp.float32) + c).astype(y.dtype)
    return _replicated(y)


# ---------------------------------------------------------------------------
# Delta-tree helpers: deltas mirror the params tree with None at
# uncompressed leaves, so block code can slice them alongside params.
# ---------------------------------------------------------------------------
def none_like(params: Any) -> Any:
    """A deltas pytree of all-None matching ``params``' dict structure."""
    if isinstance(params, dict):
        return {k: none_like(v) for k, v in params.items()}
    return None


def dget(deltas: Any, *keys: str) -> Any:
    """None-safe nested lookup into a deltas tree."""
    node = deltas
    for k in keys:
        if node is None:
            return None
        node = node.get(k) if isinstance(node, dict) else None
    return node


def dindex(deltas: Any, i) -> Any:
    """Slice every PackedDelta in a deltas subtree at stacked-layer index i."""
    if deltas is None:
        return None
    if isinstance(deltas, (SlotDelta, MultiSlotDelta)):
        return deltas.index(i)
    if isinstance(deltas, PackedDelta):
        return deltas.index(i)
    if isinstance(deltas, dict):
        return {k: dindex(v, i) for k, v in deltas.items()}
    return None


# ---------------------------------------------------------------------------
# Tenant stacking for the continuous-batching engine
# ---------------------------------------------------------------------------
def _is_pd(x) -> bool:
    return isinstance(x, PackedDelta)


def zero_delta_like(deltas: Any) -> Any:
    """An all-zero deltas tree with the same packed structure/shapes.

    Dequantizes to exactly 0 at every leaf (scale 0, codes 0), so the base
    model can occupy a row of a tenant stack without a structure change.
    """
    def z(d: PackedDelta) -> PackedDelta:
        return PackedDelta(
            jnp.zeros_like(d.idx), jnp.zeros_like(d.codes),
            jnp.zeros(jnp.shape(d.scale), jnp.float32),
            jnp.zeros(jnp.shape(d.zero), jnp.int32),
            d.h_in, d.h_out, d.h_g, d.keep, d.alpha, d.k_bits, d.m, d.codec)

    return jax.tree.map(z, deltas, is_leaf=_is_pd)


def stack_tenant_deltas(trees: list) -> Any:
    """Stack N structurally identical delta trees along a new tenant axis.

    Every leaf becomes a PackedDelta with arrays [T, ...]; scale/zero
    become [T, *lead]. Raises ValueError when the trees disagree in
    structure or packing meta (different specs cannot share one stack).
    """
    if not trees:
        raise ValueError("need at least one delta tree to stack")
    ref = jax.tree.structure(trees[0], is_leaf=_is_pd)
    for t in trees[1:]:
        if jax.tree.structure(t, is_leaf=_is_pd) != ref:
            raise ValueError("tenant delta trees differ in structure; "
                             "cannot stack for slot dispatch")

    def stack(*leaves):
        d0 = leaves[0]
        for d in leaves[1:]:
            if (d.h_in, d.h_out, d.h_g, d.keep, d.k_bits, d.m, d.codec,
                    d.idx.shape, d.codes.shape) != \
               (d0.h_in, d0.h_out, d0.h_g, d0.keep, d0.k_bits, d0.m,
                    d0.codec, d0.idx.shape, d0.codes.shape):
                raise ValueError("tenant deltas use different packing specs; "
                                 "cannot stack for slot dispatch")
        return PackedDelta(
            jnp.stack([d.idx for d in leaves]),
            jnp.stack([d.codes for d in leaves]),
            jnp.stack([jnp.asarray(d.scale, jnp.float32) for d in leaves]),
            jnp.stack([jnp.asarray(d.zero, jnp.int32) for d in leaves]),
            d0.h_in, d0.h_out, d0.h_g, d0.keep, d0.alpha, d0.k_bits, d0.m,
            d0.codec)

    return jax.tree.map(stack, *trees, is_leaf=_is_pd)


def wrap_slot_deltas(stacked: Any, slots: jnp.ndarray,
                     segments: Optional[TenantSegments] = None,
                     values: Any = None,
                     res_map: Optional[jnp.ndarray] = None) -> Any:
    """Attach per-row tenant ids (and, optionally, the sorted tenant-
    segment layout for unique-tenant dispatch, plus the pre-decoded
    residency tier: ``values`` a tree of f32 buffers mirroring
    ``stacked`` leaf-for-leaf and ``res_map`` the shared tenant-row ->
    residency-row indirection) to every leaf of a tenant-stacked tree."""
    if values is None:
        return jax.tree.map(lambda d: SlotDelta(d, slots, segments), stacked,
                            is_leaf=_is_pd)
    return jax.tree.map(
        lambda d, v: SlotDelta(d, slots, segments, v, res_map),
        stacked, values, is_leaf=_is_pd)


def merge_delta(params: Any, deltas: Any) -> Any:
    """Materialize fine-tuned params = base + dense(delta). (Eval/reference.)"""
    if isinstance(params, dict):
        return {k: merge_delta(v, deltas.get(k) if isinstance(deltas, dict) else None)
                for k, v in params.items()}
    if deltas is None:
        return params
    if isinstance(deltas, PackedDelta):
        dense = reconstruct_dense(deltas)
    else:
        # other codecs' leaves (BitDelta, low-rank residual, ...)
        from repro.core.codecs import reconstruct_dense_any
        dense = reconstruct_dense_any(deltas)
    return (params.astype(jnp.float32) + dense).astype(params.dtype)
