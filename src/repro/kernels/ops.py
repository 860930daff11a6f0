"""Jit'd public wrappers around the Pallas kernels.

Handle envelope checks (tile divisibility, supported h_g/keep), input
prep (padding, scalar shaping) and the interpret-mode switch used for
CPU validation. Outside the kernel envelope the XLA fallback
(``kernels.fallback``: gather formulation at decode token counts, dense
reconstruct-then-matmul at prefill counts) is used — mathematically
identical. Tile sizes (tb, ob, kc) default to the persisted autotune
table (``kernels.autotune``); explicit arguments always win.

Output columns that don't divide the tile run on the largest lane-legal
divisor tile (no padding); only when ``h_out`` has none is the packed
column axis padded up to a 128-lane multiple and the result sliced.

Multi-device: :func:`delta_correction_sharded` partitions the packed
delta along its output-column axis over the mesh ``model`` axis with
``shard_map``, so each shard dequantizes only its h_out/n columns —
the kernel's compressed-bytes-only HBM traffic is preserved per shard
and the correction needs no collectives (x is replicated at decode
batch sizes; each output column is produced by exactly one shard).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.pack import PackedDelta, reconstruct_dense
from repro.kernels import autotune, fallback
from repro.kernels import delta_spmm as _k


def _interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode unless the caller pinned it: kernels compile on a
    TPU and run in the Pallas interpreter elsewhere. Asked at call time,
    so importing this module never initialises a backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _note(site: str, **attrs) -> None:
    """Report the chosen path to an open trace context (no-op otherwise).
    Lazy import: serve's __init__ imports the engine, which imports us."""
    from repro.serve.trace import note_path
    note_path(site, **attrs)

MAX_HG = 256
MAX_KEEP = 128
# TPU lane width: a block's last dim must be a multiple of it or span the
# whole array, and the kernels' x block is (Tb, h_g)
LANES = 128


def kernel_refusal(d: PackedDelta) -> Optional[str]:
    """Why the Pallas kernels cannot take ``d`` (None when they can)."""
    if d.stack_shape():
        return f"stacked delta {d.stack_shape()}"
    if d.h_g > MAX_HG:
        return f"h_g={d.h_g} > {MAX_HG}"
    if d.h_g % LANES and d.h_g != d.h_in:
        return (f"h_g={d.h_g} is neither a multiple of {LANES} nor "
                f"h_in={d.h_in}")
    if d.keep > MAX_KEEP:
        return f"keep={d.keep} > {MAX_KEEP}"
    if d.k_bits is not None and not 1 <= d.k_bits <= 8:
        return f"k_bits={d.k_bits} not in 1..8"
    return None


def kernel_supported(d: PackedDelta) -> bool:
    return kernel_refusal(d) is None


def _refused(site: str, d: PackedDelta) -> bool:
    """True (and the reason noted for attribution) when ``d`` is outside
    the kernel envelope and ``site`` must take the XLA fallback."""
    why = kernel_refusal(d)
    if why is not None:
        _note(site, kernel_refused=why)
    return why is not None


def _scalars(d: PackedDelta):
    s = jnp.asarray(d.scale, jnp.float32).reshape(1, 1)
    z = jnp.asarray(d.zero, jnp.int32).reshape(1, 1)
    return s, z


def _pad_rows(x: jnp.ndarray, mult: int):
    T = x.shape[0]
    pad = (-T) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
    return x, T


def _tiles(d: PackedDelta, tb, ob, kc, t: Optional[int] = None) -> dict:
    """Resolve tile sizes: explicit args win, else the autotune table.

    ``t`` is the call's token count (a static trace-time int): the v3
    table overlays per-T tiles on the envelope point so prefill-chunk
    sized calls stop inheriting decode tiles. ``gather_max_t`` always
    comes from the base entry (one monotone formulation threshold)."""
    tuned = autotune.lookup(d.h_g, d.keep, d.k_bits, d.h_in, d.h_out, t=t)
    return {"tb": tb if tb is not None else tuned["tb"],
            "ob": ob if ob is not None else tuned["ob"],
            "kc": kc if kc is not None else tuned["kc"],
            "gather_max_t": tuned["gather_max_t"]}


def _col_tile(h_out: int, ob: int) -> int:
    """Effective column tile for ``h_out`` output columns.

    Prefer a divisor of ``h_out`` (no padding, no wasted columns — in
    the fused kernel padding also copies the whole base matrix). A tile
    must be lane-legal on a TPU: a multiple of :data:`LANES` or all of
    ``h_out``. Only when no legal divisor exists fall back to a
    lane-multiple tile and let the caller pad and slice."""
    cap = min(ob, h_out)
    if cap == h_out:
        return cap
    for t in range(cap - cap % LANES, 0, -LANES):
        if h_out % t == 0:
            return t
    return -(-cap // LANES) * LANES


def _pad_cols(d: PackedDelta, ob: int) -> PackedDelta:
    """Pad the packed column axis to an ``ob`` multiple (slice the result).

    Padded columns decode to garbage values ((0 - zero) * scale) but are
    sliced off by every caller before the result escapes, so only the
    real columns are ever observed.
    """
    pad = (-d.h_out) % ob
    if not pad:
        return d
    widths = [(0, 0)] * (d.idx.ndim - 1) + [(0, pad)]
    return PackedDelta(jnp.pad(d.idx, widths), jnp.pad(d.codes, widths),
                       d.scale, d.zero, d.h_in, d.h_out + pad, d.h_g,
                       d.keep, d.alpha, d.k_bits, d.m, d.codec)


def delta_spmm(x: jnp.ndarray, d: PackedDelta, *, tb: Optional[int] = None,
               ob: Optional[int] = None, kc: Optional[int] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = x @ dequant(d). x [..., h_in] -> [..., h_out] (f32)."""
    interpret = _interpret(interpret)
    t = _tiles(d, tb, ob, kc, t=x.size // x.shape[-1])
    if _refused("delta_spmm", d):
        return fallback.correction_nd(x, d,
                                      gather_max_t=t["gather_max_t"])
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d.h_in)
    tb_eff = min(t["tb"], max(_pow2_floor(x2.shape[0]), 8))
    x2, T = _pad_rows(x2, tb_eff)
    ob_eff = _col_tile(d.h_out, t["ob"])
    _note("delta_spmm", formulation="pallas", codec=d.codec,
          tb=tb_eff, ob=ob_eff, kc=t["kc"])
    dp = _pad_cols(d, ob_eff)
    s, z = _scalars(d)
    y = _k.delta_spmm_kernel(x2, dp.idx, dp.codes, s, z, h_g=d.h_g,
                             keep=d.keep, k_bits=d.k_bits, h_out=dp.h_out,
                             tb=tb_eff, ob=ob_eff, kc=t["kc"],
                             interpret=interpret)
    return y[:T, :d.h_out].reshape(*lead, d.h_out)


def delta_spmm_slots(x: jnp.ndarray, d: PackedDelta, *,
                     tb: Optional[int] = None, ob: Optional[int] = None,
                     kc: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-row delta matmul for mixed-tenant decode batches.

    x [B, ..., h_in]; d is a row-gathered PackedDelta stacked [B, ...]
    (one tenant's packed delta per batch row). Row b computes
    ``x[b] @ dequant(d[b])``. On TPU the per-matrix kernel is vmapped
    over the row axis; elsewhere (and in interpret mode, where the
    batching rule is not exercised) the gather-formulation fallback is
    used — it never materializes a dense ``[B, h_in, h_out]`` tensor, so
    rows sharing a tenant no longer multiply a dense reconstruction.
    """
    interpret = _interpret(interpret)
    B = x.shape[0]
    if d.stack_shape() != (B,):
        raise ValueError(
            f"stacked delta stack_shape={d.stack_shape()} must equal "
            f"({B},) — one delta row per slot row of x {x.shape}")
    probe = d.index(0)
    if interpret or _refused("delta_spmm_slots", probe):
        _note("delta_spmm_slots", formulation="per-row-gather",
              codec=d.codec, B=int(B))
        return fallback.gather_correction_rows(x, d)
    _note("delta_spmm_slots", formulation="per-row-pallas",
          codec=d.codec, B=int(B))
    fn = lambda xb, db: delta_spmm(xb, db, tb=tb, ob=ob, kc=kc,
                                   interpret=False)
    return jax.vmap(fn)(x, d)


def delta_spmm_segments(x_sorted: jnp.ndarray, d: PackedDelta,
                        seg_rows: jnp.ndarray, seg_offsets: jnp.ndarray, *,
                        values: Optional[jnp.ndarray] = None,
                        res_map: Optional[jnp.ndarray] = None,
                        tb: Optional[int] = None, ob: Optional[int] = None,
                        kc: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Unique-tenant batched slot dispatch: x_sorted rows grouped by tenant.

    x_sorted [T, h_in] (rows pre-sorted so each tenant occupies one
    contiguous segment); d is the tenant-stacked PackedDelta [R, ...];
    seg_rows [S] int32 maps segment -> tenant row; seg_offsets [S+1]
    int32 bounds each segment (empty segments allowed — S is a static
    shape). Each unique delta is dequantized once per step and applied
    to its row segment. On TPU this is the batched slot kernel
    (``delta_spmm_segments_kernel``); elsewhere the scan-over-segments
    XLA fallback.

    Decode fast path: when the whole batch fits one row tile (the decode
    regime — T = n_slots), ``tb`` collapses to the padded batch size and
    the grid has a single row block, skipping the pad-to-pow2 dance.

    ``values``/``res_map`` (pre-decoded residency tier) route to the
    values-given XLA formulation: the Pallas segments kernel already
    decodes each [h_g, Ob] VMEM tile once per segment, so the per-step
    unpack the residency tier removes is the XLA/CPU host cost — a
    values-consuming kernel variant is not worth a second TPU code
    path. Packed-only (values=None) stays the always-correct fallback.
    """
    interpret = _interpret(interpret)
    if values is not None:
        return fallback.segment_correction(x_sorted, d, seg_rows, seg_offsets,
                                           values=values, res_map=res_map)
    probe = d.index(0)
    t = _tiles(probe, tb, ob, kc, t=x_sorted.shape[0])
    if _refused("delta_spmm_segments", probe):
        return fallback.segment_correction(x_sorted, d, seg_rows, seg_offsets)
    T = x_sorted.shape[0]
    if T <= t["tb"]:
        tb_eff = max(8, -(-T // 8) * 8)     # decode fast path: one row block
    else:
        tb_eff = min(t["tb"], max(_pow2_floor(T), 8))
    x2, T = _pad_rows(x_sorted, tb_eff)
    ob_eff = _col_tile(d.h_out, t["ob"])
    _note("delta_spmm_segments", formulation="segments-pallas",
          codec=d.codec, residency="packed", tb=tb_eff, ob=ob_eff,
          kc=t["kc"])
    dp = _pad_cols(d, ob_eff)
    scale = jnp.asarray(d.scale, jnp.float32).reshape(-1, 1)
    zero = jnp.asarray(d.zero, jnp.int32).reshape(-1, 1)
    y = _k.delta_spmm_segments_kernel(
        x2, dp.idx, dp.codes, scale, zero,
        seg_rows.astype(jnp.int32), seg_offsets.astype(jnp.int32),
        h_g=d.h_g, keep=d.keep, k_bits=d.k_bits, h_out=dp.h_out,
        tb=tb_eff, ob=ob_eff, kc=t["kc"], interpret=interpret)
    return y[:T, :d.h_out]


def delta_correction_sharded(x: jnp.ndarray, d: PackedDelta, mesh, *,
                             use_pallas: bool = False,
                             interpret: Optional[bool] = None,
                             tb: Optional[int] = None,
                             ob: Optional[int] = None,
                             segments: Optional[tuple] = None,
                             values: Optional[jnp.ndarray] = None,
                             res_map: Optional[jnp.ndarray] = None
                             ) -> Optional[jnp.ndarray]:
    """y = x · dequant(d), with d partitioned along output columns.

    ``d`` is a shared delta (no stack), a row-gathered stack ``[B]``
    matching ``x``'s leading dim (per-row mixed-tenant decode), or — with
    ``segments=(seg_rows, seg_offsets)`` — the tenant stack ``[R]``
    consumed by the unique-tenant dispatch (x rows pre-sorted by
    tenant). Segment arrays may be the global ``[S]``/``[S+1]`` layout
    or the per-data-shard ``[D, B_s]``/``[D, B_s+1]`` layout (detected
    by ndim): the per-shard form additionally partitions x's rows over
    the mesh ``data`` axis, so each (data, model) device computes its
    own pool's rows for its own column slice — and dequantizes only the
    tenants its pool hosts. With ``values``/``res_map`` (segments mode
    only) the pre-decoded residency tier shards exactly like the codes
    — values partition along their output-column axis, so each shard
    reads only its slice of the decoded f32 bytes and skips the
    per-step unpack. The shard_map body computes its slice with
    the exact same local math as the single-device path (Pallas kernel
    when ``use_pallas``, the gather/segment fallback otherwise), so
    sharded serving is bit-identical to the replicated engine: the
    contraction for every output element is unchanged, only *which
    shard* produces it differs.

    Returns None when the mesh/delta layout does not apply (no model
    axis, h_out not divisible, unsupported stack shape, per-shard
    layout not matching the mesh data axis) — the caller falls back to
    the replicated path.
    """
    n = mesh.shape.get("model", 1) if mesh is not None else 1
    if n <= 1 or d.h_out % n:
        return None
    stack = d.stack_shape()
    if segments is not None:
        if len(stack) != 1:
            return None
    elif stack not in ((), (x.shape[0],)):
        return None
    scale = jnp.asarray(d.scale, jnp.float32)
    zero = jnp.asarray(d.zero, jnp.int32)

    def last_model(nd: int) -> P:
        return P(*([None] * (nd - 1) + ["model"]))

    def repl(nd: int) -> P:
        return P(*([None] * nd))

    def local_delta(idx, codes, s, z) -> PackedDelta:
        # local O-slice delta: static meta rebuilt with the shard's h_out
        return PackedDelta(idx, codes, s, z, d.h_in, idx.shape[-1], d.h_g,
                           d.keep, d.alpha, d.k_bits, d.m, d.codec)

    # tiles and formulation decided on the GLOBAL envelope point (the
    # local slice has a different h_out key: it must not flip the
    # formulation — sharded and replicated serving would use different
    # arithmetic — and has no swept autotune entry of its own). Hoisted
    # above the segments branch: its kernel body needs kc too. The
    # token-count overlay keys on the GLOBAL row count for the same
    # reason (per-shard rows would change the key with the data extent).
    t_glob = _tiles(d, tb, ob, None, t=x.size // x.shape[-1])
    tb, ob = t_glob["tb"], t_glob["ob"]
    kc = t_glob["kc"]
    _note("delta_correction_sharded", sharded=True, codec=d.codec,
          model_shards=int(n),
          per_shard_segments=segments is not None
          and jnp.ndim(segments[0]) == 2)

    if segments is not None:
        seg_rows, seg_offsets = segments
        seg_rows = jnp.asarray(seg_rows, jnp.int32)
        seg_offsets = jnp.asarray(seg_offsets, jnp.int32)
        have_values = values is not None

        def body_seg(xb, idx, codes, s, z, sr, so, *vr):
            if sr.ndim == 2:               # per-shard block: [1, B_s(+1)]
                sr, so = sr[0], so[0]
            v, rm = vr if vr else (None, None)
            dl = local_delta(idx, codes, s, z)
            if use_pallas:
                return delta_spmm_segments(xb, dl, sr, so, values=v,
                                           res_map=rm, tb=tb, ob=ob,
                                           kc=kc, interpret=interpret)
            return fallback.segment_correction(xb, dl, sr, so, values=v,
                                               res_map=rm)

        # NOTE: dtype round-trip happens in the caller (apply.py) for the
        # segments path; the body stays f32 like its local fallback.

        # residency values shard their output-column axis with the codes
        # (each shard reads only its decoded slice); res_map replicates
        val_specs = (last_model(values.ndim), repl(1)) if have_values else ()
        val_args = (values, res_map) if have_values else ()
        if seg_rows.ndim == 2:
            # per-data-shard layout: rows partition over `data`, each
            # shard consumes its own pool-local segment block
            n_data = mesh.shape.get("data", 1)
            if seg_rows.shape[0] != n_data or x.shape[0] % n_data:
                return None
            fn = jax.shard_map(
                body_seg, mesh=mesh,
                in_specs=(P(*(["data"] + [None] * (x.ndim - 1))),
                          last_model(d.idx.ndim), last_model(d.codes.ndim),
                          repl(scale.ndim), repl(zero.ndim),
                          P("data", None), P("data", None), *val_specs),
                out_specs=P(*(["data"] + [None] * (x.ndim - 2) + ["model"])),
                check_vma=False)
        else:
            fn = jax.shard_map(
                body_seg, mesh=mesh,
                in_specs=(repl(x.ndim), last_model(d.idx.ndim),
                          last_model(d.codes.ndim), repl(scale.ndim),
                          repl(zero.ndim), repl(1), repl(1), *val_specs),
                out_specs=last_model(x.ndim),
                check_vma=False)
        return fn(x, d.idx, d.codes, scale, zero, seg_rows, seg_offsets,
                  *val_args)
    gather_max_t = t_glob["gather_max_t"]

    def body(xb, idx, codes, s, z):
        dl = local_delta(idx, codes, s, z)
        if stack:
            if use_pallas:
                return delta_spmm_slots(xb, dl, tb=tb, ob=ob,
                                        interpret=interpret)
            y = fallback.gather_correction_rows(xb, dl)
        elif use_pallas:
            y = delta_spmm(xb, dl, tb=tb, ob=ob, interpret=interpret)
        else:
            y = fallback.correction_nd(xb, dl, gather_max_t=gather_max_t)
        # same dtype round-trip as the replicated path (bit-identity)
        return y.astype(xb.dtype)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(repl(x.ndim), last_model(d.idx.ndim),
                                 last_model(d.codes.ndim), repl(scale.ndim),
                                 repl(zero.ndim)),
                       out_specs=last_model(x.ndim),
                       check_vma=False)
    return fn(x, d.idx, d.codes, scale, zero)


def fused_base_delta(x: jnp.ndarray, w: jnp.ndarray, d: PackedDelta, *,
                     tb: Optional[int] = None, ob: Optional[int] = None,
                     kc: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = x @ (w + dequant(d)); reads x once (separate computation, fused)."""
    interpret = _interpret(interpret)
    if _refused("fused_base_delta", d):
        return (x @ w) + delta_spmm(x, d, interpret=interpret).astype(w.dtype)
    t = _tiles(d, tb, ob, kc, t=x.size // x.shape[-1])
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d.h_in)
    tb_eff = min(t["tb"], max(_pow2_floor(x2.shape[0]), 8))
    x2, T = _pad_rows(x2, tb_eff)
    ob_eff = _col_tile(d.h_out, t["ob"])
    dp = _pad_cols(d, ob_eff)
    wp = w if dp.h_out == d.h_out else jnp.pad(
        w, ((0, 0), (0, dp.h_out - d.h_out)))
    s, z = _scalars(d)
    y = _k.fused_base_delta_kernel(x2, wp, dp.idx, dp.codes, s, z, h_g=d.h_g,
                                   keep=d.keep, k_bits=d.k_bits,
                                   tb=tb_eff, ob=ob_eff, kc=t["kc"],
                                   interpret=interpret)
    return y[:T, :d.h_out].reshape(*lead, d.h_out)


def dequant(d: PackedDelta, *, ob: Optional[int] = None,
            kc: Optional[int] = None,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Materialize dense delta [h_in, h_out] (merge path)."""
    interpret = _interpret(interpret)
    if _refused("dequant", d):
        return reconstruct_dense(d)
    t = _tiles(d, None, ob, kc)
    ob_eff = _col_tile(d.h_out, t["ob"])
    dp = _pad_cols(d, ob_eff)
    s, z = _scalars(d)
    y = _k.dequant_kernel(dp.idx, dp.codes, s, z, h_g=d.h_g, keep=d.keep,
                          k_bits=d.k_bits, h_out=dp.h_out, ob=ob_eff,
                          kc=t["kc"], interpret=interpret)
    return y[:, :d.h_out]


def segment_decode_tiles(seg_offsets, *, n_groups: int, h_out: int,
                         tb: int, ob: int) -> int:
    """Decode-tile work the segments kernel executes for one step.

    Counts (segment, row-block, column-tile, group) grid points whose
    ``pl.when`` guard fires — i.e. how many [h_g, Ob] tiles are actually
    dequantized. The vmapped per-row kernel decodes
    ``B * n_groups * ceil(h_out / ob)`` tiles regardless of duplication;
    the segments kernel decodes per *unique* tenant per overlapped row
    block. This is the deterministic accounting behind the
    "segments beats per-row on duplicate-tenant batches" invariant
    (kernel_bench gates on it; wall-clock on CPU interpret mode is too
    noisy to gate)."""
    import numpy as np
    offs = np.asarray(seg_offsets)
    col_tiles = -(-h_out // ob)
    total = 0
    T = int(offs[-1])
    for s in range(len(offs) - 1):
        start, end = int(offs[s]), int(offs[s + 1])
        if end <= start:
            continue
        for row0 in range(0, T, tb):
            if start < row0 + tb and end > row0:
                total += n_groups * col_tiles
    return total


def per_row_decode_tiles(batch: int, *, n_groups: int, h_out: int,
                         ob: int) -> int:
    """Decode-tile work of the vmapped per-row kernel (T=1 rows)."""
    return batch * n_groups * (-(-h_out // ob))


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p
