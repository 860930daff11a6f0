"""Autotuned tile/formulation table for the delta-correction hot path.

The kernels expose three knobs — ``tb`` (token tile), ``ob`` (output
tile), ``kc`` (scatter chunk) — and the XLA fallback exposes one more:
``gather_max_t``, the token count below which the gather formulation
(kernels/fallback.py) beats dense reconstruction. The best values depend
on the packing envelope point ``(h_g, keep, k_bits, h_out)`` and on the
backend, so they are swept offline and persisted:

    PYTHONPATH=src python -m repro.kernels.autotune --out results/autotune_kernels.json

The best values depend on the packing envelope point
``(h_g, keep, k_bits, h_in, h_out)`` — ``h_in`` is part of the key
because the gather/dense crossover scales with the contraction width,
not just the packing spec. ``kernels.ops`` consults :func:`lookup`
whenever a caller does not pin the tiles explicitly. A missing table
(or a missing envelope point) falls back to :data:`DEFAULTS`, so the
table is an optimization, never a correctness dependency.

Since v3 the key additionally carries the **token count T** as an
overlay: chunked prefill drives the correction at chunk-sized T (e.g.
16 or the combined decode+chunk row count), and BENCH_kernels.json
shows the gather/dense crossover — and the best kernel tiles — moving
with T, so a prefill-sized call must not inherit decode tiles.
``lookup(..., t=T)`` merges ``DEFAULTS <- base entry <- "@T" entry``
where the T entry's key suffix is the :data:`T_GRID` bucket T snaps to
(:func:`snap_t`). Base entries keep the swept ``gather_max_t``
crossover (the formulation decision stays ONE monotone threshold — the
identity contract's guarantee that a row computes the same bits at any
batch size); T entries overlay per-T tiles (TPU) and record the
measured per-T formulation + timings (CPU), which is what kernel_bench
reports. v2 tables (no ``@T`` entries) still load: the overlay is
simply empty. Table format (JSON)::

    {"version": 3, "backend": "cpu",
     "entries": {"64/8/4/128/256": {"tb": 128, "ob": 128, "kc": 8,
                                    "gather_max_t": 64},
                 "64/8/4/128/256@T16": {"formulation": "gather",
                                        "gather_us": 8.1,
                                        "dense_us": 55.0}}}

``gather_max_t`` is floored at :data:`MIN_GATHER_T`: the segment
dispatch always uses the gather formulation, so the per-tenant
reference path must pick gather for every decode-sized batch too or the
exact token-identity contract breaks — and gather won every measured
envelope point at T <= 32 by >=3x anyway.

On CPU hosts the Pallas kernels only run in interpret mode (validation,
not perf), so the sweep measures the XLA-fallback crossover; on TPU it
additionally times the compiled kernels across the (tb, ob, kc)
candidate grid. Set ``REPRO_AUTOTUNE_TABLE`` to point ops at a
non-default table path.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

DEFAULTS = {"tb": 128, "ob": 128, "kc": 8, "gather_max_t": 64}

# floor for the stored gather/dense crossover: decode batches (n_slots)
# must take the gather formulation on the per-tenant reference path
# because the mixed-slot segment dispatch always does (bit-identity)
MIN_GATHER_T = 32

# candidate grids for the sweep (kept small: the table is per envelope
# point and the envelope has few operating points per deployment)
TB_CANDIDATES = (32, 64, 128, 256)
OB_CANDIDATES = (64, 128, 256)
KC_CANDIDATES = (4, 8, 16)
T_GRID = (1, 4, 8, 16, 32, 64, 128, 256)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_TABLE_PATH = os.path.join(_REPO, "results", "autotune_kernels.json")

_cached_table: Optional[dict] = None
_cached_path: Optional[str] = None


def table_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_TABLE", DEFAULT_TABLE_PATH)


def snap_t(t: int) -> int:
    """Snap a token count to its :data:`T_GRID` bucket (smallest grid
    point >= t; counts past the grid share the largest bucket)."""
    for g in T_GRID:
        if t <= g:
            return g
    return T_GRID[-1]


def envelope_key(h_g: int, keep: int, k_bits: Optional[int], h_in: int,
                 h_out: int, t: Optional[int] = None) -> str:
    base = f"{h_g}/{keep}/{k_bits}/{h_in}/{h_out}"
    return base if t is None else f"{base}@T{snap_t(t)}"


def load_table(path: Optional[str] = None) -> dict:
    """Load (and cache) the persisted table; {} when absent/unreadable."""
    global _cached_table, _cached_path
    path = path or table_path()
    if _cached_table is not None and _cached_path == path:
        return _cached_table
    try:
        with open(path) as f:
            tab = json.load(f)
        entries = tab.get("entries", {})
    except (OSError, ValueError):
        entries = {}
    _cached_table, _cached_path = entries, path
    return entries


def invalidate_cache() -> None:
    global _cached_table, _cached_path
    _cached_table = _cached_path = None


def lookup(h_g: int, keep: int, k_bits: Optional[int], h_in: int,
           h_out: int, t: Optional[int] = None) -> dict:
    """Tile/formulation parameters for an envelope point (always complete:
    missing keys are filled from :data:`DEFAULTS`).

    ``t`` (the call's token count — static at trace time) overlays the
    per-T entry on top of the base entry: per-T tiles win where swept,
    everything else (notably ``gather_max_t``) comes from the base
    entry, so the formulation threshold stays one monotone crossover.
    """
    entries = load_table()
    key = envelope_key(h_g, keep, k_bits, h_in, h_out)
    got = {**DEFAULTS, **entries.get(key, {})}
    if t is not None:
        overlay = entries.get(envelope_key(h_g, keep, k_bits, h_in, h_out,
                                           t=t), {})
        got.update({k: v for k, v in overlay.items()
                    if k in ("tb", "ob", "kc")})
    # the identity floor survives any table contents (see module doc)
    got["gather_max_t"] = max(int(got["gather_max_t"]), MIN_GATHER_T)
    return got


# ---------------------------------------------------------------------------
# Sweep
# ---------------------------------------------------------------------------
def _time(fn, *args, n: int = 30) -> float:
    import jax
    jfn = jax.jit(fn)
    out = jfn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = jfn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def _sweep_gather_max_t(p, rng) -> tuple:
    """Measure gather vs dense across :data:`T_GRID`.

    Returns ``(gather_max_t, overlays)``: the largest T where gather
    still wins (floored at MIN_GATHER_T, kept monotone — the first
    crossover freezes the threshold), plus the per-T ``@T`` overlay
    entries recording both timings and the formulation the runtime
    actually selects at that T under the threshold."""
    import jax
    from repro.kernels import fallback
    best = 0
    crossed = False
    timings = {}
    for T in T_GRID:
        x = jax.random.normal(rng, (T, p.h_in))
        us_gather = _time(lambda x: fallback.gather_correction(x, p), x)
        us_dense = _time(lambda x: fallback.dense_correction(x, p), x)
        timings[T] = (us_gather, us_dense)
        if not crossed and us_gather > us_dense:
            crossed = True
        if not crossed:
            best = T
    gmax = max(best, MIN_GATHER_T)
    overlays = {T: {"gather_us": round(ug, 2), "dense_us": round(ud, 2),
                    "formulation": "gather" if T <= gmax else "dense"}
                for T, (ug, ud) in timings.items()}
    return gmax, overlays


def _sweep_kernel_tiles(p, rng, T: int = 128) -> dict:
    """Best (tb, ob, kc) for the compiled Pallas kernel (TPU only).

    A packing outside the kernel envelope keeps the default tiles, with
    the reason printed: ``delta_spmm`` would time the XLA fallback."""
    import jax
    from repro.kernels import ops
    # only the kernel-tile keys: returning gather_max_t here would
    # clobber the crossover the caller just measured
    best = {k: DEFAULTS[k] for k in ("tb", "ob", "kc")}
    why = ops.kernel_refusal(p)
    if why is not None:
        print(f"# T={T} h_g={p.h_g} h_out={p.h_out}: no tile sweep, the "
              f"kernels refuse this packing ({why})", flush=True)
        return best
    x = jax.random.normal(rng, (T, p.h_in))
    best_us = float("inf")
    refused = []
    for tb in TB_CANDIDATES:
        for ob in OB_CANDIDATES:
            for kc in KC_CANDIDATES:
                try:
                    us = _time(lambda x: ops.delta_spmm(
                        x, p, tb=tb, ob=ob, kc=kc, interpret=False), x)
                except Exception as e:  # a candidate the compiler refuses
                    refused.append(((tb, ob, kc), f"{type(e).__name__}: "
                                    f"{str(e).splitlines()[0][:200]}"))
                    continue
                if us < best_us:
                    best_us = us
                    best = {"tb": tb, "ob": ob, "kc": kc}
    if refused:
        n = len(TB_CANDIDATES) * len(OB_CANDIDATES) * len(KC_CANDIDATES)
        print(f"# T={T} h_g={p.h_g} h_out={p.h_out}: the compiler refused "
              f"{len(refused)}/{n} tile candidates", flush=True)
        for tiles, why in refused:
            print(f"#   (tb, ob, kc)={tiles}: {why}", flush=True)
    if best_us == float("inf"):
        raise RuntimeError(f"every tile candidate was refused at T={T}, "
                           f"h_g={p.h_g}, h_out={p.h_out}: {refused[0][1]}")
    return best


def sweep_point(h_g: int, keep: int, k_bits: Optional[int], h_in: int,
                h_out: int, *, seed: int = 0) -> tuple:
    """Measure one envelope point.

    Returns ``(base_entry, overlays)``: the base table entry plus the
    ``{T: entry}`` per-token-count overlay map (v3) — the overlay
    measurements come for free from the crossover sweep, which already
    walks :data:`T_GRID` (so chunk-sized T is always covered). On TPU
    each overlay additionally carries the (tb, ob, kc) swept at that T,
    so prefill-chunk-sized calls stop inheriting decode tiles.
    """
    import jax
    from repro.core import groupwise_dropout_pack
    alpha = max(1, h_g // max(keep, 1))
    rng = jax.random.PRNGKey(seed)
    delta = jax.random.normal(rng, (h_in, h_out)) * 0.01
    p = groupwise_dropout_pack(rng, delta, h_g=h_g, alpha=alpha, k_bits=k_bits)
    entry = dict(DEFAULTS)
    entry["gather_max_t"], overlays = _sweep_gather_max_t(p, rng)
    if jax.default_backend() == "tpu":
        entry.update(_sweep_kernel_tiles(p, rng))
        for T in T_GRID:
            overlays[T].update(_sweep_kernel_tiles(p, rng, T))
    return entry, overlays


# the envelope points the serving configs actually hit: the smoke config
# (d_model 64, d_ff 128) at the RATIO_SPECS h_g=16 packing, the bench
# arch (d_model 128, d_ff 256, heads 128/kv 64) at h_g=64, plus wider
# table-4 h_g* points
DEFAULT_POINTS = [
    (16, 2, 4, 64, 32),
    (16, 2, 4, 64, 64),
    (16, 2, 4, 64, 128),
    (16, 2, 4, 128, 64),
    (64, 8, 4, 128, 64),
    (64, 8, 4, 128, 128),
    (64, 8, 4, 128, 256),
    (64, 8, 4, 256, 128),
    (64, 8, 4, 512, 512),
    (128, 16, 4, 256, 256),
    (16, 2, None, 64, 64),
    (64, 8, 8, 128, 256),
]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_TABLE_PATH)
    ap.add_argument("--points", default=None,
                    help="comma-separated h_g/keep/k_bits/h_in/h_out keys "
                         "(default: the serving envelope points)")
    args = ap.parse_args()

    import jax
    points = DEFAULT_POINTS
    if args.points:
        points = []
        for key in args.points.split(","):
            h_g, keep, k_bits, h_in, h_out = key.split("/")
            points.append((int(h_g), int(keep),
                           None if k_bits == "None" else int(k_bits),
                           int(h_in), int(h_out)))

    entries = {}
    for (h_g, keep, k_bits, h_in, h_out) in points:
        key = envelope_key(h_g, keep, k_bits, h_in, h_out)
        entries[key], overlays = sweep_point(h_g, keep, k_bits, h_in, h_out)
        print(f"{key}: {entries[key]}")
        for T, ov in overlays.items():
            entries[envelope_key(h_g, keep, k_bits, h_in, h_out, t=T)] = ov

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"version": 3, "backend": jax.default_backend(),
                   "entries": entries}, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {args.out}")
    invalidate_cache()


if __name__ == "__main__":
    main()
