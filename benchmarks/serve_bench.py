"""Serving benchmarks (CPU wall-clock; TPU numbers come from the dry-run
roofline, not from this container).

Measures, on the smoke config:

* decode step latency, base vs base+delta (separate-computation overhead),
* continuous-batching throughput / TTFT / occupancy for 1, 4 and 16
  tenants under a staggered mixed request stream,
* with ``--devices N``: the tensor-parallel row (``continuous_sharded``)
  and the data-parallel row (``continuous_data2``: a (2, N/2) mesh with
  slot rows in two occupancy-balanced shard pools, which also reports
  per-shard occupancy/throughput/imbalance and gates that every shard
  pool actually decoded tokens),
* multi-tenant memory footprint vs N full fine-tuned models,

and writes ``BENCH_serve.json`` at the repo root so later PRs have a perf
trajectory to beat.

CI regression gate::

    python -m benchmarks.serve_bench --quick --out BENCH_serve.fresh.json \
        --check BENCH_serve.json --tolerance 2.0

``--check`` compares the fresh run against a committed baseline with a
generous tolerance (CI runners are noisy; 2x catches real regressions,
not scheduler jitter) and exits non-zero on regression. ``--quick``
skips the slow 16-tenant run but keeps each remaining row's workload
identical to the baseline's, so throughput stays comparable.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, get_models
from repro.analysis import CompileGuard
from repro.configs import get_smoke_config
from repro.core import DeltaDQSpec, compress
from repro.launch.serve import synth_tenants
from repro.models import lm
from repro.serve import ContinuousEngine
from repro.utils import tree_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE_SPEC = DeltaDQSpec(alpha=8.0, k_bits=4, m=8, h_g=16)   # 128x class


def _time(fn, *args, n=20):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def decode_overhead():
    """Static decode-step microbenchmark on the trained bench models."""
    cfg, base, ft = get_models()
    deltas, report = compress(base, ft, DeltaDQSpec(alpha=8, k_bits=4, m=8, h_g=64))
    print("#", report.summary())

    B, S = 8, 32
    cache = lm.init_cache(cfg, B, S)
    tok = jnp.ones((B, 1), jnp.int32)
    dec_base = jax.jit(lambda c, t: lm.decode_step(cfg, base, c, t, jnp.int32(4)))
    dec_delta = jax.jit(lambda c, t: lm.decode_step(cfg, base, c, t, jnp.int32(4), deltas=deltas))

    us_base = _time(dec_base, cache, tok)
    us_delta = _time(dec_delta, cache, tok)
    print(f"decode_base_us,{us_base:.1f}")
    print(f"decode_with_delta_us,{us_delta:.1f}")
    return {"decode_base_us": us_base, "decode_with_delta_us": us_delta,
            "delta_overhead_x": us_delta / us_base}


def continuous_bench(n_tenants: int, n_requests: int = 16, max_new: int = 8,
                     n_slots: int = 4, arrival_gap: float = 0.02,
                     devices: int = 1, data: int = 1,
                     admission: str = "occupancy",
                     residency_mb: float = 0.0) -> dict:
    """Mixed staggered stream through the continuous engine (smoke config).

    ``devices > 1`` serves the same stream on a ``(data, devices/data)``
    mesh (tensor-parallel base, output-sharded packed deltas; with
    ``data > 1`` the slot rows additionally shard over ``data`` in
    contiguous pools) — on CPU the devices are faked via
    ``--xla_force_host_platform_device_count``, which is how the CI
    multi-device bench rows run. ``data > 1`` with ``devices == 1``
    runs host-side shard pools (admission-policy semantics without
    device sharding). ``admission`` picks the shard placement policy;
    ``residency_mb > 0`` enables the pre-decoded delta value cache.
    """
    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    mesh = None
    if devices > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(devices, data=data)
    from repro.serve import residency_bytes_from_mb
    eng = ContinuousEngine(cfg, base, n_slots=n_slots, max_seq=64, mesh=mesh,
                           data=data, admission=admission,
                           residency_budget_bytes=residency_bytes_from_mb(
                               residency_mb))
    for name, deltas, _ in synth_tenants(cfg, base, n_tenants, SERVE_SPEC, rng):
        eng.register_tenant(name, deltas)

    # warm every jit shape (both buckets + decode) so the measurement is
    # steady-state serving, not compilation
    warm = [eng.submit("tenant0", np.zeros(L, np.int32), max_new_tokens=2)
            for L in (4, 12)]
    eng.run()
    assert all(w.done for w in warm)
    eng.reset_metrics()             # drop warmup counters, keep compiled fns

    reqs = []
    for i in range(n_requests):
        L = 4 + (i % 3) * 4
        prompt = np.asarray(jax.random.randint(
            jax.random.fold_in(rng, 100 + i), (L,), 0, cfg.vocab))
        reqs.append(eng.submit(f"tenant{i % n_tenants}", prompt,
                               max_new_tokens=max_new,
                               arrival=i * arrival_gap))
    metrics = eng.run()
    assert all(r.done for r in reqs)
    rep = metrics.report()
    out = {
        "n_tenants": n_tenants,
        "n_requests": n_requests,
        "n_slots": n_slots,
        "devices": devices,
        "data": data,
        "admission": admission,
        "residency_mb": residency_mb,
        "residency": rep["residency"],
        "unique_tenants_per_shard_mean": rep["unique_tenants_per_shard_mean"],
        "shards": rep["shards"],
        "shard_imbalance_max": rep["shard_imbalance_max"],
        "arrival_gap_s": arrival_gap,
        "tokens_per_sec": rep["tokens_per_sec"],
        "ttft_p50_ms": 1e3 * rep["ttft_p50"] if rep["ttft_p50"] is not None else None,
        "batch_occupancy": rep["batch_occupancy"],
        "prefill_shapes": sorted(eng.prefill_shapes),
        # which codec(s) the decode path actually dispatched (from the
        # per-jit-signature attribution notes) — one entry per codec seen
        "decode_codecs": sorted({n["codec"]
                                 for notes in eng._path_notes.values()
                                 for n in notes if "codec" in n}),
        "delta_bytes_per_tenant": eng.store.total_bytes() / n_tenants,
        "base_bytes": tree_bytes(base),
        "tenants": rep["tenants"],     # per-tenant throughput/TTFT/latency
    }
    print(f"serve_{n_tenants}t: {out['tokens_per_sec']:.0f} tok/s, "
          f"ttft p50 {out['ttft_p50_ms']:.1f}ms, "
          f"occupancy {out['batch_occupancy']:.2f}")
    return out


def tracing_overhead(n_tenants: int = 4, n_requests: int = 16,
                     max_new: int = 8, n_slots: int = 4,
                     arrival_gap: float = 0.02, trials: int = 2) -> dict:
    """Throughput cost of full tracing: a traced and an untraced twin of
    the 4-tenant continuous row, interleaved trials, best-of per mode.

    Interleaving means machine noise (frequency scaling, co-tenant
    load) hits both modes; best-of-trials strips the slow-outlier tail.
    The gate is ``tracing_overhead_x <= 1.05`` — the observability
    subsystem's <3% contract with headroom for CI wall-clock jitter.
    """
    from repro.serve.trace import Tracer

    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    tenants = synth_tenants(cfg, base, n_tenants, SERVE_SPEC, rng)

    def build(traced: bool) -> ContinuousEngine:
        eng = ContinuousEngine(cfg, base, n_slots=n_slots, max_seq=64,
                               trace=Tracer() if traced else None)
        for name, deltas, _ in tenants:
            eng.register_tenant(name, deltas)
        warm = [eng.submit("tenant0", np.zeros(L, np.int32),
                           max_new_tokens=2) for L in (4, 12)]
        eng.run()
        assert all(w.done for w in warm)
        return eng

    engines = {"untraced": build(False), "traced": build(True)}
    best = {k: 0.0 for k in engines}
    for _ in range(trials):
        for mode, eng in engines.items():
            eng.reset_metrics()
            reqs = []
            for i in range(n_requests):
                L = 4 + (i % 3) * 4
                prompt = np.asarray(jax.random.randint(
                    jax.random.fold_in(rng, 100 + i), (L,), 0, cfg.vocab))
                reqs.append(eng.submit(f"tenant{i % n_tenants}", prompt,
                                       max_new_tokens=max_new,
                                       arrival=i * arrival_gap))
            rep = eng.run().report()
            assert all(r.done for r in reqs)
            best[mode] = max(best[mode], rep["tokens_per_sec"] or 0.0)
    ratio = best["untraced"] / best["traced"] if best["traced"] else None
    out = {"n_tenants": n_tenants, "n_requests": n_requests,
           "trials": trials,
           "untraced_tokens_per_sec": best["untraced"],
           "traced_tokens_per_sec": best["traced"],
           "tracing_overhead_x": ratio}
    print(f"tracing_overhead: untraced {best['untraced']:.0f} tok/s, "
          f"traced {best['traced']:.0f} tok/s -> "
          f"{ratio:.3f}x" if ratio is not None else
          "tracing_overhead: traced run produced no throughput")
    return out


def affinity_unique_check(n_tenants: int = 16, n_requests: int = 32,
                          n_slots: int = 8, data: int = 2) -> dict:
    """Deterministic replay: per-shard unique-tenant load, occupancy vs
    affinity admission, on the SAME 16-tenant skewed trace.

    Runs on a VirtualClock with host-side shard pools, so placement —
    and therefore the per-step per-shard unique-tenant counts — is a
    pure function of the trace: this is a hard gate, not a wall-clock
    measurement. The trace is zipf-ish (a few hot tenants dominate,
    like real multi-tenant traffic) so tenant repeats overlap in
    flight, which is the regime affinity exists for.
    """
    from repro.serve import VirtualClock

    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    tenants = synth_tenants(cfg, base, n_tenants, SERVE_SPEC, rng)
    rs = np.random.RandomState(7)
    trace = []
    for i in range(n_requests):
        # 60% of traffic from 4 hot tenants, the rest uniform
        t = rs.randint(4) if rs.rand() < 0.6 else rs.randint(n_tenants)
        L = 4 + (i % 3) * 4
        prompt = rs.randint(0, cfg.vocab, size=L).astype(np.int32)
        trace.append((f"tenant{t}", prompt, 0.004 * i))

    def run(admission: str) -> float:
        eng = ContinuousEngine(cfg, base, n_slots=n_slots, max_seq=64,
                               data=data, admission=admission,
                               clock=VirtualClock(tick=1e-3))
        for name, deltas, _ in tenants:
            eng.register_tenant(name, deltas)
        reqs = [eng.submit(t, p, max_new_tokens=6, arrival=a)
                for t, p, a in trace]
        metrics = eng.run()
        assert all(r.done for r in reqs)
        per_shard = metrics.report()["unique_tenants_per_shard_mean"]
        return float(np.mean(per_shard))

    occ, aff = run("occupancy"), run("affinity")
    out = {"n_tenants": n_tenants, "n_requests": n_requests,
           "n_slots": n_slots, "data": data,
           "unique_per_shard_occupancy": occ,
           "unique_per_shard_affinity": aff,
           "affinity_strictly_lower": aff < occ}
    print(f"affinity_unique_check: occupancy {occ:.3f} vs affinity "
          f"{aff:.3f} unique tenants/shard/step "
          f"({'OK' if aff < occ else 'NOT LOWER'})")
    return out


def continuous_zipf(n_tenants: int = 8, n_requests: int = 48,
                    n_slots: int = 4, max_new: int = 8,
                    arrival_gap: float = 0.004, devices: int = 1,
                    data: int = 1, chunk_size: int = 16) -> dict:
    """Sustained zipf-arrival load: chunked vs unchunked prefill twins.

    The TTFT-cliff workload: arrivals outnumber slots many times over
    at a gap far below per-request service time, so the queue stays
    deep for the whole run and every wasted dispatch (a batch-1
    whole-prompt prefill advances zero decode tokens) compounds into
    queue wait. Tenant picks are zipf-ish (hot-tenant skew like real
    multi-tenant traffic); prompt lengths span the whole bucket ladder
    (8..max_seq), because that is where the cliff lives: the
    whole-prompt engine compiles one prefill program per length bucket,
    and warmup covers only ONE typical bucket — as in production, where
    the shape ladder is too wide to pre-warm — so the first request to
    hit each remaining bucket stalls the entire engine behind a mid-run
    compile while the queue is deep. The chunked engine serves every
    length through its two fixed shapes (combined decode+chunk, masked
    decode), so after the same one-bucket warmup it never compiles
    again. Both twins serve the SAME trace with the SAME warmup; the
    chunked engine must deliver strictly better ``ttft_p95`` at
    equal-or-better throughput (the --check gate).
    """
    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    tenants = synth_tenants(cfg, base, n_tenants, SERVE_SPEC, rng)
    mesh = None
    if devices > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(devices, data=data)

    rs = np.random.RandomState(11)
    trace = []
    # one length per bucket rung (buckets 8/16/32/64 at max_seq=64),
    # cycled so every rung recurs throughout the run
    lengths = (6, 12, 20, 28, 40, 48)
    for i in range(n_requests):
        t = rs.randint(4) if rs.rand() < 0.6 else rs.randint(n_tenants)
        L = lengths[i % len(lengths)]
        prompt = rs.randint(0, cfg.vocab, size=L).astype(np.int32)
        trace.append((f"tenant{t}", prompt, i * arrival_gap))

    def run(chunked: bool) -> dict:
        eng = ContinuousEngine(
            cfg, base, n_slots=n_slots, max_seq=64, mesh=mesh, data=data,
            chunked_prefill=chunked, chunk_size=chunk_size)
        for name, deltas, _ in tenants:
            eng.register_tenant(name, deltas)
        # warm ONE typical bucket (both twins, identically) — the rest
        # of the shape ladder is deliberately left cold; mid-run bucket
        # compiles ARE the cliff this row measures
        warm = eng.submit("tenant0", np.zeros(12, np.int32),
                          max_new_tokens=2)
        eng.run()
        assert warm.done
        eng.reset_metrics()
        reqs = [eng.submit(t, p, max_new_tokens=max_new, arrival=a)
                for t, p, a in trace]
        rep = eng.run().report()
        assert all(r.done for r in reqs)
        return {
            "tokens_per_sec": rep["tokens_per_sec"],
            "ttft_p50_ms": 1e3 * rep["ttft_p50"],
            "ttft_p95_ms": 1e3 * rep["ttft_p95"],
            "itl_p50_ms": None if rep["itl_p50"] is None
            else 1e3 * rep["itl_p50"],
            "itl_p95_ms": None if rep["itl_p95"] is None
            else 1e3 * rep["itl_p95"],
            "batch_occupancy": rep["batch_occupancy"],
            "decode_steps": rep["decode_steps"],
        }

    unchunked = run(False)
    chunked = run(True)
    tps_ratio = chunked["tokens_per_sec"] / unchunked["tokens_per_sec"]
    out = {
        "n_tenants": n_tenants, "n_requests": n_requests,
        "n_slots": n_slots, "devices": devices, "data": data,
        "chunk_size": chunk_size, "arrival_gap_s": arrival_gap,
        "unchunked": unchunked, "chunked": chunked,
        "tps_chunked_vs_unchunked_x": tps_ratio,
        # the gate: strictly better tail TTFT at equal-or-better
        # throughput (5% wall-clock headroom on "equal")
        "chunked_better_ttft": chunked["ttft_p95_ms"]
        < unchunked["ttft_p95_ms"],
        "throughput_held": tps_ratio >= 1 / 1.05,
    }
    print(f"continuous_zipf: ttft p95 {unchunked['ttft_p95_ms']:.0f}ms -> "
          f"{chunked['ttft_p95_ms']:.0f}ms chunked, throughput "
          f"{unchunked['tokens_per_sec']:.0f} -> "
          f"{chunked['tokens_per_sec']:.0f} tok/s ({tps_ratio:.2f}x)")
    return out


def residency_memory_trade(n_tenants: int = 24, n_requests: int = 24,
                           n_slots: int = 8, residency_mb: float = 64.0
                           ) -> dict:
    """Residency's memory trade at a >16-tenant config (deferred half of
    the PR 5 residency row): what the value cache actually commits in
    bytes, against the packed deltas it fronts, at a fleet size where
    capacity pressure and LRU churn are real."""
    row = continuous_bench(n_tenants, n_requests=n_requests,
                           n_slots=n_slots, residency_mb=residency_mb)
    res = row.get("residency") or {}
    packed_total = row["delta_bytes_per_tenant"] * n_tenants
    out = {
        "n_tenants": n_tenants,
        "n_requests": n_requests,
        "residency_mb": residency_mb,
        "tokens_per_sec": row["tokens_per_sec"],
        "packed_delta_bytes_total": packed_total,
        "value_cache_allocated_bytes": res.get("allocated_bytes"),
        "value_cache_row_bytes": res.get("row_bytes"),
        "capacity_rows": res.get("capacity_rows"),
        "resident_rows": res.get("resident_rows"),
        "hit_rate": res.get("hit_rate"),
        "fallback_steps": res.get("fallback_steps"),
        # the trade: decoded-f32 bytes committed per packed delta byte
        "allocated_vs_packed_x": None if not res.get("allocated_bytes")
        else res["allocated_bytes"] / packed_total,
    }
    alloc = out["value_cache_allocated_bytes"] or 0
    print(f"residency_memory_24t: {alloc / 1e6:.2f}MB value cache vs "
          f"{packed_total / 1e6:.2f}MB packed deltas "
          f"({out['allocated_vs_packed_x'] or 0:.1f}x), hit rate "
          f"{out['hit_rate'] if out['hit_rate'] is not None else 'n/a'}")
    return out


def tenant_lifecycle(n_tenants: int = 3, max_new: int = 8,
                     n_slots: int = 4) -> dict:
    """Online tenant lifecycle row: raw checkpoint -> compress ->
    hot-register into a RUNNING engine -> first token.

    tenant0 is registered up front (it builds the tenant table and pays
    the delta-decode jit trace); tenants 1..N then arrive while
    tenant0's sequences are decoding, and each row measures
    ``compress_s`` (core.compress wall), ``register_s`` (the table row
    write) and ``register_to_first_token_s`` (checkpoint arrival to that
    tenant's first served token, engine live throughout). The gated
    invariant is ``decode_recompiles == 0``: hot registration, rollout
    and retirement must never retrace the decode step. Deterministic
    scheduling via VirtualClock; the wall times are real compute.
    """
    from repro.serve import DeltaRegistry, VirtualClock

    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    # +2 rows: every tenant resident plus one spare for the rollout
    eng = ContinuousEngine(cfg, base, n_slots=n_slots, max_seq=64,
                           tenant_capacity=n_tenants + 2,
                           clock=VirtualClock(tick=1e-3))
    reg = DeltaRegistry(eng, base, spec=SERVE_SPEC, codec=None)

    def ft_of(seed):
        return jax.tree.map(
            lambda p: p + 0.02 * jax.random.normal(
                jax.random.fold_in(rng, seed), p.shape,
                jnp.float32).astype(p.dtype)
            if p.ndim >= 2 else p, base)

    # tenant0 + warmup: the table exists and every jit shape (both
    # prompt buckets + the grouped decode) is compiled before the
    # measured registrations — their cost is lifecycle, not XLA
    reg.ingest("tenant0", ft_of(7)); reg.pump()
    warm = [eng.submit("tenant0", np.zeros(L, np.int32), max_new_tokens=2)
            for L in (4, 12)]
    eng.run()
    assert all(w.done for w in warm)
    # post-warmup recompile count via CompileGuard — the same (single)
    # implementation the lifecycle tests and launcher drill gate on
    guard = CompileGuard(eng, max_new={"decode": 0})

    rs = np.random.RandomState(0)
    inflight = [eng.submit("tenant0",
                           rs.randint(0, cfg.vocab, size=8).astype(np.int32),
                           max_new_tokens=max_new)]
    eng.step(eng._now())                # tenant0 genuinely in flight
    rows = []
    for t in range(1, n_tenants + 1):
        name = f"tenant{t}"
        t0 = time.perf_counter()
        reg.ingest(name, ft_of(7 + t))
        reg.pump()                      # hot-register into the live engine
        rec = reg._records[name]
        req = reg.submit(name, rs.randint(0, cfg.vocab, size=8).astype(
            np.int32), max_new_tokens=max_new)
        while not req.tokens:
            eng.step(eng._now())
        rows.append({"tenant": name, "compress_s": rec.compress_s,
                     "register_s": rec.register_s,
                     "register_to_first_token_s": time.perf_counter() - t0})
        inflight.append(req)
    eng.run()
    assert all(r.done for r in inflight)

    t0 = time.perf_counter()
    reg.ingest("tenant0", ft_of(777)); reg.pump()    # version rollout
    rollout_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.unregister_tenant("tenant1")                 # drained: retire
    retire_s = time.perf_counter() - t0

    recompiles = guard.new_compiles("decode")
    out = {
        "n_tenants": n_tenants,
        "tenants": rows,
        "compress_s_mean": float(np.mean([r["compress_s"] for r in rows])),
        "register_s_mean": float(np.mean([r["register_s"] for r in rows])),
        "register_to_first_token_s_mean": float(np.mean(
            [r["register_to_first_token_s"] for r in rows])),
        "rollout_s": rollout_s,
        "retire_s": retire_s,
        "decode_recompiles": recompiles,
        "lifecycle_events": eng.metrics.report()["tenant_lifecycle"],
    }
    print(f"tenant_lifecycle: compress {out['compress_s_mean']:.2f}s, "
          f"register {1e3 * out['register_s_mean']:.0f}ms, "
          f"register->first token {out['register_to_first_token_s_mean']:.2f}s"
          f" mean of {n_tenants}; rollout {1e3 * rollout_s:.0f}ms, retire "
          f"{1e3 * retire_s:.0f}ms, decode recompiles {recompiles}")
    return out


def compare_against(fresh: dict, baseline_path: str, tolerance: float) -> list:
    """Regressions of the fresh run vs a committed baseline (throughput
    may not drop below baseline/tolerance; decode latency may not grow
    past baseline*tolerance). Returns a list of human-readable failures."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    fails = []
    # deterministic (VirtualClock) affinity invariant: per-shard unique-
    # tenant load must be strictly lower than occupancy admission on the
    # 16-tenant skewed trace — replay-exact, so no tolerance
    auc = fresh.get("affinity_unique_check")
    if auc and not auc.get("affinity_strictly_lower"):
        fails.append(
            f"affinity admission unique-tenants/shard "
            f"{auc['unique_per_shard_affinity']:.3f} not strictly lower "
            f"than occupancy {auc['unique_per_shard_occupancy']:.3f}")
    # residency vs its packed twin: same process, back-to-back, same
    # workload — the RATIO is less noisy than absolute tok/s, but CI
    # wall-clock still shows real same-machine spread (see the data2
    # tolerance note), so the floor only catches structural regressions
    # (values path ~2x slower than the unpack it removes), not jitter;
    # the >= 1.0 expectation is reported (vs_packed_x) and pinned by the
    # committed full-run baseline
    res = fresh.get("continuous_residency")
    if res and res.get("vs_packed_x") is not None \
            and res["vs_packed_x"] < 0.5:
        fails.append(
            f"residency throughput {res['vs_packed_x']:.2f}x of its packed "
            "twin (< 0.5 floor): the values path is structurally slower "
            "than the per-step unpack it removes")
    # tracing-overhead gate: absolute (same-process twin ratio, not a
    # baseline diff) — the observability subsystem promises <3% cost at
    # default sampling; 1.05x is that contract plus CI jitter headroom
    tro = fresh.get("tracing_overhead")
    if tro and tro.get("tracing_overhead_x") is not None \
            and tro["tracing_overhead_x"] > 1.05:
        fails.append(
            f"tracing overhead {tro['tracing_overhead_x']:.3f}x > 1.05x "
            f"(traced {tro['traced_tokens_per_sec']:.0f} vs untraced "
            f"{tro['untraced_tokens_per_sec']:.0f} tok/s)")
    # chunked-prefill zipf gate: same-process twin over the SAME trace,
    # so no baseline row or tolerance — chunked must deliver strictly
    # better tail TTFT without giving up throughput (5% headroom on
    # "equal"); anything else means interleaving stopped paying its way
    zp = fresh.get("continuous_zipf")
    if zp:
        if not zp.get("chunked_better_ttft"):
            fails.append(
                f"chunked prefill ttft_p95 "
                f"{zp['chunked']['ttft_p95_ms']:.0f}ms not strictly "
                f"better than unchunked "
                f"{zp['unchunked']['ttft_p95_ms']:.0f}ms on the zipf row")
        if not zp.get("throughput_held"):
            fails.append(
                f"chunked prefill throughput "
                f"{zp['tps_chunked_vs_unchunked_x']:.2f}x of its "
                f"unchunked twin (< 1/1.05) on the zipf row")
    # lifecycle gate: hot registration / rollout / retirement must not
    # retrace the decode step — a recompile count is exact (jit cache
    # size, not wall clock), so it gates at 0 with no tolerance
    tl = fresh.get("tenant_lifecycle")
    if tl and tl.get("decode_recompiles", 0) != 0:
        fails.append(
            f"tenant_lifecycle: {tl['decode_recompiles']} decode-step "
            "recompile(s) across hot registration/rollout/retire "
            "(must be exactly 0)")
    base_us = baseline.get("micro", {}).get("decode_with_delta_us")
    fresh_us = fresh.get("micro", {}).get("decode_with_delta_us")
    if base_us and fresh_us and fresh_us > base_us * tolerance:
        fails.append(f"decode_with_delta_us {fresh_us:.0f} > "
                     f"{tolerance}x baseline {base_us:.0f}")
    base_by_n = {c["n_tenants"]: c for c in baseline.get("continuous", [])}
    for c in fresh.get("continuous", []):
        b = base_by_n.get(c["n_tenants"])
        # only compare identical workloads: a row with a different request
        # count measures a different queueing regime, not a regression
        if not b or b.get("n_requests") != c.get("n_requests"):
            continue
        floor = b["tokens_per_sec"] / tolerance
        if c["tokens_per_sec"] < floor:
            fails.append(
                f"{c['n_tenants']}-tenant throughput {c['tokens_per_sec']:.0f} "
                f"tok/s < baseline {b['tokens_per_sec']:.0f}/{tolerance}")
    for row in ("continuous_sharded", "continuous_data2",
                "continuous_affinity", "continuous_residency"):
        b_sh = baseline.get(row)
        f_sh = fresh.get(row)
        # The data-parallel row emulates shard_map collectives over BOTH
        # mesh axes on fake CPU devices; its wall-clock is noisier than
        # the single-mesh rows, so it gates at 1.5x the base tolerance
        # (tightened from the original 2x once the row's spread settled).
        # continuous_sharded keeps its original (base) sensitivity — its
        # gate predates this row and loosening it here would silently
        # blind CI to model-sharded decode regressions.
        mesh_tol = tolerance * (1.5 if row == "continuous_data2"
                                else 1.0)
        if b_sh and f_sh and b_sh.get("n_requests") == f_sh.get("n_requests") \
                and b_sh.get("devices") == f_sh.get("devices") \
                and b_sh.get("data", 1) == f_sh.get("data", 1):
            if f_sh["tokens_per_sec"] < b_sh["tokens_per_sec"] / mesh_tol:
                fails.append(
                    f"{row} ({f_sh['devices']}-device, "
                    f"data={f_sh.get('data', 1)}) throughput "
                    f"{f_sh['tokens_per_sec']:.0f} tok/s < baseline "
                    f"{b_sh['tokens_per_sec']:.0f}/{mesh_tol}")
        # Shard participation gate: with this row's workload (requests
        # outnumber slots, arrival gap << per-request service time) every
        # shard pool must decode tokens — a broken admission policy that
        # funnels the stream onto one shard zeroes the other pool's
        # count. Step-level imbalance is reported but NOT gated: it
        # depends on when finishes land relative to admission rounds
        # (timing), and for small pools its reachable range can't
        # separate broken from correct admission; the deterministic
        # admission invariants live in the hypothesis suite
        # (tests/test_serve_scheduler.py), not here.
        for s in (f_sh or {}).get("shards") or []:
            if not s["tokens"]:
                fails.append(
                    f"{row} data shard {s['shard']} decoded 0 tokens "
                    "(occupancy-balanced admission broken?)")
    return fails


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="trimmed tenant sweep (1/4, skipping the slow "
                         "16-tenant throughput rows incl. the affinity "
                         "trajectory row; the deterministic "
                         "affinity_unique_check still runs and gates) for "
                         "CI; request count stays the same so rows remain "
                         "comparable to the baseline")
    ap.add_argument("--out", default=None,
                    help="output JSON (default: repo-root BENCH_serve.json; "
                         "quick runs default to BENCH_serve.quick.json so a "
                         "trimmed sweep never overwrites the committed "
                         "baseline)")
    ap.add_argument("--check", default=None, metavar="BASELINE_JSON",
                    help="fail (exit 1) on regression vs this baseline")
    ap.add_argument("--tolerance", type=float, default=2.0)
    ap.add_argument("--devices", type=int, default=0,
                    help="also run a sharded 2-tenant row over N fake "
                         "devices (requires XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N); recorded under "
                         "'continuous_sharded'")
    args = ap.parse_args()
    if args.out is None:
        args.out = os.path.join(
            REPO, "BENCH_serve.quick.json" if args.quick else "BENCH_serve.json")

    tenant_sweep = (1, 4) if args.quick else (1, 4, 16)
    report = {"micro": decode_overhead(), "continuous": []}
    for n_tenants in tenant_sweep:
        report["continuous"].append(continuous_bench(n_tenants))
    # residency row: the exact 4-tenant workload of the continuous sweep
    # (so it exists in quick AND full runs and compares 1:1) with the
    # pre-decoded delta value cache enabled — its throughput should be
    # >= the packed twin's, since decode steps skip the per-step unpack
    report["continuous_residency"] = continuous_bench(4, residency_mb=64.0)
    packed_twin = next(c for c in report["continuous"]
                       if c["n_tenants"] == 4)
    res_tps = report["continuous_residency"]["tokens_per_sec"]
    ratio = res_tps / packed_twin["tokens_per_sec"]
    report["continuous_residency"]["vs_packed_x"] = ratio
    print(f"residency vs packed (4-tenant twin): {ratio:.2f}x "
          f"({'OK' if ratio >= 1.0 else 'below packed — wall-clock noise?'})")
    # tracing-overhead row: traced/untraced twin of the 4-tenant row;
    # runs in quick mode too (it IS the CI gate for the <3% contract)
    report["tracing_overhead"] = tracing_overhead()
    # affinity: the deterministic unique-tenant comparison is the gated
    # invariant and runs in BOTH modes (it is what --check enforces);
    # the wall-clock 16-tenant affinity trajectory row is full-mode only
    # (--quick's contract is to skip the slow 16-tenant throughput runs)
    report["affinity_unique_check"] = affinity_unique_check()
    if not args.quick:
        report["continuous_affinity"] = continuous_bench(
            16, n_requests=16, n_slots=8, data=2, admission="affinity")
    if args.devices > 1:
        report["continuous_sharded"] = continuous_bench(
            2, n_requests=8, devices=args.devices)
        if args.devices % 2 == 0:
            # data-parallel row: (2, devices/2) mesh, slot rows split into
            # two shard pools with occupancy-balanced admission
            report["continuous_data2"] = continuous_bench(
                2, n_requests=8, devices=args.devices, data=2)

    # tenant-lifecycle row: hot compress-and-register into a running
    # engine; its decode_recompiles==0 gate is deterministic (jit cache
    # size), so it runs — and gates — in quick mode too
    report["tenant_lifecycle"] = tenant_lifecycle()
    # chunked-prefill zipf row: same-trace twin (chunked vs whole-prompt)
    # under sustained hot-tenant load across the full bucket ladder; its
    # gate is within-process (twin ratio), so it runs in quick mode too
    report["continuous_zipf"] = continuous_zipf(
        n_requests=24 if args.quick else 48,
        devices=args.devices if args.devices > 1 else 1)
    if not args.quick:
        # residency memory trade at fleet scale (>16 tenants): bytes the
        # value cache commits against the packed deltas it fronts
        report["residency_memory_24t"] = residency_memory_trade()

    base_bytes = report["continuous"][0]["base_bytes"]
    delta_bytes = report["continuous"][0]["delta_bytes_per_tenant"]
    n = 16
    full = base_bytes * n
    ours = base_bytes + delta_bytes * n
    report["memory_16_tenants"] = {
        "full_models_mb": full / 1e6, "deltadq_mb": ours / 1e6,
        "saving_x": full / ours,
    }
    print(f"memory_16_tenants: full={full / 1e6:.1f}MB "
          f"deltadq={ours / 1e6:.1f}MB saving={full / ours:.1f}x")

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {args.out}")

    us = report["micro"]["decode_with_delta_us"]
    csv_row("serve_bench", us,
            f"delta_overhead={report['micro']['delta_overhead_x']:.2f}x;"
            f"mem_saving_16t={full / ours:.1f}x;"
            f"tok_s={report['continuous'][-1]['tokens_per_sec']:.0f}")

    if args.check:
        fails = compare_against(report, args.check, args.tolerance)
        if fails:
            for f_ in fails:
                print(f"REGRESSION: {f_}", file=sys.stderr)
            sys.exit(1)
        print(f"# bench regression check vs {args.check}: OK "
              f"(tolerance {args.tolerance}x)")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
