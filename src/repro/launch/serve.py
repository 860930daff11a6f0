"""Production serving launcher: base model + N DeltaDQ tenants.

Loads (or synthesizes) fine-tuned variants, compresses their deltas at the
requested ratio, and drives a mixed, staggered request stream through the
continuous-batching engine — the deployment of paper Fig. 2 as a runnable
process, now with slot-level scheduling and per-tenant metrics.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --tenants 3 --ratio 128 --requests 12 --slots 8

Multi-device (tensor-parallel base + replicated packed deltas; on CPU
the devices are faked, which is exactly how the CI multi-device job
runs it):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --tenants 2 --requests 4 --devices 8
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import CompileBudgetError, CompileGuard
from repro.configs import get_config, get_smoke_config
from repro.core import BitDeltaSpec, DeltaDQSpec, compress
from repro.models import lm
from repro.serve import ContinuousEngine
from repro.utils import enable_compile_cache, tree_bytes

RATIO_SPECS = {
    8: DeltaDQSpec(alpha=8.0, k_bits=None, h_g=16),
    16: DeltaDQSpec(alpha=8.0, k_bits=8, m=1, h_g=16),
    32: DeltaDQSpec(alpha=8.0, k_bits=4, m=1, h_g=16),
    64: DeltaDQSpec(alpha=8.0, k_bits=4, m=4, h_g=16),
    128: DeltaDQSpec(alpha=8.0, k_bits=4, m=8, h_g=16),
}


def synth_tenants(cfg, base, n, spec, rng, *, budget_bits=None):
    """Synthesize n fine-tuned variants and compress their deltas.

    ``spec`` may be a single codec spec (all tenants identical), a list
    of n per-tenant specs (mixed-codec fleets), or a codec-name string
    (``"deltadq"``/``"bitdelta"``/``"lowrank"``/``"auto"``; ``"auto"``
    takes ``budget_bits``).
    """
    specs = spec if isinstance(spec, list) else [spec] * n
    if len(specs) != n:
        raise ValueError(f"{len(specs)} codec specs for {n} tenants")
    out = []
    for t in range(n):
        ft = jax.tree.map(
            lambda p, t=t: p + 0.02 * jax.random.normal(
                jax.random.fold_in(rng, 7 + t), p.shape, jnp.float32).astype(p.dtype)
            if p.ndim >= 2 else p, base)
        kw = {}
        if isinstance(specs[t], str):
            kw = {"codec": specs[t]}
            if specs[t] == "auto":
                kw["budget_bits"] = budget_bits
            out.append((f"tenant{t}", *compress(base, ft, **kw)))
        else:
            out.append((f"tenant{t}", *compress(base, ft, specs[t])))
    return out


def _tenant_specs(args) -> list:
    """Per-tenant spec list for --codec; 'mixed' alternates codecs."""
    if args.codec == "deltadq":
        return [RATIO_SPECS[args.ratio]] * args.tenants
    if args.codec == "mixed":
        # alternate codecs across the fleet: even rows keep the DeltaDQ
        # ratio spec, odd rows ship BitDelta — two codec groups served
        # by one engine
        return [RATIO_SPECS[args.ratio] if t % 2 == 0 else BitDeltaSpec()
                for t in range(args.tenants)]
    return [args.codec] * args.tenants        # codec-name strings


def run_lifecycle(args, cfg, base, rng):
    """Online-lifecycle drill: the fleet registers INTO a running engine.

    tenant0 is compressed and registered up front and starts serving;
    tenants 1..N-1 then arrive as raw checkpoints mid-traffic and are
    compressed + hot-registered by the DeltaRegistry while tenant0's
    sequences keep decoding. Afterwards tenant0 rolls out a v2 (new
    requests only) and tenant1 is retired. The whole drill must not
    retrace the decode step. With ``--check-identity`` every request is
    also gated token-identical against engines built with the same
    tenant set up front — registration time must never change tokens.
    """
    from repro.serve import DeltaRegistry, VirtualClock

    spec = RATIO_SPECS[args.ratio]
    n = args.tenants

    def ft_of(seed):
        return jax.tree.map(
            lambda p: p + 0.02 * jax.random.normal(
                jax.random.fold_in(rng, seed), p.shape,
                jnp.float32).astype(p.dtype)
            if p.ndim >= 2 else p, base)

    fts = [ft_of(7 + t) for t in range(n)]      # v1 fleet
    ft_v2 = ft_of(777)                          # tenant0's rollout
    stream = []
    for i in range(args.requests):
        L = 4 + (i % 3) * 4
        prompt = np.asarray(jax.random.randint(
            jax.random.fold_in(rng, 100 + i), (L,), 0, cfg.vocab))
        stream.append((f"tenant{i % n}", prompt))

    # +1 row so the rollout lands without evicting anyone
    eng = ContinuousEngine(cfg, base, n_slots=args.slots,
                           max_seq=args.max_seq, tenant_capacity=n + 1,
                           clock=VirtualClock(tick=1e-3))
    reg = DeltaRegistry(eng, base, spec=spec, codec=None)

    reg.ingest("tenant0", fts[0]); reg.pump()
    phase_a = [(i, reg.submit(t, p, max_new_tokens=args.max_new))
               for i, (t, p) in enumerate(stream) if t == "tenant0"]
    for _ in range(2):
        eng.step(eng._now())            # tenant0 genuinely in flight
    # Warmup done — from here the decode step must never retrace.
    # CompileGuard (repro.analysis) is the one recompile-detection
    # implementation; strict mode additionally raises AT the retracing
    # call instead of at the end-of-drill check.
    guard = CompileGuard(eng, max_new={"decode": 0},
                         strict=args.strict_compile,
                         label="lifecycle").attach()
    for t in range(1, n):
        name = f"tenant{t}"
        reg.ingest(name, fts[t]); reg.pump()
        rec = reg._records[name]
        print(f"hot-registered {name}: compress {rec.compress_s:.2f}s, "
              f"register {1e3 * rec.register_s:.1f}ms", flush=True)
        phase_a += [(i, reg.submit(tn, p, max_new_tokens=args.max_new))
                    for i, (tn, p) in enumerate(stream) if tn == name]
        eng.step(eng._now())
    eng.run()
    undone = [r.rid for _, r in phase_a if not r.done]
    if undone:
        raise RuntimeError(
            f"lifecycle phase A left requests {undone} unfinished")

    # rollout: tenant0 v2 serves NEW requests only; then retire tenant1
    reg.ingest("tenant0", ft_v2); reg.pump()
    phase_b = [(i, eng.submit("tenant0", p, max_new_tokens=args.max_new))
               for i, (t, p) in enumerate(stream) if t == "tenant0"][:2]
    eng.run()
    undone = [r.rid for _, r in phase_b if not r.done]
    if undone:
        raise RuntimeError(
            f"lifecycle phase B left requests {undone} unfinished")
    if n > 1:
        eng.unregister_tenant("tenant1")
    guard.detach()

    recompiles = guard.new_compiles("decode")
    rep = eng.metrics.report()
    print(f"lifecycle events: {rep['tenant_lifecycle']}")
    print(f"decode recompiles across register/rollout/retire: {recompiles}")
    try:
        guard.check()
    except CompileBudgetError as e:
        raise SystemExit(f"hot lifecycle retraced the decode step: {e}")

    if args.check_identity:
        # registration time must not change tokens: reference engines
        # get the SAME tenant versions up front and serve the same
        # prompts — compare per-request
        def ref_engine(deltas_by_name):
            e = ContinuousEngine(cfg, base, n_slots=args.slots,
                                 max_seq=args.max_seq,
                                 tenant_capacity=n + 1,
                                 clock=VirtualClock(tick=1e-3))
            for name, d in deltas_by_name.items():
                e.register_tenant(name, d)
            return e

        v1 = {f"tenant{t}": compress(base, fts[t], spec)[0]
              for t in range(n)}
        ref = ref_engine(v1)
        ref_a = [(i, ref.submit(stream[i][0], stream[i][1],
                                max_new_tokens=args.max_new))
                 for i, _ in phase_a]
        ref.run()
        ref2 = ref_engine({"tenant0": compress(base, ft_v2, spec)[0]})
        ref_b = [(i, ref2.submit("tenant0", stream[i][1],
                                 max_new_tokens=args.max_new))
                 for i, _ in phase_b]
        ref2.run()
        bad = [r.rid for (_, r), (_, s) in zip(phase_a + phase_b,
                                               ref_a + ref_b)
               if not np.array_equal(r.output(), s.output())]
        if bad:
            raise SystemExit(f"lifecycle token identity FAILED for "
                             f"requests {bad}")
        print(f"token identity vs up-front engines: OK "
              f"({len(phase_a)} + {len(phase_b)} requests)", flush=True)

    if args.json:
        print(json.dumps(rep, indent=2))
    else:
        print(f"served {len(phase_a) + len(phase_b)} requests / "
              f"{rep['total_tokens']} tokens across the lifecycle drill")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--ratio", type=int, default=128, choices=sorted(RATIO_SPECS))
    ap.add_argument("--codec", default="deltadq",
                    choices=("deltadq", "bitdelta", "lowrank", "auto",
                             "mixed"),
                    help="delta codec for every tenant: 'deltadq' keeps "
                         "the --ratio spec table; 'bitdelta'/'lowrank' use "
                         "those codecs' defaults; 'auto' per-leaf picks the "
                         "cheapest codec meeting --budget-bits; 'mixed' "
                         "alternates DeltaDQ/BitDelta across tenants (one "
                         "engine, two codec groups)")
    ap.add_argument("--budget-bits", type=float, default=None,
                    help="per-element bit budget for --codec auto")
    ap.add_argument("--lifecycle", action="store_true",
                    help="online-lifecycle drill: tenant0 serves while "
                         "the rest of the fleet is compressed and "
                         "hot-registered mid-traffic, then a tenant0 "
                         "version rollout and a tenant1 retirement — "
                         "fails on any decode-step recompile; combine "
                         "with --check-identity to gate tokens against "
                         "all-up-front engines")
    ap.add_argument("--strict-compile", action="store_true",
                    help="attach a strict CompileGuard to the serving "
                         "engine: any jit retrace of an already-seen "
                         "signature raises at the retracing call "
                         "(static-decode-shape contract, enforced live); "
                         "with --lifecycle, the drill's post-warmup "
                         "recompile gate also raises at the call site")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--arrival-gap", type=float, default=0.05,
                    help="seconds between request arrivals (staggered stream)")
    ap.add_argument("--json", action="store_true",
                    help="print the metrics report as JSON")
    ap.add_argument("--print-tokens", action="store_true",
                    help="print every request's generated tokens (for "
                         "inspection; cross-process diffs are not stable — "
                         "use --check-identity for the identity contract)")
    ap.add_argument("--check-identity", action="store_true",
                    help="also serve the same stream on a single-device "
                         "DEFAULT-path engine (occupancy admission, packed "
                         "deltas, no mesh) in this process and fail unless "
                         "every request's tokens match exactly; needs "
                         "--devices N>1, --admission affinity, --chunked "
                         "or --residency-mb > 0 to differ from the "
                         "reference")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the base model over N devices ((data, "
                         "N/data) mesh; on CPU set XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N before launch)")
    ap.add_argument("--data", type=int, default=1,
                    help="data-axis extent of the serving mesh: slot rows "
                         "split into `data` contiguous shard pools with "
                         "occupancy-balanced admission (requires --devices "
                         "divisible by data and --slots divisible by data)")
    ap.add_argument("--admission", default="occupancy",
                    choices=("occupancy", "affinity"),
                    help="shard admission policy: 'occupancy' (balanced, "
                         "default) or 'affinity' (prefer the shard pool "
                         "already hosting the request's tenant within a "
                         "bounded imbalance — fewer unique tenants per "
                         "shard, fewer deltas dequantized per step)")
    ap.add_argument("--chunked", action="store_true",
                    help="chunked prefill: prompts stream in --chunk-size "
                         "token chunks inside the regular decode step "
                         "(one combined jit) instead of preempting it "
                         "with a whole-prompt prefill")
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="prompt tokens per prefill chunk (--chunked)")
    ap.add_argument("--chunk-share", type=float, default=1.0,
                    help="SLO knob: max fraction of decode-active steps "
                         "that may carry a prefill chunk (--chunked)")
    ap.add_argument("--residency-mb", type=float, default=0.0,
                    help="pre-decoded delta residency budget in MB: hot "
                         "tenants' dequantized f32 delta values stay "
                         "resident (LRU) and decode steps skip the "
                         "per-step unpack; 0 disables the tier")
    ap.add_argument("--trace-out", metavar="FILE", default=None,
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(request lifecycle spans + per-decode-step path "
                         "attribution; open at https://ui.perfetto.dev)")
    ap.add_argument("--trace-sample", type=int, default=1,
                    help="keep every Nth decode-step span in the trace "
                         "(request spans are always kept)")
    ap.add_argument("--telemetry-snapshot-secs", type=float, default=0.0,
                    help="write a JSON telemetry snapshot (metrics + SLO "
                         "counters) every N seconds of engine time; 0 "
                         "disables")
    ap.add_argument("--telemetry-out", metavar="FILE",
                    default="telemetry.json",
                    help="snapshot file for --telemetry-snapshot-secs "
                         "(atomically replaced on each write)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    if args.data > 1 and args.devices % args.data:
        raise SystemExit(f"--devices {args.devices} must be a multiple of "
                         f"--data {args.data}")
    if args.data > 1 and args.slots % args.data:
        raise SystemExit(f"--slots {args.slots} must be a multiple of "
                         f"--data {args.data} (equal shard pools)")
    mesh = None
    if args.devices > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(args.devices, data=args.data)
        print(f"mesh: {dict(mesh.shape)}", flush=True)
    elif args.data > 1:
        raise SystemExit("--data > 1 requires --devices > 1 (the shard "
                         "pools mirror the mesh data axis)")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    if args.lifecycle:
        if mesh is not None:
            raise SystemExit("--lifecycle runs single-device (the drill "
                             "measures lifecycle, not sharding)")
        run_lifecycle(args, cfg, base, rng)
        return
    tenants = synth_tenants(cfg, base, args.tenants, _tenant_specs(args),
                            rng, budget_bits=args.budget_bits)

    stream = []
    for i in range(args.requests):
        L = 4 + (i % 3) * 4         # mixed prompt lengths -> multiple buckets
        prompt = np.asarray(jax.random.randint(
            jax.random.fold_in(rng, 100 + i), (L,), 0, cfg.vocab))
        stream.append((f"tenant{i % args.tenants}", prompt))

    def serve_stream(mesh_, default_path=False):
        # the identity reference serves the DEFAULT path (occupancy
        # admission, no residency): the contract is that affinity
        # placement and the pre-decoded value tier change scheduling and
        # arithmetic *layout* only, never any request's tokens
        from repro.serve import residency_bytes_from_mb
        kw = {} if default_path else {
            "admission": args.admission,
            "residency_budget_bytes": residency_bytes_from_mb(
                args.residency_mb),
            "chunked_prefill": args.chunked,
            "chunk_size": args.chunk_size,
            "chunk_share": args.chunk_share,
        }
        if not default_path:
            # observability rides the MAIN engine only — the identity
            # reference stays untraced so the comparison itself shows up
            # as one clean engine in the trace
            if args.trace_out:
                from repro.serve.trace import Tracer
                kw["trace"] = Tracer(step_sample=args.trace_sample)
            if args.telemetry_snapshot_secs > 0:
                from repro.serve.telemetry import (SLOCounters,
                                                   TelemetrySnapshotWriter)
                kw["slo"] = SLOCounters()
                kw["telemetry"] = TelemetrySnapshotWriter(
                    args.telemetry_out, args.telemetry_snapshot_secs)
        eng_ = ContinuousEngine(cfg, base, n_slots=args.slots,
                                max_seq=args.max_seq, mesh=mesh_, **kw)
        guard_ = None
        if args.strict_compile and not default_path:
            # fresh engine: every first trace is first=True and allowed;
            # strict mode raises only on RE-traces of a seen signature
            guard_ = CompileGuard(eng_, strict=True, label="serve").attach()
        for name, deltas, report in tenants:
            eng_.register_tenant(name, deltas, report)
        reqs_ = []
        for i, (tenant, prompt) in enumerate(stream):
            reqs_.append(eng_.submit(tenant, prompt,
                                     max_new_tokens=args.max_new,
                                     arrival=i * args.arrival_gap))
        metrics_ = eng_.run()
        if guard_ is not None:
            guard_.detach()
        undone = [r.rid for r in reqs_ if not r.done]
        if undone:
            raise RuntimeError(
                f"engine run() left requests {undone} unfinished")
        return eng_, reqs_, metrics_

    ref_reqs = None
    if args.check_identity:
        nondefault = args.admission != "occupancy" or args.residency_mb > 0 \
            or args.chunked
        if mesh is None and not nondefault and args.codec != "mixed":
            raise SystemExit("--check-identity requires --devices N > 1, "
                             "--admission affinity, --residency-mb > 0, "
                             "--chunked or --codec mixed (nothing to "
                             "compare against otherwise)")
        # single-device reference FIRST (its jits trace without the mesh).
        # With --data N this is also the data=1 reference, and it always
        # runs the default path (occupancy admission, packed deltas) —
        # so --admission/--residency-mb are covered by the same check.
        if mesh is not None or nondefault:
            _, ref_reqs, _ = serve_stream(None, default_path=True)

    for name, _, report in tenants:
        print(f"registered {name}: {report.summary()}", flush=True)
    eng, reqs, metrics = serve_stream(mesh)
    rep = metrics.report()

    if ref_reqs is not None:
        bad = [r.rid for r, s in zip(reqs, ref_reqs)
               if not np.array_equal(r.output(), s.output())]
        if bad:
            raise SystemExit(f"token identity FAILED for requests {bad}")
        print(f"token identity vs single device: OK "
              f"({len(reqs)} requests)", flush=True)

    if args.check_identity and args.codec == "mixed":
        # mixed-codec contract: each request's tokens must match an
        # engine serving ONLY that tenant (same mesh, same prompts) —
        # the other codec group's row-0 zero delta contributes exactly
        # 0.0 to the summed correction, so serving together is
        # token-identical to serving alone
        bad = []
        for name, deltas, report in tenants:
            eng_a = ContinuousEngine(cfg, base, n_slots=args.slots,
                                     max_seq=args.max_seq, mesh=mesh)
            eng_a.register_tenant(name, deltas, report)
            mine = [(i, r) for i, r in enumerate(reqs) if r.tenant == name]
            alone = [eng_a.submit(name, stream[i][1],
                                  max_new_tokens=args.max_new,
                                  arrival=k * args.arrival_gap)
                     for k, (i, _) in enumerate(mine)]
            eng_a.run()
            bad += [r.rid for (_, r), s in zip(mine, alone)
                    if not np.array_equal(r.output(), s.output())]
        if bad:
            raise SystemExit(
                f"mixed-codec identity FAILED for requests {bad}")
        print(f"token identity vs per-tenant-alone engines: OK "
              f"({len(reqs)} requests, "
              f"{len(eng._groups)} codec groups)", flush=True)

    if args.print_tokens:
        # per-request token dump for inspection. Do NOT diff these across
        # separate process runs — CPU XLA is not bit-deterministic across
        # processes (serve/README.md); the identity contract is checked
        # in-process by --check-identity, which is what CI runs.
        for r in reqs:
            print(f"tokens {r.rid} {r.tenant}: {' '.join(map(str, r.output()))}")

    if args.json:
        print(json.dumps(rep, indent=2))
    else:
        # occupancy (and, with a zero-width wall clock, tokens/sec) is
        # None when no decode step ran — e.g. --max-new 1, where every
        # request finishes on its prefill-produced first token
        tps = "n/a" if rep["tokens_per_sec"] is None \
            else f"{rep['tokens_per_sec']:.0f}"
        occ = "n/a" if rep["batch_occupancy"] is None \
            else f"{rep['batch_occupancy']:.2f}"
        print(f"served {len(reqs)} requests / {rep['total_tokens']} tokens in "
              f"{rep['wall_time_s']:.2f}s "
              f"({tps} tok/s, occupancy {occ}, "
              f"{len(eng.prefill_shapes)} prefill shapes)")
        for name, t in rep["tenants"].items():
            print(f"  {name}: {t['requests']} reqs, {t['tokens']} toks, "
                  f"ttft p50 {1e3 * t['ttft_p50']:.0f}ms "
                  f"latency p95 {1e3 * t['latency_p95']:.0f}ms")
        if rep.get("shards"):
            for s in rep["shards"]:
                # occupancy is None when no decode step ran (e.g. every
                # request finished on its prefill token with --max-new 1)
                occ = "n/a" if s["occupancy"] is None \
                    else f"{s['occupancy']:.2f}"
                uniq = "n/a" if s["unique_tenants_mean"] is None \
                    else f"{s['unique_tenants_mean']:.2f}"
                print(f"  data shard {s['shard']} (slots "
                      f"{s['slots'][0]}..{s['slots'][1] - 1}): "
                      f"occupancy {occ}, {s['tokens']} toks, "
                      f"unique tenants/step {uniq}")
            print(f"  max step imbalance: {rep['shard_imbalance_max']}")
        if rep.get("residency"):
            r_ = rep["residency"]
            hr = "n/a" if r_.get("hit_rate") is None \
                else f"{r_['hit_rate']:.2f}"
            print(f"  residency: {r_.get('resident_rows')}/"
                  f"{r_.get('capacity_rows')} rows resident "
                  f"({(r_.get('allocated_bytes') or 0) / 1e6:.2f}MB "
                  f"allocated), hit rate {hr}, {r_['value_steps']} value / "
                  f"{r_['packed_steps']} packed steps")

    if eng.trace is not None:
        trace = eng.trace.export(args.trace_out)
        from repro.serve.trace import validate_chrome_trace
        problems = validate_chrome_trace(trace)
        if problems:
            raise SystemExit("emitted trace failed validation: "
                             + "; ".join(problems))
        n_spans = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
        print(f"trace: {args.trace_out} ({n_spans} spans, "
              f"{eng.trace.n_request_spans} requests)", flush=True)
    if eng.telemetry is not None:
        # final snapshot at drain so the file always reflects the full run
        eng.telemetry.write(rep["wall_time_s"], eng._telemetry_payload())
        print(f"telemetry: {args.telemetry_out} "
              f"({eng.telemetry.n_written} snapshots)", flush=True)

    store = eng.store
    base_bytes = tree_bytes(base)
    n = len(store.ordered())
    print(f"memory: base {base_bytes / 1e6:.1f}MB + deltas "
          f"{store.total_bytes() / 1e6:.2f}MB vs {n} full models "
          f"{base_bytes * n / 1e6:.1f}MB")


if __name__ == "__main__":
    main()
