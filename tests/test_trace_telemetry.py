"""Tracing + telemetry invariants (trace.py, telemetry.py, metrics glue).

Two layers:

* Pure-host tests (no jax): streaming histograms, SLO counters, the
  snapshot writer, Prometheus exposition, path attribution, and the
  tracer driven by a synthetic event stream — these pin the schema and
  the bounded-memory behavior.
* One engine integration fixture (smoke config, VirtualClock): a traced
  run whose exported Chrome trace must validate AND agree with the
  metrics report event-for-event — metrics and tracer consume the same
  bus, so any disagreement is a bug in one of them.

Everything runs on VirtualClock / explicit timestamps: no wall-clock
value reaches an assertion.
"""
import json
import math
import os

import numpy as np
import pytest

from repro.serve.metrics import Metrics, TenantStats
from repro.serve.telemetry import (
    SLOCounters,
    StreamingHistogram,
    TelemetrySnapshotWriter,
)
from repro.serve.trace import (
    EventBus,
    ServeEvent,
    Tracer,
    attribution,
    note_path,
    path_label,
    validate_chrome_trace,
)


# ---------------------------------------------------------------------------
# StreamingHistogram
# ---------------------------------------------------------------------------
def test_histogram_exact_below_cap_matches_numpy():
    h = StreamingHistogram()
    rng = np.random.RandomState(0)
    xs = rng.exponential(0.05, size=200)
    for x in xs:
        h.record(x)
    assert h.exact
    for q in (0, 50, 95, 99, 100):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q))
    assert h.mean == pytest.approx(xs.mean())
    assert h.n == 200
    assert h.vmin == xs.min() and h.vmax == xs.max()


def test_histogram_empty_matches_old_pct_contract():
    h = StreamingHistogram()
    assert h.percentile(50) is None
    assert h.mean is None
    assert h.n == 0


def test_histogram_spills_once_and_stays_bounded():
    h = StreamingHistogram(exact_cap=16)
    rng = np.random.RandomState(1)
    xs = rng.exponential(0.05, size=500)
    for x in xs:
        h.record(x)
    assert not h.exact                      # spilled past the cap
    assert h.n == 500
    assert int(h.counts.sum()) == 500       # every sample landed in a bucket
    # bucketed percentile: within one bucket ratio of the true value
    # (10^(1/5) ~ 1.58x), the documented bound
    for q in (50, 95):
        true = np.percentile(xs, q)
        got = h.percentile(q)
        assert true / 1.6 <= got <= true * 1.6
    # min/max/mean stay exact regardless of regime
    assert h.vmin == xs.min() and h.vmax == xs.max()
    assert h.mean == pytest.approx(xs.mean())


def test_histogram_bucket_layout_roundtrip():
    h = StreamingHistogram()
    # underflow, overflow, and a mid value land where bucket_le says
    assert h.bucket_index(0.0) == 0
    assert h.bucket_le(0) == h.lo
    assert math.isinf(h.bucket_le(h.n_buckets + 1))
    for x in (1e-5, 3e-3, 0.7, 42.0):
        i = h.bucket_index(x)
        assert h.bucket_le(i - 1) <= x <= h.bucket_le(i) * (1 + 1e-12)
    assert h.bucket_index(1e12) == h.n_buckets + 1    # overflow


def test_histogram_merge_exact_and_bucketed():
    a, b = StreamingHistogram(), StreamingHistogram()
    for x in (0.01, 0.02, 0.03):
        a.record(x)
    for x in (0.04, 0.05):
        b.record(x)
    m = a.merge(b)
    assert m.n == 5 and m.exact
    assert m.percentile(50) == pytest.approx(
        np.percentile([0.01, 0.02, 0.03, 0.04, 0.05], 50))
    # exact + bucketed pools into buckets, counts conserved
    c = StreamingHistogram(exact_cap=2)
    for x in (0.1, 0.2, 0.4):
        c.record(x)
    assert not c.exact
    m2 = a.merge(c)
    assert m2.n == 6 and not m2.exact
    assert int(m2.bucket_counts().sum()) == 6
    with pytest.raises(ValueError):
        a.merge(StreamingHistogram(per_decade=3))
    # merged() of nothing is a valid empty histogram
    assert StreamingHistogram.merged([]).percentile(50) is None


def test_histogram_to_dict_is_json_able():
    h = StreamingHistogram()
    h.record(0.5)
    d = h.to_dict()
    json.dumps(d)
    assert d["count"] == 1 and d["p50"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# SLO counters
# ---------------------------------------------------------------------------
def _ev(kind, t=0.0, **attrs):
    return ServeEvent(kind, t, attrs)


def test_slo_counters():
    slo = SLOCounters(ttft_target_s=0.1, itl_target_s=0.01)
    # TTFT violation for t0, within target for t1
    slo.consume(_ev("first_token", tenant="t0", ttft=0.5))
    slo.consume(_ev("first_token", tenant="t1", ttft=0.05))
    # deadline miss (negative slack), ITL violation: (1.0-0.5)/(6-1)=0.1
    slo.consume(_ev("done", tenant="t0", latency=1.0, ttft=0.5,
                    n_tokens=6, deadline_slack=-0.2))
    # no deadline -> never a miss; single token -> no ITL
    slo.consume(_ev("done", tenant="t1", latency=0.06, ttft=0.05,
                    n_tokens=1, deadline_slack=None))
    rep = slo.report()
    assert rep["requests_done"] == 2
    assert rep["ttft_violations"] == {"t0": 1}
    assert rep["deadline_misses"] == {"t0": 1}
    assert rep["itl_violations"] == {"t0": 1}


def test_slo_counters_disabled_targets_count_nothing():
    slo = SLOCounters()                     # no targets configured
    slo.consume(_ev("first_token", tenant="t0", ttft=99.0))
    slo.consume(_ev("done", tenant=None, latency=99.0, ttft=1.0,
                    n_tokens=50, deadline_slack=0.5))
    rep = slo.report()
    assert rep["ttft_violations"] == {} and rep["itl_violations"] == {}
    assert rep["deadline_misses"] == {}     # positive slack


# ---------------------------------------------------------------------------
# Snapshot writer
# ---------------------------------------------------------------------------
def test_snapshot_writer_interval_and_atomicity(tmp_path):
    path = str(tmp_path / "telemetry.json")
    w = TelemetrySnapshotWriter(path, interval_s=1.0)
    calls = []

    def payload():
        calls.append(1)
        return {"metrics": {"x": 1, "hist": _hist_with(0.5)}}

    assert w.maybe_write(0.0, payload)          # first call always writes
    assert not w.maybe_write(0.5, payload)      # inside interval: skipped
    assert len(calls) == 1                      # payload built lazily
    assert w.maybe_write(1.0, payload)
    with open(path) as f:
        snap = json.load(f)
    assert snap["t"] == 1.0 and snap["seq"] == 1
    assert snap["metrics"]["hist"]["count"] == 1   # histogram serialized
    assert not os.path.exists(path + ".tmp")       # rename completed
    with pytest.raises(ValueError):
        TelemetrySnapshotWriter(path, interval_s=0.0)


def _hist_with(*xs):
    h = StreamingHistogram()
    for x in xs:
        h.record(x)
    return h


# ---------------------------------------------------------------------------
# Path attribution
# ---------------------------------------------------------------------------
def test_note_path_noop_without_context():
    note_path("anywhere", formulation="x")            # must not raise


def test_attribution_collects_dedups_and_nests():
    with attribution() as outer:
        note_path("a", formulation="gather")
        note_path("a", formulation="gather")          # duplicate dropped
        with attribution() as inner:
            note_path("b", formulation="dense")
        assert inner == [{"site": "b", "formulation": "dense"}]
        note_path("c")
    assert outer == [{"site": "a", "formulation": "gather"}, {"site": "c"}]
    note_path("after")                                # context restored to None


def test_path_label():
    assert path_label([]) == "unknown"
    assert path_label([{"site": "s", "formulation": "segments-pallas"},
                       {"site": "r", "residency": "values"}]) \
        == "segments-pallas+values"
    assert path_label([{"site": "s", "formulation": "a"},
                       {"site": "t", "formulation": "a"},
                       {"site": "u", "formulation": "b"}]) == "a+b"
    assert path_label([{"site": "s", "dispatch": "segments"}]) == "unknown"


# ---------------------------------------------------------------------------
# Tracer on a synthetic event stream
# ---------------------------------------------------------------------------
def _lifecycle(bus, rid, tenant, t0, *, n_tokens=3):
    """One full request lifecycle offset to t0; returns finish time."""
    bus.emit("submit", t0, rid=rid, tenant=tenant, prompt_len=5)
    bus.emit("admit", t0 + 0.01, rid=rid, tenant=tenant, slot=0,
             wait=0.01, deadline_slack=1.0, prompt_len=5, bucket=8)
    bus.emit("prefill", t0 + 0.02, t_start=t0 + 0.01, rid=rid,
             tenant=tenant, prompt_len=5, bucket=8, slot=0)
    bus.emit("first_token", t0 + 0.02, rid=rid, tenant=tenant, ttft=0.02)
    t = t0 + 0.02
    for _ in range(n_tokens - 1):
        t += 0.01
        bus.emit("step", t, t_start=t - 0.01, n_active=1,
                 path="segments-xla+packed", recompiled=False)
        bus.emit("token", t, rid=rid, tenant=tenant)
    bus.emit("done", t, rid=rid, tenant=tenant, latency=t - t0,
             ttft=0.02, n_tokens=n_tokens, deadline_slack=0.5)
    return t


def test_tracer_builds_valid_chrome_trace(tmp_path):
    tr = Tracer()
    bus = EventBus([tr])
    bus.emit("start", 0.0)
    _lifecycle(bus, rid=1, tenant="t0", t0=0.0)
    _lifecycle(bus, rid=2, tenant=None, t0=0.05)
    bus.emit("jit_trace", 0.01, signature=("decode", True, False),
             site="decode", first=True, notes=[{"site": "x"}])
    bus.emit("jit_trace", 0.06, signature=("decode", True, True),
             site="decode", first=False, notes=[])
    bus.emit("stop", 1.0)

    trace = tr.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    assert tr.n_request_spans == 2
    names = [e["name"] for e in trace["traceEvents"]]
    assert names.count("request") == 2
    assert names.count("queue_wait") == 2
    assert names.count("prefill") == 2
    assert names.count("decode") == 2
    assert "jit_compile" in names and "jit_recompile" in names
    # request span args carry the SLO-relevant fields
    req = next(e for e in trace["traceEvents"] if e["name"] == "request")
    assert req["args"]["deadline_slack_s"] == 0.5
    assert req["args"]["tokens"] == 3
    # export + CLI validator agree
    out = str(tmp_path / "trace.json")
    tr.export(out)
    from repro.serve.trace import _main
    assert _main(["--validate", out]) == 0


def test_tracer_step_sampling_and_event_cap():
    tr = Tracer(step_sample=2)
    bus = EventBus([tr])
    for i in range(6):
        bus.emit("step", 0.01 * (i + 1), t_start=0.01 * i, n_active=1)
    steps = [e for e in tr.events if e["name"] == "decode_step"]
    assert len(steps) == 3                      # every 2nd kept
    with pytest.raises(ValueError):
        Tracer(step_sample=0)

    capped = Tracer(max_events=2)
    bus = EventBus([capped])
    for i in range(5):
        bus.emit("step", 0.01 * (i + 1), t_start=0.01 * i, n_active=1)
    _lifecycle(bus, rid=1, tenant="t0", t0=1.0)     # past the cap
    assert capped.dropped_events >= 3
    # request lifecycle spans still record past the cap
    assert capped.n_request_spans == 1
    assert capped.to_chrome_trace()["otherData"]["dropped_events"] >= 3


def test_validator_catches_structural_problems():
    assert validate_chrome_trace({}) == ["traceEvents missing or empty"]
    # spans but no request span
    bad = {"traceEvents": [
        {"name": "decode_step", "ph": "X", "pid": 2, "tid": 0,
         "ts": 0.0, "dur": 1.0, "args": {}}]}
    assert any("no request spans" in p for p in validate_chrome_trace(bad))
    # request span without child prefill+decode
    lonely = {"traceEvents": [
        {"name": "request", "ph": "X", "pid": 1, "tid": 1,
         "ts": 0.0, "dur": 5.0, "args": {}}]}
    assert any("child prefill+decode" in p
               for p in validate_chrome_trace(lonely))
    # non-monotonic timestamps
    shuffled = {"traceEvents": [
        {"name": "a", "ph": "i", "pid": 1, "tid": 1, "ts": 5.0, "s": "t"},
        {"name": "b", "ph": "i", "pid": 1, "tid": 1, "ts": 1.0, "s": "t"}]}
    assert any("monotonic" in p for p in validate_chrome_trace(shuffled))
    # negative ts
    neg = {"traceEvents": [
        {"name": "a", "ph": "i", "pid": 1, "tid": 1, "ts": -1.0, "s": "t"}]}
    assert any("bad ts" in p for p in validate_chrome_trace(neg))


def test_cli_validator_rejects_garbage(tmp_path):
    from repro.serve.trace import _main
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert _main(["--validate", str(bad)]) == 1
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert _main(["--validate", str(empty)]) == 1


# ---------------------------------------------------------------------------
# Metrics edge cases
# ---------------------------------------------------------------------------
def test_metrics_empty_run_report():
    m = Metrics(n_slots=4)
    rep = m.report()
    assert rep["wall_time_s"] == 0.0
    assert rep["tokens_per_sec"] is None
    assert rep["ttft_p50"] is None
    assert rep["batch_occupancy"] is None
    assert rep["decode_paths"] is None
    assert rep["tenants"] == {}


def test_metrics_wall_clamp_never_negative():
    m = Metrics(n_slots=1)
    m.start(10.0)
    m.stop(3.0)                                # stale t_end from a reset
    assert m.report()["wall_time_s"] == 0.0


def test_metrics_shard_token_range_guard():
    m = Metrics(n_slots=4, data_shards=2)
    m.record_shard_token(1)
    with pytest.raises(ValueError, match=r"shard 2 out of range for 2"):
        m.record_shard_token(2)
    with pytest.raises(ValueError, match="out of range"):
        m.record_shard_token(-1)
    assert m.shard_tokens == [0, 1]


def test_metrics_ragged_shard_rows_raise():
    m = Metrics(n_slots=4, data_shards=2)
    with pytest.raises(ValueError, match="shard_active has 3 entries"):
        m.record_step(2, shard_active=[1, 1, 1])
    with pytest.raises(ValueError, match="shard_unique has 1 entries"):
        m.record_step(2, shard_active=[1, 1], shard_unique=[1])
    # nothing partial leaked into the step matrices
    assert m.step_shard_unique == []


def test_metrics_consume_maps_event_stream():
    m = Metrics(n_slots=2, data_shards=2)
    bus = EventBus([m])
    bus.emit("start", 0.0)
    bus.emit("admit", 0.1, tenant="t0", wait=0.1)
    bus.emit("first_token", 0.2, tenant="t0", ttft=0.2)
    bus.emit("token", 0.2, tenant="t0")
    bus.emit("step", 0.3, n_active=2, shard_active=[1, 1],
             shard_unique=[1, 0], residency_used=True, path="p")
    bus.emit("shard_token", 0.3, shard=1)
    bus.emit("jit_trace", 0.3, signature="s", site="decode", first=True)
    bus.emit("done", 0.4, tenant="t0", latency=0.4)
    bus.emit("stop", 1.0)
    rep = m.report()
    assert rep["wall_time_s"] == 1.0
    assert rep["prefills"] == 1 and rep["decode_steps"] == 1
    assert rep["decode_paths"] == {"p": 1}
    assert rep["residency"]["value_steps"] == 1
    assert rep["unique_tenants_per_shard_mean"] == [1.0, 0.0]
    assert m.shard_tokens == [0, 1]
    assert m.jit_traces == 1
    assert rep["tenants"]["t0"]["ttft_p50"] == pytest.approx(0.2)


def test_tenant_stats_report_keys_backward_compatible():
    t = TenantStats()
    t.n_requests, t.n_tokens = 1, 4
    t.ttfts.record(0.2)
    t.queue_waits.record(0.1)
    t.latencies.record(0.4)
    rep = t.report(wall=2.0)
    assert set(rep) == {"requests", "tokens", "tokens_per_sec", "ttft_p50",
                        "ttft_p95", "queue_wait_p50", "latency_p50",
                        "latency_p95"}
    assert rep["tokens_per_sec"] == 2.0


# ---------------------------------------------------------------------------
# Engine integration: trace <-> metrics consistency under VirtualClock
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp  # noqa: F401
    from repro.configs import get_smoke_config
    from repro.core import DeltaDQSpec, compress
    from repro.models import lm
    from repro.serve import ContinuousEngine, VirtualClock

    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    ft = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(
            jax.random.fold_in(rng, 7), p.shape, jnp.float32).astype(p.dtype)
        if p.ndim >= 2 else p, base)
    deltas, _ = compress(base, ft, DeltaDQSpec(alpha=2.0, k_bits=8, h_g=32))

    out_dir = tmp_path_factory.mktemp("traced")
    tracer = Tracer()
    slo = SLOCounters(ttft_target_s=1e-9)     # everything violates: countable
    telem = TelemetrySnapshotWriter(str(out_dir / "telemetry.json"),
                                    interval_s=1e-4)
    eng = ContinuousEngine(cfg, base, n_slots=2, max_seq=32,
                           clock=VirtualClock(tick=1e-3),
                           trace=tracer, slo=slo, telemetry=telem)
    eng.register_tenant("t0", deltas)
    reqs = [eng.submit(t, np.arange(5 + i) % cfg.vocab, max_new_tokens=4,
                       arrival=0.001 * i, deadline=0.002 * i)
            for i, t in enumerate(("t0", None, "t0"))]
    metrics = eng.run()
    return eng, tracer, slo, telem, metrics.report(), reqs, out_dir


def test_traced_engine_trace_validates_and_matches_metrics(traced_run):
    eng, tracer, slo, telem, rep, reqs, out_dir = traced_run
    trace = tracer.to_chrome_trace()
    assert validate_chrome_trace(trace) == []

    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)

    # one source of truth: span counts == metrics counts
    assert tracer.n_request_spans == len(reqs) == rep["prefills"]
    assert len(by_name["request"]) == len(reqs)
    assert len(by_name["prefill"]) == len(reqs)
    assert len(by_name["decode"]) == len(reqs)
    assert len(by_name["decode_step"]) == rep["decode_steps"]
    # every generated token is attributed: request spans' token args sum
    # to the metrics total
    assert sum(e["args"]["tokens"] for e in by_name["request"]) \
        == rep["total_tokens"]
    # decode-path attribution resolved to a real label on every step
    assert rep["decode_paths"] is not None
    assert "unknown" not in rep["decode_paths"]
    assert sum(rep["decode_paths"].values()) == rep["decode_steps"]
    # step spans carry the same label(s) the metrics counted
    step_paths = {e["args"]["path"] for e in by_name["decode_step"]}
    assert step_paths <= set(rep["decode_paths"]) | {"base"}


def test_traced_engine_is_deterministic_on_virtual_clock(traced_run):
    """Same workload, fresh engine, same VirtualClock -> byte-identical
    trace JSON (the CI determinism contract for traces)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp  # noqa: F401
    from repro.configs import get_smoke_config
    from repro.core import DeltaDQSpec, compress
    from repro.models import lm
    from repro.serve import ContinuousEngine, VirtualClock

    eng0, tracer0 = traced_run[0], traced_run[1]
    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    ft = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(
            jax.random.fold_in(rng, 7), p.shape, jnp.float32).astype(p.dtype)
        if p.ndim >= 2 else p, base)
    deltas, _ = compress(base, ft, DeltaDQSpec(alpha=2.0, k_bits=8, h_g=32))
    tracer = Tracer()
    eng = ContinuousEngine(cfg, base, n_slots=2, max_seq=32,
                           clock=VirtualClock(tick=1e-3), trace=tracer)
    eng.register_tenant("t0", deltas)
    for i, t in enumerate(("t0", None, "t0")):
        eng.submit(t, np.arange(5 + i) % cfg.vocab, max_new_tokens=4,
                   arrival=0.001 * i, deadline=0.002 * i)
    eng.run()
    assert json.dumps(tracer.to_chrome_trace(), sort_keys=True) \
        == json.dumps(tracer0.to_chrome_trace(), sort_keys=True)


def test_traced_engine_slo_and_snapshots(traced_run):
    eng, tracer, slo, telem, rep, reqs, out_dir = traced_run
    # ttft target of 1ns: every request must have violated
    srep = slo.report()
    assert srep["requests_done"] == len(reqs)
    assert sum(srep["ttft_violations"].values()) == len(reqs)
    # deadlines were in the past relative to finish -> misses counted
    assert sum(srep["deadline_misses"].values()) >= 1
    # snapshots were written during run() on engine time
    assert telem.n_written >= 1
    with open(os.path.join(str(out_dir), "telemetry.json")) as f:
        snap = json.load(f)
    assert set(snap) >= {"t", "seq", "metrics", "slo"}
    assert snap["metrics"]["decode_steps"] <= rep["decode_steps"]


def test_reset_metrics_preserves_shards_and_rewires_bus(traced_run):
    eng = traced_run[0]
    old_metrics, shards = eng.metrics, eng.metrics.data_shards
    eng.reset_metrics()
    assert eng.metrics is not old_metrics
    assert eng.metrics.data_shards == shards
    assert eng.metrics.n_decode_steps == 0
    # the bus now feeds the NEW collector (and still the tracer/slo)
    assert eng.metrics in eng.bus.consumers
    assert old_metrics not in eng.bus.consumers
    assert eng.trace in eng.bus.consumers
    assert eng.slo in eng.bus.consumers
