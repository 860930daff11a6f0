"""Online tenant lifecycle: hot registration, rollout, retire, registry.

The acceptance bar for the tenant-table envelope:
* hot registration of tenant N+1 into a running engine triggers **zero
  decode-step recompiles** (the decode jit cache stays at one entry),
* an engine that hot-registers tenants mid-traffic is **token-identical**
  to an engine constructed with all tenants up front — for in-flight
  sequences and for the newly registered tenant,
* a version rollout serves the new version to new requests only;
  in-flight sequences drain against the old table row, which is then
  reclaimed,
* the registry's cold tiers round-trip: a tenant evicted to host RAM or
  the disk spool promotes back and serves the same tokens.

Plus regression tests for the live-mutation bug family fixed alongside:
kv claim/release raising ValueError (not assert), atomic
``_refresh_stacked`` (failed dynamic registration leaves the engine
untouched), and ``DeltaStore.register`` refusing silent same-name
replacement.

Determinism: every engine runs on a VirtualClock; every random draw is
explicitly seeded.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import CompileGuard
from repro.configs import get_smoke_config
from repro.core import DeltaDQSpec, compress
from repro.models import lm
from repro.serve import (
    ContinuousEngine,
    DeltaRegistry,
    DeltaStore,
    Metrics,
    SlotKVCache,
    Tracer,
    VirtualClock,
    validate_chrome_trace,
)
from repro.serve.registry import _load_npz, _save_npz
from repro.utils import flatten_with_paths

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

SPEC = DeltaDQSpec(alpha=2.0, k_bits=8, h_g=32)


def _ft_of(base, rng, t, scale=0.05):
    return jax.tree.map(
        lambda p, t=t: p + scale * jax.random.normal(
            jax.random.fold_in(rng, 7 + t), p.shape,
            jnp.float32).astype(p.dtype)
        if p.ndim >= 2 else p, base)


def _make_tenants(cfg, base, n, rng, scale=0.05):
    out = []
    for t in range(n):
        deltas, _ = compress(base, _ft_of(base, rng, t, scale), SPEC)
        out.append(deltas)
    return out


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3.2-1b")
    rng = jax.random.PRNGKey(0)
    base = lm.init_params(cfg, rng)
    tenants = _make_tenants(cfg, base, 4, rng)
    return cfg, base, tenants


def _prompts(cfg, n, length=8):
    rs = np.random.RandomState(0)
    return [rs.randint(0, cfg.vocab, size=length) for _ in range(n)]


def _engine(cfg, base, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("clock", VirtualClock(0.0))
    return ContinuousEngine(cfg, base, **kw)


# ---------------------------------------------------------------------------
# Tentpole: hot registration without recompile, token-identical
# ---------------------------------------------------------------------------

def test_hot_register_no_recompile_token_identical(setup):
    """Register tenant N+1 mid-traffic: zero decode recompiles, and both
    in-flight and new-tenant tokens match an all-up-front engine."""
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 3)

    ref = _engine(cfg, base, tenant_capacity=4)
    for i, d in enumerate(tenants[:3]):
        ref.register_tenant(f"t{i}", d)
    ref_reqs = [ref.submit(f"t{i}", prompts[i], max_new_tokens=6)
                for i in range(3)]
    ref.run()

    eng = _engine(cfg, base, tenant_capacity=4)
    for i, d in enumerate(tenants[:2]):
        eng.register_tenant(f"t{i}", d)
    r0 = eng.submit("t0", prompts[0], max_new_tokens=6)
    r1 = eng.submit("t1", prompts[1], max_new_tokens=6)
    # decode a few steps so t0/t1 are genuinely in flight
    for _ in range(3):
        eng.step(eng._now())
    guard = CompileGuard(eng, budgets={"decode": 1}, max_new={"decode": 0})
    eng.register_tenant("t2", tenants[2])          # HOT, mid-traffic
    r2 = eng.submit("t2", prompts[2], max_new_tokens=6)
    eng.run()

    # zero decode-step recompiles across the hot registration
    guard.check()
    # in-flight sequences untouched; the new tenant matches up-front
    assert list(r0.tokens) == list(ref_reqs[0].tokens)
    assert list(r1.tokens) == list(ref_reqs[1].tokens)
    assert list(r2.tokens) == list(ref_reqs[2].tokens)


def test_table_seeded_from_prepopulated_store(setup):
    """Tenants registered before the first step serve identically to
    tenants hot-registered after it — the identity contract both ways."""
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 2)
    a = _engine(cfg, base, tenant_capacity=3)
    a.register_tenant("t0", tenants[0])
    ra = a.submit("t0", prompts[0], max_new_tokens=5)
    a.run()
    b = _engine(cfg, base, tenant_capacity=3)
    b.step(b._now())                    # engine already running
    b.register_tenant("t0", tenants[0])
    rb = b.submit("t0", prompts[0], max_new_tokens=5)
    b.run()
    assert list(ra.tokens) == list(rb.tokens)


def test_rollout_old_version_drains_new_requests_switch(setup):
    """Re-registering a live tenant: in-flight stays on the old row, new
    requests see the new version, the old row is reclaimed after drain."""
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 2, length=6)
    eng = _engine(cfg, base, tenant_capacity=3)
    eng.register_tenant("t0", tenants[0])

    ref = _engine(cfg, base, tenant_capacity=3)
    ref.register_tenant("t0", tenants[0])
    ref_old = ref.submit("t0", prompts[0], max_new_tokens=8)
    ref.run()
    ref2 = _engine(cfg, base, tenant_capacity=3)
    ref2.register_tenant("t0", tenants[1])        # "new version" up front
    ref_new = ref2.submit("t0", prompts[1], max_new_tokens=8)
    ref2.run()

    r_old = eng.submit("t0", prompts[0], max_new_tokens=8)
    for _ in range(3):
        eng.step(eng._now())
    old_row = eng._rows["t0"]
    eng.register_tenant("t0", tenants[1])         # rollout mid-sequence
    new_row = eng._rows["t0"]
    assert new_row != old_row
    assert old_row in eng._retiring
    r_new = eng.submit("t0", prompts[1], max_new_tokens=8)
    eng.run()
    assert list(r_old.tokens) == list(ref_old.tokens)   # drained on old row
    assert list(r_new.tokens) == list(ref_new.tokens)   # served new version
    assert not eng._retiring                            # row reclaimed
    CompileGuard(eng, budgets={"decode": 1}).check()


def test_retire_frees_row_and_refuses_in_flight(setup):
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 2)
    eng = _engine(cfg, base, tenant_capacity=2)
    eng.register_tenant("t0", tenants[0])
    free_before = eng._table.n_free
    r = eng.submit("t0", prompts[0], max_new_tokens=4)
    eng.step(eng._now())
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.unregister_tenant("t0")
    eng.run()
    assert r.done
    eng.unregister_tenant("t0")
    assert eng._table.n_free == free_before + 1
    with pytest.raises(KeyError):
        eng.submit("t0", prompts[1], max_new_tokens=4)
    # the name is re-registrable after retirement
    eng.register_tenant("t0", tenants[1])
    CompileGuard(eng, budgets={"decode": 1}).check()


def test_table_full_and_incompatible_tenant_rejected(setup):
    cfg, base, tenants = setup
    eng = _engine(cfg, base, tenant_capacity=1)
    eng.register_tenant("t0", tenants[0])
    with pytest.raises(ValueError, match="full"):
        eng.register_tenant("t1", tenants[1])
    # a rejected registration is a no-op: t0 still serves
    r = eng.submit("t0", _prompts(cfg, 1)[0], max_new_tokens=3)
    eng.run()
    assert r.done


# ---------------------------------------------------------------------------
# Bugfix regressions
# ---------------------------------------------------------------------------

def test_kv_claim_release_raise_value_error():
    """Double-claim / double-free must raise ValueError, not assert —
    the guard has to survive ``python -O``."""
    cfg = get_smoke_config("llama3.2-1b")
    kv = SlotKVCache(cfg, n_slots=2, max_seq=8)
    kv.claim(0)
    with pytest.raises(ValueError, match="not free"):
        kv.claim(0)
    kv.release(0)
    with pytest.raises(ValueError, match="double-freed"):
        kv.release(0)
    assert kv.n_free == 2


def test_store_register_refuses_silent_replace(setup):
    cfg, base, tenants = setup
    store = DeltaStore()
    store.register("t0", tenants[0])
    with pytest.raises(ValueError, match="already registered"):
        store.register("t0", tenants[1])
    v = store.version
    store.register("t0", tenants[1], replace=True)
    assert store.version > v


def test_dynamic_reregister_refused_in_flight_engine_untouched(setup):
    """Dynamic mode: re-registering a tenant with in-flight sequences is
    refused, and the failed attempt leaves every piece of engine state
    (store, stacked groups, rows) exactly as before — the atomic
    ``_refresh_stacked`` contract."""
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 2)
    eng = _engine(cfg, base)                      # dynamic (no capacity)
    eng.register_tenant("t0", tenants[0])

    ref = _engine(cfg, base)
    ref.register_tenant("t0", tenants[0])
    rr = ref.submit("t0", prompts[0], max_new_tokens=6)
    ref.run()

    r = eng.submit("t0", prompts[0], max_new_tokens=6)
    eng.step(eng._now())
    version = eng.store.version
    rows = dict(eng._rows)
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.register_tenant("t0", tenants[1])
    assert eng.store.version == version           # store rolled back
    assert eng._rows == rows                      # stacked rows untouched
    eng.run()
    assert list(r.tokens) == list(rr.tokens)      # sequence unharmed


def test_registry_promote_with_full_table_keeps_host_tree(setup):
    """Regression: promoting a warm tenant when the table is full evicts
    a victim, whose spill pass must NOT pick the tenant being promoted
    (which would null its host tree mid-promotion)."""
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 3)
    eng = _engine(cfg, base, tenant_capacity=2)
    reg = DeltaRegistry(eng, base, spec=SPEC, codec=None,
                        spool_dir=None, host_capacity=1)
    for i in range(2):
        reg.ingest(f"t{i}", deltas=tenants[i])
    reg.pump()
    for i in range(2):
        reg.submit(f"t{i}", prompts[i], max_new_tokens=3)
    eng.run()
    reg.ingest("t2", deltas=tenants[2])
    reg.pump()                                    # evicts LRU -> warm
    warm = [n for n, r in reg._records.items() if r.state == "warm"]
    assert len(warm) == 1
    r = reg.submit(warm[0], prompts[0], max_new_tokens=3)   # promote
    eng.run()
    assert r.done
    assert reg._records[warm[0]].state == "hot"
    assert reg._records[warm[0]].host is not None


# ---------------------------------------------------------------------------
# Registry lifecycle
# ---------------------------------------------------------------------------

def test_registry_ingest_compress_register_serve(setup):
    cfg, base, _ = setup
    rng = jax.random.PRNGKey(0)
    eng = _engine(cfg, base, tenant_capacity=3)
    reg = DeltaRegistry(eng, base, spec=SPEC, codec="auto")
    rec = reg.ingest("a", _ft_of(base, rng, 0))
    assert rec.state == "ready" and rec.compress_s is not None
    assert reg.pump() == ["a"]
    assert rec.state == "hot" and rec.register_s is not None
    r = reg.submit("a", _prompts(cfg, 1)[0], max_new_tokens=4)
    eng.run()
    assert r.done and len(r.tokens) == 4
    CompileGuard(eng, budgets={"decode": 1}).check()


def test_registry_cold_spool_roundtrip_identity(setup, tmp_path):
    """Evict -> spill to disk -> promote serves the same tokens."""
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 1)
    eng = _engine(cfg, base, tenant_capacity=2)
    reg = DeltaRegistry(eng, base, spec=SPEC, codec=None,
                        spool_dir=str(tmp_path / "spool"), host_capacity=0)
    reg.ingest("a", deltas=tenants[0])
    reg.pump()
    r1 = reg.submit("a", prompts[0], max_new_tokens=5)
    eng.run()
    reg.evict("a")
    rec = reg._records["a"]
    assert rec.state == "cold" and rec.host is None
    assert rec.spool and os.path.exists(rec.spool)
    r2 = reg.submit("a", prompts[0], max_new_tokens=5)   # disk promote
    eng.run()
    assert rec.state == "hot"
    assert list(r2.tokens) == list(r1.tokens)


def test_registry_watch_dir_scan(setup, tmp_path):
    cfg, base, _ = setup
    rng = jax.random.PRNGKey(0)
    eng = _engine(cfg, base, tenant_capacity=2)
    watch = tmp_path / "watch"
    reg = DeltaRegistry(eng, base, spec=SPEC, codec="auto",
                        watch_dir=str(watch))
    assert reg.scan() == []                       # no dir yet: no-op
    ft = _ft_of(base, rng, 1)
    _save_npz(str(watch / "support-bot.npz"),
              {p: np.asarray(l) for p, l in flatten_with_paths(ft).items()})
    assert reg.scan() == ["support-bot"]
    assert reg.scan() == []                       # seen files not re-ingested
    reg.pump()
    r = reg.submit("support-bot", _prompts(cfg, 1)[0], max_new_tokens=4)
    eng.run()
    assert r.done


def test_registry_rollout_rollback(setup):
    cfg, base, tenants = setup
    prompts = _prompts(cfg, 1)
    eng = _engine(cfg, base, tenant_capacity=3)
    reg = DeltaRegistry(eng, base, spec=SPEC, codec=None)
    reg.ingest("a", deltas=tenants[0]); reg.pump()
    r1 = reg.submit("a", prompts[0], max_new_tokens=5); eng.run()
    reg.ingest("a", deltas=tenants[1]); reg.pump()      # v2 rollout
    assert reg._records["a"].version == 2
    reg.rollback("a")                                   # back to v1
    r3 = reg.submit("a", prompts[0], max_new_tokens=5); eng.run()
    assert list(r3.tokens) == list(r1.tokens)
    with pytest.raises(KeyError):
        reg.rollback("never-registered")
    reg.ingest("b", deltas=tenants[2]); reg.pump()
    with pytest.raises(ValueError, match="no previous"):
        reg.rollback("b")


def test_lifecycle_events_reach_metrics_and_tracer(setup, tmp_path):
    cfg, base, tenants = setup
    eng = _engine(cfg, base, tenant_capacity=2)
    tracer = Tracer()
    eng.bus.attach(tracer)
    reg = DeltaRegistry(eng, base, spec=SPEC, codec=None,
                        spool_dir=str(tmp_path / "spool"), host_capacity=0)
    reg.ingest("a", deltas=tenants[0]); reg.pump()
    reg.ingest("a", deltas=tenants[1]); reg.pump()      # rollout
    reg.ingest("b", deltas=tenants[2]); reg.pump()
    reg.evict("a")                                      # warm -> cold spill
    reg.promote("a")                                    # back to hot
    eng.unregister_tenant("b")                          # retire
    m = eng.metrics
    for kind in ("tenant_register", "tenant_rollout", "tenant_ready",
                 "tenant_evict", "tenant_promote", "tenant_retire"):
        assert m.lifecycle.get(kind, 0) >= 1, kind
    rep = m.report()
    assert rep["tenant_lifecycle"]["tenant_ready"] == 3
    names = {e["name"] for e in tracer.events if e.get("ph") == "i"}
    assert {"tenant_register", "tenant_rollout", "tenant_retire",
            "tenant_ready", "tenant_promote", "tenant_evict"} <= names
    validate_chrome_trace(tracer.to_chrome_trace())


def test_registry_background_worker(setup):
    """background=True: compression runs on the worker thread, pump()
    (serving-loop thread) picks up the finished record."""
    import time as _time
    cfg, base, _ = setup
    rng = jax.random.PRNGKey(0)
    eng = _engine(cfg, base, tenant_capacity=2)
    reg = DeltaRegistry(eng, base, spec=SPEC, codec=None, background=True)
    try:
        rec = reg.ingest("a", _ft_of(base, rng, 0))
        deadline = _time.time() + 60.0
        hot = []
        while not hot and _time.time() < deadline:
            hot = reg.pump()
            _time.sleep(0.01)
        assert hot == ["a"] and rec.state == "hot"
        r = reg.submit("a", _prompts(cfg, 1)[0], max_new_tokens=3)
        eng.run()
        assert r.done
    finally:
        reg.close()


def test_registry_compress_failure_recorded_not_raised(setup):
    cfg, base, _ = setup
    eng = _engine(cfg, base, tenant_capacity=2)
    reg = DeltaRegistry(eng, base, spec=SPEC, codec=None)
    rec = reg.ingest("bad", {"not": "a-param-tree"})
    assert rec.state == "failed" and rec.error
    assert reg.pump() == []                      # nothing went hot
    with pytest.raises(ValueError, match="ft_params or deltas"):
        reg.ingest("empty")


def test_npz_sidecar_roundtrips_bf16(tmp_path):
    arrs = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": jnp.arange(4, dtype=jnp.bfloat16)}
    path = str(tmp_path / "x.npz")
    _save_npz(path, {k: np.asarray(v) for k, v in arrs.items()})
    back = _load_npz(path)
    assert back["a"].dtype == np.float32
    assert back["b"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(back["a"], np.asarray(arrs["a"]))
    np.testing.assert_array_equal(back["b"], np.asarray(arrs["b"]))


# ---------------------------------------------------------------------------
# Property suite: lifecycle interleaved with traffic
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(st.lists(st.sampled_from(["register", "retire", "rollout",
                                     "traffic", "steps"]),
                    min_size=3, max_size=10),
           st.integers(0, 2 ** 31 - 1))
    def test_lifecycle_interleaving_never_corrupts(setup, ops, seed):
        """Any interleaving of register/retire/rollout with traffic keeps
        the engine serving, the decode jit cache at one entry, and the
        table's free-row accounting consistent."""
        cfg, base, tenants = setup
        prompts = _prompts(cfg, 4)
        rs = np.random.RandomState(seed)
        eng = _engine(cfg, base, tenant_capacity=3)
        live, version = {}, {}
        pending = []
        for op in ops:
            names = sorted(live)
            if op == "register" and len(live) < 3:
                n = f"t{len(version)}"
                try:
                    eng.register_tenant(n, tenants[rs.randint(4)])
                    version[n] = 0
                    live[n] = True
                except ValueError:
                    pass                      # retiring rows not drained yet
            elif op == "rollout" and names:
                n = names[rs.randint(len(names))]
                try:
                    eng.register_tenant(n, tenants[rs.randint(4)])
                except ValueError:
                    pass                      # no free row for the new version
            elif op == "retire" and names:
                n = names[rs.randint(len(names))]
                try:
                    eng.unregister_tenant(n)
                    del live[n]
                except RuntimeError:
                    pass                      # in-flight: correctly refused
            elif op == "traffic" and names:
                n = names[rs.randint(len(names))]
                pending.append(eng.submit(n, prompts[rs.randint(4)],
                                          max_new_tokens=3))
            elif op == "steps":
                for _ in range(2):
                    eng.step(eng._now())
            # invariants after every op
            CompileGuard(eng, budgets={"decode": 1}).check()
            rows = set(eng._rows.values())
            assert len(rows) == len(eng._rows)          # rows unique
            assert 0 not in rows                        # row 0 is base
            # live != free (the table exists from the first registration)
            free = set(eng._table._free) if eng._table is not None else set()
            assert not rows & free
        eng.run()
        for r in pending:
            assert r.done
