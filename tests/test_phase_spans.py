"""Engine phase spans on the profiler clock and the ``delta_correction``
named scope.

* ``serve.trace.phase`` / ``PhaseTimes``: a profiler span per part of an
  engine step, whose host seconds also add up in ``Metrics.phases``.
* A CPU profiler trace of a small ``ContinuousEngine``: ``engine.step``
  holds ``engine.admit``, ``engine.prefill`` and ``engine.decode``, and
  each of those its phases, on the host thread that opened the window.
* The compiled decode and prefill programs: the correction's ops carry
  ``delta_correction`` in their HLO ``op_name``; the base matmuls do not.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import DeltaDQSpec, compress
from repro.core.apply import CORRECTION_SCOPE
from repro.models import lm
from repro.serve import ContinuousEngine, Metrics, VirtualClock
from repro.serve.trace import PhaseTimes, phase

PREFILL_PHASES = ["engine.prefill.prep", "engine.prefill.dispatch",
                  "engine.prefill.insert", "engine.prefill.wait",
                  "engine.prefill.emit"]
DECODE_PHASES = ["engine.decode.prep", "engine.decode.dispatch",
                 "engine.decode.wait", "engine.decode.emit"]


# ---------------------------------------------------------------------------
# phase() and PhaseTimes
# ---------------------------------------------------------------------------
def test_phase_times_sum_each_span():
    times = PhaseTimes()
    for _ in range(3):
        with phase("engine.decode", times, n_active=2):
            with phase("engine.decode.wait", times):
                pass
    assert times.count == {"engine.decode": 3, "engine.decode.wait": 3}
    assert times.seconds["engine.decode"] >= \
        times.seconds["engine.decode.wait"] >= 0.0


def test_phase_times_host_ms_is_span_less_wait():
    times = PhaseTimes()
    assert times.host_ms("engine.decode") is None
    for dur, wait in ((0.010, 0.007), (0.020, 0.015)):
        times.add("engine.decode", dur)
        times.add("engine.decode.wait", wait)
    assert times.host_ms("engine.decode") == pytest.approx(4.0)
    times.add("engine.prefill", 0.003)            # a span with no wait
    assert times.host_ms("engine.prefill") == pytest.approx(3.0)


def test_phase_span_passes_errors_through():
    times = PhaseTimes()
    with pytest.raises(KeyError):
        with phase("engine.step", times):
            raise KeyError("x")
    assert times.count == {"engine.step": 1}


# ---------------------------------------------------------------------------
# a small engine
# ---------------------------------------------------------------------------
def _tenant_deltas(cfg, base, seed):
    rng = jax.random.PRNGKey(seed)
    ft = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(
            jax.random.fold_in(rng, 7), p.shape, jnp.float32).astype(p.dtype)
        if p.ndim >= 2 else p, base)
    deltas, _ = compress(base, ft, DeltaDQSpec(alpha=2.0, k_bits=8, h_g=32))
    return deltas


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("llama3.2-1b")
    base = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, base, _tenant_deltas(cfg, base, 1)


def _engine(model, **kw):
    cfg, base, deltas = model
    eng = ContinuousEngine(cfg, base, n_slots=2, max_seq=32,
                           clock=VirtualClock(tick=1e-3), **kw)
    eng.register_tenant("t0", deltas)
    return eng


def _host_spans(trace_dir):
    """Events named ``bench.*``/``engine.*`` of the host thread line that
    holds ``bench.window``, as (name, start, end, stats)."""
    f = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                  recursive=True)
    pd = ProfileData.from_file(f[0])
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in ln.events
                   if e.name.startswith(("bench.", "engine."))]
            if any(n == "bench.window" for n, *_ in evs):
                return evs
    raise AssertionError("no host line holds bench.window")


def _inside(outer, spans):
    _, a, b, _ = outer
    return [s for s in spans if s is not outer and a <= s[1] and s[2] <= b]


def _profile(eng, tmp_path, submit):
    submit()                             # compile outside the trace
    eng.run()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        req = submit()
        eng.run()
    jax.profiler.stop_trace()
    return req, _host_spans(str(tmp_path))


def test_engine_phase_spans_nest_on_the_profiler_clock(model, tmp_path):
    """One prefill and two decode steps: engine.step > engine.admit,
    engine.prefill > its five phases, engine.decode > its four."""
    eng = _engine(model)
    req, spans = _profile(eng, tmp_path, lambda: eng.submit(
        "t0", np.arange(6), max_new_tokens=3, arrival=eng._now()))
    names = [n for n, *_ in spans]
    assert names.count("engine.step") == 2
    assert names.count("engine.prefill") == 1
    assert names.count("engine.decode") == 2
    window = spans[names.index("bench.window")]
    steps = [s for s in spans if s[0] == "engine.step"]
    assert all(s in _inside(window, spans) for s in steps)
    # every engine.* span but the steps lies inside one step
    for s in spans:
        if s[0].startswith("engine.") and s[0] != "engine.step":
            assert sum(s in _inside(st, spans) for st in steps) == 1, s[0]
    first = _inside(steps[0], spans)
    assert [n for n, *_ in first if n in (
        "engine.admit", "engine.prefill", "engine.decode")] == [
        "engine.admit", "engine.prefill", "engine.decode"]
    pre = spans[names.index("engine.prefill")]
    assert pre[3]["rid"] == req.rid and pre[3]["tenant"] == "t0"
    assert pre[3]["prompt_len"] == 6 and pre[3]["bucket"] == 8
    assert [n for n, *_ in _inside(pre, spans)] == PREFILL_PHASES
    for dec in (s for s in spans if s[0] == "engine.decode"):
        assert [n for n, *_ in _inside(dec, spans)] == DECODE_PHASES
        assert dec[3]["n_active"] == 1 and dec[3]["groups"] == 1
    assert steps[0][3]["queue"] == 1 and steps[1][3]["n_active"] == 1


def test_chunked_engine_decode_phases(model, tmp_path):
    """The combined step (decode rows plus a prompt chunk) opens the same
    four decode phases; a step that carries a chunk names its request."""
    eng = _engine(model, chunked_prefill=True, chunk_size=4)
    req, spans = _profile(eng, tmp_path, lambda: eng.submit(
        "t0", np.arange(6), max_new_tokens=2, arrival=eng._now()))
    decodes = [s for s in spans if s[0] == "engine.decode"]
    # two prompt chunks, then one decode step for the second token
    assert len(decodes) == 3
    for dec in decodes:
        assert [n for n, *_ in _inside(dec, spans)] == DECODE_PHASES
    assert [d[3].get("chunk_rid") for d in decodes] == [req.rid, req.rid,
                                                         None]
    assert "engine.prefill" not in [n for n, *_ in spans]


def test_metrics_phase_times_follow_the_engine(model):
    eng = _engine(model)
    for i in range(2):
        eng.submit("t0" if i else None, np.arange(5 + i), max_new_tokens=3)
    m = eng.run()
    ph = m.phases
    assert ph.count["engine.decode"] == m.n_decode_steps
    assert ph.count["engine.prefill"] == m.n_prefills == 2
    for span, parts in (("engine.decode", DECODE_PHASES),
                        ("engine.prefill", PREFILL_PHASES)):
        assert all(ph.count[p] == ph.count[span] for p in parts)
        assert 0.0 < ph.host_ms(span) <= ph.seconds[span] / \
            ph.count[span] * 1e3
    assert "phases" not in m.report()       # wall clock stays out
    eng.reset_metrics()
    assert eng.metrics.phases.count == {}
    assert isinstance(eng.metrics, Metrics)


# ---------------------------------------------------------------------------
# the named scope in the compiled programs
# ---------------------------------------------------------------------------
_OP = re.compile(r"=\s*\S+\s+([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scoped_ops(hlo_text):
    """(op, in the correction's scope) of every instruction with an
    ``op_name``."""
    out = []
    for line in hlo_text.splitlines():
        m, n = _OP.search(line), _OP_NAME.search(line)
        if m and n:
            out.append((m.group(1), CORRECTION_SCOPE in n.group(1)))
    return out


def _check_scope(hlo_text):
    ops = _scoped_ops(hlo_text)
    scoped = [op for op, s in ops if s]
    assert scoped, "no op carries the correction's scope"
    # the correction contracts by multiply + sum (deltalint DL001), so
    # every dot is a base matmul or attention, outside the scope
    dots = [s for op, s in ops if op in ("dot", "convolution")]
    assert dots and not any(dots)


def test_correction_scope_in_compiled_decode(model):
    eng = _engine(model)
    eng._refresh_stacked()
    sd, _ = eng._slot_delta(np.array([1, 0], np.int32))
    text = eng._decode.lower(
        eng.base, eng.kv.cache, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32), sd).compile().as_text()
    _check_scope(text)


def test_correction_scope_in_compiled_prefill(model):
    cfg, base, deltas = model
    eng = _engine(model)
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32),
             "positions": jnp.arange(8, dtype=jnp.int32)[None]}
    text = eng._prefill.lower(
        base, batch, lm.init_cache(cfg, 1, 32),
        eng.store.get("t0").deltas).compile().as_text()
    _check_scope(text)
