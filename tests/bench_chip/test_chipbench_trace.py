"""The chip benchmark's trace reduction on a trace built by hand and on
one recorded by the JAX profiler here."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.trace_reduce import Event, Line, Plane  # noqa: E402


# operation names as a TPU v5e trace gives them (HLO text, shortened)
GATHER = ("%fusion.155 = f32[91750400]{0:T(1024)} fusion(f32[8,5120]{1,0:"
          "T(8,128)S(1)} %copy-done.2, s32[91750400]{0:T(1024)} "
          "%bitcast.787), kind=kCustom, calls=%fused_computation.4.clone")
SCATTER = ("%fusion.7 = f32[91750400]{0:T(1024)} fusion(s32[11468800]{0:"
           "T(1024)S(1)} %get-tuple-element.59, f32[11468800]{0:T(1024)} "
           "%get-tuple-element.60, f32[]{:T(128)} %constant.75), "
           "kind=kCustom, calls=%fused_computation.46")
SORT = ("%sort.6 = (s32[11468800]{0:T(1024)S(1)}, f32[11468800]{0:T(1024)}) "
        "sort(s32[11468800]{0:T(1024)} %bitcast.326, f32[11468800]{0:T(1024)"
        "S(1)} %bitcast.327), dimensions={0}, to_apply=%compare.6")
OTHER = ("%dynamic-update-slice.3178 = f32[1,8,11468800]{2,1,0:T(8,128)} "
         "dynamic-update-slice(f32[1,8,11468800]{2,1,0:T(8,128)} %gte.2, "
         "f32[1,8,1024]{2,1,0:T(8,128)} %multiply.179, u32[] %c.1)")


def _trace():
    ops = [Event(GATHER, 0, 10),
           Event(OTHER, 5, 10),
           Event(SCATTER, 20, 6),
           Event(SORT, 26, 4),
           Event("copy.4", 38, 4)]            # runs past the window's end
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Event("jit__step(1)", 0, 42)]),
        Line("XLA Ops", ops)])
    host = Plane("/host:CPU", [Line("python", [
        Event("bench.window", 0, 40),
        Event("bench.step", 14, 8),
        Event("bench.submit", 31, 2),
        Event("other", 0, 40)])])
    # a plane that is no chip must not count as one
    return [host, dev, Plane("/device:CUSTOM:Megascale Trace", [])]


def test_union_merges_overlaps():
    assert tr.union([(5, 15), (0, 10), (20, 30), (30, 31), (7, 7)]) == \
        [(0, 15), (20, 31)]


def test_summary_busy_idle_and_gaps():
    s = tr.summarize(_trace())
    assert s.window_s == pytest.approx(40e-9)
    # busy: [0, 15] + [20, 30] + [38, 40] (clipped) = 27 ns
    assert s.busy_s == pytest.approx(27e-9)
    assert s.n_devices == 1
    gaps = dict((round(sec * 1e9), label) for label, sec in s.idle_gaps)
    assert gaps == {5: "bench.step", 8: "bench.submit"}
    assert s.idle_gaps[0][1] == pytest.approx(8e-9)   # longest first


def test_op_kinds_from_hlo_text():
    assert tr.op_kind(GATHER) == "gather"
    assert tr.op_kind(SCATTER) == "scatter"
    assert tr.op_kind(SORT) == "scatter"
    assert tr.op_kind(OTHER) is None
    assert tr.op_kind("copy.4") is None
    op, result, operands = tr.hlo_parts(SORT)
    assert op == "sort" and result == [("s32", 11468800), ("f32", 11468800)]


def test_op_categories_and_top_ops():
    s = tr.summarize(_trace())
    assert s.op_seconds("gather") == pytest.approx(10e-9)
    assert s.op_seconds("scatter") == pytest.approx(10e-9)
    assert s.op_seconds("all-to-all") is None
    top = s.top_ops(2)
    assert {n for n, _ in top} == {tr.short_name(GATHER),
                                   tr.short_name(OTHER)}
    assert "{" not in top[0][0]
    assert top[0][1] == pytest.approx(10e-9)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        tr.summarize([_trace()[0]])


def test_recorded_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = tr.load(str(tmp_path))
    names = [e.name for e in tr.host_spans(planes)]
    assert tr.WINDOW in names and "bench.step" in names
    # the CPU backend writes no device plane: the reduction refuses it
    # rather than read host threads as a device
    if not tr.device_planes(planes):
        with pytest.raises(ValueError):
            tr.summarize(planes)
