"""Correction: device time in scatter operations per prefill, ms, from
the profiler trace of the window."""


def read(run):
    n = sum(len(s.prefills) for s in run.window.steps)
    if run.trace is None or not n:
        return None
    s = run.trace.op_seconds("scatter")
    return None if s is None else s / n * 1e3
