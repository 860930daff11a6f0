"""Correction: device time in gather operations per decode step, ms,
from the profiler trace of the window."""
from chipbench.window_stats import decode_steps


def read(run):
    steps = decode_steps(run)
    if run.trace is None or not steps:
        return None
    s = run.trace.op_seconds("gather")
    return None if s is None else s / len(steps) * 1e3
