"""Scheduler: decode rows per decode step, from the engine's own
counters over the window (``Metrics.step_active`` / decode steps)."""


def read(run):
    m = run.engine_metrics
    if not m.n_decode_steps:
        return None
    return sum(m.step_active) / m.n_decode_steps
