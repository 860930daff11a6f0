"""Scheduler: host milliseconds per decode step, from the engine's own
phase spans (``Metrics.phases``): the mean over ``engine.decode`` spans
of their duration less their ``engine.decode.wait`` child, in which the
host waits on the device. The engine's metrics start before the mix's
set-up fill, so a filled cell counts the fill's step too. None where
the program keeps no phase times."""


def read(run):
    phases = getattr(run.engine_metrics, "phases", None)
    return None if phases is None else phases.host_ms("engine.decode")
