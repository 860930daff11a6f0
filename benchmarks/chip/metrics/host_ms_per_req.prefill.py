"""Scheduler: host milliseconds per prefill, from the engine's own phase
spans (``Metrics.phases``): the mean over ``engine.prefill`` spans of
their duration less their ``engine.prefill.wait`` child, in which the
host waits on the device. None where the program keeps no phase
times."""


def read(run):
    phases = getattr(run.engine_metrics, "phases", None)
    return None if phases is None else phases.host_ms("engine.prefill")
