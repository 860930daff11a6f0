"""Scheduler: median wait from submit to admission, ms, from the
engine's own per-tenant queue-wait samples in the window (exact below
1024 samples)."""
from repro.serve.telemetry import StreamingHistogram


def read(run):
    hists = [t.queue_waits for t in run.engine_metrics.tenants.values()
             if t.queue_waits.n]
    if not hists:
        return None
    return StreamingHistogram.merged(hists).percentile(50) * 1e3
