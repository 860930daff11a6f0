"""Model step: the bytes the window's decode steps must move (base
weights once a step, each distinct tenant's packed delta, each row's
cache) over the device time of the traced window per decode step times
the chip's peak HBM bytes/s, %."""
from chipbench import costs
from chipbench.window_stats import decode_steps


def read(run):
    steps = decode_steps(run)
    if run.trace is None or not steps or run.trace.busy_s <= 0:
        return None
    fleet = dict(zip([f"tenant{i}" for i in
                      range(len(run.mix["fleet"]["tenants"]))],
                     run.mix["fleet"]["tenants"]))
    total = 0.0
    for s in steps:
        total += costs.base_weight_bytes(run.arch, len(s.decode_rows))
        for owner in {o for o, _ in s.decode_rows if o is not None}:
            total += costs.tenant_bytes(run.delta_shapes, fleet[owner])
        total += sum(costs.cache_bytes(run.arch, ctx)
                     for _, ctx in s.decode_rows)
    return 100.0 * total / (run.trace.busy_s * run.peaks["hbm_bytes_s"])
