"""95th percentile of every inter-token gap in the window, ms (host
clock; a gap's two tokens both stamped inside the window)."""
from chipbench.window_stats import itl_gaps, percentile


def read(run):
    gaps = itl_gaps(run)
    return percentile(gaps, 95) * 1e3 if gaps else None
