"""Output tokens completed in the window, all tenants, per second of
the window (host clock)."""
from chipbench.window_stats import window_tokens


def read(run):
    n = window_tokens(run)
    return n / run.window.seconds if n else None
