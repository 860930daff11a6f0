"""95th percentile, over every request whose first token fell in the
window, of submit to first token, ms (host clock)."""
from chipbench.window_stats import percentile, ttfts


def read(run):
    xs = ttfts(run)
    return percentile(xs, 95) * 1e3 if xs else None
