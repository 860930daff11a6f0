"""Quantities of a measured window that several metric readers share."""
from __future__ import annotations

from typing import List

import numpy as np


def window_tokens(run) -> int:
    """Tokens stamped inside the window."""
    w = run.window
    return sum(1 for r in w.records for t in r.stamps if w.t0 < t <= w.t_end)


def itl_gaps(run) -> List[float]:
    """Every gap between consecutive tokens of a request, both stamped
    inside the window."""
    w = run.window
    out = []
    for r in w.records:
        s = [t for t in r.stamps if w.t0 <= t <= w.t_end]
        out += list(np.diff(s))
    return out


def ttfts(run) -> List[float]:
    """Submit to first token, for each request whose first token fell in
    the window."""
    w = run.window
    return [r.stamps[0] - r.t_submit for r in w.records
            if r.stamps and w.t0 < r.stamps[0] <= w.t_end]


def decode_steps(run) -> list:
    """Window steps that decoded at least one row."""
    return [s for s in run.window.steps if s.decode_rows]


def percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))
