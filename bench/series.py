"""Latency and rate arithmetic over every sample of a run.

Percentiles are taken over all samples at once (numpy's linear
interpolation), never over chunks or per-request means.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def ttfts(data) -> list[float]:
    """Seconds from each due time to its first token reaching the host,
    for the requests due in the window that produced one."""
    return [r.times[0] - r.due for r in data.in_window() if r.times]


def itl_gaps(data) -> list[float]:
    """Every gap between consecutive output tokens of the requests due in
    the window."""
    out: list[float] = []
    for r in data.in_window():
        out.extend(np.diff(r.times).tolist())
    return out


def window_tokens(data) -> int:
    """Output tokens that reached the host inside the window, from any
    request."""
    t0, t1 = data.window
    return sum(1 for r in data.reqs for t in r.times if t0 <= t < t1)
