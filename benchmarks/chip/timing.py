"""End-to-end arithmetic over one window's records.

Percentiles are numpy's (linear interpolation) over every sample of the
window at once, never medians of chunks.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100), or None without samples."""
    if len(values) == 0:
        return None
    return float(np.quantile(np.asarray(values, np.float64), q / 100.0))


def due_in_window(window) -> list:
    """Open loop: every request due in the window (all of the schedule).
    Closed loop: the requests sent inside the window."""
    return [r for r in window.records
            if window.t0 <= r.due_pc < window.t1 or
            (window.loop == "open" and r.due_pc < window.t1)]


def ttfts(window) -> List[float]:
    """First-token time minus due time, for requests due in the window
    that produced a token (drain included)."""
    return [r.stamps[0] - r.due_pc for r in due_in_window(window)
            if r.stamps]


def queue_waits(window) -> List[float]:
    """Due time to admission (``Request.admit_pc``)."""
    return [r.admit_pc - r.due_pc for r in due_in_window(window)
            if r.admit_pc is not None]


def itl_gaps(window) -> List[float]:
    """Every gap between consecutive output tokens of one request whose
    later token was emitted inside the window."""
    out = []
    for r in window.records:
        s = np.asarray(r.stamps)
        if len(s) < 2:
            continue
        later = s[1:]
        keep = (later >= window.t0) & (later <= window.t1)
        out.extend(np.diff(s)[keep].tolist())
    return out


def tokens_in_window(window) -> int:
    return sum(int(np.sum((np.asarray(r.stamps) >= window.t0) &
                          (np.asarray(r.stamps) <= window.t1)))
               for r in window.records if r.stamps)


def attempted_failed(window) -> tuple:
    """Open loop: every request due in the window is attempted, and fails
    unless it completed its full budget (shed, truncated, poisoned,
    rejected or still unfinished at the drain cap).  Closed loop: the
    requests that ended inside the window; those cut at the close are
    neither."""
    if window.loop == "open":
        reqs = due_in_window(window)
    else:
        reqs = [r for r in window.records if r.status != "cut"]
    return len(reqs), sum(r.status != "ok" for r in reqs)
