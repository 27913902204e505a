"""The engine's host round trip, from its tracer's spans in the window.

Each program call that yields tokens is followed, as siblings under
``poll``, by ``logits_to_host`` (its logits copied to a host float32
array) and ``sample`` (the host draw of next tokens).  Both carry
``phase`` (``decode`` or ``prefill``) and ``rows``; ``sample`` also
carries ``used``, the rows whose token is kept.  A span counts if it
lies wholly inside the window.  A program that records no such spans
gives none, and the readers then return None.
"""
from __future__ import annotations

from typing import Optional


def spans(run, name: str, phase: Optional[str] = None) -> list:
    """``(seconds, args)`` of the window's ``name`` spans, of one phase
    when ``phase`` is given."""
    w = run.window
    return [(t1 - t0, args) for n, tid, t0, t1, args in run.spans
            if n == name and w.t0 <= t0 and t1 <= w.t1
            and (phase is None or args.get("phase") == phase)]


def mean_ms(run, name: str, phase: str):
    found = spans(run, name, phase)
    if not found:
        return None
    return 1e3 * sum(s for s, _ in found) / len(found)


def row_use(run):
    """Percent of the rows drawn whose token is kept, over all draws."""
    found = spans(run, "sample")
    rows = sum(args["rows"] for _, args in found)
    if not rows:
        return None
    return 100.0 * sum(args["used"] for _, args in found) / rows
