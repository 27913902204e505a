"""Per-call device arithmetic shared by the program-step metric readers.

A call's rows are the live rows of a ``decode_step`` span (``live``) or
the staging rows of a ``prefill_chunk`` span (``rows``); its tokens per
row are 1 or the configuration's chunk.  A reader returns None when the
profiled slice holds no call of its program.
"""
from __future__ import annotations

import counts

ROWS = {"decode_step": "live", "prefill_chunk": "rows"}


def _calls(run, span: str):
    if run.device is None:
        return []
    tokens = 1 if span == "decode_step" else run.chunk
    return [(args[ROWS[span]], tokens, dev_s)
            for args, dev_s in run.device["calls"][span] if dev_s > 0]


def mean_ms(run, span: str):
    calls = _calls(run, span)
    if not calls:
        return None
    return 1e3 * sum(c[2] for c in calls) / len(calls)


def roofline(run, span: str):
    calls = _calls(run, span)
    if not calls:
        return None
    pk = counts.peak(run.device_kind)
    least = sum(counts.min_seconds(run.cfg, r, t, pk) for r, t, _ in calls)
    return 100.0 * least / sum(c[2] for c in calls)


def mfu(run, span: str):
    calls = _calls(run, span)
    if not calls:
        return None
    pk = counts.peak(run.device_kind)
    ops = sum(counts.call(run.cfg, r, t)[0] for r, t, _ in calls)
    return 100.0 * ops / (sum(c[2] for c in calls) * pk["bf16_flops_per_s"])
