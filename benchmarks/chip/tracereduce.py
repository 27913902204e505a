"""From a profiler trace and the engine's spans to device metrics.

The traced run records two things on two clocks: the JAX profiler's
trace (``.xplane.pb``; device planes ``/device:TPU:<n>`` with an
``XLA Ops`` line, one event per operation, and an ``XLA Modules`` line,
one event per program execution) and the engine tracer's host spans
(``time.perf_counter`` seconds).  The harness brackets the profiled
interval with ``jax.profiler.TraceAnnotation(SYNC)`` events whose
perf_counter midpoints it notes, which puts both on one clock.

* Busy time is the union of the operation intervals on a device; the
  idle share is one minus busy over the traced interval.
* A program's device time per call: the serving program's jitted
  functions are anonymous (their modules carry the same name), so each
  call is found by time instead.  Both ``decode_step`` and
  ``prefill_chunk`` host spans end in a blocking read of the call's
  result, so the call's execution lies inside its span.  Within a span,
  the module executions named like the longest one are the call (a pool
  row scatter queued just before it carries another name).
* ``breakdown``: the operations that took most device time, and the
  longest idle gaps, each labelled with the innermost engine span open on
  the host at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import collections
import glob
from typing import Dict, List, Sequence, Tuple

SYNC = "chipbench_sync"
PROGRAM_SPANS = ("decode_step", "prefill_chunk")
LABEL_TIDS = (0, 1)        # the engine tracer's engine and host tracks


def load(trace_dir: str) -> List[dict]:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir`` as plain
    data: ``[{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}]``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def device_planes(planes: List[dict]) -> List[dict]:
    """Accelerator planes: a ``/device:`` plane with XLA operations (a TPU
    trace also carries ``/device:CUSTOM:...`` planes without any)."""
    return [p for p in planes if p["name"].startswith("/device:")
            and _line(p, "XLA Ops")]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def clock_offset(planes: List[dict], sync_pcs: Sequence[float]) -> float:
    """Seconds to add to a trace time (ns / 1e9) to get perf_counter."""
    mids = sorted((s + d / 2) / 1e9 for p in planes for ln in p["lines"]
                  for (n, s, d) in ln["events"] if n == SYNC)
    if len(mids) != len(sync_pcs):
        raise ValueError(f"found {len(mids)} sync events in the trace, "
                         f"expected {len(sync_pcs)}")
    offs = sorted(pc - m for pc, m in zip(sorted(sync_pcs), mids))
    return offs[len(offs) // 2]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(planes: List[dict], spans: List[tuple], sync_pcs: Sequence[float],
           top: int = 10) -> dict:
    """Device metrics of the traced interval ``[min(sync), max(sync)]``.

    ``spans``: the engine tracer's complete events as ``(name, tid, t0,
    t1, args)`` in perf_counter seconds.  Returns ``busy_s`` and
    ``window_s`` (busy averaged over the devices), ``calls`` (per program
    span name, a list of ``(args, device_s)``), and ``breakdown``."""
    off = clock_offset(planes, sync_pcs)
    lo, hi = min(sync_pcs), max(sync_pcs)
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace has no device plane")

    def to_pc(events):
        return [(n, s / 1e9 + off, (s + d) / 1e9 + off)
                for (n, s, d) in events]

    busy_total = 0.0
    op_time: Dict[str, float] = collections.defaultdict(float)
    calls: Dict[str, list] = {p: [] for p in PROGRAM_SPANS}
    gaps: List[tuple] = []
    label_spans = sorted((s for s in spans if s[1] in LABEL_TIDS),
                         key=lambda s: s[2])
    for dev in devs:
        ops = to_pc(_line(dev, "XLA Ops"))
        mods = to_pc(_line(dev, "XLA Modules")) or ops
        busy = merge(_clip([(a, b) for _, a, b in ops], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for n, a, b in ops:
            if b > lo and a < hi:
                op_time[n] += min(b, hi) - max(a, lo)
        mods.sort(key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, tid, t0, t1, args in spans:
            if name not in calls or t0 < lo or t1 > hi:
                continue
            i = bisect.bisect_left(starts, t0)
            inside = []
            while i < len(mods) and mods[i][1] <= t1:
                if mods[i][2] <= t1:
                    inside.append(mods[i])
                i += 1
            if not inside:
                continue
            main = max(inside, key=lambda m: m[2] - m[1])[0]
            dev_s = sum(b - a for n, a, b in inside if n == main)
            calls[name].append((args, dev_s))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    gaps.sort(key=lambda g: -g[0])
    gaps = [(s, _label(label_spans, mid)) for s, mid in gaps[:top]]
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_total / len(devs), "window_s": hi - lo,
            "calls": calls,
            "breakdown": {"device_ops": [[n, s / len(devs)]
                                         for n, s in ops_top],
                          "idle_gaps": [[lab, s] for s, lab in gaps]}}


def _label(spans: List[tuple], t: float) -> str:
    """Innermost (shortest) engine span open at ``t``."""
    best = None
    for name, tid, t0, t1, args in spans:
        if t0 > t:
            break
        if t1 >= t and (best is None or t1 - t0 < best[1]):
            best = (name, t1 - t0)
    return best[0] if best else "outside engine spans"


def engine_spans(tracer) -> List[tuple]:
    """The engine tracer's complete events as ``(name, tid, t0, t1,
    args)`` perf_counter tuples."""
    origin = tracer._t0
    return [(e["name"], e["tid"], origin + e["ts"] / 1e6,
             origin + (e["ts"] + e["dur"]) / 1e6, e["args"])
            for e in tracer.events if e["ph"] == "X"]
