"""Serve-stack span tracing: per-request timelines, engine phases,
recompile sentinels — behind a near-zero-overhead null tracer.

Every question the serving ROADMAP items keep asking ("where does a
request's wall time go?", "is prefill really the bottleneck?", "did
anything retrace after warmup?") needs finer data than aggregate
counters.  This module provides the three primitives the serve stack is
instrumented with:

* :class:`Tracer` — an in-memory span/event recorder whose output is
  **Chrome/Perfetto-compatible trace JSON** (``save``) and a structured
  **JSON-lines event log** (``save_jsonl``).  Spans are complete events
  (``ph: "X"``) on a small set of tracks (engine, host, queue, one per
  slot); gauges are counter events (``ph: "C"``); one-off facts are
  instants (``ph: "i"``).  ``launch/trace_report.py`` folds a saved
  trace into phase breakdowns, TTFT decompositions, and slot timelines.
  While enabled, every ``span`` also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so under a running
  profiler the engine's spans sit on the host timeline beside the
  device's operations, on one clock.
* :class:`NullTracer` — the default.  Every method is a no-op and
  ``span`` returns a shared do-nothing context manager (no profiler
  annotation), so the instrumented hot path costs a few dict builds
  and attribute lookups per *engine poll*.  What the *enabled* tracer
  costs is measured on the chip (``docs/observability.md``, Overhead);
  ``benchmarks/bench_serve_continuous.bench_phase`` times it on XLA-CPU
  at a reduced size, where its pair-to-pair noise band (about -23% to
  +13% in the committed ``BENCH_serve.json``) is far wider than the 2%
  it asserts, so that bench bounds only gross regressions.
* :class:`RecompileSentinel` — the compile-once discipline as a
  first-class check instead of an ad-hoc counter-string diff: it arms on
  a jitted callable's current cache size and counts every later growth
  as a *trip* (optionally raising in ``strict`` mode).  Engines check
  their sentinels every poll and re-arm them on ``reset_stats()`` (i.e.
  after warmup), so a trip always means "retraced after warmup".

Span taxonomy (see ``docs/observability.md`` for the full table):

==================  ====================================================
``serve.run``       one engine ``run()`` drain (engine track)
``poll``            one engine scheduling iteration (engine track)
``admit``           admission: queue pops + staging + prefix lookup
``prefix_lookup``   radix-cache longest-prefix match for one admission
``snapshot_restore``/``snapshot_export``  prefix-cache state row moves
``prefill_bucket``  one monolithic bucketed prefill program call
``prefill_chunk``   one chunked-prefill program call (all staging rows)
``decode_step``     one decode program call across all slots
``logits_to_host``  a program's logits copied to a host float32 array
``sample``          one host draw of next tokens from those logits
``pool_insert``/``pool_reset``  state-pool row scatter / zero
``host_gap``        time between polls (host track — idle + caller)
``queue``           per-request: arrival -> admission (queue track)
``staging``         per-request: admission -> first token (slot track)
``decode``          per-request: first token -> finish (slot track)
==================  ====================================================
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# Track ("tid") layout inside the single serve process ("pid" 0).
TID_ENGINE = 0      # compiled-program calls + host scheduling sections
TID_HOST = 1        # gaps between polls (idle / caller time)
TID_QUEUE = 2       # per-request queue-wait spans (overlapping is fine)
TID_SLOT0 = 100     # slot i's residency spans live on TID_SLOT0 + i

_TRACK_NAMES = {TID_ENGINE: "engine", TID_HOST: "host", TID_QUEUE: "queue"}


class _Span:
    """Context manager recording one complete event on ``__exit__``,
    inside a profiler annotation of the same name.

    ``args`` stays mutable until exit so callers can attach facts they
    only learn mid-span (e.g. how many requests an ``admit`` admitted).
    """

    __slots__ = ("_tr", "name", "tid", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, tid: int,
                 args: Dict[str, Any]):
        self._tr = tracer
        self.name = name
        self.tid = tid
        self.args = args

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._tr.complete(self.name, self._t0, t1, tid=self.tid,
                          **self.args)


class _NullSpan:
    """Shared do-nothing span: ``with NULL_TRACER.span(...):`` costs two
    method calls and nothing else."""

    __slots__ = ("args",)

    def __init__(self):
        self.args: Dict[str, Any] = {}

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        self.args.clear()


class NullTracer:
    """No-op tracer: the default for untraced engines.  ``enabled`` lets
    hot paths skip even argument construction when it matters."""

    enabled = False

    def __init__(self):
        self._null_span = _NullSpan()

    def span(self, name: str, tid: int = TID_ENGINE, **args) -> _NullSpan:
        return self._null_span

    def complete(self, name, t0, t1, tid=TID_ENGINE, **args) -> None:
        pass

    def instant(self, name, tid=TID_ENGINE, **args) -> None:
        pass

    def counter(self, name, values) -> None:
        pass

    def reset(self) -> None:
        pass

    def now(self) -> float:
        return time.perf_counter()


NULL_TRACER = NullTracer()


class Tracer(NullTracer):
    """In-memory event recorder; timestamps are ``time.perf_counter()``
    seconds converted to trace microseconds relative to construction.

    The hot path appends flat ``(ph, name, tid, t0, t1, args)`` tuples and
    the Chrome-format dicts are materialized lazily by :attr:`events`.
    This is a measured GC fix, not a style choice: per-emit dicts survive
    into the old generations and accelerate the collector's generational
    clock until a full gen-2 pass lands *inside* the serve drain (~100ms
    with jax's heap resident — an 8%+ wall hit on ``bench_phase``).
    Tuples of atoms get untracked at the first young collection, which
    keeps those full passes out of the traced drain."""

    enabled = True

    def __init__(self):
        super().__init__()
        self._t0 = time.perf_counter()
        # time.time() <-> perf_counter offset, fixed once, so wall-clock
        # stamps (Request.arrival_s) convert onto the trace clock.
        self._epoch = time.time() - self._t0
        self._raw: List[tuple] = []

    # -- clocks ------------------------------------------------------------
    def _us(self, t_pc: float) -> float:
        return (t_pc - self._t0) * 1e6

    def pc_from_walltime(self, t_wall: float) -> float:
        """Convert a ``time.time()`` stamp to the perf_counter clock."""
        return t_wall - self._epoch

    # -- emitters ----------------------------------------------------------
    def span(self, name: str, tid: int = TID_ENGINE, **args) -> _Span:
        return _Span(self, name, tid, args)

    def complete(self, name: str, t0: float, t1: float,
                 tid: int = TID_ENGINE, **args) -> None:
        """Record a complete event from perf_counter stamps ``t0..t1``."""
        self._raw.append(("X", name, tid, t0, t1, args or None))

    def instant(self, name: str, tid: int = TID_ENGINE, **args) -> None:
        self._raw.append(("i", name, tid, time.perf_counter(), 0.0,
                          args or None))

    def counter(self, name: str, values: Dict[str, float]) -> None:
        """One counter sample (Perfetto renders each key as a series)."""
        self._raw.append(("C", name, 0, time.perf_counter(), 0.0, values))

    # -- materialization ---------------------------------------------------
    @property
    def events(self) -> List[Dict[str, Any]]:
        """The recorded events as Chrome-trace dicts, in emission order,
        with each track's ``thread_name`` metadata emitted at first use
        (counter events carry no tid).  Rebuilt per access — read once,
        after the run."""
        out: List[Dict[str, Any]] = []
        named = set()
        for ph, name, tid, t0, t1, args in self._raw:
            if ph != "C" and tid not in named:
                named.add(tid)
                track = _TRACK_NAMES.get(tid, f"slot {tid - TID_SLOT0}")
                out.append({"name": "thread_name", "ph": "M", "pid": 0,
                            "tid": tid, "args": {"name": track}})
            if ph == "X":
                out.append({
                    "name": name, "cat": "serve", "ph": "X", "pid": 0,
                    "tid": tid, "ts": round(self._us(t0), 3),
                    "dur": round(max(0.0, (t1 - t0)) * 1e6, 3),
                    "args": args if args is not None else {}})
            elif ph == "i":
                out.append({
                    "name": name, "cat": "serve", "ph": "i", "s": "t",
                    "pid": 0, "tid": tid, "ts": round(self._us(t0), 3),
                    "args": args if args is not None else {}})
            else:
                out.append({
                    "name": name, "cat": "serve", "ph": "C", "pid": 0,
                    "ts": round(self._us(t0), 3), "args": args})
        return out

    def reset(self) -> None:
        """Drop recorded events (track names re-emit on next use).  The
        clock keeps its original origin so pre/post-reset timestamps stay
        comparable.  Engines call this from ``reset_stats()`` so a
        post-warmup trace starts at the measured window."""
        self._raw.clear()

    # -- output ------------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        """Chrome/Perfetto trace JSON (load in ui.perfetto.dev)."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
            f.write("\n")

    def save_jsonl(self, path: str) -> None:
        """Structured event log: one JSON object per line, in emission
        order — greppable / streamable where the Chrome JSON is not."""
        with open(path, "w") as f:
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")


class RecompileError(RuntimeError):
    """A recompile sentinel tripped in strict mode."""


class RecompileSentinel:
    """Compile-once discipline as a checkable invariant.

    Wraps a jitted callable; ``check()`` compares the callable's current
    jit-cache size against the armed baseline and counts growth as
    *trips*.  The first check (or :meth:`arm`) sets the baseline without
    counting, so warmup compiles are free; engines re-arm on
    ``reset_stats()``.  In ``strict`` mode a trip raises
    :class:`RecompileError` instead of just counting — benchmarks run
    strict so a retrace fails loudly at the step that caused it.

    On jax versions without a jit cache-size probe the sentinel is
    inert: ``supported`` is False and ``check()`` always returns 0.

    ``program_id`` (a ``serve/program_registry`` id like ``p0:decode``)
    rides in the trip instant so ``trace_report``'s recompile audit
    names the offending *program*, not just a span label.  ``fn_getter``
    defers callable resolution to check time, for programs built lazily
    after the sentinel exists (the state pools' row ops): until the
    getter returns a jitted fn the sentinel reads size -1 and stays
    inert, then lazy-arms on first sight.
    """

    def __init__(self, name: str, fn=None, strict: bool = False, *,
                 program_id: Optional[str] = None, fn_getter=None):
        self.name = name
        self._fn = fn
        self._fn_getter = fn_getter
        self.strict = strict
        self.program_id = program_id
        self.trips = 0
        self._baseline: Optional[int] = None

    @property
    def supported(self) -> bool:
        return self._size() >= 0

    def rebind(self, fn) -> None:
        """Point the sentinel at a rebuilt jit (backend fallback swaps
        the programs underneath); the caller re-arms afterwards."""
        self._fn = fn

    def _size(self) -> int:
        fn = self._fn
        if fn is None and self._fn_getter is not None:
            fn = self._fn_getter()
        try:
            return fn._cache_size()
        except Exception:
            return -1

    def arm(self) -> None:
        """(Re)baseline at the current cache size; zero the trip count."""
        self._baseline = self._size()
        self.trips = 0

    def check(self, tracer: NullTracer = NULL_TRACER) -> int:
        """Count (and optionally raise on) cache growth since arming;
        returns the cumulative trip count."""
        n = self._size()
        if n < 0:
            return 0
        if self._baseline is None or (self._baseline < 1 and n > 0):
            # Lazy arm: the first time the program shows up compiled, all
            # of its traces so far are warmup.  (Benchmarks arm
            # explicitly via reset_stats() after their warmup pass, which
            # also covers multi-bucket prefill programs.)  A baseline
            # below zero means the program didn't exist when armed (a
            # lazily-built op behind fn_getter) — same treatment.
            self._baseline = n
            return self.trips
        if n > self._baseline:
            new = n - self._baseline
            self._baseline = n
            self.trips += new
            extra = ({"program_id": self.program_id}
                     if self.program_id else {})
            tracer.instant("recompile", program=self.name, new_traces=new,
                           trips=self.trips, **extra)
            if self.strict:
                raise RecompileError(
                    f"compiled program {self.name!r} retraced after warmup "
                    f"({new} new trace(s), {self.trips} total trips)")
        return self.trips
