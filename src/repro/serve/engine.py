"""Wave-mode serving engine — lockstep batches over the shared state pool.

NPUs (and jit) require fixed shapes, so the paper enables SSMs with a
fixed-token prefill model (padding shorter inputs) plus a separate
cached-state decode model.  The serve subsystem realizes that discipline
twice, over the same building blocks (``scheduler`` admission, ``sampling``,
``state_pool`` allocation, ``metrics``):

* **this module** — *wave* policy: requests are grouped into fixed-size
  batches that prefill together (bucketed, left-padded) and decode in
  lockstep; EOS'd rows keep decoding into a sink but stop being reported.
  Simple, but a straggler holds every finished slot until the wave drains.
* **``continuous``** — slot policy: finished slots are refilled from the
  queue mid-decode (see ``repro/serve/continuous.py``).

Left-padding keeps every live request in a wave aligned at the same
position index, which is what lets SSM (position-free) and attention
(position-indexed) families share one engine.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import sampling
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import (Request, Scheduler, bucket_for,
                                   build_request)
from repro.serve.program_registry import ProgramRegistry
from repro.serve.state_pool import (StatePool, format_compile_count,
                                    jit_cache_size)
from repro.serve.tracing import (NULL_TRACER, TID_QUEUE, TID_SLOT0,
                                 RecompileSentinel, Tracer)

Array = jax.Array
log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    prefill_buckets: Sequence[int] = (32, 128, 512)
    max_new_tokens: int = 32
    eos_id: int = -1            # -1: never stops early
    pad_id: int = 0
    temperature: float = 0.0    # 0 => greedy
    seed: int = 0
    policy: str = "fcfs"        # admission order: fcfs | priority
    # -- chunked prefill (continuous engine only) ---------------------------
    # Chunk size in tokens: prompts left-pad to a chunk multiple and prefill
    # one chunk per engine step, interleaved with the decode batch, instead
    # of running one monolithic bucketed prefill that stalls every live
    # slot.  None keeps the monolithic path.  The wave engine ignores it.
    prefill_chunk: Optional[int] = None
    # Max prefill tokens processed per poll, counted as chunk_size per
    # actively-prefilling slot per chunk call.  0 = exactly one chunk call
    # per poll (the lowest decode-latency jitter); larger budgets drain
    # long prompts faster at the cost of stalling decode for longer.
    prefill_token_budget: int = 0
    # -- prefix-state cache (continuous engine, needs prefill_chunk) --------
    # Host-byte budget (MB) for cross-request reuse of chunk-boundary
    # state snapshots: admissions skip past any cached prompt prefix
    # (``serve/prefix_cache.py``; docs/prefix_cache.md).  0 disables.
    prefix_cache_mb: float = 0.0
    # Snapshot granularity in tokens — must be a multiple of
    # prefill_chunk; None means one snapshot per prefill chunk.  Coarser
    # grains store fewer, larger entries (less snapshot overhead, less
    # sharing resolution).
    prefix_chunk: Optional[int] = None
    # -- self-speculative decoding (continuous engine only) -----------------
    # Draft this many tokens per burst with the cheap draft params (a w8
    # quantization of the serve params unless the engine is given one
    # explicitly), verify them in ONE batched full-precision verify_chunk
    # call, emit the longest verified prefix + one correction token, and
    # restore mismatching rows from their pre-burst state snapshot (O(1)
    # bytes for SSM families).  Greedy outputs are byte-identical to the
    # non-speculative path; sampled outputs too, because the continuous
    # engine keys sampling noise on (seed, uid, position) rather than the
    # step counter (``serve/sampling.py: sample_keyed``).  0 disables.
    # See serve/speculative.py and docs/serving.md.
    speculate_k: int = 0
    # Quant mode for the auto-derived draft params (``nn/quant.py``).
    speculate_draft: str = "w8"
    # -- observability (docs/observability.md) ------------------------------
    # Truthy enables per-request span tracing (``serve/tracing.py``); the
    # engine records events in memory and the caller saves them
    # (``engine.tracer.save(path)`` — launch/serve wires --trace PATH).
    # Falsy keeps the near-zero-overhead null tracer.
    trace: object = None
    # Emit a metrics snapshot every N engine polls (0 = off): windowed
    # gauges + histogram quick stats into ``engine.metrics.snapshots``
    # and, when tracing, the trace's counter track / JSONL log.
    metrics_every: int = 0
    # Recompile sentinels raise RecompileError on any post-warmup retrace
    # of a compiled serve program instead of just counting trips.
    strict_recompile: bool = False
    # Deadline (seconds) for the continuous engine's hang watchdog: fires
    # when no compiled call completes within the deadline.  0 disables.
    watchdog_s: float = 0.0
    # -- fault tolerance (continuous engine; docs/robustness.md) ------------
    # Fault-injection plan: a FaultInjector, a spec string (see
    # runtime/faults.parse_plan), or an iterable of FaultEvent.  None (the
    # default) serves with zero injection machinery in the hot path.
    fault_plan: object = None
    # Bounded admission queue: submit() REFUSES (returns None, counted in
    # metrics.rejected) once the queue holds this many requests — explicit
    # backpressure instead of unbounded memory growth.  0 = unbounded.
    max_queue_depth: int = 0
    # Degraded overload mode: entered when queue depth reaches
    # overload_queue_depth OR windowed TTFT p95 crosses
    # overload_ttft_p95_s (either 0 disables that trigger); while
    # degraded, the prefill token budget drops to 0 (one chunk per poll)
    # and speculative bursts pause so decode latency of admitted work is
    # protected.  Cleared with hysteresis: queue depth must fall to
    # overload_clear_frac * overload_queue_depth.
    overload_queue_depth: int = 0
    overload_ttft_p95_s: float = 0.0
    overload_clear_frac: float = 0.5
    # Poison quarantine probes: "off" | "logits" (np.isfinite over the
    # step's already-host logits — near-free) | "state" (adds a jitted
    # per-row finiteness probe over the decode pool).  A poisoned slot is
    # reset and its request finished with status "poisoned".
    poison_probe: str = "off"
    poison_check_every: int = 1   # probe every N polls (amortize "state")
    # Backend fallback chain: on a compiled-call failure, rebuild the
    # model one decode mode down (pallas -> cumba -> naive) and retry —
    # once per mode per process.  False re-raises immediately.
    backend_fallback: bool = True
    # Watchdog escalation: "log" (default, metrics + trace instant only)
    # or "recover" (abort the stuck burst at the next poll, requeue its
    # requests with bounded retries + exponential backoff).
    watchdog_action: str = "log"
    max_retries: int = 1
    retry_backoff_s: float = 0.0  # base for runtime.elastic.backoff_delay_s
    # Deadline shedding for requests already *in flight* (staged or
    # decoding), not just queued ones.  Off by default: pre-existing
    # deployments treat deadline_s as an admission SLA only.
    shed_inflight: bool = False
    # -- flight recorder (continuous engine; serve/flight_recorder.py) ------
    # Keep the last N completed-request timelines in a bounded ring and
    # dump them (JSONL at flight_path) whenever a fault event fires —
    # quarantine, watchdog hang/recovery, shed, retry, backend fallback.
    # 0 disables the recorder entirely.  Near-zero steady-state cost
    # (one small dict per completed request, no per-step work).
    flight_records: int = 0
    flight_path: Optional[str] = None


class EngineBase:
    """Plumbing shared by the wave and continuous engines: the two jitted
    programs, uid / sampling-step counters, submit-time bookkeeping, and
    compile counters.  Subclasses provide the serving policy (``run``)
    and must create ``self._scheduler``."""

    def __init__(self, model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.tracer = Tracer() if getattr(cfg, "trace", None) else NULL_TRACER
        self._scheduler = Scheduler(getattr(cfg, "policy", "fcfs"),
                                    tracer=self.tracer)
        self._uid = 0
        self._step = 0              # sampling-rng step counter
        self.metrics = ServeMetrics(cfg.max_batch, tracer=self.tracer,
                                    metrics_every=getattr(cfg,
                                                          "metrics_every", 0))
        # Every compiled program the engine warms up registers here for
        # program-level attribution: stable ids ride through sentinels
        # and trace spans, and cost/quality cards build lazily on demand
        # (never on the hot path — see serve/program_registry.py).  The
        # wave engine registers decode/prefill name-only (its shapes
        # vary per wave); the continuous engine attaches example shapes.
        self.registry = ProgramRegistry()
        # Compile-once discipline as first-class sentinels: checked every
        # poll/wave, re-armed by reset_stats() (i.e. after warmup), so a
        # trip always means a *post-warmup* retrace.
        self.sentinels: Dict[str, RecompileSentinel] = {}
        self._build_programs()

    def _build_programs(self) -> None:
        """(Re)build every compiled program over ``self.model`` — at
        construction and on a backend rebuild.  Each program jits a
        function named after its registry name, so its XLA module is
        ``jit_<name>`` (``jit_decode``, ``jit_prefill``) and a device
        profile tells the programs apart."""
        model = self.model
        # One-time pre-sliced view of stacked layer weights for the decode
        # program (zero per-step weight copies); prefill keeps the stacked
        # layout (scan-over-layers, one trace).
        self._decode_params = getattr(model, "decode_view",
                                      lambda p: p)(self.params)

        def decode(p, tok, cache, idx):
            return model.decode_step(p, tok, cache, idx)

        def prefill(p, batch, cache):
            return model.prefill(p, batch, cache)

        # The cache pytree is DONATED into the decode program: every step
        # updates slot state in place (zero per-step state copies) while
        # the compile-once discipline keeps the program count at one.
        # (Prefill must NOT donate: its input cache is a reused scratch.)
        self._decode = self._program(decode, donate_argnums=(2,))
        self._prefill = self._program(prefill)

    def _program(self, fn, **jit_kw):
        """Jit ``fn`` as the program named ``fn.__name__``: registered
        name-only (an engine that knows its serve shapes attaches them)
        and watched by a fresh recompile sentinel."""
        name = fn.__name__
        jitted = jax.jit(fn, **jit_kw)
        self.registry.register(name, jitted)
        self.sentinels[name] = RecompileSentinel(
            name, jitted, strict=getattr(self.cfg, "strict_recompile", False),
            program_id=self.registry.program_id(name))
        return jitted

    def _buckets(self) -> Sequence[int]:
        return self.cfg.prefill_buckets

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None, *,
               priority: int = 0, deadline_s: Optional[float] = None,
               on_token=None) -> Optional[int]:
        """Queue a request; returns its uid, or **None** when the bounded
        admission queue (``max_queue_depth``) is full — explicit
        backpressure the caller must handle (resubmit later or surface
        the rejection upstream)."""
        depth_cap = getattr(self.cfg, "max_queue_depth", 0)
        if depth_cap and len(self._scheduler) >= depth_cap:
            self.metrics.record_reject()
            self.tracer.instant("reject", queue_depth=len(self._scheduler))
            log.warning("admission queue full (%d): rejecting request",
                        depth_cap)
            return None
        self._uid += 1
        req = build_request(
            self._uid, prompt,
            max_new_tokens or self.cfg.max_new_tokens,
            priority=priority, deadline_s=deadline_s, on_token=on_token,
            buckets=self._buckets(), metrics=self.metrics)
        self._scheduler.submit(req)
        return req.uid

    def _logits_to_host(self, logits, phase: str) -> np.ndarray:
        """A program's logits as a host float32 array, copied under the
        ``logits_to_host`` span (``phase``: ``decode`` | ``prefill``)."""
        with self.tracer.span("logits_to_host", phase=phase,
                              rows=logits.shape[0]):
            return np.asarray(logits, np.float32)

    def _sample(self, logits) -> np.ndarray:
        out = sampling.sample(np.asarray(logits, np.float32),
                              self.cfg.temperature,
                              sampling.step_rng(self.cfg.seed, self._step))
        self._step += 1
        return out

    def _sample_rows(self, logits, uids, positions) -> np.ndarray:
        """Keyed sampling (continuous engine): noise is a pure function of
        ``(seed, uid, position)``, so a token's draw doesn't depend on
        slot assignment, batch composition, or whether it came from a
        decode step or a speculative verify chunk — spec-on/off streams
        match even under temperature (``serve/sampling.py``)."""
        return sampling.sample_keyed(np.asarray(logits, np.float32),
                                     self.cfg.temperature, self.cfg.seed,
                                     uids, positions)

    @property
    def busy(self) -> bool:
        return len(self._scheduler) > 0

    @property
    def counters(self) -> dict:
        return {"decode_compiles":
                format_compile_count(jit_cache_size(self._decode)),
                "prefill_compiles":
                format_compile_count(jit_cache_size(self._prefill)),
                "recompile_trips":
                {name: s.trips for name, s in self.sentinels.items()}}

    def check_sentinels(self) -> None:
        """Run every recompile sentinel (cheap jit-cache-size probes)."""
        for s in self.sentinels.values():
            s.check(self.tracer)

    @property
    def expired(self) -> List[Request]:
        """Requests shed because their deadline passed while queued."""
        return self._scheduler.expired

    def reset_stats(self) -> None:
        """Drop accumulated metrics and trace events and re-arm the
        recompile sentinels (e.g. after a compile warmup) — everything
        observed afterwards is post-warmup."""
        self.metrics.reset()
        self.tracer.reset()
        for s in self.sentinels.values():
            s.arm()

    def close(self) -> None:
        """Release background resources (watchdog threads); engines stay
        usable for inspection afterwards."""


class Engine(EngineBase):
    def __init__(self, model, params, cfg: ServeConfig):
        super().__init__(model, params, cfg)
        self._wall_s = 0.0          # summed sequential wave wall time

    def _bucket_for(self, length: int) -> int:
        return bucket_for(self.cfg.prefill_buckets, length)[0]

    def reset_stats(self) -> None:
        self._wall_s = 0.0
        super().reset_stats()

    # ------------------------------------------------------------------
    def run(self) -> List[Request]:
        """Serve everything in the queue; returns completed requests."""
        done: List[Request] = []
        while len(self._scheduler):
            wave: List[Request] = []
            now = time.time()
            n_shed0 = len(self._scheduler.expired)
            while len(wave) < self.cfg.max_batch and len(self._scheduler):
                req = self._scheduler.pop_ready(now)
                if req is None:
                    break
                req.admit_pc = time.perf_counter()
                if self.tracer.enabled:
                    self.tracer.complete(
                        "queue", self.tracer.pc_from_walltime(req.arrival_s),
                        req.admit_pc, tid=TID_QUEUE, uid=req.uid)
                wave.append(req)
            for _ in range(len(self._scheduler.expired) - n_shed0):
                self.metrics.record_shed()
            if wave:
                done.extend(self._run_wave(wave))
        return done

    def _run_wave(self, wave: List[Request]) -> List[Request]:
        cfg = self.cfg
        t0 = time.time()
        wave_span = self.tracer.span("poll", requests=len(wave))
        wave_span.__enter__()
        b = cfg.max_batch
        longest = max(len(r.prompt) for r in wave)
        bucket = self._bucket_for(longest)
        max_new = max(r.max_new_tokens for r in wave)

        # Left-pad prompts into the bucket (static shape).
        tokens = np.full((b, bucket), cfg.pad_id, np.int32)
        for i, r in enumerate(wave):
            r.bucket = bucket
            p = r.prompt[-bucket:]
            tokens[i, bucket - len(p):] = p

        # Wave policy over the shared pool: allocate a slot block for this
        # wave's lifetime (the continuous engine keeps one pool forever).
        # The cache length is padded to the configured budget so per-wave
        # max_new variation doesn't change compiled shapes for attention
        # families (compile-once per bucket).
        pool = StatePool(self.model, b,
                         bucket + max(self.cfg.max_new_tokens, max_new),
                         self.model.cfg.dtype)
        with self.tracer.span("prefill_bucket", bucket=bucket,
                              rows=len(wave)):
            logits, cache = self._prefill(self.params,
                                          {"tokens": jnp.asarray(tokens)},
                                          pool.cache)
            jax.block_until_ready(logits)
        lg = self._logits_to_host(logits, "prefill")
        with self.tracer.span("sample", phase="prefill", rows=b,
                              used=len(wave)):
            next_tok = self._sample(lg)

        def finish(r: Request, slot: int) -> None:
            r.done = True
            r.finish_s = time.time()
            r.latency_s = r.finish_s - r.arrival_s
            self.metrics.record_finish(r.latency_s, len(r.out_tokens))
            if self.tracer.enabled and r.decode_pc is not None:
                self.tracer.complete("decode", r.decode_pc,
                                     time.perf_counter(),
                                     tid=TID_SLOT0 + slot, uid=r.uid,
                                     tokens=len(r.out_tokens))

        alive = np.array([True] * len(wave) + [False] * (b - len(wave)))
        t_first = time.time()
        t_first_pc = time.perf_counter()
        for i, r in enumerate(wave):
            r.first_token_s = t_first
            r.decode_pc = t_first_pc
            self.metrics.record_first_token(t_first - r.arrival_s)
            self.metrics.record_token()
            if self.tracer.enabled and r.admit_pc is not None:
                self.tracer.complete("staging", r.admit_pc, t_first_pc,
                                     tid=TID_SLOT0 + i, uid=r.uid)
            r.emit(int(next_tok[i]))
            if (cfg.eos_id >= 0 and next_tok[i] == cfg.eos_id) or \
                    r.max_new_tokens == 1:
                alive[i] = False
                finish(r, i)

        for t in range(1, max_new):
            if not alive[:len(wave)].any():
                break
            n_live = int(alive[:len(wave)].sum())
            with self.tracer.span("decode_step", live=n_live):
                ts0 = time.perf_counter()
                tok = jnp.asarray(next_tok[:, None])
                logits, cache = self._decode(self._decode_params, tok,
                                             cache, jnp.int32(bucket + t - 1))
                jax.block_until_ready(logits)
                ts1 = time.perf_counter()
            self.metrics.record_step(n_live, ts1 - ts0)
            lg = self._logits_to_host(logits, "decode")
            with self.tracer.span("sample", phase="decode", rows=b,
                                  used=n_live):
                next_tok = self._sample(lg)
            for i, r in enumerate(wave):
                if alive[i] and len(r.out_tokens) < r.max_new_tokens:
                    r.emit(int(next_tok[i]))
                    self.metrics.record_token()
                    if (cfg.eos_id >= 0 and next_tok[i] == cfg.eos_id) or \
                            len(r.out_tokens) >= r.max_new_tokens:
                        alive[i] = False
                        finish(r, i)

        for i, r in enumerate(wave):
            if not r.done:
                finish(r, i)
        dt = time.time() - t0
        self._wall_s += dt
        self.metrics.record_wall(dt)
        wave_span.__exit__(None, None, None)
        self.check_sentinels()
        self.metrics.maybe_snapshot()
        return wave

    # ------------------------------------------------------------------
    def stats(self, requests: List[Request]) -> Dict[str, float]:
        """Throughput over the *summed* sequential wave time (waves run one
        after another; the old max-latency denominator over-reported
        tokens/s whenever there was more than one wave)."""
        toks = sum(len(r.out_tokens) for r in requests)
        wall = self._wall_s or (max((r.latency_s for r in requests),
                                    default=0.0))
        return {"requests": len(requests), "generated_tokens": toks,
                "tokens_per_s": toks / wall if wall else 0.0,
                "wall_s": wall}
