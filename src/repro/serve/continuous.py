"""Continuous-batching engine: slot-level refill under static shapes.

The wave engine decodes lockstep batches: one straggler request holds
every finished slot hostage, and queued requests wait for the whole wave
to drain.  This engine keeps ``max_batch`` persistent *slots* backed by a
:class:`~repro.serve.state_pool.StatePool`; the moment a slot's request
finishes (EOS / token budget), the scheduler admits the next queued
request into that slot mid-decode.

Compile-once discipline (the paper's Step-1 constraint) is preserved with
exactly three compiled programs (plus one prefill variant per bucket):

* **decode**  — ``(params, tok (slots,1), cache, pos (slots,))``; the
  position vector gives every slot its own offset, so freshly admitted
  requests decode next to old ones without recompiling.  Dead slots keep
  decoding into a sink row (static shapes, zero recompiles).  The pool's
  cache pytree is *donated* into the program: slot state updates in place
  every step — no per-step state copies, no fresh pytree allocations.
* **prefill** — per-bucket, always at batch ``slots`` (unused rows are
  padding): a refill of one slot reuses the same program as a full wave.
* **insert**  — the pool's row scatter moves a prefilled request's state
  (SSM state + conv tail / KV rows) into its slot; slot index is traced.

Position realignment: a request prefilled at bucket ``B`` starts decoding
at position ``B`` regardless of what its neighbours are doing — SSM rows
carry position in their state, attention rows take the per-row position
vector (RoPE + KV write + causal mask all realign per row).

Chunked prefill (``ServeConfig.prefill_chunk``): the monolithic per-bucket
prefill blocks every live slot for the whole prompt — a long prompt stalls
the decode wave and spikes the running requests' inter-token latency and
the queue's TTFT tail.  With a chunk size configured, admitted prompts
left-pad to a chunk multiple and advance **one chunk per poll** (per the
token budget), batched across all prefilling slots in a second state pool,
interleaved with the decode step.  That adds ONE more compiled program —
``prefill_chunk`` at ``(slots, chunk)`` with a per-row offset vector — so
the compile-once discipline still holds (0 decode recompiles after
warmup); ``models/base.py: DecodeAPI.prefill_chunk`` guarantees the result
is numerically the whole-sequence prefill.

Self-speculative decoding (``ServeConfig.speculate_k``): when every live
slot is caught up, a poll runs a *burst* instead of one decode step —
``k`` decode-program calls with the cheap draft params (w8 by default) on
a scratch copy of the slot states, then ONE ``verify_chunk`` call at
``(slots, k)`` with the full-precision params on the decode pool.  Each
row emits its longest verified prefix plus one correction token (accept
rule: ``serve/speculative.py``); rows whose window contained a rejected
draft restore their pre-burst snapshot (a compile-once pool row scatter —
O(1) state bytes, the SSM advantage) and re-consume their emitted tokens
through the ordinary decode program, one per poll, before the next burst.
That re-advance keeps rolled-back state bit-exact with the
non-speculative trajectory; emitted tokens are always the verify
stream's, so outputs match the non-speculative engine byte-for-byte
under greedy AND under keyed temperature sampling.  Three more compiled
programs (draft decode = the decode program retraced for the quantized
param pytree, ``verify``, and the two extra pools' row ops), all fixed
shape — compile-once discipline holds.

Prefix-state cache (``ServeConfig.prefix_cache_mb``): on top of chunked
prefill, admission consults a radix cache of chunk-boundary state
snapshots (``serve/prefix_cache.py``): the longest cached prefix of the
staged (padded) stream seeds the staging row — the snapshot scatters into
the row via the same jitted row ops as slot turnover — and chunking
resumes from the matched offset, inserting snapshots of new boundaries on
the way.  Still zero extra compiled programs in the steady state: the
chunk program is offset-vectorized already, and snapshot gather/scatter
are the pool's compile-once row ops.
"""
from __future__ import annotations

import logging
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.elastic import backoff_delay_s
from repro.runtime.faults import as_injector
from repro.runtime.health import StepMonitor, Watchdog
from repro.serve.engine import EngineBase, ServeConfig
from repro.serve.flight_recorder import FlightRecorder
from repro.serve.prefix_cache import PrefixCache, chunk_key
from repro.serve.program_registry import budget_for
from repro.serve.scheduler import Request, bucket_for, chunk_span
from repro.serve.speculative import accept_lengths, emit_counts, \
    needs_rollback
from repro.serve.state_pool import (StatePool, format_compile_count,
                                    jit_cache_size)
from repro.serve.tracing import (TID_HOST, TID_QUEUE, TID_SLOT0,
                                 RecompileSentinel)

log = logging.getLogger("repro.serve")

# Backend degradation ladder (docs/robustness.md): on a compiled-call
# failure the engine rebuilds the model one decode mode down and retries.
# Every xamba decode mode shares one cache layout (``init_cache`` is
# mode-independent), so the live pools survive the swap untouched.
_FALLBACK_NEXT = {"pallas": "cumba", "pallas_interpret": "cumba",
                  "cumba": "naive"}


class ContinuousEngine(EngineBase):
    """Slot-scheduled serving over a shared per-slot state pool."""

    def __init__(self, model, params, cfg: ServeConfig, *,
                 draft_params=None):
        self.slots = cfg.max_batch
        self.buckets = tuple(sorted(cfg.prefill_buckets))
        # Normalize "disabled" spellings (None and 0) to None so every
        # downstream gate can test `self.chunk` / `is None` consistently.
        self.chunk = cfg.prefill_chunk or None
        self.spec_k = int(getattr(cfg, "speculate_k", 0) or 0)
        if self.spec_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {self.spec_k}")
        # Set before the base builds the programs: chunk and spec_k decide
        # which of them exist (_build_programs).
        super().__init__(model, params, cfg)
        # One static cache length covers every tenant a slot can host; with
        # chunked prefill the longest padded prompt can overshoot the
        # largest bucket by up to chunk-1 pad tokens.  A speculative burst
        # near the output budget can consume up to k tokens past the last
        # decode position, so attention-bearing caches get that headroom.
        max_prompt = (chunk_span(self.buckets, self.chunk, self.buckets[-1])
                      if self.chunk else self.buckets[-1])
        self.max_seq = max_prompt + cfg.max_new_tokens + \
            (self.spec_k + 1 if self.spec_k else 0)
        dtype = model.cfg.dtype
        self.pool = StatePool(model, self.slots, self.max_seq, dtype,
                              tracer=self.tracer)
        # Zeroed prefill input cache, reused by every admission (prefill is
        # functional; its output rows are scattered into the pool).
        self._scratch = model.init_cache(self.slots, self.max_seq, dtype)
        self.scheduler = self._scheduler
        self._slot_req: List[Optional[Request]] = [None] * self.slots
        self._pos = np.zeros(self.slots, np.int32)
        self._next_tok = np.full(self.slots, cfg.pad_id, np.int32)
        self._finished: List[Request] = []
        if self.spec_k:
            # Draft params: a w8 quantization of the serve params unless
            # the caller hands in its own pair (e.g. bf16 verify + w8
            # draft in the benchmarks).  Pre-sliced like the decode view
            # so the draft steps reuse the SAME decode program — the
            # quantized pytree is one extra trace of it, not a new
            # program shape.
            if draft_params is None:
                from repro.nn import quant
                draft_params = quant.quantize_params_for_mode(
                    params, getattr(cfg, "speculate_draft", "w8"))
            # Raw (un-sliced) draft pytree kept for backend-fallback
            # rebuilds: decode_view must re-derive from it, not from its
            # own output.
            self._raw_draft = draft_params
            self._draft_params = getattr(model, "decode_view",
                                         lambda p: p)(draft_params)
            # Two more arenas over the decode-pool layout: the draft
            # scratch rows (refreshed from live state each burst) and the
            # pre-burst backup rows rollback restores from.  All row
            # moves are the pools' compile-once scatters.
            self._dpool = StatePool(model, self.slots, self.max_seq, dtype,
                                    tracer=self.tracer)
            self._bpool = StatePool(model, self.slots, self.max_seq, dtype,
                                    tracer=self.tracer)
            # Rolled-back rows park their already-emitted tokens here and
            # re-consume them through the ordinary decode step (one per
            # poll); bursts only run when every live row has drained.
            self._overflow: List[List[int]] = [[] for _ in range(self.slots)]
            # Pre-trace the burst programs at serve shapes on a throwaway
            # cache: the full-precision decode variant first runs at the
            # first rollback drain — possibly many polls in — and a lazy
            # first trace there would read as a post-warmup retrace.
            # Order matters for mixed-precision drafts (e.g. a w8 draft
            # with fp32 scales inside a bf16 serve stack): verify must be
            # traced on a SERVE-dtype cache (the live pool, which drafts
            # never touch), and the draft step on both cache dtypes it
            # will see — the serve-dtype arena it starts each burst from
            # and its own (possibly promoted) output dtype.  When draft
            # and serve dtypes agree the second draft call hits the
            # existing trace and compiles nothing.
            tok = jnp.zeros((self.slots, 1), jnp.int32)
            pos = jnp.zeros(self.slots, jnp.int32)
            tmp = model.init_cache(self.slots, self.max_seq, dtype)
            _, tmp = self._decode(self._decode_params, tok, tmp, pos)
            _, tmp = self._verify(
                self.params, jnp.zeros((self.slots, self.spec_k), jnp.int32),
                tmp, pos)
            _, tmp = self._decode(self._draft_params, tok, tmp, pos)
            self._decode(self._draft_params, tok, tmp, pos)
        if self.chunk:
            # Chunk-prefill state accumulates in a SECOND pool (one row per
            # slot, donated into the chunk program) until the prompt is
            # fully consumed, then the row is scattered into the decode
            # pool.  Slot i prefills in row i: a request reserves its
            # decode slot at admission, so prefill work can never outrun
            # decode capacity.
            self._ppool = StatePool(model, self.slots, self.max_seq, dtype,
                                    tracer=self.tracer)
            self._pref_req: List[Optional[Request]] = [None] * self.slots
            self._pref_toks: List[Optional[np.ndarray]] = [None] * self.slots
            self._pref_off = np.zeros(self.slots, np.int32)
        self._pcache: Optional[PrefixCache] = None
        if cfg.prefix_cache_mb:
            if not self.chunk:
                raise ValueError(
                    "prefix_cache_mb requires chunked prefill: snapshots "
                    "live at chunk boundaries (set prefill_chunk)")
            grain = cfg.prefix_chunk or self.chunk
            if grain % self.chunk:
                raise ValueError(
                    f"prefix_chunk ({grain}) must be a multiple of "
                    f"prefill_chunk ({self.chunk}): snapshots are taken "
                    "between chunk program calls")
            self._pcache = PrefixCache(int(cfg.prefix_cache_mb * 2 ** 20),
                                       grain, tracer=self.tracer)
            # Per-slot trie walk state while staging: the chunk key of the
            # padded stream, the deepest visited node (the cursor new
            # snapshots attach under), the pins released when the request
            # leaves staging, and an insert gate that closes when the
            # byte budget refuses a node (children would dangle).
            self._pref_key: List[Optional[list]] = [None] * self.slots
            self._pref_node: List[Optional[object]] = [None] * self.slots
            self._pref_pins: List[list] = [[] for _ in range(self.slots)]
            self._pref_insert_ok = [True] * self.slots
        # -- observability (docs/observability.md) --------------------------
        # Flight recorder: a bounded ring of the last-N completed-request
        # timelines, dumped to JSONL whenever a fault event fires
        # (quarantine / shed / retry / watchdog / backend fallback) —
        # created before the watchdog so its thread can always dump.
        self.flight: Optional[FlightRecorder] = None
        if getattr(cfg, "flight_records", 0):
            self.flight = FlightRecorder(cfg.flight_records,
                                         getattr(cfg, "flight_path", None))
        # Program registry: every compiled program above registers its
        # serve shapes (ShapeDtypeStructs only — card builds are lazy and
        # off the hot path) so ids thread through spans and sentinels.
        self._register_programs()
        # Host scheduling gaps: time between the end of one poll and the
        # start of the next (caller time + idle waits) gets its own trace
        # track so phase breakdowns account for ALL wall time.
        self._last_poll_end: Optional[float] = None
        # Step-time health: rolling-median straggler flags on decode and
        # prefill program calls (runtime/health.StepMonitor), plus an
        # optional deadline watchdog that fires when no compiled call
        # completes within cfg.watchdog_s (a hung device/compile).
        self.monitor_decode = StepMonitor()
        self.monitor_prefill = StepMonitor()
        self.monitor_spec = StepMonitor()
        self._watchdog: Optional[Watchdog] = None
        if getattr(cfg, "watchdog_s", 0.0):
            if getattr(cfg, "watchdog_action", "log") not in ("log",
                                                              "recover"):
                raise ValueError(
                    f"watchdog_action must be 'log' or 'recover', got "
                    f"{cfg.watchdog_action!r}")
            self._watchdog = Watchdog(cfg.watchdog_s, on_hang=self._on_hang)
        # -- fault tolerance (docs/robustness.md) ---------------------------
        probe = getattr(cfg, "poison_probe", "off") or "off"
        if probe not in ("off", "logits", "state"):
            raise ValueError(f"poison_probe must be off|logits|state, "
                             f"got {probe!r}")
        self._poison_probe = probe
        self._injector = as_injector(getattr(cfg, "fault_plan", None))
        self._poll_idx = 0          # engine poll clock (fault schedule base)
        self._overloaded = False    # degraded overload mode latch
        self._recover_pending = False   # watchdog asked for a recovery
        self._state_probe = None
        if probe == "state":
            self._state_probe = self._build_state_probe()
            # Warm the probe now so its one compile lands in construction,
            # not mid-serve (the pool cache is read, never donated).
            np.asarray(self._state_probe(self.pool.cache))

    @property
    def poll_index(self) -> int:
        """The engine's poll clock — fault-plan event polls are absolute,
        so chaos drivers arm plans relative to this after warmup."""
        return self._poll_idx

    def set_fault_plan(self, plan) -> None:
        """(Re)arm the fault injector mid-run: chaos harnesses warm the
        compiled programs fault-free, then schedule events at
        ``poll_index + k`` (None disarms)."""
        self._injector = as_injector(plan)

    def _on_hang(self) -> None:
        self.metrics.watchdog_fires += 1
        self.tracer.instant("watchdog_hang",
                            deadline_s=self.cfg.watchdog_s)
        log.error("serve watchdog: no compiled call completed within "
                  "%.1fs — engine may be hung", self.cfg.watchdog_s)
        if getattr(self, "flight", None) is not None:
            # Runs on the watchdog thread; the recorder only appends to
            # its file, which is safe from here.
            self.flight.record_fault("watchdog_hang",
                                     deadline_s=self.cfg.watchdog_s)
        if getattr(self.cfg, "watchdog_action", "log") == "recover":
            # The watchdog thread cannot abort a compiled call; it flags
            # the engine and the next poll() aborts the stuck burst and
            # requeues its requests (bounded retries + backoff).
            self._recover_pending = True

    def close(self) -> None:
        """Stop the hang watchdog thread (idempotent); asserts the thread
        actually joined so a leaked watchdog fails loudly in tests."""
        if self._watchdog is not None:
            wd = self._watchdog
            wd.stop()
            assert not wd.alive, "watchdog thread failed to join in close()"
            self._watchdog = None

    def reset_stats(self) -> None:
        # Fresh health baselines too: warmup steps include compiles, which
        # would pollute the rolling-median straggler threshold.  The
        # cleared poll stamp keeps the first post-warmup poll from
        # emitting a host_gap that spans the whole warmup.
        self.monitor_decode = StepMonitor()
        self.monitor_prefill = StepMonitor()
        self.monitor_spec = StepMonitor()
        self._last_poll_end = None
        super().reset_stats()

    # ------------------------------------------------------------------
    # program registry (docs/observability.md)
    # ------------------------------------------------------------------
    def _build_programs(self) -> None:
        """The base's ``decode`` and ``prefill`` plus, when configured,
        ``prefill_chunk`` (modules ``jit_prefill_chunk``) and ``verify``
        (``jit_verify``); the chunk and verify programs donate the pool
        arena they advance."""
        super()._build_programs()
        model = self.model
        if self.chunk:
            def prefill_chunk(p, toks, cache, off):
                return model.prefill_chunk(p, toks, cache, off)

            self._chunk_step = self._program(prefill_chunk,
                                             donate_argnums=(2,))
        if self.spec_k:
            def verify(p, toks, cache, off):
                return model.verify_chunk(p, toks, cache, off)

            self._verify = self._program(verify, donate_argnums=(2,))

    def _register_programs(self) -> None:
        """(Re)attach every compiled program this engine warms up to the
        registry at its serve shapes.  Cheap — ShapeDtypeStructs only, no
        compiles — and re-run by a backend rebuild so program cards
        always lower the jits currently serving.  Ids are stable across
        re-registration, so a sentinel's program id (given when
        ``_program`` built it) keeps naming the same program."""
        reg = self.registry
        i32 = jnp.int32
        tok = jax.ShapeDtypeStruct((self.slots, 1), i32)
        pos = jax.ShapeDtypeStruct((self.slots,), i32)
        reg.register("decode", self._decode,
                     (self._decode_params, tok, self.pool.cache, pos),
                     budget=budget_for(self.model.cfg, "decode"))
        reg.register(
            "prefill", self._prefill,
            (self.params,
             {"tokens": jax.ShapeDtypeStruct((self.slots, self.buckets[-1]),
                                             i32)},
             self._scratch))
        if self.chunk:
            reg.register(
                "prefill_chunk", self._chunk_step,
                (self.params,
                 jax.ShapeDtypeStruct((self.slots, self.chunk), i32),
                 self._ppool.cache, pos))
        if self.spec_k:
            reg.register(
                "verify", self._verify,
                (self.params,
                 jax.ShapeDtypeStruct((self.slots, self.spec_k), i32),
                 self.pool.cache, pos))
            # The draft step is the decode program's second trace (the
            # quantized pytree) — its own card shows the int8 variant.
            reg.register("draft", self._decode,
                         (self._draft_params, tok, self.pool.cache, pos))
            # The W8 dequant-matmul the draft trace calls into, at a
            # representative (slots, d_model) x (d_model, d_model) shape.
            # On CPU the serving path is nn/quant.qdot's XLA variant
            # (dot_general on the int8 payload + per-channel scale); the
            # fused pallas kernel only lowers on accelerator backends.
            d = self.model.cfg.d_model
            qx = jax.ShapeDtypeStruct((self.slots, d), jnp.float32)
            qw = jax.ShapeDtypeStruct((d, d), jnp.int8)
            qs = jax.ShapeDtypeStruct((1, d), jnp.float32)
            if jax.default_backend() == "cpu":
                def _qmm(x, q, scale):
                    y = jax.lax.dot_general(
                        x, q, (((x.ndim - 1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return y * scale.reshape(-1)
                reg.register("qmatmul", jax.jit(_qmm), (qx, qw, qs))
            else:
                from repro.kernels import ops as kops
                reg.register("qmatmul", kops.qmatmul, (qx, qw, qs))
        # The decode pool's row ops (slot turnover, snapshot export /
        # import share the same compiled gather/scatter) build lazily on
        # first use — thunks resolve at card-build time.
        scalar = jax.ShapeDtypeStruct((), i32)
        pool = self.pool

        def pool_op(attr):
            def thunk():
                if getattr(pool, attr) is None:
                    pool._build_ops()
                return getattr(pool, attr)
            return thunk

        reg.register("pool_insert", fn_thunk=pool_op("_insert"),
                     example_args=(pool.cache, pool.cache, scalar, scalar))
        reg.register("pool_extract", fn_thunk=pool_op("_extract"),
                     example_args=(pool.cache, scalar))
        reg.register("pool_reset", fn_thunk=pool_op("_reset"),
                     example_args=(pool.cache, scalar))
        # The lazily-built pool ops get inert-until-first-sight sentinels
        # of their own (fn_getter reads size -1 until the op exists).
        strict = getattr(self.cfg, "strict_recompile", False)
        for name, attr in (("pool_insert", "_insert"),
                           ("pool_reset", "_reset")):
            if name not in self.sentinels:
                # NB: the sentinel getter must NOT force-build the ops
                # (pool_op does, for cards) — it just observes them.
                self.sentinels[name] = RecompileSentinel(
                    name, strict=strict,
                    fn_getter=lambda p=pool, a=attr: getattr(p, a),
                    program_id=reg.program_id(name))
        # Hot-path span args use these pre-resolved id strings — constant
        # string refs, not registry lookups, per compiled call.
        self._pid_decode = reg.program_id("decode")
        self._pid_prefill = reg.program_id("prefill")
        self._pid_chunk = (reg.program_id("prefill_chunk")
                           if self.chunk else None)
        self._pid_verify = (reg.program_id("verify")
                            if self.spec_k else None)
        self._pid_draft = (reg.program_id("draft")
                           if self.spec_k else None)
        # Which multi-token mixer path each prefill program traced
        # (``model.prefill_path``), decided by the config and its length
        # alone: one instant per compiled program, and on every chunk span.
        lengths = ([(self._pid_chunk, self.chunk)] if self.chunk else
                   [(self._pid_prefill, b) for b in self.buckets])
        if self.spec_k:
            lengths.append((self._pid_verify, self.spec_k))
        for pid, length in lengths:
            path, reason = self.model.prefill_path(length)
            self.tracer.instant("mixer_path", program=pid, tokens=length,
                                path=path, reason=reason)
        self._chunk_path = (self.model.prefill_path(self.chunk)[0]
                            if self.chunk else None)

    def _observe_step(self, monitor: StepMonitor, kind: str,
                      dt_s: float) -> None:
        """Feed one compiled-call duration to its StepMonitor; surface
        straggler flags through metrics and the trace, pet the watchdog."""
        # step=None -> the monitor's cumulative count (its record list is
        # a trimmed rolling window, so len(records) is NOT the step index).
        rec = monitor.observe(None, dt_s)
        if rec.straggler:
            self.metrics.record_straggler(kind)
            self.tracer.instant(f"straggler_{kind}", seconds=dt_s)
        if self._watchdog is not None:
            self._watchdog.pet()

    # ------------------------------------------------------------------
    # fault tolerance (docs/robustness.md)
    # ------------------------------------------------------------------
    def _guarded_call(self, program: str, fn):
        """Run one compiled call behind the fault boundary: the injector's
        pre-call hook (stalls; simulated failures raise *before* the jit
        executes, so donated arenas stay intact) and the backend fallback
        chain.  ``fn`` must re-read the engine's program attributes
        (``self._decode`` etc.) so a retry picks up the rebuilt jits."""
        try:
            if self._injector is not None:
                self._injector.pre_call(program, self._poll_idx)
            return fn()
        except Exception as e:  # noqa: BLE001 — the fallback boundary
            if not self._try_fallback(program, e):
                raise
            return fn()

    def _try_fallback(self, program: str, err: Exception) -> bool:
        """Degrade one decode mode down the ladder and report whether a
        retry is worth attempting.  A real (non-injected) failure that
        already consumed a donated arena will fail its retry too — that
        re-raise is the honest outcome."""
        if not getattr(self.cfg, "backend_fallback", True):
            return False
        mode = self.model.cfg.xamba.decode
        nxt = _FALLBACK_NEXT.get(mode)
        if nxt is None:
            log.error("backend failure in %s with decode mode %r and no "
                      "fallback left: %s", program, mode, err)
            return False
        log.error("backend failure in %s (decode mode %r): %s — falling "
                  "back to %r", program, mode, err, nxt)
        self._rebuild_backend(nxt)
        self.metrics.record_backend_fallback()
        self.tracer.instant("backend_fallback", program=program,
                            from_mode=mode, to_mode=nxt, error=str(err))
        if self.flight is not None:
            self.flight.record_fault("backend_fallback", program=program,
                                     from_mode=mode, to_mode=nxt)
        return True

    def _rebuild_backend(self, mode: str) -> None:
        """Rebuild the model and every compiled program at decode mode
        ``mode``, then re-warm them all at serve shapes.  Cache layouts
        are identical across xamba decode modes, so the pools (and their
        compiled row ops) survive; the fresh jits get fresh sentinels
        armed over the re-warmup's traces — a fallback never reads as a
        post-warmup retrace."""
        from repro.models.registry import build_model
        model = build_model(self.model.cfg.with_decode_mode(mode))
        self.model = model
        self._build_programs()
        for pool in (self.pool,
                     getattr(self, "_ppool", None),
                     getattr(self, "_dpool", None),
                     getattr(self, "_bpool", None)):
            if pool is not None:
                pool.model = model  # snapshot export/import path
        if self.spec_k:
            self._draft_params = getattr(model, "decode_view",
                                         lambda p: p)(self._raw_draft)
        if self._state_probe is not None:
            self._state_probe = self._build_state_probe()
        # The rebuilt jits replace the registry's lowering recipes (same
        # ids — spans keep meaning the same program) and drop any cached
        # cards; the fresh sentinels re-learn their program ids.
        self._register_programs()
        # A rebuild is a new warmup: trace every rebuilt program at its
        # serve shapes NOW, on throwaway inputs, so all compiles land
        # inside the fallback event.  The sentinels arm lazily, but only
        # until the next poll's check — a program first *used* polls later
        # (e.g. prefill at the next admission) would otherwise read as a
        # post-warmup retrace.
        dtype = model.cfg.dtype
        tok = jnp.zeros((self.slots, 1), jnp.int32)
        pos = jnp.zeros(self.slots, jnp.int32)
        tmp = model.init_cache(self.slots, self.max_seq, dtype)
        _, tmp = self._decode(self._decode_params, tok, tmp, pos)
        if self.chunk:
            ctmp = model.init_cache(self.slots, self.max_seq, dtype)
            self._chunk_step(self.params,
                             jnp.zeros((self.slots, self.chunk), jnp.int32),
                             ctmp, pos)
        else:
            # Monolithic prefill is functional (no donation): _scratch
            # rides through untouched, exactly like a real admission.
            for bucket in self.buckets:
                self._prefill(self.params,
                              {"tokens": jnp.full((self.slots, bucket),
                                                  self.cfg.pad_id,
                                                  jnp.int32)},
                              self._scratch)
        if self.spec_k:
            _, tmp = self._verify(
                self.params,
                jnp.zeros((self.slots, self.spec_k), jnp.int32), tmp, pos)
            _, tmp = self._decode(self._draft_params, tok, tmp, pos)
            self._decode(self._draft_params, tok, tmp, pos)

    def _build_state_probe(self):
        """Jitted all-rows finiteness probe over the decode pool: one
        ``bool[slots]`` gather per check (compiled once — the cache layout
        never changes).  Integer leaves are trivially finite but ride
        through the float32 cast rather than special-casing the tree."""
        slots = self.slots
        axes = self.pool.batch_axes

        def probe(cache):
            def leaf(x, ax):
                flat = jnp.moveaxis(x.astype(jnp.float32), ax, 0)
                flat = flat.reshape(slots, -1)
                return jnp.all(jnp.isfinite(flat), axis=1)
            flags = jax.tree.map(leaf, cache, axes)
            return jnp.all(jnp.stack(jax.tree.leaves(flags)), axis=0)

        return jax.jit(probe)

    def _quarantine(self, slot: int, now: float, where: str) -> None:
        """Contain a poisoned slot: zero its pool row (the compile-once
        reset scatter), finish its request with status ``poisoned`` (NOT
        counted as a completion), and free the slot.  Neighbour slots and
        the prefix cache are untouched."""
        req = self._slot_req[slot]
        self.pool.reset_rows([slot])
        self._pos[slot] = 0
        self._next_tok[slot] = self.cfg.pad_id
        if self.spec_k:
            self._overflow[slot] = []
        self._slot_req[slot] = None
        req.done = True
        req.status = "poisoned"
        req.finish_s = now
        req.latency_s = now - req.arrival_s
        self.metrics.record_quarantine()
        self.metrics.record_shed("poison")
        self.tracer.instant("quarantine", uid=req.uid, slot=slot,
                            where=where, tokens=len(req.out_tokens))
        if self.flight is not None:
            self.flight.record_request(req, slot=slot, status="poisoned")
            self.flight.record_fault("quarantine", uid=req.uid, slot=slot,
                                     where=where)
        log.error("request %d: non-finite %s output in slot %d — "
                  "quarantined (row reset, request shed)", req.uid, where,
                  slot)
        self._finished.append(req)

    def _probe_rows(self, live: List[int], host_logits: np.ndarray,
                    now: float, where: str) -> List[int]:
        """Poison probe over one step's live rows: NaN/Inf in the (already
        host-side) logits, plus — in ``state`` mode — the jitted per-row
        state finiteness probe.  Quarantines every hit; returns the
        quarantined slots."""
        if self._poison_probe == "off" or not live:
            return []
        every = max(1, getattr(self.cfg, "poison_check_every", 1))
        if self._poll_idx % every:
            return []
        self.metrics.record_poison_probe()
        lg = host_logits.reshape(host_logits.shape[0], -1)
        # One vectorized pass over the whole batch, then bail on the
        # all-finite common case: the probe runs every poll of every
        # hardened serve, and per-row np calls are ~5x the cost (a few %
        # of a reduced-model poll; BENCH_serve.json's probe_overhead arm
        # bounds the healthy-path total at 3%).
        row_ok = np.isfinite(lg).all(axis=1)
        if row_ok.all() and self._state_probe is None:
            return []
        bad = {i for i in live if not row_ok[i]}
        if self._state_probe is not None:
            finite = np.asarray(self._state_probe(self.pool.cache))
            bad.update(i for i in live if not finite[i])
        for i in sorted(bad):
            self._quarantine(i, now, where)
        return sorted(bad)

    def _inject_poison(self) -> None:
        """Apply due state-poison faults: corrupt the slot's row through
        the pool's host snapshot/restore pair (the fault path may be slow;
        the serving path must stay compile-once)."""
        live = [i for i, r in enumerate(self._slot_req) if r is not None]
        for slot, mode in self._injector.poison_targets(self._poll_idx,
                                                        live):
            snap = self.pool.clone_row(slot)
            self.pool.restore_row(slot, self._injector.corrupt(snap, mode))

    def _snapshot_finite(self, snap) -> bool:
        """Host-side finiteness gate for a prefix-cache snapshot."""
        for x in jax.tree.leaves(snap):
            a = np.asarray(x)
            if np.issubdtype(a.dtype, np.floating) and \
                    not np.isfinite(a.astype(np.float32)).all():
                return False
        return True

    def _update_overload(self) -> None:
        """Degraded-mode state machine (docs/robustness.md): enter when
        queue depth or cumulative TTFT p95 crosses its threshold; while
        degraded the prefill token budget collapses to one chunk per poll
        and speculative bursts pause.  Exit on queue depth alone, with
        hysteresis (``overload_clear_frac``) — TTFT p95 is cumulative and
        would latch forever."""
        cfg = self.cfg
        q_thresh = getattr(cfg, "overload_queue_depth", 0)
        t_thresh = getattr(cfg, "overload_ttft_p95_s", 0.0)
        if not q_thresh and not t_thresh:
            return
        depth = len(self.scheduler)
        if not self._overloaded:
            trip = bool(q_thresh and depth >= q_thresh) or bool(
                t_thresh and self.metrics.ttft.count and
                self.metrics.ttft.percentile(0.95) > t_thresh)
            if trip:
                self._overloaded = True
                self.metrics.record_overload(True)
                self.tracer.instant("overload_enter", queue_depth=depth)
                log.warning("overload: entering degraded mode (queue "
                            "depth %d) — prefill budget 0, speculation "
                            "paused", depth)
        else:
            clear_at = (getattr(cfg, "overload_clear_frac", 0.5) * q_thresh
                        if q_thresh else 0)
            if depth <= clear_at:
                self._overloaded = False
                self.metrics.record_overload(False)
                self.tracer.instant("overload_exit", queue_depth=depth)
                log.info("overload cleared (queue depth %d): restoring "
                         "prefill budget and speculation", depth)

    def _shed_inflight(self, now: float) -> None:
        """Deadline shedding for requests already past admission: decoding
        tenants and staged (prefilling) rows whose SLA has passed free
        their capacity for work that can still meet its deadline."""
        for i, req in enumerate(self._slot_req):
            if req is None or req.deadline_s is None or \
                    now <= req.deadline_s:
                continue
            self.pool.reset_rows([i])
            self._pos[i] = 0
            self._next_tok[i] = self.cfg.pad_id
            if self.spec_k:
                self._overflow[i] = []
            self._slot_req[i] = None
            self._shed_request(req, now, "deadline", "shed_deadline")
        if self.chunk:
            for i, req in enumerate(self._pref_req):
                if req is None or req.deadline_s is None or \
                        now <= req.deadline_s:
                    continue
                if self._pcache is not None:
                    self._prefix_release(i)
                self._pref_req[i] = None
                self._pref_toks[i] = None
                self._shed_request(req, now, "deadline", "shed_deadline")

    def _shed_request(self, req: Request, now: float, reason: str,
                      status: str) -> None:
        """Common in-flight shed bookkeeping (deadline / retry-exhausted):
        the request finishes unsuccessfully and lands in both
        ``_finished`` (the caller sees it) and ``scheduler.expired``."""
        req.done = True
        req.expired = True
        req.status = status
        req.finish_s = now
        req.latency_s = now - req.arrival_s
        self.metrics.record_shed(reason)
        self.scheduler.expired.append(req)
        self.tracer.instant("shed", uid=req.uid, reason=reason,
                            inflight=True)
        if self.flight is not None:
            self.flight.record_request(req, status=status)
            self.flight.record_fault("shed", uid=req.uid, reason=reason)
        log.warning("request %d: shed in flight (%s)", req.uid, reason)
        self._finished.append(req)

    def _watchdog_recover(self, now: float) -> None:
        """Engine-level hang recovery (``watchdog_action="recover"``):
        abort every in-flight tenant and staged row, requeue each with a
        bounded retry budget and exponential backoff, and reset their
        rows.  Requeued requests restart from scratch — keyed sampling
        makes the replayed stream identical, so a recovered request's
        final output matches an undisturbed run."""
        self._recover_pending = False
        requeued = 0
        for i, req in enumerate(self._slot_req):
            if req is None:
                continue
            self.pool.reset_rows([i])
            self._pos[i] = 0
            self._next_tok[i] = self.cfg.pad_id
            if self.spec_k:
                self._overflow[i] = []
            self._slot_req[i] = None
            requeued += self._retry_or_shed(req, now)
        if self.chunk:
            for i, req in enumerate(self._pref_req):
                if req is None:
                    continue
                if self._pcache is not None:
                    self._prefix_release(i)
                self._pref_req[i] = None
                self._pref_toks[i] = None
                requeued += self._retry_or_shed(req, now)
        self.metrics.record_watchdog_recovery(requeued)
        self.tracer.instant("watchdog_recover", requeued=requeued)
        if self.flight is not None:
            self.flight.record_fault("watchdog_recover", requeued=requeued)
        log.error("watchdog recovery: aborted stuck burst, requeued %d "
                  "request(s)", requeued)

    def _retry_or_shed(self, req: Request, now: float) -> int:
        """Requeue an aborted request (1) or shed it (0) once its retry
        budget is exhausted.  A retried request restarts clean: emitted
        tokens are discarded (streaming callbacks will re-emit them) and
        admission defers by the shared exponential-backoff curve."""
        req.retries += 1
        if req.retries > getattr(self.cfg, "max_retries", 1):
            self._shed_request(req, now, "retry_exhausted",
                               "retry_exhausted")
            return 0
        req.out_tokens.clear()
        req.done = False
        req.first_token_s = None
        req.decode_pc = None
        req.admit_pc = None
        base = getattr(self.cfg, "retry_backoff_s", 0.0)
        req.not_before_s = (now + backoff_delay_s(req.retries, base)
                            if base else None)
        self.tracer.instant("retry", uid=req.uid, attempt=req.retries)
        if self.flight is not None:
            self.flight.record_fault("retry", uid=req.uid,
                                     attempt=req.retries)
        self.scheduler.submit(req)
        return 1

    def _snapshot_extra(self) -> dict:
        """Engine-side facts folded into each periodic metrics snapshot."""
        out = {"monitor_decode": self.monitor_decode.summary(),
               "monitor_prefill": self.monitor_prefill.summary(),
               "recompile_trips": {name: s.trips
                                   for name, s in self.sentinels.items()}}
        if self.spec_k:
            out["monitor_spec"] = self.monitor_spec.summary()
        if self._pcache is not None:
            out["prefix_cache"] = self._pcache.stats()
        if self._injector is not None:
            out["fault_injector"] = self._injector.summary()
        out["overloaded"] = self._overloaded
        return out

    def _buckets(self):
        return self.buckets

    @property
    def busy(self) -> bool:
        return (len(self.scheduler) > 0 or
                any(r is not None for r in self._slot_req) or
                (self.chunk is not None and
                 any(r is not None for r in self._pref_req)))

    @property
    def counters(self) -> dict:
        out = {**super().counters,
               **{f"pool_{k}_compiles": v
                  for k, v in self.pool.compile_counts().items()}}
        if self.chunk:
            out["prefill_chunk_compiles"] = format_compile_count(
                jit_cache_size(self._chunk_step))
            out.update({f"ppool_{k}_compiles": v
                        for k, v in self._ppool.compile_counts().items()})
        if self.spec_k:
            out["verify_compiles"] = format_compile_count(
                jit_cache_size(self._verify))
            out.update({f"dpool_{k}_compiles": v
                        for k, v in self._dpool.compile_counts().items()})
        if self._pcache is not None:
            out["prefix_cache"] = self._pcache.stats()
        return out

    @property
    def prefix_cache(self) -> Optional[PrefixCache]:
        """The prefix-state cache (None unless ``prefix_cache_mb`` set)."""
        return self._pcache

    # ------------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req)
                if r is None and
                (self.chunk is None or self._pref_req[i] is None)]

    def _finish(self, req: Request, now: float, slot: int) -> None:
        req.done = True
        req.finish_s = now
        req.latency_s = now - req.arrival_s
        self.metrics.record_finish(req.latency_s, len(req.out_tokens))
        if self.flight is not None:
            self.flight.record_request(req, slot=slot,
                                       status=getattr(req, "status", "ok"))
        if self.tracer.enabled:
            if req.decode_pc is not None:
                self.tracer.complete("decode", req.decode_pc,
                                     time.perf_counter(),
                                     tid=TID_SLOT0 + slot, uid=req.uid,
                                     tokens=len(req.out_tokens))
            self.tracer.instant("finish", uid=req.uid,
                                tokens=len(req.out_tokens),
                                latency_s=req.latency_s)
        self._finished.append(req)

    def _start_tenant(self, slot: int, req: Request, span: int, tok: int,
                      t_first: float) -> None:
        """Request-start semantics shared by both admission paths
        (monolithic ``_admit`` and chunked ``_prefill_step``): clamp the
        output budget to the slot's remaining cache, stamp first-token
        metrics, emit, and either finish immediately (EOS on the prefill
        token / 1-token budget — the request never occupies a decode
        step, the slot stays free) or install the request as the slot's
        decoding tenant at position ``span``."""
        cfg = self.cfg
        budget = max(1, min(req.max_new_tokens, self.max_seq - span))
        if budget < req.max_new_tokens:
            log.warning(
                "request %d: max_new_tokens %d exceeds slot budget; "
                "clamping to %d", req.uid, req.max_new_tokens, budget)
            req.max_new_tokens = budget
        req.first_token_s = t_first
        t_first_pc = time.perf_counter()
        if self.tracer.enabled and req.admit_pc is not None:
            # Per-slot staging residency: queue pop -> first token (covers
            # all the request's prefill chunks and the waits between them).
            self.tracer.complete("staging", req.admit_pc, t_first_pc,
                                 tid=TID_SLOT0 + slot, uid=req.uid,
                                 span=span)
        self.metrics.record_first_token(t_first - req.arrival_s)
        self.metrics.record_token()
        req.emit(tok)
        if (cfg.eos_id >= 0 and tok == cfg.eos_id) or \
                len(req.out_tokens) >= req.max_new_tokens:
            self._finish(req, t_first, slot)
        else:
            req.decode_pc = t_first_pc
            self._slot_req[slot] = req
            self._pos[slot] = span
            self._next_tok[slot] = tok

    def _admit(self, now: float) -> int:
        """Fill free slots from the queue; returns requests admitted."""
        cfg = self.cfg
        free = self._free_slots()
        n_shed0 = len(self.scheduler.expired)
        batch = []
        while free and len(self.scheduler):
            req = self.scheduler.pop_ready(now)
            if req is None:
                break
            req.admit_pc = time.perf_counter()
            if self.tracer.enabled:
                self.tracer.complete(
                    "queue", self.tracer.pc_from_walltime(req.arrival_s),
                    req.admit_pc, tid=TID_QUEUE, uid=req.uid)
            batch.append((free.pop(0), req))
        for _ in range(len(self.scheduler.expired) - n_shed0):
            self.metrics.record_shed()
        if not batch:
            return 0

        groups = {}
        for slot, req in batch:
            b, _ = bucket_for(self.buckets, len(req.prompt))
            groups.setdefault(b, []).append((slot, req))

        for bucket, group in groups.items():
            tokens = np.full((self.slots, bucket), cfg.pad_id, np.int32)
            for row, (_, req) in enumerate(group):
                p = req.prompt[-bucket:]
                tokens[row, bucket - len(p):] = p
            with self.tracer.span("prefill_bucket", bucket=bucket,
                                  rows=len(group),
                                  tokens=bucket * len(group),
                                  program=self._pid_prefill):
                t0 = time.perf_counter()
                toks_dev = jnp.asarray(tokens)
                logits, cache = self._guarded_call(
                    "prefill",
                    lambda: self._prefill(self.params, {"tokens": toks_dev},
                                          self._scratch))
                jax.block_until_ready(logits)
                t1 = time.perf_counter()
            self._observe_step(self.monitor_prefill, "prefill", t1 - t0)
            self.metrics.record_prefill(bucket * len(group), t1 - t0)
            lg = self._logits_to_host(logits, "prefill")
            # First tokens sample at position = bucket (tokens consumed so
            # far), keyed per owning request — see _sample_rows.  Only the
            # group's rows (the first len(group)) are drawn.
            n = len(group)
            uids = np.array([req.uid for _, req in group], np.int64)
            with self.tracer.span("sample", phase="prefill", rows=n, used=n):
                first = self._sample_rows(lg[:n], uids,
                                          np.full(n, bucket, np.int64))
            self.pool.insert_rows(cache,
                                  [row for row in range(len(group))],
                                  [slot for slot, _ in group])
            t_first = time.time()
            for row, (slot, req) in enumerate(group):
                req.bucket = bucket
                self._start_tenant(slot, req, bucket, int(first[row]),
                                   t_first)
        return len(batch)

    # ------------------------------------------------------------------
    # chunked prefill
    # ------------------------------------------------------------------
    def _admit_chunked(self, now: float) -> int:
        """Reserve free slots for queued requests and stage their padded
        prompts for chunk-wise prefill.  No model work happens here — the
        chunks run in ``_prefill_step`` under the poll's token budget."""
        cfg = self.cfg
        free = self._free_slots()
        n_shed0 = len(self.scheduler.expired)
        admitted = 0
        while free and len(self.scheduler):
            req = self.scheduler.pop_ready(now)
            if req is None:
                break
            req.admit_pc = time.perf_counter()
            if self.tracer.enabled:
                self.tracer.complete(
                    "queue", self.tracer.pc_from_walltime(req.arrival_s),
                    req.admit_pc, tid=TID_QUEUE, uid=req.uid)
            slot = free.pop(0)
            p = req.prompt[-self.buckets[-1]:]
            span = chunk_span(self.buckets, self.chunk, len(p))
            toks = np.full(span, cfg.pad_id, np.int32)
            toks[span - len(p):] = p
            req.bucket = span
            off = 0
            if self._pcache is not None:
                off = self._prefix_match(slot, toks, span)
            if not off:
                # The row's previous tenant left state behind; the chunk
                # program accumulates into the row, so it must start from
                # zero (a prefix-cache restore overwrites the whole row
                # instead — reset would be a wasted scatter).
                self._ppool.reset_rows([slot])
            self._pref_req[slot] = req
            self._pref_toks[slot] = toks
            self._pref_off[slot] = off
            admitted += 1
        for _ in range(len(self.scheduler.expired) - n_shed0):
            self.metrics.record_shed()
        return admitted

    # -- prefix-state cache -------------------------------------------------
    def _prefix_match(self, slot: int, toks: np.ndarray, span: int) -> int:
        """Longest-prefix lookup for a staged (padded) stream: restore the
        matched snapshot into the staging row and return the offset
        chunking resumes from (0 = miss).  The match is capped so at
        least one prefill chunk always runs — the final chunk's logits
        produce the request's first token."""
        cache = self._pcache
        with self.tracer.span("prefix_lookup") as sp:
            key = chunk_key(toks, cache.chunk)
            cap = max(0, (span - self.chunk) // cache.chunk)
            node, depth = cache.match(key, max_depth=cap)
            off = depth * cache.chunk
            sp.args["matched_tokens"] = off
        self.metrics.record_prefix_lookup(off)
        self._pref_key[slot] = key
        self._pref_node[slot] = node
        self._pref_pins[slot] = [node] if node is not None else []
        self._pref_insert_ok[slot] = True
        if node is not None:
            self._ppool.restore_row(slot, node.snapshot, index=off)
        return off

    def _prefix_insert(self, row: int) -> None:
        """After a chunk call: if the row crossed a snapshot boundary the
        cache hasn't seen, clone the staging row (jitted gather + host
        copy, off the donated arena) and attach it under the row's trie
        cursor.  A budget refusal closes the gate — deeper nodes would
        have no parent path."""
        cache = self._pcache
        off = int(self._pref_off[row])
        if off % cache.chunk or not self._pref_insert_ok[row]:
            return
        depth = off // cache.chunk
        key = self._pref_key[row]
        if depth > len(key):
            return
        nxt = cache.child(self._pref_node[row], key[depth - 1])
        if nxt is None:
            snap = self._ppool.clone_row(row, index=off)
            if self._injector is not None:
                fault = self._injector.snapshot_fault(self._poll_idx)
                if fault == "drop":
                    # Lost write: close the gate like a budget refusal —
                    # deeper nodes would have no parent path.
                    self._pref_insert_ok[row] = False
                    return
                if fault == "corrupt":
                    snap = self._injector.corrupt(snap)
            if self._poison_probe != "off" and \
                    not self._snapshot_finite(snap):
                # Poison gate: a corrupt snapshot must never enter the
                # cross-request cache — refuse it and stop attaching
                # deeper nodes for this request.
                self._pref_insert_ok[row] = False
                self.tracer.instant("snapshot_poison_refused", slot=row,
                                    offset=off)
                log.error("prefix snapshot at offset %d (row %d) is "
                          "non-finite — refused", off, row)
                return
            nxt = cache.insert(self._pref_node[row], key[depth - 1], snap)
            if nxt is None:
                self._pref_insert_ok[row] = False
                return
        self._pref_node[row] = nxt
        self._pref_pins[row].append(nxt)

    def _prefix_release(self, row: int) -> None:
        """Staging is over (first token sampled or request finished):
        unpin the row's trie path — its nodes become evictable again."""
        for node in self._pref_pins[row]:
            self._pcache.release(node)
        self._pref_pins[row] = []
        self._pref_node[row] = None
        self._pref_key[row] = None

    def _prefill_step(self) -> int:
        """Advance every prefilling slot by one chunk (one compiled call at
        ``(slots, chunk)`` + offset vector); finished prompts sample their
        first token and move their state rows into the decode pool.
        Returns prompt tokens advanced (0 when nothing is prefilling)."""
        cfg = self.cfg
        rows = [i for i, r in enumerate(self._pref_req) if r is not None]
        if not rows:
            return 0
        C = self.chunk
        tokens = np.full((self.slots, C), cfg.pad_id, np.int32)
        for i in rows:
            off = self._pref_off[i]
            tokens[i] = self._pref_toks[i][off:off + C]
        with self.tracer.span("prefill_chunk", rows=len(rows),
                              tokens=C * len(rows), program=self._pid_chunk,
                              path=self._chunk_path):
            t0 = time.perf_counter()
            toks_dev = jnp.asarray(tokens)
            off_dev = jnp.asarray(self._pref_off)
            logits, self._ppool.cache = self._guarded_call(
                "prefill_chunk",
                lambda: self._chunk_step(self.params, toks_dev,
                                         self._ppool.cache, off_dev))
            # Synchronize before the host-side bookkeeping so the recorded
            # chunk time is the compiled call alone — snapshot exports,
            # the logits copy and sampling get their own spans.
            jax.block_until_ready(logits)
            t1 = time.perf_counter()
        self._observe_step(self.monitor_prefill, "prefill", t1 - t0)
        self.metrics.record_prefill(C * len(rows), t1 - t0)
        done_rows = []
        for i in rows:
            self._pref_off[i] += C
            if self._pcache is not None:
                self._prefix_insert(i)
            if self._pref_off[i] >= len(self._pref_toks[i]):
                if self._pcache is not None:
                    self._prefix_release(i)
                done_rows.append(i)
        if done_rows:
            # The hand-off's one copy: the probe and the sampler read it.
            lg = self._logits_to_host(logits, "prefill")
        if done_rows and self._poison_probe != "off":
            # Gate the staging->decode handoff: a non-finite final-chunk
            # logits row means the staged state is poisoned — shed it here
            # so it never reaches the decode pool (or the prefix cache,
            # whose inserts are separately gated in _prefix_insert).
            now_p = time.time()
            kept = []
            for i in done_rows:
                if np.isfinite(lg[i]).all():
                    kept.append(i)
                    continue
                req = self._pref_req[i]
                if self._pcache is not None:
                    self._prefix_release(i)
                self._pref_req[i] = None
                self._pref_toks[i] = None
                self._ppool.reset_rows([i])
                req.status = "poisoned"
                req.done = True
                req.finish_s = now_p
                req.latency_s = now_p - req.arrival_s
                self.metrics.record_quarantine()
                self.metrics.record_shed("poison")
                self.tracer.instant("quarantine", uid=req.uid, slot=i,
                                    where="prefill")
                if self.flight is not None:
                    self.flight.record_request(req, slot=i,
                                               status="poisoned")
                    self.flight.record_fault("quarantine", uid=req.uid,
                                             slot=i, where="prefill")
                log.error("request %d: non-finite prefill output in "
                          "staging row %d — quarantined", req.uid, i)
                self._finished.append(req)
            done_rows = kept
        if done_rows:
            # Only the finished prompts' rows are drawn; first[j] is the
            # first token of slot done_rows[j].
            uids = np.array([self._pref_req[i].uid for i in done_rows],
                            np.int64)
            poss = np.array([len(self._pref_toks[i]) for i in done_rows],
                            np.int64)
            with self.tracer.span("sample", phase="prefill",
                                  rows=len(done_rows), used=len(done_rows)):
                first = self._sample_rows(lg[done_rows], uids, poss)
            # Row i prefilled in the second pool becomes slot i's decode
            # state (same index — the slot was reserved at admission).
            self.pool.insert_rows(self._ppool.cache, done_rows, done_rows)
            t_first = time.time()
            for i, tok in zip(done_rows, first):
                req = self._pref_req[i]
                span = len(self._pref_toks[i])
                self._pref_req[i] = None
                self._pref_toks[i] = None
                self._start_tenant(i, req, span, int(tok), t_first)
        return C * len(rows)

    # ------------------------------------------------------------------
    # self-speculative decoding
    # ------------------------------------------------------------------
    def _row_uids(self) -> List[int]:
        """Per-slot owning-request uids (0 for dead/staging rows — their
        sampled tokens are discarded anyway)."""
        return [r.uid if r is not None else 0 for r in self._slot_req]

    def _spec_burst(self, live: List[int]) -> None:
        """One speculative burst across the live slots (accept rule and
        notation: ``serve/speculative.py``): snapshot live rows, draft
        ``k`` tokens with the draft params on the scratch pool, verify
        all ``k`` in one chunk call on the decode pool, emit per-row
        ``min(m + 1, k)`` verify-stream tokens, restore rows that
        consumed a rejected draft and park their emitted tokens in the
        overflow queue for the decode-step drain."""
        cfg = self.cfg
        k = self.spec_k
        uids = self._row_uids()
        # Pre-burst snapshot + draft working copies: compile-once pool
        # row scatters, no host roundtrip.  Dead/staging rows are left
        # stale — the verify chunk advances them as garbage sinks and a
        # refill overwrites the whole row (same discipline as decode).
        with self.tracer.span("spec_copy", rows=len(live)):
            self._bpool.insert_rows(self.pool.cache, live, live)
            self._dpool.insert_rows(self.pool.cache, live, live)

        # Draft pass: k calls of the ordinary decode program (the
        # quantized pytree is a second trace of it, warmed up with
        # everything else), donating the scratch pool's arena.
        drafts = np.zeros((self.slots, k), np.int32)
        cur = self._next_tok.copy()
        t0 = time.perf_counter()
        for j in range(k):
            cur_dev = jnp.asarray(cur[:, None])
            posj_dev = jnp.asarray(self._pos + j)
            logits, self._dpool.cache = self._guarded_call(
                "draft",
                lambda: self._decode(self._draft_params, cur_dev,
                                     self._dpool.cache, posj_dev))
            cur = self._sample_rows(logits, uids, self._pos + j + 1)
            drafts[:, j] = cur
        t1 = time.perf_counter()
        self.tracer.complete("draft", t0, t1, rows=len(live), k=k,
                             tokens=k * len(live),
                             program=self._pid_draft)
        self._observe_step(self.monitor_spec, "draft", t1 - t0)

        # Verify pass: ONE chunk call over [t0, d_1 .. d_{k-1}], donating
        # the decode pool — rows that keep their window inherit the
        # post-chunk state for free.
        vtoks = np.empty((self.slots, k), np.int32)
        vtoks[:, 0] = self._next_tok
        if k > 1:
            vtoks[:, 1:] = drafts[:, :k - 1]
        t0 = time.perf_counter()
        vtoks_dev = jnp.asarray(vtoks)
        vpos_dev = jnp.asarray(self._pos)
        vlogits, self.pool.cache = self._guarded_call(
            "verify",
            lambda: self._verify(self.params, vtoks_dev, self.pool.cache,
                                 vpos_dev))
        vl = np.asarray(vlogits, np.float32)
        t1 = time.perf_counter()
        self.tracer.complete("verify", t0, t1, rows=len(live),
                             tokens=k * len(live),
                             program=self._pid_verify)
        self._observe_step(self.monitor_spec, "verify", t1 - t0)
        self.metrics.record_step(len(live), t1 - t0)

        # The verify stream: position j's token samples with the same
        # (uid, position) key the plain decode step would use there.
        verify = np.empty((self.slots, k), np.int32)
        for j in range(k):
            verify[:, j] = self._sample_rows(vl[:, j], uids,
                                             self._pos + j + 1)
        m = accept_lengths(drafts, verify)
        n_emit = emit_counts(m, k)
        rollback = needs_rollback(m, k)
        now = time.time()
        # Poison probe on the verify logits (+ state probe in "state"
        # mode): quarantined rows drop out of the emit loop below.
        self._probe_rows(live, vl, now, "verify")
        emitted_total = 0
        accepted = 0
        rollbacks = 0
        for i in live:
            req = self._slot_req[i]
            if req is None:         # quarantined by the probe above
                continue
            accepted += int(min(m[i], k))
            emitted: List[int] = []
            finished = False
            for j in range(int(n_emit[i])):
                tok = int(verify[i, j])
                req.emit(tok)
                emitted.append(tok)
                self.metrics.record_token()
                if (cfg.eos_id >= 0 and tok == cfg.eos_id) or \
                        len(req.out_tokens) >= req.max_new_tokens:
                    self._finish(req, now, i)
                    self._slot_req[i] = None
                    finished = True
                    break
            emitted_total += len(emitted)
            if finished:
                self._overflow[i] = []
                continue
            if rollback[i]:
                rollbacks += 1
                with self.tracer.span("rollback", slot=i,
                                      accepted=int(m[i])):
                    self.pool.insert_rows(self._bpool.cache, [i], [i])
                # _pos / _next_tok stay pre-burst: the decode-step drain
                # re-consumes the emitted tokens from the restored state.
                self._overflow[i] = emitted
            else:
                # The verify chunk consumed exactly the emitted stream's
                # prefix — its output state IS the post-emission state.
                self._pos[i] = min(int(self._pos[i]) + k, self.max_seq - 1)
                self._next_tok[i] = int(verify[i, k - 1])
        self.metrics.record_speculative(
            rows=len(live), drafted=k * len(live), accepted=accepted,
            emitted=emitted_total, rollbacks=rollbacks)

    # ------------------------------------------------------------------
    def poll(self) -> List[Request]:
        """Admit waiting requests into free slots, then run one decode
        step across all slots; returns requests completed this poll.

        With ``prefill_chunk`` set, admission only *stages* prompts: each
        poll advances the prefilling slots by one chunk (or more, up to
        ``prefill_token_budget`` prompt tokens) before the decode step, so
        long prompts stream in next to the running decode batch instead of
        stalling it."""
        cfg = self.cfg
        done0 = len(self._finished)
        t_poll0 = time.perf_counter()
        if self.tracer.enabled and self._last_poll_end is not None:
            # Host scheduling gap: everything between polls (the caller's
            # arrival loop, sleeps, network...) on its own trace track.
            self.tracer.complete("host_gap", self._last_poll_end, t_poll0,
                                 tid=TID_HOST)
        poll_span = self.tracer.span("poll")
        poll_span.__enter__()
        now = time.time()
        # -- fault-tolerance pre-work (docs/robustness.md) ------------------
        self._poll_idx += 1
        if self._recover_pending:
            self._watchdog_recover(now)
        if self._injector is not None:
            self._inject_poison()
        if getattr(cfg, "shed_inflight", False):
            self._shed_inflight(now)
        self._update_overload()
        if self.chunk:
            with self.tracer.span("admit") as sp:
                sp.args["admitted"] = self._admit_chunked(now)
            spent = self._prefill_step()
            # Degraded overload mode collapses the budget: exactly one
            # chunk call per poll, protecting decode latency while the
            # queue drains.
            budget = 0 if self._overloaded else cfg.prefill_token_budget
            while spent and budget > spent:
                # A finished prefill may have freed nothing, but an
                # EOS-on-prefill finish frees its slot for the queue.
                with self.tracer.span("admit") as sp:
                    sp.args["admitted"] = self._admit_chunked(time.time())
                adv = self._prefill_step()
                if not adv:
                    break
                spent += adv
        else:
            # Re-admit until slots are full or the queue drains (a request
            # that EOS'd on its prefill token frees its slot immediately).
            while self._free_slots() and len(self.scheduler):
                with self.tracer.span("admit") as sp:
                    n_admitted = sp.args["admitted"] = self._admit(now)
                if not n_admitted:
                    break
                now = time.time()

        live = [i for i, r in enumerate(self._slot_req) if r is not None]
        if live and self.spec_k and not self._overloaded and \
                not any(self._overflow[i] for i in live):
            self._spec_burst(live)
        elif live:
            # The program span ends when the logits are ready; the copy to
            # the host and the draw are its siblings under ``poll``.
            with self.tracer.span("decode_step", live=len(live),
                                  tokens=len(live), program=self._pid_decode):
                t0 = time.perf_counter()
                tok_dev = jnp.asarray(self._next_tok[:, None])
                pos_dev = jnp.asarray(self._pos)
                logits, self.pool.cache = self._guarded_call(
                    "decode",
                    lambda: self._decode(self._decode_params, tok_dev,
                                         self.pool.cache, pos_dev))
                jax.block_until_ready(logits)
                t1 = time.perf_counter()
            self._observe_step(self.monitor_decode, "decode", t1 - t0)
            self.metrics.record_step(len(live), t1 - t0)
            lg = self._logits_to_host(logits, "decode")
            # Only live rows are drawn (all of lg, uncopied, when every
            # slot is live); dead rows get no token.
            with self.tracer.span("sample", phase="decode", rows=len(live),
                                  used=len(live)):
                drawn = self._sample_rows(
                    lg if len(live) == self.slots else lg[live],
                    [self._slot_req[i].uid for i in live], self._pos[live] + 1)
            nxt = np.zeros(self.slots, np.int32)
            nxt[live] = drawn
            # Dead slots decode into a sink: their position pins to the last
            # cache column until a refill overwrites the whole row.
            self._pos = np.minimum(self._pos + 1, self.max_seq - 1)
            now = time.time()
            # Poison probe on this step's logits (+ state in "state"
            # mode): quarantined rows drop out of the emit loop.
            self._probe_rows(live, lg, now, "decode")
            for i in live:
                req = self._slot_req[i]
                if req is None:     # quarantined by the probe above
                    continue
                if self.spec_k and self._overflow[i]:
                    # Rollback drain: this step re-consumed a token the
                    # burst already emitted, re-advancing the restored
                    # state on the exact non-speculative trajectory; the
                    # freshly sampled token is discarded (once the queue
                    # empties, the next step recomputes it from
                    # bit-identical state).
                    self._next_tok[i] = self._overflow[i].pop(0)
                    continue
                tok = int(nxt[i])
                req.emit(tok)
                self.metrics.record_token()
                self._next_tok[i] = tok
                if (cfg.eos_id >= 0 and tok == cfg.eos_id) or \
                        len(req.out_tokens) >= req.max_new_tokens:
                    self._finish(req, now, i)
                    self._slot_req[i] = None
        poll_span.__exit__(None, None, None)
        self._last_poll_end = time.perf_counter()
        self.check_sentinels()
        self.metrics.observe_gauges(
            queue_depth=len(self.scheduler),
            live_slots=len(live),
            overloaded=float(self._overloaded),
            staging_depth=(sum(r is not None for r in self._pref_req)
                           if self.chunk else 0),
            **({"prefix_resident_bytes": self._pcache.resident_bytes}
               if self._pcache is not None else {}))
        self.metrics.maybe_snapshot(self._snapshot_extra)
        return self._finished[done0:]

    def run(self) -> List[Request]:
        """Serve until queue and slots drain; returns completed requests."""
        t0 = time.perf_counter()
        done: List[Request] = []
        while self.busy:
            done.extend(self.poll())
        t1 = time.perf_counter()
        self.tracer.complete("serve.run", t0, t1)
        self.metrics.record_wall(t1 - t0)
        return done

    def stats(self, requests: Optional[List[Request]] = None) -> dict:
        del requests  # parity with Engine.stats; metrics already aggregate
        return self.metrics.summary()
