#!/usr/bin/env python3
"""Chip smoke run: full-width mamba2-130m served on one TPU.

A smoke run, not a benchmark.  It proves that the serving path starts on
the chip and gives the right answers, and prints what it saw (device,
compile seconds per program, requests and tokens served, wall time) for
the record.  None of its times is a performance number.

Everything runs in this one process, which holds the chip:

(a) serve      The serve launcher's own steps (``repro.launch.serve``,
               ``--engine continuous --prefill-chunk 64``) at full width
               with seeded random weights: 8 slots, 16 requests of 40 new
               tokens, the backend fallback ladder off.  Every request
               must finish its budget, with one decode program, no
               recompile-sentinel trip and no backend fallback.
(b) reference  A few served requests are replayed through the engine's
               own compiled programs (the prefill chunks plus 8 decode
               steps).  Their logits are compared with a plain float32
               reference: ``model.forward`` under
               ``XambaConfig.baseline()``, params cast to fp32, inside
               ``jax.default_matmul_precision("highest")``, fed the padded
               prompt and the generated tokens.
(c) pallas     (a) again with ``--decode-mode pallas --prefill-mode
               pallas`` (64-token chunks pass both kernel gates).  The
               compiled decode and prefill-chunk programs must contain
               Pallas kernels (``tpu_custom_call``), so a silent skip to
               the XLA chain cannot pass, and the replayed logits must
               match (a)'s.

Tolerance.  The served path runs in bf16 (params and streams), the
reference in fp32; with random weights the bf16 rounding grows with
depth, so no fixed fraction of the logits fits every model.  The
tolerance is measured instead: the same reference function run in the
params' own dtype at default precision (a plain bf16 forward pass) gives
the error bf16 alone causes, and

    tol = TOL_FACTOR * max |plain bf16 forward - fp32 reference|

over the checked positions (plus a floor of 1e-3 of the logits' RMS).
Every served logit must lie within ``tol`` of the reference, and top-1
must agree wherever the reference's top-2 margin exceeds ``tol``.  (c)
holds the Pallas arm to (a)'s logits with the same ``tol``.

Exits non-zero, printing no result line, when JAX finds no TPU or any
phase fails.  On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage: ``python chip_smoke.py`` from the repository root on a TPU host.
"""
from __future__ import annotations

import json
import logging
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.xamba import XambaConfig  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

SERVE_ARGV = ["--arch", "mamba2-130m", "--engine", "continuous",
              "--prefill-chunk", "64", "--batch", "8", "--requests", "16",
              "--max-new", "40", "--prompt-len", "120",
              "--no-backend-fallback", "--seed", "0"]
# Served requests replayed against the reference, and the decode steps
# compared after each one's prefill.
CHECK_REQUESTS = 4
CHECK_DECODE_STEPS = 8
# The served logits may differ from the fp32 reference by at most this
# many times the difference a plain bf16 forward pass shows (docstring).
TOL_FACTOR = 2.0


class SmokeError(RuntimeError):
    """A phase found the system wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# ----------------------------------------------------------------------------
# (a) serve
# ----------------------------------------------------------------------------

def phase_serve(argv):
    """Build the engine the serve launcher builds for ``argv``, compile
    its decode and prefill-chunk programs (timed, via the engine's program
    cards), serve the launcher's workload, and check the health counters.
    Returns ``(engine, done, info)``."""
    args = serve_cli.parse_args(argv)
    engine = serve_cli.build_engine(args)
    cards = engine.registry.cards(["decode", "prefill_chunk"])
    serve_cli.submit_workload(engine, args)
    t0 = time.perf_counter()
    try:
        done = engine.run()
    finally:
        engine.close()
    wall = time.perf_counter() - t0
    serve_cli.report(engine, done, args)

    counters = engine.counters
    trips = {k: v for k, v in counters["recompile_trips"].items() if v}
    fallbacks = engine.metrics.backend_fallbacks
    check(len(done) == args.requests,
          f"{len(done)} of {args.requests} requests finished")
    short = [(r.uid, r.status, len(r.out_tokens)) for r in done
             if r.status != "ok" or len(r.out_tokens) != args.max_new]
    check(not short, f"requests short of their {args.max_new}-token "
          f"budget (uid, status, tokens): {short}")
    check(counters["decode_compiles"] == 1,
          f"decode_compiles = {counters['decode_compiles']}")
    check(counters["prefill_chunk_compiles"] == 1,
          f"prefill_chunk_compiles = {counters['prefill_chunk_compiles']}")
    check(not trips, f"recompile sentinels tripped: {trips}")
    check(fallbacks == 0, f"{fallbacks} backend fallbacks")
    info = {"requests": len(done),
            "tokens": sum(len(r.out_tokens) for r in done),
            "wall_s": wall,
            "compile_s": {n: c.compile_s for n, c in cards.items()},
            "tpu_kernels": {n: c.tpu_kernels for n, c in cards.items()},
            "decode_compiles": counters["decode_compiles"]}
    return engine, done, info


# ----------------------------------------------------------------------------
# (b) reference
# ----------------------------------------------------------------------------

def padded_prompt(engine, req) -> np.ndarray:
    """The left-padded stream the engine prefilled for ``req``."""
    span = req.bucket
    p = req.prompt[-engine.buckets[-1]:]
    toks = np.full(span, engine.cfg.pad_id, np.int32)
    toks[span - len(p):] = p
    return toks


def replay_logits(engine, req, n_decode: int) -> np.ndarray:
    """Logits of ``req`` recomputed by the engine's own compiled programs
    (row 0 of a fresh cache): the last prefill chunk, then ``n_decode``
    decode steps fed the tokens the engine served.  Row ``j`` is what
    produced ``req.out_tokens[j]``."""
    slots, chunk, pad = engine.slots, engine.chunk, engine.cfg.pad_id
    toks = padded_prompt(engine, req)
    cache = engine.model.init_cache(slots, engine.max_seq,
                                    engine.model.cfg.dtype)
    idx = jnp.zeros(slots, jnp.int32)
    for off in range(0, len(toks), chunk):
        batch = np.full((slots, chunk), pad, np.int32)
        batch[0] = toks[off:off + chunk]
        logits, cache = engine._chunk_step(engine.params, jnp.asarray(batch),
                                           cache, idx)
    rows = [np.asarray(logits[0], np.float32)]
    for tok in req.out_tokens[:n_decode]:
        step = np.full((slots, 1), pad, np.int32)
        step[0, 0] = tok
        logits, cache = engine._decode(engine._decode_params,
                                       jnp.asarray(step), cache, idx)
        rows.append(np.asarray(logits[0], np.float32))
    return np.stack(rows)


def reference_logits(engine, reqs, n_decode: int, *, fp32: bool = True):
    """The reference at the positions ``replay_logits`` covers: one
    baseline-mode ``model.forward`` over each padded prompt plus its first
    ``n_decode`` served tokens (right-padded to one length; causality
    keeps the padding out of earlier positions).  ``fp32`` casts the
    params to float32 and runs at HIGHEST matmul precision; otherwise the
    same function runs in the params' own dtype at default precision."""
    cfg = engine.model.cfg.replace(xamba=XambaConfig.baseline())
    params = engine.params
    precision = "default"
    if fp32:
        cfg = cfg.replace(param_dtype="float32")
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        precision = "highest"
    model = build_model(cfg)
    streams = [np.concatenate([padded_prompt(engine, r),
                               np.asarray(r.out_tokens[:n_decode],
                                          np.int32)]) for r in reqs]
    length = max(len(s) for s in streams)
    batch = np.full((len(streams), length), engine.cfg.pad_id, np.int32)
    for i, s in enumerate(streams):
        batch[i, :len(s)] = s
    with jax.default_matmul_precision(precision):
        logits = jax.jit(model.forward)(params, jnp.asarray(batch))
    logits = np.asarray(logits, np.float32)
    return np.concatenate([logits[i, r.bucket - 1:r.bucket + n_decode]
                           for i, r in enumerate(reqs)])


def compare_logits(got: np.ndarray, want: np.ndarray, tol: float,
                   what: str) -> dict:
    """Hold ``got`` to ``want`` (rows of logits) within ``tol``, with
    top-1 agreement wherever ``want``'s top-2 margin exceeds ``tol``;
    returns the largest observed difference."""
    diff = float(np.max(np.abs(got - want)))
    check(diff <= tol, f"{what}: max |diff| {diff:.4g} over tolerance "
                       f"{tol:.4g}")
    top2 = np.sort(want, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > tol
    agree = np.argmax(got, axis=-1) == np.argmax(want, axis=-1)
    check(bool(np.all(agree[decisive])),
          f"{what}: top-1 differs on {int(np.sum(decisive & ~agree))} of "
          f"{int(np.sum(decisive))} positions with a decisive margin")
    return {"positions": int(want.shape[0]),
            "decisive_positions": int(np.sum(decisive)),
            "max_abs_diff": diff, "tol": tol}


def phase_reference(engine, done):
    """Served logits (replayed) against the fp32 reference, within the
    tolerance the plain bf16 forward pass sets; the replay must also
    reproduce the served greedy tokens wherever its top-2 margin exceeds
    that tolerance.  Returns ``(checked requests, their replayed logits,
    info)``."""
    reqs = sorted(done, key=lambda r: r.uid)[:CHECK_REQUESTS]
    served = np.concatenate([replay_logits(engine, r, CHECK_DECODE_STEPS)
                             for r in reqs])
    ref = reference_logits(engine, reqs, CHECK_DECODE_STEPS)
    plain = reference_logits(engine, reqs, CHECK_DECODE_STEPS, fp32=False)
    plain_err = float(np.max(np.abs(plain - ref)))
    tol = TOL_FACTOR * plain_err + 1e-3 * float(np.sqrt(np.mean(ref ** 2)))
    info = compare_logits(served, ref, tol, "served vs fp32 reference")
    tokens = np.concatenate([r.out_tokens[:CHECK_DECODE_STEPS + 1]
                             for r in reqs])
    top2 = np.sort(served, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > tol
    same = np.argmax(served, axis=-1) == tokens
    check(bool(np.all(same[decisive])),
          "replayed greedy tokens differ from the served ones")
    info.update(plain_bf16_max_abs_diff=plain_err, tol_factor=TOL_FACTOR,
                ref_rms=float(np.sqrt(np.mean(ref ** 2))))
    return reqs, served, info


# ----------------------------------------------------------------------------
# (c) pallas
# ----------------------------------------------------------------------------

def phase_pallas(argv, reqs, xla_served, tol: float, mode: str = "pallas"):
    """Serve (a)'s workload with the Pallas decode and prefill kernels,
    then replay (a)'s checked requests (same streams) through this
    engine's programs and hold the logits to (a)'s.  ``mode="pallas"``
    also requires Pallas kernels in both compiled programs (interpret
    mode, the CPU rehearsal, lowers them to plain XLA)."""
    engine, _, info = phase_serve(
        list(argv) + ["--decode-mode", mode, "--prefill-mode", mode])
    if mode == "pallas":
        for name, n in info["tpu_kernels"].items():
            check(n > 0, f"compiled {name} program has no tpu_custom_call: "
                         "the Pallas path was skipped")
    got = np.concatenate([replay_logits(engine, r, CHECK_DECODE_STEPS)
                          for r in reqs])
    info["vs_xla"] = compare_logits(got, xla_served, tol,
                                    "pallas vs XLA arm")
    return info


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this is a chip run only", file=sys.stderr)
        return 2
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    cache_dir = enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] chip smoke run, not a benchmark; device {device}; "
          f"compile cache {cache_dir}", flush=True)

    engine, done, serve = phase_serve(SERVE_ARGV)
    print(f"[smoke] (a) serve: {json.dumps(serve)}", flush=True)
    reqs, xla_served, ref = phase_reference(engine, done)
    print(f"[smoke] (b) reference: {json.dumps(ref)}", flush=True)
    del engine, done
    pallas = phase_pallas(SERVE_ARGV, reqs, xla_served, ref["tol"])
    print(f"[smoke] (c) pallas: {json.dumps(pallas)}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
