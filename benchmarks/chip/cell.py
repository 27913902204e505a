#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s``, from process start): JAX's persistent
compile cache inside the checkout, the configuration's weights made on
the device from the seed, the continuous engine with chunked prefill,
two warm-up requests through every program the window uses, and for a
closed loop the ramp until every client decodes.  Then the window: the
cell's traffic for ``--seconds`` seconds (``serving.py``).  Then the
device's peak memory is read, the engine is freed, and the served logits
of a sample of requests are compared with the plain reference
(``verdict.py``).

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the engine's span tracer is on, a slice of
the window is profiled, and the metrics are the cell's per-layer ones.
Each metric is read by ``metrics/<name>.py`` from the run.

The last line of standard output is the result as one JSON object.  The
numbers compared for ``correct`` are printed beside their limits as the
last lines of standard error and under ``check``, the result's last key.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import catalog  # noqa: E402
import loadgen  # noqa: E402
import program  # noqa: E402
import serving  # noqa: E402
import timing  # noqa: E402
import tracereduce  # noqa: E402
import verdict  # noqa: E402
import weights  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"cell: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs


def enable_cache() -> None:
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def sample_keys(cell: dict, mix: dict, plan, seed: int) -> set:
    """Requests whose logits are captured, chosen from the seed before the
    window: open loop, the longest output and others at random; closed
    loop, every request of some clients."""
    k = cell["check"]["requests"]
    rng = np.random.default_rng([seed, 3])
    if mix["loop"] == "open":
        longest = max(plan, key=lambda q: q.max_new).key
        rest = [q.key for q in plan if q.key != longest]
        pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
        return {longest} | {rest[i] for i in pick}
    clients = rng.choice(len(plan), min(k, len(plan)), replace=False)
    return {q.key for c in clients for q in plan[c]}


def warm_up(engine, cfg: dict, seed: int) -> None:
    """Two requests of two prefill chunks and three tokens: every program
    and pool row operation of the window, at the window's shapes."""
    rng = np.random.default_rng([seed, 4])
    chunk = cfg["serve"]["chunk"]
    for _ in range(2):
        engine.submit(rng.integers(1, cfg["vocab_size"],
                                   chunk + 1).tolist(), 3)
    engine.run()
    engine.reset_stats()


def serve(name: str, seed: int, seconds: float, trace: bool, *,
          base: Path = HERE, bench: dict = None, require_tpu: bool = True,
          engine_hook=None) -> SimpleNamespace:
    """Set-up, the window, the device's peak memory, then the engine freed
    (``engine_hook(engine)`` lets a test break the timed path underneath).
    Returns what the metrics and the check read."""
    import jax
    bench = bench if bench is not None else catalog.benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    devs = device_or_exit(entry["chips"]) if require_tpu else jax.devices()
    cell = catalog.workload(name, base)
    cfg, mix = cell["config_data"], cell["traffic_data"]
    vocab = cfg["vocab_size"]

    params = weights.make_params(cfg, seed)
    jax.block_until_ready(params)
    engine = program.build(cfg, params, max_prompt=loadgen.max_prompt(mix),
                           max_new=loadgen.max_output(mix), seed=seed,
                           trace=trace)
    if mix["loop"] == "open":
        plan = loadgen.open_loop(mix, seconds, seed, vocab)
    else:
        plan = loadgen.closed_loop(mix, seed, vocab)
    dm = weights.dims(cfg)
    cols = np.sort(np.random.default_rng([seed, 2]).choice(
        dm["V"], min(cell["check"]["columns"], dm["V"]), replace=False))
    capture = serving.Capture(cols)
    warm_up(engine, cfg, seed)
    capture.install(engine)
    if engine_hook is not None:
        engine_hook(engine)
    load = serving.LoadLoop(engine, capture,
                            sample_keys(cell, mix, plan, seed))
    counter = serving.CompileCounter()
    syncs: list = []
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    marks = {}

    def open_window():
        marks["setup_s"] = time.time() - T_START
        counter.armed = True

    def close_window():
        counter.armed = False

    def sync():
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracereduce.SYNC):
            pass
        syncs.append((t0 + time.perf_counter()) / 2)

    def trace_on():
        jax.profiler.start_trace(trace_dir)
        sync()

    def trace_off():
        sync()
        jax.profiler.stop_trace()

    hooks = [(0.0, open_window), (seconds, close_window)]
    if trace:
        start = cell["trace"]["start_frac"] * seconds
        hooks += [(start, trace_on),
                  (min(seconds, start + cell["trace"]["seconds"]), trace_off)]
    if mix["loop"] == "open":
        window = load.open_loop(plan, seconds, mix["drain_cap_s"], hooks)
    else:
        window = load.closed_loop(plan, seconds, hooks)

    dev = devs[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    spans = tracereduce.engine_spans(engine.tracer) if trace else []
    trips = sum(s.trips for s in engine.sentinels.values())
    counter.close()
    engine.close()
    del engine, load
    gc.collect()
    reduced = None
    if trace:
        reduced = tracereduce.reduce(tracereduce.load(trace_dir), spans,
                                     syncs)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return SimpleNamespace(
        name=name, cell=cell, cfg=cfg, mix=mix, params=params, window=window,
        capture=capture, cols=cols, device=device, spans=spans,
        reduced=reduced, trips=trips, compiles=counter.count,
        setup_s=marks["setup_s"], bench=bench, base=base, seed=seed)


def check(sv, control: bool = False) -> tuple:
    """The sampled requests and the numbers compared for ``correct``:
    the served path's, or (``control``) the float8 reference's in its
    place."""
    recs = _check_sample(sv.window, sv.capture, sv.cell, sv.seed)
    nums = verdict.numbers(sv.cfg, sv.params, recs, sv.capture.rows, sv.cols,
                           sv.seed, sv.cfg["serve"]["chunk"],
                           loadgen.max_output(sv.mix), control=control)
    return recs, nums


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             **kw) -> dict:
    """One run; returns the result object."""
    sv = serve(name, seed, seconds, trace, **kw)
    attempted, failed = timing.attempted_failed(sv.window)
    # What a metric reader sees of the run.
    run = SimpleNamespace(
        cell=sv.cell, cfg=sv.cfg, mix=sv.mix, window=sv.window,
        setup_s=sv.setup_s, spans=sv.spans, device=sv.reduced,
        slots=sv.cfg["serve"]["slots"], chunk=sv.cfg["serve"]["chunk"],
        device_kind=sv.device["kind"])
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in catalog.metrics_for(sv.bench, name, kind):
        value = catalog.reader(m["name"], sv.base)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_ref = time.perf_counter()
    recs, nums = check(sv)
    limits = sv.cell["check"]["limits"]
    correct = verdict.judge(nums, limits)
    late = sv.window.lateness_s
    print(f"cell {name}: seed {seed}, {attempted} attempted, {failed} "
          f"failed, {len(recs)} requests / {nums['positions']} positions "
          f"checked in {time.perf_counter() - t_ref:.1f} s, {sv.compiles} "
          f"compiles and {sv.trips} recompile trips in the window, "
          f"generator late by p99 "
          f"{np.quantile(late, 0.99) if late else 0.0:.4f} s; logit_err "
          f"{nums['logit_err']!r}, token_gap {nums['token_gap']!r}",
          file=sys.stderr)
    checked = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    for k, v in checked.items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": sv.device}
    if sv.reduced is not None:
        result["breakdown"] = sv.reduced["breakdown"]
    result["check"] = checked
    return result


def _check_sample(window, capture, cell, seed: int) -> list:
    """Captured requests that completed (and, in a closed loop, those cut
    at the close, over the tokens they were served), the longest always
    among them."""
    done = [r for r in window.records if r.uid in capture.uids and
            r.tokens and r.status in ("ok", "cut")]
    cap = cell["check"]["requests"]
    if len(done) <= cap:
        return done
    done.sort(key=lambda r: (-len(r.tokens), r.uid))
    rng = np.random.default_rng([seed, 5])
    pick = rng.choice(len(done) - 1, cap - 1, replace=False) + 1
    return [done[0]] + [done[i] for i in sorted(pick)]


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
