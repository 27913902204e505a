#!/usr/bin/env python3
"""Knee sweep for an open-loop cell: the highest rate the engine sustains.

Builds the cell's engine once, then offers the cell's traffic at each
rate for ``--seconds`` and prints one JSON line per rate: requests due,
those finished by the window's close, the backlog left, the seconds the
drain took, TTFT p50/p90, ITL p95 and output tokens/s.  Past the knee the
backlog and the drain grow with the rate.  Used once, when a cell's rate
is fixed (``traffic/<mix>.json``, ``rate_per_s`` at about 4/5 of the
knee); the benchmark's runs never search for a rate.

    python benchmarks/chip/sweep.py --workload <cell> --rates 0.5,1,2 \
        --seconds 20 --seed 1
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import catalog  # noqa: E402
import cell  # noqa: E402
import loadgen  # noqa: E402
import program  # noqa: E402
import serving  # noqa: E402
import timing  # noqa: E402
import weights  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell.device_or_exit(1)
    cell.enable_cache()
    spec = catalog.workload(args.workload)
    cfg, mix = spec["config_data"], spec["traffic_data"]
    params = weights.make_params(cfg, args.seed)
    engine = program.build(cfg, params, max_prompt=loadgen.max_prompt(mix),
                           max_new=loadgen.max_output(mix), seed=args.seed)
    cell.warm_up(engine, cfg, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = loadgen.open_loop(dict(mix, rate_per_s=rate), args.seconds,
                                 args.seed, cfg["vocab_size"])
        drv = serving.LoadLoop(engine)
        w = drv.open_loop(plan, args.seconds, mix["drain_cap_s"])
        end = max((r.finish_pc or w.t1) for r in w.records)
        row = {"rate_per_s": rate, "due": len(plan),
               "finished_in_window": sum(r.finish_pc is not None and
                                         r.finish_pc <= w.t1
                                         for r in w.records),
               "backlog_at_close": sum(r.finish_pc is None or
                                       r.finish_pc > w.t1
                                       for r in w.records),
               "drain_s": max(0.0, end - w.t1),
               "ttft_p50_s": timing.percentile(timing.ttfts(w), 50),
               "ttft_p90_s": timing.percentile(timing.ttfts(w), 90),
               "itl_p95_ms": (timing.percentile(timing.itl_gaps(w), 95)
                              or 0) * 1e3,
               "tokens_per_s": timing.tokens_in_window(w) / args.seconds,
               "failed": timing.attempted_failed(w)[1]}
        print(json.dumps(row), flush=True)
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
