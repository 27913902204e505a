#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, in one process.

For each seed the cell's window is served at its own size and load, and
the numbers ``correct`` compares (``verdict.py``) are read two ways:

* ``served``: the engine as configured (bfloat16), every seed: the
  lower reading is the largest over a dozen seeds or more;
* ``fp8``: the control, the reference with float8 matmul operands put in
  the engine's place on the same prompts and tokens (``--control``
  seeds): the upper reading is the smallest.

One JSON line per seed and arm.  The benchmark's runs never run this.

    python benchmarks/chip/calibrate.py --workload <cell> --seconds 20 \
        --seeds 1,2,3 --control 1,2,3
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cell  # noqa: E402
import timing  # noqa: E402


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control", type=_ints, default=[])
    args = ap.parse_args(argv)
    cell.device_or_exit(1)
    cell.enable_cache()
    for seed in args.seeds:
        sv = cell.serve(args.workload, seed, args.seconds, False)
        attempted, failed = timing.attempted_failed(sv.window)
        todo = [("served", False)]
        if seed in args.control:
            todo.append(("fp8", True))
        for arm, control in todo:
            t0 = time.perf_counter()
            recs, nums = cell.check(sv, control=control)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "arm": arm, **nums, "requests": len(recs),
                              "attempted": attempted, "failed": failed,
                              "check_s": time.perf_counter() - t0,
                              "memory_peak_bytes":
                                  sv.device["memory_peak_bytes"]}),
                  flush=True)
        del sv
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
