"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; ``--json`` additionally writes
machine-readable ``BENCH_decode.json`` / ``BENCH_serve.json`` (tokens/s
per family, speedups, compile counts) so the perf trajectory is tracked
across PRs.  Usage:

    PYTHONPATH=src python -m benchmarks.run [--only fig1,kpi,...]
    PYTHONPATH=src python -m benchmarks.run --json --smoke   # CI
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from repro.runtime.compile_cache import enable_compile_cache

BENCHES = ("fig1", "fig4a", "fig4c", "table1", "kpi", "roofline", "serve")
# Benchmarks with a --smoke-aware run(smoke=...) and a JSON artifact.
JSON_OUT = {"kpi": "BENCH_decode.json", "serve": "BENCH_serve.json",
            "table1": "BENCH_quality.json"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of " + ",".join(BENCHES))
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_decode.json / BENCH_serve.json")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the --json artifacts")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced iteration counts (CI); restricts the "
                         "default set to the JSON-producing benchmarks")
    args = ap.parse_args()
    enable_compile_cache()
    want = [w for w in args.only.split(",") if w]
    if not want:
        want = list(JSON_OUT) if args.smoke else list(BENCHES)

    print("name,us_per_call,derived")
    failures = 0
    for key in want:
        try:
            if key == "fig1":
                from benchmarks import bench_fig1_op_breakdown as m
            elif key == "fig4a":
                from benchmarks import bench_fig4a_cumba_reduba as m
            elif key == "fig4c":
                from benchmarks import bench_fig4c_actiba as m
            elif key == "table1":
                from benchmarks import bench_table1_quality as m
            elif key == "kpi":
                from benchmarks import bench_kpi_decode as m
            elif key == "roofline":
                from benchmarks import roofline as m
            elif key == "serve":
                from benchmarks import bench_serve_continuous as m
            else:
                raise ValueError(f"unknown benchmark {key!r}")
            kwargs = {"smoke": args.smoke} if key in JSON_OUT else {}
            result = m.run(**kwargs)
            if args.json and key in JSON_OUT:
                os.makedirs(args.out_dir, exist_ok=True)
                path = os.path.join(args.out_dir, JSON_OUT[key])
                with open(path, "w") as f:
                    json.dump(result, f, indent=2, sort_keys=True)
                    f.write("\n")
                print(f"# wrote {path}", file=sys.stderr)
        except Exception:  # noqa: BLE001 — keep the harness running
            failures += 1
            print(f"{key},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
