"""Finds the benchmark's pieces by name: nothing here names a cell.

* ``BENCHMARK.json`` at the checkout root lists the cells and metrics;
* ``workloads/<cell>.json`` names a configuration and a traffic mix and
  holds the cell's correctness check (sample size and limits);
* ``configs/<config>.json`` holds a configuration's sizes;
* ``traffic/<traffic>.json`` holds a mix's parameters;
* ``metrics/<metric>.py`` holds a reader ``read(run) -> float | None``.

A later change adds a configuration, a mix, a cell or a metric by adding
files (and its entries in ``BENCHMARK.json``), without editing any file
that is already here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, base: Path = HERE) -> dict:
    cell = load_json(base / "workloads" / f"{name}.json")
    cell["config_data"] = load_json(base / "configs" / f"{cell['config']}.json")
    cell["traffic_data"] = load_json(base / "traffic" / f"{cell['traffic']}.json")
    return cell


def reader(metric: str, base: Path = HERE):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = base / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics a cell
    reports: those that list it, and those without a ``workloads`` key
    whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out
