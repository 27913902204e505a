"""The cell ``mamba2-2.7b.chat`` and the program's published norms.

* With the registry as it is (the published norms: epsilon 1e-5 and,
  for Mamba-2, the gate before the norm), the tiny cells of both families
  read ``correct`` against the reference given the published settings.
* A traced run of the tiny Mamba-2 cell gives each of the SSD cell's nine
  per-layer readers a finite value.  The CPU has no device plane in its
  profiler trace, so its XLA client threads stand in for one; the
  readers' roofline arithmetic is then taken at the v5e's peaks.
* The benchmark gives the new cell its nine metrics, ``setup_s`` and
  ``itl_p95_ms``, and ``mamba-130m.decode`` what it had.
"""
import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import catalog  # noqa: E402
import cell  # noqa: E402
import chipbench_fixture  # noqa: E402
import tracereduce  # noqa: E402

BIG_SEED = 2 ** 34 + 17
SSD_METRICS = ["ssd_decode_step_ms", "ssd_prefill_chunk_ms",
               "ssd_decode_roofline", "ssd_prefill_roofline",
               "ssd_decode_mfu", "ssd_prefill_mfu", "ssd_device_idle_share",
               "ssd_prefill_sample_ms", "ssd_decode_sample_ms"]
# What ``mamba-130m.decode`` reports, as before the SSD cell was added.
DECODE_CELL = {
    "end_to_end": ["setup_s", "itl_p95_ms", "output_tokens_per_s"],
    "per_layer": ["slot_occupancy", "decode_step_ms", "prefill_chunk_ms",
                  "decode_roofline", "prefill_roofline", "decode_mfu",
                  "prefill_mfu", "device_idle_share", "decode_sample_ms",
                  "prefill_sample_ms", "logits_to_host_ms",
                  "sample_row_use"]}
# Markers of the CPU client's thread pool, not operations.
NOT_OPS = ("ThreadpoolListener", "ThunkExecutor", "end: ")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    base = tmp_path_factory.mktemp("cells")
    bench = chipbench_fixture.write(base)
    # The traced slice covers the whole window.
    path = base / "workloads" / "tiny2.open.json"
    spec = json.loads(path.read_text())
    spec["trace"] = {"start_frac": 0.0, "seconds": 60}
    path.write_text(json.dumps(spec))
    return base, bench


def test_tiny_cells_state_the_published_norms(tree):
    base, _ = tree
    c2 = catalog.workload("tiny2.open", base)["config_data"]
    c1 = catalog.workload("tiny1.closed", base)["config_data"]
    assert (c2["norm_epsilon"], c2["norm_before_gate"]) == (1e-5, False)
    assert c1["norm_epsilon"] == 1e-5


@pytest.mark.parametrize("name", ["tiny2.open", "tiny1.closed"])
def test_published_norms_read_correct(tree, name):
    base, bench = tree
    res = cell.run_cell(name, BIG_SEED, 2.0, False, base=base, bench=bench,
                        require_tpu=False)
    assert res["correct"] is True
    assert res["check"]["gap"]["value"] < chipbench_fixture.LIMITS["gap"]


def cpu_as_device(planes):
    """The CPU trace's XLA operations (its client threads) as one device
    plane."""
    ops = [e for p in planes if p["name"].startswith("/host:")
           for ln in p["lines"] if ln["name"].startswith("tf_XLA")
           for e in ln["events"] if not e[0].startswith(NOT_OPS)]
    return [{"name": "/device:CPU:0",
             "lines": [{"name": "XLA Ops", "events": ops}]}]


def test_traced_run_feeds_the_ssd_readers(tree, monkeypatch):
    base, bench = tree
    monkeypatch.setattr(tracereduce, "device_planes", cpu_as_device)
    sv = cell.serve("tiny2.open", BIG_SEED, 3.0, True, base=base,
                    bench=bench, require_tpu=False)
    assert {s[0] for s in sv.spans} >= {"decode_step", "prefill_chunk"}
    paths = {s[4]["path"] for s in sv.spans if s[0] == "prefill_chunk"}
    assert paths == {"fused-cumba"}
    run = SimpleNamespace(
        cell=sv.cell, cfg=sv.cfg, mix=sv.mix, window=sv.window,
        setup_s=sv.setup_s, spans=sv.spans, device=sv.reduced,
        slots=sv.cfg["serve"]["slots"], chunk=sv.cfg["serve"]["chunk"],
        device_kind="TPU v5 lite")
    for name in SSD_METRICS:
        value = catalog.reader(name, base)(run)
        assert value is not None and math.isfinite(value) and value > 0, \
            (name, value)


def test_ssd_cell_reports_its_metrics():
    bench = catalog.benchmark()
    e2e = catalog.metrics_for(bench, "mamba2-2.7b.chat", "end_to_end")
    layer = catalog.metrics_for(bench, "mamba2-2.7b.chat", "per_layer")
    assert [m["name"] for m in e2e] == ["setup_s", "itl_p95_ms"]
    assert [m["name"] for m in layer] == SSD_METRICS
    assert all(m["moves"] == "itl_p95_ms" for m in layer)
    for name in SSD_METRICS:
        assert callable(catalog.reader(name))
    spec = catalog.workload("mamba2-2.7b.chat")
    assert spec["config_data"]["norm_before_gate"] is False
    assert spec["traffic_data"]["rate_per_s"] == 0.9


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_decode_cell_reports_what_it_did(kind):
    got = catalog.metrics_for(catalog.benchmark(), "mamba-130m.decode", kind)
    assert [m["name"] for m in got] == DECODE_CELL[kind]
