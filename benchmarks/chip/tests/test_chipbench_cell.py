"""A whole run of a cell on the CPU at test size, the chip check skipped;
and the exits without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import catalog  # noqa: E402
import cell  # noqa: E402
import chipbench_fixture  # noqa: E402

BIG_SEED = 2 ** 33 + 5
EXTRA_METRIC = '''"""Longest time to first token (a metric added as a file)."""
import timing


def read(run):
    v = timing.ttfts(run.window)
    return max(v) if v else None
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    base = tmp_path_factory.mktemp("cells")
    bench = chipbench_fixture.write(base)
    # A metric added as a new file and a new entry, nothing edited.
    (base / "metrics" / "ttft_max_s.py").write_text(EXTRA_METRIC)
    bench["end_to_end"].append({"name": "ttft_max_s", "unit": "s",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny2.open"]})
    return base, bench


def test_additions_are_found_by_name(tree):
    base, bench = tree
    cellspec = catalog.workload("tiny2.open", base)
    assert cellspec["config_data"]["d_model"] == 64
    assert cellspec["traffic_data"]["loop"] == "open"
    names = [m["name"] for m in catalog.metrics_for(bench, "tiny2.open",
                                                    "end_to_end")]
    assert "ttft_max_s" in names
    assert "ttft_max_s" not in [m["name"] for m in catalog.metrics_for(
        bench, "tiny1.closed", "end_to_end")]
    assert callable(catalog.reader("ttft_max_s", base))


@pytest.mark.parametrize("name", ["tiny2.open", "tiny1.closed"])
def test_cell_runs_and_is_correct(tree, name):
    base, bench = tree
    res = cell.run_cell(name, BIG_SEED, 2.0, False, base=base, bench=bench,
                        require_tpu=False)
    assert res["correct"] is True
    assert list(res)[-1] == "check"
    assert set(res["check"]) == {"gap"}
    assert res["check"]["gap"]["value"] < chipbench_fixture.LIMITS["gap"]
    assert res["attempted"] > 0 and res["failed"] == 0
    got = set(res["metrics"])
    assert {"setup_s", "itl_p95_ms"} <= got
    if name == "tiny2.open":
        assert {"ttft_p50_s", "ttft_max_s"} <= got
    else:
        assert "output_tokens_per_s" in got
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/cell.py", "--workload",
         "mamba-130m.decode", "--seed", str(BIG_SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
