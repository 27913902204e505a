"""CPU rehearsal of ``chip_smoke.py``: its phases at ``--reduced`` with
the Pallas kernels in interpret mode (control flow, counters, replay and
reference plumbing), its tolerance rule, and its refusal to report a
result anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_rehearse_on_cpu(smoke):
    argv = smoke.SERVE_ARGV + ["--reduced"]
    engine, done, info = smoke.phase_serve(argv)
    assert info["requests"] == 16 and info["tokens"] == 16 * 40
    assert info["decode_compiles"] == 1
    reqs, served, ref = smoke.phase_reference(engine, done)
    assert ref["positions"] == smoke.CHECK_REQUESTS * (
        smoke.CHECK_DECODE_STEPS + 1)
    assert 0 < ref["max_abs_diff"] <= ref["tol"]
    pallas = smoke.phase_pallas(argv, reqs, served, ref["tol"],
                                mode="pallas_interpret")
    assert pallas["decode_compiles"] == 1
    assert pallas["vs_xla"]["max_abs_diff"] <= ref["tol"]


def test_compare_logits_rule(smoke):
    want = np.array([[0.0, 1.0, 3.0], [0.0, 2.0, 2.1]], np.float32)
    info = smoke.compare_logits(want + 0.05, want, 0.2, "shifted")
    assert info["decisive_positions"] == 1
    # A flip where the margin (0.1) is inside the tolerance is allowed...
    smoke.compare_logits(want[1:, [0, 2, 1]], want[1:], 0.2, "tie")
    # ...but an error over the tolerance, or a flip on a row whose margin
    # (1.5) exceeds it, fails.
    with pytest.raises(smoke.SmokeError, match="over tolerance"):
        smoke.compare_logits(want + 0.5, want, 0.2, "off")
    with pytest.raises(smoke.SmokeError, match="top-1"):
        smoke.compare_logits(np.array([[0.0, 1.9, 1.6]], np.float32),
                             np.array([[0.0, 1.0, 2.5]], np.float32), 1.0,
                             "flip")


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_without_a_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(lone, tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
