"""A benchmark tree of tiny cells, written to a temporary directory.

Two cells at CPU-test size, one of each configuration family and loop:
``tiny2.open`` (Mamba-2, open loop) and ``tiny1.closed`` (Mamba-1, closed
loop, with requests in flight at the close), with the real metric readers
copied beside them.  Their limits
come from CPU readings at this size: the served bf16 path reads a
``gap`` of about 0.006, the float8 control about 0.05.

These cells test the harness, not how closely the program follows the
published models, so their configurations state whatever norm settings
the program states (``program_norm``): its RMSNorm epsilon and, for
Mamba-2, its gated-norm order, where these may differ from the
published models'.
"""
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import program  # noqa: E402

LIMITS = {"gap": 0.03}
# What the program computes where its ModelConfig states no norm settings:
# RMSNorm at ``layers.norm``'s epsilon, and Mamba-2's gate after the norm.
UNSTATED_NORM = {"norm_eps": 1e-6, "norm_before_gate": True}


def program_norm(cfg: dict) -> dict:
    """The reference's norm keys for what the program computes for ``cfg``.

    Read from ``program.model_config(cfg)``: ``norm_eps`` as
    ``norm_epsilon`` and, for Mamba-2, ``norm_before_gate`` (``mamba_ssm``'s
    name and meaning); ``UNSTATED_NORM`` where the config has no such field.
    """
    mcfg = program.model_config(cfg)
    out = {"norm_epsilon": getattr(mcfg, "norm_eps",
                                   UNSTATED_NORM["norm_eps"])}
    if mcfg.family == "mamba2":
        out["norm_before_gate"] = getattr(mcfg, "norm_before_gate",
                                          UNSTATED_NORM["norm_before_gate"])
    return out


def write(base: Path) -> dict:
    """Write the tree under ``base``; returns its BENCHMARK.json data."""
    for d in ("configs", "traffic", "workloads"):
        (base / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(HERE / "metrics", base / "metrics", dirs_exist_ok=True)
    c2 = json.loads((HERE / "configs" / "mamba2-2.7b.json").read_text())
    c2.update(d_model=64, n_layer=2, vocab_size=128, d_state=16, headdim=16,
              chunk_size=32, serve={"slots": 4, "chunk": 32})
    c2.update(program_norm(c2))
    c1 = json.loads((HERE / "configs" / "mamba-130m.json").read_text())
    c1.update(d_model=64, n_layer=2, vocab_size=128, dt_rank=4,
              serve={"slots": 4, "chunk": 16})
    c1.update(program_norm(c1))
    mixes = {
        "open": {"loop": "open", "rate_per_s": 4, "drain_cap_s": 30,
                 "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                            "min": 8, "max": 100},
                 "output": {"dist": "uniform", "min": 2, "max": 12}},
        "closed": {"loop": "closed", "clients": 4, "requests_per_client": 64,
                   "stagger_first": True,
                   "prompt": {"dist": "uniform", "min": 8, "max": 40},
                   "output": {"dist": "uniform", "min": 16, "max": 64}},
    }
    for name, data in (("configs/tiny2", c2), ("configs/tiny1", c1),
                       ("traffic/open", mixes["open"]),
                       ("traffic/closed", mixes["closed"])):
        (base / f"{name}.json").write_text(json.dumps(data))
    cells = {"tiny2.open": ("tiny2", "open"),
             "tiny1.closed": ("tiny1", "closed")}
    for name, (cfg, mix) in cells.items():
        (base / "workloads" / f"{name}.json").write_text(json.dumps({
            "config": cfg, "traffic": mix, "why": "test",
            "check": {"requests": 4, "columns": 64, "limits": LIMITS},
            "trace": {"start_frac": 0.3, "seconds": 1}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "test"} for n, (c, t) in cells.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if m["name"] in ("setup_s", "itl_p95_ms",
                                            "output_tokens_per_s")]
    # The TTFT reader that open-loop cells use (``metrics/ttft_p50_s.py``).
    bench["end_to_end"].append({"name": "ttft_p50_s", "unit": "s",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock"})
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench
