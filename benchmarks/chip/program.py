"""The system under test, as the benchmark builds it.

Everything the benchmark takes from the serving program passes through
here: the model configuration for a ``configs/<name>.json`` file, the
model, and the continuous engine with chunked prefill that the serve
launcher builds (``repro.launch.serve``), at default backends
(``XambaConfig.optimized()``: decode and prefill ``cumba`` on XLA).
"""
from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

from repro.configs import get_config  # noqa: E402
from repro.core.xamba import XambaConfig  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import ContinuousEngine, ServeConfig  # noqa: E402

from weights import dims  # noqa: E402


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a benchmark configuration."""
    dm = dims(cfg)
    sizes = dict(vocab_size=dm["V"], d_model=cfg["d_model"],
                 n_layers=cfg["n_layer"], d_state=cfg["d_state"],
                 d_conv=cfg["d_conv"], expand=cfg["expand"],
                 tie_embeddings=cfg["tie_embeddings"],
                 param_dtype=cfg["param_dtype"], scan_layers=True,
                 xamba=XambaConfig.optimized())
    if cfg["family"] == "mamba2":
        sizes.update(ssm_head_dim=cfg["headdim"],
                     ssm_ngroups=cfg["ngroups"],
                     chunk_size=cfg["chunk_size"])
    else:
        sizes.update(dt_rank=cfg["dt_rank"])
    mcfg = get_config(cfg["program_arch"]).replace(**sizes)
    if mcfg.family != cfg["family"]:
        raise ValueError(f"{cfg['program_arch']} is a {mcfg.family} model, "
                         f"the configuration says {cfg['family']}")
    return mcfg


def build(cfg: dict, params, *, max_prompt: int, max_new: int, seed: int,
          trace: bool = False):
    """A ``ContinuousEngine`` over ``params`` for ``cfg``: chunked prefill
    at the configuration's chunk, one prefill bucket that holds the
    longest prompt (so nothing is truncated), temperature 1.0, no EOS."""
    model = build_model(model_config(cfg))
    serve = cfg["serve"]
    chunk = serve["chunk"]
    bucket = -(-max_prompt // chunk) * chunk
    scfg = ServeConfig(
        max_batch=serve["slots"], prefill_buckets=(bucket,),
        max_new_tokens=max_new, eos_id=-1, temperature=1.0, seed=seed,
        prefill_chunk=chunk, trace=bool(trace), backend_fallback=False)
    return ContinuousEngine(model, params, scfg)


def abstract_params(cfg: dict):
    """The program's parameter tree for ``cfg`` as shapes and dtypes."""
    from repro.nn.params import abstract_params as ap
    mcfg = model_config(cfg)
    return ap(build_model(mcfg).param_specs(), mcfg.dtype)
