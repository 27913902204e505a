"""The benchmark's weights and plain reference against the program.

At test size and in float32, given the program's own norm settings, the
reference and the program's own forward pass compute the same function;
the weights the benchmark makes have exactly the program's parameter
layout.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import program  # noqa: E402
from chipbench_fixture import PROGRAM_NORM  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402
from repro.models import build_model  # noqa: E402

TINY = {"mamba2-2.7b": dict(d_model=64, n_layer=2, vocab_size=100,
                            d_state=16, headdim=16, chunk_size=32),
        "mamba-130m": dict(d_model=64, n_layer=2, vocab_size=100,
                           dt_rank=4)}


def tiny(name, **kw):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    cfg.update(TINY[name], **kw)
    return cfg


def test_configurations_state_the_published_norms():
    for name in TINY:
        cfg = tiny(name)
        assert cfg["norm_epsilon"] == 1e-5 and cfg["residual_in_fp32"]
    assert tiny("mamba2-2.7b")["norm_before_gate"] is False


@pytest.mark.parametrize("name", list(TINY))
def test_weights_have_the_program_layout_and_repeat(name):
    cfg = tiny(name)
    a = weights.make_params(cfg, 2 ** 40 + 3)
    want = program.abstract_params(cfg)
    assert jax.tree.structure(a) == jax.tree.structure(want)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(want)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)
    b = weights.make_params(cfg, 2 ** 40 + 3)
    c = weights.make_params(cfg, 2 ** 40 + 4)
    leaf = lambda t: np.asarray(t["layers"]["mixer"]["in_proj"]["w"],  # noqa: E731
                                np.float32)
    assert np.array_equal(leaf(a), leaf(b))
    assert not np.array_equal(leaf(a), leaf(c))


@pytest.mark.parametrize("name", list(TINY))
def test_reference_matches_the_program_in_float32(name):
    cfg = tiny(name, param_dtype="float32")
    cfg.update(PROGRAM_NORM[cfg["family"]])
    params = weights.make_params(cfg, 7, jnp.float32)
    model = build_model(program.model_config(cfg))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 100, 96),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.forward(params, toks[None])[0]
    want = reference.logits_at(cfg, params, toks, 0, 96)
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * max(scale, 1.0)
    # The float8 control is a different function at this precision.
    low = reference.logits_at(cfg, params, toks, 0, 96, reference.fp8)
    assert float(jnp.max(jnp.abs(low - want))) > 1e-3 * scale
