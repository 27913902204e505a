"""The benchmark's weights and plain reference against the program.

At test size and in float32, given the norm settings the program states
(``chipbench_fixture.program_norm``, which follows the program's config),
the reference and the program's own forward pass compute the same
function, and given other settings they do not; the weights the
benchmark makes have exactly the program's parameter layout.
"""
import dataclasses
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
import chipbench_fixture  # noqa: E402
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
    cfg.update(chipbench_fixture.program_norm(cfg))
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
    # Each norm setting changed alone (the other epsilon of 1e-6 and 1e-5;
    # Mamba-2's other gated-norm order) fails the comparison.
    others = [{"norm_epsilon": 1e-5 if cfg["norm_epsilon"] == 1e-6 else 1e-6}]
    if "norm_before_gate" in cfg:
        others.append({"norm_before_gate": not cfg["norm_before_gate"]})
    for other in others:
        off = reference.logits_at({**cfg, **other}, params, toks, 0, 96)
        assert float(jnp.max(jnp.abs(got - off))) > 1e-4 * max(scale, 1.0)


def stating(mcfg, **norm):
    """``mcfg`` with the norm fields ``norm`` set, whether or not its
    ``ModelConfig`` declares them: a program that states other norms."""
    later = dataclasses.make_dataclass(
        "Stated", [(k, type(v), v) for k, v in norm.items()],
        bases=(type(mcfg),), frozen=True)
    fields = {f.name: getattr(mcfg, f.name) for f in dataclasses.fields(mcfg)}
    return later(**{**fields, **norm})


@pytest.mark.parametrize("eps", [1e-6, 1e-5])
@pytest.mark.parametrize("before", [True, False])
def test_program_norm_follows_the_program_config(monkeypatch, tmp_path, eps,
                                                 before):
    real = program.get_config
    monkeypatch.setattr(program, "get_config", lambda arch: stating(
        real(arch), norm_eps=eps, norm_before_gate=before))
    norm = chipbench_fixture.program_norm
    assert norm(tiny("mamba2-2.7b")) == {"norm_epsilon": eps,
                                         "norm_before_gate": before}
    assert norm(tiny("mamba-130m")) == {"norm_epsilon": eps}
    # The tiny cells' configurations follow with no file edited.
    chipbench_fixture.write(tmp_path)
    c2 = json.loads((tmp_path / "configs" / "tiny2.json").read_text())
    c1 = json.loads((tmp_path / "configs" / "tiny1.json").read_text())
    assert (c2["norm_epsilon"], c2["norm_before_gate"]) == (eps, before)
    assert c1["norm_epsilon"] == eps and "norm_before_gate" not in c1
