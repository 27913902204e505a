"""Mamba-1 selective scan: the time loop against an independent float64
recurrence, the absence of any ``(b, l, d_inner, d_state)`` array from a
stateful prefill, and training gradients under the default scan."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import selective_scan
from repro.models import ModelConfig
from repro.nn import ssm
from repro.nn.params import init_params


def _recurrence_f64(u, delta, A, B, C, D, h0):
    """h_t = exp(delta_t A) h_{t-1} + delta_t u_t B_t, y_t = C_t . h_t + D u_t."""
    h = h0.copy()
    ys = []
    for t in range(u.shape[1]):
        h = (np.exp(delta[:, t, :, None] * A[None]) * h
             + (delta[:, t] * u[:, t])[:, :, None] * B[:, t, None, :])
        ys.append((h * C[:, t, None, :]).sum(-1) + D * u[:, t])
    return np.stack(ys, axis=1), h


@pytest.mark.parametrize("l", [1, 7, 64])
def test_sequential_scan_matches_float64_recurrence(l):
    rng = np.random.default_rng(l)
    b, d, n = 3, 40, 16
    u = jnp.asarray(rng.standard_normal((b, l, d)), jnp.bfloat16)
    delta = jnp.asarray(rng.uniform(0.001, 0.2, (b, l, d)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 4.0, (d, n)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, l, n)), jnp.bfloat16)
    C = jnp.asarray(rng.standard_normal((b, l, n)), jnp.bfloat16)
    D = jnp.asarray(rng.standard_normal((d,)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, d, n)), jnp.float32)

    y, hT = selective_scan.selective_scan(
        u, delta, A, B, C, D, mode="sequential", initial_state=h0,
        return_final_state=True)
    f64 = [np.asarray(x, np.float64) for x in (u, delta, A, B, C, D, h0)]
    y_ref, h_ref = _recurrence_f64(*f64)

    assert y.dtype == jnp.bfloat16 and hT.dtype == jnp.float32
    assert hT.shape == (b, d, n)
    # fp32 state: a few roundings per step; bf16 output: within one ulp.
    np.testing.assert_allclose(np.asarray(hT), h_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y, np.float64), y_ref,
                               rtol=2.0 ** -7, atol=1e-4)


def _tiny_mamba1(**kw):
    return ModelConfig(name="mamba1", family="mamba", vocab_size=64,
                       d_model=24, n_layers=1, d_state=16,
                       param_dtype="float32", **kw)


def _layer_params(cfg, seed=0):
    return init_params(ssm.mamba1_specs(cfg), jax.random.PRNGKey(seed),
                       jnp.float32)


_F32_4D = re.compile(r"tensor<(\d+)x(\d+)x(\d+)x(\d+)xf32>")


@pytest.mark.parametrize("scan_mode,materialised",
                         [(None, False), ("associative", True)])
def test_stateful_prefill_builds_no_per_step_state(scan_mode, materialised):
    """The default scan never holds one state per token: no f32 array of
    the dims (b, l, d_inner, d_state), in any order, is in the lowered
    prefill.  The associative scan does hold them, which shows the check
    finds what it looks for."""
    kw = {} if scan_mode is None else {"scan_mode": scan_mode}
    cfg = _tiny_mamba1(**kw)
    b, l, d_inner, n = 3, 64, cfg.expand * cfg.d_model, cfg.d_state
    params = _layer_params(cfg)
    x = jnp.zeros((b, l, cfg.d_model), jnp.bfloat16)
    state = ssm.mamba1_init_state(cfg, b)

    text = jax.jit(lambda p, x, s: ssm.mamba1_apply(p, cfg, x, s)).lower(
        params, x, state).as_text()
    per_step = sorted((b, l, d_inner, n))
    found = [m.group(0) for m in _F32_4D.finditer(text)
             if sorted(map(int, m.groups())) == per_step]
    assert bool(found) == materialised, found


def test_default_scan_gradients_match_associative():
    cfg = _tiny_mamba1()
    assert cfg.scan_mode == "sequential"
    assoc = dataclasses.replace(cfg, scan_mode="associative")
    params = _layer_params(cfg, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model),
                          jnp.float32)

    def loss(p, c):
        out, _ = ssm.mamba1_apply(p, c, x)
        return jnp.sum(out.astype(jnp.float32) * w)

    g_seq = jax.grad(loss)(params, cfg)
    g_assoc = jax.grad(loss)(params, assoc)
    flat_seq, tree = jax.tree.flatten(g_seq)
    flat_assoc, tree_assoc = jax.tree.flatten(g_assoc)
    assert tree == tree_assoc
    for gs, ga in zip(flat_seq, flat_assoc):
        assert np.isfinite(np.asarray(gs)).all()
        np.testing.assert_allclose(np.asarray(gs), np.asarray(ga),
                                   rtol=1e-4, atol=1e-5)
