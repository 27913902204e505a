"""Fused SSD prefill pipeline (``XambaConfig.prefill``): kernel-vs-oracle
parity, carried-state resumability, ActiBA / W8 composition, and the
engine-level contract that the fused backend changes NOTHING observable —
chunked == whole-sequence prefill and greedy outputs identical to the
unfused chain (fp32 configs; see ``kernels/prefill_chunk.py``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.xamba import XambaConfig
from repro.kernels import ops, prefill_chunk, ref
from repro.models import ModelConfig, build_model
from repro.nn import quant
from repro.nn.params import init_params
from repro.serve import ContinuousEngine, Engine, ServeConfig

V = 64
# The gated norm's epsilon as the published Mamba-2 states it.
PUBLISHED = dict(eps=1e-5)


def _inputs(rng, b, l, di, h, g, n, w):
    """Random fused-prefill operands with a nonzero carried state."""
    dxbc = di + 2 * g * n
    r = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return dict(
        z=r(b, l, di), xbc=r(b, l, dxbc), dt=r(b, l, h),
        conv_state=r(b, w - 1, dxbc), ssm_state=r(b, h, di // h, n) * 0.1,
        conv_w=r(w, dxbc) * 0.3, conv_b=r(dxbc) * 0.1, dt_bias=r(h) * 0.1,
        A=-jnp.exp(r(h) * 0.3), D=r(h) * 0.2,
        norm_scale=jnp.abs(r(di)) + 0.5)


# ---------------------------------------------------------------------------
# kernel-vs-oracle parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [64, 128, 256])
@pytest.mark.parametrize("g", [1, 2])
def test_kernel_matches_oracle(chunk, g):
    """Both fused backends match the exact sequential-scan oracle across
    chunk sizes (64-multiples, satellite of the relaxed ssd gate) and
    grouped-head layouts (the gated norm normalising each group on its
    own), with a carried initial state."""
    b, l, h, p, n, w = 2, 256, 4, 8, 8, 4
    ops_in = _inputs(np.random.default_rng(chunk + g), b, l, h * p, h, g,
                     n, w)
    kw = dict(ngroups=g, head_dim=p, silu=jax.nn.silu,
              softplus=jax.nn.softplus, **PUBLISHED)
    ry, rc, rs = ref.mamba2_prefill_ref(**ops_in, **kw)
    for name, got in [
        ("xla", prefill_chunk.mamba2_prefill_xla(**ops_in, chunk=chunk,
                                                 **kw)),
        ("pallas", prefill_chunk.mamba2_prefill_pallas(
            **ops_in, chunk=chunk, interpret=True, **kw)),
    ]:
        y, c, s = got
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry),
                                   atol=2e-4, err_msg=f"{name} y")
        np.testing.assert_allclose(np.asarray(c), np.asarray(rc),
                                   atol=1e-5, err_msg=f"{name} conv")
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   atol=2e-4, err_msg=f"{name} ssm")


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_initial_state_carry_resumes(backend):
    """Splitting a sequence in two fused calls, threading (conv, ssm)
    state through, reproduces the single whole-sequence call — the
    serve engines' carried-state ``prefill_chunk`` contract."""
    b, l, h, p, g, n, w = 1, 32, 4, 8, 2, 8, 4
    ops_in = _inputs(np.random.default_rng(3), b, l, h * p, h, g, n, w)
    kw = dict(ngroups=g, head_dim=p, chunk=16, silu=jax.nn.silu,
              softplus=jax.nn.softplus, **PUBLISHED)
    fn = (prefill_chunk.mamba2_prefill_xla if backend == "xla" else
          lambda **k: prefill_chunk.mamba2_prefill_pallas(interpret=True,
                                                          **k))
    y_all, c_all, s_all = fn(**ops_in, **kw)
    half = {k: (v[:, :16] if k in ("z", "xbc", "dt") else v)
            for k, v in ops_in.items()}
    y1, c1, s1 = fn(**half, **kw)
    half2 = dict(ops_in, z=ops_in["z"][:, 16:], xbc=ops_in["xbc"][:, 16:],
                 dt=ops_in["dt"][:, 16:], conv_state=c1, ssm_state=s1)
    y2, c2, s2 = fn(**half2, **kw)
    np.testing.assert_allclose(np.concatenate([y1, y2], axis=1),
                               np.asarray(y_all), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(c_all), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all), atol=1e-4)


@pytest.mark.parametrize("segments", [0, 16])
def test_actiba_tables_compose(segments):
    """The wrapper bakes ActiBA PWL activations into both backends; each
    must match the oracle evaluated with the same (exact or PWL)
    activation callables."""
    from repro.core import pwl
    b, l, dm, h, p, g, n, w = 1, 16, 24, 2, 8, 1, 4, 4
    di = h * p
    xa = XambaConfig.full(segments=segments) if segments else \
        XambaConfig.optimized()
    rng = np.random.default_rng(segments)
    ops_in = _inputs(rng, b, l, di, h, g, n, w)
    d_proj = 2 * di + 2 * g * n + h
    x = jnp.asarray(rng.normal(size=(b, l, dm)), jnp.float32)
    in_w = jnp.asarray(rng.normal(size=(dm, d_proj)) * 0.2, jnp.float32)
    zxbcdt = jnp.dot(x, in_w)
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
    okw = dict(ngroups=g, head_dim=p, silu=pwl.activation("silu", xa),
               softplus=pwl.activation("softplus", xa), **PUBLISHED)
    ops_ref = dict(ops_in, z=z, xbc=xbc, dt=dt)
    ry, rc, rs = ref.mamba2_prefill_ref(**ops_ref, **okw)
    common = {k: v for k, v in ops_in.items() if k not in ("z", "xbc", "dt")}
    for mode in ("cumba", "pallas_interpret"):
        y, c, s = ops.mamba2_prefill(x, in_w, **common, ngroups=g,
                                     head_dim=p, chunk=8, xamba=xa,
                                     mode=mode, **PUBLISHED)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-4,
                                   err_msg=f"{mode} segments={segments}")
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), atol=1e-4)


def test_w8_epilogue_on_quantized_in_proj():
    """A ``QuantTensor`` in-projection dispatches through the fused
    dequant path inside the pipeline; parity against the oracle fed the
    identical quantized projection output."""
    b, l, dm, h, p, g, n, w = 2, 16, 32, 2, 8, 1, 4, 4
    di = h * p
    rng = np.random.default_rng(9)
    ops_in = _inputs(rng, b, l, di, h, g, n, w)
    d_proj = 2 * di + 2 * g * n + h
    x = jnp.asarray(rng.normal(size=(b, l, dm)), jnp.float32)
    in_w = jnp.asarray(rng.normal(size=(dm, d_proj)) * 0.2, jnp.float32)
    qw = quant.quantize_tensor(in_w)
    zxbcdt = quant.qdot(x, qw)
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
    kw = dict(ngroups=g, head_dim=p, silu=jax.nn.silu,
              softplus=jax.nn.softplus, **PUBLISHED)
    ops_ref = dict(ops_in, z=z, xbc=xbc, dt=dt)
    ry, _, rs = ref.mamba2_prefill_ref(**ops_ref, **kw)
    common = {k: v for k, v in ops_in.items() if k not in ("z", "xbc", "dt")}
    for mode in ("cumba", "pallas_interpret"):
        y, _, s = ops.mamba2_prefill(x, qw, **common, ngroups=g, head_dim=p,
                                     chunk=8, mode=mode, **PUBLISHED)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-4,
                                   err_msg=mode)
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), atol=1e-4)


# ---------------------------------------------------------------------------
# model / engine level
# ---------------------------------------------------------------------------
CFG = ModelConfig(name="mamba2", family="mamba2", vocab_size=V, d_model=32,
                  n_layers=2, d_state=8, ssm_head_dim=8, chunk_size=8,
                  param_dtype="float32")


def _model_params(cfg):
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         jnp.float32)
    return model, params


@pytest.mark.parametrize("mode", ["cumba", "pallas_interpret"])
def test_fused_matches_naive_whole_sequence(mode):
    """Whole-sequence prefill under the fused backend: logits close to
    the unfused chain and greedy next-token identical (fp32)."""
    model_n, params = _model_params(CFG.with_prefill_mode("naive"))
    model_f, _ = _model_params(CFG.with_prefill_mode(mode))
    toks = jnp.asarray(np.random.default_rng(1).integers(1, V, (2, 16)),
                       jnp.int32)
    cache = model_n.init_cache(2, 0, jnp.float32)
    ln, _ = model_n.prefill(params, {"tokens": toks}, cache)
    cache = model_f.init_cache(2, 0, jnp.float32)
    lf, _ = model_f.prefill(params, {"tokens": toks}, cache)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ln), atol=1e-4)
    np.testing.assert_array_equal(np.argmax(np.asarray(lf), -1),
                                  np.argmax(np.asarray(ln), -1))


def test_fused_falls_back_on_odd_length(caplog):
    """A seqlen that is not a chunk multiple runs the unfused chain (the
    fused kernel consumes raw dt and the live conv tail, so padding is
    not an option) — with a logged one-line reason."""
    import logging
    model_n, params = _model_params(CFG.with_prefill_mode("naive"))
    model_f, _ = _model_params(CFG.with_prefill_mode("cumba"))
    toks = jnp.asarray(np.random.default_rng(2).integers(1, V, (1, 13)),
                       jnp.int32)
    cache = model_n.init_cache(1, 0, jnp.float32)
    ln, _ = model_n.prefill(params, {"tokens": toks}, cache)
    cache = model_f.init_cache(1, 0, jnp.float32)
    with caplog.at_level(logging.INFO, logger="repro.ssm"):
        lf, _ = model_f.prefill(params, {"tokens": toks}, cache)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ln), atol=1e-5)
    assert any("fused prefill" in r.message and "skipped" in r.message
               for r in caplog.records)


def test_engine_fused_chunked_matches_whole_greedy():
    """Continuous engine with chunked admission under the fused backend:
    outputs identical to the wave engine's monolithic prefill AND to the
    unfused chain, one compiled chunk program, one decode program."""
    prompts = [np.random.default_rng(5).integers(1, V, 16).tolist()
               for _ in range(4)]

    def run(cfg, engine_cls, **scfg_kw):
        model, params = _model_params(cfg)
        eng = engine_cls(model, params, ServeConfig(
            max_batch=2, prefill_buckets=(16,), max_new_tokens=6,
            **scfg_kw))
        for p in prompts:
            eng.submit(p)
        return {r.uid: r.out_tokens for r in eng.run()}, eng

    fused = CFG.with_prefill_mode("cumba")
    naive = CFG.with_prefill_mode("naive")
    whole_f, _ = run(fused, Engine)
    whole_n, _ = run(naive, Engine)
    chunk_f, eng = run(fused, ContinuousEngine, prefill_chunk=8)
    assert whole_f == whole_n          # fused backend: greedy-identical
    assert chunk_f == whole_f          # chunked == monolithic prefill
    assert eng.counters["prefill_chunk_compiles"] == 1
    assert eng.counters["decode_compiles"] == 1


def test_prefill_mode_validation():
    with pytest.raises(ValueError):
        dataclasses.replace(XambaConfig(), prefill="nope")
    assert CFG.with_prefill_mode("pallas").xamba.prefill == "pallas"
