"""Fused decode-step subsystem: kernel/reference/full-sequence parity
across every XambaConfig decode mode, the pre-sliced decode view, the
grouped recurrentgemma cache layout, donation compile-once, and the
deprecated ``apply`` shim.

``pallas`` (compiled) needs a TPU; ``pallas_interpret`` runs the same
kernel bodies on CPU and is what CI exercises.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import selective_scan as sscan, ssd as ssd_mod
from repro.core.xamba import XambaConfig
from repro.kernels import ops as kops, ref
from repro.models import ModelConfig, build_model
from repro.nn.params import init_params, restack_layers
from repro.serve import ContinuousEngine, ServeConfig

V = 64
MODES = ("naive", "cumba", "pallas_interpret")

CFGS = {
    "mamba2": ModelConfig(name="mamba2", family="mamba2", vocab_size=V,
                          d_model=32, n_layers=2, d_state=8, ssm_head_dim=8,
                          chunk_size=8, param_dtype="float32"),
    "mamba1": ModelConfig(name="mamba1", family="mamba", vocab_size=V,
                          d_model=32, n_layers=2, d_state=8,
                          param_dtype="float32"),
    "rglru": ModelConfig(name="rglru", family="recurrentgemma", vocab_size=V,
                         d_model=32, n_layers=3, n_heads=4, n_kv_heads=1,
                         head_dim=8, d_ff=96, mlp_type="geglu", lru_width=32,
                         sliding_window=8, scan_layers=True,
                         param_dtype="float32"),
}


def _with_mode(cfg, mode, **xkw):
    return dataclasses.replace(cfg, xamba=XambaConfig(decode=mode, **xkw))


def _params(cfg, seed=0):
    return init_params(build_model(cfg).param_specs(),
                       jax.random.PRNGKey(seed), jnp.float32)


# ---------------------------------------------------------------------------
# core-level decode steps: every mode ties the oracle at <= 1e-5
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", MODES)
def test_ssd_decode_step_modes_tie_reference(mode):
    rng = np.random.default_rng(0)
    b, h, p, n, g = 3, 4, 8, 16, 2
    state = jnp.asarray(rng.normal(size=(b, h, p, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, size=(b, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.1, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, g, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, g, n)), jnp.float32)
    ns, y = ssd_mod.ssd_decode_step(state, x, dt, A, B, C, mode=mode)
    ns_r, y_r = ref.ssd_step_ref(state, x, dt, A, B, C)
    assert float(jnp.abs(ns - ns_r).max()) <= 1e-5
    assert float(jnp.abs(y - y_r).max()) <= 1e-5


@pytest.mark.parametrize("mode", MODES)
def test_selective_scan_decode_step_modes_tie_reference(mode):
    rng = np.random.default_rng(1)
    b, d, n = 3, 12, 8
    state = jnp.asarray(rng.normal(size=(b, d, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, size=(b, d)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.1, 2.0, size=(d, n)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    ns, y = sscan.selective_scan_decode_step(state, u, dt, A, B, C, D,
                                             mode=mode)
    ns_r, y_r = ref.sscan_step_ref(state, u, dt, A, B, C, D)
    assert float(jnp.abs(ns - ns_r).max()) <= 1e-5
    assert float(jnp.abs(y - y_r).max()) <= 1e-5


# ---------------------------------------------------------------------------
# fused mixer kernels: pallas_interpret ties the jnp oracle at <= 1e-5
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("actiba", [False, True], ids=["exact", "actiba"])
def test_mamba2_fused_kernel_ties_reference(actiba):
    """The gated norm as published: ``norm(y * silu(z))`` at epsilon
    1e-5, by groups of d_inner / ngroups."""
    rng = np.random.default_rng(2)
    xamba = XambaConfig(decode="pallas_interpret", actiba=actiba)
    b, h, p, n, g, w = 2, 4, 8, 16, 2, 4
    di = h * p
    dxbc = di + 2 * g * n
    f = jnp.float32
    args = (jnp.asarray(rng.normal(size=(b, di)), f),
            jnp.asarray(rng.normal(size=(b, dxbc)), f),
            jnp.asarray(rng.normal(size=(b, h)), f),
            jnp.asarray(rng.normal(size=(b, w - 1, dxbc)), f),
            jnp.asarray(rng.normal(size=(b, h, p, n)), f),
            jnp.asarray(rng.normal(size=(w, dxbc)) * 0.3, f),
            jnp.asarray(rng.normal(size=(dxbc,)) * 0.1, f),
            jnp.asarray(rng.normal(size=(h,)) * 0.1, f),
            -jnp.asarray(rng.uniform(0.1, 2.0, size=(h,)), f),
            jnp.asarray(rng.normal(size=(h,)), f),
            jnp.asarray(rng.normal(size=(di,)), f))
    got = kops.mamba2_decode_step(*args, ngroups=g, head_dim=p, xamba=xamba,
                                  interpret=True, eps=1e-5)
    from repro.core import pwl
    want = ref.mamba2_step_ref(*args, ngroups=g, head_dim=p, eps=1e-5,
                               silu=pwl.activation("silu", xamba),
                               softplus=pwl.activation("softplus", xamba))
    for a, r in zip(got, want):
        assert float(jnp.abs(a - r).max()) <= 1e-5


def test_mamba1_fused_kernel_ties_reference():
    rng = np.random.default_rng(3)
    b, d, n, w, r_ = 2, 12, 8, 4, 6
    f = jnp.float32
    args = (jnp.asarray(rng.normal(size=(b, d)), f),
            jnp.asarray(rng.normal(size=(b, d)), f),
            jnp.asarray(rng.normal(size=(b, w - 1, d)), f),
            jnp.asarray(rng.normal(size=(b, d, n)), f),
            jnp.asarray(rng.normal(size=(w, d)) * 0.3, f),
            jnp.asarray(rng.normal(size=(d,)) * 0.1, f),
            jnp.asarray(rng.normal(size=(d, r_ + 2 * n)) * 0.2, f),
            jnp.asarray(rng.normal(size=(r_, d)) * 0.2, f),
            jnp.asarray(rng.normal(size=(d,)) * 0.1, f),
            -jnp.asarray(rng.uniform(0.1, 2.0, size=(d, n)), f),
            jnp.asarray(rng.normal(size=(d,)), f))
    got = kops.mamba1_decode_step(*args, dt_rank=r_, interpret=True)
    want = ref.mamba1_step_ref(*args, dt_rank=r_)
    for a, r in zip(got, want):
        assert float(jnp.abs(a - r).max()) <= 1e-5


def test_rglru_fused_kernel_ties_reference():
    rng = np.random.default_rng(4)
    b, wd, wc = 2, 16, 4
    f = jnp.float32
    args = (jnp.asarray(rng.normal(size=(b, wd)), f),
            jnp.asarray(rng.normal(size=(b, wd)), f),
            jnp.asarray(rng.normal(size=(b, wc - 1, wd)), f),
            jnp.asarray(rng.normal(size=(b, wd)), f),
            jnp.asarray(rng.normal(size=(wc, wd)) * 0.3, f),
            jnp.asarray(rng.normal(size=(wd,)) * 0.1, f),
            jnp.asarray(rng.normal(size=(wd, wd)) * 0.2, f),
            jnp.asarray(rng.normal(size=(wd,)) * 0.1, f),
            jnp.asarray(rng.normal(size=(wd, wd)) * 0.2, f),
            jnp.asarray(rng.normal(size=(wd,)) * 0.1, f),
            jnp.asarray(rng.uniform(0.5, 2.0, size=(wd,)), f))
    got = kops.rglru_decode_step(*args, interpret=True)
    want = ref.rglru_step_ref(*args)
    for a, r in zip(got, want):
        assert float(jnp.abs(a - r).max()) <= 1e-5


# ---------------------------------------------------------------------------
# model-level: fused decode == reference step == force_prefill_path slice
# ---------------------------------------------------------------------------
def _full_logits(cfg, params, tokens):
    model = build_model(cfg)
    if cfg.family in ("mamba", "mamba2"):
        return model.forward(params, tokens)
    x = model._embed(params, tokens)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    h, _ = model._trunk(params, x, positions)
    return model._logits(params, h)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("family", ["mamba2", "mamba1", "rglru"])
def test_decode_modes_match_full_forward(family, mode):
    cfg = _with_mode(CFGS[family], mode)
    model = build_model(cfg)
    params = _params(CFGS[family])
    S, P = 16, 12
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S), 0, V)
    full = _full_logits(CFGS[family], params, tokens)

    cache = model.init_cache(2, S, jnp.float32)
    logits, cache = model.prefill(params, {"tokens": tokens[:, :P]}, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, P - 1]),
                               rtol=5e-4, atol=5e-4)
    for t in range(P, S):
        logits, cache = model.decode_step(params, tokens[:, t:t + 1], cache,
                                          jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, t]),
            rtol=5e-4, atol=5e-4, err_msg=f"{family}/{mode} t={t}")


@pytest.mark.parametrize("family", ["mamba2", "mamba1"])
def test_decode_matches_force_prefill_path_slice(family):
    """The O(1) fused step == re-running the full-sequence (chunked) form
    one token longer — the paper's two-model equivalence."""
    cfg = _with_mode(CFGS[family], "cumba")
    model = build_model(cfg)
    fp = build_model(dataclasses.replace(cfg, force_prefill_path=True))
    params = _params(CFGS[family])
    S, P = 14, 10
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, S), 0, V)

    cache = model.init_cache(2, S, jnp.float32)
    _, cache = model.prefill(params, {"tokens": tokens[:, :P]}, cache)
    cache_fp = fp.init_cache(2, S, jnp.float32)
    _, cache_fp = fp.prefill(params, {"tokens": tokens[:, :P]}, cache_fp)
    for t in range(P, S):
        tok = tokens[:, t:t + 1]
        logits, cache = model.decode_step(params, tok, cache, jnp.int32(t))
        logits_fp, cache_fp = fp.decode_step(params, tok, cache_fp,
                                             jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_fp),
                                   rtol=5e-4, atol=5e-4, err_msg=f"t={t}")


# ---------------------------------------------------------------------------
# decode view / stacked layouts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", ["mamba2", "mamba1"])
def test_decode_view_matches_stacked(family):
    cfg = _with_mode(CFGS[family], "cumba")
    model = build_model(cfg)
    params = _params(CFGS[family])
    view = model.decode_view(params)
    assert isinstance(view["layers"], tuple)
    # idempotent
    assert model.decode_view(view) is view or \
        isinstance(model.decode_view(view)["layers"], tuple)

    cache = model.init_cache(2, 16, jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, V)
    _, cache = model.prefill(params, {"tokens": tokens}, cache)
    tok = tokens[:, :1]
    l_stacked, c_stacked = model.decode_step(params, tok, cache, jnp.int32(8))
    l_view, c_view = model.decode_step(view, tok, cache, jnp.int32(8))
    np.testing.assert_allclose(np.asarray(l_stacked), np.asarray(l_view),
                               rtol=1e-5, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
        c_stacked, c_view)


def test_rglru_grouped_scan_matches_per_layer_loop():
    cfg = CFGS["rglru"]
    model = build_model(cfg)                                   # grouped
    loop = build_model(dataclasses.replace(cfg, scan_layers=False))
    params = _params(cfg)
    S, P = 14, 10
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, S), 0, V)

    cache_g = model.init_cache(2, S, jnp.float32)
    assert isinstance(cache_g, dict) and "groups" in cache_g
    cache_l = loop.init_cache(2, S, jnp.float32)
    lg, cache_g = model.prefill(params, {"tokens": tokens[:, :P]}, cache_g)
    ll, cache_l = loop.prefill(params, {"tokens": tokens[:, :P]}, cache_l)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ll),
                               rtol=1e-5, atol=1e-5)
    for t in range(P, S):
        tok = tokens[:, t:t + 1]
        lg, cache_g = model.decode_step(params, tok, cache_g, jnp.int32(t))
        ll, cache_l = loop.decode_step(params, tok, cache_l, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ll),
                                   rtol=1e-5, atol=1e-5, err_msg=f"t={t}")


def test_restack_layers_matches_per_layer_params():
    loop_cfg = dataclasses.replace(CFGS["mamba2"], scan_layers=False)
    loop = build_model(loop_cfg)
    params = _params(loop_cfg)
    stacked = build_model(CFGS["mamba2"])
    sparams = dict(params, layers=restack_layers(params["layers"]))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0, V)
    np.testing.assert_allclose(
        np.asarray(loop.forward(params, tokens)),
        np.asarray(stacked.forward(sparams, tokens)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# serving: greedy identity through the continuous engine + compile-once
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["cumba", "pallas_interpret"])
def test_continuous_engine_greedy_identity_fused(mode):
    """The donated, pooled, slot-scheduled engine emits exactly the tokens
    of a manual prefill + decode_step loop in the same decode mode."""
    cfg = _with_mode(CFGS["mamba2"], mode)
    model = build_model(cfg)
    params = _params(CFGS["mamba2"])
    prompts = [list(range(1, 9)), list(range(9, 17))]
    max_new = 4

    eng = ContinuousEngine(model, params, ServeConfig(
        max_batch=2, prefill_buckets=(8,), max_new_tokens=max_new))
    for p in prompts:
        eng.submit(p)
    done = {r.uid: r.out_tokens for r in eng.run()}
    assert eng.counters["decode_compiles"] in (1, "unavailable")

    cache = model.init_cache(2, 8 + max_new, jnp.float32)
    toks = jnp.asarray(prompts, jnp.int32)
    logits, cache = model.prefill(params, {"tokens": toks}, cache)
    cur = jnp.argmax(logits, -1)
    outs = [[int(c)] for c in cur]
    for t in range(1, max_new):
        logits, cache = model.decode_step(params, cur[:, None], cache,
                                          jnp.int32(8 + t - 1))
        cur = jnp.argmax(logits, -1)
        for i in range(2):
            outs[i].append(int(cur[i]))
    for uid, manual in zip(sorted(done), outs):
        assert done[uid] == manual, f"uid={uid} mode={mode}"


def test_donated_decode_compiles_once_across_turnover():
    """Slot turnover + donation: the decode program still compiles exactly
    once and the pool arena survives being donated every step."""
    cfg = _with_mode(CFGS["mamba2"], "cumba")
    model = build_model(cfg)
    params = _params(CFGS["mamba2"])
    rng = np.random.default_rng(11)
    eng = ContinuousEngine(model, params, ServeConfig(
        max_batch=2, prefill_buckets=(8, 16), max_new_tokens=3))
    for n in (6, 14, 7, 13, 5):
        eng.submit(rng.integers(1, V, n).tolist())
    done = eng.run()
    assert len(done) == 5 and all(len(r.out_tokens) == 3 for r in done)
    assert eng.counters["decode_compiles"] in (1, "unavailable")
    assert eng.counters["prefill_compiles"] in (2, "unavailable")


# ---------------------------------------------------------------------------
# deprecated apply() shim
# ---------------------------------------------------------------------------
def test_apply_shim_dispatches_and_warns():
    cfg = CFGS["mamba2"]
    model = build_model(cfg)
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 8), 0, V)
    cache = model.init_cache(2, 12, jnp.float32)

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        logits, cache2 = model.apply(params, tokens, state=cache)
        assert any(issubclass(x.category, DeprecationWarning) for x in w)
    want, _ = model.prefill(params, {"tokens": tokens}, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-6, atol=1e-6)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        l2, _ = model.apply(params, tokens[:, :1], state=cache2,
                            index=jnp.int32(8))
        want2, _ = model.decode_step(params, tokens[:, :1], cache2,
                                     jnp.int32(8))
        # single-token dispatch without a position is an error, not a
        # silent position-0 KV write
        with pytest.raises(TypeError):
            model.apply(params, tokens[:, :1], state=cache2)
    np.testing.assert_allclose(np.asarray(l2), np.asarray(want2),
                               rtol=1e-6, atol=1e-6)
