"""SSM mixer blocks: Mamba-2 (SSD), Mamba-1 (selective scan), RG-LRU.

These are the layers the paper actually profiles.  Every sequential op the
NPU chokes on is mode-switched through XambaConfig:

* SSD's segsum/cumsum           -> CumBA        (``core/segsum.py``)
* SSD's einsum contractions     -> ReduBA       (``core/reduce.py``)
* SiLU gates / Softplus(dt)     -> ActiBA       (``core/pwl.py``)
* fused intra-chunk kernel      -> ``kernels/ssd_chunk.py`` (pallas modes)

Each mixer exposes (specs, apply, init_state); ``apply`` handles both
full-sequence (train/prefill) and single-token (decode) paths with the same
parameters — the paper's Step-1 two-model enablement.  Passing ``state``
with a multi-token ``x`` resumes mid-prompt: the conv tail and SSM/LRU
state thread through, so feeding a prompt in slices equals one
whole-sequence call — this is what the serve engines' chunked prefill
leans on (``models/base.py: DecodeAPI.prefill_chunk``).
"""
from __future__ import annotations

import logging
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pwl, selective_scan as sscan, ssd as ssd_mod
from repro.kernels.common import RG_LRU_C as _RG_C, gate_then_rmsnorm
from repro.nn import layers, quant
from repro.nn.params import ParamSpec

Array = jax.Array

log = logging.getLogger("repro.ssm")


# ============================================================================
# Mamba-2 mixer (SSD)
# ============================================================================

class Mamba2State(NamedTuple):
    conv: Array   # (b, d_conv-1, d_conv_dim)
    ssm: Array    # (b, nheads, headdim, d_state)


def mamba2_specs(cfg) -> dict:
    d = cfg.d_model
    d_inner = cfg.expand * d
    nheads = d_inner // cfg.ssm_head_dim
    g, n = cfg.ssm_ngroups, cfg.d_state
    d_xbc = d_inner + 2 * g * n
    d_in_proj = 2 * d_inner + 2 * g * n + nheads
    return {
        "in_proj": layers.linear_specs(d, d_in_proj, axes=("embed", "mlp")),
        "conv": layers.conv1d_specs(d_xbc, cfg.d_conv),
        "dt_bias": ParamSpec((nheads,), (None,), init="zeros"),
        "A_log": ParamSpec((nheads,), (None,), init="ones"),
        "D": ParamSpec((nheads,), (None,), init="ones"),
        "norm": layers.norm_specs(d_inner),
        "out_proj": layers.linear_specs(d_inner, d, axes=("mlp", "embed")),
    }


def mamba2_dims(cfg):
    d_inner = cfg.expand * cfg.d_model
    nheads = d_inner // cfg.ssm_head_dim
    return d_inner, nheads, cfg.ssm_ngroups, cfg.d_state


def mamba2_init_state(cfg, batch: int, dtype=jnp.float32) -> Mamba2State:
    d_inner, nheads, g, n = mamba2_dims(cfg)
    d_xbc = d_inner + 2 * g * n
    return Mamba2State(
        conv=jnp.zeros((batch, cfg.d_conv - 1, d_xbc), dtype),
        ssm=jnp.zeros((batch, nheads, cfg.ssm_head_dim, n), jnp.float32))


def mamba2_prefill_path(cfg, length: int) -> Tuple[str, Optional[str]]:
    """Which multi-token path ``mamba2_apply`` takes for ``length`` tokens:
    ``("fused-cumba" | "fused-pallas", None)`` for the fused pipeline
    (``kernels/prefill_chunk.py``), else ``("unfused", reason)``.  Decided
    at trace time from the config and the shape alone.  The fused pipeline
    takes RAW dt and the live conv tail, so it cannot hide ineligible
    shapes behind dt=0 padding the way ``core/ssd.py`` does: it requires
    exact chunking."""
    mode = cfg.xamba.prefill
    if mode == "naive":
        return "unfused", "prefill mode naive"
    chunk = min(cfg.chunk_size, length)
    if cfg.ssd_dtype != "float32":
        return "unfused", (f"ssd_dtype={cfg.ssd_dtype} (fused prefill is "
                           "fp32-only)")
    if length % chunk:
        return "unfused", f"seqlen {length} not a multiple of chunk {chunk}"
    if mode == "pallas" and chunk % 64:
        return "unfused", f"chunk {chunk} not a multiple of 64 (MXU tiling)"
    return ("fused-cumba" if mode == "cumba" else "fused-pallas"), None


def _mamba2_decode_naive(params: dict, cfg, x: Array, state: Mamba2State
                         ) -> Tuple[Array, Mamba2State]:
    """The unfused dense step (the pre-refactor / NPU-baseline op chain):
    seq-axis (b, 1, d) operands end to end, per-tap conv slices, and the
    state contraction as broadcast-multiply + ReduceSum."""
    b, l, d = x.shape
    d_inner, nheads, g, n = mamba2_dims(cfg)
    p_hd = cfg.ssm_head_dim
    xamba = cfg.xamba
    silu = pwl.activation("silu", xamba)
    softplus = pwl.activation("softplus", xamba)

    zxbcdt = layers.linear(params["in_proj"], x)
    z, xbc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    xbc_conv, new_conv = layers.causal_conv1d(params["conv"], xbc,
                                              state.conv)
    xbc_conv = silu(xbc_conv)
    xs, B, C = jnp.split(xbc_conv, [d_inner, d_inner + g * n], axis=-1)
    xs = xs.reshape(b, l, nheads, p_hd)
    dt = softplus(dt.astype(jnp.float32) +
                  params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    new_ssm, y = ssd_mod.ssd_decode_step(
        state.ssm, xs[:, 0], dt[:, 0], A, B.reshape(b, g, n),
        C.reshape(b, g, n), mode="naive")
    y = y[:, None] + xs * params["D"].astype(x.dtype)[None, None, :, None]
    y = gate_then_rmsnorm(y.reshape(b, l, d_inner), z, params["norm"]["scale"],
                          eps=cfg.norm_eps, ngroups=g, silu=silu)
    out = layers.linear(params["out_proj"], y.astype(x.dtype))
    return out, Mamba2State(new_conv, new_ssm)


def _mamba2_decode(params: dict, cfg, x: Array, state: Mamba2State
                   ) -> Tuple[Array, Mamba2State]:
    """Fused single-token step, dispatched on ``XambaConfig.decode``."""
    b = x.shape[0]
    d_inner, nheads, g, n = mamba2_dims(cfg)
    p_hd = cfg.ssm_head_dim
    xamba = cfg.xamba
    mode = xamba.decode
    if mode == "naive":
        return _mamba2_decode_naive(params, cfg, x, state)

    # Token-major 2D layout throughout: (b, 1, d) batched matmuls hit a
    # slow XLA-CPU gemm path; the whole step runs on (b, d) operands.
    zxbcdt = layers.linear(params["in_proj"], x[:, 0])       # (b, d_in_proj)
    z, xbc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))        # (nheads,)

    if mode in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        y, new_conv, new_ssm = kops.mamba2_decode_step(
            z, xbc, dt, state.conv, state.ssm,
            params["conv"]["w"], params["conv"]["b"], params["dt_bias"],
            A, params["D"], params["norm"]["scale"],
            ngroups=g, head_dim=p_hd, eps=cfg.norm_eps, xamba=xamba,
            interpret=(mode == "pallas_interpret"))
    else:
        silu = pwl.activation("silu", xamba)
        softplus = pwl.activation("softplus", xamba)
        xbc_conv, new_conv = layers.causal_conv1d_step(
            params["conv"], xbc, state.conv)
        xbc_conv = silu(xbc_conv)
        xs, B, C = jnp.split(xbc_conv, [d_inner, d_inner + g * n], axis=-1)
        xs = xs.reshape(b, nheads, p_hd)
        dt_f = softplus(dt.astype(jnp.float32) +
                        params["dt_bias"].astype(jnp.float32))
        new_ssm, y = ssd_mod.ssd_decode_step(
            state.ssm, xs, dt_f, A, B.reshape(b, g, n), C.reshape(b, g, n),
            mode=mode)
        y = y + xs * params["D"].astype(x.dtype)[None, :, None]
        y = gate_then_rmsnorm(y.reshape(b, d_inner), z,
                              params["norm"]["scale"], eps=cfg.norm_eps,
                              ngroups=g, silu=silu)
    out = layers.linear(params["out_proj"], y.astype(x.dtype))[:, None]
    return out, Mamba2State(new_conv, new_ssm)


def mamba2_apply(params: dict, cfg, x: Array,
                 state: Optional[Mamba2State] = None,
                 ) -> Tuple[Array, Optional[Mamba2State]]:
    """x: (b, l, d). l==1 + state -> decode step; else full sequence."""
    b, l, d = x.shape
    d_inner, nheads, g, n = mamba2_dims(cfg)
    p_hd = cfg.ssm_head_dim
    xamba = cfg.xamba

    if state is not None and l == 1 and not cfg.force_prefill_path:
        return _mamba2_decode(params, cfg, x, state)

    pf_mode = xamba.prefill
    path, reason = mamba2_prefill_path(cfg, l)
    if path != "unfused":
        from repro.kernels import ops as kops
        if state is not None:
            conv_state, init = state.conv, state.ssm
        else:
            d_xbc = d_inner + 2 * g * n
            conv_state = jnp.zeros((b, cfg.d_conv - 1, d_xbc), x.dtype)
            init = jnp.zeros((b, nheads, p_hd, n), jnp.float32)
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        y, new_conv, new_ssm = kops.mamba2_prefill(
            x, params["in_proj"]["w"], conv_state, init,
            params["conv"]["w"], params["conv"]["b"],
            params["dt_bias"], A, params["D"], params["norm"]["scale"],
            ngroups=g, head_dim=p_hd, chunk=min(cfg.chunk_size, l),
            eps=cfg.norm_eps, xamba=xamba, mode=pf_mode)
        out = layers.linear(params["out_proj"], y.astype(x.dtype))
        new_state = (Mamba2State(new_conv, new_ssm)
                     if state is not None else None)
        return out, new_state
    if pf_mode != "naive":
        # Fires once per compiled shape (trace-time), not per call.
        log.warning("fused prefill (%s) skipped: %s — running the unfused "
                    "chain", pf_mode, reason)

    silu = pwl.activation("silu", xamba)
    softplus = pwl.activation("softplus", xamba)

    zxbcdt = layers.linear(params["in_proj"], x)
    z, xbc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)

    conv_state = state.conv if state is not None else None
    xbc_conv, new_conv = layers.causal_conv1d(params["conv"], xbc, conv_state)
    xbc_conv = silu(xbc_conv)
    xs, B, C = jnp.split(xbc_conv, [d_inner, d_inner + g * n], axis=-1)
    xs = xs.reshape(b, l, nheads, p_hd)
    B = B.reshape(b, l, g, n)
    C = C.reshape(b, l, g, n)
    dt = softplus(dt.astype(jnp.float32) +
                  params["dt_bias"].astype(jnp.float32))     # (b, l, nheads)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))        # (nheads,)

    init = state.ssm if state is not None else None
    mm_dtype = jnp.bfloat16 if cfg.ssd_dtype == "bfloat16" else None
    y, new_ssm = ssd_mod.ssd(
        xs, dt, A, B, C, chunk_size=min(cfg.chunk_size, l),
        initial_state=init, xamba=xamba, return_final_state=True,
        matmul_dtype=mm_dtype)

    y = y + xs * params["D"].astype(x.dtype)[None, None, :, None]
    y = gate_then_rmsnorm(y.reshape(b, l, d_inner), z, params["norm"]["scale"],
                          eps=cfg.norm_eps, ngroups=g, silu=silu)
    out = layers.linear(params["out_proj"], y.astype(x.dtype))
    new_state = Mamba2State(new_conv, new_ssm) if state is not None else None
    return out, new_state


# ============================================================================
# Mamba-1 mixer (selective scan)
# ============================================================================

class Mamba1State(NamedTuple):
    conv: Array  # (b, d_conv-1, d_inner)
    ssm: Array   # (b, d_inner, d_state)


def mamba1_specs(cfg) -> dict:
    d = cfg.d_model
    d_inner = cfg.expand * d
    n = cfg.d_state
    dt_rank = cfg.dt_rank or math.ceil(d / 16)
    return {
        "in_proj": layers.linear_specs(d, 2 * d_inner, axes=("embed", "mlp")),
        "conv": layers.conv1d_specs(d_inner, cfg.d_conv),
        "x_proj": layers.linear_specs(d_inner, dt_rank + 2 * n,
                                      axes=("mlp", None)),
        "dt_proj": {
            "w": ParamSpec((dt_rank, d_inner), (None, "mlp"), scale=0.1),
            "b": ParamSpec((d_inner,), ("mlp",), init="small_normal"),
        },
        "A_log": ParamSpec((d_inner, n), ("mlp", None), init="ones"),
        "D": ParamSpec((d_inner,), ("mlp",), init="ones"),
        "out_proj": layers.linear_specs(d_inner, d, axes=("mlp", "embed")),
    }


def mamba1_init_state(cfg, batch: int, dtype=jnp.float32) -> Mamba1State:
    d_inner = cfg.expand * cfg.d_model
    return Mamba1State(
        conv=jnp.zeros((batch, cfg.d_conv - 1, d_inner), dtype),
        ssm=jnp.zeros((batch, d_inner, cfg.d_state), jnp.float32))


def _mamba1_decode_naive(params: dict, cfg, x: Array, state: Mamba1State
                         ) -> Tuple[Array, Mamba1State]:
    """The unfused dense step (pre-refactor / NPU-baseline op chain)."""
    b, l, d = x.shape
    n = cfg.d_state
    dt_rank = cfg.dt_rank or math.ceil(d / 16)
    xamba = cfg.xamba
    silu = pwl.activation("silu", xamba)
    softplus = pwl.activation("softplus", xamba)

    xz = layers.linear(params["in_proj"], x)
    xs, z = jnp.split(xz, 2, axis=-1)
    xs, new_conv = layers.causal_conv1d(params["conv"], xs, state.conv)
    xs = silu(xs)
    dbc = layers.linear(params["x_proj"], xs)
    dt, B, C = jnp.split(dbc, [dt_rank, dt_rank + n], axis=-1)
    dt = jnp.dot(dt, params["dt_proj"]["w"].astype(dt.dtype)) + \
        params["dt_proj"]["b"].astype(dt.dtype)
    dt = softplus(dt.astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    new_ssm, y = sscan.selective_scan_decode_step(
        state.ssm, xs[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], params["D"],
        mode="naive")
    y = y[:, None] * silu(z)
    out = layers.linear(params["out_proj"], y.astype(x.dtype))
    return out, Mamba1State(new_conv, new_ssm)


def _mamba1_decode(params: dict, cfg, x: Array, state: Mamba1State
                   ) -> Tuple[Array, Mamba1State]:
    """Fused single-token step, dispatched on ``XambaConfig.decode``."""
    n = cfg.d_state
    dt_rank = cfg.dt_rank or math.ceil(x.shape[-1] / 16)
    xamba = cfg.xamba
    mode = xamba.decode
    if mode == "naive":
        return _mamba1_decode_naive(params, cfg, x, state)

    xz = layers.linear(params["in_proj"], x[:, 0])           # (b, 2*d_inner)
    xs_raw, z = jnp.split(xz, 2, axis=-1)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))        # (d_inner, n)

    if mode in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        y, new_conv, new_ssm = kops.mamba1_decode_step(
            xs_raw, z, state.conv, state.ssm,
            params["conv"]["w"], params["conv"]["b"],
            params["x_proj"]["w"], params["dt_proj"]["w"],
            params["dt_proj"]["b"], A, params["D"],
            dt_rank=dt_rank, xamba=xamba,
            interpret=(mode == "pallas_interpret"))
    else:
        silu = pwl.activation("silu", xamba)
        softplus = pwl.activation("softplus", xamba)
        xs, new_conv = layers.causal_conv1d_step(
            params["conv"], xs_raw, state.conv)
        xs = silu(xs)
        dbc = layers.linear(params["x_proj"], xs)
        dt, B, C = jnp.split(dbc, [dt_rank, dt_rank + n], axis=-1)
        dt = jnp.dot(dt, params["dt_proj"]["w"].astype(dt.dtype)) + \
            params["dt_proj"]["b"].astype(dt.dtype)
        dt = softplus(dt.astype(jnp.float32))                # (b, d_inner)
        new_ssm, y = sscan.selective_scan_decode_step(
            state.ssm, xs, dt, A, B, C, params["D"], mode=mode)
        y = y * silu(z)
    out = layers.linear(params["out_proj"], y.astype(x.dtype))[:, None]
    return out, Mamba1State(new_conv, new_ssm)


def mamba1_apply(params: dict, cfg, x: Array,
                 state: Optional[Mamba1State] = None,
                 ) -> Tuple[Array, Optional[Mamba1State]]:
    b, l, d = x.shape
    d_inner = cfg.expand * d
    n = cfg.d_state
    dt_rank = cfg.dt_rank or math.ceil(d / 16)
    xamba = cfg.xamba

    if state is not None and l == 1 and not cfg.force_prefill_path:
        return _mamba1_decode(params, cfg, x, state)

    silu = pwl.activation("silu", xamba)
    softplus = pwl.activation("softplus", xamba)

    xz = layers.linear(params["in_proj"], x)
    xs, z = jnp.split(xz, 2, axis=-1)

    conv_state = state.conv if state is not None else None
    xs, new_conv = layers.causal_conv1d(params["conv"], xs, conv_state)
    xs = silu(xs)

    dbc = layers.linear(params["x_proj"], xs)
    dt, B, C = jnp.split(dbc, [dt_rank, dt_rank + n], axis=-1)
    dt = jnp.dot(dt, params["dt_proj"]["w"].astype(dt.dtype)) + \
        params["dt_proj"]["b"].astype(dt.dtype)
    dt = softplus(dt.astype(jnp.float32))                    # (b, l, d_inner)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))        # (d_inner, n)
    D = params["D"]

    init = state.ssm if state is not None else None
    y, new_ssm = sscan.selective_scan(
        xs, dt, A, B, C, D, mode=cfg.scan_mode, initial_state=init,
        xamba=xamba, return_final_state=True)

    y = y * silu(z)
    out = layers.linear(params["out_proj"], y.astype(x.dtype))
    new_state = Mamba1State(new_conv, new_ssm) if state is not None else None
    return out, new_state


# ============================================================================
# RG-LRU recurrent block (recurrentgemma / Griffin)
# ============================================================================

class RGLRUState(NamedTuple):
    conv: Array  # (b, d_conv-1, lru_width)
    h: Array     # (b, lru_width)


def rglru_specs(cfg) -> dict:
    d, w = cfg.d_model, cfg.lru_width
    return {
        "in_x": layers.linear_specs(d, w, axes=("embed", "mlp")),
        "in_gate": layers.linear_specs(d, w, axes=("embed", "mlp")),
        "conv": layers.conv1d_specs(w, cfg.d_conv),
        "rg": layers.linear_specs(w, w, axes=("mlp", "mlp2"), bias=True),
        "ig": layers.linear_specs(w, w, axes=("mlp", "mlp2"), bias=True),
        "lam": ParamSpec((w,), ("mlp",), init="ones", scale=1.0),
        "out": layers.linear_specs(w, d, axes=("mlp", "embed")),
    }


def rglru_init_state(cfg, batch: int, dtype=jnp.float32) -> RGLRUState:
    return RGLRUState(
        conv=jnp.zeros((batch, cfg.d_conv - 1, cfg.lru_width), dtype),
        h=jnp.zeros((batch, cfg.lru_width), jnp.float32))


def _rglru_decode_naive(params: dict, cfg, x: Array, state: RGLRUState
                        ) -> Tuple[Array, RGLRUState]:
    """The unfused dense step (pre-refactor / NPU-baseline op chain)."""
    xamba = cfg.xamba
    sigmoid = pwl.activation("sigmoid", xamba)
    softplus = pwl.activation("softplus", xamba)
    gelu = pwl.activation("gelu", xamba)

    u = layers.linear(params["in_x"], x)                     # (b, 1, w)
    gate = layers.linear(params["in_gate"], x)
    u, new_conv = layers.causal_conv1d(params["conv"], u, state.conv)
    r = sigmoid(layers.linear(params["rg"], u).astype(jnp.float32))
    i = sigmoid(layers.linear(params["ig"], u).astype(jnp.float32))
    log_a = -_RG_C * softplus(params["lam"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * \
        (i * u.astype(jnp.float32))
    h_new = a[:, 0] * state.h + gated_in[:, 0]
    y = h_new[:, None].astype(x.dtype) * gelu(gate)
    out = layers.linear(params["out"], y)
    return out, RGLRUState(new_conv, h_new)


def _rglru_decode(params: dict, cfg, x: Array, state: RGLRUState
                  ) -> Tuple[Array, RGLRUState]:
    """Fused single-token step, dispatched on ``XambaConfig.decode``."""
    xamba = cfg.xamba
    mode = xamba.decode
    if mode == "naive":
        return _rglru_decode_naive(params, cfg, x, state)

    u = layers.linear(params["in_x"], x[:, 0])               # (b, w)
    gate = layers.linear(params["in_gate"], x[:, 0])

    if mode in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        # The fused step kernel takes raw fp gate weights; under W8 the
        # rg/ig projections dequantize in-program here, which
        # MATERIALIZES an fp32 copy per step (pallas_call operands are
        # concrete) — correctness-first: this path keeps the storage win
        # but not the bandwidth win until the kernel ingests int8+scale
        # tiles like kernels/qmatmul.py does.
        y, new_conv, h_new = kops.rglru_decode_step(
            u, gate, state.conv, state.h,
            params["conv"]["w"], params["conv"]["b"],
            quant.maybe_dequant(params["rg"]["w"]), params["rg"]["b"],
            quant.maybe_dequant(params["ig"]["w"]), params["ig"]["b"],
            params["lam"],
            xamba=xamba, interpret=(mode == "pallas_interpret"))
        y = y.astype(x.dtype)
    else:
        sigmoid = pwl.activation("sigmoid", xamba)
        softplus = pwl.activation("softplus", xamba)
        gelu = pwl.activation("gelu", xamba)
        u, new_conv = layers.causal_conv1d_step(params["conv"], u, state.conv)
        r = sigmoid(layers.linear(params["rg"], u).astype(jnp.float32))
        i = sigmoid(layers.linear(params["ig"], u).astype(jnp.float32))
        log_a = -_RG_C * softplus(params["lam"].astype(jnp.float32)) * r
        a = jnp.exp(log_a)
        gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
            * (i * u.astype(jnp.float32))
        h_new = a * state.h + gated_in
        y = h_new.astype(x.dtype) * gelu(gate)
    out = layers.linear(params["out"], y)[:, None]
    return out, RGLRUState(new_conv, h_new)


def rglru_apply(params: dict, cfg, x: Array,
                state: Optional[RGLRUState] = None,
                ) -> Tuple[Array, Optional[RGLRUState]]:
    b, l, d = x.shape
    xamba = cfg.xamba

    if state is not None and l == 1 and not cfg.force_prefill_path:
        return _rglru_decode(params, cfg, x, state)

    sigmoid = pwl.activation("sigmoid", xamba)
    softplus = pwl.activation("softplus", xamba)
    gelu = pwl.activation("gelu", xamba)

    u = layers.linear(params["in_x"], x)                     # (b, l, w)
    gate = layers.linear(params["in_gate"], x)

    conv_state = state.conv if state is not None else None
    u, new_conv = layers.causal_conv1d(params["conv"], u, conv_state)

    r = sigmoid(layers.linear(params["rg"], u).astype(jnp.float32))
    i = sigmoid(layers.linear(params["ig"], u).astype(jnp.float32))
    log_a = -_RG_C * softplus(params["lam"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * \
        (i * u.astype(jnp.float32))

    if xamba.cumba in ("pallas", "pallas_interpret") and state is None:
        from repro.kernels import ops as kops
        h = kops.rg_lru_scan(
            a, gated_in, interpret=(xamba.cumba == "pallas_interpret"))
    else:
        def comb(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, b1 * a2 + b2
        a_sc, h_sc = jax.lax.associative_scan(comb, (a, gated_in), axis=1)
        h0 = state.h if state is not None else jnp.zeros(
            (b, cfg.lru_width), jnp.float32)
        h = h_sc + a_sc * h0[:, None]
    h_new = h[:, -1]

    y = h.astype(x.dtype) * gelu(gate)
    out = layers.linear(params["out"], y)
    new_state = RGLRUState(new_conv, h_new) if state is not None else None
    return out, new_state
