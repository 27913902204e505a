"""Public jit'd wrappers for the XAMBA Pallas kernels.

Every op dispatches between the Pallas kernel (TPU target; ``interpret=True``
runs the same kernel body on CPU for validation) and the XLA reference.  The
models call these through ``XambaConfig`` modes; tests sweep shapes/dtypes
against ``kernels/ref.py``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import pwl
from repro.core.pwl import PWLTable
from repro.kernels import (actiba as _actiba, cumba as _cumba,
                           decode_step as _ds, flash_attention as _fa,
                           matmul_pwl as _mpwl, qmatmul as _qm,
                           reduba as _reduba, rg_lru as _rg, ref)

Array = jax.Array


@partial(jax.jit, static_argnames=("interpret",))
def cumba_cumsum(x: Array, *, interpret: bool = False) -> Array:
    """CumBA: cumulative sum along the trailing axis."""
    return _cumba.cumsum_last(x, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def reduba_sum(x: Array, *, interpret: bool = False) -> Array:
    """ReduBA: sum over the trailing axis (input moved so target is last)."""
    # reduce over last axis == reduce_rows of the transpose
    x2 = x.reshape(-1, x.shape[-1]).T          # (m=last, n=rest)
    out = _reduba.reduce_rows(x2, interpret=interpret)
    return out.reshape(x.shape[:-1])


@partial(jax.jit, static_argnames=("table", "interpret"))
def actiba_activate(x: Array, table: PWLTable, *,
                    interpret: bool = False) -> Array:
    """ActiBA: standalone PWL activation."""
    return _actiba.pwl_activate(x, table, interpret=interpret)


@partial(jax.jit, static_argnames=("table", "interpret"))
def matmul_pwl(x: Array, w: Array, table: PWLTable,
               v: Optional[Array] = None, *,
               interpret: bool = False) -> Array:
    """ActiBA vertical fusion: pwl(x @ w) [* (x @ v)]."""
    return _mpwl.matmul_pwl(x, w, table, v, interpret=interpret)


@partial(jax.jit, static_argnames=("table", "interpret"))
def qmatmul(x: Array, q: Array, scale: Array, *,
            table: Optional[PWLTable] = None,
            qv: Optional[Array] = None, vscale: Optional[Array] = None,
            interpret: bool = False) -> Array:
    """W8 fused dequant-matmul: ``epi((x @ q) * scale)`` with int8 weight
    tiles dequantized in-register; optional PWL epilogue + gated form."""
    return _qm.qmatmul(x, q, scale, table=table, qv=qv, vscale=vscale,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x_c: Array, a_c: Array, A_cum: Array, B_c: Array, C_c: Array,
              *, interpret: bool = False):
    """Fused SSD intra-chunk pass -> (y_diag, chunk_states)."""
    del a_c  # only the prefix sums are needed; kept for API symmetry
    from repro.kernels import ssd_chunk as _ssd
    return _ssd.ssd_chunk(x_c, None, A_cum, B_c, C_c, interpret=interpret)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> Array:
    """Flash attention (custom_vjp handles the backward pass)."""
    return _fa.flash_attention(q, k, v, causal, window, scale, 128, 128,
                               interpret)


@partial(jax.jit, static_argnames=("interpret",))
def rg_lru_scan(a: Array, b: Array, *, interpret: bool = False) -> Array:
    """Gated linear recurrence h_t = a_t h_{t-1} + b_t."""
    return _rg.rg_lru_scan(a, b, interpret=interpret)


# ----------------------------------------------------------------------------
# Fused single-token decode steps (``XambaConfig.decode`` pallas modes)
# ----------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("interpret",))
def ssd_step(state, x_t, dt_t, A, B_t, C_t, *, interpret: bool = False):
    """Bare SSD recurrent update (core/ssd.py pallas dispatch target)."""
    return _ds.ssd_step(state, x_t, dt_t, A, B_t, C_t, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def sscan_step(state, u_t, delta_t, A, B_t, C_t, D=None, *,
               interpret: bool = False):
    """Bare selective-scan update (core/selective_scan.py pallas target)."""
    return _ds.sscan_step(state, u_t, delta_t, A, B_t, C_t, D,
                          interpret=interpret)


@partial(jax.jit, static_argnames=("ngroups", "head_dim", "eps", "xamba",
                                   "interpret"))
def mamba2_decode_step(z, xbc, dt, conv_state, ssm_state, conv_w, conv_b,
                       dt_bias, A, D, norm_scale, *, ngroups: int,
                       head_dim: int, eps: float, xamba=None,
                       interpret: bool = False):
    """Fused Mamba-2 single-token step (conv + SiLU + softplus + SSD +
    gated norm at ``eps``).  ``xamba`` (hashable config) bakes ActiBA
    tables in."""
    return _ds.mamba2_step(
        z, xbc, dt, conv_state, ssm_state, conv_w, conv_b, dt_bias, A, D,
        norm_scale, ngroups=ngroups, head_dim=head_dim, eps=eps,
        silu=pwl.activation("silu", xamba),
        softplus=pwl.activation("softplus", xamba), interpret=interpret)


@partial(jax.jit, static_argnames=("ngroups", "head_dim", "chunk", "eps",
                                   "xamba", "mode"))
def mamba2_prefill(x, in_w, conv_state, ssm_state, conv_w, conv_b, dt_bias,
                   A, D, norm_scale, *, ngroups: int, head_dim: int,
                   chunk: int, eps: float, xamba=None, mode: str = "cumba"):
    """Fused Mamba-2 multi-token prefill (``XambaConfig.prefill``):
    in-projection (W8-fused when ``in_w`` is quantized) + conv + SiLU +
    softplus(dt) + chunked SSD scan + gated norm (at ``eps``) in one
    pass.

    ``mode``: ``cumba`` = fused-structure XLA pipeline; ``pallas`` /
    ``pallas_interpret`` = the one-kernel Pallas pipeline.  Returns
    ``(y, new_conv, new_ssm)`` with ``y`` the pre-out-projection gated
    mixer output (b, l, d_inner) in the stream dtype of ``x``.
    """
    from repro.kernels import prefill_chunk as _pc
    di = norm_scale.shape[-1]
    g, n = ngroups, ssm_state.shape[-1]
    zxbcdt = _pc.project_in(x, in_w)
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
    kwargs = dict(ngroups=g, head_dim=head_dim, chunk=chunk, eps=eps,
                  silu=pwl.activation("silu", xamba),
                  softplus=pwl.activation("softplus", xamba))
    if mode in ("pallas", "pallas_interpret"):
        return _pc.mamba2_prefill_pallas(
            z, xbc, dt, conv_state, ssm_state, conv_w, conv_b, dt_bias,
            A, D, norm_scale, interpret=(mode == "pallas_interpret"),
            **kwargs)
    return _pc.mamba2_prefill_xla(
        z, xbc, dt, conv_state, ssm_state, conv_w, conv_b, dt_bias,
        A, D, norm_scale, **kwargs)


@partial(jax.jit, static_argnames=("dt_rank", "xamba", "interpret"))
def mamba1_decode_step(xs_raw, z, conv_state, ssm_state, conv_w, conv_b,
                       xproj_w, dtproj_w, dtproj_b, A, D, *, dt_rank: int,
                       xamba=None, interpret: bool = False):
    """Fused Mamba-1 single-token step (conv + SiLU + dt projections +
    selective scan + gate)."""
    return _ds.mamba1_step(
        xs_raw, z, conv_state, ssm_state, conv_w, conv_b, xproj_w,
        dtproj_w, dtproj_b, A, D, dt_rank=dt_rank,
        silu=pwl.activation("silu", xamba),
        softplus=pwl.activation("softplus", xamba), interpret=interpret)


@partial(jax.jit, static_argnames=("xamba", "interpret"))
def rglru_decode_step(u, gate, conv_state, h_state, conv_w, conv_b, rg_w,
                      rg_b, ig_w, ig_b, lam, *, xamba=None,
                      interpret: bool = False):
    """Fused RG-LRU single-token step (conv + sigmoid gates + recurrence
    + GeLU output gate)."""
    return _ds.rglru_step(
        u, gate, conv_state, h_state, conv_w, conv_b, rg_w, rg_b, ig_w,
        ig_b, lam, sigmoid=pwl.activation("sigmoid", xamba),
        softplus=pwl.activation("softplus", xamba),
        gelu=pwl.activation("gelu", xamba), interpret=interpret)


# Re-export oracles for convenience in tests/benchmarks.
reference = ref
