"""Fused single-token decode-step Pallas kernels (one per mixer family).

The continuous-batching engine calls ``decode_step`` once per generated
token, so the per-step op chain — conv-tail shift, SiLU, Softplus(dt),
the SSM state update and the output gate — is the hottest code in the
repo.  XLA executes it as dozens of tiny HBM-roundtripping ops; these
kernels run the whole post-``in_proj`` / pre-``out_proj`` chain for one
batch row in VMEM, writing each state exactly once:

* ``mamba2_step``  — conv shift + SiLU + softplus(dt) + SSD recurrence +
                     D-skip + gated RMSNorm + SiLU(z) gate;
* ``mamba1_step``  — conv shift + SiLU + x_proj/dt_proj matmuls +
                     softplus + selective-scan recurrence + SiLU(z) gate;
* ``rglru_step``   — conv shift + r/i sigmoid gates + RG-LRU update +
                     GeLU(gate) output gate;
* ``ssd_step`` / ``sscan_step`` — the bare recurrent updates, used when
  ``core/{ssd,selective_scan}.py`` are called directly in ``pallas`` mode.

Activations honor ActiBA: callers pass the (compile-time) activation
callables from ``core.pwl.activation`` so the PWL tables are baked into
the kernel body, exactly like the NPU's C-LUT programming.

TPU layout rules shape the code (Mosaic tiles the last two dims of every
block by (8, 128)):

* one program per batch row; per-row vectors travel as ``(b, 1, d)``
  so a row block ``(1, 1, d)`` spans its array's last two dims;
* SSM states travel as ``(b, h*p, n)`` — the same bytes as
  ``(b, h, p, n)``, but the update becomes 2-D: each channel is a
  sublane row and ``n`` the lanes;
* a channel-major column never has to be built from a lane row: outer
  products are K=1 ``dot_general``s contracting the leading axis, the
  state->output contraction is an ``(1, n) x (rows, n)^T`` matmul, and
  per-head scalars expand to channels through a 0/1 ``(h, h*p)`` matmul.

On CPU use ``interpret=True``; numerics are fp32 throughout (fp32
matmuls at ``HIGHEST`` precision; the RG-LRU gate weights stay in their
storage dtype), tied to the ``kernels/ref.py`` oracles at <= 1e-5.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

Array = jax.Array


def _row(shape):
    """BlockSpec for a per-batch-row block: (1, ...) indexed by program 0."""
    ndim = len(shape)
    return pl.BlockSpec((1,) + tuple(shape),
                        lambda i, _nd=ndim: (i,) + (0,) * _nd)


def _rep(shape):
    """BlockSpec for a broadcast (weight) block shared by every program."""
    ndim = len(shape)
    return pl.BlockSpec(tuple(shape), lambda i, _nd=ndim: (0,) * (_nd))


def _rows(x):
    """(b, d) -> (b, 1, d): row blocks that span the array's last dims."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def _f32(ref):
    return ref[...].astype(jnp.float32)


def _outer(u, v):
    """(1, m) x (1, n) -> (m, n): a K=1 contraction over the leading axis
    (the MXU builds the column layout the lane row cannot)."""
    return common.dot(u, v, ((0,), (0,)))


def _rowdot(c, m):
    """(1, n) x (rows, n)^T -> (1, rows)."""
    return common.dot(c, m, ((1,), (1,)))


# ============================================================================
# Bare recurrent updates (core-level dispatch targets)
# ============================================================================

def _ssd_update(st, xdt, decay, B, C, g):
    """st (h*p, n); xdt/decay (1, h*p) per channel; B/C (1, g*n).
    Returns (new_st (h*p, n), y (1, h*p))."""
    m = st.shape[0] // g
    n = st.shape[1]
    ones = jnp.ones((1, n), jnp.float32)
    news, ys = [], []
    for gi in range(g):
        r = slice(gi * m, (gi + 1) * m)
        Bg, Cg = B[:, gi * n:(gi + 1) * n], C[:, gi * n:(gi + 1) * n]
        new = st[r] * _outer(decay[:, r], ones) + _outer(xdt[:, r], Bg)
        news.append(new)
        ys.append(_rowdot(Cg, new))
    return jnp.concatenate(news, axis=0), jnp.concatenate(ys, axis=1)


def ssd_step(state: Array, x_t: Array, dt_t: Array, A: Array,
             B_t: Array, C_t: Array, *,
             interpret: bool = False) -> Tuple[Array, Array]:
    """state (b,h,p,n), x_t (b,h,p), dt_t (b,h), A (h,), B_t/C_t (b,g,n)."""
    b, h, p, n = state.shape
    g = B_t.shape[1]
    A2 = A.reshape(1, h).astype(jnp.float32)

    def kernel(st_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, ns_ref, y_ref):
        E = common.head_expand(h, p)
        dt = _f32(dt_ref)[0]                                 # (1, h)
        new, y = _ssd_update(_f32(st_ref)[0],
                             _f32(x_ref)[0] * common.dot(dt, E),
                             common.dot(jnp.exp(dt * _f32(a_ref)), E),
                             _f32(b_ref)[0], _f32(c_ref)[0], g)
        ns_ref[0] = new.astype(ns_ref.dtype)
        y_ref[0] = y.astype(y_ref.dtype)

    new_state, y = common.pallas_call(
        kernel, grid=(b,),
        in_specs=[_row((h * p, n)), _row((1, h * p)), _row((1, h)),
                  _rep((1, h)), _row((1, g * n)), _row((1, g * n))],
        out_specs=(_row((h * p, n)), _row((1, h * p))),
        out_shape=(jax.ShapeDtypeStruct((b, h * p, n), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, h * p), x_t.dtype)),
        dimension_semantics=("parallel",),
        interpret=interpret, name="ssd_decode_step",
    )(state.reshape(b, h * p, n), x_t.reshape(b, 1, h * p), _rows(dt_t),
      A2, B_t.reshape(b, 1, g * n), C_t.reshape(b, 1, g * n))
    return new_state.reshape(b, h, p, n), y.reshape(b, h, p)


def _sscan_update(st, u, dt, A, B, C, D):
    """st/A (d,n); u/dt (1,d); B/C (1,n); D (1,d) or None ->
    (new_st (d,n), y (1,d))."""
    ones = jnp.ones((1, st.shape[1]), jnp.float32)
    new = st * jnp.exp(_outer(dt, ones) * A) + _outer(dt * u, B)
    y = _rowdot(C, new)
    if D is not None:
        y = y + D * u
    return new, y


def sscan_step(state: Array, u_t: Array, delta_t: Array, A: Array,
               B_t: Array, C_t: Array, D: Optional[Array] = None, *,
               interpret: bool = False) -> Tuple[Array, Array]:
    """state (b,d,n), u_t/delta_t (b,d), A (d,n), B_t/C_t (b,n), D (d,)."""
    b, d, n = state.shape
    has_d = D is not None
    D2 = (D.reshape(1, d).astype(jnp.float32) if has_d
          else jnp.zeros((1, d), jnp.float32))

    def kernel(st_ref, u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
               ns_ref, y_ref):
        new, y = _sscan_update(_f32(st_ref)[0], _f32(u_ref)[0],
                               _f32(dt_ref)[0], _f32(a_ref),
                               _f32(b_ref)[0], _f32(c_ref)[0],
                               _f32(d_ref) if has_d else None)
        ns_ref[0] = new.astype(ns_ref.dtype)
        y_ref[0] = y.astype(y_ref.dtype)

    new_state, y = common.pallas_call(
        kernel, grid=(b,),
        in_specs=[_row((d, n)), _row((1, d)), _row((1, d)), _rep((d, n)),
                  _row((1, n)), _row((1, n)), _rep((1, d))],
        out_specs=(_row((d, n)), _row((1, d))),
        out_shape=(jax.ShapeDtypeStruct((b, d, n), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, d), u_t.dtype)),
        dimension_semantics=("parallel",),
        interpret=interpret, name="sscan_decode_step",
    )(state, _rows(u_t), _rows(delta_t), A.astype(jnp.float32),
      _rows(B_t), _rows(C_t), D2)
    return new_state, y.reshape(b, d)


# ============================================================================
# Fused mixer steps (conv tail + activations + recurrence + output gate)
# ============================================================================

def _conv_shift(conv_state, x_row, w, bias):
    """conv_state (w-1,d), x_row (1,d), w (width,d), bias (1,d) ->
    (conv_out (1,d), new_state (w-1,d)) — one causal-conv step."""
    win = jnp.concatenate([conv_state, x_row], axis=0)       # (width, d)
    out = jnp.sum(win * w, axis=0, keepdims=True) + bias     # (1, d)
    return out, win[1:]


def mamba2_step(z: Array, xbc: Array, dt: Array, conv_state: Array,
                ssm_state: Array, conv_w: Array, conv_b: Array,
                dt_bias: Array, A: Array, D: Array, norm_scale: Array, *,
                ngroups: int, head_dim: int,
                silu: Callable = jax.nn.silu,
                softplus: Callable = jax.nn.softplus,
                eps: float,
                interpret: bool = False) -> Tuple[Array, Array, Array]:
    """Fused Mamba-2 decode step for one token, its gated RMSNorm
    (``common.gate_then_rmsnorm``) at ``eps``.

    z (b,di), xbc (b,dxbc), dt (b,h) — the ``in_proj`` splits;
    conv_state (b,w-1,dxbc), ssm_state (b,h,p,n); conv_w (w,dxbc);
    conv_b (dxbc,), dt_bias/A/D (h,), norm_scale (di,).
    A is the negative decay rate (``-exp(A_log)``).
    Returns (y (b,di) — gated, pre-``out_proj``; new_conv; new_ssm).
    """
    b, di = z.shape
    h = dt.shape[1]
    p = head_dim
    g = ngroups
    n = ssm_state.shape[-1]
    w = conv_w.shape[0]
    dxbc = xbc.shape[1]

    conv_b2 = conv_b.reshape(1, dxbc).astype(jnp.float32)
    dtb2 = dt_bias.reshape(1, h).astype(jnp.float32)
    A2 = A.reshape(1, h).astype(jnp.float32)
    D2 = D.reshape(1, h).astype(jnp.float32)
    ns2 = norm_scale.reshape(1, di).astype(jnp.float32)

    def kernel(z_ref, xbc_ref, dt_ref, cs_ref, st_ref, cw_ref, cb_ref,
               dtb_ref, a_ref, d_ref, nsc_ref, y_ref, nc_ref, nst_ref):
        conv_out, new_conv = _conv_shift(_f32(cs_ref)[0], _f32(xbc_ref)[0],
                                         _f32(cw_ref), _f32(cb_ref))
        act = silu(conv_out)                                 # (1, dxbc)
        xs = act[:, :di]
        B = act[:, di:di + g * n]
        C = act[:, di + g * n:]
        E = common.head_expand(h, p)
        dt_f = softplus(_f32(dt_ref)[0] + _f32(dtb_ref))     # (1, h)
        new, y = _ssd_update(_f32(st_ref)[0], xs * common.dot(dt_f, E),
                             common.dot(jnp.exp(dt_f * _f32(a_ref)), E),
                             B, C, g)
        y = y + common.dot(_f32(d_ref), E) * xs              # D skip
        out = common.gate_then_rmsnorm(y, _f32(z_ref)[0], _f32(nsc_ref),
                                       eps=eps, ngroups=g, silu=silu)
        y_ref[0] = out.astype(y_ref.dtype)
        nc_ref[0] = new_conv.astype(nc_ref.dtype)
        nst_ref[0] = new.astype(nst_ref.dtype)

    y, new_conv, new_ssm = common.pallas_call(
        kernel, grid=(b,),
        in_specs=[_row((1, di)), _row((1, dxbc)), _row((1, h)),
                  _row((w - 1, dxbc)), _row((h * p, n)),
                  _rep((w, dxbc)), _rep((1, dxbc)), _rep((1, h)),
                  _rep((1, h)), _rep((1, h)), _rep((1, di))],
        out_specs=(_row((1, di)), _row((w - 1, dxbc)), _row((h * p, n))),
        out_shape=(jax.ShapeDtypeStruct((b, 1, di), z.dtype),
                   jax.ShapeDtypeStruct((b, w - 1, dxbc), conv_state.dtype),
                   jax.ShapeDtypeStruct((b, h * p, n), jnp.float32)),
        dimension_semantics=("parallel",),
        interpret=interpret, name="mamba2_decode_step",
    )(_rows(z), _rows(xbc), _rows(dt), conv_state,
      ssm_state.reshape(b, h * p, n), conv_w.astype(jnp.float32),
      conv_b2, dtb2, A2, D2, ns2)
    return y.reshape(b, di), new_conv, new_ssm.reshape(b, h, p, n)


def mamba1_step(xs_raw: Array, z: Array, conv_state: Array, ssm_state: Array,
                conv_w: Array, conv_b: Array, xproj_w: Array, dtproj_w: Array,
                dtproj_b: Array, A: Array, D: Array, *,
                dt_rank: int,
                silu: Callable = jax.nn.silu,
                softplus: Callable = jax.nn.softplus,
                interpret: bool = False) -> Tuple[Array, Array, Array]:
    """Fused Mamba-1 decode step.

    xs_raw/z (b,di) — the ``in_proj`` halves; conv_state (b,w-1,di);
    ssm_state (b,di,n); xproj_w (di, dt_rank+2n); dtproj_w (dt_rank,di);
    dtproj_b (di,); A (di,n) negative; D (di,).
    Returns (y (b,di) — gated, pre-``out_proj``; new_conv; new_ssm).
    """
    b, di = z.shape
    n = ssm_state.shape[-1]
    w = conv_w.shape[0]
    r = dt_rank

    conv_b2 = conv_b.reshape(1, di).astype(jnp.float32)
    dtb2 = dtproj_b.reshape(1, di).astype(jnp.float32)
    D2 = D.reshape(1, di).astype(jnp.float32)

    def kernel(x_ref, z_ref, cs_ref, st_ref, cw_ref, cb_ref, xp_ref,
               dtw_ref, dtb_ref, a_ref, d_ref, y_ref, nc_ref, nst_ref):
        conv_out, new_conv = _conv_shift(_f32(cs_ref)[0], _f32(x_ref)[0],
                                         _f32(cw_ref), _f32(cb_ref))
        xs = silu(conv_out)                                  # (1, di)
        dbc = common.dot(xs, _f32(xp_ref))                   # (1, r+2n)
        dt_low, B, C = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
        dt_f = softplus(common.dot(dt_low, _f32(dtw_ref)) + _f32(dtb_ref))
        new, y = _sscan_update(_f32(st_ref)[0], xs, dt_f, _f32(a_ref),
                               B, C, _f32(d_ref))
        out = y * silu(_f32(z_ref)[0])
        y_ref[0] = out.astype(y_ref.dtype)
        nc_ref[0] = new_conv.astype(nc_ref.dtype)
        nst_ref[0] = new.astype(nst_ref.dtype)

    y, new_conv, new_ssm = common.pallas_call(
        kernel, grid=(b,),
        in_specs=[_row((1, di)), _row((1, di)), _row((w - 1, di)),
                  _row((di, n)), _rep((w, di)), _rep((1, di)),
                  _rep((di, r + 2 * n)), _rep((r, di)), _rep((1, di)),
                  _rep((di, n)), _rep((1, di))],
        out_specs=(_row((1, di)), _row((w - 1, di)), _row((di, n))),
        out_shape=(jax.ShapeDtypeStruct((b, 1, di), z.dtype),
                   jax.ShapeDtypeStruct((b, w - 1, di), conv_state.dtype),
                   jax.ShapeDtypeStruct((b, di, n), jnp.float32)),
        dimension_semantics=("parallel",),
        interpret=interpret, name="mamba1_decode_step",
    )(_rows(xs_raw), _rows(z), conv_state, ssm_state,
      conv_w.astype(jnp.float32), conv_b2, xproj_w, dtproj_w, dtb2,
      A.astype(jnp.float32), D2)
    return y.reshape(b, di), new_conv, new_ssm


_RG_C = common.RG_LRU_C  # Griffin's fixed gate exponent
# Output channels per RG-LRU program: the (w, w) gate weights stream
# through VMEM as (w, tile) column blocks instead of whole matrices.
_RG_TILE = 256


def rglru_step(u: Array, gate: Array, conv_state: Array, h_state: Array,
               conv_w: Array, conv_b: Array, rg_w: Array, rg_b: Array,
               ig_w: Array, ig_b: Array, lam: Array, *,
               sigmoid: Callable = jax.nn.sigmoid,
               softplus: Callable = jax.nn.softplus,
               gelu: Callable = jax.nn.gelu,
               interpret: bool = False) -> Tuple[Array, Array, Array]:
    """Fused RG-LRU decode step.

    u/gate (b,w) — the ``in_x``/``in_gate`` projections; conv_state
    (b,wc-1,w); h_state (b,w); rg_w/ig_w (w,w) with (w,) biases; lam (w,).
    Returns (y (b,w) — gated, pre-``out``; new_conv; new_h).

    Grid ``(w // tile, b)``: program ``(j, i)`` owns output channels
    ``j*tile:(j+1)*tile`` of row ``i``.  It convolves the whole row (the
    gate matmuls contract over every channel) and its own channel tile;
    batch is the inner axis, so each weight tile is fetched once.
    """
    b, wd = u.shape
    wc = conv_w.shape[0]
    tn = _RG_TILE if wd % _RG_TILE == 0 else wd

    conv_w32 = conv_w.astype(jnp.float32)
    conv_b2 = conv_b.reshape(1, wd).astype(jnp.float32)
    rgb2 = rg_b.reshape(1, wd).astype(jnp.float32)
    igb2 = ig_b.reshape(1, wd).astype(jnp.float32)
    lam2 = lam.reshape(1, wd).astype(jnp.float32)
    u3, g3, h3 = _rows(u), _rows(gate), _rows(h_state)

    def kernel(u_ref, cs_ref, cw_ref, cb_ref, ut_ref, cst_ref, cwt_ref,
               cbt_ref, g_ref, h_ref, rw_ref, rb_ref, iw_ref, ib_ref,
               lam_ref, y_ref, nc_ref, nh_ref):
        u_c, _ = _conv_shift(_f32(cs_ref)[0], _f32(u_ref)[0], _f32(cw_ref),
                             _f32(cb_ref))                   # (1, w)
        u_t, new_conv = _conv_shift(_f32(cst_ref)[0], _f32(ut_ref)[0],
                                    _f32(cwt_ref), _f32(cbt_ref))
        rw, iw = rw_ref[...], iw_ref[...]
        r = sigmoid(common.dot(u_c.astype(rw.dtype), rw) + _f32(rb_ref))
        i = sigmoid(common.dot(u_c.astype(iw.dtype), iw) + _f32(ib_ref))
        log_a = -_RG_C * softplus(_f32(lam_ref)) * r
        a = jnp.exp(log_a)
        gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
            * (i * u_t)
        h_new = a * _f32(h_ref)[0] + gated_in                # (1, tile)
        out = h_new * gelu(_f32(g_ref)[0])
        y_ref[0] = out.astype(y_ref.dtype)
        nc_ref[0] = new_conv.astype(nc_ref.dtype)
        nh_ref[0] = h_new.astype(nh_ref.dtype)

    full3 = lambda d1: pl.BlockSpec((1, d1, wd), lambda j, i: (i, 0, 0))
    tile3 = lambda d1: pl.BlockSpec((1, d1, tn), lambda j, i: (i, 0, j))
    full2 = lambda d0: pl.BlockSpec((d0, wd), lambda j, i: (0, 0))
    tile2 = lambda d0: pl.BlockSpec((d0, tn), lambda j, i: (0, j))
    y, new_conv, new_h = common.pallas_call(
        kernel, grid=(wd // tn, b),
        in_specs=[full3(1), full3(wc - 1), full2(wc), full2(1),
                  tile3(1), tile3(wc - 1), tile2(wc), tile2(1),
                  tile3(1), tile3(1), tile2(wd), tile2(1), tile2(wd),
                  tile2(1), tile2(1)],
        out_specs=(tile3(1), tile3(wc - 1), tile3(1)),
        out_shape=(jax.ShapeDtypeStruct((b, 1, wd), u.dtype),
                   jax.ShapeDtypeStruct((b, wc - 1, wd), conv_state.dtype),
                   jax.ShapeDtypeStruct((b, 1, wd), jnp.float32)),
        dimension_semantics=("parallel", "parallel"),
        interpret=interpret, name="rglru_decode_step",
    )(u3, conv_state, conv_w32, conv_b2, u3, conv_state, conv_w32, conv_b2,
      g3, h3, rg_w, rgb2, ig_w, igb2, lam2)
    return y.reshape(b, wd), new_conv, new_h.reshape(b, wd)
