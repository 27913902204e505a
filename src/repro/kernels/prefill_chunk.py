"""Fused SSD prefill pipeline: conv + SiLU + softplus(dt) + chunk scan + gate.

Chunked prefill used to run the unfused XLA chain (projection -> causal
conv -> segsum -> intra-chunk scan -> inter-chunk scan -> gated norm),
each stage a separate op group with its intermediates round-tripping
through HBM.  This module fuses the whole post-projection mixer into a
single pass over the sequence, in two interchangeable backends selected
by ``XambaConfig.prefill``:

* ``mamba2_prefill_xla``  — the fused-structure single-pass XLA pipeline
  (mode ``cumba``): one chunk-sequential sweep that carries the SSM state
  and conv tail, with the CumBA triangular-matmul cumsum and all
  contractions as MXU-shaped ``dot_general``s.  This is the portable
  fast path (and the one the CPU serve bench measures).
* ``mamba2_prefill_pallas`` — the one-kernel Pallas pipeline (modes
  ``pallas`` / ``pallas_interpret``): a ``(batch, chunk)`` grid walked
  sequentially so VMEM scratch carries the conv tail and SSM state
  across chunks — zero intermediate HBM round-trips between the conv,
  the activations, the intra-chunk CumBA scan (absorbing
  ``kernels/ssd_chunk.py``), the inter-chunk recurrence, and the gated
  RMSNorm epilogue.

Both take the *projected* streams (z / xbc / raw dt).  The in-projection
that produces them runs through :func:`project_in`, which keeps the W8
serve path fused: a quantized weight on a pallas backend goes through the
blocked dequant-matmul kernel (``kernels/qmatmul.py``) so the int8 tiles
dequantize in-register — the streams are born from the fused epilogue
instead of a materialized fp copy of the weight.

ActiBA composes the same way as the decode-step kernel: ``silu`` /
``softplus`` arrive as compile-time callables (``pwl.activation``), so
PWL tables bake into either backend unchanged.

Oracle: ``kernels/ref.py: mamba2_prefill_ref`` (sequential
``ssd_reference`` semantics).  Dispatch: ``nn/ssm.py: mamba2_apply``.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

Array = jax.Array
# Scoped VMEM for one (batch row, chunk) cell: a 256-token mamba2-130m
# chunk needs ~29 MiB (the (L, L) decay blocks, the conv window and the
# double-buffered streams), above Mosaic's 16 MiB default.
_PREFILL_VMEM_BYTES = 64 * 2 ** 20


# ----------------------------------------------------------------------------
# In-projection (optionally W8-fused)
# ----------------------------------------------------------------------------

def project_in(x: Array, w) -> Array:
    """``x @ w`` producing the z/xbc/dt streams.

    ``w`` is either an fp weight or a ``QuantTensor``; quantized weights
    on a pallas backend run the blocked dequant-matmul kernel
    (``kernels/qmatmul.py``) so the prefill pipeline's first stage stays
    int8-in-HBM.  Mirrors ``nn/layers.py: linear`` (the in-projection has
    no bias).
    """
    from repro.nn import quant
    if quant.is_quantized(w):
        y = quant.qdot(x, w)
    else:
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------------------------
# Shared chunk math (the CumBA intra-chunk scan + state carry), XLA form
# ----------------------------------------------------------------------------

def _chunk_scan(xdt: Array, a: Array, B: Array, C: Array, state: Array,
                g: int) -> Tuple[Array, Array]:
    """One chunk of the SSD recurrence with an incoming state.

    xdt: (b, L, h, p) dt-scaled values; a: (b, L, h) log decays;
    B, C: (b, L, g, n); state: (b, h, p, n).
    Returns (y (b, L, h, p), new_state (b, h, p, n)), all fp32.
    """
    b, L, h, p = xdt.shape
    n = B.shape[-1]
    hpg = h // g
    tril = jnp.tril(jnp.ones((L, L), jnp.float32))
    # CumBA: inclusive prefix sums as one triangular matmul on the MXU.
    cs = jnp.einsum("ls,bsh->blh", tril, a,
                    preferred_element_type=jnp.float32)      # (b, L, h)
    seg = cs[:, :, None, :] - cs[:, None, :, :]              # (b, L, S, h)
    trilb = (tril > 0)[None, :, :, None]
    decay = jnp.where(trilb, jnp.exp(jnp.where(trilb, seg, 0.0)), 0.0)
    CB = jnp.einsum("blgn,bsgn->blsg", C, B,
                    preferred_element_type=jnp.float32)      # (b, L, S, g)
    x_r = xdt.reshape(b, L, g, hpg, p)
    M = CB[..., None] * decay.reshape(b, L, L, g, hpg)       # (b, L, S, g, q)
    y = jnp.einsum("blsgq,bsgqp->blgqp", M, x_r,
                   preferred_element_type=jnp.float32).reshape(b, L, h, p)
    # State -> output for tokens in this chunk (the inter-chunk term).
    cse = jnp.exp(cs)                                        # (b, L, h)
    st_g = state.reshape(b, g, hpg, p, n)
    y_off = jnp.einsum("blgn,bgqpn->blgqp", C, st_g,
                       preferred_element_type=jnp.float32)
    y = y + y_off.reshape(b, L, h, p) * cse[..., None]
    # Outgoing state: decayed incoming state + this chunk's contribution.
    dstate = jnp.exp(cs[:, -1:, :] - cs)                     # (b, L, h)
    xw = (xdt * dstate[..., None]).reshape(b, L, g, hpg, p)
    st_new = jnp.einsum("blgn,blgqp->bgqpn", B, xw,
                        preferred_element_type=jnp.float32)
    st_new = st_new.reshape(b, h, p, n) + \
        state * jnp.exp(cs[:, -1])[..., None, None]
    return y, st_new


def _conv_window(conv_state: Array, xbc: Array, conv_w: Array,
                 conv_b: Array) -> Tuple[Array, Array]:
    """Causal conv over the sequence with an incoming tail.

    conv_state: (b, w-1, dxbc); xbc: (b, l, dxbc).
    Returns (conv (b, l, dxbc) fp32, new_tail (b, w-1, dxbc) fp32).
    """
    l = xbc.shape[1]
    width = conv_w.shape[0]
    win = jnp.concatenate([conv_state.astype(jnp.float32),
                           xbc.astype(jnp.float32)], axis=1)
    w = conv_w.astype(jnp.float32)
    conv = sum(win[:, i:i + l] * w[i] for i in range(width)) + \
        conv_b.astype(jnp.float32)
    return conv, win[:, l:]


# ----------------------------------------------------------------------------
# Backend 1: fused-structure XLA pipeline (mode "cumba")
# ----------------------------------------------------------------------------

def mamba2_prefill_xla(z: Array, xbc: Array, dt: Array, conv_state: Array,
                       ssm_state: Array, conv_w: Array, conv_b: Array,
                       dt_bias: Array, A: Array, D: Array,
                       norm_scale: Array, *, ngroups: int, head_dim: int,
                       chunk: int, silu: Callable, softplus: Callable,
                       eps: float) -> Tuple[Array, Array, Array]:
    """Single-pass prefill: streams in, gated mixer output + states out.

    z: (b, l, di); xbc: (b, l, dxbc); dt: (b, l, h) RAW (pre-softplus);
    conv_state: (b, w-1, dxbc); ssm_state: (b, h, p, n).
    Returns (y (b, l, di) in the stream dtype, new_conv (b, w-1, dxbc),
    new_ssm fp32).  ``l`` must be a multiple of ``chunk`` (the dispatcher
    gates on this — no padding, so the conv tail and raw dt stay exact).
    """
    b, l, di = z.shape
    g, p = ngroups, head_dim
    h = dt.shape[-1]
    n = (xbc.shape[-1] - di) // (2 * g)
    sd = z.dtype
    assert l % chunk == 0, (l, chunk)

    conv, new_tail = _conv_window(conv_state, xbc, conv_w, conv_b)
    # Activated streams round to the STREAM dtype (the unfused chain's
    # boundary) before re-entering the fp32 scan core.
    act = silu(conv.astype(sd))
    xs = act[..., :di]
    B = act[..., di:di + g * n].reshape(b, l, g, n).astype(jnp.float32)
    C = act[..., di + g * n:].reshape(b, l, g, n).astype(jnp.float32)
    dt_f = softplus(dt.astype(jnp.float32) +
                    dt_bias.astype(jnp.float32))             # (b, l, h)
    a = dt_f * A.astype(jnp.float32)                         # (b, l, h)
    xs_r = xs.reshape(b, l, h, p)
    xdt = xs_r.astype(jnp.float32) * dt_f[..., None]

    state0 = ssm_state.astype(jnp.float32)
    nchunks = l // chunk
    if nchunks == 1:
        y, new_ssm = _chunk_scan(xdt, a, B, C, state0, g)
    else:
        def split(t):  # (b, l, ...) -> (c, b, L, ...)
            return jnp.moveaxis(
                t.reshape((b, nchunks, chunk) + t.shape[2:]), 1, 0)

        def body(state, blk):
            y_c, state = _chunk_scan(*blk, state, g)
            return state, y_c

        new_ssm, ys = jax.lax.scan(
            body, state0, (split(xdt), split(a), split(B), split(C)))
        y = jnp.moveaxis(ys, 0, 1).reshape(b, l, h, p)

    # D skip in the stream dtype, gated norm in fp32: the unfused chain's
    # rounding boundaries, so fused and unfused prefill agree under bf16.
    y = y.astype(sd) + xs_r.astype(sd) * D.astype(sd)[None, None, :, None]
    out = common.gate_then_rmsnorm(y.reshape(b, l, di), z, norm_scale,
                                   eps=eps, ngroups=g, silu=silu).astype(sd)
    return out, new_tail.astype(conv_state.dtype), new_ssm


# ----------------------------------------------------------------------------
# Backend 2: one-kernel Pallas pipeline (modes "pallas"/"pallas_interpret")
# ----------------------------------------------------------------------------

def _prefill_kernel(width: int, di: int, g: int, p: int, n: int,
                    silu: Callable, softplus: Callable, eps: float):
    h = (di // p)
    hpg = h // g

    def kernel(z_ref, xbc_ref, dt_ref, c0_ref, s0_ref, cw_ref, cb_ref,
               dtb_ref, a_ref, d_ref, ns_ref, y_ref, co_ref, so_ref,
               tail_scr, st_scr):
        ci = pl.program_id(1)

        @pl.when(ci == 0)
        def _init():
            tail_scr[...] = c0_ref[0].astype(jnp.float32)
            st_scr[...] = s0_ref[0].astype(jnp.float32)

        sd = z_ref.dtype
        xbc = xbc_ref[0].astype(jnp.float32)                 # (L, dxbc)
        L = xbc.shape[0]
        win = jnp.concatenate([tail_scr[...], xbc], axis=0)  # (L+w-1, dxbc)
        w = cw_ref[...].astype(jnp.float32)                  # (w, dxbc)
        conv = sum(win[i:i + L] * w[i:i + 1] for i in range(width)) + \
            cb_ref[...].astype(jnp.float32)
        # Stream-dtype rounding at the activation boundary (matches the
        # unfused chain, so fused/unfused agree under bf16 params); the
        # activation itself evaluates in fp32 (Mosaic has no bf16 path
        # for its constants).
        act = silu(conv.astype(sd).astype(jnp.float32)).astype(sd)
        xs = act[:, :di]                                     # (L, di), sd
        Bq = act[:, di:di + g * n].astype(jnp.float32)       # (L, g*n)
        Cq = act[:, di + g * n:].astype(jnp.float32)
        dt_f = softplus(dt_ref[0].astype(jnp.float32) +
                        dtb_ref[...].astype(jnp.float32))    # (L, h)
        a = dt_f * a_ref[...].astype(jnp.float32)            # (L, h)
        rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        trilb = rows >= cols
        tril = trilb.astype(jnp.float32)
        # CumBA: prefix sums for all heads as one (L, L) x (L, h) matmul.
        cs = common.dot(tril, a)                             # (L, h)
        one = jnp.ones((1, 1), jnp.float32)
        E = common.head_expand(h, p)                         # (h, di)
        xdt = xs.astype(jnp.float32) * common.dot(dt_f, E)   # (L, di)
        state = st_scr[...]                                  # (h*p, n)
        # Whole-chunk decay of the incoming state, one row per channel:
        # per-head totals expand to channels, then a K=1 outer product
        # lays them down the sublanes.
        chunk_decay = common.dot(jnp.exp(cs[L - 1:L, :]), E)  # (1, di)
        state_decay = common.dot(chunk_decay, jnp.ones((1, n), jnp.float32),
                                 ((0,), (0,)))               # (h*p, n)

        ys = []
        sts = []
        for gi in range(g):
            Bg = Bq[:, gi * n:(gi + 1) * n]                  # (L, n)
            Cg = Cq[:, gi * n:(gi + 1) * n]
            CB = common.dot(Cg, Bg, ((1,), (1,)))            # (L, L)
            for qi in range(hpg):
                hi = gi * hpg + qi
                cs_c = cs[:, hi:hi + 1]                      # (L, 1)
                # The same sums as a row (an exact K=1 product), so the
                # segment differences cancel on the diagonal.
                cs_r = common.dot(one, cs_c, ((1,), (1,)))   # (1, L)
                seg = cs_c - cs_r
                dec = jnp.where(trilb,
                                jnp.exp(jnp.where(trilb, seg, 0.0)), 0.0)
                x_h = xdt[:, hi * p:(hi + 1) * p]            # (L, p)
                st_h = state[hi * p:(hi + 1) * p]            # (p, n)
                y_h = common.dot(CB * dec, x_h) + \
                    common.dot(Cg, st_h, ((1,), (1,))) * jnp.exp(cs_c)
                ys.append(y_h)
                # This chunk's contribution to the outgoing state.
                sts.append(common.dot(
                    x_h * jnp.exp(cs_c[L - 1:L] - cs_c), Bg, ((0,), (0,))))
        y = jnp.concatenate(ys, axis=1)                      # (L, di)
        new_state = state * state_decay + \
            jnp.concatenate(sts, axis=0)                     # (h*p, n)

        d_ch = common.dot(d_ref[...].astype(jnp.float32), E)  # (1, di)
        y = y.astype(sd) + xs * d_ch.astype(sd)
        out = common.gate_then_rmsnorm(y, z_ref[0], ns_ref[...], eps=eps,
                                       ngroups=g, silu=silu)

        y_ref[0] = out.astype(y_ref.dtype)
        tail_scr[...] = win[L:]
        st_scr[...] = new_state
        co_ref[0] = win[L:].astype(co_ref.dtype)
        so_ref[0] = new_state.astype(so_ref.dtype)

    return kernel


def mamba2_prefill_pallas(z: Array, xbc: Array, dt: Array, conv_state: Array,
                          ssm_state: Array, conv_w: Array, conv_b: Array,
                          dt_bias: Array, A: Array, D: Array,
                          norm_scale: Array, *, ngroups: int, head_dim: int,
                          chunk: int, silu: Callable, softplus: Callable,
                          eps: float, interpret: bool = False
                          ) -> Tuple[Array, Array, Array]:
    """One-kernel prefill (shapes/contract as :func:`mamba2_prefill_xla`).

    Grid ``(batch, nchunks)`` with both axes "arbitrary": the sequential
    row-major walk lets VMEM scratch carry each row's conv tail and SSM
    state chunk-to-chunk; the state outputs revisit one block per batch
    row, so only the final chunk's write leaves VMEM.  The SSM state
    travels as ``(b, h*p, n)`` (the same bytes as ``(b, h, p, n)``): each
    head's ``(p, n)`` block is then a sublane-aligned row slice.
    """
    b, l, di = z.shape
    g, p = ngroups, head_dim
    h = dt.shape[-1]
    dxbc = xbc.shape[-1]
    n = (dxbc - di) // (2 * g)
    width = conv_w.shape[0]
    assert l % chunk == 0, (l, chunk)
    nchunks = l // chunk

    kernel = _prefill_kernel(width, di, g, p, n, silu, softplus, eps)
    row = lambda bi, ci: (bi, ci, 0)
    head = lambda bi, ci: (bi, 0, 0)
    rep2 = lambda bi, ci: (0, 0)
    y, new_conv, new_ssm = common.pallas_call(
        kernel,
        grid=(b, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, di), row),
            pl.BlockSpec((1, chunk, dxbc), row),
            pl.BlockSpec((1, chunk, h), row),
            pl.BlockSpec((1, width - 1, dxbc), head),
            pl.BlockSpec((1, h * p, n), head),
            pl.BlockSpec((width, dxbc), rep2),
            pl.BlockSpec((1, dxbc), rep2),
            pl.BlockSpec((1, h), rep2),
            pl.BlockSpec((1, h), rep2),
            pl.BlockSpec((1, h), rep2),
            pl.BlockSpec((1, di), rep2),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, di), row),
            pl.BlockSpec((1, width - 1, dxbc), head),
            pl.BlockSpec((1, h * p, n), head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, di), z.dtype),
            jax.ShapeDtypeStruct((b, width - 1, dxbc), conv_state.dtype),
            jax.ShapeDtypeStruct((b, h * p, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((width - 1, dxbc), jnp.float32),
            pltpu.VMEM((h * p, n), jnp.float32),
        ],
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_PREFILL_VMEM_BYTES,
        interpret=interpret,
        name="mamba2_prefill",
    )(z, xbc, dt, conv_state, ssm_state.reshape(b, h * p, n), conv_w,
      conv_b.reshape(1, dxbc), dt_bias.reshape(1, h), A.reshape(1, h),
      D.reshape(1, h), norm_scale.reshape(1, di))
    return y, new_conv, new_ssm.reshape(b, h, p, n)
