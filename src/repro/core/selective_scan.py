"""Mamba-1 selective scan (the paper's other profiled model).

Mamba-1's NPU bottleneck is its activations (Swish/Softplus -> ActiBA), not
cumsum; the scan itself is a per-channel linear recurrence

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * u_t ,   y_t = C_t . h_t + D u_t

which we implement three ways:

* ``sequential``   — the default for prefill, serving and training: a
                     ``jax.lax.scan`` over time whose carry is the
                     ``(b, d, n)`` state, held as ``(b, n, d)`` so
                     ``d_inner`` lies on the lanes.  Each step forms its
                     own decay and input, so nothing of size
                     ``(b, l, d, n)`` is built; it is exact in fp32 and the
                     oracle the other two are tested against.
* ``associative``  — ``jax.lax.associative_scan`` (log-depth, XLA).  Not
                     the default: its decay, input and up/down-sweep
                     partials are fp32 ``(b, l, d, n)`` arrays in HBM, and
                     with a decay that varies per ``(d, n)`` every step
                     there is no matmul for the MXU to win that back.
* ``chunked``      — CumBA-style: within a chunk the decay products
                     ``prod_{k=j+1..t} a_k = exp(segsum(log a))`` are the same
                     1-semiseparable structure SSD uses, so the intra-chunk
                     part becomes matmuls (this is the Mamba-1 analogue of the
                     paper's CumSum->MatMul remap; it is exact in fp32).

``initial_state`` + ``return_final_state`` make every mode resumable:
feeding a sequence in slices, threading each call's final ``h`` into the
next call, matches one whole-sequence call (chunked prefill — see
``models/base.py: DecodeAPI.prefill_chunk``).

Shapes (Mamba-1 convention):
  u:     (batch, seqlen, dinner)
  delta: (batch, seqlen, dinner)   -- post-softplus
  A:     (dinner, dstate)          -- negative
  B, C:  (batch, seqlen, dstate)
  D:     (dinner,)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import segsum as xsegsum
from repro.core.xamba import XambaConfig

Array = jax.Array


def selective_scan(u: Array, delta: Array, A: Array, B: Array, C: Array,
                   D: Optional[Array] = None, *,
                   mode: str = "sequential",
                   chunk_size: int = 128,
                   initial_state: Optional[Array] = None,
                   xamba: XambaConfig = XambaConfig(),
                   return_final_state: bool = False):
    """Returns y: (b, l, d) [and final state (b, d, n)]."""
    b, l, d = u.shape
    n = A.shape[-1]
    uf = u.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    h0 = (jnp.zeros((b, d, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    if mode == "sequential":
        y, hT = _scan_over_time(u, delta, Af, B, C, h0)
    elif mode == "associative":
        dA, dBu = _discretize(uf, delta, Af, B)
        decay = jnp.exp(dA)                                # (b, l, d, n)

        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, b1 * a2 + b2

        a_sc, h_sc = jax.lax.associative_scan(
            combine, (decay, dBu), axis=1)
        h_all = h_sc + a_sc * h0[:, None]                  # fold initial state
        y = jnp.einsum("bldn,bln->bld", h_all, C.astype(jnp.float32))
        hT = h_all[:, -1]
    elif mode == "chunked":
        dA, dBu = _discretize(uf, delta, Af, B)
        Cf = C.astype(jnp.float32)
        # Pad to a chunk multiple with dt=0 steps (decay=1, input=0): exact
        # no-ops for outputs and final state, so any prefill-chunk length
        # works (mirrors core/ssd.py).
        l_orig = l
        pad = (-l) % chunk_size
        if pad:
            dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dBu = jnp.pad(dBu, ((0, 0), (0, pad), (0, 0), (0, 0)))
            Cf = jnp.pad(Cf, ((0, 0), (0, pad), (0, 0)))
            l = l + pad
        c = l // chunk_size
        # (b, c, L, d, n)
        dA_c = dA.reshape(b, c, chunk_size, d, n)
        dBu_c = dBu.reshape(b, c, chunk_size, d, n)
        C_c = Cf.reshape(b, c, chunk_size, n)
        # intra-chunk: h_t = sum_j exp(segsum)(t,j) dBu_j  (+ carry term)
        a_perm = jnp.transpose(dA_c, (0, 1, 3, 4, 2))      # (b, c, d, n, L)
        S = xsegsum.segsum(a_perm, mode=xamba.cumba)       # (b, c, d, n, L, L)
        Lmat = jnp.exp(S)
        h_intra = jnp.einsum("bcdnts,bcsdn->bctdn", Lmat, dBu_c)
        # chunk-level recurrence on the running state
        cum = xsegsum.cumsum(a_perm, axis=-1, mode=xamba.cumba)  # (b,c,d,n,L)
        chunk_decay = jnp.exp(cum[..., -1])                # (b, c, d, n)
        chunk_state = h_intra[:, :, -1]                    # (b, c, d, n)

        def step(h, t_in):
            cd, cs = t_in
            return cd * h + cs, h                          # emit state *entering* chunk

        (hT, h_enter) = jax.lax.scan(
            step, h0, (jnp.moveaxis(chunk_decay, 1, 0),
                       jnp.moveaxis(chunk_state, 1, 0)))
        h_enter = jnp.moveaxis(h_enter, 0, 1)              # (b, c, d, n)
        decay_in = jnp.exp(cum)                            # (b, c, d, n, L)
        h_all = h_intra + jnp.transpose(decay_in, (0, 1, 4, 2, 3)) * h_enter[:, :, None]
        y = jnp.einsum("bctdn,bctn->bctd", h_all, C_c)
        y = y.reshape(b, l, d)[:, :l_orig]
    else:
        raise ValueError(f"unknown selective_scan mode {mode!r}")

    if D is not None:
        y = y + uf * D.astype(jnp.float32)[None, None]
    y = y.astype(u.dtype)
    if return_final_state:
        return y, hT
    return y


def _discretize(uf: Array, delta: Array, Af: Array, B: Array
                ) -> Tuple[Array, Array]:
    """ZOH on A, Euler on B as in Mamba: the (b, l, d, n) log-decay
    ``delta * A`` and input ``delta * u * B`` of every step at once."""
    df = delta.astype(jnp.float32)
    dA = df[..., None] * Af[None, None]
    dBu = (df * uf)[..., None] * B.astype(jnp.float32)[:, :, None, :]
    return dA, dBu


def _scan_over_time(u: Array, delta: Array, Af: Array, B: Array, C: Array,
                    h0: Array) -> Tuple[Array, Array]:
    """One step per token with the state as the loop's carry.

    Each step reads its ``(b, d)`` slices of ``u``/``delta`` and ``(b, n)``
    slices of ``B``/``C``, and forms its decay, input, state update and
    output over the state alone, so no ``(b, l, d, n)`` array exists.  The
    carry is ``(b, n, d)``: ``d_inner`` on the lanes, ``d_state`` on the
    sublanes.  Returns y (b, l, d) in f32 and the final state (b, d, n).
    """
    At = Af.T                                              # (n, d)

    def step(h, t_in):
        u_t, dt_t, B_t, C_t = (x.astype(jnp.float32) for x in t_in)
        h = (jnp.exp(dt_t[:, None, :] * At[None]) * h
             + (dt_t * u_t)[:, None, :] * B_t[:, :, None])
        return h, jnp.sum(h * C_t[:, :, None], axis=1)

    ins = tuple(jnp.moveaxis(x, 1, 0) for x in (u, delta, B, C))
    hT, ys = jax.lax.scan(step, jnp.swapaxes(h0, 1, 2), ins)
    return jnp.moveaxis(ys, 0, 1), jnp.swapaxes(hT, 1, 2)


def selective_scan_decode_step(state: Array, u_t: Array, delta_t: Array,
                               A: Array, B_t: Array, C_t: Array,
                               D: Optional[Array] = None, *,
                               mode: str = "cumba") -> Tuple[Array, Array]:
    """One-token recurrent update, XambaConfig-dispatched (``naive`` =
    mul + ReduceSum, ``cumba`` = MXU dot_general, ``pallas*`` = the fused
    Pallas step kernel).  state: (b, d, n); u_t, delta_t: (b, d);
    B_t, C_t: (b, n)."""
    if mode in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return kops.sscan_step(state, u_t, delta_t, A, B_t, C_t, D,
                               interpret=(mode == "pallas_interpret"))
    dtf = delta_t.astype(jnp.float32)
    decay = jnp.exp(dtf[..., None] * A.astype(jnp.float32)[None])
    dBu = (dtf * u_t.astype(jnp.float32))[..., None] * B_t.astype(jnp.float32)[:, None, :]
    new_state = state.astype(jnp.float32) * decay + dBu
    Cf = C_t.astype(jnp.float32)
    if mode == "naive":
        y = jnp.sum(new_state * Cf[:, None, :], axis=-1)
    else:
        y = jnp.einsum("bdn,bn->bd", new_state, Cf,
                       preferred_element_type=jnp.float32)
    if D is not None:
        y = y + u_t.astype(jnp.float32) * D.astype(jnp.float32)[None]
    return new_state, y.astype(u_t.dtype)
