"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.pwl import PWLTable, eval_pwl

Array = jax.Array


def cumsum_last_ref(x: Array) -> Array:
    return jnp.cumsum(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def reduce_rows_ref(x: Array) -> Array:
    return jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)


def pwl_activate_ref(x: Array, table: PWLTable) -> Array:
    return eval_pwl(table, x)


def matmul_pwl_ref(x: Array, w: Array, table: PWLTable,
                   v: Optional[Array] = None) -> Array:
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    out = eval_pwl(table, acc)
    if v is not None:
        out = out * jnp.dot(x, v, preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def qmatmul_ref(x: Array, q: Array, scale: Array,
                table: Optional[PWLTable] = None,
                qv: Optional[Array] = None,
                vscale: Optional[Array] = None) -> Array:
    """W8 dequant-matmul oracle: dequantize-then-dot in fp32 (shapes as
    kernels/qmatmul.py; per-channel scales commute with the contraction,
    so this equals the kernel's drain-phase rescale)."""
    deq = q.astype(jnp.float32) * scale.reshape(1, -1)
    acc = jnp.dot(x.astype(jnp.float32), deq,
                  preferred_element_type=jnp.float32)
    out = eval_pwl(table, acc) if table is not None else acc
    if qv is not None:
        deqv = qv.astype(jnp.float32) * vscale.reshape(1, -1)
        out = out * jnp.dot(x.astype(jnp.float32), deqv,
                            preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def ssd_chunk_ref(x_c: Array, a_c: Array, A_cum: Array, B_c: Array,
                  C_c: Array):
    """Intra-chunk SSD oracle.  Shapes as in kernels/ssd_chunk.py."""
    b, c, L, h, p = x_c.shape
    g = B_c.shape[3]
    hpg = h // g
    xf = x_c.astype(jnp.float32)
    cs = A_cum.astype(jnp.float32)                         # (b, h, c, L)
    Bh = jnp.repeat(B_c.astype(jnp.float32), hpg, axis=3)  # (b, c, L, h, n)
    Ch = jnp.repeat(C_c.astype(jnp.float32), hpg, axis=3)

    seg = cs[..., :, None] - cs[..., None, :]              # (b, h, c, L, L)
    tril = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.where(tril, jnp.exp(jnp.where(tril, seg, 0.0)), 0.0)

    scores = jnp.einsum("bclhn,bcshn->bhcls", Ch, Bh)
    y = jnp.einsum("bhcls,bcshp->bclhp", scores * decay, xf)

    dstate = jnp.exp(cs[..., -1:] - cs)                    # (b, h, c, L)
    dstate = jnp.transpose(dstate, (0, 2, 3, 1))           # (b, c, L, h)
    states = jnp.einsum("bclhp,bclh,bclhn->bchpn", xf, dstate, Bh)
    return y, states


def attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
                  window: Optional[int] = None,
                  scale: Optional[float] = None) -> Array:
    """Standard softmax attention with GQA/causal/sliding-window semantics.

    q: (b, hq, Lq, d); k, v: (b, hkv, Lk, d).
    """
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    qpg = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    kq = jnp.repeat(k, qpg, axis=1)
    vq = jnp.repeat(v, qpg, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                   kq.astype(jnp.float32))
    q_ids = jnp.arange(lq)[:, None]
    k_ids = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= k_ids <= q_ids
    if window is not None:
        mask &= k_ids > q_ids - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def _conv_shift_ref(conv_state, x, w, b):
    """One causal-conv decode step: conv_state (bt,w-1,d), x (bt,d)."""
    win = jnp.concatenate([conv_state.astype(jnp.float32),
                           x.astype(jnp.float32)[:, None]], axis=1)
    out = jnp.sum(win * w.astype(jnp.float32)[None], axis=1) + \
        b.astype(jnp.float32)[None]
    return out, win[:, 1:]


def ssd_step_ref(state, x_t, dt_t, A, B_t, C_t):
    """Single-token SSD recurrence oracle (shapes as core.ssd)."""
    b, h, p, n = state.shape
    hpg = h // B_t.shape[1]
    Bh = jnp.repeat(B_t.astype(jnp.float32), hpg, axis=1)
    Ch = jnp.repeat(C_t.astype(jnp.float32), hpg, axis=1)
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None, :])
    dBx = dtf[..., None, None] * Bh[:, :, None, :] * \
        x_t.astype(jnp.float32)[..., None]
    new = state.astype(jnp.float32) * decay[..., None, None] + dBx
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch)
    return new, y.astype(x_t.dtype)


def sscan_step_ref(state, u_t, delta_t, A, B_t, C_t, D=None):
    """Single-token selective-scan oracle (shapes as core.selective_scan)."""
    dtf = delta_t.astype(jnp.float32)
    decay = jnp.exp(dtf[..., None] * A.astype(jnp.float32)[None])
    dBu = (dtf * u_t.astype(jnp.float32))[..., None] * \
        B_t.astype(jnp.float32)[:, None, :]
    new = state.astype(jnp.float32) * decay + dBu
    y = jnp.einsum("bdn,bn->bd", new, C_t.astype(jnp.float32))
    if D is not None:
        y = y + u_t.astype(jnp.float32) * D.astype(jnp.float32)[None]
    return new, y.astype(u_t.dtype)


def _gated_norm_ref(y, z, scale, *, eps, ngroups, silu):
    """Mamba-2's gated RMSNorm on (..., di) in float32, as
    ``mamba_ssm``'s ``RMSNormGated``: ``norm(y * silu(z))`` over groups
    of di / ngroups channels."""
    y = y.astype(jnp.float32) * silu(z.astype(jnp.float32))
    yg = y.reshape(y.shape[:-1] + (ngroups, y.shape[-1] // ngroups))
    ms = jnp.mean(yg * yg, axis=-1, keepdims=True)
    return (yg * jax.lax.rsqrt(ms + eps)).reshape(y.shape) * \
        scale.astype(jnp.float32)


def mamba2_step_ref(z, xbc, dt, conv_state, ssm_state, conv_w, conv_b,
                    dt_bias, A, D, norm_scale, *, ngroups, head_dim, eps,
                    silu=jax.nn.silu, softplus=jax.nn.softplus):
    """Fused Mamba-2 decode-step oracle (shapes as kernels.decode_step)."""
    b, di = z.shape
    g, p = ngroups, head_dim
    n = ssm_state.shape[-1]
    h = dt.shape[1]
    conv_out, new_conv = _conv_shift_ref(conv_state, xbc, conv_w, conv_b)
    act = silu(conv_out)
    xs = act[:, :di].reshape(b, h, p)
    B = act[:, di:di + g * n].reshape(b, g, n)
    C = act[:, di + g * n:].reshape(b, g, n)
    dt_f = softplus(dt.astype(jnp.float32) +
                    dt_bias.astype(jnp.float32)[None])
    new, y = ssd_step_ref(ssm_state, xs, dt_f, A, B, C)
    y = y + D.astype(jnp.float32)[None, :, None] * xs
    out = _gated_norm_ref(y.reshape(b, di), z, norm_scale, eps=eps,
                          ngroups=g, silu=silu)
    return (out.astype(z.dtype), new_conv.astype(conv_state.dtype),
            new.astype(jnp.float32))


def mamba2_prefill_ref(z, xbc, dt, conv_state, ssm_state, conv_w, conv_b,
                       dt_bias, A, D, norm_scale, *, ngroups, head_dim,
                       eps, silu=jax.nn.silu, softplus=jax.nn.softplus):
    """Fused prefill-pipeline oracle (shapes as kernels.prefill_chunk):
    plain-jnp conv + activations feeding the exact sequential SSD
    recurrence (``core.ssd.ssd_reference``), then the gated-norm
    epilogue.  z: (b, l, di); xbc: (b, l, dxbc); dt raw (b, l, h)."""
    from repro.core.ssd import ssd_reference
    b, l, di = z.shape
    g, p = ngroups, head_dim
    h = dt.shape[-1]
    n = (xbc.shape[-1] - di) // (2 * g)
    width = conv_w.shape[0]
    win = jnp.concatenate([conv_state.astype(jnp.float32),
                           xbc.astype(jnp.float32)], axis=1)
    conv = sum(win[:, i:i + l] * conv_w.astype(jnp.float32)[i]
               for i in range(width)) + conv_b.astype(jnp.float32)
    act = silu(conv)
    xs = act[..., :di].reshape(b, l, h, p)
    B = act[..., di:di + g * n].reshape(b, l, g, n)
    C = act[..., di + g * n:].reshape(b, l, g, n)
    dt_f = softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    y, new_ssm = ssd_reference(xs, dt_f, A, B, C, initial_state=ssm_state)
    y = y + xs * D.astype(jnp.float32)[None, None, :, None]
    out = _gated_norm_ref(y.reshape(b, l, di), z, norm_scale, eps=eps,
                          ngroups=g, silu=silu)
    return (out, win[:, l:].astype(conv_state.dtype),
            new_ssm.astype(jnp.float32))


def mamba1_step_ref(xs_raw, z, conv_state, ssm_state, conv_w, conv_b,
                    xproj_w, dtproj_w, dtproj_b, A, D, *, dt_rank,
                    silu=jax.nn.silu, softplus=jax.nn.softplus):
    """Fused Mamba-1 decode-step oracle (shapes as kernels.decode_step)."""
    n = ssm_state.shape[-1]
    r = dt_rank
    conv_out, new_conv = _conv_shift_ref(conv_state, xs_raw, conv_w, conv_b)
    xs = silu(conv_out)
    dbc = jnp.dot(xs, xproj_w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    dt_low, B, C = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt_f = softplus(jnp.dot(dt_low, dtproj_w.astype(jnp.float32),
                            preferred_element_type=jnp.float32) +
                    dtproj_b.astype(jnp.float32)[None])
    new, y = sscan_step_ref(ssm_state, xs, dt_f, A, B, C, D)
    out = y * silu(z.astype(jnp.float32))
    return (out.astype(z.dtype), new_conv.astype(conv_state.dtype),
            new.astype(jnp.float32))


def rglru_step_ref(u, gate, conv_state, h_state, conv_w, conv_b, rg_w,
                   rg_b, ig_w, ig_b, lam, *, sigmoid=jax.nn.sigmoid,
                   softplus=jax.nn.softplus, gelu=None):
    """Fused RG-LRU decode-step oracle (shapes as kernels.decode_step)."""
    from functools import partial
    gelu = gelu or partial(jax.nn.gelu, approximate=True)
    from repro.kernels.common import RG_LRU_C
    u_c, new_conv = _conv_shift_ref(conv_state, u, conv_w, conv_b)
    r = sigmoid(jnp.dot(u_c, rg_w.astype(jnp.float32)) +
                rg_b.astype(jnp.float32)[None])
    i = sigmoid(jnp.dot(u_c, ig_w.astype(jnp.float32)) +
                ig_b.astype(jnp.float32)[None])
    log_a = -RG_LRU_C * softplus(lam.astype(jnp.float32))[None] * r
    a = jnp.exp(log_a)
    gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * \
        (i * u_c)
    h_new = a * h_state.astype(jnp.float32) + gated_in
    out = h_new * gelu(gate.astype(jnp.float32))
    return (out.astype(u.dtype), new_conv.astype(conv_state.dtype),
            h_new.astype(jnp.float32))


def rg_lru_scan_ref(a: Array, b: Array) -> Array:
    """h_t = a_t h_{t-1} + b_t via lax.scan (exact sequential semantics)."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)

    def step(h, t_in):
        at, bt = t_in
        h = at * h + bt
        return h, h

    h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(af, 1, 0),
                                    jnp.moveaxis(bf, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)
