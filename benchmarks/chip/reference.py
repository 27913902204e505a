"""Plain float32 reference of the benchmark's models.

A straightforward ``jax.numpy`` forward pass of Mamba-1 (selective scan)
and Mamba-2 (SSD), written from the published descriptions (Gu & Dao,
arXiv:2312.00752; Dao & Gu, arXiv:2405.21060 and ``mamba_ssm``'s
``Mamba``/``Mamba2`` modules) for one token stream at a time.  It shares
no code with the serving program: no kernels, no cache, no batching, no
chunked prefill.  Weights are the benchmark's own (``weights.py``), cast
to float32 layer by layer inside the layer scan, and every matmul runs at
``highest`` precision.

It follows the published model as the configuration file states it:
every RMSNorm with ``norm_epsilon``; Mamba-2's gated RMSNorm as
``norm(y * silu(z))`` (``mamba_ssm``'s ``norm_before_gate=False``), or
``norm(y) * silu(z)`` where a configuration sets ``norm_before_gate``;
the residual stream in float32 (``residual_in_fp32``), as is everything
here.

The SSD is computed chunk by chunk (the exact "minimal SSD" algorithm of
the Mamba-2 paper, chunk length ``chunk_size``) with the state carried
between chunks; the selective scan is an associative scan over time.
Both are exact reformulations of the recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import dims

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=HIGHEST)


def _ein(spec, *ops, cast):
    return jnp.einsum(spec, *map(cast, ops), precision=HIGHEST)


def exact(a):
    """Matmul operands as they are (the float32 reference)."""
    return a


def fp8(a):
    """Matmul operands rounded to float8 e4m3, accumulated in float32:
    the reference one precision step below the served bfloat16."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _conv(x, w, b):
    """Causal depthwise conv over time: x (T, c), w (K, c), b (c)."""
    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(K)) + b


def _ssd(x, dt, A, B, C, chunk, cast):
    """y_t = sum_{s<=t} C_t.B_s exp(sum_{s<r<=t} dt_r A) dt_s x_s per head.

    x (T, H, P); dt (T, H); A (H,); B, C (T, G, N); T a multiple of
    ``chunk``.  Heads map to groups in contiguous blocks of H/G."""
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    rep = H // G
    nc = T // chunk
    xs = (x.reshape(nc, chunk, H, P), dt.reshape(nc, chunk, H),
          B.reshape(nc, chunk, G, N), C.reshape(nc, chunk, G, N))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(state, c):
        xc, dtc, Bc, Cc = c
        a = dtc * A                                   # (Q, H)
        cum = jnp.cumsum(a, axis=0)                   # (Q, H)
        seg = cum[:, None, :] - cum[None, :, :]       # (Qi, Qj, H)
        decay = jnp.exp(jnp.where(causal[:, :, None], seg, -jnp.inf))
        cb = _ein("ign,jgn->gij", Cc, Bc, cast=cast)
        cb = jnp.repeat(cb, rep, axis=0)              # (H, Qi, Qj)
        w = cb * jnp.moveaxis(decay, 2, 0) * dtc.T[:, None, :]
        y = _ein("hij,jhp->ihp", w, xc, cast=cast)
        Ch = jnp.repeat(Cc, rep, axis=1)              # (Q, H, N)
        Bh = jnp.repeat(Bc, rep, axis=1)
        y = y + _ein("ihn,hpn->ihp", Ch, state,
                     cast=cast) * jnp.exp(cum)[:, :, None]
        tail = jnp.exp(cum[-1][None, :] - cum) * dtc  # (Q, H)
        new = state * jnp.exp(cum[-1])[:, None, None] + _ein(
            "jhp,jhn->hpn", tail[:, :, None] * xc, Bh, cast=cast)
        return new, y

    _, ys = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), xs)
    return ys.reshape(T, H, P)


def _mamba2_mixer(p, h, cfg, dm, cast):
    di, H, P, G, N = dm["di"], dm["H"], dm["P"], dm["G"], dm["N"]
    zxbcdt = _mm(h, p["in_proj"]["w"], cast)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * G * N]
    dt = zxbcdt[:, 2 * di + 2 * G * N:]
    xbc = jax.nn.silu(_conv(xbc, p["conv"]["w"], p["conv"]["b"]))
    x = xbc[:, :di].reshape(-1, H, P)
    B = xbc[:, di:di + G * N].reshape(-1, G, N)
    C = xbc[:, di + G * N:].reshape(-1, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    T = h.shape[0]
    Q = cfg["chunk_size"]
    pad = -T % Q
    padded = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
              for a in (x, dt, B, C)]
    y = _ssd(*padded[:2], A, *padded[2:], Q, cast)[:T]
    y = (y + x * p["D"][:, None]).reshape(T, di)
    eps = cfg["norm_epsilon"]
    if cfg["norm_before_gate"]:
        y = _rmsnorm(y, p["norm"]["scale"], eps) * jax.nn.silu(z)
    else:
        y = _rmsnorm(y * jax.nn.silu(z), p["norm"]["scale"], eps)
    return _mm(y, p["out_proj"]["w"], cast)


def _mamba1_mixer(p, h, cfg, dm, cast):
    di, N, R = dm["di"], dm["N"], dm["R"]
    xz = _mm(h, p["in_proj"]["w"], cast)
    x, z = xz[:, :di], xz[:, di:]
    x = jax.nn.silu(_conv(x, p["conv"]["w"], p["conv"]["b"]))
    dbc = _mm(x, p["x_proj"]["w"], cast)
    dt = jax.nn.softplus(_mm(dbc[:, :R], p["dt_proj"]["w"], cast) +
                         p["dt_proj"]["b"])                   # (T, di)
    B, C = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(p["A_log"])                                  # (di, N)
    dA = jnp.exp(dt[:, :, None] * A)
    dBx = (dt * x)[:, :, None] * B[:, None, :]

    def comb(l, r):
        return l[0] * r[0], l[1] * r[0] + r[1]

    _, hs = jax.lax.associative_scan(comb, (dA, dBx), axis=0)
    y = _ein("tdn,tn->td", hs, C, cast=cast) + x * p["D"]
    return _mm(y * jax.nn.silu(z), p["out_proj"]["w"], cast)


def hidden(cfg: dict, params, tokens, cast=exact):
    """Final-norm hidden states ``(T, d_model)`` of one token stream."""
    dm = dims(cfg)
    mixer = _mamba2_mixer if cfg["family"] == "mamba2" else _mamba1_mixer
    eps = cfg["norm_epsilon"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(
        jnp.float32)

    def layer(x, p):
        p = f32(p)
        h = _rmsnorm(x, p["ln"]["scale"], eps)
        return x + mixer(p["mixer"], h, cfg, dm, cast), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rmsnorm(x, params["final_norm"]["scale"].astype(jnp.float32),
                    eps)


def logits_at(cfg: dict, params, tokens, start: int, count: int,
              cast=exact):
    """Reference logits ``(count, vocab)`` at positions ``start ..
    start+count-1`` of ``tokens`` (tied embedding unembedding).  ``cast``
    rounds every matmul operand: ``exact`` for the reference, ``fp8`` for
    the control one precision step down."""
    h = jax.lax.dynamic_slice_in_dim(hidden(cfg, params, tokens, cast),
                                     start, count)
    table = params["embed"]["table"].astype(jnp.float32)
    return _ein("td,vd->tv", h, table, cast=cast)
