"""Mamba-2 SSD (structured state-space duality), technique-parameterized.

Faithful to Listing 1 of Dao & Gu (2024) — the exact algorithm the paper
profiles on the NPU — with every XAMBA remapping exposed:

* the in-chunk ``segsum`` (the paper's dominant ``CumSum_b`` bottleneck) runs
  in ``naive`` / ``cumba`` / ``pallas`` mode (see ``core/segsum.py``);
* every contraction runs in ``naive`` (mul + ReduceSum — the op chain the NPU
  compiler produced and the paper measured) or ``reduba`` (dot_general / MXU)
  mode (see ``core/reduce.py``);
* a fully fused Pallas intra-chunk kernel (``kernels/ssd_chunk.py``) is used
  when ``cumba`` mode is ``pallas*`` and shapes allow.

Shapes follow the Mamba-2 convention:
  x:  (batch, seqlen, nheads, headdim)        -- values
  dt: (batch, seqlen, nheads)                 -- softplus'd step sizes
  A:  (nheads,)                                -- negative decay rates
  B:  (batch, seqlen, ngroups, dstate)        -- input projection (like K)
  C:  (batch, seqlen, ngroups, dstate)        -- output projection (like Q)

All SSD internals run in float32 (segsum differences are cancellation-prone);
inputs/outputs keep the caller's dtype.

``initial_state`` + ``return_final_state`` make the inter-chunk recurrence
resumable: feeding a sequence in slices, threading each call's final state
into the next call's ``initial_state``, is numerically equivalent to one
whole-sequence call (the serve engines' chunked prefill is exactly this —
see ``models/base.py: DecodeAPI.prefill_chunk``).
"""
from __future__ import annotations

import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import reduce as xreduce
from repro.core import segsum as xsegsum
from repro.core.xamba import XambaConfig

Array = jax.Array

log = logging.getLogger("repro.ssd")


def _split_chunks(x: Array, chunk: int) -> Array:
    b, l = x.shape[0], x.shape[1]
    assert l % chunk == 0, f"seqlen {l} not divisible by chunk {chunk}"
    return x.reshape((b, l // chunk, chunk) + x.shape[2:])


def _merge_chunks(x: Array) -> Array:
    b, c, l = x.shape[:3]
    return x.reshape((b, c * l) + x.shape[3:])


def ssd(x: Array, dt: Array, A: Array, B: Array, C: Array, *,
        chunk_size: int = 256,
        initial_state: Optional[Array] = None,
        xamba: XambaConfig = XambaConfig(),
        return_final_state: bool = False,
        matmul_dtype=None,
        ) -> Array | Tuple[Array, Array]:
    """Chunked SSD forward pass. Returns y: (batch, seqlen, nheads, headdim)
    and optionally the final state (batch, nheads, headdim, dstate)."""
    in_dtype = x.dtype
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert h % g == 0, f"nheads {h} not divisible by ngroups {g}"

    # Pad the sequence to a chunk multiple: dt=0 on padded steps makes them
    # exact no-ops for both the outputs we keep and the final state.
    l_orig = l
    pad = (-l) % chunk_size
    if pad:
        zpad = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                                 [(0, 0)] * (t.ndim - 2))
        x, dt, B, C = zpad(x), zpad(dt), zpad(B), zpad(C)
        l = l + pad

    cs_mode, rd_mode = xamba.cumba, xamba.reduba
    store_dtype = matmul_dtype or jnp.float32

    # Discretize: per-step log decay and dt-scaled input.  The wide value /
    # B / C streams are stored in ``matmul_dtype`` (bf16 in perf mode —
    # halves the dominant HBM traffic); decays stay fp32 (cancellation).
    dt_f = dt.astype(jnp.float32)
    a = dt_f * A.astype(jnp.float32)[None, None, :]        # (b, l, h), negative
    xdt = (x.astype(jnp.float32) * dt_f[..., None]).astype(store_dtype)

    # Chunk: (b, c, L, ...)
    a_c = _split_chunks(a, chunk_size)                      # (b, c, L, h)
    a_c = jnp.transpose(a_c, (0, 3, 1, 2))                  # (b, h, c, L)
    x_c = _split_chunks(xdt, chunk_size)                    # (b, c, L, h, p)
    B_c = _split_chunks(B.astype(store_dtype), chunk_size)  # (b, c, L, g, n)
    C_c = _split_chunks(C.astype(store_dtype), chunk_size)  # (b, c, L, g, n)

    hpg = h // g  # heads per group

    # Chunk-parallel layout (distributed): pin the CHUNK axis onto the mesh's
    # sequence axes so each device owns whole chunks — the intra-chunk pass
    # (the L x L work, the paper's CumSum_b home) then runs with ZERO
    # collectives instead of XLA re-sharding (b, seq) slices chunk-by-chunk.
    from repro.distributed import api as dist_api
    lay = dist_api.current_layout()
    chunk_parallel = lay is not None and lay.get("seq") is not None and \
        (l // chunk_size) > 1
    if chunk_parallel:
        x_c = dist_api.constrain_dims(x_c, {0: "batch", 1: "seq"})
        B_c = dist_api.constrain_dims(B_c, {0: "batch", 1: "seq"})
        C_c = dist_api.constrain_dims(C_c, {0: "batch", 1: "seq"})
        a_c = dist_api.constrain_dims(a_c, {0: "batch", 2: "seq"})

    A_cum = xsegsum.cumsum(a_c, axis=-1, mode=cs_mode)      # (b, h, c, L)

    # ---- 1+2. intra-chunk (diagonal blocks) + per-chunk states -----------
    # Heads are processed GROUPED (b, g, hpg, ...) so the group-shared CB
    # scores broadcast against per-head decays instead of being materialized
    # hpg times (beyond-paper optimization; algebraically identical).
    mm_dtype = matmul_dtype or jnp.float32

    def _intra(x_k, a_k, cs_k, B_k, C_k):
        """One chunk: x (b,L,h,p), a/cs (b,h,L), B/C (b,L,g,n) ->
        (y_diag (b,L,h,p), states (b,h,p,n))."""
        bq, Lk = x_k.shape[0], x_k.shape[1]
        seg = cs_k[..., :, None] - cs_k[..., None, :]       # (b, h, L, L)
        tril = jnp.tril(jnp.ones((seg.shape[-1],) * 2, bool))
        if cs_mode == "naive":
            seg = xsegsum.segsum(a_k, mode="naive")
        L_mat = jnp.exp(jnp.where(tril, seg, -1e30))        # (b, h, L, L)
        L_g = L_mat.reshape(bq, g, hpg, Lk, Lk).astype(mm_dtype)
        CB = xreduce.contract("blgn,bsgn->bgls", C_k.astype(mm_dtype),
                              B_k.astype(mm_dtype), mode=rd_mode)
        M = CB[:, :, None] * L_g                            # (b, g, q, L, S)
        x_r = x_k.reshape(bq, Lk, g, hpg, -1).astype(mm_dtype)
        y_k = xreduce.contract("bgqls,bsgqp->blgqp", M, x_r, mode=rd_mode)
        y_k = y_k.reshape(bq, Lk, h, -1).astype(jnp.float32)
        dstates = jnp.exp(cs_k[..., -1:] - cs_k)            # (b, h, L)
        xw = x_r * jnp.transpose(dstates, (0, 2, 1)) \
            .reshape(bq, Lk, g, hpg)[..., None].astype(mm_dtype)
        st_k = xreduce.contract("blgn,blgqp->bgqpn", B_k.astype(mm_dtype),
                                xw, mode=rd_mode)
        st_k = st_k.reshape(bq, h, st_k.shape[-2], n).astype(jnp.float32)
        return y_k, st_k

    nchunks_ = l // chunk_size
    # Stream chunks through a scan only when NOT chunk-parallel: with the
    # chunk axis sharded, the batched path is already one-chunk-per-device
    # memory AND avoids serializing across the mesh.
    use_scan = nchunks_ > 8 and not chunk_parallel
    # 64-multiples are MXU-viable (the compiler pads the (L, L) decay
    # block's lane dim); below that the padding overhead wins, so fall
    # back to the XLA chain — loudly, at trace time, so a pallas request
    # never silently runs unfused.
    use_kernel = cs_mode in ("pallas", "pallas_interpret")
    if use_kernel and chunk_size % 64:
        log.warning("ssd_chunk kernel (%s) skipped: chunk %d not a multiple "
                    "of 64 — running the XLA chain", cs_mode, chunk_size)
        use_kernel = False
    if use_kernel:
        from repro.kernels import ops as kops
        y_diag, states = kops.ssd_chunk(
            x_c, a_c, A_cum, B_c, C_c,
            interpret=(cs_mode == "pallas_interpret"))
    elif use_scan:
        xs = (jnp.moveaxis(x_c, 1, 0), jnp.moveaxis(a_c, 2, 0),
              jnp.moveaxis(A_cum, 2, 0), jnp.moveaxis(B_c, 1, 0),
              jnp.moveaxis(C_c, 1, 0))

        @jax.checkpoint
        def body(_, blk):
            return None, _intra(*blk)

        from repro.core import accounting
        _, (y_st, st_st) = jax.lax.scan(
            body, None, xs, unroll=accounting.inner_unroll(nchunks_))
        y_diag = jnp.moveaxis(y_st, 0, 1)                   # (b, c, L, h, p)
        states = jnp.moveaxis(st_st, 0, 1)                  # (b, c, h, p, n)
    else:
        # batched over chunks: same math as _intra with a chunk axis.
        xs_all = (x_c, jnp.moveaxis(a_c, 2, 1), jnp.moveaxis(A_cum, 2, 1),
                  B_c, C_c)
        y_diag, states = jax.vmap(_intra, in_axes=(1, 1, 1, 1, 1),
                                  out_axes=(1, 1))(*xs_all)

    # ---- 3. inter-chunk recurrence (sequential over chunks) --------------
    nchunks = states.shape[1]
    chunk_decay_log = A_cum[..., -1]                        # (b, h, c) total decay per chunk
    if initial_state is None:
        init = jnp.zeros((b, h, p, n), jnp.float32)
    else:
        init = initial_state.astype(jnp.float32)

    # Associative scan over chunks: s_c = exp(d_c) * s_{c-1} + states_c.
    decays = jnp.exp(chunk_decay_log)                       # (b, h, c)
    dec_t = jnp.moveaxis(decays, -1, 0)                     # (c, b, h)
    st_t = jnp.moveaxis(states, 1, 0)                       # (c, b, h, p, n)

    def combine(carry, nxt):
        d1, s1 = carry
        d2, s2 = nxt
        return d1 * d2, s1 * d2[..., None, None] + s2

    dec_scan, st_scan = jax.lax.associative_scan(combine, (dec_t, st_t), axis=0)
    # states *entering* chunk c = scanned state of chunk c-1 (+ decayed init).
    prev_states = jnp.concatenate([init[None], st_scan[:-1]], axis=0)
    if initial_state is not None and nchunks > 1:
        prev_states = prev_states.at[1:].add(
            init[None] * dec_scan[:-1][..., None, None])
    final_state = st_scan[-1]
    if initial_state is not None:
        final_state = final_state + init * dec_scan[-1][..., None, None]
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # (b, c, h, p, n)

    # ---- 4. state -> output ----------------------------------------------
    state_decay_out = jnp.exp(A_cum)                        # (b, h, c, L)
    # grouped: C (b,c,L,g,n) x states (b,c,g,q,p,n) -> (b,c,L,g,q,p)
    ps_g = prev_states.reshape(b, nchunks, g, hpg, p, n).astype(mm_dtype)
    y_off = xreduce.contract("bclgn,bcgqpn->bclgqp", C_c.astype(mm_dtype),
                             ps_g, mode=rd_mode)
    y_off = y_off.reshape(b, nchunks, chunk_size, h, p).astype(jnp.float32)
    sdo = jnp.transpose(state_decay_out, (0, 2, 3, 1))      # (b, c, L, h)
    y_off = y_off * sdo[..., None]

    y = _merge_chunks(y_diag + y_off).astype(in_dtype)
    if pad:
        y = y[:, :l_orig]
    if return_final_state:
        return y, final_state.astype(jnp.float32)
    return y


def ssd_reference(x, dt, A, B, C, *, initial_state=None):
    """O(L) sequential recurrence oracle (exact semantics, slow).

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ;  y_t = C_t . h_t
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hpg = h // g
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(B.astype(jnp.float32), hpg, axis=2)  # (b, l, h, n)
    Cf = jnp.repeat(C.astype(jnp.float32), hpg, axis=2)

    state0 = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
              else initial_state.astype(jnp.float32))

    def step(state, t_in):
        xt, dtt, Bt, Ct = t_in                      # (b,h,p), (b,h), (b,h,n) x2
        decay = jnp.exp(dtt * Af[None, :])          # (b, h)
        dBx = (dtt[..., None, None] * Bt[:, :, None, :] * xt[..., None])
        state = state * decay[..., None, None] + dBx
        yt = jnp.einsum("bhpn,bhn->bhp", state, Ct)
        return state, yt

    ins = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
           jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    final, ys = jax.lax.scan(step, state0, ins)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def ssd_decode_step(state: Array, x_t: Array, dt_t: Array, A: Array,
                    B_t: Array, C_t: Array, *,
                    mode: str = "cumba") -> Tuple[Array, Array]:
    """Single-token recurrent update (the paper's Step-1 decode model),
    XambaConfig-dispatched like the prefill path:

    * ``naive``  — broadcast-multiply + ReduceSum chains (the dense op
      structure the NPU compiler produced and the paper measured);
    * ``cumba``  — the state->output contraction as one MXU ``dot_general``
      over grouped heads (no materialized B/C head-repeat);
    * ``pallas`` / ``pallas_interpret`` — the fused Pallas step kernel
      (``kernels/decode_step.py``).

    state: (b, h, p, n); x_t: (b, h, p); dt_t: (b, h);
    B_t, C_t: (b, g, n).  Returns (new_state, y_t: (b, h, p)).
    """
    if mode in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return kops.ssd_step(state, x_t, dt_t, A, B_t, C_t,
                             interpret=(mode == "pallas_interpret"))
    b, h, p, n = state.shape
    g = B_t.shape[1]
    hpg = h // g
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None, :])   # (b, h)
    # Grouped layout: B/C broadcast against per-head streams instead of
    # being materialized h/g times (matches the prefill path's grouping).
    st_g = state.astype(jnp.float32).reshape(b, g, hpg, p, n)
    x_g = x_t.astype(jnp.float32).reshape(b, g, hpg, p)
    dt_g = dtf.reshape(b, g, hpg)
    Bf = B_t.astype(jnp.float32)                            # (b, g, n)
    Cf = C_t.astype(jnp.float32)
    dBx = (dt_g[..., None] * x_g)[..., None] * Bf[:, :, None, None, :]
    new_g = st_g * decay.reshape(b, g, hpg)[..., None, None] + dBx
    if mode == "naive":
        y_g = xreduce.contract("bgqpn,bgn->bgqp", new_g, Cf, mode="naive")
    else:
        y_g = xreduce.contract("bgqpn,bgn->bgqp", new_g, Cf, mode="reduba")
    new_state = new_g.reshape(b, h, p, n)
    return new_state, y_g.reshape(b, h, p).astype(x_t.dtype)
