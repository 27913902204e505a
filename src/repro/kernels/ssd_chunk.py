"""Fused SSD intra-chunk Pallas kernel (CumBA inside the hot loop).

One grid step processes one (batch, chunk, head) cell entirely in VMEM:

    cs      = A_cum row (precomputed prefix decay, fp32)
    L       = exp(segsum)   -- via the CumBA broadcast-difference of ``cs``
    scores  = (C @ B^T) * L          (MXU, (L, L))
    y_diag  = scores @ x             (MXU, (L, p))
    state   = (x * decay).T @ B      (MXU, (p, n))  -- the chunk's outgoing state

i.e. the paper's CumSum_b bottleneck *and* the three einsum contractions
(ReduBA) fuse into a single kernel with zero intermediate HBM traffic — the
(L, L) decay matrix never leaves VMEM.  The inter-chunk recurrence stays
outside (associative scan over ~L/chunk terms, negligible).

Shapes (fp32 in, native SSD convention, heads-per-group broadcast handled by
the BlockSpec index map, so grouped B/C are read once per group):
  x_c:   (b, c, L, h, p)   dt-scaled values
  a_c:   (b, h, c, L)      per-step log decay
  A_cum: (b, h, c, L)      inclusive cumsum of a_c
  B_c:   (b, c, L, g, n)
  C_c:   (b, c, L, g, n)
Outputs:
  y_diag: (b, c, L, h, p)
  states: (b, c, h, p, n)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

Array = jax.Array


def _ssd_chunk_kernel(x_ref, csr_ref, csc_ref, b_ref, c_ref, y_ref, st_ref):
    cs_r = csr_ref[0, 0, 0].astype(jnp.float32)             # (1, L) row
    cs_c = csc_ref[0, 0, 0].astype(jnp.float32)             # (L, 1) column
    x = x_ref[0, 0, 0].astype(jnp.float32)                  # (L, p)
    B = b_ref[0, 0, 0].astype(jnp.float32)                  # (L, n)
    C = c_ref[0, 0, 0].astype(jnp.float32)                  # (L, n)
    L = x.shape[0]

    # CumBA segsum: S_ij = cs_i - cs_j, masked above the diagonal.
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tril = rows >= cols
    seg = cs_c - cs_r
    decay = jnp.where(tril, jnp.exp(jnp.where(tril, seg, 0.0)), 0.0)

    scores = common.dot(C, B, ((1,), (1,)))                 # MXU, C @ B^T
    y = common.dot(scores * decay, x)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # Outgoing chunk state: sum_l B_l (exp(cs_last - cs_l)) x_l
    xw = x * jnp.exp(cs_c[L - 1:L] - cs_c)                  # (L, p)
    st = common.dot(xw, B, ((0,), (0,)))                    # (p, n)
    st_ref[0, 0, 0] = st.astype(st_ref.dtype)


def ssd_chunk(x_c: Array, a_c: Array, A_cum: Array, B_c: Array, C_c: Array,
              *, interpret: bool = False):
    """Run the fused intra-chunk pass. See module docstring for shapes.

    The kernel sees head- and group-major copies (``(b, c, h, L, p)``,
    ``(b, c, g, L, n)``) and the prefix sums as both a row and a column,
    so every block spans its array's last two dims and no in-kernel
    transpose is needed."""
    b, c, L, h, p = x_c.shape
    g, n = B_c.shape[3], B_c.shape[4]
    hpg = h // g
    f32 = jnp.float32
    x_t = jnp.swapaxes(x_c.astype(f32), 2, 3)               # (b, c, h, L, p)
    B_t = jnp.swapaxes(B_c.astype(f32), 2, 3)               # (b, c, g, L, n)
    C_t = jnp.swapaxes(C_c.astype(f32), 2, 3)
    cs = A_cum.astype(f32)
    cs_row, cs_col = cs[..., None, :], cs[..., :, None]     # (b, h, c, ...)

    head = lambda bi, ci, hi: (bi, ci, hi, 0, 0)
    grp = lambda bi, ci, hi: (bi, ci, hi // hpg, 0, 0)
    hc = lambda bi, ci, hi: (bi, hi, ci, 0, 0)
    y, st = common.pallas_call(
        _ssd_chunk_kernel,
        grid=(b, c, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, p), head),
            pl.BlockSpec((1, 1, 1, 1, L), hc),
            pl.BlockSpec((1, 1, 1, L, 1), hc),
            pl.BlockSpec((1, 1, 1, L, n), grp),
            pl.BlockSpec((1, 1, 1, L, n), grp),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, p), head),
            pl.BlockSpec((1, 1, 1, p, n), head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, h, L, p), f32),
            jax.ShapeDtypeStruct((b, c, h, p, n), f32),
        ],
        dimension_semantics=("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="ssd_chunk",
    )(x_t, cs_row, cs_col, B_t, C_t)
    return jnp.swapaxes(y, 2, 3), st
