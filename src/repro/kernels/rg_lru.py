"""RG-LRU chunked linear-recurrence Pallas kernel (recurrentgemma).

    h_t = a_t * h_{t-1} + b_t

with per-channel gates ``a_t in (0, 1)``.  XLA's associative scan
materializes O(log L) intermediate (L, d) tensors in HBM; the kernel instead
streams (block_t, block_d) tiles through VMEM, carrying the running state in
a scratch register across the sequential time-block dimension.  Within a
block the recurrence is unrolled as a serial VPU loop over ``block_t`` steps
— entirely VMEM-resident, so the kernel is bandwidth-optimal (reads a, b
once, writes h once).

Layouts:  a, b: (B, L, D)  ->  h: (B, L, D).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

Array = jax.Array


def _rg_lru_kernel(block_t: int):
    def kernel(a_ref, b_ref, o_ref, h_ref):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            h_ref[...] = jnp.zeros_like(h_ref)

        # Serial VPU loop over the block's time rows; the carried state
        # lives in the (1, bd) scratch across the sequential time axis.
        def step(i, h):
            row = pl.ds(i, 1)
            h = a_ref[0, row, :].astype(jnp.float32) * h + \
                b_ref[0, row, :].astype(jnp.float32)
            o_ref[0, row, :] = h.astype(o_ref.dtype)
            return h

        h_ref[...] = jax.lax.fori_loop(0, block_t, step, h_ref[...])

    return kernel


def rg_lru_scan(a: Array, b: Array, *, block_t: int = 256, block_d: int = 512,
                interpret: bool = False) -> Array:
    """Run the gated linear recurrence with zero initial state."""
    assert a.shape == b.shape and a.ndim == 3, (a.shape, b.shape)
    B, L, D = a.shape
    bt = min(block_t, common.round_up(L, 8))
    bd = min(block_d, common.round_up(D, 128))
    lp, dp = common.round_up(L, bt), common.round_up(D, bd)
    # 'a' pads with 1s would propagate state; 0-pad is fine since padded
    # region is sliced away and never feeds back.
    a2 = common.pad_axis(common.pad_axis(a, 1, lp), 2, dp)
    b2 = common.pad_axis(common.pad_axis(b, 1, lp), 2, dp)

    out = common.pallas_call(
        _rg_lru_kernel(bt),
        grid=(B, dp // bd, lp // bt),
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, bt, bd), lambda bi, di, ti: (bi, ti, di)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd), lambda bi, di, ti: (bi, ti, di)),
        out_shape=jax.ShapeDtypeStruct((B, lp, dp), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="rg_lru_scan",
    )(a2, b2)
    return out[:, :L, :D]
