"""Shared Pallas utilities for the XAMBA TPU kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Griffin's fixed RG-LRU gate exponent — single source for the mixer
# (nn/ssm.py), the fused decode kernel and its oracle.
RG_LRU_C = 8.0


def pallas_call(kernel, *, grid, in_specs, out_specs, out_shape,
                scratch_shapes=(), interpret=False, dimension_semantics=None,
                vmem_limit_bytes=None, name=None):
    """``pl.pallas_call`` with TPU compiler params.  ``vmem_limit_bytes``
    raises Mosaic's scoped-VMEM limit (16 MiB by default; a v5e core has
    128 MiB) for kernels whose working set is a whole chunk."""
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(None if dimension_semantics is None
                                 else tuple(dimension_semantics)),
            vmem_limit_bytes=vmem_limit_bytes)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch_shapes),
        interpret=interpret, name=name, **kwargs)


def dot(a, b, contract=((1,), (0,))):
    """In-kernel 2-D contraction with an fp32 accumulator.  fp32 operands
    run at HIGHEST precision (Mosaic otherwise rounds them to bf16 passes);
    bf16 operands keep the default, whose products are already exact and
    which is the only precision Mosaic accepts for them."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None)
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def head_expand(h: int, p: int):
    """(h, h*p) 0/1 matrix: ``v @ E`` repeats each head's scalar p times,
    so per-head values reach their channels without a lane->sublane
    relayout (built from iotas: a compile-time constant in VMEM)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, h * p), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (h, h * p), 1)
    return ((cols >= rows * p) & (cols < rows * p + p)).astype(jnp.float32)


def gate_then_rmsnorm(y, z, scale, *, eps: float, ngroups: int, silu):
    """Mamba-2's published gated norm (``mamba_ssm``'s ``RMSNormGated``
    with ``norm_before_gate=False``): ``rmsnorm(y * silu(z)) * scale`` in
    float32, each group of ``d_inner / ngroups`` channels normalised on
    its own.  ``y``, ``z``: ``(..., d_inner)`` of any dtype; ``scale``
    broadcasts against them.  Returns float32.  Only static lane slices,
    so kernel bodies call it as well as the XLA paths."""
    x = y.astype(jnp.float32) * silu(z.astype(jnp.float32))
    size = x.shape[-1] // ngroups
    parts = []
    for k in range(ngroups):
        xk = x[..., k * size:(k + 1) * size]
        ms = jnp.mean(xk * xk, axis=-1, keepdims=True)
        parts.append(xk * jax.lax.rsqrt(ms + eps))
    xn = parts[0] if ngroups == 1 else jnp.concatenate(parts, axis=-1)
    return xn * scale.astype(jnp.float32)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pad_axis(x, axis: int, target: int, value=0):
    """Pad ``axis`` of ``x`` up to ``target`` with ``value``."""
    cur = x.shape[axis]
    if cur == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - cur)
    return jnp.pad(x, pads, constant_values=value)


def acc_dtype(dtype) -> jnp.dtype:
    return jnp.float32 if dtype in (jnp.bfloat16, jnp.float16) else jnp.dtype(dtype)
