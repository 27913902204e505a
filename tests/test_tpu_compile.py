"""Every Pallas kernel compiles for a described TPU v5e chip at full width.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached (``jax.experimental.topologies``).
That catches what interpret mode cannot — block shapes off the (8, 128)
tiling, primitives Mosaic cannot lower, kernels over their VMEM budget —
and each compiled program must hold a Mosaic kernel
(``tpu_custom_call``), so a kernel that quietly became XLA cannot pass.

This is the only file that describes the topology, and it does so only
inside the ``one_chip`` fixture: only one process at a time may load the
TPU library, so describing it at import (or in ``skipif``/``parametrize``
/``conftest.py``) would break collection under several test workers.
Shapes are the published widths: mamba2-130m (d_inner 1536, 24 heads of
64, d_state 128), mamba-130m (d_inner 1536, d_state 16, dt_rank 48) and
recurrentgemma-2b (lru_width 2560; local attention 10 heads of 256),
at a serving batch of 8.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (cumba, decode_step, flash_attention,
                           prefill_chunk, qmatmul, reduba, rg_lru,
                           ssd_chunk)

F32, BF16 = jnp.float32, jnp.bfloat16
B = 8                                   # serving batch
DI, H, P, N, G, W = 1536, 24, 64, 128, 1, 4      # mamba2-130m
DX = DI + 2 * G * N
DI1, N1, R1 = 1536, 16, 48              # mamba-130m
WD = 2560                               # recurrentgemma-2b lru_width
L = 256                                 # mamba2-130m chunk_size
EPS = 1e-5                              # Mamba-2's published norm epsilon


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with JAX's persistent
    compile cache off (a compile for a described chip is written to it
    but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _mamba2_step(*a):
    return decode_step.mamba2_step(*a, ngroups=G, head_dim=P, eps=EPS)


def _mamba1_step(*a):
    return decode_step.mamba1_step(*a, dt_rank=R1)


def _prefill(*a):
    return prefill_chunk.mamba2_prefill_pallas(
        *a, ngroups=G, head_dim=P, chunk=L, silu=jax.nn.silu,
        softplus=jax.nn.softplus, eps=EPS)


def _ssd_chunk(x, a_cum, b, c):
    return ssd_chunk.ssd_chunk(x, None, a_cum, b, c)


def _flash(q, k, v):
    return flash_attention.flash_attention(q, k, v, True, None, None, 128,
                                           128, False)


# kernel -> (function, [(shape, dtype) per operand])
KERNELS = {
    "mamba2_decode_step": (_mamba2_step, [
        ((B, DI), BF16), ((B, DX), BF16), ((B, H), BF16),
        ((B, W - 1, DX), BF16), ((B, H, P, N), F32), ((W, DX), BF16),
        ((DX,), BF16), ((H,), F32), ((H,), F32), ((H,), F32),
        ((DI,), BF16)]),
    "ssd_decode_step": (decode_step.ssd_step, [
        ((B, H, P, N), F32), ((B, H, P), F32), ((B, H), F32), ((H,), F32),
        ((B, G, N), F32), ((B, G, N), F32)]),
    "mamba1_decode_step": (_mamba1_step, [
        ((B, DI1), BF16), ((B, DI1), BF16), ((B, W - 1, DI1), BF16),
        ((B, DI1, N1), F32), ((W, DI1), BF16), ((DI1,), BF16),
        ((DI1, R1 + 2 * N1), BF16), ((R1, DI1), BF16), ((DI1,), BF16),
        ((DI1, N1), F32), ((DI1,), BF16)]),
    "sscan_decode_step": (decode_step.sscan_step, [
        ((B, DI1, N1), F32), ((B, DI1), F32), ((B, DI1), F32),
        ((DI1, N1), F32), ((B, N1), F32), ((B, N1), F32), ((DI1,), F32)]),
    "rglru_decode_step": (decode_step.rglru_step, [
        ((B, WD), BF16), ((B, WD), BF16), ((B, W - 1, WD), BF16),
        ((B, WD), F32), ((W, WD), BF16), ((WD,), BF16), ((WD, WD), BF16),
        ((WD,), BF16), ((WD, WD), BF16), ((WD,), BF16), ((WD,), BF16)]),
    "mamba2_prefill_pallas": (_prefill, [
        ((B, L, DI), BF16), ((B, L, DX), BF16), ((B, L, H), BF16),
        ((B, W - 1, DX), BF16), ((B, H, P, N), F32), ((W, DX), BF16),
        ((DX,), BF16), ((H,), F32), ((H,), F32), ((H,), F32),
        ((DI,), BF16)]),
    "rg_lru_scan": (rg_lru.rg_lru_scan, [((B, L, WD), F32)] * 2),
    "ssd_chunk": (_ssd_chunk, [
        ((B, 2, L, H, P), F32), ((B, H, 2, L), F32), ((B, 2, L, G, N), F32),
        ((B, 2, L, G, N), F32)]),
    "qmatmul": (qmatmul.qmatmul, [
        ((B, 768), BF16), ((768, DX + 2 * DI + H), jnp.int8),
        ((1, DX + 2 * DI + H), F32)]),
    "cumba_cumsum": (cumba.cumsum_last, [((B * H, 2 * L), F32)]),
    "reduba_sum": (reduba.reduce_rows, [((DI, 1024), F32)]),
    # recurrentgemma-2b local attention: 10 heads of 256 (MQA, 1 KV head).
    "flash_attention": (_flash, [((B, 10, L, 256), BF16),
                                 ((B, 1, L, 256), BF16),
                                 ((B, 1, L, 256), BF16)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, operands = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
