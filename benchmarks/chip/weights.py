"""Seeded random weights for a configuration, made on the device.

The benchmark makes its own weights: one jitted call turns ``--seed``
into every leaf, in bfloat16 (the type they are served in), laid out as
the serving program's parameter tree expects.  The distributions are
those of the published implementation (``mamba_ssm``'s ``Mamba`` and
``Mamba2`` constructors and ``_init_weights``), so the random model has
the dynamics of a freshly initialised real one: dt drawn log-uniform in
``[dt_min, dt_max]``, ``A`` from ``A_init_range`` (Mamba-2) or
``1..d_state`` (Mamba-1), ``D = 1``, out-projections scaled by
``1/sqrt(n_layer)``, embeddings with standard deviation 0.02.

Layout: linear weights are ``(d_in, d_out)``; conv taps ``(d_conv, d)``;
every per-layer leaf is stacked on a leading ``n_layer`` axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    """Derived widths of a configuration (``configs/<name>.json``)."""
    d = cfg["d_model"]
    di = cfg["expand"] * d
    pad = cfg.get("pad_vocab_size_multiple", 1)
    vocab = -(-cfg["vocab_size"] // pad) * pad
    out = {"d": d, "di": di, "L": cfg["n_layer"], "V": vocab,
           "N": cfg["d_state"], "K": cfg["d_conv"]}
    if cfg["family"] == "mamba2":
        g, n, p = cfg["ngroups"], cfg["d_state"], cfg["headdim"]
        out.update(P=p, H=di // p, G=g, d_xbc=di + 2 * g * n,
                   d_in_proj=2 * di + 2 * g * n + di // p)
    else:
        out.update(R=cfg["dt_rank"])
    return out


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer (``--seed`` may exceed
    32 bits, which ``jax.random.PRNGKey`` refuses without x64)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _dt_bias(key, shape, cfg):
    """Inverse softplus of dt ~ exp(U(log dt_min, log dt_max))."""
    lo, hi = math.log(cfg["dt_min"]), math.log(cfg["dt_max"])
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    dt = jnp.maximum(dt, cfg["dt_init_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def _layers(key, cfg, dm):
    L, d, di, K = dm["L"], dm["d"], dm["di"], dm["K"]
    ks = iter(jax.random.split(key, 12))
    out_scale = 1.0 / math.sqrt(L)
    if cfg["family"] == "mamba2":
        H = dm["H"]
        a_lo, a_hi = cfg["A_init_range"]
        mixer = {
            "in_proj": {"w": _uniform(next(ks), (L, d, dm["d_in_proj"]),
                                      1 / math.sqrt(d))},
            "conv": {"w": _uniform(next(ks), (L, K, dm["d_xbc"]),
                                   1 / math.sqrt(K)),
                     "b": _uniform(next(ks), (L, dm["d_xbc"]),
                                   1 / math.sqrt(K))},
            "dt_bias": _dt_bias(next(ks), (L, H), cfg),
            "A_log": jnp.log(jax.random.uniform(next(ks), (L, H),
                                                jnp.float32, a_lo, a_hi)),
            "D": jnp.ones((L, H), jnp.float32),
            "norm": {"scale": jnp.ones((L, di), jnp.float32)},
            "out_proj": {"w": _uniform(next(ks), (L, di, d),
                                       1 / math.sqrt(di)) * out_scale},
        }
    else:
        N, R = dm["N"], dm["R"]
        mixer = {
            "in_proj": {"w": _uniform(next(ks), (L, d, 2 * di),
                                      1 / math.sqrt(d))},
            "conv": {"w": _uniform(next(ks), (L, K, di), 1 / math.sqrt(K)),
                     "b": _uniform(next(ks), (L, di), 1 / math.sqrt(K))},
            "x_proj": {"w": _uniform(next(ks), (L, di, R + 2 * N),
                                     1 / math.sqrt(di))},
            "dt_proj": {"w": _uniform(next(ks), (L, R, di),
                                      R ** -0.5),
                        "b": _dt_bias(next(ks), (L, di), cfg)},
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                (L, di, N)),
            "D": jnp.ones((L, di), jnp.float32),
            "out_proj": {"w": _uniform(next(ks), (L, di, d),
                                       1 / math.sqrt(di)) * out_scale},
        }
    return {"ln": {"scale": jnp.ones((L, d), jnp.float32)}, "mixer": mixer}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every weight of ``cfg`` from ``seed``, in ``dtype``, made on the
    default device by one compiled call."""
    dm = dims(cfg)

    def build(key):
        k_emb, k_layers = jax.random.split(key)
        tree = {
            "embed": {"table": jax.random.normal(
                k_emb, (dm["V"], dm["d"]), jnp.float32) * 0.02},
            "final_norm": {"scale": jnp.ones((dm["d"],), jnp.float32)},
            "layers": _layers(k_layers, cfg, dm),
        }
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    return jax.jit(build)(seed_key(seed))
