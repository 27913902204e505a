"""Mamba-1 and Mamba-2 language models — the paper's profiled subjects.

Block = RMSNorm -> mixer (selective scan / SSD) -> residual, as in the
reference implementations; Mamba-2's extra post-skip norm is the mixer's
internal gated RMSNorm.  Serving follows the paper's Step-1: prefill runs
the chunked parallel form and emits the recurrent state; decode is the O(1)
recurrence with conv + SSM state caches (static shapes throughout).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import api as dist_api
from repro.models import base
from repro.nn import layers, ssm
from repro.nn.params import stack_specs

Array = jax.Array


class MambaLM(base.DecodeAPI):
    """family == "mamba" (v1, selective scan) or "mamba2" (SSD)."""

    def __init__(self, cfg: base.ModelConfig):
        assert cfg.family in ("mamba", "mamba2"), cfg.family
        self.cfg = cfg
        self.v2 = cfg.family == "mamba2"

    # ---------------- specs ----------------
    def _mixer_specs(self):
        return (ssm.mamba2_specs(self.cfg) if self.v2
                else ssm.mamba1_specs(self.cfg))

    def param_specs(self) -> dict:
        cfg = self.cfg
        block = {
            "ln": layers.norm_specs(cfg.d_model),
            "mixer": self._mixer_specs(),
        }
        specs: Dict[str, Any] = {
            "embed": layers.embed_specs(cfg.vocab_size, cfg.d_model),
            "final_norm": layers.norm_specs(cfg.d_model),
        }
        if cfg.scan_layers:
            specs["layers"] = stack_specs(block, cfg.n_layers)
        else:
            specs["layers"] = {str(i): block for i in range(cfg.n_layers)}
        if not cfg.tie_embeddings:
            specs["lm_head"] = layers.linear_specs(
                cfg.d_model, cfg.vocab_size, axes=("embed", "vocab"))
        return specs

    def prefill_path(self, length: int):
        if self.v2:
            return ssm.mamba2_prefill_path(self.cfg, length)
        return super().prefill_path(length)

    # ---------------- trunk ----------------
    def _mixer_apply(self, p, x, state):
        if self.v2:
            return ssm.mamba2_apply(p, self.cfg, x, state)
        return ssm.mamba1_apply(p, self.cfg, x, state)

    def _block(self, p, x, state):
        h, new_state = self._mixer_apply(
            p["mixer"], layers.norm(p["ln"], x, eps=self.cfg.norm_eps), state)
        return x + h, new_state

    def _trunk(self, params, x, states=None):
        cfg = self.cfg
        block = self._block
        if cfg.remat in ("full", "dots"):
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat == "dots" else None)
            block = jax.checkpoint(block, policy=policy)

        if cfg.scan_layers and isinstance(params["layers"], tuple):
            # Decode view: layer weights are pre-sliced buffers; only the
            # (small) stacked states are sliced/restacked in-program.
            ns = []
            for i, p_i in enumerate(params["layers"]):
                st_i = jax.tree.map(lambda a: a[i], states)
                x, n_i = block(p_i, x, st_i)
                x = dist_api.shard_tokens3d(x)
                ns.append(n_i)
            new_states = jax.tree.map(lambda *ls: jnp.stack(ls), *ns)
        elif cfg.scan_layers:
            def body(x, xs):
                p, state = xs
                y, new_state = block(p, x, state)
                y = dist_api.shard_tokens3d(y)
                return y, new_state
            # Decode (one token) fully unrolls the layer scan: one trace of
            # the stacked pytree (no per-layer Python dispatch) and no
            # XLA while-loop overhead per generated token.  ``naive``
            # decode mode keeps the rolled scan, matching the program
            # structure decode had before the fused path existed (the
            # benchmark baseline; its step math is the paper's
            # mul+ReduceSum chain, see nn/ssm.py).
            unroll = (True if x.shape[1] == 1 and
                      cfg.xamba.decode != "naive" else 1)
            x, new_states = jax.lax.scan(body, x, (params["layers"], states),
                                         unroll=unroll)
        else:
            new_states = []
            for i in range(cfg.n_layers):
                state = None if states is None else states[i]
                x, ns = block(params["layers"][str(i)], x, state)
                new_states.append(ns)
        return x, new_states

    def _trunk_train(self, params, x):
        cfg = self.cfg

        def block(p, x):
            y, _ = self._block(p, x, None)
            return y

        if cfg.remat in ("full", "dots"):
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat == "dots" else None)
            block = jax.checkpoint(block, policy=policy)

        if cfg.scan_layers:
            def body(x, p):
                return dist_api.shard_tokens3d(block(p, x)), None
            x, _ = jax.lax.scan(body, x, params["layers"])
        else:
            for i in range(cfg.n_layers):
                x = block(params["layers"][str(i)], x)
        return x

    def _logits(self, params, x) -> Array:
        x = layers.norm(params["final_norm"], x, eps=self.cfg.norm_eps)
        if self.cfg.tie_embeddings:
            return layers.unembed(params["embed"], x)
        return layers.linear(params["lm_head"], x).astype(jnp.float32)

    # ---------------- training ----------------
    def loss(self, params, batch) -> Tuple[Array, dict]:
        x = dist_api.shard_tokens3d(layers.embed(params["embed"], batch["tokens"]))
        x = self._trunk_train(params, x)
        logits = self._logits(params, x)
        loss, metrics = base.cross_entropy_loss(
            logits[:, :-1], batch["labels"][:, 1:])
        metrics["loss_total"] = loss
        return loss, metrics

    def forward(self, params, tokens) -> Array:
        """Full-sequence logits (used by quality/equivalence benchmarks)."""
        x = layers.embed(params["embed"], tokens)
        x = self._trunk_train(params, x)
        return self._logits(params, x)

    # ---------------- serving ----------------
    def init_cache(self, batch: int, max_seq: int = 0, dtype=jnp.bfloat16):
        cfg = self.cfg
        del max_seq  # SSM state is O(1) in sequence length
        one = (ssm.mamba2_init_state(cfg, batch, dtype) if self.v2
               else ssm.mamba1_init_state(cfg, batch, dtype))
        if cfg.scan_layers:
            return jax.tree.map(
                lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one)
        # Distinct buffers per layer: an aliased list (same arrays repeated)
        # cannot be donated into the jitted decode program.
        return [jax.tree.map(jnp.copy, one) for _ in range(cfg.n_layers)]

    def cache_batch_axes(self, cache):
        # Scan-stacked states are (n_layers, b, ...); per-layer lists are
        # (b, ...).  Either way the snapshot is O(1) in sequence length —
        # the whole point of prefix-state caching for SSMs.
        return jax.tree.map(lambda a: 1 if self.cfg.scan_layers else 0,
                            cache)

    def prefill(self, params, batch, cache) -> Tuple[Array, Any]:
        x = layers.embed(params["embed"], batch["tokens"])
        x, new_states = self._trunk(params, x, cache)
        return self._logits(params, x[:, -1]), new_states

    def prefill_chunk(self, params, tokens, cache, index) -> Tuple[Array, Any]:
        """One prompt slice with carried state.  ``index`` is accepted for
        API uniformity and ignored: the SSM recurrence and conv tail carry
        position in ``cache`` (the same O(1)-state property that makes SSM
        slots relocatable under continuous batching), so chunked prefill is
        just the whole-sequence path re-entered with the previous chunk's
        state — SSD resumes through its inter-chunk recurrence
        (``initial_state``), selective scan through the carried ``h``."""
        del index
        x = layers.embed(params["embed"], tokens)
        x, new_states = self._trunk(params, x, cache)
        return self._logits(params, x[:, -1]), new_states

    def verify_chunk(self, params, tokens, cache, index) -> Tuple[Array, Any]:
        """``prefill_chunk`` with per-position logits (``(b, s, vocab)``):
        the speculative verifier scores a whole draft window in one call
        (``serve/speculative.py``).  Same trunk, same carried state —
        only the final-logits slice differs."""
        del index
        x = layers.embed(params["embed"], tokens)
        x, new_states = self._trunk(params, x, cache)
        return self._logits(params, x), new_states

    def decode_step(self, params, token, cache, index) -> Tuple[Array, Any]:
        """index: () or (b,) — accepted for engine uniformity and ignored;
        the recurrence carries position implicitly, which is why SSM slots
        are trivially relocatable under continuous batching."""
        del index
        x = layers.embed(params["embed"], token)
        x, new_states = self._trunk(params, x, cache)
        # Final norm + unembed on the squeezed (b, d) token — the batched
        # (b, 1, d) gemm is a pathological layout for single-token decode.
        return self._logits(params, x[:, 0]), new_states
