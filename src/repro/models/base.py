"""Model configuration (all families) and shared loss/metrics utilities."""
from __future__ import annotations

import dataclasses
import warnings
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.xamba import XambaConfig

Array = jax.Array


def chunk_positions(index, batch: int, seq: int):
    """(b, s) absolute positions for a prefill chunk whose first token sits
    at ``index`` (``()`` or ``(b,)`` int32)."""
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.full((batch,), idx)
    return idx[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]


class DecodeAPI:
    """The serving surface every model family implements:

    * ``prefill(params, batch, cache) -> (last_logits, cache)`` — run the
      chunked/parallel form over the whole prompt at once and emit the
      recurrent state;
    * ``prefill_chunk(params, tokens, cache, index) -> (logits, cache)``
      — one fixed-size slice of the prompt, carrying state across calls:
      SSM state + conv tail (Mamba), RG-LRU ``h``, and KV rows appended at
      ``index`` (attention).  ``index`` is ``()`` or ``(b,)`` int32 — the
      number of tokens each row has already consumed; feeding a prompt
      chunk-by-chunk is numerically equivalent to one ``prefill`` call
      (≤ 1e-5 fp32, greedy-identical continuations).  This is what lets
      the continuous engine admit long prompts incrementally instead of
      stalling the decode wave on a monolithic prefill.  (Whisper's
      override mirrors its ``prefill`` and takes the ``{"tokens",
      "frames"}`` batch dict instead of a bare token array — like its
      whole-sequence prefill, it is not servable by the token-only
      engines);
    * ``decode_step(params, token, cache, index) -> (logits, cache)`` —
      the O(1) cached-state step (``index``: ``()`` or ``(b,)`` int32);
    * ``export_state(cache, index, rows)`` / ``import_state(cache, index,
      rows, snapshot)`` — host-side snapshot / restore of cache rows over
      the same pytrees ``prefill_chunk`` carries (SSM state + conv tail,
      RG-LRU ``h``, KV rows clipped to the ``index``-token prefix) — the
      prefix-state cache's primitives (``docs/prefix_cache.md``).

    ``apply`` is a deprecation shim for the pre-split call signature
    (``model.apply(params, tokens, state=...)``); external callers should
    migrate to the explicit trio above.
    """

    def prefill_chunk(self, params, tokens, cache, index):
        raise NotImplementedError(
            f"{type(self).__name__} does not implement prefill_chunk")

    def prefill_path(self, length: int) -> Tuple[str, Optional[str]]:
        """``(path, reason)``: which multi-token mixer path a prefill
        program of ``length`` tokens traces, for the engine's trace;
        ``("n/a", None)`` for a model with one path only."""
        return "n/a", None

    def verify_chunk(self, params, tokens, cache, index):
        """``prefill_chunk`` with per-position logits: ``(b, s, vocab)``
        instead of the last position only — one batched call scores every
        token of a speculative draft window against the full-precision
        stream (``serve/speculative.py``).  Families implement it by
        re-entering their chunk trunk and skipping the ``x[:, -1]``
        slice, so state carry semantics are identical to prefill_chunk."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement verify_chunk")

    def speculative_step(self, params_draft, params_verify, token, cache,
                         index, k: int):
        """One self-speculative burst, functional and host-driven: draft
        ``k`` greedy tokens with ``params_draft`` on a throwaway copy of
        the state, verify them in one ``verify_chunk`` call with
        ``params_verify``, emit the longest verified prefix plus one
        correction token, and repair rolled-back rows by re-advancing the
        pre-burst snapshot through ``decode_step`` (the reference
        semantics the continuous engine's compiled burst must match; see
        ``serve/speculative.py`` for the accept rule).

        ``token``: ``(b, 1)`` pending next-input tokens; ``index``: ``()``
        or ``(b,)`` consumed-token counts.  Returns ``(emitted, n_emit,
        cache, new_index)`` — ``emitted`` is ``(b, k)`` int32 with only
        the first ``n_emit[i]`` entries of row ``i`` meaningful, and
        ``new_index = index + n_emit`` per row.  ``cache`` is treated
        functionally (not donated): the caller's argument stays valid.
        """
        from repro.serve.speculative import accept_lengths, emit_counts, \
            needs_rollback
        if k < 1:
            raise ValueError(f"speculative_step needs k >= 1, got {k}")
        tok0 = np.asarray(token, np.int32).reshape(-1)
        b = tok0.shape[0]
        idx = np.asarray(index, np.int32)
        if idx.ndim == 0:
            idx = np.full((b,), idx, np.int32)

        # Draft pass: decode_step is functional here (no donation), so
        # ``cache`` itself survives as the pre-burst snapshot.
        dcache = cache
        cur = tok0
        drafts = np.zeros((b, k), np.int32)
        for j in range(k):
            logits, dcache = self.decode_step(
                params_draft, jnp.asarray(cur[:, None]), dcache,
                jnp.asarray(idx + j))
            cur = np.argmax(np.asarray(logits, np.float32),
                            axis=-1).astype(np.int32)
            drafts[:, j] = cur

        # Verify pass: one chunk over [t0, d_1 .. d_{k-1}].
        vtoks = np.empty((b, k), np.int32)
        vtoks[:, 0] = tok0
        if k > 1:
            vtoks[:, 1:] = drafts[:, :k - 1]
        vlogits, vcache = self.verify_chunk(
            params_verify, jnp.asarray(vtoks), cache, jnp.asarray(idx))
        verify = np.argmax(np.asarray(vlogits, np.float32),
                           axis=-1).astype(np.int32)

        m = accept_lengths(drafts, verify)
        n_emit = emit_counts(m, k).astype(np.int32)
        # Rolled-back rows: re-advance the pre-burst row state over the
        # tokens the emitted stream actually consumed — [t0, g_0 ..
        # g_{n-2}] — through the full-precision decode step, exactly the
        # non-speculative trajectory.
        for i in np.nonzero(needs_rollback(m, k))[0]:
            snap = self.export_state(cache, None, [int(i)])
            rcache = jax.tree.map(jnp.asarray, snap)
            consume = [int(tok0[i])] + \
                [int(verify[i, j]) for j in range(int(n_emit[i]) - 1)]
            for j, t in enumerate(consume):
                _, rcache = self.decode_step(
                    params_verify, jnp.asarray([[t]], jnp.int32), rcache,
                    jnp.asarray(int(idx[i]) + j, jnp.int32))
            vcache = self.import_state(
                vcache, None, [int(i)], self.export_state(rcache, None, [0]))
        return verify, n_emit, vcache, idx + n_emit

    # ---------------- state snapshot / restore ----------------
    #
    # The inverse pair over the same pytrees ``prefill_chunk`` carries:
    # ``export_state`` gathers cache rows out as a host-side snapshot
    # (the prefix cache's unit of storage, ``serve/prefix_cache.py``),
    # ``import_state`` scatters a snapshot back into cache rows.  The
    # device work is the same jitted row gather/scatter the serve pools
    # use (``serve/state_pool.py: make_row_ops``) — one compiled program
    # per cache layout, row indices traced, never touching the donated
    # arenas except to scatter into them — while the per-family
    # clipping (``_clip_snapshot`` / ``_unclip_snapshot``) runs on the
    # host copy, so a varying ``index`` never retraces anything.

    def cache_batch_axes(self, cache):
        """Pytree of ints matching ``cache``: every leaf's batch axis
        (the layout rule ``state_pool.infer_batch_axes`` probes for,
        stated structurally per family)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement cache_batch_axes")

    def _clip_snapshot(self, snapshot, axes, index):
        """Drop state past the ``index``-token prefix from a host snapshot
        (byte honesty for length-proportional state; see the transformer
        override).  Default: recurrent state is O(1) — keep everything."""
        del axes, index
        return snapshot

    def _unclip_snapshot(self, snapshot, axes, index, like):
        """Inverse of ``_clip_snapshot``: rebuild full-size rows (zeros
        past the prefix — exactly what an in-place prefill would have
        left there) so the row scatter stays one compiled program."""
        del axes, index, like
        return snapshot

    def _state_row_ops(self, cache):
        """(gather, scatter) jitted row ops for this family's cache
        layout, built once per model instance (jit re-specializes per
        cache shape, e.g. pool-vs-test batch sizes, on its own)."""
        ops = getattr(self, "_state_row_ops_cache", None)
        if ops is None:
            from repro.serve.state_pool import make_row_ops
            scatter, gather, _ = make_row_ops(self.cache_batch_axes(cache))
            ops = self._state_row_ops_cache = (gather, scatter)
        return ops

    def export_state(self, cache, index, rows):
        """Host-side snapshot of ``rows``' state after ``index`` consumed
        tokens: a pytree shaped like ``cache`` with batch ``len(rows)``
        and length-proportional leaves (attention KV) clipped to the
        valid prefix (``index=None`` keeps full rows).  The gather runs
        off the live arena — the snapshot's lifetime is independent of
        any later donation of ``cache``."""
        gather, _ = self._state_row_ops(cache)
        axes = self.cache_batch_axes(cache)
        parts = [gather(cache, jnp.int32(r)) for r in rows]
        snap = parts[0] if len(parts) == 1 else jax.tree.map(
            lambda ax, *ls: jnp.concatenate(ls, axis=ax), axes, *parts)
        return self._clip_snapshot(jax.device_get(snap), axes, index)

    def import_state(self, cache, index, rows, snapshot):
        """Scatter snapshot row ``j`` into ``cache`` row ``rows[j]`` —
        the exact inverse of :meth:`export_state` over the same pytrees.
        ``cache`` is DONATED (like every serve-pool row op): callers must
        rebind the return value and drop the argument."""
        _, scatter = self._state_row_ops(cache)
        axes = self.cache_batch_axes(cache)
        full = self._unclip_snapshot(snapshot, axes, index, cache)
        full = jax.tree.map(jnp.asarray, full)
        for j, r in enumerate(rows):
            cache = scatter(cache, full, jnp.int32(j), jnp.int32(r))
        return cache

    def decode_view(self, params):
        """Decode-optimized *view* of ``params``: scan-stacked layer
        pytrees are pre-sliced into per-layer tuples ONCE (outside the
        jitted program).  XLA materializes a fresh copy of every sliced
        weight on each call when the slice happens in-program, so the
        serving engines build this view at init and feed it to the decode
        program; parameter *storage* (checkpoints, training, prefill)
        stays stacked.  Families without a stacked ``layers`` trunk
        return ``params`` unchanged (RecurrentGemma overrides for its
        group-stacked layout)."""
        layers_p = params.get("layers") if isinstance(params, dict) else None
        if layers_p is None or not getattr(self.cfg, "scan_layers", False) \
                or isinstance(layers_p, tuple):
            return params
        return dict(params, layers=tuple(
            jax.tree.map(lambda a: a[i], layers_p)
            for i in range(self.cfg.n_layers)))

    def apply(self, params, tokens, state=None, index=None):
        warnings.warn(
            "model.apply(state=...) is deprecated; call model.prefill() / "
            "model.decode_step() explicitly (see docs/architecture.md)",
            DeprecationWarning, stacklevel=2)
        batch = tokens if isinstance(tokens, dict) else {"tokens": tokens}
        toks = batch["tokens"]
        if state is None:
            fwd = getattr(self, "forward", None)
            if fwd is None:
                raise TypeError(
                    f"{type(self).__name__}.apply() without state= has no "
                    "stateless equivalent; use loss()/prefill() instead")
            return fwd(params, toks)
        if toks.shape[1] == 1:
            if index is None:
                # Defaulting to position 0 would silently misplace KV rows
                # for attention-bearing families; make the caller say it.
                raise TypeError(
                    "apply(state=...) with a single token dispatches to "
                    "decode_step and needs index= (the token's position)")
            return self.decode_step(params, toks, state, index)
        return self.prefill(params, batch, state)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config type for every assigned architecture family."""

    name: str = "model"
    family: str = "transformer"   # transformer | mamba | mamba2 |
    #                               recurrentgemma | whisper
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4

    # -- attention ----------------------------------------------------------
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 64
    qkv_bias: bool = False
    rope_theta: float = 1e4
    sliding_window: Optional[int] = None
    attn_logit_softcap: Optional[float] = None
    attn_probs_bf16: bool = False  # cast softmax probs to bf16 before PV

    # -- mlp ------------------------------------------------------------------
    d_ff: int = 2048
    mlp_type: str = "swiglu"      # swiglu | geglu | mlp

    # -- norms / embeddings ---------------------------------------------------
    norm_type: str = "rmsnorm"    # rmsnorm | gemma_rmsnorm | layernorm
    # Mamba-1 / Mamba-2 only (other families keep ``layers.norm``'s
    # default), fixed at the published models' values, not options: the
    # epsilon of every RMSNorm, and Mamba-2's gated-norm order in
    # ``mamba_ssm``'s terms (False: ``norm(y * silu(z))``).
    norm_eps: ClassVar[float] = 1e-5
    norm_before_gate: ClassVar[bool] = False
    embed_scale: bool = False     # gemma: x *= sqrt(d_model)
    tie_embeddings: bool = True

    # -- MoE ------------------------------------------------------------------
    moe: bool = False
    n_experts: int = 0
    n_experts_per_token: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    moe_renormalize: bool = True
    moe_aux_weight: float = 0.01
    # Pin the dispatch buffers' capacity dim to the batch axes.  Helps when
    # the expert count cannot shard over "model" (grok-1: 8 experts vs 16) —
    # without it XLA gathers the buffers to every device; HURTS when experts
    # are model-sharded (qwen3: 128) by fighting the natural EP layout.
    moe_cap_batch_sharding: bool = False

    # -- SSM (mamba / mamba2) -------------------------------------------------
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    ssm_head_dim: int = 64
    ssm_ngroups: int = 1
    chunk_size: int = 256
    dt_rank: int = 0              # 0 -> ceil(d_model/16) (mamba1)
    scan_mode: str = "sequential"  # mamba1: sequential|associative|chunked
    ssd_dtype: str = "float32"    # SSD big-matmul dtype (bf16 = perf mode)

    # -- recurrentgemma ---------------------------------------------------------
    lru_width: int = 0
    block_pattern: Tuple[str, ...] = ()   # e.g. ("recurrent","recurrent","attention")

    # -- multimodal stubs -------------------------------------------------------
    frontend: Optional[str] = None        # vision_stub | audio_stub
    num_patches: int = 0                  # llava: image token count
    encoder_layers: int = 0               # whisper
    encoder_seq: int = 1500               # whisper frame count

    # -- execution policies -----------------------------------------------------
    param_dtype: str = "bfloat16"
    remat: str = "none"                   # none | full | dots
    scan_layers: bool = True
    use_flash: bool = False               # Pallas flash attention
    flash_interpret: bool = False
    force_prefill_path: bool = False
    logical_rules: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()

    xamba: XambaConfig = XambaConfig()

    # convenience -----------------------------------------------------------
    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def with_decode_mode(self, mode: str) -> "ModelConfig":
        """Config with ``XambaConfig.decode`` overridden (CLI plumbing)."""
        return self.replace(xamba=dataclasses.replace(self.xamba,
                                                      decode=mode))

    def with_prefill_mode(self, mode: str) -> "ModelConfig":
        """Config with ``XambaConfig.prefill`` overridden (CLI plumbing):
        how the multi-token SSD prefill pipeline executes."""
        return self.replace(xamba=dataclasses.replace(self.xamba,
                                                      prefill=mode))

    def with_quant(self, mode: str) -> "ModelConfig":
        """Config with ``XambaConfig.quant`` overridden (CLI plumbing);
        pair with ``nn.quant.quantize_params_for_mode`` on the params."""
        return self.replace(xamba=dataclasses.replace(self.xamba,
                                                      quant=mode))


def cross_entropy_loss(logits: Array, labels: Array,
                       mask: Optional[Array] = None,
                       z_loss: float = 1e-4) -> Tuple[Array, dict]:
    """Token-level CE with optional z-loss; labels < 0 are ignored."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    if mask is not None:
        valid = jnp.logical_and(valid, mask > 0)
    labels_safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    denom = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / denom
    acc = jnp.sum(jnp.where(
        valid, (jnp.argmax(logits, -1) == labels_safe).astype(jnp.float32),
        0.0)) / denom
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}
