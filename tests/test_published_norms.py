"""Mamba-1 and Mamba-2 norms as the published models compute them.

The program computes every Mamba norm as the published models state it
(``ModelConfig.norm_eps`` 1e-5; Mamba-2's gated norm ``norm(y *
silu(z))``, ``norm_before_gate=False`` in ``mamba_ssm``'s terms), fixed
values rather than options.  At test size, in float32 at ``highest``
matmul precision, every decode and prefill path
(``XambaConfig.optimized()``, ``naive``, ``pallas_interpret``) gives the
logits of the plain reference the benchmark checks the chip against
(``benchmarks/chip/reference.py``, given the configuration files'
published settings): the whole-sequence forward pass, and chunked
prefill then cached decode through ``ContinuousEngine``.  The same
outputs lie off the reference given each norm setting changed alone
(epsilon 1e-6; Mamba-2's other order), so the comparison sees a path
(or one norm site in it) that computes another norm.

The tolerance, 1e-4 of the logits' scale, is float32 rounding with a
wide margin (the paths read 1e-7 to 3e-7); at this size the epsilon moves
the logits by 4e-3 to 1e-2, Mamba-2's order by 0.14 to 0.19.

Also here: which multi-token mixer path a prefill program took is on the
engine's trace (``mixer_path`` instants, ``prefill_chunk`` spans).
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

import program  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402
from repro.core.xamba import XambaConfig  # noqa: E402
from repro.models import ModelConfig, build_model  # noqa: E402
from repro.nn.params import init_params  # noqa: E402
from repro.serve import ContinuousEngine, ServeConfig  # noqa: E402

TOL = 1e-4
TINY = {"mamba2-2.7b": dict(d_model=64, n_layer=2, vocab_size=100,
                            d_state=16, headdim=16, chunk_size=32),
        "mamba-130m": dict(d_model=64, n_layer=2, vocab_size=100,
                           dt_rank=4)}
# Prefill chunk of the engine: Mamba-2's equals its chunk_size, so the
# fused SSD pipeline runs.
CHUNK = {"mamba2-2.7b": 32, "mamba-130m": 16}
PATHS = {"optimized": XambaConfig.optimized(),
         "naive": XambaConfig(decode="naive", prefill="naive"),
         "pallas_interpret": XambaConfig(decode="pallas_interpret",
                                         prefill="pallas_interpret")}
# Each norm setting changed alone, in the reference's (``mamba_ssm``'s)
# names; Mamba-1 has no gated norm.
OTHERS = {"mamba2-2.7b": [{"norm_epsilon": 1e-6}, {"norm_before_gate": True}],
          "mamba-130m": [{"norm_epsilon": 1e-6}]}
PROMPTS = (40, 70)
NEW_TOKENS = 5


def tiny(name: str) -> dict:
    cfg = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    cfg.update(TINY[name], param_dtype="float32")
    return cfg


def served_logits(model, params, chunk: int, prompts):
    """Greedy chunked prefill then cached decode through the engine: per
    request, its consumed stream (prompt left-padded to whole chunks, then
    its tokens but the last) and the logits each token was drawn from."""
    bucket = -(-max(len(p) for p in prompts) // chunk) * chunk
    engine = ContinuousEngine(model, params, ServeConfig(
        max_batch=2, prefill_buckets=(bucket,), max_new_tokens=NEW_TOKENS,
        eos_id=-1, temperature=0.0, prefill_chunk=chunk))
    rows = {}
    inner = engine._sample_rows

    def sample_rows(logits, uids, positions):
        lg = np.asarray(logits, np.float32)
        for i, u in enumerate(uids):
            rows.setdefault(int(u), {})[int(positions[i])] = lg[i]
        return inner(lg, uids, positions)

    engine._sample_rows = sample_rows
    uids = [engine.submit(list(p)) for p in prompts]
    done = {r.uid: r for r in engine.run()}
    engine.close()
    out = []
    for uid, prompt in zip(uids, prompts):
        span = -(-len(prompt) // chunk) * chunk
        stream = np.zeros(span, np.int32)
        stream[span - len(prompt):] = prompt
        toks = done[uid].out_tokens
        stream = np.concatenate([stream, np.asarray(toks[:-1], np.int32)])
        got = np.stack([rows[uid][span + j] for j in range(len(toks))])
        out.append((stream, span - 1, got))
    return out


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("name", list(TINY))
def test_paths_match_the_published_reference(name, path):
    cfg = tiny(name)
    mcfg = program.model_config(cfg).replace(xamba=PATHS[path])
    model = build_model(mcfg)
    params = weights.make_params(cfg, 7, jnp.float32)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(1, 100, 96), jnp.int32)
    prompts = [rng.integers(1, 100, n).tolist() for n in PROMPTS]
    with jax.default_matmul_precision("highest"):
        forward = np.asarray(model.forward(params, toks[None])[0])
        served = served_logits(model, params, CHUNK[name], prompts)

    def gaps(ref_cfg):
        """Forward, and each request served through prefill and decode."""
        want = np.asarray(reference.logits_at(ref_cfg, params, toks, 0, 96))
        out = [float(np.max(np.abs(forward - want)))]
        for stream, start, got in served:
            ref = np.asarray(reference.logits_at(
                ref_cfg, params, jnp.asarray(stream), start, len(got)))
            out.append(float(np.max(np.abs(got - ref))))
        return out, max(float(np.sqrt(np.mean(want ** 2))), 1.0)

    same, scale = gaps(cfg)
    assert max(same) < TOL * scale, same
    for other in OTHERS[name]:
        off, scale = gaps({**cfg, **other})
        assert min(off) > TOL * scale, (other, off)


def test_registry_states_the_published_norms():
    from repro.configs import get_config
    for arch in ("mamba-130m", "mamba2-2.7b", "mamba2-130m"):
        assert get_config(arch).norm_eps == 1e-5
    assert get_config("mamba2-2.7b").norm_before_gate is False
    assert get_config("mamba2-130m").norm_before_gate is False
    # Fixed values, not options a config could set to something else.
    for field in ("norm_eps", "norm_before_gate"):
        with pytest.raises(TypeError):
            ModelConfig(**{field: getattr(ModelConfig, field)})


# ---------------------------------------------------------------------------
# the mixer path on the trace
# ---------------------------------------------------------------------------
def _engine(cfg, chunk):
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         jnp.float32)
    return ContinuousEngine(model, params, ServeConfig(
        max_batch=2, prefill_buckets=(64,), max_new_tokens=3,
        prefill_chunk=chunk, trace=True))


SSD = ModelConfig(name="ssd", family="mamba2", vocab_size=64, d_model=32,
                  n_layers=2, d_state=8, ssm_head_dim=8, chunk_size=16,
                  param_dtype="float32")


@pytest.mark.parametrize("chunk,path,reason", [
    (16, "fused-cumba", None),
    (24, "unfused", "seqlen 24 not a multiple of chunk 16")])
def test_mixer_path_is_on_the_trace(chunk, path, reason):
    engine = _engine(SSD, chunk)
    marks = [e for e in engine.tracer.events if e["name"] == "mixer_path"]
    assert [m["args"] for m in marks] == [{
        "program": engine._pid_chunk, "tokens": chunk, "path": path,
        "reason": reason}]
    engine.submit(list(range(1, 40)))
    engine.run()
    spans = [e for e in engine.tracer.events
             if e["name"] == "prefill_chunk" and e["ph"] == "X"]
    assert spans and all(s["args"]["path"] == path for s in spans)
    engine.close()


def test_mixer_path_of_other_families_and_modes():
    def mixer_path(cfg, length):
        return build_model(cfg).prefill_path(length)

    assert mixer_path(SSD.replace(family="mamba"), 16) == ("n/a", None)
    assert mixer_path(ModelConfig(vocab_size=64, d_model=32, n_layers=1,
                                  n_heads=2, n_kv_heads=2, head_dim=16,
                                  d_ff=64), 16) == ("n/a", None)
    assert mixer_path(SSD, 32) == ("fused-cumba", None)
    assert mixer_path(SSD.with_prefill_mode("pallas"), 16) == (
        "unfused", "chunk 16 not a multiple of 64 (MXU tiling)")
    assert mixer_path(SSD.replace(chunk_size=64).with_prefill_mode(
        "pallas"), 128) == ("fused-pallas", None)
    assert mixer_path(SSD.with_prefill_mode("naive"), 16) == (
        "unfused", "prefill mode naive")
    assert mixer_path(SSD.replace(ssd_dtype="bfloat16"), 16)[0] == "unfused"
