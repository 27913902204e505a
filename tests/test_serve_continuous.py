"""Continuous-batching subsystem: slot refill identity, state pool,
scheduler policy, vectorized sampling, metrics, compile-once discipline."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, build_model
from repro.nn.params import init_params
from repro.serve import (ContinuousEngine, Engine, Request, Scheduler,
                         ServeConfig, StatePool)
from repro.serve import sampling
from repro.serve.state_pool import infer_batch_axes

V = 64

CFGS = {
    "dense": ModelConfig(name="dense", family="transformer", vocab_size=V,
                         d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                         head_dim=8, d_ff=64, param_dtype="float32"),
    "mamba2": ModelConfig(name="mamba2", family="mamba2", vocab_size=V,
                          d_model=32, n_layers=2, d_state=8, ssm_head_dim=8,
                          chunk_size=8, param_dtype="float32"),
    "mamba1": ModelConfig(name="mamba1", family="mamba", vocab_size=V,
                          d_model=32, n_layers=2, d_state=8,
                          param_dtype="float32"),
    "rgemma": ModelConfig(name="rgemma", family="recurrentgemma",
                          vocab_size=V, d_model=32, n_layers=3, n_heads=4,
                          n_kv_heads=1, head_dim=8, d_ff=96,
                          mlp_type="geglu", lru_width=32, sliding_window=8,
                          scan_layers=False, param_dtype="float32"),
}


def _model_params(name):
    cfg = CFGS[name]
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         jnp.float32)
    return model, params


def _prompts(rng, n, length):
    return [rng.integers(1, V, length).tolist() for _ in range(n)]


# ---------------------------------------------------------------------------
# tentpole: continuous == wave, token for token, with zero decode recompiles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", ["mamba2", "mamba1", "dense"])
def test_continuous_matches_wave_greedy(family):
    model, params = _model_params(family)
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, 10, 16)          # one bucket for both engines
    budgets = [2, 7, 3, 8, 2, 6, 4, 8, 3, 5]  # heterogeneous -> staggered

    scfg = ServeConfig(max_batch=4, prefill_buckets=(16,), max_new_tokens=8)
    wave = Engine(model, params, scfg)
    cont = ContinuousEngine(model, params, scfg)
    for p, m in zip(prompts, budgets):
        wave.submit(p, m)
        cont.submit(p, m)
    wave_out = {r.uid: r.out_tokens for r in wave.run()}
    cont_out = {r.uid: r.out_tokens for r in cont.run()}

    assert set(wave_out) == set(cont_out)
    for uid in wave_out:
        assert cont_out[uid] == wave_out[uid], f"uid={uid}"
    # compile-once: slot turnover must never retrace the decode program
    assert cont.counters["decode_compiles"] == 1
    assert cont.counters["prefill_compiles"] == 1


@pytest.mark.parametrize("family", ["mamba2", "mamba1", "dense", "rgemma"])
def test_mid_decode_admission_matches_solo(family):
    """Requests admitted into freed slots mid-decode generate exactly the
    tokens they'd generate running alone (greedy)."""
    model, params = _model_params(family)
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, 5, 12)
    budgets = [2, 6, 3, 6, 4]                # staggered completions

    scfg = ServeConfig(max_batch=2, prefill_buckets=(16,), max_new_tokens=6)
    cont = ContinuousEngine(model, params, scfg)
    for p, m in zip(prompts, budgets):
        cont.submit(p, m)
    batched = {r.uid: r.out_tokens for r in cont.run()}
    assert len(batched) == 5

    for i, (p, m) in enumerate(zip(prompts, budgets)):
        solo = ContinuousEngine(model, params, scfg)
        uid = solo.submit(p, m)
        (r,) = solo.run()
        assert r.uid == uid
        assert batched[i + 1] == r.out_tokens, f"request {i}"


def test_mixed_buckets_one_decode_program():
    """Slots prefilled at different buckets coexist (per-slot positions);
    decode still compiles exactly once, prefill once per bucket."""
    model, params = _model_params("dense")
    rng = np.random.default_rng(7)
    scfg = ServeConfig(max_batch=2, prefill_buckets=(8, 16),
                       max_new_tokens=5)
    cont = ContinuousEngine(model, params, scfg)
    for length in (6, 14, 7, 13, 5):
        cont.submit(rng.integers(1, V, length).tolist())
    done = cont.run()
    assert len(done) == 5 and all(len(r.out_tokens) == 5 for r in done)
    assert cont.counters["decode_compiles"] == 1
    assert cont.counters["prefill_compiles"] == 2

    # per-request greedy identity vs solo at the same bucket
    for r in done:
        solo = ContinuousEngine(model, params, scfg)
        solo.submit(r.prompt)
        (s,) = solo.run()
        assert s.out_tokens == r.out_tokens


# ---------------------------------------------------------------------------
# serving edge cases (satellite)
# ---------------------------------------------------------------------------
def _first_greedy_token(model, params, prompt, bucket):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, bucket - len(prompt):] = prompt
    cache = model.init_cache(1, bucket + 4, jnp.float32)
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(toks)}, cache)
    return int(np.argmax(np.asarray(logits), -1)[0])


@pytest.mark.parametrize("engine_cls", [Engine, ContinuousEngine],
                         ids=["wave", "continuous"])
def test_eos_on_prefill_token(engine_cls):
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, V, 10).tolist()
    eos = _first_greedy_token(model, params, prompt, 16)

    scfg = ServeConfig(max_batch=2, prefill_buckets=(16,), max_new_tokens=8,
                       eos_id=eos)
    eng = engine_cls(model, params, scfg)
    eng.submit(prompt)
    other = rng.integers(1, V, 10).tolist()  # slot must still be reusable
    eng.submit(other)
    eng.submit(other)
    done = {r.uid: r for r in eng.run()}
    assert len(done) == 3 and all(r.done for r in done.values())
    assert done[1].out_tokens == [eos]


@pytest.mark.parametrize("engine_cls", [Engine, ContinuousEngine],
                         ids=["wave", "continuous"])
def test_max_new_tokens_one(engine_cls):
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(13)
    scfg = ServeConfig(max_batch=2, prefill_buckets=(16,), max_new_tokens=8)
    eng = engine_cls(model, params, scfg)
    for p in _prompts(rng, 3, 9):
        eng.submit(p, max_new_tokens=1)
    done = eng.run()
    assert len(done) == 3
    assert all(r.done and len(r.out_tokens) == 1 for r in done)


@pytest.mark.parametrize("engine_cls", [Engine, ContinuousEngine],
                         ids=["wave", "continuous"])
def test_ragged_wave_fewer_requests_than_batch(engine_cls):
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(17)
    scfg = ServeConfig(max_batch=8, prefill_buckets=(16,), max_new_tokens=3)
    eng = engine_cls(model, params, scfg)
    for p in _prompts(rng, 3, 8):
        eng.submit(p)
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.out_tokens) == 3 for r in done)


@pytest.mark.parametrize("engine_cls", [Engine, ContinuousEngine],
                         ids=["wave", "continuous"])
def test_prompt_truncation_flagged_and_warned(engine_cls, caplog):
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(19)
    scfg = ServeConfig(max_batch=2, prefill_buckets=(8, 16),
                       max_new_tokens=2)
    eng = engine_cls(model, params, scfg)
    with caplog.at_level(logging.WARNING, logger="repro.serve"):
        eng.submit(rng.integers(1, V, 40).tolist())   # > largest bucket
        eng.submit(rng.integers(1, V, 10).tolist())
    assert any("truncating" in rec.message for rec in caplog.records)
    done = {r.uid: r for r in eng.run()}
    assert done[1].truncated and not done[2].truncated
    assert len(done[1].out_tokens) == 2


# ---------------------------------------------------------------------------
# state pool
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", ["mamba2", "dense", "rgemma"])
def test_state_pool_row_roundtrip(family):
    model, params = _model_params(family)
    rng = np.random.default_rng(23)
    max_seq = 24
    toks = jnp.asarray(rng.integers(1, V, (4, 8)), jnp.int32)
    src = model.init_cache(4, max_seq, jnp.float32)
    _, src = model.prefill(params, {"tokens": toks}, src)

    pool = StatePool(model, 4, max_seq, jnp.float32)
    axes = pool.batch_axes
    pool.insert_rows(src, [0, 2], [3, 1])

    got = pool.extract_rows([3])
    jax.tree.map(
        lambda g, s, ax: np.testing.assert_array_equal(
            np.asarray(g).take(0, axis=ax),
            np.asarray(s).take(0, axis=ax)),
        got, src, axes)
    got = pool.extract_rows([1])
    jax.tree.map(
        lambda g, s, ax: np.testing.assert_array_equal(
            np.asarray(g).take(0, axis=ax),
            np.asarray(s).take(2, axis=ax)),
        got, src, axes)

    pool.reset_rows([3])
    got = pool.extract_rows([3])
    jax.tree.map(lambda g: np.testing.assert_array_equal(
        np.asarray(g), np.zeros_like(np.asarray(g))), got)
    # untouched slot survives the reset
    got = pool.extract_rows([1])
    jax.tree.map(
        lambda g, s, ax: np.testing.assert_array_equal(
            np.asarray(g).take(0, axis=ax),
            np.asarray(s).take(2, axis=ax)),
        got, src, axes)


@pytest.mark.parametrize("family", ["mamba2", "dense", "rgemma"])
def test_state_pool_snapshot_row(family):
    """clone_row snapshots one slot to the host without touching the
    donated arena; restore_row is its exact inverse — the prefix cache's
    primitives (and the supported way to extract per-slot state, instead
    of ad-hoc per-field gathers)."""
    model, params = _model_params(family)
    rng = np.random.default_rng(31)
    max_seq = 24
    toks = jnp.asarray(rng.integers(1, V, (4, 8)), jnp.int32)
    src = model.init_cache(4, max_seq, jnp.float32)
    _, src = model.prefill(params, {"tokens": toks}, src)

    pool = StatePool(model, 4, max_seq, jnp.float32)
    pool.insert_rows(src, [0, 2], [3, 1])
    snap = pool.clone_row(3)
    # host-side pytree: lifetime decoupled from the pool arena
    assert all(isinstance(leaf, np.ndarray)
               for leaf in jax.tree.leaves(snap))
    pool.reset_rows([3])
    pool.restore_row(3, snap)
    got = pool.extract_rows([3])
    jax.tree.map(
        lambda g, s, ax: np.testing.assert_array_equal(
            np.asarray(g).take(0, axis=ax),
            np.asarray(s).take(0, axis=ax)),
        got, src, pool.batch_axes)
    # clipped snapshot (index=8 consumed tokens) restores identically:
    # everything past the prefix is zero by the write discipline
    clipped = pool.clone_row(1, index=8)
    pool.restore_row(3, clipped, index=8)
    got = pool.extract_rows([3])
    jax.tree.map(
        lambda g, s, ax: np.testing.assert_array_equal(
            np.asarray(g).take(0, axis=ax),
            np.asarray(s).take(2, axis=ax)),
        got, src, pool.batch_axes)


def test_infer_batch_axes_scan_vs_loop_layouts():
    # scan-stacked mamba2: leaves are (n_layers, b, ...) -> batch axis 1
    model, _ = _model_params("mamba2")
    axes = infer_batch_axes(model, 8, jnp.float32)
    assert set(jax.tree.leaves(axes)) == {1}
    # per-layer loop (rgemma): leaves are (b, ...) -> batch axis 0
    model, _ = _model_params("rgemma")
    axes = infer_batch_axes(model, 8, jnp.float32)
    assert set(jax.tree.leaves(axes)) == {0}


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------
def test_scheduler_priority_order_and_fcfs_tiebreak():
    sched = Scheduler("priority")
    for uid, pri in [(1, 5), (2, 1), (3, 5), (4, 0)]:
        sched.submit(Request(uid=uid, prompt=[1], max_new_tokens=1,
                             priority=pri))
    order = [sched.pop_ready(0.0).uid for _ in range(4)]
    assert order == [4, 2, 1, 3]
    assert sched.pop_ready(0.0) is None


def test_scheduler_deadline_shedding():
    sched = Scheduler("fcfs")
    sched.submit(Request(uid=1, prompt=[1], max_new_tokens=1,
                         deadline_s=10.0))
    sched.submit(Request(uid=2, prompt=[1], max_new_tokens=1))
    got = sched.pop_ready(now=20.0)      # uid 1 expired while queued
    assert got.uid == 2
    assert [r.uid for r in sched.expired] == [1]
    assert sched.expired[0].expired and sched.expired[0].done


@pytest.mark.parametrize("engine_cls", [Engine, ContinuousEngine],
                         ids=["wave", "continuous"])
def test_engine_deadline_shedding(engine_cls):
    import time as _time
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(43)
    eng = engine_cls(model, params, ServeConfig(
        max_batch=1, prefill_buckets=(8,), max_new_tokens=2))
    expired = eng.submit(rng.integers(1, V, 6).tolist(),
                         deadline_s=_time.time() - 1.0)
    kept = eng.submit(rng.integers(1, V, 6).tolist())
    done = eng.run()
    assert [r.uid for r in done] == [kept]
    assert [r.uid for r in eng.expired] == [expired]
    assert eng.metrics.shed == 1


def test_continuous_priority_admission():
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(29)
    scfg = ServeConfig(max_batch=1, prefill_buckets=(16,), max_new_tokens=2,
                       policy="priority")
    eng = ContinuousEngine(model, params, scfg)
    low = eng.submit(rng.integers(1, V, 8).tolist(), priority=9)
    high = eng.submit(rng.integers(1, V, 8).tolist(), priority=0)
    done = eng.run()
    assert [r.uid for r in done] == [high, low]


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------
def test_gumbel_sampler_deterministic_and_vectorized():
    logits = np.random.default_rng(0).normal(size=(16, V)).astype(np.float32)
    a = sampling.sample(logits, 0.8, sampling.step_rng(0, 7))
    b = sampling.sample(logits, 0.8, sampling.step_rng(0, 7))
    c = sampling.sample(logits, 0.8, sampling.step_rng(0, 8))
    assert a.shape == (16,) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)      # same (seed, step) replays
    assert not np.array_equal(a, c)          # step advances the stream
    # temperature 0 is exact argmax
    np.testing.assert_array_equal(
        sampling.sample(logits, 0.0, sampling.step_rng(0, 0)),
        np.argmax(logits, -1))


def test_gumbel_sampler_matches_softmax_distribution():
    logits = np.array([[np.log(3.0), 0.0]], np.float32)  # p = (0.75, 0.25)
    draws = np.array([
        sampling.sample(logits, 1.0, sampling.step_rng(1, s))[0]
        for s in range(2000)])
    p0 = float((draws == 0).mean())
    assert 0.70 < p0 < 0.80


def test_engine_temperature_sampling_deterministic():
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(31)
    prompts = _prompts(rng, 4, 8)

    def run_once():
        eng = ContinuousEngine(model, params, ServeConfig(
            max_batch=2, prefill_buckets=(8,), max_new_tokens=4,
            temperature=0.9, seed=42))
        for p in prompts:
            eng.submit(p)
        return {r.uid: r.out_tokens for r in eng.run()}

    assert run_once() == run_once()


@pytest.mark.parametrize("chunk", [None, 8], ids=["bucket", "chunked"])
@pytest.mark.parametrize("family", ["mamba1", "mamba2"])
def test_keyed_draw_takes_only_kept_rows(family, chunk):
    """Every ``_sample_rows`` call carries only rows whose token the
    engine keeps, and each served token is the keyed draw of its own
    logits row: a turnover draws its finished prompts, decode its live
    slots, and the tokens land back in the right slots."""
    model, params = _model_params(family)
    rng = np.random.default_rng(43)
    seed = 11
    eng = ContinuousEngine(model, params, ServeConfig(
        max_batch=4, prefill_buckets=(16,), max_new_tokens=8,
        prefill_chunk=chunk, temperature=1.0, seed=seed))
    # Staggered prompt lengths and budgets: turnovers land next to running
    # decode, and the tail leaves slots dead.
    lengths = [5, 12, 9, 3, 14, 7, 11]
    budgets = [3, 7, 2, 5, 4, 8, 1]
    for n, m in zip(lengths, budgets):
        eng.submit(rng.integers(1, V, n).tolist(), m)

    inner = eng._sample_rows
    drawn = {}              # uid -> {position: logits row}
    calls = []

    def sample_rows(logits, uids, positions):
        # Same shape as the benchmark's capture: row i belongs to uids[i].
        lg = np.asarray(logits, np.float32)
        assert len(lg) == len(uids) == len(positions)
        finished = {r.uid for r in eng._finished}
        calls.append(len(uids))
        for i, u in enumerate(uids):
            assert u != 0 and u not in finished, (u, finished)
            assert int(positions[i]) not in drawn.get(int(u), {})
            drawn.setdefault(int(u), {})[int(positions[i])] = lg[i].copy()
        assert len(set(int(u) for u in uids)) == len(uids)
        return inner(logits, uids, positions)

    eng._sample_rows = sample_rows
    done = eng.run()
    assert len(done) == len(lengths)
    assert all(r.status == "ok" for r in done)
    # no row is drawn only to be thrown away
    assert sum(calls) == sum(len(r.out_tokens) for r in done)
    assert min(calls) < 4       # some draws took fewer rows than slots
    for r in done:
        rows = drawn[r.uid]
        p0 = min(rows)
        assert sorted(rows) == list(range(p0, p0 + len(r.out_tokens)))
        for k, tok in enumerate(r.out_tokens):
            want = sampling.sample_keyed(rows[p0 + k][None], 1.0, seed,
                                         [r.uid], [p0 + k])
            assert tok == int(want[0]), (r.uid, k)


# ---------------------------------------------------------------------------
# metrics / accounting / streaming
# ---------------------------------------------------------------------------
def test_wave_latency_accounting_per_request():
    """Same-wave requests with different budgets finish at different times;
    stats use summed sequential wave time, not the max request latency."""
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(37)
    scfg = ServeConfig(max_batch=2, prefill_buckets=(8,), max_new_tokens=12)
    eng = Engine(model, params, scfg)
    eng.submit(rng.integers(1, V, 6).tolist(), max_new_tokens=2)
    eng.submit(rng.integers(1, V, 6).tolist(), max_new_tokens=12)
    eng.submit(rng.integers(1, V, 6).tolist(), max_new_tokens=2)  # wave 2
    done = {r.uid: r for r in eng.run()}
    assert done[1].latency_s < done[2].latency_s
    stats = eng.stats(list(done.values()))
    assert stats["wall_s"] > 0
    # two sequential waves: total wall >= the longest single request
    assert stats["wall_s"] >= max(r.latency_s for r in done.values()) * 0.5
    assert stats["tokens_per_s"] == pytest.approx(
        stats["generated_tokens"] / stats["wall_s"])


def test_streaming_callback_and_metrics():
    model, params = _model_params("mamba2")
    rng = np.random.default_rng(41)
    streamed = {}

    def on_token(uid, tok):
        streamed.setdefault(uid, []).append(tok)

    eng = ContinuousEngine(model, params, ServeConfig(
        max_batch=2, prefill_buckets=(8,), max_new_tokens=3))
    for p in _prompts(rng, 3, 6):
        eng.submit(p, on_token=on_token)
    done = eng.run()
    for r in done:
        assert streamed[r.uid] == r.out_tokens
        assert r.first_token_s is not None and r.finish_s >= r.first_token_s
        assert r.latency_s > 0
    m = eng.metrics.summary()
    assert m["completed"] == 3
    assert m["generated_tokens"] == sum(len(r.out_tokens) for r in done)
    assert 0.0 < m["slot_occupancy"] <= 1.0
    assert eng.metrics.ttft.count == 3
