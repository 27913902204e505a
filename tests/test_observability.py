"""Observability stack: tracer schema, streaming metrics, recompile
sentinels, trace-report analysis, and null-tracer identity."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import trace_report
from repro.models import ModelConfig, build_model
from repro.nn.params import init_params
from repro.serve import (ContinuousEngine, Engine, ServeConfig,
                         StreamingHistogram)
from repro.serve.metrics import RateMeter, ServeMetrics, WindowedGauge, \
    _percentile
from repro.serve.tracing import (NULL_TRACER, TID_ENGINE, TID_HOST,
                                 TID_QUEUE, TID_SLOT0, NullTracer,
                                 RecompileError, RecompileSentinel, Tracer)

V = 64

CFG = ModelConfig(name="mamba2", family="mamba2", vocab_size=V,
                  d_model=32, n_layers=2, d_state=8, ssm_head_dim=8,
                  chunk_size=8, param_dtype="float32")


def _model_params():
    model = build_model(CFG)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         jnp.float32)
    return model, params


# ---------------------------------------------------------------------------
# percentiles: linear interpolation + streaming histogram vs exact
# ---------------------------------------------------------------------------
def test_percentile_matches_numpy_quantile():
    rng = np.random.default_rng(0)
    for n in (1, 2, 5, 20, 101):
        xs = rng.uniform(0.0, 10.0, n).tolist()
        for q in (0.0, 0.5, 0.9, 0.95, 0.99, 1.0):
            assert _percentile(xs, q) == pytest.approx(
                float(np.quantile(xs, q)), abs=1e-12), (n, q)


def test_percentile_no_nearest_rank_bias():
    # The old round() nearest-rank picked the MAX of 20 samples as p95;
    # linear interpolation lands between ranks 18 and 19.
    xs = list(range(20))
    p95 = _percentile([float(x) for x in xs], 0.95)
    assert p95 == pytest.approx(18.05)
    assert p95 < 19.0


def test_percentile_empty():
    assert _percentile([], 0.95) == 0.0


def test_streaming_histogram_vs_exact_quantiles():
    rng = np.random.default_rng(1)
    xs = rng.lognormal(mean=-3.0, sigma=1.0, size=5000)
    h = StreamingHistogram()
    for x in xs:
        h.add(float(x))
    assert h.count == len(xs)
    assert h.mean == pytest.approx(float(xs.mean()))
    assert h.vmin == pytest.approx(float(xs.min()))
    assert h.vmax == pytest.approx(float(xs.max()))
    # 32 bins/decade -> interpolated percentiles within ~7.5% relative.
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        assert h.percentile(q) == pytest.approx(exact, rel=0.08), q


def test_streaming_histogram_edges():
    h = StreamingHistogram()
    assert h.percentile(0.5) == 0.0
    assert h.summary()["count"] == 0
    h.add(0.25)
    assert h.percentile(0.99) == pytest.approx(0.25)
    # out-of-range samples clamp into edge buckets, stats stay exact
    h.add(1e-9)
    h.add(1e9)
    assert h.count == 3
    assert h.vmin == pytest.approx(1e-9)
    assert h.vmax == pytest.approx(1e9)
    assert h.percentile(0.0) >= h.vmin
    assert h.percentile(1.0) <= h.vmax


def test_windowed_gauge_and_rate_meter():
    g = WindowedGauge(window_s=10.0)
    for t, v in ((0.0, 2.0), (5.0, 4.0), (9.0, 6.0)):
        g.record(v, now=t)
    s = g.snapshot(now=9.0)
    assert s == {"last": 6.0, "mean": 4.0, "max": 6.0, "n": 3}
    s = g.snapshot(now=12.0)      # first point aged out of the window
    assert s["n"] == 2 and s["mean"] == 5.0 and s["last"] == 6.0

    r = RateMeter(window_s=10.0)
    for t in (0.0, 1.0, 2.0, 3.0, 4.0):
        r.record(4, now=t)
    assert r.rate(now=4.0) == pytest.approx(20 / 4.0)
    assert r.rate(now=100.0) == 0.0


# ---------------------------------------------------------------------------
# tracer: schema, round-trips, null identity
# ---------------------------------------------------------------------------
def test_tracer_schema_and_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("poll") as sp:
        sp.args["admitted"] = 2
        with tr.span("decode_step", live=3):
            pass
    tr.instant("finish", tid=TID_SLOT0 + 1, uid=7, tokens=4)
    tr.counter("serve_gauges", {"queue_depth": 1.0})

    spans = [e for e in tr.events if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["decode_step", "poll"]  # exit order
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 0
        assert e["cat"] == "serve"
    assert spans[1]["args"] == {"admitted": 2}
    # nesting: child inside parent
    child, parent = spans
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-6
    # every used tid got exactly one thread_name metadata record
    metas = [e for e in tr.events if e["ph"] == "M"]
    assert {m["tid"] for m in metas} == {TID_ENGINE, TID_SLOT0 + 1}
    assert all(m["name"] == "thread_name" for m in metas)

    chrome, jsonl = tmp_path / "t.json", tmp_path / "t.jsonl"
    tr.save(str(chrome))
    tr.save_jsonl(str(jsonl))
    assert json.loads(chrome.read_text())["traceEvents"] == tr.events
    assert trace_report.load_events(str(chrome)) == tr.events
    assert trace_report.load_events(str(jsonl)) == tr.events


def test_tracer_walltime_conversion():
    import time
    tr = Tracer()
    t_wall = time.time()
    t_pc = tr.pc_from_walltime(t_wall)
    assert abs(t_pc - time.perf_counter()) < 0.5


def test_tracer_reset():
    tr = Tracer()
    with tr.span("poll"):
        pass
    assert tr.events
    tr.reset()
    assert tr.events == []
    with tr.span("poll"):
        pass   # track names re-emit after reset
    assert any(e["ph"] == "M" for e in tr.events)


def test_null_tracer_is_inert():
    nt = NullTracer()
    assert not nt.enabled
    s1 = nt.span("poll", tid=TID_QUEUE, x=1)
    s2 = nt.span("decode_step")
    assert s1 is s2                       # one shared do-nothing span
    with s1 as sp:
        sp.args["admitted"] = 3
        assert sp.args["admitted"] == 3   # readable inside the span
    assert sp.args == {}                  # cleared on exit
    nt.instant("finish", uid=1)
    nt.counter("g", {"a": 1.0})
    nt.reset()
    assert NULL_TRACER.span("x") is NULL_TRACER.span("y")


# ---------------------------------------------------------------------------
# recompile sentinel
# ---------------------------------------------------------------------------
def test_recompile_sentinel_trips_on_real_retrace():
    f = jax.jit(lambda x: x * 2)
    s = RecompileSentinel("f", f)
    assert s.supported
    assert s.check() == 0          # nothing compiled yet
    f(jnp.ones((2,)))
    assert s.check() == 0          # first compile lazy-arms, not a trip
    f(jnp.ones((2,)))
    assert s.check() == 0          # cache hit
    f(jnp.ones((3,)))              # new shape -> retrace
    tr = Tracer()
    assert s.check(tr) == 1
    assert [e["name"] for e in tr.events if e["ph"] == "i"] == ["recompile"]
    ev = next(e for e in tr.events if e["ph"] == "i")
    assert ev["args"] == {"program": "f", "new_traces": 1, "trips": 1}
    s.arm()                        # re-baseline zeroes the count
    assert s.trips == 0 and s.check() == 0


def test_recompile_sentinel_strict_raises():
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones((2,)))
    s = RecompileSentinel("f", f, strict=True)
    s.arm()
    f(jnp.ones((4,)))
    with pytest.raises(RecompileError, match="retraced after warmup"):
        s.check()
    assert s.trips == 1            # counted even when raising


def test_recompile_sentinel_unsupported_fn_is_inert():
    s = RecompileSentinel("plain", lambda x: x)
    assert not s.supported
    assert s.check() == 0 and s.trips == 0


# ---------------------------------------------------------------------------
# trace_report: golden event stream -> summary
# ---------------------------------------------------------------------------
def _ev(name, ts, dur, tid=TID_ENGINE, **args):
    return {"name": name, "cat": "serve", "ph": "X", "pid": 0, "tid": tid,
            "ts": float(ts), "dur": float(dur), "args": args}


def _golden_events():
    """Hand-built 1000us trace with exactly known self-times.

    engine: serve.run[0,1000] { poll[0,400] { admit[0,100] {
    prefix_lookup[20,60], snapshot_restore[60,80] }, prefill_chunk
    [100,300], decode_step[300,400] }, poll[500,1000] {
    decode_step[500,950], pool_reset[950,980] } }
    host:   host_gap[400,500]
    """
    return [
        _ev("serve.run", 0, 1000),
        _ev("poll", 0, 400),
        _ev("admit", 0, 100, admitted=2),
        _ev("prefix_lookup", 20, 40, uid=1, matched_tokens=8),
        _ev("snapshot_restore", 60, 20, slot=0),
        _ev("prefill_chunk", 100, 200, rows=2, tokens=16),
        _ev("decode_step", 300, 100, live=2),
        _ev("host_gap", 400, 100, tid=TID_HOST),
        _ev("poll", 500, 500),
        _ev("decode_step", 500, 450, live=2),
        _ev("pool_reset", 950, 30, rows=1),
        # per-request tracks
        _ev("queue", 0, 50, tid=TID_QUEUE, uid=1),
        _ev("queue", 10, 60, tid=TID_QUEUE, uid=2),
        _ev("staging", 50, 250, tid=TID_SLOT0, uid=1),
        _ev("staging", 70, 230, tid=TID_SLOT0 + 1, uid=2),
        _ev("decode", 300, 650, tid=TID_SLOT0, uid=1),
        _ev("decode", 300, 150, tid=TID_SLOT0 + 1, uid=2),
        {"name": "finish", "cat": "serve", "ph": "i", "s": "t", "pid": 0,
         "tid": TID_ENGINE, "ts": 450.0,
         "args": {"uid": 2, "tokens": 3, "latency_s": 0.00045}},
        {"name": "recompile", "cat": "serve", "ph": "i", "s": "t", "pid": 0,
         "tid": TID_ENGINE, "ts": 960.0,
         "args": {"program": "decode", "new_traces": 1, "trips": 1}},
    ]


def test_golden_phase_breakdown():
    pb = trace_report.phase_breakdown(_golden_events())
    us = 1e-6
    assert pb["wall_s"] == pytest.approx(1000 * us)
    assert pb["phases_s"]["decode"] == pytest.approx(550 * us)
    assert pb["phases_s"]["prefill"] == pytest.approx(200 * us)
    # admit self (40) + prefix_lookup (40)
    assert pb["phases_s"]["admission"] == pytest.approx(80 * us)
    # snapshot_restore (20) + pool_reset (30)
    assert pb["phases_s"]["snapshot"] == pytest.approx(50 * us)
    # poll selves (0 + 20) + serve.run self (1000-400-500-100gap = 0)
    assert pb["phases_s"]["host_other"] == pytest.approx(20 * us)
    assert pb["phases_s"]["idle"] == pytest.approx(100 * us)
    assert pb["phase_total_s"] == pytest.approx(pb["wall_s"])
    assert pb["coverage"] == pytest.approx(1.0)


def test_golden_requests_ttft_slots_and_check():
    rep = trace_report.analyze(_golden_events())

    table = rep["requests"]
    assert [r["uid"] for r in table] == [1, 2]    # arrival order
    assert table[0]["queue_s"] == pytest.approx(50e-6)
    assert table[0]["staging_s"] == pytest.approx(250e-6)
    assert table[0]["decode_s"] == pytest.approx(650e-6)
    assert table[0]["slot"] == 0 and table[1]["slot"] == 1
    assert table[1]["tokens"] == 3
    assert table[1]["latency_s"] == pytest.approx(0.00045)

    td = rep["ttft_decomposition"]
    assert td["requests"] == 2
    # uid1: 50+250, uid2: 60+230 (us)
    assert td["ttft_mean_s"] == pytest.approx((300e-6 + 290e-6) / 2)
    assert td["queue_frac"] + td["prefill_frac"] == pytest.approx(1.0)
    assert td["first_decode_frac"] == 0.0

    su = rep["slot_utilization"]
    assert su["slots"]["0"]["busy_frac"] == pytest.approx(0.9)
    assert su["slots"]["1"]["busy_frac"] == pytest.approx(0.38)

    assert rep["recompile_trips"] == {"decode": 1}
    problems = trace_report.check(rep)
    assert len(problems) == 1 and "decode" in problems[0]

    # drop the recompile instant -> clean check
    clean = [e for e in _golden_events() if e["name"] != "recompile"]
    assert trace_report.check(trace_report.analyze(clean)) == []


def test_check_flags_bad_coverage():
    # one poll covering a third of the wall extent -> phases can't
    # reconcile with wall
    events = [_ev("poll", 0, 100), _ev("poll", 2900, 100)]
    rep = trace_report.analyze(events)
    problems = trace_report.check(rep)
    assert problems and "reconcile" in problems[0]


def test_trace_report_cli(tmp_path, capsys):
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": _golden_events()}))
    rc = trace_report.main([str(path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "per-phase wall breakdown" in out
    assert "TTFT decomposition" in out
    assert "slot-timeline utilization" in out
    # --check fails on the golden trace's planted decode recompile
    assert trace_report.main([str(path), "--check"]) == 1
    rc = trace_report.main([str(path), "--json"])
    assert rc == 0


# ---------------------------------------------------------------------------
# engine integration: null-tracer identity + live trace validity
# ---------------------------------------------------------------------------
def _run_engine(model, params, trace, **cfg_kw):
    scfg = ServeConfig(max_batch=2, prefill_buckets=(16,), max_new_tokens=4,
                       trace=trace, **cfg_kw)
    eng = ContinuousEngine(model, params, scfg)
    rng = np.random.default_rng(7)
    for n in (6, 12, 5, 9):
        eng.submit(rng.integers(1, V, n).tolist())
    done = eng.run()
    eng.close()
    return eng, {r.uid: r.out_tokens for r in done}


def test_null_tracer_identity_greedy():
    """Tracing must not change behavior: greedy outputs and compile
    counts identical with tracing on and off (monolithic AND chunked)."""
    model, params = _model_params()
    eng_off, out_off = _run_engine(model, params, trace=None)
    eng_plain, out_plain = _run_engine(model, params, trace=True)
    assert out_plain == out_off
    _, out_ch_off = _run_engine(model, params, trace=None, prefill_chunk=8)
    eng_on, out_ch_on = _run_engine(model, params, trace=True,
                                    metrics_every=2, prefill_chunk=8)
    assert out_ch_on == out_ch_off
    assert eng_plain.counters["decode_compiles"] == \
        eng_off.counters["decode_compiles"]
    assert isinstance(eng_off.tracer, NullTracer)
    assert not eng_off.tracer.enabled
    assert eng_off.tracer.span("x") is eng_off.tracer.span("y")
    assert eng_on.tracer.enabled and eng_on.tracer.events


def test_live_trace_validates_and_reconciles():
    model, params = _model_params()
    eng, out = _run_engine(model, params, trace=True, metrics_every=2,
                           prefill_chunk=8)
    events = eng.tracer.events
    names = {e["name"] for e in events}
    assert {"serve.run", "poll", "decode_step", "prefill_chunk", "admit",
            "queue", "staging", "decode", "finish"} <= names
    # every request has its per-request spans
    for kind in ("queue", "staging", "decode"):
        uids = {e["args"]["uid"] for e in events
                if e.get("ph") == "X" and e["name"] == kind}
        assert uids == set(out), kind

    rep = trace_report.analyze(events)
    assert trace_report.check(rep) == [], trace_report.check(rep)
    assert rep["recompile_trips"] == {}
    assert rep["ttft_decomposition"]["requests"] == len(out)
    assert rep["metrics_snapshots"] == len(eng.metrics.snapshots) > 0

    # sentinels saw the live run and never tripped
    assert all(s.trips == 0 for s in eng.sentinels.values())
    assert eng.counters["recompile_trips"]["decode"] == 0


def test_wave_engine_traced():
    model, params = _model_params()
    scfg = ServeConfig(max_batch=2, prefill_buckets=(16,), max_new_tokens=3,
                       trace=True)
    eng = Engine(model, params, scfg)
    rng = np.random.default_rng(5)
    for n in (6, 9, 12):
        eng.submit(rng.integers(1, V, n).tolist())
    done = eng.run()
    names = {e["name"] for e in eng.tracer.events}
    assert {"poll", "prefill_bucket", "decode_step", "queue",
            "staging", "decode"} <= names
    rep = trace_report.analyze(eng.tracer.events)
    assert rep["ttft_decomposition"]["requests"] == len(done) == 3
    assert trace_report.check(rep) == [], trace_report.check(rep)


def test_strict_recompile_config_plumbed():
    model, params = _model_params()
    scfg = ServeConfig(max_batch=2, prefill_buckets=(16,),
                       max_new_tokens=3, strict_recompile=True)
    eng = ContinuousEngine(model, params, scfg)
    assert all(s.strict for s in eng.sentinels.values())
    eng.submit([1, 2, 3])
    eng.run()                       # warmup compiles must not raise
    assert all(s.trips == 0 for s in eng.sentinels.values())


# ---------------------------------------------------------------------------
# metrics: snapshots, wall_source, health counters
# ---------------------------------------------------------------------------
def test_metrics_snapshot_cadence_and_content():
    tr = Tracer()
    m = ServeMetrics(slots=2, tracer=tr, metrics_every=2)
    m.record_arrival()
    m.record_first_token(0.010)
    m.record_step(2, 0.004)
    m.observe_gauges(queue_depth=3, live_slots=2)
    for _ in range(5):
        m.maybe_snapshot(extra_fn=lambda: {"extra": 1})
    assert len(m.snapshots) == 2     # polls 2 and 4
    snap = m.snapshots[-1]
    assert snap["extra"] == 1
    assert snap["gauges"]["queue_depth"]["last"] == 3.0
    assert snap["ttft"]["count"] == 1
    assert any(e["ph"] == "C" and e["name"] == "serve_gauges"
               for e in tr.events)
    assert sum(e["ph"] == "i" and e["name"] == "metrics_snapshot"
               for e in tr.events) == 2


def test_metrics_wall_source():
    m = ServeMetrics(slots=2)
    assert m.summary()["wall_source"] == "none"
    m.record_step(1, 0.5)
    s = m.summary()
    assert s["wall_source"] == "decode_time"
    assert s["wall_s"] == pytest.approx(0.5)
    m.record_wall(2.0)
    s = m.summary()
    assert s["wall_source"] == "measured"
    assert s["wall_s"] == pytest.approx(2.0)


def test_metrics_summary_percentiles_and_health():
    m = ServeMetrics(slots=2)
    for t in (0.010, 0.020, 0.030, 0.100):
        m.record_first_token(t)
    s = m.summary()
    # histogram percentiles resolve to the bucket holding the rank (tight
    # for large samples, tested above); with 4 samples just pin the order
    assert 0.018 <= s["ttft_p50_s"] <= 0.031
    assert s["ttft_p50_s"] <= s["ttft_p95_s"] <= s["ttft_p99_s"] <= 0.100
    assert s["ttft_p99_s"] >= 0.030
    m.record_straggler("decode")
    m.watchdog_fires += 1
    s = m.summary()
    assert s["stragglers_decode"] == 1 and s["watchdog_fires"] == 1


def test_reset_stats_resets_observability():
    model, params = _model_params()
    eng, _ = _run_engine(model, params, trace=True)
    assert eng.tracer.events and eng.metrics.completed == 4
    eng.reset_stats()
    assert eng.tracer.events == []
    assert eng.metrics.completed == 0
    assert all(s.trips == 0 for s in eng.sentinels.values())
    assert eng.monitor_decode.records == []


# ---------------------------------------------------------------------------
# host round trip: program spans end at ready logits; the copy and the draw
# are their siblings under poll
# ---------------------------------------------------------------------------
PROGRAM_SPANS = ("decode_step", "prefill_chunk", "prefill_bucket")
HOST_SPANS = ("logits_to_host", "sample")


def _engine_spans(events):
    return [e for e in events if e["ph"] == "X" and e["tid"] == TID_ENGINE]


def _inside(inner, outer, tol=1e-3):
    return (outer["ts"] - tol <= inner["ts"] and
            inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + tol)


def _overlap_us(a, b):
    return (min(a["ts"] + a["dur"], b["ts"] + b["dur"]) -
            max(a["ts"], b["ts"]))


def _assert_host_spans_outside_programs(events):
    spans = _engine_spans(events)
    progs = [e for e in spans if e["name"] in PROGRAM_SPANS]
    hosts = [e for e in spans if e["name"] in HOST_SPANS]
    assert progs and hosts
    for h in hosts:
        for p in progs:
            assert _overlap_us(h, p) <= 1e-3, (h, p)


def test_turnover_poll_emits_host_round_trip_siblings():
    """One traced chunked poll in which a prompt finishes next to a
    decoding request: the program spans, ``logits_to_host`` and
    ``sample`` are siblings under ``poll``, with the rows each draws and
    the rows whose token is kept."""
    model, params = _model_params()
    slots = 4
    eng = ContinuousEngine(model, params, ServeConfig(
        max_batch=slots, prefill_buckets=(16,), max_new_tokens=8,
        prefill_chunk=8, temperature=1.0, trace=True))
    try:
        eng.submit([3, 1, 4, 1, 5])
        while not any(r is not None for r in eng._slot_req):
            eng.poll()
        eng.submit([2, 7, 1, 8])            # one chunk: done in one poll
        eng.tracer.reset()
        eng.poll()
        spans = _engine_spans(eng.tracer.events)
    finally:
        eng.close()
    (poll,) = [e for e in spans if e["name"] == "poll"]
    kids = [e for e in spans if e is not poll]
    names = [e["name"] for e in kids]
    for name in ("prefill_chunk", "decode_step", "logits_to_host", "sample"):
        assert name in names, names
    for e in kids:
        assert _inside(e, poll), e
        if e["name"] in PROGRAM_SPANS + HOST_SPANS:
            # no engine span other than poll encloses a sibling
            assert not [o for o in kids if o is not e and _inside(e, o)
                        and o["dur"] > e["dur"]], e
    _assert_host_spans_outside_programs(spans)

    by_phase = {(e["name"], e["args"]["phase"]): e["args"] for e in kids
                if e["name"] in HOST_SPANS}
    assert by_phase[("sample", "prefill")] == {"phase": "prefill",
                                               "rows": slots, "used": 1}
    assert by_phase[("sample", "decode")] == {"phase": "decode",
                                              "rows": slots, "used": 2}
    assert by_phase[("logits_to_host", "prefill")]["rows"] == slots
    assert by_phase[("logits_to_host", "decode")]["rows"] == slots
    (dec,) = [e for e in kids if e["name"] == "decode_step"]
    assert dec["args"]["live"] == 2
    # the copy follows its program, and the draw follows the copy
    order = [(e["name"], e["args"].get("phase")) for e in
             sorted(kids, key=lambda e: e["ts"])
             if e["name"] in ("prefill_chunk", "decode_step") + HOST_SPANS]
    assert order == [("prefill_chunk", None), ("logits_to_host", "prefill"),
                     ("sample", "prefill"), ("decode_step", None),
                     ("logits_to_host", "decode"), ("sample", "decode")]


@pytest.mark.parametrize("chunk", [None, 8], ids=["bucket", "chunked"])
def test_program_spans_hold_no_host_round_trip(chunk):
    """Over a whole traced drain, no ``logits_to_host`` or ``sample``
    interval overlaps a program span, monolithic or chunked, and the
    phase report still reconciles."""
    model, params = _model_params()
    eng, _ = _run_engine(model, params, trace=True, prefill_chunk=chunk)
    events = eng.tracer.events
    _assert_host_spans_outside_programs(events)
    phases = {e["args"]["phase"] for e in _engine_spans(events)
              if e["name"] == "sample"}
    assert phases == {"prefill", "decode"}
    rep = trace_report.analyze(events)
    assert trace_report.check(rep) == [], trace_report.check(rep)
    assert rep["phase_breakdown"]["phases_s"]["sampling"] > 0


@pytest.mark.parametrize("chunk", [None, 8], ids=["bucket", "chunked"])
def test_traced_and_untraced_tokens_identical_under_temperature(chunk):
    model, params = _model_params()
    _, off = _run_engine(model, params, trace=None, prefill_chunk=chunk,
                         temperature=1.0, seed=3)
    _, on = _run_engine(model, params, trace=True, prefill_chunk=chunk,
                        temperature=1.0, seed=3)
    assert on == off
    assert len(off) == 4 and all(len(t) == 4 for t in off.values())


def test_program_modules_are_named_after_registry_names():
    """Each program's XLA module is ``jit_<registry name>``, also after a
    backend rebuild swaps the jits."""
    model, params = _model_params()
    slots, chunk = 2, 8
    eng = ContinuousEngine(model, params, ServeConfig(
        max_batch=slots, prefill_buckets=(16,), max_new_tokens=4,
        prefill_chunk=chunk))
    tok = jnp.zeros((slots, 1), jnp.int32)
    pos = jnp.zeros(slots, jnp.int32)

    def texts():
        return {
            "decode": eng._decode.lower(eng._decode_params, tok,
                                        eng.pool.cache, pos).as_text(),
            "prefill": eng._prefill.lower(
                eng.params, {"tokens": jnp.zeros((slots, 16), jnp.int32)},
                eng._scratch).as_text(),
            "prefill_chunk": eng._chunk_step.lower(
                eng.params, jnp.zeros((slots, chunk), jnp.int32),
                eng._ppool.cache, pos).as_text(),
        }

    def check(when):
        for name, text in texts().items():
            assert f"@jit_{name} " in text, (when, name)
            assert "jit__lambda" not in text, (when, name)
            assert eng.sentinels[name].program_id == \
                eng.registry.program_id(name), (when, name)

    try:
        check("built")
        eng._rebuild_backend("naive")
        check("rebuilt")
    finally:
        eng.close()


def test_tracer_span_enters_profiler_annotation(monkeypatch):
    from repro.serve import tracing
    seen = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracing, "TraceAnnotation", FakeAnnotation)
    with NullTracer().span("decode_step", live=1):
        pass
    assert seen == []
    tr = Tracer()
    with tr.span("poll"):
        with tr.span("sample", phase="decode", rows=2, used=1):
            pass
    assert seen == [("enter", "poll"), ("enter", "sample"),
                    ("exit", "sample"), ("exit", "poll")]
    assert [e["name"] for e in tr.events if e["ph"] == "X"] == \
        ["sample", "poll"]
