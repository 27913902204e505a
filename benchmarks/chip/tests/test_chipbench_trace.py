"""Trace reduction on a small synthetic trace with known answers."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import tracereduce as tr  # noqa: E402

MS = 1e6        # nanoseconds per millisecond
OFF = 100.0     # perf_counter = trace seconds + OFF


def planes():
    """A 100 ms traced interval (with a device plane that holds no XLA
    operations, as a TPU trace has) (sync at 0 and 100 ms): a prefill chunk
    program 10-40 ms, a pool scatter 41-42 ms, a decode program 45-55
    ms, another decode 70-80 ms."""
    sync = [(tr.SYNC, 0.0, 0.0), (tr.SYNC, 100 * MS, 0.0)]
    mods = [("jit__lambda", 10 * MS, 30 * MS), ("jit_insert", 41 * MS, 1 * MS),
            ("jit__lambda", 45 * MS, 10 * MS),
            ("jit__lambda", 70 * MS, 10 * MS)]
    ops = [("fusion.1", 10 * MS, 20 * MS), ("dot.2", 28 * MS, 12 * MS),
           ("scatter", 41 * MS, 1 * MS), ("fusion.1", 45 * MS, 10 * MS),
           ("fusion.1", 70 * MS, 10 * MS)]
    return [{"name": "/host:CPU",
             "lines": [{"name": "python", "events": sync}]},
            {"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": mods},
                       {"name": "XLA Ops", "events": ops}]},
            {"name": "/device:CUSTOM:Megascale Trace", "lines": []}]


def spans():
    s = lambda a, b: (OFF + a / 1e3, OFF + b / 1e3)  # noqa: E731
    return [("poll", 0, *s(5, 60), {}),
            ("prefill_chunk", 0, *s(8, 40.5), {"rows": 2}),
            ("decode_step", 0, *s(40.5, 58), {"live": 3}),
            ("host_gap", 1, *s(60, 68), {}),
            ("poll", 0, *s(68, 85), {}),
            ("decode_step", 0, *s(69, 84), {"live": 4})]


def test_busy_union_and_idle_share():
    r = tr.reduce(planes(), spans(), [OFF, OFF + 0.1])
    # ops 10-40 (two overlapping ops), 41-42, 45-55, 70-80: 51 ms busy.
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.051)


def test_device_time_is_attributed_to_program_spans():
    r = tr.reduce(planes(), spans(), [OFF, OFF + 0.1])
    assert [(a["rows"], pytest.approx(s)) for a, s in
            r["calls"]["prefill_chunk"]] == [(2, 0.030)]
    # The pool scatter queued inside the first decode span is not decode.
    assert [(a["live"], pytest.approx(s)) for a, s in
            r["calls"]["decode_step"]] == [(3, 0.010), (4, 0.010)]


def test_breakdown_lists():
    r = tr.reduce(planes(), spans(), [OFF, OFF + 0.1], top=3)
    ops = r["breakdown"]["device_ops"]
    assert [n for n, _ in ops] == ["fusion.1", "dot.2", "scatter"]
    assert ops[0][1] == pytest.approx(0.040)
    gaps = r["breakdown"]["idle_gaps"]
    # Idle: 0-10 (poll from 5 ms; midpoint 5 ms), 42-45 (decode span),
    # 55-70 (host gap at the midpoint 62.5 ms), 80-100 (outside).
    assert gaps == [["outside engine spans", pytest.approx(0.020)],
                    ["host_gap", pytest.approx(0.015)],
                    ["poll", pytest.approx(0.010)]]


def test_clock_offset_from_sync_events():
    assert tr.clock_offset(planes(), [OFF, OFF + 0.1]) == pytest.approx(OFF)
    with pytest.raises(ValueError, match="sync events"):
        tr.clock_offset(planes(), [OFF])


def test_merge():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
