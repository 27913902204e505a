"""The host round-trip readers on synthetic engine spans."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import catalog  # noqa: E402

READERS = ("decode_sample_ms", "prefill_sample_ms", "logits_to_host_ms",
           "sample_row_use")


def _run(spans, t0=10.0, t1=20.0):
    return SimpleNamespace(window=SimpleNamespace(t0=t0, t1=t1), spans=spans)


def _span(name, t0, dur, **args):
    return (name, 0, t0, t0 + dur, args)


def two_polls():
    """Two polls in the window (10-20 s): a turnover draw of 8 rows that
    keeps 1, and decode draws that keep 6 and 7; plus spans that straddle
    the window's edges or lie outside it, which must not count."""
    return [
        _span("poll", 11.0, 1.0),
        _span("prefill_chunk", 11.0, 0.1, rows=1),
        _span("logits_to_host", 11.1, 0.004, phase="prefill", rows=8),
        _span("sample", 11.104, 0.3, phase="prefill", rows=8, used=1),
        _span("decode_step", 11.404, 0.005, live=6),
        _span("logits_to_host", 11.409, 0.002, phase="decode", rows=8),
        _span("sample", 11.411, 0.5, phase="decode", rows=8, used=6),
        _span("poll", 12.0, 1.0),
        _span("decode_step", 12.0, 0.005, live=7),
        _span("logits_to_host", 12.005, 0.004, phase="decode", rows=8),
        _span("sample", 12.009, 0.3, phase="decode", rows=8, used=7),
        # straddling the open and the close, and wholly outside
        _span("sample", 9.9, 0.2, phase="decode", rows=8, used=0),
        _span("sample", 19.9, 0.2, phase="prefill", rows=8, used=0),
        _span("logits_to_host", 19.95, 0.1, phase="decode", rows=8),
        _span("sample", 25.0, 1.0, phase="decode", rows=8, used=0),
    ]


def test_readers_average_inside_the_window():
    run = _run(two_polls())
    got = {name: catalog.reader(name)(run) for name in READERS}
    assert got["decode_sample_ms"] == pytest.approx(400.0)
    assert got["prefill_sample_ms"] == pytest.approx(300.0)
    assert got["logits_to_host_ms"] == pytest.approx(3.0)
    assert got["sample_row_use"] == pytest.approx(100.0 * 14 / 24)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_without_spans(name):
    read = catalog.reader(name)
    assert read(_run([])) is None
    # a program that records no host round-trip spans
    assert read(_run([_span("poll", 11.0, 1.0),
                      _span("decode_step", 11.0, 0.5, live=3)])) is None
    # spans only across the edges of the window
    assert read(_run([_span("sample", 9.5, 1.0, phase="decode", rows=4,
                            used=2),
                      _span("sample", 19.5, 1.0, phase="prefill", rows=4,
                            used=1),
                      _span("logits_to_host", 19.9, 0.2, phase="decode",
                            rows=4)])) is None


def test_phase_readers_ignore_the_other_phase():
    only_prefill = [_span("sample", 11.0, 0.2, phase="prefill", rows=4,
                          used=1),
                    _span("logits_to_host", 11.3, 0.01, phase="prefill",
                          rows=4)]
    run = _run(only_prefill)
    assert catalog.reader("decode_sample_ms")(run) is None
    assert catalog.reader("logits_to_host_ms")(run) is None
    assert catalog.reader("prefill_sample_ms")(run) == pytest.approx(200.0)
    assert catalog.reader("sample_row_use")(run) == pytest.approx(25.0)
