"""Operation and byte counts of the roofline, checked by hand."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import counts  # noqa: E402


def cfg(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_mamba2_2p7b_decode_call_by_hand():
    c = cfg("mamba2-2.7b")
    d, di, L, V, H, P, N = 2560, 5120, 64, 50288, 80, 64, 128
    d_xbc = di + 2 * N              # 5376
    d_in = 2 * di + 2 * N + H       # 10576
    per_token_layer = (2 * d * d_in + 2 * di * d + 2 * 4 * d_xbc +
                       5 * H * P * N)
    ops = 8 * (L * per_token_layer + 2 * d * V)
    w = 2 * (L * (d * d_in + 4 * d_xbc + d_xbc + 3 * H + di + di * d + d) +
             V * d + d)
    state = L * (H * P * N * 4 + 3 * d_xbc * 2)
    nbytes = w + 8 * 2 * state + 8 * V * 4
    assert counts.call(c, 8, 1) == (ops, nbytes)
    assert w == 5_405_199_360       # the described compile's weight bytes
    assert state * 8 == 1_358_692_352   # and its arena
    assert ops == pytest.approx(4.4906e10, rel=1e-4)


def test_mamba2_2p7b_chunk_call_by_hand():
    c = cfg("mamba2-2.7b")
    d, di, L, V, H, P, N, Q = 2560, 5120, 64, 50288, 80, 64, 128, 256
    d_xbc, d_in = di + 2 * N, 2 * di + 2 * N + H
    proj = 2 * d * d_in + 2 * di * d + 2 * 4 * d_xbc
    ssd = 2 * Q * N + 2 * Q * P * H + 4 * N * P * H   # per token, one group
    ops = 8 * (L * 256 * (proj + ssd) + 2 * d * V)
    ops1, bytes1 = counts.call(c, 8, 256)
    assert ops1 == ops
    assert bytes1 == counts.call(c, 8, 1)[1]   # tokens add no compulsory bytes
    pk = counts.peak("TPU v5 lite")
    assert counts.min_seconds(c, 8, 256, pk) == pytest.approx(ops / 197e12)


def test_mamba_130m_decode_call_by_hand():
    c = cfg("mamba-130m")
    d, di, L, V, N, R = 768, 1536, 24, 50280, 16, 48
    proj = (2 * d * 2 * di + 2 * 4 * di + 2 * di * (R + 2 * N) +
            2 * R * di + 2 * di * d)
    ops = 256 * (L * (proj + 5 * di * N) + 2 * d * V)
    w = 2 * (L * (d * 2 * di + 4 * di + di + di * (R + 2 * N) + R * di +
                  di + di * N + di + di * d + d) + V * d + d)
    state = L * (di * N * 4 + 3 * di * 2)
    assert counts.call(c, 256, 1) == (ops, w + 256 * 2 * state + 256 * V * 4)
    pk = counts.peak("TPU v5 lite")
    # 1.6 GB at 819 GB/s: decode at 256 rows is bound by bytes.
    assert counts.min_seconds(c, 256, 1, pk) == pytest.approx(
        counts.call(c, 256, 1)[1] / 819e9)


@pytest.mark.parametrize("tokens", [1, 256])
def test_counts_are_of_live_rows_only(tokens):
    c = cfg("mamba2-2.7b")
    ops1, b1 = counts.call(c, 1, tokens)
    ops3, b3 = counts.call(c, 3, tokens)
    assert ops3 == 3 * ops1
    # Weights are read once whatever the rows; each row adds its state.
    assert b3 - b1 == 2 * (2 * counts.state_bytes(c) +
                           50288 * counts.F32)
    assert counts.call(c, 0, tokens) == (0, counts.weight_bytes(c))


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peak("cpu")
