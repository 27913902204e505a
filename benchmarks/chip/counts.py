"""Operations and bytes of one program call, from a configuration's shapes.

The yardstick of every roofline and MFU share: what the algorithm needs
for the rows that carry a request, never what an implementation happens
to do, and never the padded rows of a static-shape program.  A later
change that skips idle rows, fuses passes or picks another kernel leaves
these numbers as they are.

* Operations are multiply-adds counted as 2: the projections, the causal
  conv, the state-space recurrence, the final unembedding of the logits
  the call returns.  Mamba-2 prefill counts the chunked SSD algorithm at
  the configuration's ``chunk_size`` (its quadratic intra-chunk form is
  part of the published model); decode counts the O(1) recurrence.
* Bytes are the compulsory traffic: every weight once per call (bf16),
  each live row's recurrent state read and written (SSM state fp32, conv
  tail in the served dtype), and the fp32 logits written.  Activations
  that a fused program keeps on chip are not counted, so the bytes are a
  lower bound.

``peak(device_kind)`` reads ``peaks.json``; a device that is not in the
table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path

from weights import dims

HERE = Path(__file__).resolve().parent
F32, WEIGHT = 4, 2   # bytes: fp32 SSM state and logits, bf16 weights


def peak(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def weight_bytes(cfg: dict) -> int:
    dm = dims(cfg)
    d, di, L, K = dm["d"], dm["di"], dm["L"], dm["K"]
    if cfg["family"] == "mamba2":
        H = dm["H"]
        per = (d * dm["d_in_proj"] + K * dm["d_xbc"] + dm["d_xbc"] +
               3 * H + di + di * d + d)
    else:
        N, R = dm["N"], dm["R"]
        per = (d * 2 * di + K * di + di + di * (R + 2 * N) + R * di + di +
               di * N + di + di * d + d)
    return WEIGHT * (L * per + dm["V"] * d + d)


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state: SSM state (fp32) plus conv tail."""
    dm = dims(cfg)
    L, K = dm["L"], dm["K"]
    if cfg["family"] == "mamba2":
        return L * (dm["H"] * dm["P"] * dm["N"] * F32 +
                    (K - 1) * dm["d_xbc"] * WEIGHT)
    return L * (dm["di"] * dm["N"] * F32 + (K - 1) * dm["di"] * WEIGHT)


def _layer_flops(cfg: dict, tokens: int) -> int:
    """One layer over ``tokens`` consecutive tokens of one row."""
    dm = dims(cfg)
    d, di, K, N = dm["d"], dm["di"], dm["K"], dm["N"]
    if cfg["family"] == "mamba2":
        H, P, G = dm["H"], dm["P"], dm["G"]
        proj = 2 * d * dm["d_in_proj"] + 2 * di * d + 2 * K * dm["d_xbc"]
        if tokens == 1:
            ssm = 5 * H * P * N
        else:
            q = min(cfg["chunk_size"], tokens)
            ssm = 2 * q * N * G + 2 * q * P * H + 4 * N * P * H
        return tokens * (proj + ssm)
    R = dm["R"]
    proj = (2 * d * 2 * di + 2 * K * di + 2 * di * (R + 2 * N) +
            2 * R * di + 2 * di * d)
    return tokens * (proj + 5 * di * N)


def call(cfg: dict, rows: int, tokens: int) -> tuple:
    """``(operations, bytes)`` of one call that advances ``rows`` live
    rows by ``tokens`` tokens each and returns each row's last logits:
    ``tokens=1`` is a decode step, ``tokens=chunk`` a prefill chunk."""
    dm = dims(cfg)
    ops = rows * (dm["L"] * _layer_flops(cfg, tokens) +
                  2 * dm["d"] * dm["V"])
    nbytes = (weight_bytes(cfg) + rows * 2 * state_bytes(cfg) +
              rows * dm["V"] * F32)
    return ops, nbytes


def min_seconds(cfg: dict, rows: int, tokens: int, pk: dict) -> float:
    """The roofline bound: the larger of ops over peak and bytes over
    bandwidth."""
    ops, nbytes = call(cfg, rows, tokens)
    return max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
