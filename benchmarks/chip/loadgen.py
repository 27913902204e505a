"""One general traffic generator, driven by a mix's parameters.

A mix (``traffic/<name>.json``) is data:

* ``loop``: ``"open"`` (independent users, Poisson arrivals at
  ``rate_per_s``) or ``"closed"`` (``clients`` callers, each sending its
  next request when the previous one completes);
* ``prompt`` and ``output``: length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
  ``{"dist": "loguniform", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``;
* ``drain_cap_s`` (open loop): how long after the window requests due in
  it are followed to completion;
* ``stagger_first`` (closed loop): each client's first output budget is
  a uniform fraction of its drawn length, so completions and admissions
  run through the window at a steady rate from the start.

Every seed gets the same work.  The lengths, inter-arrival gaps and
stagger fractions are one multiset drawn from the mix's ``base_seed``;
``--seed`` draws the prompts' token ids and, with ``"order": "seeded"``
(the default), permutes the multiset's order.  With ``"order": "fixed"``
every seed replays the same arrivals and lengths in the same order, and
the seed changes only the tokens: a tail over a few dozen requests then
varies from run to run with the system alone.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    key: int                 # position in the schedule
    client: int              # closed loop: the caller; open loop: -1
    due_s: Optional[float]   # open loop: offset from the window start
    prompt: List[int]
    max_new: int


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    kind = spec["dist"]
    if kind == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif kind == "loguniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    elif kind == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _base(mix: dict) -> np.random.Generator:
    return np.random.default_rng(mix.get("base_seed", 0))


def _tokens(rng, n, vocab) -> List[int]:
    return rng.integers(1, vocab, n).tolist()


def _order(mix: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if mix.get("order", "seeded") == "fixed":
        return np.arange(n)
    return rng.permutation(n)


def open_loop(mix: dict, seconds: float, seed: int, vocab: int) -> List[Req]:
    """``round(rate * seconds)`` requests, all due inside the window."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    base = _base(mix)
    gaps = base.exponential(1.0, n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    prompt = lengths(mix["prompt"], n, base)
    out = lengths(mix["output"], n, base)
    rng = np.random.default_rng([seed, 1])
    due = np.cumsum(gaps[_order(mix, rng, n)])
    order = _order(mix, rng, n)
    return [Req(i, -1, float(due[i]), _tokens(rng, int(prompt[j]), vocab),
                int(out[j])) for i, j in enumerate(order)]


def closed_loop(mix: dict, seed: int, vocab: int) -> List[List[Req]]:
    """Per client, its requests in the order it sends them."""
    c, m = mix["clients"], mix["requests_per_client"]
    base = _base(mix)
    prompt = lengths(mix["prompt"], c * m, base)
    out = lengths(mix["output"], c * m, base)
    if mix.get("stagger_first", False):
        frac = base.uniform(0.0, 1.0, c)
        out[:c] = np.maximum(1, np.ceil(frac * out[:c]))
    rng = np.random.default_rng([seed, 1])
    first = _order(mix, rng, c)              # the first requests' draws
    rest = c + _order(mix, rng, c * (m - 1))  # and everyone else's
    clients = []
    for k in range(c):
        draws = [first[k]] + list(rest[k * (m - 1):(k + 1) * (m - 1)])
        clients.append([Req(k * m + i, k, None,
                            _tokens(rng, int(prompt[j]), vocab), int(out[j]))
                        for i, j in enumerate(draws)])
    return clients


def max_prompt(mix: dict) -> int:
    return int(mix["prompt"]["max"])


def max_output(mix: dict) -> int:
    return int(mix["output"]["max"])
