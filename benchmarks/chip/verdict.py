"""Whether what the timed path served is correct.

After the window has closed, the engine is freed, and the plain float32
reference (``reference.py``) is run over each sampled request's stream
exactly as the engine consumed it: the prompt left-padded with the pad id
to a whole number of prefill chunks, then every served token but the
last.  That covers chunked prefill through the staging pool (the first
token), the hand-off into the decode pool (the second) and cached decode
(the rest).  One number is compared, ``gap``, with its limit from the
cell's file (``workloads/<cell>.json``, ``check.limits``): the widest
gap, in logits, between what the engine served and the reference, over
every sampled position.  It is the larger of two readings:

* ``logit_err``: the widest gap between a logit the engine sampled from
  and the reference's, over the captured vocabulary columns;
* ``token_gap``: the widest gap by which a served token's score lies
  below the reference's best.  Tokens are drawn at temperature 1 by the
  Gumbel-max rule with noise keyed on ``(seed, uid, position)``
  (``default_rng([seed, uid, position])``, the engine's documented
  sampling rule), so the score is ``reference logit + that noise`` and a
  correctly served token is the reference's best up to rounding.

A state that goes stale moves the logits; a token altered after sampling
leaves the logits right and moves only its score; a lower precision
moves both.

The control (``numbers(..., control=True)``) reads the same for the
reference computed with its matmul operands in float8, one precision
step below the served bfloat16, put in the engine's place on the same
prompts and tokens: its logits at the captured columns, and the token
it puts first under the same noise.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import reference

TINY = 1e-20


def gumbel(seed: int, uid: int, position: int, vocab: int) -> np.ndarray:
    u = np.random.default_rng([seed, uid, position]).random(size=vocab)
    return -np.log(-np.log(u + TINY) + TINY)


def span(rec, chunk: int) -> int:
    """The prompt's length left-padded to whole chunks, as the engine
    stages it (prompts are never longer than its one prefill bucket)."""
    return -(-len(rec.req.prompt) // chunk) * chunk


def stream(rec, chunk: int, pad_id: int = 0) -> np.ndarray:
    """The tokens the engine consumed for ``rec``: its prompt left-padded
    to whole chunks and its served tokens but the last."""
    n = span(rec, chunk)
    prompt = np.full(n, pad_id, np.int32)
    prompt[n - len(rec.req.prompt):] = rec.req.prompt
    return np.concatenate([prompt, np.asarray(rec.tokens[:-1], np.int32)])


@functools.lru_cache(maxsize=None)
def _ref_fn(cfg_key, count: int, cast: Callable):
    cfg = dict(cfg_key)
    return jax.jit(lambda p, t, s: reference.logits_at(cfg, p, t, s, count,
                                                       cast))


def _key(cfg: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if not isinstance(v, dict)))


def ref_logits(cfg: dict, params, rec, chunk: int, count_bucket: int,
               cast=reference.exact, t_bucket: int = 2048) -> np.ndarray:
    """Reference logits ``(len(rec.tokens), vocab)`` at the positions the
    served tokens were sampled from.  Lengths are padded to buckets (at
    the end: causal, so earlier positions do not see the padding) to keep
    the number of compiled shapes small."""
    toks = stream(rec, chunk)
    n, start = len(rec.tokens), span(rec, chunk) - 1
    t = -(-max(len(toks), start + count_bucket) // t_bucket) * t_bucket
    padded = np.zeros(t, np.int32)
    padded[:len(toks)] = toks
    fn = _ref_fn(_key(cfg), count_bucket, cast)
    out = fn(params, jnp.asarray(padded), jnp.int32(start))
    return np.asarray(out, np.float32)[:n]


def numbers(cfg: dict, params, recs: List, rows: Dict[int, dict],
            cols: np.ndarray, seed: int, chunk: int, count_bucket: int,
            control: bool = False) -> Dict[str, float]:
    """``gap`` (the larger of ``logit_err`` and ``token_gap``) and the
    count of positions compared, for the served path (captured logits and
    served tokens) or, with ``control``, for the float8 reference in its
    place on the same streams."""
    err = gap = 0.0
    positions = 0
    for rec in recs:
        ref = ref_logits(cfg, params, rec, chunk, count_bucket)
        low = (ref_logits(cfg, params, rec, chunk, count_bucket,
                          reference.fp8) if control else None)
        vocab = ref.shape[1]
        first = span(rec, chunk)
        for j, tok in enumerate(rec.tokens):
            pos = first + j
            score = ref[j] + gumbel(seed, rec.uid, pos, vocab)
            if control:
                got = low[j, cols]
                tok = int(np.argmax(low[j] + gumbel(seed, rec.uid, pos,
                                                    vocab)))
            else:
                got = rows[rec.uid][pos]
            err = max(err, float(np.max(np.abs(got - ref[j, cols]))))
            gap = max(gap, float(score.max() - score[tok]))
            positions += 1
    return {"gap": max(err, gap), "logit_err": err, "token_gap": gap,
            "positions": positions}


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return nums["positions"] > 0 and all(nums[k] <= v
                                         for k, v in limits.items())
