"""Traffic generation and the end-to-end metric arithmetic."""
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402
import timing  # noqa: E402
from serving import LoadLoop, Record, Window  # noqa: E402

BIG_SEED = 2 ** 40 + 17


# Long prompts, log-uniform, and short uniform answers: document QA.
LONGPROMPT = {"loop": "open", "rate_per_s": 0.6, "drain_cap_s": 120,
              "prompt": {"dist": "loguniform", "min": 2048, "max": 16384},
              "output": {"dist": "uniform", "min": 16, "max": 64}}


def mix(name):
    if name == "longprompt":
        return dict(LONGPROMPT)
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "longprompt"])
def test_open_loop_reproduces_and_keeps_the_work(name):
    m = mix(name)
    a = loadgen.open_loop(m, 30.0, BIG_SEED, 50277)
    b = loadgen.open_loop(m, 30.0, BIG_SEED, 50277)
    assert [(q.due_s, q.prompt, q.max_new) for q in a] == \
        [(q.due_s, q.prompt, q.max_new) for q in b]
    c = loadgen.open_loop(m, 30.0, BIG_SEED + 1, 50277)
    # Another seed: another order and other tokens, the same work.
    assert sorted((len(q.prompt), q.max_new) for q in a) == \
        sorted((len(q.prompt), q.max_new) for q in c)
    assert [q.prompt for q in a] != [q.prompt for q in c]
    assert max(q.due_s for q in a) == pytest.approx(max(q.due_s
                                                        for q in c))
    assert len(a) == round(m["rate_per_s"] * 30)
    assert all(0 < q.due_s < 30.0 for q in a)
    p, o = m["prompt"], m["output"]
    assert all(p["min"] <= len(q.prompt) <= p["max"] for q in a)
    assert all(o["min"] <= q.max_new <= o["max"] for q in a)


def test_fixed_order_replays_the_same_arrivals_with_other_tokens():
    m = dict(mix("chat"), order="fixed")
    a = loadgen.open_loop(m, 30.0, BIG_SEED, 50277)
    c = loadgen.open_loop(m, 30.0, BIG_SEED + 1, 50277)
    assert [(q.due_s, len(q.prompt), q.max_new) for q in a] == \
        [(q.due_s, len(q.prompt), q.max_new) for q in c]
    assert [q.prompt for q in a] != [q.prompt for q in c]


def test_closed_loop_reproduces_and_staggers_first_budgets():
    m = mix("decode")
    a = loadgen.closed_loop(m, BIG_SEED, 50280)
    b = loadgen.closed_loop(m, BIG_SEED, 50280)
    assert [[(q.prompt, q.max_new) for q in c] for c in a] == \
        [[(q.prompt, q.max_new) for q in c] for c in b]
    assert len(a) == m["clients"]
    firsts = np.array([c[0].max_new for c in a], float)
    later = np.array([q.max_new for c in a for q in c[1:]], float)
    # Staggered: first budgets are a uniform fraction of the drawn
    # length, so about half the length on average and spread wide.
    assert firsts.mean() < 0.7 * later.mean()
    assert firsts.min() < m["output"]["min"] <= later.min()
    c = loadgen.closed_loop(m, BIG_SEED + 1, 50280)
    # Another seed deals the same first budgets and the same later
    # requests to other clients.
    for pick in (lambda cl: cl[:1], lambda cl: cl[1:]):
        assert sorted((len(q.prompt), q.max_new) for cl in a
                      for q in pick(cl)) == \
            sorted((len(q.prompt), q.max_new) for cl in c for q in pick(cl))
    assert [cl[0].prompt for cl in a] != [cl[0].prompt for cl in c]


def window(records, t0=10.0, t1=20.0, loop="open"):
    return Window(t0, t1, records, loop, [])


def rec(due, stamps, status="ok", admit=None):
    r = Record(loadgen.Req(0, -1, due, [1], len(stamps)), 1, due, due,
               stamps=list(stamps), tokens=[0] * len(stamps),
               admit_pc=admit)
    r.status = status
    return r


def test_ttft_counts_from_the_due_time_not_the_submit():
    r = rec(11.0, [11.5, 11.6])
    r.submit_pc = 11.4          # the generator ran late
    w = window([r])
    assert timing.ttfts(w) == [pytest.approx(0.5)]


def test_itl_gaps_come_from_token_stamps_inside_the_window():
    a = rec(9.0, [9.5, 10.5, 10.7, 20.5])   # first gap ends in the window
    b = rec(12.0, [12.1, 12.4])
    gaps = sorted(timing.itl_gaps(window([a, b])))
    assert gaps == pytest.approx([0.2, 0.3, 1.0])
    assert timing.tokens_in_window(window([a, b])) == 4


def test_percentiles_follow_numpy_over_the_whole_window():
    vals = list(np.random.default_rng(0).exponential(1.0, 1001))
    for q in (50, 90, 95):
        assert timing.percentile(vals, q) == pytest.approx(
            float(np.quantile(vals, q / 100)))
    # Not a median of per-chunk percentiles.
    chunks = [timing.percentile(vals[i:i + 100], 95)
              for i in range(0, 1000, 100)]
    assert timing.percentile(vals, 95) != pytest.approx(float(
        np.median(chunks)))
    assert timing.percentile([], 50) is None


def test_attempted_and_failed():
    ok = rec(11.0, [11.5])
    late = rec(19.0, [], status="unfinished")
    shed = rec(15.0, [15.5], status="shed_deadline")
    assert timing.attempted_failed(window([ok, late, shed])) == (3, 2)
    cut = rec(15.0, [15.5], status="cut")
    assert timing.attempted_failed(window([ok, cut], loop="closed")) == \
        (1, 0)


def test_queue_wait_from_due_to_admission():
    w = window([rec(11.0, [11.9], admit=11.25), rec(12.0, [], admit=None)])
    assert timing.queue_waits(w) == [pytest.approx(0.25)]


class FakeEngine:
    """Advances every live request by one token per poll of ``poll_s``."""

    def __init__(self, poll_s):
        self.poll_s = poll_s
        self.live = {}
        self.uids = 0

    @property
    def busy(self):
        return bool(self.live)

    def submit(self, prompt, max_new, on_token=None):
        self.uids += 1
        self.live[self.uids] = [max_new, on_token]
        return self.uids

    def poll(self):
        time.sleep(self.poll_s)
        done = []
        for uid, entry in list(self.live.items()):
            entry[1](uid, 7)
            entry[0] -= 1
            if entry[0] == 0:
                del self.live[uid]
                done.append(SimpleNamespace(uid=uid, admit_pc=None,
                                            status="ok", truncated=False,
                                            out_tokens=[7]))
        return done


def test_closed_loop_window_holds_whole_polls_and_hooks_fire_apart():
    clients = [[loadgen.Req(k * 100 + i, k, None, [1], 3) for i in range(100)]
               for k in range(2)]
    fired = []
    hooks = [(0.11, lambda: fired.append(time.perf_counter())),
             (0.12, lambda: fired.append(time.perf_counter()))]
    w = LoadLoop(FakeEngine(0.1)).closed_loop(clients, 0.35, hooks)
    # Closed on the poll in flight at 0.35 s: four whole polls, each
    # emitting a token for both clients.
    assert 0.35 <= w.t1 - w.t0 < 0.45
    assert timing.tokens_in_window(w) == 2 * 4
    # Both hooks fell due during one poll; they ran on different polls.
    assert len(fired) == 2 and fired[1] - fired[0] >= 0.09
