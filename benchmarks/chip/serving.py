"""The measured window: traffic offered to the engine, and what it served.

One loop in one thread offers the load and drives the engine: it submits
every request whose due time has come, then calls ``engine.poll()``
(admission, one prefill chunk, one decode step), and sleeps only when
the engine has nothing to do.  Each output token is stamped on the host
clock through ``Request.on_token``; TTFT counts from when a request was
*due*, so a stall also delays the requests queued behind it.

* Open loop: the window's requests are due at fixed offsets; after the
  window, those not finished are followed to completion for at most the
  mix's ``drain_cap_s``, and one still unfinished then counts as failed.
* Closed loop: each client's first request goes in before the window,
  and the window opens once every client's first token is out; a client
  sends its next request the moment the previous one completes.  The
  window closes with the poll in flight at ``seconds``, so it holds
  whole polls (a rate over it is not cut in the middle of one).  At the
  close, requests in flight are cut: their tokens inside the window
  count towards throughput, and they count neither as attempted nor as
  failed.

Hooks (``(offset_s, fn)``: the window's open and close, a traced slice)
run between polls, one at a time, so a slice starts and ends on
different polls.

``Capture`` records, for a sample of requests chosen from the seed before
the window, the logits the engine samples each of their tokens from (at a
seeded set of vocabulary columns): the correctness check compares them
with the reference afterwards.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from loadgen import Req

pc = time.perf_counter


@dataclasses.dataclass
class Record:
    req: Req
    uid: int
    due_pc: float
    submit_pc: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    admit_pc: Optional[float] = None
    status: str = "pending"   # ok | failed status | cut
    finish_pc: Optional[float] = None


class Capture:
    """Wraps the engine's row sampler to keep, for the sampled requests,
    the logits each token was drawn from at ``cols``."""

    def __init__(self, cols: np.ndarray):
        self.cols = cols
        self.uids: set = set()
        self.rows: Dict[int, Dict[int, np.ndarray]] = {}

    def install(self, engine) -> None:
        inner = engine._sample_rows
        cols, uids, rows = self.cols, self.uids, self.rows

        def sample_rows(logits, row_uids, positions):
            lg = np.asarray(logits, np.float32)
            for i, u in enumerate(row_uids):
                if u in uids:
                    rows.setdefault(int(u), {})[int(positions[i])] = \
                        lg[i, cols]
            return inner(lg, row_uids, positions)

        engine._sample_rows = sample_rows


class CompileCounter:
    """Counts JAX traces and backend compiles while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: List[Record]
    loop: str
    lateness_s: List[float]


class LoadLoop:
    def __init__(self, engine, capture: Optional[Capture] = None,
                 capture_keys=frozenset()):
        self.engine = engine
        self.capture = capture
        self.capture_keys = capture_keys
        self.by_uid: Dict[int, Record] = {}
        self.records: List[Record] = []

    def submit(self, req: Req, due_pc: float) -> Record:
        rec = Record(req, -1, due_pc, pc())

        def on_token(uid, tok, rec=rec):
            rec.stamps.append(pc())
            rec.tokens.append(tok)

        uid = self.engine.submit(req.prompt, req.max_new, on_token=on_token)
        if uid is None:
            rec.status = "rejected"
        else:
            rec.uid = uid
            self.by_uid[uid] = rec
            if self.capture is not None and req.key in self.capture_keys:
                self.capture.uids.add(uid)
        self.records.append(rec)
        return rec

    def poll(self) -> List[Record]:
        done = []
        for r in self.engine.poll():
            rec = self.by_uid[r.uid]
            rec.admit_pc = r.admit_pc
            rec.finish_pc = pc()
            ok = r.status == "ok" and not r.truncated and \
                len(r.out_tokens) == rec.req.max_new
            rec.status = "ok" if ok else (r.status if r.status != "ok"
                                          else "short")
            done.append(rec)
        return done

    def open_loop(self, schedule: List[Req], seconds: float,
                  drain_cap_s: float, hooks=()) -> Window:
        pending = collections.deque(sorted(schedule, key=lambda q: q.due_s))
        late = []
        t0 = pc()
        t1 = t0 + seconds
        hooks = sorted(hooks, key=lambda h: h[0])
        while True:
            now = pc()
            if hooks and t0 + hooks[0][0] <= now:
                hooks.pop(0)[1]()
            if now >= t1:
                break
            while pending and t0 + pending[0].due_s <= now:
                q = pending.popleft()
                self.submit(q, t0 + q.due_s)
                late.append(now - (t0 + q.due_s))
            if self.engine.busy:
                self.poll()
            else:
                nxt = t0 + pending[0].due_s if pending else t1
                if hooks:
                    nxt = min(nxt, t0 + hooks[0][0])
                time.sleep(max(0.0, min(nxt, t1) - pc()))
        for _, fn in hooks:
            fn()
        cap = t1 + drain_cap_s
        while self.engine.busy and pc() < cap:
            self.poll()
        for rec in self.records:
            if rec.status == "pending":
                rec.status = "unfinished"
        return Window(t0, t1, self.records, "open", late)

    def closed_loop(self, clients: List[List[Req]], seconds: float,
                    hooks=()) -> Window:
        nxt = [0] * len(clients)
        first = []
        now = pc()
        for k, reqs in enumerate(clients):
            first.append(self.submit(reqs[0], now))
            nxt[k] = 1
        # Ramp (set-up): every client's first request admitted and
        # decoding, so the window measures the steady state.
        while any(not r.stamps and r.status == "pending" for r in first):
            self._closed_poll(clients, nxt)
        t0 = pc()
        t1 = t0 + seconds
        hooks = sorted(hooks, key=lambda h: h[0])
        while pc() < t1:
            if hooks and t0 + hooks[0][0] <= pc():
                hooks.pop(0)[1]()
            self._closed_poll(clients, nxt)
        t1 = pc()
        for _, fn in hooks:
            fn()
        for rec in self.records:
            if rec.status == "pending":
                rec.status = "cut"
        return Window(t0, t1, self.records, "closed", [])

    def _closed_poll(self, clients, nxt) -> None:
        for rec in self.poll():
            k = rec.req.client
            if nxt[k] < len(clients[k]):
                self.submit(clients[k][nxt[k]], pc())
                nxt[k] += 1
