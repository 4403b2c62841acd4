"""Open-loop load from a traffic file, and the driver that feeds it to a
``ContinuousBatchingServer`` at the scheduler's own admission point.

A traffic file is JSON with these keys:

* ``arrivals``: ``"poisson"`` (``rate_rps`` requests a second) or
  ``"backlog"`` (``backlog`` requests, all due at t = 0);
* ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length in tokens, clipped to ``[min, max]``;
* ``levels``: ``{rung: share}``, each request pinned to one rung;
* ``drain``: ``"all"`` (every request due in the window is served to
  its end after the close) or ``"active"`` (requests still queued at the
  close are withdrawn; those in a slot finish).

Poisson arrivals are a Poisson process conditioned on its count: the
window of ``seconds`` holds ``n = rate_rps x seconds`` requests, and
their due times are ``n`` independent uniform draws over the window,
sorted.  That is exactly how a Poisson process of that rate places the
arrivals it has, bursts and lulls included; only the count no longer
varies from seed to seed.

Every seed gets the same work.  The prompt lengths, output lengths and
rungs are fixed quantiles of their distributions (the i-th of n at
probability (i + 0.5) / n), drawn for each rung's requests apart; the
seed orders each list, places the arrivals and draws the prompt tokens.
The lists' order is of low discrepancy (``_spread_order``): every prefix
of it is an even sample of the list's quantiles, so a window that serves
only the first part of a backlog sees nearly the same mix for every
seed.  Runs with different seeds differ in order and timing, not in
amount: what the spread between runs measures is the system, not the
draw.
"""

from __future__ import annotations

import dataclasses
import math
from collections import Counter
import time
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""

    index: int
    due: float              # seconds after the window opens
    prompt: List[int]
    max_new: int
    level: str


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    vals = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


def _spread_order(values, rng) -> np.ndarray:
    """``values`` in a seeded low-discrepancy order: the i-th smallest
    value goes to the place of ``(u + i * golden ratio) mod 1`` among
    all, ``u`` drawn from the seed, so every prefix of the order is an
    even sample of the values' quantiles."""
    v = np.sort(np.asarray(values))
    keys = np.mod(rng.random() + _GOLDEN * np.arange(len(v)), 1.0)
    return v[np.argsort(keys, kind="stable")]


def _shares(levels: Dict[str, float], n: int) -> List[str]:
    """``n`` rung names in the stated shares (largest remainder)."""
    names = sorted(levels)
    raw = [levels[k] * n for k in names]
    counts = [int(math.floor(r)) for r in raw]
    for i in sorted(range(len(names)), key=lambda i: counts[i] - raw[i])[: n - sum(counts)]:
        counts[i] += 1
    return [k for k, c in zip(names, counts) for _ in range(c)]


def n_requests(traffic: dict, seconds: float) -> int:
    if traffic["arrivals"] == "poisson":
        return max(1, int(round(traffic["rate_rps"] * seconds)))
    if traffic["arrivals"] == "backlog":
        return int(traffic["backlog"])
    raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")


def output_lengths(traffic: dict, seconds: float) -> List[int]:
    """The distinct output lengths a run of ``seconds`` can serve: the
    same for every seed."""
    n = n_requests(traffic, seconds)
    out = set()
    for k in Counter(_shares(traffic["levels"], n)).values():
        out |= set(_lognormal_quantiles(traffic["output"], k).tolist())
    return sorted(out)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    """The run's requests, in due order."""
    n = n_requests(traffic, seconds)
    rng = np.random.default_rng(int(seed))
    levels = _spread_order(np.array(_shares(traffic["levels"], n)), rng)
    # each rung gets its own fixed quantiles, so the work of every rung,
    # and not only the total, is the same for every seed
    lengths = {}
    for lv in sorted(set(levels.tolist())):
        k = int(np.sum(levels == lv))
        lengths[lv] = (iter(_spread_order(_lognormal_quantiles(traffic["prompt"], k), rng)),
                       iter(_spread_order(_lognormal_quantiles(traffic["output"], k), rng)))
    prompts, outputs = [], []
    for lv in levels:
        prompts.append(next(lengths[lv][0]))
        outputs.append(next(lengths[lv][1]))
    if traffic["arrivals"] == "poisson":
        due = np.sort(rng.uniform(0.0, float(seconds), size=n))
    else:
        due = np.zeros(n)
    return [
        Planned(index=i, due=float(due[i]),
                prompt=rng.integers(1, vocab, size=int(prompts[i])).tolist(),
                max_new=int(outputs[i]), level=str(levels[i]))
        for i in range(n)
    ]


@dataclasses.dataclass
class Served:
    """What the client saw of one request (host clock, seconds)."""

    planned: Planned
    rid: int = -1
    sent: Optional[float] = None          # submitted to the scheduler
    times: List[float] = dataclasses.field(default_factory=list)   # per token
    levels: List[int] = dataclasses.field(default_factory=list)    # rung index per token
    amps: List[Optional[float]] = dataclasses.field(default_factory=list)  # max |logit|
    from_tick: List[bool] = dataclasses.field(default_factory=list)
    tokens: Optional[List[int]] = None    # generated tokens, once finished
    withdrawn: bool = False


class OpenLoop:
    """Drives ``srv.serve`` with requests injected at their due times.

    Hooks are set on the server's own instances (scheduler ``admit``,
    ``has_work``, ``advance``; arbiter ``observe``) and removed after
    the run: the program is not edited.  With ``eos_id`` set the loop
    pulls each tick's tokens to the host before ``advance`` is called,
    so ``advance`` is when the client holds the token.

    ``events`` maps offsets into the window (seconds) to callbacks, and
    ``on_close`` is called as the window closes: the harness snapshots
    counters and starts or stops a trace with them.
    """

    def __init__(self, srv, plan: List[Planned], seconds: float, drain: str,
                 drain_limit_s: float = 90.0, clock=time.perf_counter,
                 events: Optional[Dict[float, object]] = None, on_close=None):
        if drain not in ("all", "active"):
            raise ValueError(f"drain must be 'all' or 'active', got {drain!r}")
        self.srv = srv
        self.plan = plan
        self.seconds = float(seconds)
        self.drain = drain
        self.drain_limit_s = drain_limit_s
        self.clock = clock
        self.events = sorted((events or {}).items())
        self.on_close = on_close
        self.served = [Served(p) for p in plan]
        self._by_rid: Dict[int, Served] = {}
        self._next = 0
        self._tick_snapshot = None
        self.t0 = self.t_close = None
        self.closed = False

    # -- hooks --------------------------------------------------------------

    def _fire_events(self, now):
        while self.events and now >= self.t0 + self.events[0][0]:
            _, fn = self.events.pop(0)
            fn()

    def _inject(self, now):
        from repro.runtime.scheduler import Request

        sched = self.srv.scheduler
        while (self._next < len(self.plan) and not self.closed
               and self.t0 + self.plan[self._next].due <= now):
            s = self.served[self._next]
            p = s.planned
            s.rid = self.srv.next_rid()
            self._by_rid[s.rid] = s
            sched.submit(Request(rid=s.rid, prompt=p.prompt, max_new=p.max_new,
                                 level=p.level))
            s.sent = self.clock()
            self._next += 1

    def _admit(self, can_admit=None, limit=None):
        self._tick_snapshot = None
        now = self.clock()
        self._fire_events(now)
        self._inject(now)
        sched = self.srv.scheduler
        if not sched.pending and not sched.active_mask().any() and not self.closed:
            # idle: nothing to serve until the next request is due
            wake = self.t0 + self.seconds
            if self._next < len(self.plan):
                wake = min(wake, self.t0 + self.plan[self._next].due)
            time.sleep(max(0.0, wake - self.clock()))
            now = self.clock()
            self._fire_events(now)
            self._inject(now)
        return self._orig["admit"](can_admit, limit)

    def _close(self):
        self.closed = True
        self.t_close = self.clock()
        for _, fn in self.events:   # whatever was due by the close
            fn()
        self.events = []
        if self.on_close is not None:
            self.on_close()
        if self.drain == "active":
            sched = self.srv.scheduler
            while sched.pending:
                req = sched.pending.popleft()
                self._by_rid[req.rid].withdrawn = True
                sched._submitted.discard(req.rid)
            sched._m_queue.set(0)

    def _has_work(self):
        now = self.clock()
        if not self.closed:
            self._fire_events(now)
            if now >= self.t0 + self.seconds:
                self._close()
            else:
                return True
        if now >= self.t_close + self.drain_limit_s:
            return False
        return self._orig["has_work"]()

    def _observe(self, step, nonfinite, amplitude, active=None, acceptance=None):
        self._tick_snapshot = (self.srv.arbiter.idx.copy(), np.asarray(amplitude, np.float64))
        return self._orig["observe"](step, nonfinite, amplitude, active, acceptance)

    def _advance(self, slot, eos=False):
        t = self.clock()
        sched = self.srv.scheduler
        s = self._by_rid[sched.slots[slot].request.rid]
        if self._tick_snapshot is not None:
            idx, amp = self._tick_snapshot
            s.levels.append(int(idx[slot]))
            s.amps.append(float(amp[slot]))
            s.from_tick.append(True)
        else:  # the first token, sampled from the admission prefill
            s.levels.append(int(self.srv.arbiter.idx[slot]))
            s.amps.append(None)
            s.from_tick.append(False)
        s.times.append(t)
        return self._orig["advance"](slot, eos)

    # -- run ----------------------------------------------------------------

    def run(self):
        srv, sched = self.srv, self.srv.scheduler
        self._orig = {"admit": sched.admit, "has_work": sched.has_work,
                      "advance": sched.advance, "observe": srv.arbiter.observe}
        sched.admit, sched.has_work = self._admit, self._has_work
        sched.advance, srv.arbiter.observe = self._advance, self._observe
        try:
            self.t0 = self.clock()
            self._fire_events(self.t0)
            srv.serve([])
        finally:
            sched.admit, sched.has_work = self._orig["admit"], self._orig["has_work"]
            sched.advance, srv.arbiter.observe = self._orig["advance"], self._orig["observe"]
        for rid, s in self._by_rid.items():
            fin = sched.finished.get(rid)
            if fin is not None:
                s.tokens = fin.tokens[len(s.planned.prompt):]
        return self.served
