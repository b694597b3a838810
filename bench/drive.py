"""Drives the engine through its public surface and keeps the books.

After every ``tick()`` the load generator stamps each request's new entries of
``handle.request.out_tokens`` with the host clock, and records the tick's
counter deltas (``packed_tokens``, ``admissions``,
``prefix_tokens_reused``).  Spans of its own (``bench.submit``,
``bench.tick``, ``bench.stamp``) go into the profiler's trace when one is
being taken.  Nothing here reads the engine's private state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax

from traffic import Req, Sessions

COUNTERS = ("packed_tokens", "ragged_ticks", "admissions",
            "prefix_tokens_reused")


@dataclasses.dataclass
class Tracked:
    req: Req
    request: object  # the engine's record (``handle.request``); holding
    # the handle would keep the engine, and its pools, alive past the window
    due: float  # host clock at which it was due
    submitted: float  # host clock at submit
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.request.done

    @property
    def failed(self) -> bool:
        return self.request.error is not None


@dataclasses.dataclass
class TickRec:
    start: float
    end: float
    delta: dict  # counter deltas over the tick
    emitted: List[tuple]  # (index into LoadGen.tracked, tokens before, tokens after)


class LoadGen:
    def __init__(self, engine, clock: Callable[[], float] = time.perf_counter):
        self.eng = engine
        self.clock = clock
        self.tracked: List[Tracked] = []
        self.refused: List[Req] = []
        self.ticks: List[TickRec] = []
        self._live: List[int] = []
        self._last = self._counters()

    @property
    def in_flight(self) -> int:
        return len(self._live)

    def _counters(self) -> dict:
        st = self.eng.stats
        return {k: st[k] for k in COUNTERS}

    def submit(self, req: Req, due: float) -> Optional[Tracked]:
        with jax.profiler.TraceAnnotation("bench.submit"):
            try:
                h = self.eng.submit(req.prompt, max_tokens=req.max_tokens)
            except (ValueError, RuntimeError):  # serve.errors subclass these
                self.refused.append(req)
                return None
            t = Tracked(req, h.request, due, self.clock())
            self.tracked.append(t)
            self._live.append(len(self.tracked) - 1)
            return t

    def tick(self) -> TickRec:
        start = self.clock()
        with jax.profiler.TraceAnnotation("bench.tick"):
            self.eng.tick()
        with jax.profiler.TraceAnnotation("bench.stamp"):
            now = self.clock()
            emitted, live = [], []
            for i in self._live:
                t = self.tracked[i]
                n, had = len(t.request.out_tokens), len(t.times)
                if n > had:
                    t.times.extend([now] * (n - had))
                    emitted.append((i, had, n))
                if not t.done:
                    live.append(i)
            self._live = live
            cur = self._counters()
            delta = {k: cur[k] - self._last[k] for k in COUNTERS}
            self._last = cur
        rec = TickRec(start, now, delta, emitted)
        self.ticks.append(rec)
        return rec

    def completed_since(self, rec: TickRec) -> List[Tracked]:
        return [self.tracked[i] for i, _, _ in rec.emitted
                if self.tracked[i].done]


def run_sessions(drv: LoadGen, feed: Sessions, origin: float, until: float,
                 between: Optional[Callable[[], None]] = None) -> None:
    """Chat sessions: submit each turn when it falls due (``origin`` + its
    schedule time), tell the feed of every answer completed, tick while
    anything is live, sleep while nothing is, until the host clock reaches
    ``until``.  ``between`` runs between ticks."""
    while True:
        now = drv.clock()
        if now >= until:
            return
        for req in feed.pop_due(now - origin):
            drv.submit(req, origin + req.due)
        if drv.eng.idle:
            nxt = feed.next_due()
            nxt = until if nxt is None else origin + nxt
            time.sleep(max(0.0, min(nxt, until) - now))
            continue
        rec = drv.tick()
        for t in drv.completed_since(rec):
            if t.req.idx >= 0 and not t.failed:
                feed.finished(t.req, t.request.out_tokens, rec.end - origin)
        if between is not None:
            between()


def run_closed(drv: LoadGen, reqs: List[Req], until: float, next_i: int,
               between: Optional[Callable[[], None]] = None) -> int:
    """Closed loop: each completed request is followed at once by the next
    request of the list (one per client), until the host clock reaches
    ``until``.  Returns the index of the next request not yet sent."""
    while drv.clock() < until:
        rec = drv.tick()
        for _ in drv.completed_since(rec):
            if next_i < len(reqs):
                drv.submit(reqs[next_i], rec.end)
                next_i += 1
        if between is not None:
            between()
    return next_i
