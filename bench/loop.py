"""Drive a serving engine with a traffic stream and time every token.

The engine is anything with ``submit(request)``, ``step() -> {"event":
...}`` and ``has_work`` (``repro.serve.engine.ServeEngine``). Each
``step()`` runs inside a span named ``bench_step`` whose ``idx`` pairs it
with the event it returned; every token a step produced is stamped with
the host clock when that step returns. An open loop submits each request
when it is due; a closed loop gives each client its next request as soon
as its previous one completes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

from bench.traffic import RequestSpec


@dataclasses.dataclass
class Tracked:
    """One submitted request as the harness saw it."""

    spec: RequestSpec
    req: Any                       # the engine's request object
    due: float                     # host clock: when it was due
    submitted: float               # host clock: when it was handed over
    tokens: List[float] = dataclasses.field(default_factory=list)
    done_at: Optional[float] = None


def _no_span(idx: int):
    return contextlib.nullcontext()


class Driver:
    """One run's traffic, spans and token stamps."""

    def __init__(self, engine, make_request: Callable[[int, RequestSpec], Any],
                 span: Callable[[int], Any] = _no_span):
        self.engine = engine
        self.make_request = make_request
        self.clock = time.perf_counter
        self.span = span
        self.tracked: List[Tracked] = []
        self.live: List[Tracked] = []
        self.spans: List[Dict[str, Any]] = []
        self.snapshots: Dict[str, Dict[str, Any]] = {}
        #: called with "w0" / "w1" as the window opens and closes
        self.on_mark: Callable[[str], None] = lambda name: None

    def submit(self, spec: RequestSpec, due: float) -> Tracked:
        req = self.make_request(len(self.tracked), spec)
        t = Tracked(spec, req, due, self.clock())
        self.engine.submit(req)
        self.tracked.append(t)
        self.live.append(t)
        return t

    def step(self) -> List[Tracked]:
        """One engine step; returns the requests it completed."""
        idx = len(self.spans)
        t0 = self.clock()
        with self.span(idx):
            ev = self.engine.step()
        t1 = self.clock()
        self.spans.append({"idx": idx, "t0": t0, "t1": t1,
                           "event": ev["event"]})
        finished = []
        for t in self.live:
            n = len(t.req.output)
            if n > len(t.tokens):
                t.tokens.extend([t1] * (n - len(t.tokens)))
            if t.req.done:
                t.done_at = t1
                finished.append(t)
        if finished:
            self.live = [t for t in self.live if t.done_at is None]
        return finished

    def _snapshot(self, name: str) -> None:
        stats = getattr(self.engine, "stats", None)
        self.snapshots[name] = dict(stats()) if stats else {}
        self.on_mark(name)

    def run_open(self, stream: Iterator[RequestSpec], lead_in_s: float,
                 seconds: float) -> Dict[str, float]:
        """Submit each request at its due time; step while there is work.
        Returns the window's bounds on the host clock."""
        start = self.clock()
        w0, w1 = start + lead_in_s, start + lead_in_s + seconds
        nxt = next(stream)
        in_window = False
        while True:
            now = self.clock()
            if not in_window and now >= w0:
                in_window = True
                self._snapshot("w0")
            if now >= w1:
                break
            while start + nxt.due_s <= now:
                self.submit(nxt, start + nxt.due_s)
                nxt = next(stream)
            if self.engine.has_work:
                self.step()
            else:
                time.sleep(max(0.0, min(start + nxt.due_s, w1) - now))
        self._snapshot("w1")
        return {"start": start, "w0": w0, "w1": w1}

    def run_closed(self, stream: Iterator[RequestSpec], clients: int,
                   lead_in_s: float, seconds: float) -> Dict[str, float]:
        """``clients`` requests in flight at all times: a completed one is
        replaced at once. Returns the window's bounds on the host clock."""
        start = self.clock()
        w0, w1 = start + lead_in_s, start + lead_in_s + seconds
        for _ in range(clients):
            self.submit(next(stream), start)
        in_window = False
        while True:
            now = self.clock()
            if not in_window and now >= w0:
                in_window = True
                self._snapshot("w0")
            if now >= w1:
                break
            for t in self.step():
                self.submit(next(stream), t.done_at)
        self._snapshot("w1")
        return {"start": start, "w0": w0, "w1": w1}
