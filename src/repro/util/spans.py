"""Host spans on the profiler's clock, with totals kept in memory.

``Spans.span(name, **attrs)`` is a context manager. It enters a
``jax.profiler.TraceAnnotation``, so that while the profiler records, the
span lands on the host plane on the same clock as the device's
operations. Whether or not the profiler runs, it also adds to a total per
name: the count ``n``, the seconds ``s``, and the self seconds ``self_s``
(``s`` less the part of it that child spans cover). A span's children are
the spans opened while it is open, so one recorder serves one thread.

Attributes go to the trace only; keep them to a few integers.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax


class Span:
    """One span; after it closes, ``t0`` and ``t1`` hold its bounds on
    ``time.perf_counter``."""

    __slots__ = ("_spans", "_name", "_ann", "_child_s", "t0", "t1")

    def __init__(self, spans: "Spans", name: str, ann):
        self._spans = spans
        self._name = name
        self._ann = ann
        self._child_s = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._spans._open.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        spans = self._spans
        spans._open.pop()
        s = self.t1 - self.t0
        total = spans._totals.get(self._name)
        if total is None:
            total = spans._totals[self._name] = [0, 0.0, 0.0]
        total[0] += 1
        total[1] += s
        total[2] += s - self._child_s
        if spans._open:
            spans._open[-1]._child_s += s
        return False


class Spans:
    """A recorder: opens spans and keeps their totals by name."""

    def __init__(self):
        self._open: List[Span] = []
        self._totals: Dict[str, List[float]] = {}

    def span(self, name: str, **attrs: int) -> Span:
        return Span(self, name, jax.profiler.TraceAnnotation(name, **attrs))

    def reset(self) -> None:
        """Zero the totals (spans open now still count when they close)."""
        self._totals = {}

    def totals(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"n", "s", "self_s"}}``, a fresh copy."""
        return {name: {"n": int(n), "s": s, "self_s": self_s}
                for name, (n, s, self_s) in self._totals.items()}
