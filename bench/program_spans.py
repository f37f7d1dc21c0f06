"""Device idle split by the program's own host spans.

``ServeEngine`` opens spans named ``serve.<part>`` (``repro.util.spans``)
that land on a host line of the profiler's trace, on the device's clock.
Over the window that ``bench/trace.py`` reads (first to last harness
``bench_step`` span), with the same device idle gaps:

* span_idle: device idle seconds inside each span name's intervals;
* span_n: spans of each name that start inside the window;
* idle_by_span: each idle gap given to the innermost ``serve.`` span open
  at its midpoint, or, where none is open, to the label ``idle_gaps``
  gives it (the harness step's event, or ``between_steps``);
* idle_by_step: the same split within each of those labels.

A trace without such spans (a program that opens none) gives empty
``span_idle`` and ``span_n``, and ``idle_by_span`` equal to the
whole of ``idle_gaps``.

    python3 bench/program_spans.py <window.xplane.pb> <window.xplane.pb.spans.json>

prints the split of a trace kept with ``bench/run.py --keep-trace``.
"""
from __future__ import annotations

import bisect
import collections
import json
import pathlib
import sys
from typing import Any, Dict, List, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

PREFIX = "serve."
Interval = Tuple[float, float]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def innermost(spans: Sequence[Tuple[float, float, str]],
              points: Sequence[float]) -> List[str]:
    """For each of the sorted ``points``, the name of the innermost of the
    properly nested ``spans`` open there, or ``""``."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(order) and order[i][0] <= t:
            while stack and stack[-1][1] <= order[i][0]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else "")
    return out


def reduce(source, spans: Sequence[Dict[str, Any]], chips: int = 1
           ) -> Dict[str, Any]:
    """The split, from the ``.xplane.pb`` path or its
    ``jax.profiler.ProfileData`` and the harness's step records."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(source) if isinstance(source, str) else source
    by_idx = {s["idx"]: s for s in spans}
    steps, prog, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name == trace.SPAN:
                    idx = dict(e.stats).get("idx")
                    if idx in by_idx:
                        steps.append(iv + (by_idx[idx]["event"],))
                elif e.name.startswith(PREFIX):
                    prog.append(iv + (e.name,))
    if not steps:
        raise ValueError(f"no {trace.SPAN} spans in the trace")
    steps.sort()
    lo, hi = steps[0][0], max(s[1] for s in steps)
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:chips]
    prog = [s for s in prog if lo <= s[0] < hi]
    by_name: Dict[str, List[Interval]] = collections.defaultdict(list)
    for a, b, name in prog:
        by_name[name].append((a, b))
    by_name = {k: trace.clip(trace.union(v), lo, hi)
               for k, v in by_name.items()}
    step_starts = [s[0] for s in steps]

    span_idle: Dict[str, float] = collections.Counter()
    idle_by_span: Dict[str, float] = collections.Counter()
    idle_by_step: Dict[str, Dict[str, float]] = collections.defaultdict(
        collections.Counter)
    for plane in devices:
        ops = [(a, b) for _, a, b in trace._events(plane, "XLA Ops")
               if b > lo and a < hi]
        gaps, prev = [], lo
        for a, b in trace.clip(trace.union(ops), lo, hi) + [(hi, hi)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        for name, ivs in by_name.items():
            span_idle[name] += overlap(gaps, ivs)
        mids = [(a + b) / 2 for a, b in gaps]
        for (a, b), mid, inner in zip(gaps, mids, innermost(prog, mids)):
            j = bisect.bisect_right(step_starts, mid) - 1
            label = (steps[j][2] if j >= 0 and mid < steps[j][1]
                     else "between_steps")
            idle_by_span[inner or label] += b - a
            idle_by_step[label][inner or label] += b - a

    n = max(len(devices), 1)
    return {
        "span_idle": {k: v / n / 1e9 for k, v in span_idle.items()},
        "span_n": dict(collections.Counter(s[2] for s in prog)),
        "idle_by_span": [[k, v / n / 1e9]
                         for k, v in idle_by_span.most_common()],
        "idle_by_step": {label: [[k, v / n / 1e9] for k, v in c.most_common()]
                         for label, c in idle_by_step.items()},
    }


def decode_idle_ms(split: Dict[str, Any]):
    """Device idle per decode call, on the device's clock, in ms; None
    where the trace holds no ``serve.decode`` span."""
    n = split["span_n"].get("serve.decode", 0)
    if not n:
        return None
    return 1e3 * split["span_idle"].get("serve.decode", 0.0) / n


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 2:
        print("usage: program_spans.py <window.xplane.pb> <spans.json>",
              file=sys.stderr)
        return 2
    spans = json.loads(pathlib.Path(args[1]).read_text())
    split = reduce(args[0], spans)
    split["decode_idle_ms"] = decode_idle_ms(split)
    print(json.dumps(split))
    return 0


if __name__ == "__main__":
    sys.exit(main())
