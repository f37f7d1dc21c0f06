"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Layout of a TPU trace, as read from one by hand: each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per
program execution (``jit_<name>(<hash>)``) and whose line ``XLA Ops``
holds one event per HLO operation (its name is the HLO instruction's
text). The harness's ``bench_step`` spans sit on a host line, each with
an ``idx`` stat that pairs it with the event its ``step()`` returned. All
events share one clock.

* busy: the union of the ``XLA Ops`` intervals inside the traced window
  (first span start to last span end), averaged over the chips read;
* modules: device seconds per program, by name without its hash;
* kernels: device seconds and calls of ``tpu_custom_call`` operations,
  keyed ``<module>:tpu_custom_call`` by the program that ran them;
* device_ops: the ten operations that took the most device time (a
  loop's own event is left out: its body's operations are listed);
* idle_gaps: device idle time inside the window, by the harness span open
  at the time (``prefill``, ``decode``, or ``between_steps``).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Any, Dict, List, Sequence, Tuple

SPAN = "bench_step"
#: operations whose interval holds other operations' (a loop's body)
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def module_base(name: str) -> str:
    """``jit_decode_step_paged(1234)`` -> ``decode_step_paged``."""
    base = name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def op_label(name: str) -> str:
    """``%copy.72 = bf16[...] copy(...)`` -> ``copy.72 copy``."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:60]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def summarize(source, spans: Sequence[Dict[str, Any]], chips: int = 1
              ) -> Dict[str, Any]:
    """Summary of a trace: ``source`` is the ``.xplane.pb`` path or its
    ``jax.profiler.ProfileData``; ``spans`` are the harness's step records
    (``idx``, ``t0``, ``t1``, ``event``) on its own host clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(source) if isinstance(source, str) else source
    by_idx = {s["idx"]: s for s in spans}
    host = []                       # (start_ns, end_ns, event, idx)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != SPAN:
                    continue
                idx = dict(e.stats).get("idx")
                if idx in by_idx:
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 by_idx[idx]["event"], idx))
    if not host:
        raise ValueError(f"no {SPAN} spans in the trace")
    host.sort()
    lo, hi = host[0][0], max(h[1] for h in host)
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:chips]

    busy_ns = 0.0
    modules: Dict[str, float] = collections.Counter()
    kernels: Dict[str, Dict[str, float]] = {}
    ops: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    starts = [h[0] for h in host]
    for plane in devices:
        mods = sorted((a, b, module_base(n))
                      for n, a, b in _events(plane, "XLA Modules"))
        mod_starts = [m[0] for m in mods]
        op_iv = []
        for name, a, b in _events(plane, "XLA Ops"):
            if b <= lo or a >= hi:
                continue
            op_iv.append((a, b))
            i = bisect.bisect_right(mod_starts, a) - 1
            mod = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            dur = (min(b, hi) - max(a, lo)) / 1e9
            label = op_label(name)
            if label.rsplit(" ", 1)[-1] not in CONTAINERS:
                ops[f"{mod}/{label}"] += dur
            if 'custom_call_target="tpu_custom_call"' in name:
                k = kernels.setdefault(f"{mod}:tpu_custom_call",
                                       {"s": 0.0, "n": 0})
                k["s"] += dur
                k["n"] += 1
        for a, b, mod in mods:
            if b > lo and a < hi:
                modules[mod] += (min(b, hi) - max(a, lo)) / 1e9
        merged = clip(union(op_iv), lo, hi)
        busy_ns += sum(b - a for a, b in merged)
        prev = lo
        for a, b in merged + [(hi, hi)]:
            if a > prev:
                mid = (prev + a) / 2
                j = bisect.bisect_right(starts, mid) - 1
                what = (host[j][2] if j >= 0 and mid < host[j][1]
                        else "between_steps")
                idle[what] += (a - prev) / 1e9
            prev = max(prev, b)

    n = max(len(devices), 1)
    first, last = by_idx[host[0][3]], by_idx[max(host, key=lambda h: h[1])[3]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "host_window": (first["t0"], last["t1"]),
        "chips": len(devices),
        "modules": {k: v / n for k, v in modules.items()},
        "kernels": {k: {"s": v["s"] / n, "n": v["n"]}
                    for k, v in kernels.items()},
        "device_ops": [[k, v / n] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v / n] for k, v in idle.most_common(10)],
    }
