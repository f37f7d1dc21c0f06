"""Measure a cell's spread for its bounds, on the chip: sets of whole runs.

    python3 bench/sets.py --workload qwen3_1_7b.chat --seconds 51 \\
        --seeds 601 602 603 604 605 606 --sets 2 \\
        --trace-seeds 611 612 613 --out chiprun_out/chat.jsonl

Each run is its own process of ``bench/run.py``, as the benchmark is run,
one after another. ``--sets`` sets of ``--seeds`` (the same seeds in each
set), then one traced run per ``--trace-seeds``. Every run's result line
goes to ``--out``; then, for each end-to-end metric and set, the median
and the spread (first to third quartile over the median, by
``statistics.quantiles``), with and without the run farthest from the
median, and the bound that five times the widest spread gives.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread_trimmed(values):
    """The spread without the value farthest from the median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread([v for i, v in enumerate(values) if i != far])


def one_run(workload: str, seed: int, seconds: float, trace: int):
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)], cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return {"workload": workload, "seed": seed, "trace": trace,
            "rc": p.returncode, "wall_s": time.perf_counter() - t0,
            "line": line, "log": p.stderr.strip().splitlines()[-12:]}


def report(runs, sets: int):
    """Median and spread of each metric in each set."""
    out = {}
    per = len(runs) // sets
    for s in range(sets):
        lines = [r["line"] for r in runs[s * per:(s + 1) * per] if r["line"]]
        names = sorted({k for ln in lines for k in ln["metrics"]})
        for name in names:
            vals = [ln["metrics"][name]["value"] for ln in lines
                    if name in ln["metrics"]]
            if len(vals) >= 3:
                out.setdefault(name, []).append(
                    {"median": statistics.median(vals),
                     "spread": spread(vals),
                     "spread_trimmed": spread_trimmed(vals),
                     "n": len(vals)})
    for name, by_set in out.items():
        widest = max(s["spread"] for s in by_set)
        by_set.append({"widest_spread": widest, "bound_5x": 5 * widest})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    runs = []
    plan = ([(seed, 0) for _ in range(args.sets) for seed in args.seeds]
            + [(seed, 1) for seed in args.trace_seeds])
    for seed, trace in plan:
        r = one_run(args.workload, seed, args.seconds, trace)
        runs.append(r)
        with open(out, "a") as f:
            f.write(json.dumps(r) + "\n")
        ln = r["line"] or {}
        print(json.dumps({"seed": seed, "trace": trace, "rc": r["rc"],
                          "wall_s": round(r["wall_s"], 1),
                          "correct": ln.get("correct"),
                          "metrics": {k: v["value"] for k, v in
                                      ln.get("metrics", {}).items()},
                          "compared": ln.get("compared")}), flush=True)
        if r["line"] is None:
            print("\n".join(r["log"]), flush=True)
    if args.seeds:
        print(json.dumps({"workload": args.workload, "sets": report(
            [r for r in runs if r["trace"] == 0], args.sets)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
