"""Readings that set a cell's limit, on the chip: the program's and the
control's, seed by seed, in one process.

    python3 bench/calibrate.py --workload qwen3_1_7b.chat --seconds 10 \\
        --seeds 11 12 13 --out chiprun_out/calib.jsonl

Each seed is a whole run of the cell (weights, warm-up, lead-in, a window
at the cell's own load) followed by the check, with the control read at
the same positions: the reference computed in float8 e4m3, the precision
below the bfloat16 the configurations serve in. Every reading
``bench/check.py`` takes is printed for both, compared or not; a limit
in the configuration file goes above the largest program reading of its
number and below the smallest control reading. The control goes through
the same comparison as the program, and has to come out as not correct:
the command exits 1 where it passes on any seed, or where the program
fails. The benchmark's own runs never read the control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        run.log("calibration reads the chip; JAX found no TPU")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = spec.peaks(devices[0].device_kind)
    sound = True
    for seed in args.seeds:
        line = run.execute(args.workload, seed, args.seconds, False, devices,
                           peaks, t_process=time.perf_counter(),
                           control=True)
        out = {"workload": args.workload, "seed": seed,
               "program": line["readings"],
               "control": {k: v for k, v in line["control"].items()
                           if k != "correct"},
               "limits": {k: c["limit"] for k, c in line["compared"].items()},
               "correct": line["correct"],
               "control_correct": line["control"]["correct"],
               "metrics": line["metrics"],
               "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
        print(json.dumps(out), flush=True)
        if args.out:
            pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(out) + "\n")
        sound &= out["correct"] and not out["control_correct"]
    if not sound:
        run.log("calibration: the program failed or the control passed "
                "the comparison on some seed")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
