"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload qwen3_1_7b.chat --seeds 12 13 14 \\
        --rates 0.5 0.6 0.7 0.8 0.9 1.0 --seconds 50

The rule, from the limits the mix file states under ``slo``: a request
meets them when it finishes with its first token at most ``ttft_s`` after
it was due and a mean gap between its tokens (time per output token) of
at most ``tpot_s``. A rate is sustained when, pooled over every seed, at
least ``share`` of the requests due in the windows meet both; a backlog
that grows fails the time to first token of every later request. The
sustained rate is the highest swept rate that is sustained, with every
lower one sustained too. The cell runs at ``load`` times it, and the
traffic file states that rate as a number; the benchmark never sweeps.

One process sets the cell up once, then for each rate and seed offers the
rate for a lead-in and ``--seconds`` of window, and drains the engine
(at most ``DRAIN_S``) so that every request due in the window has its
readings. Each seed sets the order of lengths and gaps (the mix's
``schedule_seed``) and the prompt tokens, so the seeds differ in arrivals.
One line per rate and seed, one per rate pooled, and the decision last.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import loop, records, run, spec, traffic  # noqa: E402

#: longest drain after a window, seconds
DRAIN_S = 60.0


def readings(tracked, w0: float, w1: float):
    """(ttft, tpot) of each request due in [w0, w1); None where it did not
    finish."""
    out = []
    for t in tracked:
        if not w0 <= t.due < w1:
            continue
        if t.done_at is None or not t.tokens:
            out.append(None)
            continue
        n = len(t.tokens)
        tpot = (t.tokens[-1] - t.tokens[0]) / (n - 1) if n > 1 else 0.0
        out.append((t.tokens[0] - t.due, tpot))
    return out


def met(rs, slo) -> int:
    return sum(1 for r in rs if r is not None and r[0] <= slo["ttft_s"]
               and r[1] <= slo["tpot_s"])


def summary(rs, slo):
    done = [r for r in rs if r is not None]
    return {"due": len(rs), "finished": len(done),
            "met": met(rs, slo),
            "met_share": met(rs, slo) / len(rs) if rs else None,
            "ttft_p50_s": records.percentile([r[0] for r in done], 50),
            "ttft_p90_s": records.percentile([r[0] for r in done], 90),
            "tpot_p50_s": records.percentile([r[1] for r in done], 50),
            "tpot_p90_s": records.percentile([r[1] for r in done], 90)}


def sustained(pooled, slo):
    """The highest rate whose pooled share meets ``slo``, every lower rate
    meeting it too; None where the lowest does not."""
    best = None
    for rate in sorted(pooled):
        share = pooled[rate]["met_share"]
        if share is None or share < slo["share"]:
            break
        best = rate
    return best


def sweep(engine, cfg_file, mix, seeds, rates, seconds, out=print):
    """Offer each rate on each seed to a set-up ``engine``; returns the
    pooled readings by rate and the decision."""
    slo = mix["slo"]
    pooled = {}
    for rate in sorted(rates):
        every = []
        for seed in seeds:
            d = loop.Driver(engine, lambda k, s: run.make_request(
                k, s.prompt, s.max_new))
            stream = traffic.requests(
                dict(mix, rate_per_s=rate, schedule_seed=seed), seed,
                cfg_file["model"]["vocab_size"])
            win = d.run_open(stream, mix["lead_in_s"], seconds)
            backlog = len(d.live)
            stop = time.perf_counter() + DRAIN_S
            while engine.has_work and time.perf_counter() < stop:
                d.step()
            rs = readings(d.tracked, win["w0"], win["w1"])
            every.extend(rs)
            out(json.dumps({"rate_per_s": rate, "seed": seed,
                            "in_flight_at_end": backlog,
                            **summary(rs, slo)}))
            while engine.has_work:
                engine.step()
        pooled[rate] = summary(every, slo)
        out(json.dumps({"rate_per_s": rate, "seeds": list(seeds),
                        **pooled[rate]}))
    best = sustained(pooled, slo)
    decision = {"slo": slo, "sustained_rate_per_s": best,
                "cell_rate_per_s": (None if best is None
                                    else round(mix["load"] * best, 4))}
    out(json.dumps(decision))
    return pooled, decision


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("the sweep measures the chip; JAX found no TPU")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.workload(args.workload)
    cfg_file = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    ref = spec.reference_module(cfg_file["reference"])
    engine, _, _, _ = run.build(cfg_file, ref, mix, args.seeds[0])
    sweep(engine, cfg_file, mix, args.seeds, args.rates, args.seconds,
          out=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
