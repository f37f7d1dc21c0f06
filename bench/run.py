"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
their files under ``bench/`` say everything else. The run builds the
weights on the device from the seed, serves a warm-up that touches every
program shape the mix can make, then drives ``ServeEngine.submit`` and
``ServeEngine.step`` with the mix for a lead-in and ``--seconds`` of
measured window. With ``--trace 1`` the window runs under the profiler
and the run reports the per-layer metrics instead of the end-to-end ones.
After the window the engine is freed and the plain reference checks a
sample of the served requests. The last line of standard output is the
result; the last lines on standard error say what was compared.

Exits with 2, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, loop, spec, traffic, warmup, weights  # noqa: E402


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Programs met for the first time, from ``jax.monitoring``: each is
    lowered, then either compiled or loaded from the persistent cache
    (JAX reports a backend compile for both)."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.counts = {self.LOWER: 0, self.BACKEND: 0, self.HIT: 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(
            lambda name, **_kw: self._on(name, 0.0))

    def _on(self, name, _secs, **_kw):
        if name in self.counts:
            self.counts[name] += 1

    def snapshot(self):
        return {"lowered": self.counts[self.LOWER],
                "cache_hits": self.counts[self.HIT],
                "compiled": self.counts[self.BACKEND] - self.counts[self.HIT]}


def model_config(cfg_file):
    """The program's ModelConfig for a configuration file, checked against
    the sizes the file states."""
    from repro.configs import get_config
    # JSON has no tuples: a list (such as a block pattern) becomes one
    cfg = get_config(cfg_file["repro_config"]).with_overrides(
        **{k: tuple(v) if isinstance(v, list) else v
           for k, v in cfg_file.get("overrides", {}).items()})
    for key, want in cfg_file["model"].items():
        if getattr(cfg, key) != want:
            raise spec.SpecError(f"{cfg_file['repro_config']}: {key} is "
                                 f"{getattr(cfg, key)} in the program, "
                                 f"{want} in the configuration file")
    return cfg


def max_seq(mix, page: int) -> int:
    """Room for the mix's longest prompt plus its longest output."""
    return -(-traffic.longest_context(mix) // page) * page


def make_request(i, prompt, n):
    from repro.serve.engine import Request
    return Request(rid=i, prompt=prompt, max_new_tokens=n)


def build(cfg_file, ref, mix, seed: int):
    """The served engine for one configuration and mix, warmed up: weights
    from the seed, in the shapes and the tree that ``ref`` (the
    configuration's reference module) gives, and every program shape the
    mix can make run once."""
    import jax
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import SchedulerConfig
    serve = dict(cfg_file["serve"], max_seq=max_seq(
        mix, cfg_file["serve"]["page_size"]))
    m = cfg_file["model"]
    w = weights.make(ref.shapes(m), m["dtype"], seed)
    jax.block_until_ready(w)
    engine = ServeEngine(model_config(cfg_file), ref.to_program(w, m),
                         max_batch=serve["max_batch"],
                         max_seq=serve["max_seq"],
                         seed=weights.jax_seed(seed, "engine"),
                         scheduler=SchedulerConfig(
                             page_size=serve["page_size"]))
    del w
    cohorts = warmup.plan(mix, serve["max_batch"], serve["page_size"])
    steps = warmup.run(engine, lambda p, n: make_request(-1, p, n),
                       cohorts, m["vocab_size"], seed)
    return engine, serve, len(cohorts), steps


def execute(workload: str, seed: int, seconds: float, trace: bool,
            devices, peaks, *, root: pathlib.Path = ROOT,
            keep_trace: str = None, t_process: float = T_PROCESS,
            control: bool = False):
    """Everything after the device check; returns the result line.
    ``control`` adds the control's readings under ``control`` and the
    program's, compared or not, under ``readings`` (for
    ``bench/calibrate.py``; the benchmark's own runs never read them)."""
    import jax

    bench_dir = root / "bench"
    bench = spec.benchmark(root)
    cell = spec.workload(workload, root)
    cfg_file = spec.config(cell["config"], bench_dir)
    mix = spec.traffic(cell["traffic"], bench_dir)
    m = cfg_file["model"]
    ref = spec.reference_module(cfg_file["reference"], bench_dir)
    compiles = CompileCounter()
    engine, serve, n_cohorts, warm_steps = build(cfg_file, ref, mix, seed)
    warm = compiles.snapshot()
    log(f"set-up: {n_cohorts} warm-up cohorts in {warm_steps} steps, "
        f"{warm['lowered']} programs lowered, {warm['compiled']} compiled, "
        f"{warm['cache_hits']} loaded from the cache, "
        f"{time.perf_counter() - t_process:.3f} s to the lead-in")

    span = loop._no_span
    if trace:
        def span(idx):
            return jax.profiler.TraceAnnotation("bench_step", idx=idx)
    driver = loop.Driver(
        engine, lambda i, s: make_request(i, s.prompt, s.max_new), span=span)
    marks = {"trace_dir": None}

    def on_mark(name):
        marks[name] = compiles.snapshot()
        if trace and name == "w0":
            marks["trace_dir"] = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            # device operations and the harness's spans only: the Python
            # tracer would record every interpreted call
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(marks["trace_dir"],
                                     profiler_options=opts)
        elif trace and name == "w1":
            jax.profiler.stop_trace()

    driver.on_mark = on_mark
    stream = traffic.requests(mix, seed, m["vocab_size"])
    if mix["loop"] == "open":
        win = driver.run_open(stream, mix["lead_in_s"], seconds)
    else:
        win = driver.run_closed(stream, mix["clients"], mix["lead_in_s"],
                                seconds)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0
               for d in devices[:cell["chips"]])
    lowered = marks["w1"]["lowered"] - marks["w0"]["lowered"]
    compiled = marks["w1"]["compiled"] - marks["w0"]["compiled"]
    log(f"window: {lowered} programs lowered, {compiled} compiled inside it")
    due = [t for t in driver.tracked if win["w0"] <= t.due < win["w1"]]
    late = sorted(t.submitted - t.due for t in due)
    if late:
        log(f"generator lateness over {len(late)} requests: median "
            f"{late[len(late) // 2]:.6f} s, max {late[-1]:.6f} s")

    rec = {
        "window": win,
        "requests": [{"due": t.due, "submitted": t.submitted,
                      "plen": t.spec.plen, "max_new": t.spec.max_new,
                      "tokens": t.tokens} for t in driver.tracked],
        "spans": driver.spans,
        "stats": driver.snapshots,
        "setup_s": win["w0"] - t_process,
        "trace": None,
        "model": m,
        "reference": ref,
        "peaks": peaks,
    }
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        from bench import trace as trace_mod
        xplane = trace_mod.find_xplane(marks["trace_dir"])
        if keep_trace:
            pathlib.Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, keep_trace)
            with open(keep_trace + ".spans.json", "w") as f:
                json.dump(driver.spans, f)
        rec["trace"] = trace_mod.summarize(xplane, driver.spans,
                                           cell["chips"])
        shutil.rmtree(marks["trace_dir"], ignore_errors=True)
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        breakdown = {"device_ops": rec["trace"]["device_ops"],
                     "idle_gaps": rec["trace"]["idle_gaps"]}

    metrics = {}
    for mdef in spec.metrics_for(bench, workload, trace):
        value = spec.metric_fn(mdef["name"], bench_dir)(rec)
        if value is not None:
            metrics[mdef["name"]] = {"value": value, "unit": mdef["unit"]}

    # the engine goes first: the reference's memory never shows in the
    # peak read above, and the whole chip is the reference's
    finished = [t for t in driver.tracked if t.done_at is not None]
    del driver, engine, stream
    gc.collect()
    t_check = time.perf_counter()
    result = check.run(cfg_file, ref, serve["max_seq"], finished, seed,
                       control=control)
    log(f"check: {result['sample_requests']} requests, "
        f"{result['sample_tokens']} served tokens, against the reference "
        f"in {time.perf_counter() - t_check:.3f} s")
    if control:
        log(f"check: program readings {result['readings']}")
        log(f"check: control readings {result['control']}")
    for name, (value, limit) in result["compared"].items():
        log(f"check: {name} {value!r} limit {limit!r}")

    line = {"correct": result["correct"], "attempted": len(due),
            "failed": 0, "metrics": metrics, "device": device,
            "window_programs": {"lowered": lowered, "compiled": compiled}}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if control:
        line["readings"] = result["readings"]
        line["control"] = result["control"]
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in result["compared"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the traced window's .xplane.pb here")
    args = ap.parse_args(argv)
    cell = spec.workload(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"the cell needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    log(f"device: {devices[0].platform}, {devices[0].device_kind}, "
        f"{len(devices)} found, {cell['chips']} used")
    peaks = spec.peaks(devices[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # small programs (sampling, reads) persist too, so a warm set-up
    # compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices, peaks, keep_trace=args.keep_trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
