"""The program's spans as the benchmark reads them: ``decode_host_ms``
from the engine's span totals, and the device idle split by the spans
(``bench/program_spans.py``) on the recorded chip windows."""
import gzip
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import program_spans, run, spec, trace
from bench.tests import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _totals(decode_n, decode_s, wait_s):
    return {"spans": {"serve.decode": {"n": decode_n, "s": decode_s,
                                       "self_s": 0.0},
                      "serve.device_wait": {"n": decode_n, "s": wait_s,
                                            "self_s": wait_s}}}


def test_decode_host_ms_is_host_time_per_decode_call_in_the_window():
    rec = {"stats": {"w0": _totals(10, 1.0, 0.6),
                     "w1": _totals(30, 3.0, 1.8)}}
    # 20 calls: 2.0 s in serve.decode, 1.2 s of it waiting on the device
    got = spec.metric_fn("decode_host_ms.chat")(rec)
    assert got == pytest.approx(1e3 * 0.8 / 20)
    # a window with no decode call, and a program that keeps no spans
    rec["stats"]["w1"] = rec["stats"]["w0"]
    assert spec.metric_fn("decode_host_ms.chat")(rec) is None
    bare = {"stats": {"w0": {"decode_steps": 1}, "w1": {"decode_steps": 9}}}
    assert spec.metric_fn("decode_host_ms.chat")(bare) is None


def test_overlap_and_innermost_by_hand():
    assert program_spans.overlap([(0, 10), (20, 30)],
                                 [(5, 25), (28, 40)]) == 5 + 5 + 2
    assert program_spans.overlap([(0, 1)], []) == 0.0
    spans = [(0, 100, "step"), (10, 50, "tick"), (12, 20, "decode"),
             (14, 16, "sample"), (60, 70, "admit")]
    got = program_spans.innermost(spans, [5, 13, 15, 18, 30, 55, 65, 101])
    assert got == ["step", "decode", "sample", "decode", "tick", "step",
                   "admit", ""]


def _load(name):
    from jax.profiler import ProfileData
    spans = json.loads((DATA / f"{name}.xplane.pb.spans.json").read_text())
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        (DATA / f"{name}.xplane.pb.gz").read_bytes()))
    return pd, spans


def test_a_trace_without_program_spans_splits_as_idle_gaps():
    pd, spans = _load("qwen3_chat")
    split = program_spans.reduce(pd, spans)
    assert split["span_idle"] == {} and split["span_n"] == {}
    assert program_spans.decode_idle_ms(split) is None
    want = dict(trace.summarize(pd, spans)["idle_gaps"])
    got = dict(split["idle_by_span"])
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k])


def test_the_line_of_a_traced_run_holds_decode_host_ms(tmp_path):
    root = tiny.checkout(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "decode_host_ms.chat", "unit": "ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    keep = str(tmp_path / "window.xplane.pb")
    line = run.execute("tiny_dense.tinychat", 2**31 + 5, 1.0, True,
                       jax.devices(), tiny.CPU_PEAKS, root=root,
                       keep_trace=keep)
    assert line["correct"]
    assert line["metrics"]["decode_host_ms.chat"]["value"] > 0
    # the trace holds the decode calls the window's span totals count
    from jax.profiler import ProfileData
    spans = json.loads(pathlib.Path(keep + ".spans.json").read_text())
    pd = ProfileData.from_file(keep)
    split = program_spans.reduce(pd, spans)
    event = {s["idx"]: s["event"] for s in spans}
    traced = [event[dict(e.stats)["idx"]] for p in pd.planes
              for ln in p.lines for e in ln.events if e.name == "bench_step"]
    # one engine step per harness step, one tick per decode step
    assert split["span_n"]["serve.step"] == len(traced) > 0
    assert split["span_n"]["serve.tick"] == traced.count("decode") > 0
    assert split["span_n"]["serve.admit"] == traced.count("prefill")
    assert split["span_n"]["serve.decode"] >= traced.count("decode")


# -- a recorded chip window with program spans -------------------------------
# ``data/qwen3_chat_spans.xplane.pb.gz`` is the traced window of a
# ``--trace 1 --keep-trace`` run of ``qwen3_1_7b.chat`` on a TPU v5 lite,
# from a program that opens ``serve.`` spans; the expected numbers are
# counted a second way, on a 1-us grid.

RES = 1000  # ns


@pytest.fixture(scope="module")
def recorded():
    pd, spans = _load("qwen3_chat_spans")
    steps = [(e.start_ns, e.start_ns + e.duration_ns) for p in pd.planes
             if not p.name.startswith("/device") for ln in p.lines
             for e in ln.events if e.name == "bench_step"]
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = [(e.start_ns, e.start_ns + e.duration_ns) for ln in dev.lines
           if ln.name == "XLA Ops" for e in ln.events]
    prog = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in pd.planes if not p.name.startswith("/device")
            for ln in p.lines for e in ln.events
            if e.name.startswith("serve.") and lo <= e.start_ns < hi]
    # a cell an operation touches is busy, so that operations shorter
    # than a cell still split the idle time around them
    idle = np.ones(int((hi - lo) // RES) + 1, bool)
    for a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            idle[int((a - lo) // RES):-int(-(b - lo) // RES)] = False
    return (program_spans.reduce(pd, spans), trace.summarize(pd, spans),
            idle, prog, lo)


def _cells(a, b, lo):
    return slice(int((a - lo) // RES), int((b - lo) // RES))


def test_recorded_window_holds_the_engine_spans(recorded):
    split, _, _, prog, _ = recorded
    assert split["span_n"]["serve.decode"] == sum(
        1 for *_, n in prog if n == "serve.decode") > 0
    assert split["span_n"]["serve.device_wait"] == \
        split["span_n"]["serve.decode"]
    ms = program_spans.decode_idle_ms(split)
    assert 0 < ms < 1e3 * split["span_idle"]["serve.tick"]


def test_span_idle_against_a_grid_count(recorded):
    split, _, idle, prog, lo = recorded
    for name in ("serve.step", "serve.tick", "serve.decode",
                 "serve.sample", "serve.device_wait"):
        mask = np.zeros_like(idle)
        for a, b, n in prog:
            if n == name:
                mask[_cells(a, b, lo)] = True
        want = (idle & mask).sum() * RES / 1e9
        assert split["span_idle"][name] == pytest.approx(
            want, rel=0.02, abs=2e-4), name
    # a span's idle time holds its children's
    idle = split["span_idle"]
    assert idle["serve.step"] >= idle["serve.tick"] >= idle["serve.decode"] \
        >= idle["serve.sample"] + idle["serve.device_wait"]


def test_idle_by_span_against_a_grid_count(recorded):
    split, summary, idle, prog, lo = recorded
    got = dict(split["idle_by_span"])
    # the same idle time as idle_gaps, split further
    assert sum(got.values()) == pytest.approx(
        sum(v for _, v in summary["idle_gaps"]), rel=1e-9)
    for label, v in summary["idle_gaps"]:
        assert sum(x for _, x in split["idle_by_step"][label]) == \
            pytest.approx(v, rel=1e-9)
    # innermost span by painting: a nested span starts later, paints last
    names = sorted({n for *_, n in prog})
    owner = np.full(idle.shape, -1)
    for a, b, n in sorted(prog, key=lambda s: (s[0], -s[1])):
        owner[_cells(a, b, lo)] = names.index(n)
    edges = np.flatnonzero(np.diff(np.r_[0, idle.astype(np.int8), 0]))
    want = dict.fromkeys(names, 0.0)
    for a, b in zip(edges[::2], edges[1::2]):
        o = owner[(a + b) // 2]
        if o >= 0:
            want[names[o]] += (b - a) * RES / 1e9
    for n in ("serve.tick", "serve.decode", "serve.sample",
              "serve.read_tokens", "serve.dispatch"):
        assert got.get(n, 0.0) == pytest.approx(want[n], rel=0.05,
                                                abs=5e-4), n
