"""Host spans: the recorder's totals, the engine's spans against its own
statistics, and the spans on the profiler's host plane."""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.model import init_params
from repro.serve.engine import Request, ServeEngine
from repro.util.spans import Spans

#: every span name the engine opens
NAMES = {"serve.step", "serve.select", "serve.admit", "serve.prefill",
         "serve.kv_blocks", "serve.kv_scatter", "serve.sample",
         "serve.read_tokens", "serve.retire", "serve.tick", "serve.decode",
         "serve.kv_table", "serve.dispatch", "serve.device_wait",
         "serve.chunk"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced_config("qwen3_1_7b").with_overrides(
        n_layers=2, d_model=64, vocab_size=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, n=5, **kw):
    rng = np.random.default_rng(21)
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=32, **kw)
    for i in range(n):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=8 if i % 2 else 12).astype(np.int32),
            max_new_tokens=3 + i))
    return eng


def test_spans_nest_and_self_time_leaves_out_the_children():
    sp = Spans()
    with sp.span("outer", width=2) as outer:
        time.sleep(0.004)
        with sp.span("child") as c1:
            with sp.span("grandchild") as g:
                time.sleep(0.003)
            time.sleep(0.002)
        with sp.span("child") as c2:
            time.sleep(0.002)
    tot = sp.totals()
    assert {k: v["n"] for k, v in tot.items()} == {
        "outer": 1, "child": 2, "grandchild": 1}
    s = lambda x: x.t1 - x.t0  # noqa: E731
    assert tot["outer"]["s"] == pytest.approx(s(outer))
    assert tot["child"]["s"] == pytest.approx(s(c1) + s(c2))
    # a grandchild counts against its parent only, never twice
    assert tot["outer"]["self_s"] == pytest.approx(
        s(outer) - s(c1) - s(c2))
    assert tot["child"]["self_s"] == pytest.approx(s(c1) + s(c2) - s(g))
    assert tot["grandchild"]["self_s"] == pytest.approx(s(g))
    assert tot["outer"]["self_s"] >= 0.004
    assert outer.t0 <= c1.t0 <= g.t0 < g.t1 <= c1.t1 <= c2.t0 < c2.t1 \
        <= outer.t1


def test_a_span_closed_by_an_exception_still_counts():
    sp = Spans()
    with pytest.raises(RuntimeError):
        with sp.span("outer"):
            with sp.span("inner"):
                raise RuntimeError("boom")
    assert sp.totals()["outer"]["n"] == sp.totals()["inner"]["n"] == 1
    with sp.span("after"):
        pass
    # the failed spans left the stack: "after" is nobody's child
    assert sp.totals()["outer"]["self_s"] == pytest.approx(
        sp.totals()["outer"]["s"] - sp.totals()["inner"]["s"])


@pytest.mark.parametrize("kv_layout", ["paged", "contiguous"])
def test_engine_span_totals_agree_with_its_statistics(setup, kv_layout):
    from repro.serve.scheduler import SchedulerConfig
    cfg, params = setup
    eng = _serve(cfg, params, scheduler=SchedulerConfig(kv_layout=kv_layout))
    st = eng.run()
    sp = st["spans"]
    assert set(sp) <= NAMES
    assert sp["serve.step"]["s"] == st["wall_s"] > 0
    assert sp["serve.decode"]["n"] == st["decode_steps"] > 0
    assert sp["serve.device_wait"]["n"] == st["decode_steps"]
    assert sp["serve.tick"]["n"] == st["decode_ticks"]
    assert sp["serve.admit"]["n"] == sp["serve.prefill"]["n"] == \
        st["prefills"]
    # the timed decode step runs from the span's start to the end of its
    # device wait: inside the span, around its first three children
    timed = st["measured_step_s"] * st["decode_steps"]
    assert timed == pytest.approx(sum(eng._step_times))
    assert timed <= sp["serve.decode"]["s"]
    before_wait = sum(sp.get(k, {}).get("s", 0.0) for k in (
        "serve.kv_table", "serve.dispatch", "serve.device_wait"))
    assert timed >= before_wait
    for k, v in sp.items():
        assert 0 <= v["self_s"] <= v["s"] + 1e-9, k
    if kv_layout == "paged":
        assert sp["serve.kv_table"]["n"] == st["decode_steps"]
        assert sp["serve.kv_scatter"]["n"] == st["prefills"]
    else:
        assert "serve.kv_table" not in sp and "serve.kv_scatter" not in sp


def test_reset_stats_zeroes_the_spans_and_snapshots_stay_put(setup):
    cfg, params = setup
    eng = _serve(cfg, params, n=3)
    eng.step()
    first = eng.stats()["spans"]
    n_steps = first["serve.step"]["n"]
    eng.run()
    # a snapshot is a copy: later steps do not move it
    assert first["serve.step"]["n"] == n_steps
    assert eng.stats()["spans"]["serve.step"]["n"] > n_steps
    eng.reset_stats()
    st = eng.stats()
    assert st["spans"] == {} and st["wall_s"] == 0.0


def test_chunked_prefill_opens_chunk_spans(setup):
    from repro.serve.scheduler import SchedulerConfig
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=64,
                      scheduler=SchedulerConfig(prefill_chunk=16))
    eng.submit(Request(rid=0, prompt=np.arange(40, dtype=np.int32) % 100,
                       max_new_tokens=3))
    st = eng.run()
    assert st["spans"]["serve.chunk"]["n"] == st["chunk_steps"] == 3
    assert "serve.prefill" not in st["spans"]


def test_serve_spans_land_inside_the_callers_span_on_one_host_line(
        setup, tmp_path):
    from jax.profiler import ProfileData
    cfg, params = setup
    eng = _serve(cfg, params, n=3)
    eng.step()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        i = 0
        while eng.has_work:
            with jax.profiler.TraceAnnotation("bench_step", idx=i):
                eng.step()
            i += 1
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(xplane))
    lines = [ln for p in pd.planes for ln in p.lines
             if any(e.name == "serve.decode" for e in ln.events)]
    assert len(lines) == 1
    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
          for e in lines[0].events]
    steps = [(a, b) for n, a, b in ev if n == "bench_step"]
    decodes = [(a, b) for n, a, b in ev if n == "serve.decode"]
    assert len(steps) == i and decodes
    for a, b in decodes:
        assert any(sa <= a and b <= sb for sa, sb in steps)
    attrs = next(dict(e.stats) for e in lines[0].events
                 if e.name == "serve.decode")
    assert {"cohort", "width", "pos"} <= set(attrs)
    names = {n for n, _, _ in ev}
    assert {"serve.step", "serve.tick", "serve.device_wait",
            "serve.sample"} <= names
