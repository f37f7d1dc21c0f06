"""The trace reduction, on a short window recorded on a TPU v5 lite.

``data/qwen3_chat.xplane.pb.gz`` is the traced window of a ``--trace 1``
run of ``qwen3_1_7b.chat`` (``--keep-trace``), and ``.spans.json`` beside
it the harness's step records of that run. The expected numbers are
counted here a second way, straight from the trace's events.
"""
import gzip
import json
import pathlib

import numpy as np
import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
XPLANE = DATA / "qwen3_chat.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    spans = json.loads((DATA / "qwen3_chat.xplane.pb.spans.json").read_text())
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        XPLANE.read_bytes()))
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in ln.events] for ln in dev.lines}
    steps = [(e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)["idx"])
             for p in pd.planes if not p.name.startswith("/device")
             for ln in p.lines for e in ln.events if e.name == "bench_step"]
    return trace.summarize(pd, spans), lines, steps, spans


def _busy_by_grid(ops, lo, hi, res=1000):
    """Busy time counted on a 1 us grid: a second, independent count."""
    grid = np.zeros(int((hi - lo) // res) + 1, bool)
    for _, a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // res):int((b - lo) // res)] = True
    return grid.sum() * res / 1e9


def test_window_is_the_span_extent(recorded):
    s, _, steps, _ = recorded
    lo = min(a for a, _, _ in steps)
    hi = max(b for _, b, _ in steps)
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert s["chips"] == 1


def test_busy_time_is_the_union_of_operations(recorded):
    s, lines, steps, _ = recorded
    lo = min(a for a, _, _ in steps)
    hi = max(b for _, b, _ in steps)
    want = _busy_by_grid(lines["XLA Ops"], lo, hi)
    assert s["busy_s"] == pytest.approx(want, rel=0.02)
    assert 0 < s["busy_s"] < s["window_s"]
    # idle gaps, by what the host was doing, fill the rest of the window
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)


def test_module_and_kernel_time(recorded):
    s, lines, steps, _ = recorded
    lo = min(a for a, _, _ in steps)
    hi = max(b for _, b, _ in steps)
    decode = sum(min(b, hi) - max(a, lo) for n, a, b in lines["XLA Modules"]
                 if n.startswith("jit_decode_step_paged") and b > lo and a < hi)
    assert s["modules"]["decode_step_paged"] == pytest.approx(decode / 1e9)
    mods = [(a, b) for n, a, b in lines["XLA Modules"]
            if n.startswith("jit_decode_step_paged")]
    kern = [(a, b) for n, a, b in lines["XLA Ops"]
            if 'custom_call_target="tpu_custom_call"' in n
            and any(ma <= a < mb for ma, mb in mods) and b > lo and a < hi]
    got = s["kernels"]["decode_step_paged:tpu_custom_call"]
    assert got["n"] == len(kern)
    assert got["s"] == pytest.approx(
        sum(min(b, hi) - max(a, lo) for a, b in kern) / 1e9)
    assert got["s"] < s["modules"]["decode_step_paged"]


def test_breakdown_is_bounded_and_named(recorded):
    s, _, _, spans = recorded
    assert 0 < len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in s["device_ops"])
    assert not any(k.endswith(" while") for k, _ in s["device_ops"])
    names = {k for k, _ in s["idle_gaps"]}
    assert names <= {sp["event"] for sp in spans} | {"between_steps"}
    # the host-clock window comes from the paired spans themselves
    a, b = s["host_window"]
    assert b - a == pytest.approx(s["window_s"], rel=0.05)


#: step_mfu.chat and paged_attention_roofline.chat of each recorded window
#: with the requests of ``_requests``, as counted before the work counts
#: moved into the reference modules: the same digits after the move
PINNED_SHARES = {
    "qwen3_chat": (3.696865699145934, 15.304651791289743),
    "qwen3_chat_spans": (4.545842857474632, 16.080840348642916),
}


def _requests(a, b):
    """Six requests of the chat mix's prompt lengths whose tokens are
    spread over the host window [a, b]: one prefill each, the rest
    decodes."""
    reqs = []
    for i, plen in enumerate((128, 256, 512, 1024, 128, 256)):
        n = 4 + i
        reqs.append({"due": a, "plen": plen, "max_new": n,
                     "tokens": [a + (b - a) * (j + 0.5 + 0.1 * i) / (n + 1)
                                for j in range(n)]})
    return reqs


@pytest.mark.parametrize("stem", sorted(PINNED_SHARES))
def test_model_and_kernel_shares_of_the_recorded_windows(stem):
    from jax.profiler import ProfileData
    from bench import spec
    spans = json.loads((DATA / f"{stem}.xplane.pb.spans.json").read_text())
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        (DATA / f"{stem}.xplane.pb.gz").read_bytes()))
    s = trace.summarize(pd, spans)
    a, b = s["host_window"]
    cfg_file = spec.config("qwen3_1_7b")
    rec = {"window": {"start": a, "w0": a, "w1": b},
           "requests": _requests(a, b), "spans": spans, "stats": {},
           "trace": s, "model": cfg_file["model"],
           "reference": spec.reference_module(cfg_file["reference"]),
           "peaks": spec.peaks("TPU v5 lite")}
    got = (spec.metric_fn("step_mfu.chat")(rec),
           spec.metric_fn("paged_attention_roofline.chat")(rec))
    assert got == PINNED_SHARES[stem]
