"""The yardstick's arithmetic, on inputs whose answers are known."""
import json
import shutil
import statistics

import numpy as np
import pytest

from bench import records, spec, traffic, warmup

QWEN3 = spec.config("qwen3_1_7b")["model"]
GRANITE = spec.config("granite_moe_1b_a400m")["model"]
DECODER = spec.reference_module("decoder")


# -- traffic ------------------------------------------------------------------

def _take(mix, seed, n):
    it = traffic.requests(mix, seed, vocab=1000)
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_traffic_is_a_function_of_the_seed(mix):
    m = spec.traffic(mix)
    seed = 2**31 + 12345
    a, b = _take(m, seed, 150), _take(m, seed, 150)
    for x, y in zip(a, b):
        assert (x.plen, x.max_new, x.due_s) == (y.plen, y.max_new, y.due_s)
        assert np.array_equal(x.prompt, y.prompt)
    c = _take(m, seed + 1, 150)
    assert any(not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_every_seed_gets_the_same_schedule(mix):
    m = spec.traffic(mix)
    a, b = _take(m, 1, 100), _take(m, 2, 100)
    assert [(r.plen, r.max_new, r.due_s) for r in a] == \
        [(r.plen, r.max_new, r.due_s) for r in b]
    # blocks of BLOCK requests hold the same sizes, in another order
    n = traffic.BLOCK
    c = _take(m, 1, 2 * n)
    assert sorted(r.plen for r in c[:n]) == sorted(r.plen for r in c[n:])
    assert [r.plen for r in c[:n]] != [r.plen for r in c[n:]]
    if m["loop"] == "open":
        assert np.isclose(c[n - 1].due_s, n / m["rate_per_s"], rtol=0.1)


def test_stratified_lengths_keep_their_shares():
    d = {"values": [128, 256, 512, 1024], "weights": [0.4, 0.3, 0.2, 0.1]}
    got = traffic.stratified(d, 64)
    assert [int((got == v).sum()) for v in d["values"]] == [26, 19, 13, 6]
    ln = traffic.stratified({"lognormal": {"median": 32, "sigma": 1.0},
                             "min": 8, "max": 256}, 64)
    assert ln.min() >= 8 and ln.max() <= 256
    assert np.median(ln) == pytest.approx(32, abs=2)


# -- end-to-end metrics from a synthetic timestamp log ------------------------

def _rec():
    # window [10, 20); three requests due in it, one before it
    reqs = [
        {"due": 9.0, "plen": 100, "max_new": 3, "tokens": [9.5, 10.5, 11.5]},
        {"due": 10.0, "plen": 200, "max_new": 3, "tokens": [10.4, 10.6, 11.0]},
        {"due": 12.0, "plen": 300, "max_new": 4, "tokens": [13.0, 19.0]},
        # no first token by the end: counts as 20 - 18 = 2
        {"due": 18.0, "plen": 400, "max_new": 2, "tokens": []},
        # due after the window: not counted
        {"due": 21.0, "plen": 500, "max_new": 2, "tokens": [21.5]},
    ]
    return {"window": {"start": 5.0, "w0": 10.0, "w1": 20.0},
            "requests": reqs, "spans": [], "stats": {}}


def test_ttft_counts_every_request_due_in_the_window():
    assert sorted(records.ttft_samples(_rec())) == pytest.approx(
        [0.4, 1.0, 2.0])
    got = spec.metric_fn("ttft_p90_s")(_rec())
    assert got == pytest.approx(np.percentile([0.4, 1.0, 2.0], 90))
    assert spec.metric_fn("ttft_p50_s")(_rec()) == pytest.approx(1.0)


def test_itl_includes_the_gap_a_request_is_still_waiting_in():
    # request 2: 0.2, 0.4; request 3: 6.0 and still waiting 1.0 at the end
    assert sorted(records.itl_samples(_rec())) == pytest.approx(
        [0.2, 0.4, 1.0, 6.0])
    assert spec.metric_fn("itl_p95_s")(_rec()) == pytest.approx(
        np.percentile([0.2, 0.4, 1.0, 6.0], 95))


def test_tokens_per_s_counts_prompts_and_tokens_made_in_the_window():
    # prompts whose first token came in the window: 200, 300
    # tokens stamped in [10, 20]: 2 + 3 + 2 = 7
    assert spec.metric_fn("tokens_per_s")(_rec()) == pytest.approx(
        (200 + 300 + 7) / 10.0)


def test_host_span_metrics():
    rec = _rec()
    rec["spans"] = [
        {"idx": 0, "t0": 9.0, "t1": 9.5, "event": "prefill"},
        {"idx": 1, "t0": 10.0, "t1": 10.5, "event": "prefill"},
        {"idx": 2, "t0": 11.0, "t1": 11.03, "event": "decode"},
        {"idx": 3, "t0": 12.0, "t1": 12.05, "event": "decode"},
    ]
    rec["stats"] = {"w0": {"decode_steps": 10, "decode_ticks": 5,
                           "prefill_tokens": 1000},
                    "w1": {"decode_steps": 40, "decode_ticks": 15,
                           "prefill_tokens": 3000}}
    assert spec.metric_fn("tick_ms.chat")(rec) == pytest.approx(40.0)
    assert spec.metric_fn("decode_calls_per_tick")(rec) == pytest.approx(3.0)
    assert spec.metric_fn("prefill_ms_per_ktok.longdoc")(rec) == \
        pytest.approx(500.0 / 2.0)


# -- operations and bytes, against hand counts --------------------------------

def test_qwen3_flops_by_hand():
    # per layer: q 2*2048*2048, k and v 2*2048*1024 each, o 2*2048*2048,
    # MLP 3 * 2*2048*6144; head 2*2048*151936
    per_layer = 8388608 + 2 * 4194304 + 8388608 + 75497472
    head = 622329856
    assert DECODER.decode_token_flops(QWEN3, 1) == \
        28 * (per_layer + 4 * 16 * 128) + head
    assert DECODER.decode_token_flops(QWEN3, 1000) - \
        DECODER.decode_token_flops(QWEN3, 1) == 28 * 4 * 16 * 128 * 999
    # causal prompt of 3: 3 tokens through the layers, 1+2+3 keys, 1 head
    assert DECODER.prefill_flops(QWEN3, 3) == \
        28 * (3 * per_layer + 6 * 4 * 16 * 128) + head


def test_granite_counts_active_experts_only():
    # per layer: q 2*1024*1024, k and v 2*1024*512 each, o 2*1024*1024,
    # router 2*1024*32, 8 experts of 3 * 2*1024*512; head 2*1024*49155
    per_layer = 2097152 + 2 * 1048576 + 2097152 + 65536 + 8 * 3145728
    assert DECODER.decode_token_flops(GRANITE, 1) == \
        24 * (per_layer + 4 * 16 * 64) + 100669440


def test_paged_attention_need_by_hand():
    # in each of 28 layers: 100 keys of K and V over 8 heads x 128, plus
    # q and out (16 x 128)
    need = DECODER.paged_attention_need(QWEN3, 100)
    assert need["bytes"] == 28 * (2 * (2 * 100 * 8 * 128)
                                  + 2 * (2 * 16 * 128))
    assert need["flops"] == 28 * 4 * 16 * 128 * 100


#: (prefill of 1024 and of 2048 tokens, a decoded token over 1100 keys,
#: the paged kernel's need for a row over 1100 keys), as counted before
#: the counts moved into the reference modules
PINNED_COUNTS = {
    "qwen3_1_7b": (3007216877568, 6254329593856, 3693215744,
                   {"flops": 28 * 9011200, "bytes": 28 * 4513792}),
    "granite_moe_1b_a400m": (826395334656, 1755769214976, 965351424,
                             {"flops": 24 * 4505600, "bytes": 24 * 2256896}),
}


@pytest.mark.parametrize("name", sorted(PINNED_COUNTS))
def test_work_counts_are_the_same_as_before(name):
    cfg_file = spec.config(name)
    ref, m = spec.reference_module(cfg_file["reference"]), cfg_file["model"]
    assert (ref.prefill_flops(m, 1024), ref.prefill_flops(m, 2048),
            ref.decode_token_flops(m, 1100),
            ref.paged_attention_need(m, 1100)) == PINNED_COUNTS[name]


def test_roofline_share_from_trace_summary():
    rec = _rec()
    rec["model"], rec["peaks"] = QWEN3, {"bf16_flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9}
    rec["reference"] = DECODER
    # tokens 1 and 2 of request 2 are decodes at keys 201 and 202
    rec["trace"] = {"chips": 1, "window_s": 1.0, "busy_s": 0.25,
                    "host_window": (10.55, 11.2),
                    "kernels": {"k": {"s": 1e-3, "n": 56}}}
    need = sum(DECODER.paged_attention_need(QWEN3, k)["bytes"]
               for k in (201, 202))
    assert records.kernel_roofline_pct(rec, "k") == pytest.approx(
        100 * need / 819e9 / 1e-3)
    assert spec.metric_fn("device_idle.chat")(rec) == pytest.approx(75.0)
    rec["trace"]["chips"] = 0
    assert spec.metric_fn("device_idle.chat")(rec) is None


# -- warm-up shapes -----------------------------------------------------------

def test_decode_columns():
    assert warmup.decode_columns(128, 128, 16) == 16
    assert warmup.decode_columns(128, 255, 16) == 16
    assert warmup.decode_columns(128, 256, 16) == 32
    assert warmup.decode_columns(1024, 1024, 16) == 128
    assert warmup.decode_columns(2048, 2111, 16) == 256


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_warmup_reaches_every_decode_shape_the_mix_can(mix):
    m = spec.traffic(mix)
    lengths = traffic.support(m["prompt_len"])
    max_out = max(traffic.support(m["output_len"]))
    need = {(w, warmup.decode_columns(L, p, 16)) for L in lengths
            for p in range(L, L + max_out - 1) for w in range(1, 9)}
    got = set()
    for L, news in warmup.plan(m, 8, 16):
        for j in range(1, max(news)):
            live = sum(1 for n in news if n > j)
            got.add((len(news) if live == len(news) else
                     warmup._pow2(live), warmup.decode_columns(L, L + j - 1,
                                                                16)))
    assert need <= got


# -- everything is found by name ---------------------------------------------

def test_a_new_config_mix_and_metric_are_found_as_files(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "configs" / "new_model.json").write_text(
        json.dumps({"model": {"n_layers": 1}}))
    (bench / "traffic" / "bursty.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 1.0}))
    (bench / "metrics" / "answer.new.py").write_text(
        "def compute(rec):\n    return 42.0\n")
    assert spec.config("new_model", bench)["model"]["n_layers"] == 1
    assert spec.traffic("bursty", bench)["rate_per_s"] == 1.0
    assert spec.metric_fn("answer.new", bench)({}) == 42.0
    with pytest.raises(spec.SpecError):
        spec.traffic("missing", bench)
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v0", bench)
    b = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}],
         "per_layer": [{"name": "answer.new", "workloads": ["y"]}]}
    assert [m["name"] for m in spec.metrics_for(b, "y", False)] == ["a"]
    assert [m["name"] for m in spec.metrics_for(b, "y", True)] == [
        "answer.new"]


def test_a_metric_split_by_cells_shares_the_file_of_its_first_part(
        tmp_path):
    bench = tmp_path / "bench"
    (bench / "metrics").mkdir(parents=True)
    (bench / "metrics" / "busy.py").write_text(
        "def compute(rec):\n    return rec['x']\n")
    (bench / "metrics" / "busy.special.py").write_text(
        "def compute(rec):\n    return -1.0\n")
    assert spec.metric_fn("busy.chat", bench)({"x": 3.0}) == 3.0
    assert spec.metric_fn("busy.longdoc", bench)({"x": 4.0}) == 4.0
    assert spec.metric_fn("busy.special", bench)({"x": 4.0}) == -1.0
    with pytest.raises(spec.SpecError):
        spec.metric_fn("idle.chat", bench)


def test_benchmark_json_names_only_files_that_exist():
    b = spec.benchmark()
    for c in b["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        spec.reference_module(spec.config(c["name"])["reference"])
    for w in b["workloads"]:
        spec.traffic(w["traffic"])
    for group in ("end_to_end", "per_layer"):
        for m in b[group]:
            spec.metric_fn(m["name"])


def test_every_cell_reports_every_end_to_end_metric():
    b = spec.benchmark()
    names = {m["name"] for m in b["end_to_end"]}
    assert {"itl_p95_s", "tokens_per_s", "setup_s"} <= names
    for w in b["workloads"]:
        got = {m["name"] for m in spec.metrics_for(b, w["name"], False)}
        assert got == names, w["name"]


def test_sets_report_spreads_by_the_standard_library_quartiles():
    from bench import sets
    a = [1.0, 1.1, 0.9, 1.0, 1.05, 0.95]
    b = [2.0, 2.0, 2.0, 2.0, 2.0, 3.0]
    runs = [{"line": {"metrics": {"m": {"value": v}}}} for v in a + b]
    got = sets.report(runs, 2)["m"]
    q1, _, q3 = statistics.quantiles(a, n=4)
    assert got[0]["spread"] == pytest.approx((q3 - q1) / 1.0)
    # the run farthest from the median is left out of the trimmed spread
    assert got[1]["spread_trimmed"] == 0.0
    assert got[2]["widest_spread"] == max(got[0]["spread"], got[1]["spread"])


def test_check_readings_by_hand():
    from bench import check
    got = check.readings(np.array([0.0, 0.0, 0.5, 0.1], np.float32))
    assert got == pytest.approx({"max_logit_gap": 0.5,
                                 "mean_logit_gap": 0.15})
