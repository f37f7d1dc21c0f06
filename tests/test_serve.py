"""Serving engine: correctness of batched greedy decode + scheduler."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.model import Model, init_params
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import Scheduler, SchedulerConfig


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced_config("qwen3_1_7b").with_overrides(
        n_layers=2, d_model=64, vocab_size=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new):
    """Reference: full forward re-run per generated token."""
    model = Model(cfg)
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        batch = {"tokens": jnp.asarray(np.array(toks, np.int32))[None]}
        x = model._input_x(params, batch)
        from repro.models.model import make_positions
        from repro.models import layers
        pos = make_positions(cfg, len(toks))
        xb, _ = model.backbone_train(params, x, pos)
        xb = layers.apply_norm(cfg.norm, params["final_norm"], xb)
        logits = model.unembed(params, xb[:, -1])
        nxt = int(jnp.argmax(logits[0]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_engine_greedy_matches_reference(setup):
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    n_new = 6
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=8 + n_new)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=n_new))
    eng.run()
    got = eng.done[0].output
    expect = _greedy_reference(cfg, params, prompt, n_new)
    assert got == expect


def test_engine_reports_predicted_vs_measured_step(setup):
    cfg, params = setup
    rng = np.random.default_rng(3)
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=16,
                      predicted_step_s=1.5e-3)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=8).astype(np.int32), max_new_tokens=4))
    stats = eng.run()
    assert stats["decode_steps"] == 3          # 4 tokens = 1 sampled + 3 steps
    assert stats["measured_step_s"] > 0.0
    assert stats["predicted_step_s"] == 1.5e-3
    expect = (1.5e-3 - stats["measured_step_s"]) / stats["measured_step_s"]
    assert stats["oracle_rel_error"] == pytest.approx(expect)
    # without a prediction the error key is absent, not None/garbage
    eng2 = ServeEngine(cfg, params, max_batch=2, max_seq=16)
    eng2.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=8).astype(np.int32), max_new_tokens=2))
    stats2 = eng2.run()
    assert stats2["predicted_step_s"] is None
    assert "oracle_rel_error" not in stats2


def test_engine_reports_latency_percentiles(setup):
    """p50/p95 TTFT, per-request decode latency, and per-step percentiles
    — the serve-time check for the planner's latency claims."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=24)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=8).astype(np.int32), max_new_tokens=4))
    stats = eng.run()
    assert 0.0 < stats["p50_ttft_s"] <= stats["p95_ttft_s"]
    assert 0.0 <= stats["p50_decode_s"] <= stats["p95_decode_s"]
    assert 0.0 < stats["p50_step_s"] <= stats["p95_step_s"]
    # percentiles summarize the same samples the aggregates come from
    assert stats["p50_ttft_s"] <= max(
        r.t_first_token - r.t_submit for r in eng.done)
    assert stats["p95_step_s"] <= stats["decode_steps"] * stats[
        "measured_step_s"] + 1e-9
    # an idle engine reports zeroed percentiles, not NaN/crash
    empty = ServeEngine(cfg, params, max_batch=2, max_seq=24).run()
    for k in ("p50_ttft_s", "p95_ttft_s", "p50_decode_s", "p95_decode_s",
              "p50_step_s", "p95_step_s"):
        assert empty[k] == 0.0


def test_engine_batches_multiple_requests(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=24)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(6)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    stats = eng.run()
    assert stats["requests"] == 6
    assert stats["prefills"] == 2       # 4 + 2 with max_batch=4
    assert stats["total_new_tokens"] == 24
    # batching must not cross-contaminate: request 0 alone == in batch
    solo = ServeEngine(cfg, params, max_batch=1, max_seq=24)
    solo.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=4))
    solo.run()
    batched_r0 = next(r for r in eng.done if r.rid == 0)
    assert solo.done[0].output == batched_r0.output


def test_engine_mixed_length_prompts_wave_correctly(setup):
    cfg, params = setup
    rng = np.random.default_rng(2)
    eng = ServeEngine(cfg, params, max_batch=8, max_seq=32)
    for i, L in enumerate((8, 8, 12, 12, 8)):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=L).astype(np.int32), max_new_tokens=2))
    stats = eng.run()
    assert stats["requests"] == 5
    assert stats["prefills"] >= 2       # length groups cannot share a wave


def test_engine_serves_real_pruned_params_end_to_end():
    """Prune via the session front door, then serve the *pruned* params:
    decode outputs keep their shapes and the batch accounting adds up."""
    from repro.api import CPruneConfig, PruningSession, TrainHooks, Workload

    cfg = get_reduced_config("qwen3_1_7b").with_overrides(
        n_layers=2, d_model=64, d_ff=512, n_heads=8, n_kv_heads=2,
        head_dim=8, vocab_size=128)
    session = PruningSession(
        cfg, workload=Workload(tokens_global=8192),
        hooks=TrainHooks(short_term_train=lambda p, s: p,
                         eval_acc=lambda p, s: 0.9),
        pcfg=CPruneConfig(a_g=0.5, alpha=0.5, beta=0.9999,
                          max_iterations=2, seq_len=64))
    res = session.prune(strategy="cprune")
    assert any(h.accepted for h in res.history)
    ffn = next(s for s in res.sites if s.kind == "ffn")
    assert ffn.dim < cfg.d_ff                     # params really shrank
    assert res.params["stack"]["pos0"]["ffn"]["w_up"].shape[-1] == ffn.dim

    eng = session.serve(max_batch=4, max_seq=24)
    rng = np.random.default_rng(3)
    n_req, n_new = 6, 4
    for i in range(n_req):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=8).astype(np.int32),
            max_new_tokens=n_new))
    stats = eng.run()
    # batch accounting: every request finished with exactly its token budget
    assert stats["requests"] == n_req
    assert stats["prefills"] == 2                 # 4 + 2 with max_batch=4
    assert stats["total_new_tokens"] == n_req * n_new
    for r in eng.done:
        assert r.done and len(r.output) == n_new
        assert all(0 <= t < cfg.vocab_size for t in r.output)
    # pruned-model decode must match its own full-forward reference
    r0 = next(r for r in eng.done if r.rid == 0)
    expect = _greedy_reference(cfg, res.params, r0.prompt, n_new)
    assert r0.output == expect


# ---------------------------------------------------------------------------
# Scheduler core (ISSUE 5): bucketed admission, slot compaction, step API
# ---------------------------------------------------------------------------

def _mk(rng, cfg, rid, plen, n_new):
    return Request(rid=rid, prompt=rng.integers(
        0, cfg.vocab_size, size=plen).astype(np.int32),
        max_new_tokens=n_new)


def test_scheduler_buckets_by_prompt_length_and_groups_decode_lengths():
    """Pure policy: interleaved lengths land in per-length buckets, the
    fullest bucket is admitted first, and a bucket's admission slice
    groups similar max_new_tokens so the cohort finishes together."""
    sched = Scheduler(SchedulerConfig())
    rng = np.random.default_rng(0)
    cfg = get_reduced_config("qwen3_1_7b")
    reqs = []
    for i in range(8):
        r = _mk(rng, cfg, i, 8 if i % 2 == 0 else 12,
                4 if i % 4 < 2 else 16)
        reqs.append(r)
        sched.submit(r)
    assert len(sched) == 8
    batch = sched.select(4)
    # one prompt-length bucket, grouped by decode length
    assert len(batch) == 4
    assert len({len(r.prompt) for r in batch}) == 1
    assert [r.max_new_tokens for r in batch] == sorted(
        r.max_new_tokens for r in batch)
    assert len(sched) == 4
    # the other bucket comes next; wave policy refuses mid-decode admission
    batch2 = sched.select(4)
    assert len(batch2) == 4
    assert len({len(r.prompt) for r in batch2}) == 1
    assert len(batch[0].prompt) != len(batch2[0].prompt)
    wave = Scheduler(SchedulerConfig(policy="wave"))
    wave.submit(_mk(rng, cfg, 99, 8, 4))
    assert wave.select(4, live_groups=1) == []
    assert len(wave.select(4, live_groups=0)) == 1


def test_engine_stops_stepping_finished_slots_on_mixed_max_new(setup):
    """Satellite: a wave used to run max(max_new_tokens) full-width steps.
    The scheduler core compacts finished slots away, so mixed decode
    budgets stop paying for the longest request — outputs unchanged."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(4)]
    budgets = [8, 2, 2, 2]

    def drain(policy):
        eng = ServeEngine(cfg, params, max_batch=4, max_seq=24,
                          scheduler=policy)
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=n))
        return eng, eng.run()

    legacy, legacy_stats = drain("wave")
    new, new_stats = drain("bucketed")
    # legacy: 4 slots x (8 - 1) decode steps, finished or not
    assert legacy_stats["slot_steps"] == 4 * 7
    # scheduler core: the three short requests leave after their second
    # token; the rest of the drain is a compacted batch
    assert new_stats["slot_steps"] < legacy_stats["slot_steps"]
    assert new_stats["active_slot_steps"] <= new_stats["slot_steps"]
    assert new_stats["total_new_tokens"] == legacy_stats[
        "total_new_tokens"] == sum(budgets)
    # greedy outputs are bit-identical across policies, per request
    for rid in range(4):
        a = next(r for r in legacy.done if r.rid == rid)
        b = next(r for r in new.done if r.rid == rid)
        assert a.output == b.output


def test_engine_keeps_batches_full_on_interleaved_prompt_lengths(setup):
    """Satellite: alternating prompt lengths must not collapse batch
    occupancy — length bucketing admits full same-length cohorts."""
    cfg, params = setup
    rng = np.random.default_rng(12)
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=32)
    for i in range(8):
        eng.submit(_mk(rng, cfg, i, 8 if i % 2 == 0 else 12, 4))
    stats = eng.run()
    assert stats["requests"] == 8
    # every admitted cohort was a full batch of one prompt length
    assert stats["prefills"] == 2
    assert stats["mean_batch_occupancy"] == pytest.approx(1.0)
    assert stats["slot_steps"] == stats["active_slot_steps"]


def test_engine_step_api_is_non_blocking_and_resumable(setup):
    cfg, params = setup
    rng = np.random.default_rng(13)
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=24)
    for i in range(3):
        eng.submit(_mk(rng, cfg, i, 8, 3))
    assert eng.has_work
    # a zero deadline does no work and loses nothing
    stats0 = eng.serve_forever(deadline_s=0.0)
    assert stats0["requests"] == 0 and len(eng.pending) == 3
    # first quantum admits (prefill), later quanta decode, idle when done
    ev = eng.step()
    assert ev["event"] == "prefill" and ev["admitted"] == 2
    seen = {ev["event"]}
    while eng.has_work:
        seen.add(eng.step()["event"])
    assert seen == {"prefill", "decode"}
    assert eng.step()["event"] == "idle"
    assert len(eng.done) == 3
    # run() on the drained engine is a no-op, not an error
    assert eng.run()["requests"] == 3


def test_engine_empty_run_returns_zeroed_stats(setup):
    """Satellite: run() on an empty queue yields zeroed, finite stats —
    never NaN — and no oracle-error key."""
    cfg, params = setup
    stats = ServeEngine(cfg, params, max_batch=2, max_seq=24).run()
    assert stats["requests"] == 0
    assert "oracle_rel_error" not in stats
    for k, v in stats.items():
        if isinstance(v, float):
            assert math.isfinite(v), f"{k} is not finite: {v}"
            assert v == 0.0 or k == "wall_s", f"{k} nonzero on empty run"
    assert stats["predicted_step_s"] is None
    assert stats["tokens_per_s"] == 0.0
    assert stats["mean_batch_occupancy"] == 0.0


def test_engine_records_decode_step_into_measurement_log(setup):
    from repro.core.oracle import MeasurementLog

    cfg, params = setup
    rng = np.random.default_rng(14)
    log = MeasurementLog()
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=24,
                      measurements=log)
    eng.submit(_mk(rng, cfg, 0, 8, 4))
    stats = eng.run()
    key = MeasurementLog.step_key(cfg.name, 2, 24)
    assert log.lookup(key) is not None and log.lookup(key) > 0.0
    # the recorded value summarizes the same samples stats() reports
    assert log.lookup(key) <= stats["p95_step_s"] + 1e-12
    # an idle engine records nothing rather than garbage
    eng2 = ServeEngine(cfg, params, max_batch=2, max_seq=24)
    assert eng2.record_measurements(MeasurementLog()) is None
    with pytest.raises(ValueError, match="MeasurementLog"):
        eng2.record_measurements()


def test_engine_admits_next_cohort_mid_decode(setup):
    """Continuous batching at group granularity: slots freed by finished
    requests are refilled by a new cohort before the first finishes."""
    cfg, params = setup
    rng = np.random.default_rng(15)
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=32,
                      scheduler=SchedulerConfig(compact="exact"))
    # cohort 1: one long, three short -> three slots free mid-decode
    for i, n in enumerate((12, 2, 2, 2)):
        eng.submit(_mk(rng, cfg, i, 8, n))
    # cohort 2 waits in another length bucket
    for i in range(4, 7):
        eng.submit(_mk(rng, cfg, i, 10, 2))
    events = []
    while eng.has_work:
        ev = eng.step()
        events.append((ev["event"], len(eng.done)))
    # the second prefill happened while the long request was still
    # decoding (fewer than all 7 requests were done at that point)
    prefill_points = [done for e, done in events if e == "prefill"]
    assert len(prefill_points) == 2
    assert prefill_points[1] < 7
    assert next(r for r in eng.done if r.rid == 0).output and \
        len(eng.done) == 7


# ---------------------------------------------------------------------------
# Sampling inside the decode programs: one device call and one token read
# per decode call
# ---------------------------------------------------------------------------

LAYOUTS = {
    "paged": SchedulerConfig(kv_layout="paged", page_size=8),
    "contiguous": SchedulerConfig(kv_layout="contiguous"),
}


def _eager_sample(logits, temps, key):
    """Sampling as separate eager ops on the model's logits: a key split,
    the greedy argmax, a categorical draw at max(temperature, 1e-6), and
    a select by temperature > 0."""
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits[:, 0], axis=-1)
    t = jnp.asarray(temps, jnp.float32)[:, None]
    noisy = jax.random.categorical(sub, logits[:, 0] / jnp.maximum(t, 1e-6))
    tok = jnp.where(t[:, 0] > 0, noisy, greedy)
    return tok[:, None].astype(jnp.int32), key


def _sample_eagerly(eng):
    """Rewire ``eng`` to the eager path: the model's bare decode step
    returns logits, and sampling runs after it as eager ops, at admission
    and at every decode call, on the engine's key in the same order."""
    step_paged = jax.jit(eng.model.decode_step_paged, donate_argnums=2)
    step = jax.jit(eng.model.decode_step)

    def paged(params, cur, pools, table, pos, temps, key):
        logits, pools = step_paged(params, cur, pools, table, pos)
        tok, key = _eager_sample(logits, temps, key)
        return tok, pools, key

    def contiguous(params, cur, caches, temps, key):
        logits, caches = step(params, cur, caches)
        tok, key = _eager_sample(logits, temps, key)
        return tok, caches, key

    def admission(logits, rows):
        temps = [r.temperature if r is not None else 0.0 for r in rows]
        tok, eng.key = _eager_sample(logits, temps, eng.key)
        return tok

    eng._decode_paged, eng._decode, eng._sample = paged, contiguous, \
        admission
    return eng


def _sampling_mix(cfg, temps, seed=21):
    """Two length buckets, ragged answer lengths (so groups compact)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (plen, n) in enumerate(((8, 10), (8, 3), (8, 6), (12, 4),
                                   (12, 8))):
        r = _mk(rng, cfg, i, plen, n)
        r.temperature = temps[i % len(temps)]
        reqs.append(r)
    return reqs


def _outputs(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert len(eng.done) == len(reqs)
    return {r.rid: list(r.output) for r in eng.done}


@pytest.mark.parametrize("temps", [(0.0,), (0.8, 0.0, 0.3)],
                         ids=["greedy", "mixed"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_decode_program_samples_like_the_eager_path(setup, layout, temps):
    """Sampling traced into the decode program makes, token for token,
    what the eager ops make from the model's logits with the same key
    sequence — greedy rows and temperature rows alike."""
    cfg, params = setup
    fused = ServeEngine(cfg, params, max_batch=4, max_seq=24, seed=3,
                        scheduler=LAYOUTS[layout])
    eager = _sample_eagerly(ServeEngine(cfg, params, max_batch=4,
                                        max_seq=24, seed=3,
                                        scheduler=LAYOUTS[layout]))
    assert eager.kv_layout == fused.kv_layout == layout
    assert _outputs(fused, _sampling_mix(cfg, temps)) == \
        _outputs(eager, _sampling_mix(cfg, temps))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_mixed_cohort_is_seeded_and_keeps_greedy_rows(setup, layout):
    """Greedy and temperature-0.8 rows in one cohort: the same seed gives
    the same outputs in two engines, and the greedy rows match a run in
    which every row is greedy."""
    cfg, params = setup
    runs = [_outputs(ServeEngine(cfg, params, max_batch=4, max_seq=24,
                                 seed=9, scheduler=LAYOUTS[layout]),
                     _sampling_mix(cfg, temps))
            for temps in ((0.0, 0.8), (0.0, 0.8), (0.0,))]
    mixed, again, greedy = runs
    assert mixed == again
    hot = [r.rid for r in _sampling_mix(cfg, (0.0, 0.8)) if r.temperature]
    for rid in mixed:
        if rid not in hot:
            assert mixed[rid] == greedy[rid]
    # the temperature rows do draw: some token leaves the greedy path
    assert any(mixed[rid] != greedy[rid] for rid in hot)


@pytest.mark.parametrize("layout,chunk", [("paged", 0), ("contiguous", 0),
                                          ("paged", 16)],
                         ids=["paged", "contiguous", "paged-chunked"])
def test_one_device_call_and_one_read_per_decode_call(setup, layout, chunk):
    """``serve.sample`` counts admissions (and chunked-prefill
    completions) only; each decode call is one jitted call, read back
    by one ``serve.read_tokens``."""
    import dataclasses
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=40,
                      scheduler=dataclasses.replace(LAYOUTS[layout],
                                                    prefill_chunk=chunk))
    calls = {"n": 0}
    name = "_decode_paged" if layout == "paged" else "_decode"
    program = getattr(eng, name)

    def counted(*args):
        calls["n"] += 1
        return program(*args)
    setattr(eng, name, counted)
    reqs = _sampling_mix(cfg, (0.0, 0.8))
    if chunk:
        rng = np.random.default_rng(22)
        reqs.append(_mk(rng, cfg, 5, 20, 4))     # 20 > 16: chunked
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    spans = stats["spans"]
    assert stats["requests"] == len(reqs)
    assert stats["decode_steps"] > stats["prefills"] > 1
    assert calls["n"] == stats["decode_steps"]
    assert spans["serve.sample"]["n"] == stats["prefills"]
    assert spans["serve.read_tokens"]["n"] == \
        stats["prefills"] + stats["decode_steps"]
    if chunk:
        assert stats["chunk_steps"] > 0
