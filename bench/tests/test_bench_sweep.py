"""The knee sweep's rule, and one short sweep of a tiny cell on the CPU."""
from bench import run, spec, sweep
from bench.loop import Tracked
from bench.tests import tiny
from bench.traffic import RequestSpec

SLO = {"ttft_s": 1.0, "tpot_s": 0.2, "share": 0.9}


def _tracked(due, tokens, done=True):
    t = Tracked(RequestSpec(8, len(tokens), None), None, due, due,
                list(tokens))
    t.done_at = tokens[-1] if done and tokens else None
    return t


def test_readings_take_due_requests_and_mark_the_unfinished():
    ts = [_tracked(0.5, [1.0, 1.1, 1.3]),     # before the window
          _tracked(2.0, [2.5, 2.7, 2.9]),
          _tracked(3.0, [3.2], done=False),
          _tracked(9.0, [9.1, 9.2])]          # after it
    rs = sweep.readings(ts, 1.0, 5.0)
    assert rs[1] is None and len(rs) == 2
    assert abs(rs[0][0] - 0.5) < 1e-9 and abs(rs[0][1] - 0.2) < 1e-9
    assert sweep.met(rs, SLO) == 1


def test_the_sustained_rate_is_the_highest_with_every_lower_one_met():
    def s(share):
        return {"met_share": share}
    assert sweep.sustained({0.5: s(1.0), 0.6: s(0.95), 0.7: s(0.85),
                            0.8: s(0.92)}, SLO) == 0.6
    assert sweep.sustained({0.5: s(0.5), 0.6: s(1.0)}, SLO) is None


def test_a_short_sweep_of_a_tiny_cell(tmp_path):
    root = tiny.checkout(tmp_path, "tiny_dense", "tinychat")
    bench_dir = root / "bench"
    cfg_file = spec.config("tiny_dense", bench_dir)
    mix = dict(spec.traffic("tinychat", bench_dir), load=0.8,
               slo={"ttft_s": 30.0, "tpot_s": 30.0, "share": 0.9})
    ref = spec.reference_module(cfg_file["reference"], bench_dir)
    engine, _, _, _ = run.build(cfg_file, ref, mix, 7)
    lines = []
    pooled, decision = sweep.sweep(engine, cfg_file, mix, [1, 2], [5.0, 10.0],
                                   0.5, out=lines.append)
    assert len(lines) == 2 * 2 + 2 + 1
    assert all(p["due"] > 0 and p["finished"] == p["due"]
               for p in pooled.values())
    assert decision["sustained_rate_per_s"] == 10.0
    assert decision["cell_rate_per_s"] == 8.0
