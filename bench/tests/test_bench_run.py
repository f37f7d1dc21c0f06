"""Whole runs of the harness on the CPU, at a size a test run can hold.

The command itself refuses the CPU; these tests call ``run.execute``,
which is everything after that look for a chip, on a copy of ``bench/``
with a two-layer model and a light mix added as files.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import run, spec, weights
from bench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
E2E = {m["name"] for m in spec.benchmark()["end_to_end"]}


def _command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3_1_7b.chat",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()


def _execute(tmp_path, config, mix, dtype, limit, control=False,
             reading="max_logit_gap"):
    root = tiny.checkout(tmp_path, config, mix, limit=limit, dtype=dtype,
                         reading=reading)
    return run.execute(f"{config}.{mix}", 2**31 + 3, 1.0, False,
                       jax.devices(), tiny.CPU_PEAKS, root=root,
                       control=control)


# every reading a configuration may name, the MoE's widest gap included
@pytest.mark.parametrize("config,mix,dtype,reading,limit", [
    ("tiny_dense", "tinychat", "bfloat16", "max_logit_gap", 0.1),
    ("tiny_dense", "tinydoc", "float32", "max_logit_gap", 0.01),
    ("tiny_moe", "tinydoc", "float32", "max_logit_gap", 0.01),
    ("tiny_moe", "tinydoc", "float32", "mean_logit_gap", 0.001),
])
def test_served_tokens_agree_with_the_reference_and_the_control_fails(
        tmp_path, config, mix, dtype, reading, limit):
    line = _execute(tmp_path, config, mix, dtype, limit, control=True,
                    reading=reading)
    gap = line["compared"][reading]
    assert line["correct"] and gap["value"] <= limit
    # the reference computed in float8 puts other tokens first, and the
    # same comparison finds it not correct
    assert line["control"][reading] > 2 * limit
    assert line["control"]["correct"] is False
    # warm-up met every shape the traffic made
    assert line["window_programs"]["lowered"] == 0
    assert set(line["metrics"]) == E2E
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "compared"
    json.dumps(line)


def test_a_token_altered_where_it_is_made_fails_the_check(tmp_path,
                                                          monkeypatch):
    from repro.serve.engine import ServeEngine
    sample = ServeEngine._sample

    def altered(self, logits, rows):
        tok = sample(self, logits, rows)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    line = _execute(tmp_path, "tiny_dense", "tinychat", "bfloat16", 0.1)
    assert not line["correct"]
    assert line["compared"]["max_logit_gap"]["value"] > 0.1


def test_a_decoded_token_altered_in_the_moe_closed_loop_fails_the_check(
        tmp_path, monkeypatch):
    # the decode programs sample inside themselves: alter what they emit
    from repro.serve import engine
    sample = engine.sample_tokens

    def altered(logits, temps, key):
        tok, key = sample(logits, temps, key)
        return (tok + 1) % logits.shape[-1], key

    monkeypatch.setattr(engine, "sample_tokens", altered)
    line = _execute(tmp_path, "tiny_moe", "tinydoc", "float32", 0.001,
                    reading="mean_logit_gap")
    assert not line["correct"]
    assert line["compared"]["mean_logit_gap"]["value"] > 0.001


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (spec.BENCH_DIR / "configs").glob("*.json")))
def test_weights_fill_the_programs_params_tree(name):
    from repro.models.model import init_params
    cfg_file = spec.config(name)
    ref = spec.reference_module(cfg_file["reference"])
    m = cfg_file["model"]
    cfg = run.model_config(cfg_file)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: ref.to_program(
        weights.make(ref.shapes(m), m["dtype"], 0), m))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


#: sha256 (first 16 hex digits) of every leaf drawn at the tiny sizes from
#: seed 2**31 + 7, as the weights were drawn before the shapes moved into
#: the reference modules
PINNED = {
    "qwen3_1_7b": (tiny.MODEL, {
        "attn_norm": "8eba6b6b26a68667", "embed": "00ce747ade4ebee6",
        "final_norm": "caaf5144eaa02e9a", "k_norm": "05a2f00e7c0ef39d",
        "mlp_norm": "749ec4592840fffc", "q_norm": "61fcd87891a61ef4",
        "w_down": "e94a3847863e1981", "w_gate": "5a5c38a8f16266c1",
        "w_up": "94e03141e1a717f7", "wk": "019eaff5b14eba50",
        "wo": "799eee9cdf5b3847", "wq": "1b4a61a973bb423b",
        "wv": "a8ccf34623db23c8"}),
    "granite_moe_1b_a400m": (tiny.MOE_MODEL, {
        "attn_norm": "8eba6b6b26a68667", "embed": "00ce747ade4ebee6",
        "final_norm": "caaf5144eaa02e9a", "mlp_norm": "749ec4592840fffc",
        "router": "680df6db2c85539f", "w_down": "1d39fcc4ac4bcc84",
        "w_gate": "54e6bcbfd29e8ea0", "w_up": "ff3e324a26ef4a48",
        "wk": "019eaff5b14eba50", "wo": "799eee9cdf5b3847",
        "wq": "1b4a61a973bb423b", "wv": "a8ccf34623db23c8"}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_are_the_same_bits_as_before(name):
    import hashlib
    m, want = PINNED[name]
    ref = spec.reference_module(spec.config(name)["reference"])
    w = weights.make(ref.shapes(m), m["dtype"], 2**31 + 7)
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in w.items()}
    assert got == want


def test_a_new_architecture_is_added_as_files_alone(tmp_path):
    # tiny_two_blocks brings its reference module as a file and names it
    # in its configuration; nothing of the harness changes for it
    root = tiny.checkout(tmp_path, "tiny_two_blocks", "tinychat",
                         limit=0.01, dtype="float32")
    bench_dir = root / "bench"
    cfg_file = spec.config("tiny_two_blocks", bench_dir)
    ref = spec.reference_module(cfg_file["reference"], bench_dir)
    m = cfg_file["model"]
    tree = ref.to_program(weights.make(ref.shapes(m), m["dtype"], 0), m)
    assert sorted(tree["stack"]) == ["pos0", "pos1"]
    assert sorted(tree["tail"]) == ["0"]
    line = run.execute("tiny_two_blocks.tinychat", 2**31 + 3, 1.0, False,
                       jax.devices(), tiny.CPU_PEAKS, root=root)
    assert line["correct"]
    assert line["compared"]["max_logit_gap"]["value"] <= 0.01
    assert set(line["metrics"]) >= {"itl_p95_s", "tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_a_traced_moe_closed_loop_reads_the_longdoc_layer_metrics(tmp_path):
    # the per-layer entries the real granite cell lists, in a tiny MoE
    # closed loop; those that need a TPU plane read nothing on the CPU
    root = tiny.checkout(tmp_path, "tiny_moe", "tinydoc", limit=0.01,
                         dtype="float32")
    cell = "tiny_moe.tinydoc"
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"] = [
        dict(m, workloads=[cell]) for m in spec.benchmark()["per_layer"]
        if "granite_moe_1b_a400m.longdoc" in m.get("workloads", [])]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    line = run.execute(cell, 2**31 + 9, 1.0, True, jax.devices(),
                       tiny.CPU_PEAKS, root=root)
    assert line["correct"]
    host = {"prefill_ms_per_ktok.longdoc", "decode_host_ms.longdoc",
            "decode_calls_per_tick.longdoc"}
    assert host <= set(line["metrics"]) <= {m["name"] for m in b["per_layer"]}
    assert all(line["metrics"][n]["value"] > 0 for n in host)
    assert line["metrics"]["decode_calls_per_tick.longdoc"]["value"] >= 1


def test_weight_seeds_beyond_32_bits_differ():
    assert weights.jax_seed(2**32 + 5, "weights") != \
        weights.jax_seed(5, "weights")
