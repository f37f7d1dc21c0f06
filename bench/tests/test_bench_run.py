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
import pytest

from bench import run, weights
from bench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]


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


def _execute(tmp_path, config, mix, dtype, limit, control=False):
    root = tiny.checkout(tmp_path, config, mix, limit=limit, dtype=dtype)
    return run.execute(f"{config}.{mix}", 2**31 + 3, 1.0, False,
                       jax.devices(), tiny.CPU_PEAKS, root=root,
                       control=control)


@pytest.mark.parametrize("config,mix,dtype,limit", [
    ("tiny_dense", "tinychat", "bfloat16", 0.1),
    ("tiny_dense", "tinydoc", "float32", 0.01),
    ("tiny_moe", "tinydoc", "float32", 0.01),
])
def test_served_tokens_agree_with_the_reference_and_the_control_fails(
        tmp_path, config, mix, dtype, limit):
    line = _execute(tmp_path, config, mix, dtype, limit, control=True)
    gap = line["compared"]["max_logit_gap"]
    assert line["correct"] and gap["value"] <= limit
    # the reference computed in float8 puts other tokens first, and the
    # same comparison finds it not correct
    assert line["control"]["max_logit_gap"] > 2 * limit
    assert line["control"]["correct"] is False
    # warm-up met every shape the traffic made
    assert line["window_programs"]["lowered"] == 0
    want = ({"itl_p95_s", "setup_s"} if mix == "tinychat"
            else {"tokens_per_s", "setup_s"})
    assert set(line["metrics"]) == want
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


@pytest.mark.parametrize("name", ["qwen3_1_7b", "granite_moe_1b_a400m"])
def test_weights_fill_the_programs_params_tree(name):
    from bench import spec
    from repro.models.model import init_params
    cfg_file = spec.config(name)
    cfg = run.model_config(cfg_file)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(
        lambda: weights.to_program(weights.make(cfg_file["model"], 0)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_weight_seeds_beyond_32_bits_differ():
    assert weights.jax_seed(2**32 + 5, "weights") != \
        weights.jax_seed(5, "weights")
