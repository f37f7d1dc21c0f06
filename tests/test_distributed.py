"""Distributed integration tests (subprocesses with forced host devices —
conftest must NOT set XLA_FLAGS globally, so these spawn fresh pythons)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
ENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = {**ENV, "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_sharded_train_step_runs_and_matches_single_device():
    """Loss on a 4x2 mesh == loss on 1 device (same batch, same init)."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced_config
from repro.data.pipeline import DataPipeline
from repro.launch import steps as S
from repro.models.model import Model, init_params
from repro.optim.optimizers import adamw_init
from repro.sharding import logical, rules

cfg = get_reduced_config("qwen3_1_7b").with_overrides(
    n_layers=2, d_model=64, d_ff=128, vocab_size=256)
pipe = DataPipeline(cfg, global_batch=8, seq_len=32)
batch = pipe.batch(0)
params = init_params(jax.random.PRNGKey(0), cfg)
opt = adamw_init(params)

# single device reference
m0 = Model(cfg)
loss0, _ = jax.jit(m0.loss_fn)(params, batch)

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"), jax.devices()[:8])
model = S.build_model(cfg, mesh)
step = S.make_train_step(cfg, model)
with mesh, logical.set_rules(mesh, rules.logical_rules(mesh)):
    jitted = S.jit_train_step(step, mesh, jax.eval_shape(lambda: params),
                              jax.eval_shape(lambda: batch), donate=False)
    p2, o2, metrics = jitted(params, opt, batch)
diff = abs(float(metrics["loss"]) - float(loss0))
assert diff < 2e-3, (float(metrics["loss"]), float(loss0))
print("OK", diff)
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_sharded_serve_step_matches_single_device():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_reduced_config
from repro.launch import steps as S
from repro.models.model import Model, init_params
from repro.sharding import logical, rules

cfg = get_reduced_config("qwen3_1_7b").with_overrides(
    n_layers=2, d_model=64, vocab_size=256)
params = init_params(jax.random.PRNGKey(0), cfg)
model0 = Model(cfg)
B, S0 = 8, 16
toks = jax.random.randint(jax.random.PRNGKey(5), (B, S0), 0, cfg.vocab_size)
logits0, caches0 = jax.jit(lambda p, b: model0.prefill(p, b, 32))(
    params, {"tokens": toks})
tok = jnp.argmax(logits0[:, 0], -1).astype(jnp.int32)[:, None]
ref_logits, _ = jax.jit(model0.decode_step)(params, tok, caches0)

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"), jax.devices()[:8])
model = S.build_model(cfg, mesh)
serve = S.make_serve_step(cfg, model)
with mesh, logical.set_rules(mesh, rules.logical_rules(mesh, seq_shard=False)):
    jitted = S.jit_serve_step(serve, mesh, cfg, model,
                              jax.eval_shape(lambda: params),
                              jax.eval_shape(lambda: caches0),
                              jax.eval_shape(lambda: tok), donate=False)
    logits, caches = jitted(params, tok, caches0)
np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                           rtol=2e-4, atol=2e-4)
print("OK")
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_train_driver_with_checkpoint_resume(tmp_path):
    """launch.train runs, checkpoints, and resumes on a different mesh
    (elastic: 4x2 -> 2x2)."""
    ckpt = str(tmp_path / "ck")
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "qwen3_1_7b", "--reduced", "--batch", "8", "--seq", "32",
            "--ckpt-dir", ckpt]
    env8 = {**ENV, "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r1 = subprocess.run(base + ["--devices", "8", "--dp", "4", "--tp", "2",
                                "--steps", "10"],
                        env=env8, capture_output=True, text=True, timeout=600)
    assert r1.returncode == 0, r1.stderr[-2000:]
    env4 = {**ENV, "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r2 = subprocess.run(base + ["--devices", "4", "--dp", "2", "--tp", "2",
                                "--steps", "14", "--resume"],
                        env=env4, capture_output=True, text=True, timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 10" in r2.stdout


def test_dryrun_single_cell_smoke():
    """The dry-run entry point works end to end for one cheap cell."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite_moe_1b_a400m", "--shape", "decode_32k", "--mesh", "single"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[OK  ]" in r.stdout


def test_compile_cache_dir_env_wins_else_checkout(monkeypatch, tmp_path):
    import jax

    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            enable_compile_cache)
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old  # JAX reads env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR == Path(__file__).resolve().parents[1] \
            / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
