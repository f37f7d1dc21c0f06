"""Compile the served kernels and the decode step for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: these tests
describe a ``v5e:2x2`` topology and compile for one of its chips, which
refuses what interpret mode cannot see (unaligned blocks, scoped-VMEM
overflow, a step that does not fit the device). Nothing runs.

The topology is described inside a module fixture — never at import —
and every test of this file skips when it cannot be described. Code that
asks ``jax.default_backend()`` still sees the CPU here, so the tests steer
the paged decode onto its Pallas branch and compiled kernels themselves.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.cost_model import Block
from repro.kernels.matmul import matmul
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.paged_attention import paged_attention
from repro.models.model import Model, init_params
from repro.models.paged_cache import RESERVED_BLOCKS, init_paged_pools

#: one TPU v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("head_dim", [128, 64], ids=["qwen3", "granite"])
def test_paged_attention_compiles(one_chip, no_compile_cache, head_dim):
    B, hq, hkv, bs, nc = 8, 16, 8, 16, 128
    s = _on(one_chip)
    pool = s((RESERVED_BLOCKS + B * nc, bs, hkv, head_dim), jnp.bfloat16)
    c = _compile(paged_attention, s((B, hq, head_dim), jnp.bfloat16),
                 pool, pool, s((B, nc), jnp.int32), s((B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_matmul_tuner_largest_block_compiles(one_chip, no_compile_cache):
    # the largest block of the tuner's grid needs more than the compiler's
    # default scoped VMEM; the kernel raises the limit to the target budget
    s = _on(one_chip)
    c = _compile(lambda a, b: matmul(a, b, block=Block(512, 1024, 2048)),
                 s((1024, 2048), jnp.bfloat16), s((2048, 4096), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_moe_gmm_granite_experts_compiles(one_chip, no_compile_cache):
    cfg = get_config("granite_moe_1b_a400m")
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    s = _on(one_chip)
    c = _compile(lambda x, w: moe_gmm(x, w, block=Block(128, 256, 256)),
                 s((E, 128, d), jnp.bfloat16), s((E, d, f), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def _qwen3_decode_step(monkeypatch, place, replicated):
    """Lower the full-width qwen3 paged decode step (B=8, max_seq 2048)
    on its Pallas branch; ``place(tree, pspecs)`` shards the shapes."""
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "pallas")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = get_config("qwen3_1_7b")
    model = Model(cfg)
    B, max_seq, bs = 8, 2048, 16
    nc = max_seq // bs
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)), "params")
    pools = place(jax.eval_shape(
        lambda: init_paged_pools(model, RESERVED_BLOCKS + B * nc, bs)),
        "pools")
    s = _on(replicated)
    step = jax.jit(model.decode_step_paged, donate_argnums=2)
    return step.lower(params, s((B, 1), jnp.int32), pools,
                      s((B, nc), jnp.int32), s((), jnp.int32)).compile()


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_qwen3_paged_decode_step_compiles_full_width(one_chip,
                                                     no_compile_cache,
                                                     monkeypatch):
    def place(tree, _):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)
    c = _qwen3_decode_step(monkeypatch, place, one_chip)
    assert "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < V5E_HBM_BYTES


def test_qwen3_tp4_paged_decode_step_compiles(topo, no_compile_cache,
                                              monkeypatch):
    # GSPMD refuses to partition a Mosaic kernel; traced under the mesh
    # (as ShardedServeEngine does) the kernel runs per KV-head shard, and
    # the pools are never gathered around it
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.serve.distributed import _pool_pspecs
    from repro.sharding import rules
    mesh = make_mesh((1, 4), ("data", "model"), topo.devices[:4])

    def place(tree, kind):
        specs = (rules.param_pspecs(tree, mesh) if kind == "params"
                 else _pool_pspecs(tree, mesh))
        return jax.tree.map(lambda x, p: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, p)), tree, specs)
    with jax.set_mesh(mesh):
        c = _qwen3_decode_step(monkeypatch, place, NamedSharding(mesh, P()))
    text = c.as_text()
    assert "tpu_custom_call" in text and "all-gather" not in text
    assert _device_bytes(c) < V5E_HBM_BYTES
