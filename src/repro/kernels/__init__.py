"""Pallas TPU kernels (validated in interpret mode on CPU; compiled for a
described TPU v5e by tests/test_tpu_compile.py).

matmul          — tiled GEMM, configurable BlockSpec (the tuner's target)
flash_attention — blocked online-softmax attention (causal/SWA/GQA)
paged_attention — single-token decode attention through a KV block table
rglru_scan      — RG-LRU linear recurrence, state resident in VMEM
rwkv6_scan      — RWKV-6 WKV recurrence, (D,D) state resident in VMEM
moe_gmm         — grouped expert GEMM (MegaBlocks-style, TPU pipeline)
ops             — jit'd public wrappers; ref — pure-jnp oracles
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
