"""Grouped expert GEMM Pallas kernel: (E, C, K) x (E, K, N) -> (E, C, N).

The MoE hot path after capacity dispatch. Grid (E, C/bm, N/bn, K/bk) with K
minor; expert weights stream through VMEM once per (C-block, N-block), the
fp32 accumulator lives in VMEM scratch. This is MegaBlocks' grouped GEMM
adapted to the TPU pipeline (dense per-expert tiles instead of CUDA
block-sparse descriptors).

Validated with interpret=True against ref.moe_gmm_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cost_model import Block
from repro.kernels.matmul import vmem, vmem_limit


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm(x: jax.Array, w: jax.Array, *, block: Block = Block(128, 256, 256),
            interpret: bool = False) -> jax.Array:
    """x: (E, C, K); w: (E, K, N) -> (E, C, N)."""
    E, C, K = x.shape
    _, _, N = w.shape
    bm, bk, bn = min(block.bm, C), min(block.bk, K), min(block.bn, N)
    pc, pk, pn = (-C) % bm, (-K) % bk, (-N) % bn
    if pc or pk:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, 0), (0, pk), (0, pn)))
    grid = (E, (C + pc) // bm, (N + pn) // bn, (K + pk) // bk)

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C + pc, N + pn), x.dtype),
        scratch_shapes=[vmem((bm, bn), jnp.float32)],
        compiler_params=vmem_limit(),
        interpret=interpret,
    )(x, w)
    return out[:, :C, :N]
