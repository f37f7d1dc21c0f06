"""Tiled matmul Pallas kernel — the tuner's target program.

The block config (bm, bk, bn) IS the "program structure" CPrune preserves:
the grid iterates (M/bm, N/bn, K/bk) with K minor (sequential accumulation
into a VMEM fp32 scratch tile). Pruning in multiples of bn (N) / bk (K)
removes whole grid steps without re-shaping any block.

TPU target: MXU-aligned blocks (bm mult of 8, bk/bn mult of 128), inputs
double-buffered by the Pallas pipeline, fp32 accumulator in VMEM, scoped
VMEM raised to the target budget (:func:`vmem_limit`).
Validated on CPU with interpret=True against ref.matmul_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cost_model import Block


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, block: Block,
           out_dtype=None, interpret: bool = False) -> jax.Array:
    """[M, K] x [K, N] with the given block config. Pads to block multiples."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bk, bn = block.bm, block.bk, block.bn
    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    grid = (Mp // bm, Np // bn, Kp // bk)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[vmem((bm, bn), jnp.float32)],
        compiler_params=vmem_limit(),
        interpret=interpret,
    )(a, b)
    return out[:M, :N]


def vmem(shape, dtype):
    """VMEM scratch allocation (TPU); interpret mode emulates it on CPU."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def vmem_limit():
    """Mosaic compiler params whose scoped VMEM limit is the ``tpu_v5e``
    budget (``TargetSpec.vmem_bytes``) that the tuner's candidate filter
    and the K003 check size blocks against — so every block they admit
    compiles, not only those under the compiler's smaller default."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.api.targets import TPU_V5E
    return pltpu.CompilerParams(vmem_limit_bytes=TPU_V5E.vmem_bytes)
