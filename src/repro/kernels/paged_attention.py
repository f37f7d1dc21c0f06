"""Paged decode attention Pallas kernel: single-token queries reading K/V
through a block table (vLLM-style), online-softmax.

Grid: (B, n_cols) with the block-table column minor. The table and the
per-row sequence lengths ride in as scalar-prefetch operands
(``PrefetchScalarGridSpec``) so the KV BlockSpec index map can chase the
indirection — grid step (b, ki) DMAs pool block ``table[b, ki]`` for every
KV head at once; the pool itself never moves. Running max / sum /
accumulator live in VMEM scratch across column steps, exactly the
``flash_attention`` schedule with the KV walk order given by the table.

Every block spans the full trailing two dims of its array — q ``(1, Hq,
D)``, K/V ``(1, bs, Hkv, D)`` — which is what the TPU lowering requires of
a block whose head count is not a multiple of 8. Inside the body the
block's ``(bs, Hkv)`` rows flatten to ``bs * Hkv`` keys (row ``t * Hkv +
h``) and one matmul scores every q head against all of them; a key of
another KV head than the q head's group (``h != q_head // (Hq // Hkv)``)
is masked like a future position. That spends ``Hkv`` times the MXU work
of a per-head walk on masked keys.

Numerics match ``blockwise_attention`` / ``ref.paged_attention_ref`` (the
oracle); positions are implicit — slot (c, o) holds absolute position
c * block_size + o, so masking ``c*bs + o >= seq_len`` is the causal mask.

``seq_lens`` must be >= 1 everywhere (a decode query always has at least
its own freshly written position; an all-masked *first* column would poison
the running max).

Validated with interpret=True against ref.paged_attention_ref, and compiled
for TPU v5e by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import vmem

NEG_INF = -1e30


def _pa_kernel(tbl_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, scale: float, bs: int, n_kv: int,
               group: int, n_c: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale                  # (Hq, D)
    n_q, d = q.shape
    k = k_ref[0].astype(jnp.float32).reshape(bs * n_kv, d)    # (bs*Hkv, D)
    v = v_ref[0].astype(jnp.float32).reshape(bs * n_kv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # key row t*Hkv + h is position ki*bs + t of KV head h; everything at
    # or past seq_len is unwritten (zero block, pad garbage, future slots)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_q, bs * n_kv), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_q, bs * n_kv), 1)
    live = ((col % n_kv == row // group)
            & (ki * bs + col // n_kv < lens_ref[b]))
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[...][:, :1]                                # (Hq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...][:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_c - 1)
    def _flush():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, seq_lens: jax.Array, *,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); k/v_pool: (n_blocks, bs, Hkv, D);
    block_table: (B, n_cols) int32; seq_lens: (B,) int32 (>= 1).
    Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    _, bs, Hkv, _ = k_pool.shape
    n_c = block_table.shape[1]
    scale = scale if scale is not None else D ** -0.5

    kv_spec = pl.BlockSpec((1, bs, Hkv, D),
                           lambda b, ki, tbl, lens: (tbl[b, ki], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_c),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, ki, tbl, lens: (b, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, ki, tbl, lens: (b, 0, 0)),
        scratch_shapes=[
            vmem((Hq, 128), jnp.float32),   # running max (lane-replicated)
            vmem((Hq, 128), jnp.float32),   # running sum
            vmem((Hq, D), jnp.float32),     # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_pa_kernel, scale=scale, bs=bs, n_kv=Hkv,
                          group=Hq // Hkv, n_c=n_c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, k_pool, v_pool)
