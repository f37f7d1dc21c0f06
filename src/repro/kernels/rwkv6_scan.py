"""RWKV-6 WKV recurrence Pallas kernel.

State S (D x D per head) lives in VMEM for the entire sequence:
grid (B*H, S/bs) with the sequence dim minor. Each grid step loads a
(bs, D) tile of r/k/v/w, runs bs recurrence steps with the state resident
(outer products + row scaling on the VPU/MXU), writes the (bs, D) output
tile. The naive XLA scan ships the (D, D) state through HBM every token —
this kernel ships it never.

Validated with interpret=True against ref.rwkv6_scan_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.matmul import vmem


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref, s_ref,
                *, bs: int, n_heads: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)   # (bs, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0, 0].astype(jnp.float32)   # (D,) for this head

    def body(t, carry):
        s, out = carry                  # s: (D, D)
        kv = k[t][:, None] * v[t][None, :]          # (D, D)
        o = jnp.sum(r[t][:, None] * (s + u[:, None] * kv), axis=0)
        s = w[t][:, None] * s + kv
        out = out.at[t].set(o)
        return s, out

    s_fin, out = jax.lax.fori_loop(
        0, bs, body,
        (s_ref[...], jnp.zeros((bs, r.shape[1]), jnp.float32)))
    o_ref[0] = out.astype(o_ref.dtype)
    s_ref[...] = s_fin
    s_out_ref[0] = s_fin


def rwkv6_scan(r, k, v, w, u, *, bs: int = 64, interpret: bool = False):
    """r,k,v,w: (B, S, H, D); u: (H, D). Returns (o, s_last (B,H,D,D))."""
    B, S, H, D = r.shape
    bs = min(bs, S)
    ps = (-S) % bs

    def to_bh(x):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
        if ps:
            x = jnp.pad(x, ((0, 0), (0, ps), (0, 0)))
        return x

    rt, kt, vt = to_bh(r), to_bh(k), to_bh(v)
    # pad decay with ones so padded steps keep the state unchanged
    wt = to_bh(w)
    if ps:
        wt = wt.at[:, S:].set(1.0)
    grid = (B * H, (S + ps) // bs)

    o, s_last = pl.pallas_call(
        functools.partial(_wkv_kernel, bs=bs, n_heads=H),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, bs, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, bs, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, bs, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, 1, D), lambda bh, s: (bh % H, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, D, D), lambda bh, s: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S + ps, D), r.dtype),
            jax.ShapeDtypeStruct((B * H, D, D), jnp.float32),
        ],
        scratch_shapes=[vmem((D, D), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, u.reshape(H, 1, D))
    o = o[:, :S].reshape(B, H, S, D).transpose(0, 2, 1, 3)
    return o, s_last.reshape(B, H, D, D)
