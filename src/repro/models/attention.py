"""Attention: GQA / MQA / MHA, causal / bidirectional / sliding-window.

Two execution paths:

* ``blockwise_attention`` — memory-efficient online-softmax attention written
  in pure jnp + lax.scan (never materializes the (S, S) score matrix). This is
  the XLA path used by the distributed dry-run (Pallas does not lower to the
  CPU backend) and the numerical oracle for the Pallas flash kernel.
* ``repro.kernels.flash_attention`` — the Pallas TPU kernel (same math).

KV cache layout (decode): a *rolling* cache of ``cache_len`` slots with an
absolute-position side array, which unifies full attention
(cache_len == seq_len, never wraps) and sliding-window attention
(cache_len == window, wraps) in one code path.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Rolling KV cache for one attention stack.

    k, v: (B, cache_len, n_kv, head_dim) — written at slot ``pos % cache_len``.
    slot_pos: (cache_len,) int32 absolute position held by each slot (-1 empty).
    """

    k: jax.Array
    v: jax.Array
    slot_pos: jax.Array

    @staticmethod
    def init(batch: int, cache_len: int, n_kv: int, head_dim: int, dtype) -> "KVCache":
        return KVCache(
            k=jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
            v=jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
            slot_pos=jnp.full((cache_len,), -1, jnp.int32),
        )


def cache_len_for(seq_len: int, window: int) -> int:
    return seq_len if window <= 0 else min(window, seq_len)


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
               window: int) -> jax.Array:
    """Additive mask bias (0 or NEG_INF). q_pos: (..., Sq), k_pos: (..., Sk)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = kp >= 0
    if causal:
        ok &= kp <= qp
    if window > 0:
        ok &= kp > qp - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention in pure jnp — the XLA path + kernel oracle
# ---------------------------------------------------------------------------

def blockwise_attention(
    q: jax.Array,               # (B, Sq, Hq, D)
    k: jax.Array,               # (B, Sk, Hkv, D)
    v: jax.Array,               # (B, Sk, Hkv, D)
    *,
    causal: bool,
    window: int = 0,
    q_positions: Optional[jax.Array] = None,   # (Sq,) absolute positions
    k_positions: Optional[jax.Array] = None,   # (Sk,)
    q_block: int = 512,
    k_block: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention, O(Sq·D + block²) memory. Returns (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5

    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)
    if k_positions is None:
        k_positions = jnp.arange(Sk, dtype=jnp.int32)

    q_block = min(q_block, Sq)
    k_block = min(k_block, Sk)
    # Pad sequence dims to block multiples.
    pq = (-Sq) % q_block
    pk = (-Sk) % k_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=-(10 ** 9))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pk), constant_values=-1)
    nq, nk = (Sq + pq) // q_block, (Sk + pk) // k_block

    # (B, nq, bq, Hkv, g, D) — inputs stay in model dtype (bf16); blocks are
    # upcast inside the scan body only (keeps the big resharded/gathered
    # arrays half-width; the f32 math happens on block-sized tiles).
    qb = q.reshape(B, nq, q_block, Hkv, g, D)
    kb = k.reshape(B, nk, k_block, Hkv, D)
    vb = v.reshape(B, nk, k_block, Hkv, D)
    qpb = q_positions.reshape(nq, q_block)
    kpb = k_positions.reshape(nk, k_block)

    # jax.checkpoint = flash-attention backward: nothing from the inner
    # online-softmax scan is saved between fwd and bwd; the kv sweep is
    # recomputed per q-chunk during the backward pass. Without it, autodiff
    # saves the (B, bq, H, g, D) accumulator for EVERY kv block.
    @jax.checkpoint
    def q_chunk(qi, qp):
        # qi: (B, bq, Hkv, g, D); qp: (bq,)
        m0 = jnp.full((B, q_block, Hkv, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_block, Hkv, g), jnp.float32)
        a0 = jnp.zeros((B, q_block, Hkv, g, D), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, vj, kp = inp                      # (B, bk, Hkv, D), (bk,)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            s = s + _mask_bias(qp, kp, causal=causal, window=window)[
                None, :, None, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kpb))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(lambda args: q_chunk(*args), (qb.swapaxes(0, 1), qpb))
    out = out.swapaxes(0, 1).reshape(B, nq * q_block, Hq, D)[:, :Sq]
    return out.astype(q.dtype)


def direct_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool, window: int = 0,
    q_positions: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Materialized-score attention (decode path, Sq small)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)
    if k_positions is None:
        k_positions = jnp.arange(Sk, dtype=jnp.int32)
    qf = q.reshape(B, Sq, Hkv, g, D).astype(jnp.float32) * scale
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, k.astype(jnp.float32))
    s = s + _mask_bias(q_positions, k_positions, causal=causal, window=window)[
        None, :, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention layer (projections + rope + qk-norm) — train/prefill and decode
# ---------------------------------------------------------------------------

def qkv_project(params: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_scale"])
        k = layers.rms_norm(k, params["k_scale"])
    return q, k, v


def attention_block(params: dict, x: jax.Array, cfg, *, positions,
                    window: int) -> jax.Array:
    """Full-sequence attention (train / prefill). x: (B, S, d)."""
    q, k, v = qkv_project(params, x, cfg)
    if cfg.rope != "none":
        q = layers.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
        k = layers.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    seq_pos = None  # blockwise uses iota positions; mrope handled in projections
    out = blockwise_attention(q, k, v, causal=cfg.causal, window=window)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


def attention_decode(params: dict, x: jax.Array, cache: KVCache, cfg, *,
                     pos: jax.Array, positions, window: int
                     ) -> Tuple[jax.Array, KVCache]:
    """One-token decode. x: (B, 1, d); pos: scalar int32 absolute position."""
    q, k, v = qkv_project(params, x, cfg)
    if cfg.rope != "none":
        q = layers.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
        k = layers.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    cache_len = cache.k.shape[1]
    slot = (pos % cache_len).astype(jnp.int32)
    k_new = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                         (0, slot, 0, 0))
    v_new = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                         (0, slot, 0, 0))
    slot_pos = cache.slot_pos.at[slot].set(pos.astype(jnp.int32))
    out = direct_attention(
        q, k_new, v_new, causal=cfg.causal, window=window,
        q_positions=pos[None].astype(jnp.int32),
        k_positions=slot_pos)
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return out, KVCache(k_new, v_new, slot_pos)


def fill_cache_from_prefill(k: jax.Array, v: jax.Array, cache_len: int,
                            dtype) -> KVCache:
    """Build the rolling cache holding the last ``cache_len`` of S tokens.

    Slot s holds token t(s) = s + cache_len * floor((S-1-s)/cache_len) —
    the last token whose index ≡ s (mod cache_len). Deterministic gather.
    """
    B, S, Hkv, D = k.shape
    s_idx = jnp.arange(cache_len, dtype=jnp.int32)
    t_idx = s_idx + cache_len * ((S - 1 - s_idx) // cache_len)
    valid = t_idx < S  # always true when cache_len <= S
    t_gather = jnp.clip(t_idx, 0, S - 1)
    return KVCache(
        k=jnp.take(k, t_gather, axis=1).astype(dtype),
        v=jnp.take(v, t_gather, axis=1).astype(dtype),
        slot_pos=jnp.where(valid, t_idx, -1),
    )


def attention_prefill(params: dict, x: jax.Array, cfg, *, positions,
                      window: int, cache_len: int
                      ) -> Tuple[jax.Array, KVCache]:
    """Full-sequence attention that also returns the rolling cache."""
    q, k, v = qkv_project(params, x, cfg)
    if cfg.rope != "none":
        q = layers.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
        k = layers.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    out = blockwise_attention(q, k, v, causal=cfg.causal, window=window)
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    cache = fill_cache_from_prefill(k, v, cache_len, k.dtype)
    return out, cache


# ---------------------------------------------------------------------------
# Paged attention layer — decode and chunked prefill through a block table
# ---------------------------------------------------------------------------

def _use_paged_kernel() -> bool:
    """Pallas on TPU, XLA gather+``direct_attention`` elsewhere (the same
    math). Overridable with REPRO_PAGED_BACKEND=pallas|xla for kernel
    testing: forced ``pallas`` off the TPU runs the kernel in interpret
    mode (see :func:`repro.kernels.ops.interpret_mode`)."""
    import os
    forced = os.environ.get("REPRO_PAGED_BACKEND", "auto")
    if forced == "pallas":
        return True
    if forced == "xla":
        return False
    return jax.default_backend() == "tpu"


def _paged_kernel(n_kv: int):
    """The Pallas paged-decode kernel, fitted to the mesh in context.

    A Mosaic kernel cannot be partitioned by GSPMD, so under a mesh with
    a ``model`` axis (``jax.set_mesh``, as ``ShardedServeEngine`` traces
    its decode step) the kernel runs per shard through ``shard_map``:
    each device attends its own KV heads and the q heads of their groups,
    the axis the pools are sharded on. Heads the axis does not divide
    stay replicated, as the pools do."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.kernels.ops import interpret_mode
    from repro.kernels.paged_attention import paged_attention
    kernel = partial(paged_attention, interpret=interpret_mode())
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get("model", 1) == 1:
        return kernel
    heads = "model" if n_kv % mesh.shape["model"] == 0 else None
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(None, heads, None), P(None, None, heads, None),
                  P(None, None, heads, None), P(), P()),
        out_specs=P(None, heads, None), check_vma=False)


def _paged_attend(q: jax.Array, pool: "PagedKVCache", table: jax.Array,
                  pos: jax.Array) -> jax.Array:
    """Decode attention over pool blocks. q: (B, 1, Hq, D); table: (B, nc)
    int32; pos: scalar absolute position of the (already written) query
    token. Slot (c, o) of a row holds absolute position c*bs + o, so the
    causal mask alone rejects every not-yet-written slot — including the
    zero block behind padded table columns."""
    B, _, Hq, D = q.shape
    bs = pool.k.shape[-3]
    nc = table.shape[1]
    if _use_paged_kernel():
        lens = jnp.broadcast_to(pos.astype(jnp.int32) + 1, (B,))
        out = _paged_kernel(pool.k.shape[-2])(q[:, 0], pool.k, pool.v,
                                              table, lens)
        return out[:, None]
    kg = pool.k[table].reshape(B, nc * bs, *pool.k.shape[-2:])
    vg = pool.v[table].reshape(B, nc * bs, *pool.v.shape[-2:])
    return direct_attention(
        q, kg, vg, causal=True,
        q_positions=pos[None].astype(jnp.int32),
        k_positions=jnp.arange(nc * bs, dtype=jnp.int32))


def attention_decode_paged(params: dict, x: jax.Array, pool: "PagedKVCache",
                           cfg, *, pos: jax.Array, positions,
                           table: jax.Array):
    """One-token decode writing/reading KV through the block table.

    x: (B, 1, d); pool k/v: (n_blocks, bs, Hkv, D) shared across rows;
    table: (B, nc) int32. The new K/V lands in block ``table[b, pos//bs]``
    at offset ``pos % bs`` (pad rows' tables point that column at the
    scratch block)."""
    from repro.models.paged_cache import PagedKVCache
    q, k, v = qkv_project(params, x, cfg)
    if cfg.rope != "none":
        q = layers.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
        k = layers.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    bs = pool.k.shape[1]
    col = (pos // bs).astype(jnp.int32)
    off = (pos % bs).astype(jnp.int32)
    bids = jax.lax.dynamic_index_in_dim(table, col, axis=1, keepdims=False)
    k_new = pool.k.at[bids, off].set(k[:, 0].astype(pool.k.dtype))
    v_new = pool.v.at[bids, off].set(v[:, 0].astype(pool.v.dtype))
    new_pool = PagedKVCache(k_new, v_new)
    out = _paged_attend(q, new_pool, table, pos)
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return out, new_pool


def attention_decode_paged_gathered(params: dict, x: jax.Array,
                                    kg: jax.Array, vg: jax.Array, cfg, *,
                                    pos: jax.Array, positions):
    """One-token decode over *pre-gathered* paged KV (XLA fallback path).

    kg/vg: (B, nc*bs, Hkv, D) — the row's table-gathered KV as of *before*
    this step. Carrying whole pools through ``lax.scan`` double-buffers
    them (a full pool copy per layer per step), so on the XLA path the
    caller gathers once outside the scan and this layer only *reads*: the
    fresh K/V is appended at attend time (its stale pool slot masked with
    position -1, which ``_mask_bias`` always rejects) and returned so the
    caller can scatter every layer's new row with one post-scan update."""
    q, k, v = qkv_project(params, x, cfg)
    if cfg.rope != "none":
        q = layers.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
        k = layers.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    k1 = k[:, 0].astype(kg.dtype)
    v1 = v[:, 0].astype(vg.dtype)
    iota = jnp.arange(kg.shape[1], dtype=jnp.int32)
    kpos = jnp.where(iota == pos, jnp.int32(-1), iota)
    out = direct_attention(
        q, jnp.concatenate([kg, k1[:, None]], axis=1),
        jnp.concatenate([vg, v1[:, None]], axis=1), causal=True,
        q_positions=pos[None].astype(jnp.int32),
        k_positions=jnp.concatenate([kpos, pos[None].astype(jnp.int32)]))
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return out, (k1, v1)


def attention_prefill_chunk_paged_gathered(params: dict, x: jax.Array,
                                           kg: jax.Array, vg: jax.Array,
                                           cfg, *, start: jax.Array,
                                           positions):
    """One chunk of a paged prefill over pre-gathered KV (same pool-copy
    avoidance as :func:`attention_decode_paged_gathered`). Gathered slots
    at/after ``start`` are this chunk's own stale storage — masked with
    position -1 — and the chunk's fresh K/V is appended at positions
    ``start + [0, C)``; the caller scatters the returned chunk K/V into
    the pools after the layer scan."""
    q, k, v = qkv_project(params, x, cfg)
    if cfg.rope != "none":
        q = layers.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
        k = layers.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    C = x.shape[1]
    kc = k.astype(kg.dtype)
    vc = v.astype(vg.dtype)
    iota = jnp.arange(kg.shape[1], dtype=jnp.int32)
    kpos = jnp.where(iota < start, iota, jnp.int32(-1))
    qpos = (start + jnp.arange(C)).astype(jnp.int32)
    out = direct_attention(
        q, jnp.concatenate([kg, kc], axis=1),
        jnp.concatenate([vg, vc], axis=1), causal=True,
        q_positions=qpos, k_positions=jnp.concatenate([kpos, qpos]))
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return out, (kc, vc)


def attention_prefill_chunk_paged(params: dict, x: jax.Array,
                                  pool: "PagedKVCache", cfg, *,
                                  start: jax.Array, positions,
                                  table: jax.Array):
    """One chunk of a paged prefill. x: (B, C, d) with C a multiple of the
    block size and ``start`` (the chunk's first absolute position) a
    block multiple; writes the chunk's C/bs blocks through the table and
    attends causally over everything written so far. Chunk-padding tokens
    past the prompt land at positions the causal mask hides from every
    real query, and decode overwrites them before they become visible."""
    from repro.models.paged_cache import PagedKVCache
    q, k, v = qkv_project(params, x, cfg)
    if cfg.rope != "none":
        q = layers.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
        k = layers.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    B, C = x.shape[0], x.shape[1]
    bs = pool.k.shape[1]
    nc = table.shape[1]
    ncb = C // bs
    c0 = (start // bs).astype(jnp.int32)
    bids = jax.lax.dynamic_slice_in_dim(table, c0, ncb, axis=1)  # (B, ncb)
    k_new = pool.k.at[bids].set(
        k.reshape(B, ncb, bs, *k.shape[-2:]).astype(pool.k.dtype))
    v_new = pool.v.at[bids].set(
        v.reshape(B, ncb, bs, *v.shape[-2:]).astype(pool.v.dtype))
    kg = k_new[table].reshape(B, nc * bs, *k_new.shape[-2:])
    vg = v_new[table].reshape(B, nc * bs, *v_new.shape[-2:])
    out = direct_attention(
        q, kg, vg, causal=True,
        q_positions=(start + jnp.arange(C)).astype(jnp.int32),
        k_positions=jnp.arange(nc * bs, dtype=jnp.int32))
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return out, PagedKVCache(k_new, v_new)


def init_attention_params(key, cfg, dtype) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, hq, hd), dtype, fan_in=d),
        "wk": layers.dense_init(ks[1], (d, hkv, hd), dtype, fan_in=d),
        "wv": layers.dense_init(ks[2], (d, hkv, hd), dtype, fan_in=d),
        "wo": layers.dense_init(ks[3], (hq, hd, d), dtype, fan_in=hq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), dtype)
        p["bk"] = jnp.zeros((hkv, hd), dtype)
        p["bv"] = jnp.zeros((hkv, hd), dtype)
    if cfg.qk_norm:
        p["q_scale"] = jnp.zeros((hd,), dtype)
        p["k_scale"] = jnp.zeros((hd,), dtype)
    return p
