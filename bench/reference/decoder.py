"""Plain reference of the served decoders, in float32 at ``HIGHEST``.

Written from the published descriptions, not from the code under test, and
importing nothing of it:

* Qwen3 (hf:Qwen/Qwen3-1.7B): pre-norm decoder; RMSNorm (eps 1e-6);
  grouped-query attention, q head ``h`` reading KV head ``h // (Hq/Hkv)``;
  RMSNorm over ``head_dim`` on q and k before RoPE (rotate-half,
  theta 1e6); softmax scale ``1/sqrt(head_dim)``, causal; SwiGLU MLP
  ``down(silu(gate(x)) * up(x))``; final RMSNorm; logits against the tied
  embedding.
* Granite 3.0 MoE (hf:ibm-granite/granite-3.0-1b-a400m-base): the same
  attention without q/k norms (theta 1e4), and a dropless top-k mixture:
  router logits ``x @ W_r``, the k largest, softmax over those k, and the
  gated sum of the k experts' SwiGLU outputs. Departure: Granite's scalar
  multipliers (embedding, attention, residual, logits) are left out,
  because the served program has none; they change no shape or cost.

A norm weight is ``1 + offset``, the form the weights are stored in. The
model runs layer by layer (a scan over the stacked weights, each layer
cast to float32 inside it) and the logits in chunks of positions, so one
call holds one layer's activations and one chunk's logits.

``fp8=True`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (per-tensor scale to the format's largest value),
the precision below the bfloat16 the configurations serve in.

This module is all the harness knows of the architecture, which a
configuration file names under ``"reference"``: ``shapes`` (the weights
it reads), ``to_program`` (the same arrays in the served params tree),
the work the math needs (``prefill_flops``, ``decode_token_flops``,
``paged_attention_need``) and the forward (``gaps``, ``argmax``). Another
architecture brings a module of its own with the same names.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench import flops

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6
F8_MAX = 448.0
LOGIT_CHUNK = 256
LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "q_norm",
              "k_norm", "router", "w_gate", "w_up", "w_down")


def _q8(x):
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _dot(eq, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, offset):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + EPS)
    return x * (1.0 + offset)


def _rope(x, theta):
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, lw, m, fp8):
    B, S, _ = x.shape
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _rms(x, lw["attn_norm"])
    q = _dot("bsd,dhe->bshe", h, lw["wq"], fp8)
    k = _dot("bsd,dhe->bshe", h, lw["wk"], fp8)
    v = _dot("bsd,dhe->bshe", h, lw["wv"], fp8)
    if m.get("qk_norm"):
        q, k = _rms(q, lw["q_norm"]), _rms(k, lw["k_norm"])
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    q = q.reshape(B, S, hkv, hq // hkv, hd)
    s = _dot("bqhgd,bkhd->bhgqk", q, k, fp8) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]   # (q, k)
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _dot("bhgqk,bkhd->bqhgd", p, v, fp8).reshape(B, S, hq, hd)
    return _dot("bshe,hed->bsd", o, lw["wo"], fp8)


def _mlp(h, lw, m, fp8):
    if not m.get("n_experts"):
        a = jax.nn.silu(_dot("bsd,df->bsf", h, lw["w_gate"], fp8)) \
            * _dot("bsd,df->bsf", h, lw["w_up"], fp8)
        return _dot("bsf,fd->bsd", a, lw["w_down"], fp8)
    E, K = m["n_experts"], m["top_k"]
    logits = _dot("bsd,de->bse", h, lw["router"], fp8)
    top, idx = jax.lax.top_k(logits, K)
    gates = jax.nn.softmax(top, axis=-1)                        # (B, S, K)
    g = jnp.sum(jax.nn.one_hot(idx, E) * gates[..., None], axis=-2)
    a = jax.nn.silu(_dot("bsd,edf->bsef", h, lw["w_gate"], fp8)) \
        * _dot("bsd,edf->bsef", h, lw["w_up"], fp8)
    # every expert runs on every token; the gate is 0 where it was not
    # chosen, so the sum is the dropless top-k mixture
    return _dot("bsef,efd->bsd", a * g[..., None], lw["w_down"], fp8)


def _hidden(w, tokens, m, fp8):
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    layers = {k: w[k] for k in LAYER_KEYS if k in w}

    def body(x, lw):
        lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
        x = x + _attention(x, lw, m, fp8)
        return x + _mlp(_rms(x, lw["mlp_norm"]), lw, m, fp8), None

    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, w["final_norm"].astype(jnp.float32))


def _chunked_logits(w, hidden, fp8, reduce):
    """``reduce(logits_chunk, i)`` over chunks of LOGIT_CHUNK positions."""
    B, S, d = hidden.shape
    n = S // LOGIT_CHUNK
    hc = hidden.reshape(B, n, LOGIT_CHUNK, d).swapaxes(0, 1)
    emb = w["embed"].astype(jnp.float32)

    def one(args):
        h, i = args
        return reduce(_dot("bsd,vd->bsv", h, emb, fp8), i)

    out = jax.lax.map(one, (hc, jnp.arange(n)))
    return out.swapaxes(0, 1).reshape(B, S)


@functools.partial(jax.jit, static_argnames=("mkey",))
def _gaps(w, tokens, targets, mkey):
    m = dict(mkey)
    hidden = _hidden(w, tokens, m, False)
    tc = targets.reshape(targets.shape[0], -1, LOGIT_CHUNK).swapaxes(0, 1)

    def reduce(logits, i):
        t = jax.lax.dynamic_index_in_dim(tc, i, 0, keepdims=False)
        picked = jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
        return jnp.max(logits, -1) - picked

    return _chunked_logits(w, hidden, False, reduce)


@functools.partial(jax.jit, static_argnames=("mkey", "fp8"))
def _argmax(w, tokens, mkey, fp8):
    hidden = _hidden(w, tokens, dict(mkey), fp8)
    return _chunked_logits(w, hidden, fp8,
                           lambda logits, i: jnp.argmax(logits, -1))


def _key(m: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))


def gaps(w, tokens, targets, m):
    """(B, S) float32: the reference's largest logit minus its logit of
    ``targets`` at each position (0 where the target is its first choice).
    ``tokens``/``targets``: (B, S) int32, S a multiple of LOGIT_CHUNK."""
    return _gaps(w, tokens, targets, _key(m))


def argmax(w, tokens, m, fp8=False):
    """(B, S) int32: the token each position puts first."""
    return _argmax(w, tokens, _key(m), fp8)


# -- what the harness reads besides the forward -------------------------------

def shapes(m: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], int, str]]:
    """name -> (shape, fan_in or 0 for a norm offset, dtype kind): every
    weight the forward reads, layers stacked on the leading axis. Kind
    ``f32`` stays float32 (the router, as the program keeps it); ``w`` is
    drawn in the serving dtype."""
    L, d, V = m["n_layers"], m["d_model"], m["vocab_size"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    out = {
        "embed": ((V, d), d, "w"),
        "final_norm": ((d,), 0, "w"),
        "attn_norm": ((L, d), 0, "w"),
        "mlp_norm": ((L, d), 0, "w"),
        "wq": ((L, d, hq, hd), d, "w"),
        "wk": ((L, d, hkv, hd), d, "w"),
        "wv": ((L, d, hkv, hd), d, "w"),
        "wo": ((L, hq, hd, d), hq * hd, "w"),
    }
    if m.get("qk_norm"):
        out["q_norm"] = ((L, hd), 0, "w")
        out["k_norm"] = ((L, hd), 0, "w")
    if m.get("n_experts", 0):
        E, f = m["n_experts"], m["moe_d_ff"]
        out["router"] = ((L, d, E), d, "f32")
        out["w_gate"] = ((L, E, d, f), d, "w")
        out["w_up"] = ((L, E, d, f), d, "w")
        out["w_down"] = ((L, E, f, d), f, "w")
    else:
        F = m["d_ff"]
        out["w_gate"] = ((L, d, F), d, "w")
        out["w_up"] = ((L, d, F), d, "w")
        out["w_down"] = ((L, F, d), F, "w")
    return out


def to_program(w: Dict[str, Any], m: Dict[str, Any]) -> Dict[str, Any]:
    """The same arrays, not copied, in ``repro.models.model``'s params
    tree: one attention block in the block pattern, so every layer is
    scanned under ``stack.pos0`` and the ``tail`` is empty."""
    del m  # one block kind: the tree does not depend on the sizes
    mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if "q_norm" in w:
        mixer["q_scale"], mixer["k_scale"] = w["q_norm"], w["k_norm"]
    ffn = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    if "router" in w:
        ffn["router"] = w["router"]
    return {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
        "stack": {"pos0": {"norm1": {"scale": w["attn_norm"]},
                           "norm2": {"scale": w["mlp_norm"]},
                           "mixer": mixer, "ffn": ffn}},
        "tail": {},
    }


def _linear_per_token(m: Dict[str, Any]) -> int:
    """Matrix-multiply FLOPs of one token through one layer, outside the
    attention core: the projections, and the MLP or the router and the
    ``top_k`` experts the token is routed to."""
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attn = 2 * d * hq * hd + 2 * 2 * d * hkv * hd + 2 * hq * hd * d
    if m.get("n_experts", 0):
        ffn = 2 * d * m["n_experts"] + m["top_k"] * 2 * 3 * d * m["moe_d_ff"]
    else:
        ffn = 2 * 3 * d * m["d_ff"]
    return attn + ffn


def decode_token_flops(m: Dict[str, Any], keys: int) -> int:
    """One decoded token whose query attends ``keys`` positions in every
    layer."""
    per_layer = _linear_per_token(m) + flops.attention_core_flops(m, keys)
    return m["n_layers"] * per_layer + flops.unembed_flops(m)


def prefill_flops(m: Dict[str, Any], plen: int) -> int:
    """One causal prompt of ``plen`` tokens; logits at its last position."""
    return (m["n_layers"] * (plen * _linear_per_token(m)
                             + flops.attention_core_flops(
                                 m, flops.causal_keys(plen)))
            + flops.unembed_flops(m))


def paged_attention_need(m: Dict[str, Any], keys: int) -> Dict[str, int]:
    """FLOPs and HBM bytes one query row needs from the paged decode
    kernel over every layer, each attending ``keys`` positions."""
    need = flops.attention_row_need(m, keys)
    return {k: m["n_layers"] * v for k, v in need.items()}
