"""Seeded random weights, made on the device in one jitted call.

The weights live in one flat dict under the names the plain references
read; ``to_program`` re-keys the same arrays into the tree
``repro.models.model`` serves from, without copying them. Each leaf is
drawn from its own key (the seed folded with the leaf's name), scaled by
``1/sqrt(fan_in)``, in the configuration's serving dtype. Norm weights
are stored as offsets from 1 (``w = 1 + offset``), as the program keeps
them; the router stays float32, as the program keeps it.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_OFFSET_STD = 0.1


def jax_seed(seed: int, stream: str) -> int:
    """A 31-bit key seed from any whole number (``PRNGKey`` keeps only 32
    bits of what it is given)."""
    ss = np.random.SeedSequence([int(seed), *stream.encode()])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def shapes(m: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], int, str]]:
    """name -> (shape, fan_in or 0 for a norm offset, dtype kind)."""
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


def make(m: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight of the model, from ``seed``, in one jitted call, in the
    dtype the configuration serves in."""
    spec = shapes(m)
    dtype = jnp.dtype(m["dtype"])

    def build(key):
        out = {}
        for name, (shape, fan_in, kind) in spec.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            std = fan_in ** -0.5 if fan_in else NORM_OFFSET_STD
            x = jax.random.normal(k, shape, jnp.float32) * std
            out[name] = x if kind == "f32" else x.astype(dtype)
        return out

    key = jax.random.PRNGKey(jax_seed(seed, "weights"))
    return jax.jit(build)(key)


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    """The same arrays in ``repro.models.model``'s params tree for a model
    whose block pattern is one attention block (every layer scanned)."""
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
