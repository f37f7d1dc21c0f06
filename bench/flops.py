"""Operations and bytes the model's work needs, from its shapes alone.

``model`` is the ``"model"`` section of a configuration file: the
published sizes under the names used below. Only the work the math needs
is counted: active experts only, no padded rows, no masked keys, no
capacity padding. Embedding lookups and norms are not counted.
"""
from __future__ import annotations

from typing import Any, Dict


def _linear_per_token(m: Dict[str, Any]) -> int:
    """Matrix-multiply FLOPs of one token through one layer, outside the
    attention core."""
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attn = 2 * d * hq * hd + 2 * 2 * d * hkv * hd + 2 * hq * hd * d
    if m.get("n_experts", 0):
        ffn = 2 * d * m["n_experts"] + m["top_k"] * 2 * 3 * d * m["moe_d_ff"]
    else:
        ffn = 2 * 3 * d * m["d_ff"]
    return attn + ffn


def attention_core_flops(m: Dict[str, Any], keys: int) -> int:
    """QK^T and PV of one query over ``keys`` positions, one layer."""
    return 4 * m["n_heads"] * m["head_dim"] * keys


def unembed_flops(m: Dict[str, Any]) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def decode_token_flops(m: Dict[str, Any], keys: int) -> int:
    """One decoded token whose query attends ``keys`` positions."""
    per_layer = _linear_per_token(m) + attention_core_flops(m, keys)
    return m["n_layers"] * per_layer + unembed_flops(m)


def prefill_flops(m: Dict[str, Any], plen: int) -> int:
    """One causal prompt of ``plen`` tokens; logits at its last position."""
    causal_keys = plen * (plen + 1) // 2
    return (m["n_layers"] * (plen * _linear_per_token(m)
                             + attention_core_flops(m, causal_keys))
            + unembed_flops(m))


def paged_attention_need(m: Dict[str, Any], keys: int,
                         itemsize: int = 2) -> Dict[str, int]:
    """FLOPs and HBM bytes one query row needs in one layer of the paged
    decode kernel: its K and V over ``keys`` positions, its q, its out."""
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"flops": attention_core_flops(m, keys),
            "bytes": (2 * keys * hkv * hd + 2 * hq * hd) * itemsize}
