"""Operations and bytes of attention, the part of the work that the
architecture modules under ``bench/reference/`` share.

``m`` is the ``"model"`` section of a configuration file. Only the work
the math needs is counted: no padded rows, no masked keys. Each module's
``prefill_flops``, ``decode_token_flops`` and ``paged_attention_need``
add these up over its own layers.
"""
from __future__ import annotations

from typing import Any, Dict


def attention_core_flops(m: Dict[str, Any], keys: int) -> int:
    """QK^T and PV of one query over ``keys`` positions, one layer."""
    return 4 * m["n_heads"] * m["head_dim"] * keys


def causal_keys(plen: int) -> int:
    """Keys a causal prompt of ``plen`` tokens attends, over its queries."""
    return plen * (plen + 1) // 2


def unembed_flops(m: Dict[str, Any]) -> int:
    """Logits of one position against the vocabulary."""
    return 2 * m["d_model"] * m["vocab_size"]


def attention_row_need(m: Dict[str, Any], keys: int,
                       itemsize: int = 2) -> Dict[str, int]:
    """FLOPs and HBM bytes one query row needs in one layer of the paged
    decode kernel: its K and V over ``keys`` positions, its q, its out."""
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"flops": attention_core_flops(m, keys),
            "bytes": (2 * keys * hkv * hd + 2 * hq * hd) * itemsize}
