"""Seeded random weights, made on the device in one jitted call.

The weights live in one flat dict under the names the configuration's
reference module reads: its ``shapes(m)`` lists them, and its
``to_program(w, m)`` re-keys the same arrays into the tree
``repro.models.model`` serves from. Each leaf is drawn from its own key
(the seed folded with the leaf's name), scaled by ``1/sqrt(fan_in)``.
Norm weights are stored as offsets from 1 (``w = 1 + offset``), as the
program keeps them.
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


def make(shapes: Dict[str, Tuple[Tuple[int, ...], int, str]], dtype: str,
         seed: int) -> Dict[str, Any]:
    """Every weight that ``shapes`` lists (a reference module's
    ``shapes(m)``: name -> (shape, fan_in or 0 for a norm offset, kind)),
    from ``seed``, in one jitted call: kind ``f32`` in float32, every
    other kind in ``dtype``, the dtype the configuration serves in."""
    dtype = jnp.dtype(dtype)

    def build(key):
        out = {}
        for name, (shape, fan_in, kind) in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            std = fan_in ** -0.5 if fan_in else NORM_OFFSET_STD
            x = jax.random.normal(k, shape, jnp.float32) * std
            out[name] = x if kind == "f32" else x.astype(dtype)
        return out

    key = jax.random.PRNGKey(jax_seed(seed, "weights"))
    return jax.jit(build)(key)
