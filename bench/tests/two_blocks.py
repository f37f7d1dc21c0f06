"""A test architecture that comes as a file alone: ``decoder``'s layers
served under a block pattern of two attention kinds.

``bench/tests/tiny.py`` copies this file into a checkout's
``bench/reference/``, where a configuration names it. With an odd number
of layers the served params tree has two block positions (``stack.pos0``
and ``stack.pos1``, the pattern's periods stacked) and a ``tail`` layer;
the forward, the weights' names and the work counts are ``decoder``'s,
since both kinds attend every earlier position.
"""
from __future__ import annotations

import pathlib
from typing import Any, Dict

import jax

from bench import spec

decoder = spec.reference_module("decoder",
                                pathlib.Path(__file__).resolve().parents[1])

LOGIT_CHUNK = decoder.LOGIT_CHUNK
gaps, argmax, shapes = decoder.gaps, decoder.argmax, decoder.shapes
prefill_flops = decoder.prefill_flops
decode_token_flops = decoder.decode_token_flops
paged_attention_need = decoder.paged_attention_need

#: block positions in the pattern
PERIOD = 2


def to_program(w: Dict[str, Any], m: Dict[str, Any]) -> Dict[str, Any]:
    """Layer ``i * PERIOD + p`` of period ``i`` under ``stack.pos<p>``;
    the layers after the last whole period under ``tail``, one each."""
    tree = decoder.to_program(w, m)
    layers = tree["stack"].pop("pos0")
    n_periods = m["n_layers"] // PERIOD
    whole = n_periods * PERIOD
    for p in range(PERIOD):
        tree["stack"][f"pos{p}"] = jax.tree.map(
            lambda x, p=p: x[p:whole:PERIOD], layers)
    tree["tail"] = {str(i): jax.tree.map(lambda x, i=i: x[whole + i], layers)
                    for i in range(m["n_layers"] - whole)}
    return tree
