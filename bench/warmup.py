"""Set-up's warm-up: run every program shape the cell's traffic can make.

The engine compiles one prefill per (cohort width U, prompt length) and
one decode per (cohort width W, block-table columns rounded up to a power
of two); its sampling, token reads and compaction run as eager ops, one
program per width. ``plan`` lists cohorts that, served through the
engine's own ``submit``/``step``, touch each such shape once:

* for every prompt length L of the mix and every width W in
  1..max_batch, W requests of length L that decode one token: prefill
  (W, L), and the decode (W, columns at position L);
* decode column counts that the mix reaches only later in a request
  (a position past the first one), for every width, by a request long
  enough to get there;
* for every width W and every power of two P below it, a cohort in which
  W - P requests stop after one decode and P go on: compaction W -> P.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from bench.traffic import support


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def decode_columns(plen: int, pos: int, page: int) -> int:
    """Power-of-two column count of the block table a decode step at
    ``pos`` runs with, for a prompt of ``plen`` tokens."""
    return _pow2(max(-(-plen // page), pos // page + 1))


def plan(mix: Dict[str, Any], max_batch: int, page: int
         ) -> List[Tuple[int, List[int]]]:
    """Cohorts as (prompt length, max_new_tokens per request)."""
    lengths = support(mix["prompt_len"])
    max_out = max(support(mix["output_len"]))
    cohorts: List[Tuple[int, List[int]]] = []
    first = set()
    for L in lengths:
        for W in range(1, max_batch + 1):
            cohorts.append((L, [2] * W))
            first.add(decode_columns(L, L, page))
    # a decode at position pos makes output token pos - L + 1
    for L in lengths:
        for pos in range(L, L + max_out - 1):
            cols = decode_columns(L, pos, page)
            if cols not in first:
                first.add(cols)
                cohorts += [(L, [pos - L + 2] * W)
                            for W in range(1, max_batch + 1)]
    L = min(lengths)
    for W in range(2, max_batch + 1):
        P = 1
        while P < W:
            cohorts.append((L, [2] * (W - P) + [3] * P))
            P *= 2
    return cohorts


def run(engine, make_request, cohorts: List[Tuple[int, List[int]]],
        vocab: int, seed: int) -> int:
    """Serve each cohort alone until it drains; returns the steps taken."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    steps = 0
    for L, news in cohorts:
        for n in news:
            prompt = rng.integers(0, vocab, L, dtype=np.int32)
            engine.submit(make_request(prompt, n))
        while engine.has_work:
            engine.step()
            steps += 1
    return steps
