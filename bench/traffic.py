"""The one traffic generator: reads a mix file and the seed, gives requests.

Every seed gets the same work: lengths and inter-arrival gaps are drawn
by stratification over blocks of ``BLOCK`` requests (exact shares of each
listed length, quantiles of a continuous distribution) and put in an
order fixed by the mix's ``schedule_seed``. The run's ``--seed`` draws the
prompt tokens (and, elsewhere, the weights). A short window holds a few
dozen requests, and the order of long and short ones within it moves a
tail by more than any change worth catching, so the order is part of the
mix and not of the seed.

Mix keys read here:

``loop``            ``open`` (arrivals on a schedule) or ``closed``
``rate_per_s``      open loop: mean arrival rate (Poisson gaps)
``clients``         closed loop: clients, each sending its next request as
                    soon as its previous one completes
``prompt_len``      a length distribution (below)
``output_len``      a length distribution (below)
``schedule_seed``   fixes the order of lengths and gaps

A length distribution is either ``{"values": [...], "weights": [...]}`` or
``{"lognormal": {"median": m, "sigma": s}, "min": a, "max": b}``.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

BLOCK = 64


@dataclasses.dataclass
class RequestSpec:
    plen: int
    max_new: int
    prompt: np.ndarray                 # (plen,) int32
    due_s: Optional[float] = None      # open loop: offset from traffic start


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), *stream.encode()]))


def support(dist: Dict[str, Any]) -> List[int]:
    """Every length the distribution can give."""
    if "values" in dist:
        return sorted(int(v) for v in dist["values"])
    return list(range(int(dist["min"]), int(dist["max"]) + 1))


def stratified(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths in a fixed order: exact shares of each value (largest
    remainder), or the ``(i + 0.5) / n`` quantiles of a log-normal."""
    if "values" in dist:
        vals = np.asarray(dist["values"], np.int64)
        w = np.asarray(dist.get("weights", [1.0] * len(vals)), np.float64)
        exact = n * w / w.sum()
        counts = np.floor(exact).astype(np.int64)
        short = n - int(counts.sum())
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
        return np.repeat(vals, counts)
    ln = dist["lognormal"]
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(ln["median"]) * np.exp(float(ln["sigma"]) * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def requests(mix: Dict[str, Any], seed: int, vocab: int
             ) -> Iterator[RequestSpec]:
    """An endless stream of requests for ``mix`` under ``seed``; for an
    open loop each carries its due time."""
    order = _rng(mix["schedule_seed"], "order")
    tokens = _rng(seed, "tokens")
    plens = stratified(mix["prompt_len"], BLOCK)
    news = stratified(mix["output_len"], BLOCK)
    gaps = None
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        gaps = np.asarray([-math.log(1.0 - (i + 0.5) / BLOCK) / rate
                           for i in range(BLOCK)])
    t = 0.0
    while True:
        p = order.permutation(plens)
        m = order.permutation(news)
        g = order.permutation(gaps) if gaps is not None else None
        for i in range(BLOCK):
            due = None
            if g is not None:
                t += float(g[i])
                due = t
            prompt = tokens.integers(0, vocab, int(p[i]), dtype=np.int32)
            yield RequestSpec(int(p[i]), int(m[i]), prompt, due)


def longest_context(mix: Dict[str, Any]) -> int:
    """Prompt plus output of the longest request the mix can send."""
    return max(support(mix["prompt_len"])) + max(support(mix["output_len"]))
