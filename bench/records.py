"""Reductions from a run's records to numbers; the metric files call these.

``rec`` is the dict ``bench/run.py`` builds after the window:

``window``      ``{"start", "w0", "w1"}`` on the host clock (seconds)
``requests``    one dict per submitted request: ``due``, ``submitted``,
                ``plen``, ``max_new``, ``tokens`` (host-clock stamp of each
                token, taken when the ``step()`` that made it returned)
``spans``       one dict per ``step()``: ``idx``, ``t0``, ``t1``, ``event``
``stats``       ``{"w0": ..., "w1": ...}``: ``engine.stats()`` at both ends
``setup_s``     process start to window start
``trace``       ``bench/trace.py``'s summary of the traced window, or None
``model``       the configuration's published sizes
``reference``   the configuration's reference module, whose
                ``prefill_flops``, ``decode_token_flops`` and
                ``paged_attention_need`` count the work
``peaks``       the chip's entry of ``bench/peaks.json``
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def percentile(xs: List[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile; None for no samples."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def in_window(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Requests due inside the measured window."""
    w0, w1 = rec["window"]["w0"], rec["window"]["w1"]
    return [r for r in rec["requests"] if w0 <= r["due"] < w1]


def ttft_samples(rec: Dict[str, Any]) -> List[float]:
    """Due time to first token for every request due in the window; one
    with no token by the window's end counts as (end - due)."""
    w1 = rec["window"]["w1"]
    out = []
    for r in in_window(rec):
        first = r["tokens"][0] if r["tokens"] else None
        out.append((first if first is not None and first <= w1 else w1)
                   - r["due"])
    return out


def itl_samples(rec: Dict[str, Any]) -> List[float]:
    """Every gap between consecutive tokens of the requests due in the
    window, up to its end; a request still decoding at the end adds the
    gap it is waiting in, so a stall cannot hide."""
    w1 = rec["window"]["w1"]
    out = []
    for r in in_window(rec):
        ts = [t for t in r["tokens"] if t <= w1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
        if ts and len(r["tokens"]) < r["max_new"]:
            out.append(w1 - ts[-1])
    return out


def window_tokens(rec: Dict[str, Any]) -> int:
    """Prompt tokens prefilled plus tokens generated inside the window. A
    prompt counts where its first token was made, which its prefill did."""
    w0, w1 = rec["window"]["w0"], rec["window"]["w1"]
    n = 0
    for r in rec["requests"]:
        ts = r["tokens"]
        if ts and w0 <= ts[0] <= w1:
            n += r["plen"]
        n += sum(1 for t in ts if w0 <= t <= w1)
    return n


def window_spans(rec: Dict[str, Any], event: str) -> List[Dict[str, Any]]:
    w0, w1 = rec["window"]["w0"], rec["window"]["w1"]
    return [s for s in rec["spans"]
            if s["event"] == event and w0 <= s["t0"] and s["t1"] <= w1]


def stats_delta(rec: Dict[str, Any], key: str) -> float:
    return rec["stats"]["w1"][key] - rec["stats"]["w0"][key]


# -- reductions that need the trace ------------------------------------------

def traced_work(rec: Dict[str, Any]) -> Dict[str, Any]:
    """Prefills and decoded tokens whose step returned inside the traced
    window, as (plen) and (keys attended) lists."""
    a, b = rec["trace"]["host_window"]
    prefills, decode_keys = [], []
    for r in rec["requests"]:
        for j, t in enumerate(r["tokens"]):
            if not a <= t <= b:
                continue
            if j == 0:
                prefills.append(r["plen"])
            else:
                # token j comes from the decode step at position
                # plen + j - 1, whose query attends plen + j keys
                decode_keys.append(r["plen"] + j)
    return {"prefills": prefills, "decode_keys": decode_keys}


def _device_trace(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The trace summary, where it read at least one device."""
    tr = rec.get("trace")
    return tr if tr and tr["chips"] and tr["window_s"] > 0 else None


def step_mfu_pct(rec: Dict[str, Any]) -> Optional[float]:
    tr = _device_trace(rec)
    if tr is None:
        return None
    m, ref = rec["model"], rec["reference"]
    work = traced_work(rec)
    if not work["prefills"] and not work["decode_keys"]:
        return None
    total = (sum(ref.prefill_flops(m, p) for p in work["prefills"])
             + sum(ref.decode_token_flops(m, k)
                   for k in work["decode_keys"]))
    return 100.0 * total / (tr["window_s"] * rec["peaks"]["bf16_flops_per_s"])


def device_idle_pct(rec: Dict[str, Any]) -> Optional[float]:
    tr = _device_trace(rec)
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_roofline_pct(rec: Dict[str, Any], kernel: str
                        ) -> Optional[float]:
    """Least time the paged decode kernel's calls need at the chip's peaks,
    over its device time in the trace. The calls' needs come from the
    keys each live row attends, in every layer."""
    tr = _device_trace(rec)
    if tr is None:
        return None
    k_s = tr["kernels"].get(kernel, {}).get("s", 0.0)
    keys = traced_work(rec)["decode_keys"]
    if k_s <= 0 or not keys:
        return None
    m, ref = rec["model"], rec["reference"]
    need_f = need_b = 0
    for k in keys:
        need = ref.paged_attention_need(m, k)
        need_f += need["flops"]
        need_b += need["bytes"]
    pk = rec["peaks"]
    least = max(need_f / pk["bf16_flops_per_s"], need_b / pk["hbm_bytes_per_s"])
    return 100.0 * least / k_s
