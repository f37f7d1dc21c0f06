"""Public jit'd wrappers for the Pallas kernels, and the interpret switch.

On a TPU backend the kernels compile; on any other backend (the CPU
tests) they run in Pallas interpret mode. :func:`interpret_mode` is
the one place that decides, and it never lets a TPU run the interpreter:
forcing interpret mode there raises. ``REPRO_PALLAS_INTERPRET=0`` forces
compiled kernels on a CPU process — what a compile rehearsal against a
described TPU topology needs, since ``jax.default_backend()`` still says
``cpu`` there. The wrappers are what the benchmarks time.
"""
from __future__ import annotations

import functools
import os

import jax

from repro.core.cost_model import Block
from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import moe_gmm as _gmm
from repro.kernels import rglru_scan as _rg
from repro.kernels import rwkv6_scan as _wkv


def interpret_mode(forced=None) -> bool:
    """Whether Pallas kernels run interpreted: ``forced`` if given, else
    ``REPRO_PALLAS_INTERPRET`` if set, else off the TPU only. Interpret
    mode on a TPU backend is an error — a timing or a serve run there
    must come from the compiled kernel."""
    if forced is None:
        env = os.environ.get("REPRO_PALLAS_INTERPRET")
        if env is not None:
            forced = env not in ("0", "false", "False")
    on_tpu = jax.default_backend() == "tpu"
    if forced is None:
        return not on_tpu
    if forced and on_tpu:
        raise RuntimeError(
            "Pallas interpret mode was forced on a TPU backend (through "
            "REPRO_PALLAS_INTERPRET or MeasurementConfig(interpret=True)); "
            "kernels on a TPU always compile")
    return bool(forced)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn"))
def matmul(a, b, *, bm: int = 128, bk: int = 256, bn: int = 256):
    return _mm.matmul(a, b, block=Block(bm, bk, bn),
                      interpret=interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bs", "bw"))
def rglru_scan(a, x, *, bs: int = 128, bw: int = 128):
    return _rg.rglru_scan(a, x, bs=bs, bw=bw, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bs",))
def rwkv6_scan(r, k, v, w, u, *, bs: int = 64):
    return _wkv.rwkv6_scan(r, k, v, w, u, bs=bs, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn"))
def moe_gmm(x, w, *, bm: int = 128, bk: int = 256, bn: int = 256):
    return _gmm.moe_gmm(x, w, block=Block(bm, bk, bn),
                        interpret=interpret_mode())
