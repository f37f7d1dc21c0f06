"""Smoke run of the serving path on a TPU chip: the quickest proof that
the system still starts there. Not a benchmark.

    python chip_smoke.py            # one chip: serve + plan phases
    python chip_smoke.py --chips 4  # four chips: tp=4 and a replica fleet

One process drives every chip it uses and starts no children. It exits
non-zero, printing no result, when JAX finds no TPU — there is no CPU
fallback — or when any phase fails. On success the last line of standard
output is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.

Serve phase (one chip): ``qwen3_1_7b`` at its full published width with
random bf16 parameters from a seed, built through the same calls as
``python -m repro.launch.serve`` (``get_config``, ``init_params``,
``ServeEngine`` with the default paged scheduler) and fed the launcher's
own request mix. It checks that every request completes with its token
count, that the compiled decode step holds the Pallas paged-attention
kernel (``tpu_custom_call``), that the first decode steps' log-probs
agree with a kernel-free reference forward over the same tokens within
:func:`logprob_tol`, and that each greedy row's token, sampled inside the
decode program, is the argmax of those logits (:func:`check_greedy_tokens`).

Plan phase (one chip): tunes a few qwen3 GEMMs and prices one paged
decode attention under the ``measured`` oracle, whose timings must be
finite, positive and taken from compiled kernels.

Four-chip phase (``--chips 4``, and nothing else): ``ShardedServeEngine``
at tp=4 on a (1, 4) mesh against tp=1 on device 0 within the same
tolerance, each with its greedy tokens checked as above, then a
``ReplicaSupervisor`` of four one-chip replicas, one
per device, that must complete every request with zero crashes.

Every time printed is host wall-clock on the chip's machine and is
informational only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ARCH = "qwen3_1_7b"
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, 128, 32
MAX_BATCH = 8
REF_STEPS = 4            # decode steps compared against the reference
# Log-prob tolerance, in bf16 ulps at the largest logit magnitude. Both
# sides run bf16 weights and activations (8 significant bits, so one ulp
# of a logit in [4, 8) is 2**-5) and differ only in the order of their
# reductions: on a cut-down qwen3 the kernel-free reference and the
# decode step differ by 2-3 ulps with and without the Pallas kernel,
# while a kernel that reads the wrong head group or drops the newest
# slot misses by 70 ulps or more.
LOGPROB_ULPS = 8
# A greedy token may miss the argmax of the logits recorded beside it only
# by a tie this close: the recorded logits come from the model's step
# compiled alone, the token from the same step compiled with its sampling.
TIE_ULPS = 2


def _bf16_ulp(x: float) -> float:
    import math
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -126))) - 7)


def logprob_tol(max_abs_logit: float) -> float:
    return LOGPROB_ULPS * _bf16_ulp(max_abs_logit)


class SmokeError(AssertionError):
    """A phase produced a wrong or missing result."""


def _check(cond, msg):
    if not cond:
        raise SmokeError(msg)


def _say(msg):
    print(f"chip_smoke: {msg}", flush=True)


# -- shared helpers ------------------------------------------------------------

def _requests(cfg, n):
    """The launcher's own seeded mix: greedy and temperature 0.8."""
    from repro.launch.serve import synthetic_requests
    args = argparse.Namespace(requests=n, prompt_len=PROMPT_LEN,
                              max_new=MAX_NEW, floor=None)
    return list(synthetic_requests(args, cfg, None))


def _record_decode(eng, steps):
    """Wrap the engine's own jitted paged decode step so its first
    ``steps`` calls leave (row rids, input tokens, pos, logits) behind.
    The step samples inside its program, so the logits come from the
    model's decode step jitted alone on the same inputs, before the step
    takes the pools. The step itself is untouched; the first call's
    argument shapes are kept to lower it again for inspection."""
    import contextlib

    import jax
    import numpy as np
    step = eng._decode_paged
    logits_of = jax.jit(eng.model.decode_step_paged)
    mesh = getattr(eng, "mesh", None)
    seen = {"calls": [], "shapes": None}

    def recording(*args):
        params, cur, pools, table, pos = args[:5]
        if seen["shapes"] is None:
            seen["shapes"] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
                args)
        if len(seen["calls"]) < steps:
            _check(len(eng.groups) == 1, "expected one decoding cohort")
            rids = [r.rid if r is not None else None
                    for r in eng.groups[0].requests]
            with (jax.set_mesh(mesh) if mesh is not None
                  else contextlib.nullcontext()):
                logits, _ = logits_of(params, cur, pools, table, pos)
            seen["calls"].append((rids, np.asarray(cur[:, 0]), int(pos),
                                  np.asarray(logits[:, 0], np.float32)))
        return step(*args)

    eng._decode_paged = recording
    return step, seen


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    stats = eng.run()
    wall = time.perf_counter() - t0
    for r in reqs:
        _check(r.done and len(r.output) == r.max_new_tokens,
               f"request {r.rid} ended with {len(r.output)} of "
               f"{r.max_new_tokens} tokens (done={r.done})")
    return stats, wall


def _log_softmax(x):
    import numpy as np
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


class _Diff:
    """Largest |log p - log p_ref| over compared rows, and the largest
    reference logit magnitude, which sets the tolerance."""

    def __init__(self):
        self.worst, self.scale, self.rows = 0.0, 0.0, 0

    def add(self, got, want):
        import numpy as np
        d = np.abs(_log_softmax(got) - _log_softmax(want))
        self.worst = max(self.worst, float(d.max()))
        self.scale = max(self.scale, float(np.abs(want).max()))
        self.rows += 1

    def check(self, what):
        tol = logprob_tol(self.scale)
        _say(f"{what}: max |dlogp| over {self.rows} row-steps = "
             f"{self.worst:.6f}, tolerance {tol} ({LOGPROB_ULPS} bf16 ulps "
             f"at max |logit| {self.scale:.4f})")
        _check(self.rows > 0, f"{what}: no row-steps compared")
        _check(self.worst <= tol,
               f"{what}: log-probs differ by {self.worst} > {tol}")


def reference_logprob_diff(cfg, params, reqs, calls):
    """Kernel-free reference: the model's plain forward
    (``backbone_train``, blockwise jnp attention, no cache) over prompt +
    the tokens each row was fed, against the logits the engine's decode
    steps produced. Returns (the :class:`_Diff`, greedy agreement)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import Model, make_positions
    model = Model(cfg)
    by_rid = {r.rid: r for r in reqs}
    plen = len(reqs[0].prompt)
    k = len(calls)
    rids = [rid for rid in calls[0][0] if rid is not None]
    for j, (row_rids, cur, pos, _) in enumerate(calls):
        _check(pos == plen + j, f"decode step {j} ran at pos {pos}")
        for i, rid in enumerate(row_rids):
            if rid is not None:
                _check(int(cur[i]) == by_rid[rid].output[j],
                       f"row {i} was fed a token its request did not emit")
    toks = np.stack([np.concatenate([by_rid[rid].prompt,
                                     by_rid[rid].output[:k]])
                     for rid in rids]).astype(np.int32)

    @jax.jit
    def forward(params, tokens):
        x = model.embed(params, tokens)
        x, _ = model.backbone_train(params, x,
                                    make_positions(cfg, tokens.shape[1]))
        return model.unembed(params, x[:, plen:]).astype(jnp.float32)

    ref = np.asarray(forward(params, jnp.asarray(toks)))    # (rows, k, V)
    diff, agree = _Diff(), 0
    for j, (row_rids, _, _, logits) in enumerate(calls):
        for i, rid in enumerate(row_rids):
            if rid is not None:
                got, want = logits[i], ref[rids.index(rid), j]
                diff.add(got, want)
                agree += int(np.argmax(got) == np.argmax(want))
    return diff, agree / max(diff.rows, 1)


def check_greedy_tokens(by_rid, calls, what):
    """Tie the tokens the decode program sampled to the logits that were
    checked: decode call ``j`` emitted ``output[j + 1]``, which for every
    greedy row must be the argmax of that row's recorded logits, or within
    :data:`TIE_ULPS` bf16 ulps of it."""
    import numpy as np
    rows = exact = 0
    for j, (row_rids, _, _, logits) in enumerate(calls):
        for i, rid in enumerate(row_rids):
            if rid is None or by_rid[rid].temperature > 0:
                continue
            got, tok = logits[i], by_rid[rid].output[j + 1]
            top = float(got.max())
            _check(got[tok] >= top - TIE_ULPS * _bf16_ulp(top),
                   f"{what}: decode call {j} row {i} emitted token {tok} "
                   f"(logit {got[tok]}), the argmax logit is {top}")
            rows += 1
            exact += int(tok == int(np.argmax(got)))
    _say(f"{what}: {exact} of {rows} greedy tokens sampled in the decode "
         f"program equal the argmax of the recorded logits, the rest lie "
         f"within {TIE_ULPS} bf16 ulps of it")
    _check(rows > 0, f"{what}: no greedy row-steps compared")


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# -- phases --------------------------------------------------------------------

def serve_phase(cfg, *, expect_kernel=True):
    import jax

    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    t0 = time.perf_counter()
    params = init_params(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(params)
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH,
                      max_seq=PROMPT_LEN + MAX_NEW)
    _check(eng.kv_layout == "paged", f"engine fell back to {eng.kv_layout}")
    step, seen = _record_decode(eng, REF_STEPS)
    _drain(eng, _requests(cfg, N_REQUESTS))      # compiles every shape
    setup_s = time.perf_counter() - t0

    eng.reset_stats()
    seen["calls"].clear()
    reqs = _requests(cfg, N_REQUESTS)
    stats, serve_s = _drain(eng, reqs)
    _check(stats["requests"] == N_REQUESTS,
           f"{stats['requests']} of {N_REQUESTS} requests completed")
    tokens = sum(len(r.output) for r in reqs)
    _say(f"serve: {N_REQUESTS} requests x {PROMPT_LEN} prompt + {MAX_NEW} "
         f"new tokens, set-up+compile {setup_s:.3f} s, serve {serve_s:.6f} "
         f"s, {tokens} tokens, {tokens / serve_s:.3f} tokens/s, "
         f"p50 decode step {stats['p50_step_s']:.6f} s")

    text = step.lower(*seen["shapes"]).compile().as_text()
    has_kernel = "tpu_custom_call" in text
    _say(f"serve: compiled decode step holds tpu_custom_call: {has_kernel}")
    if expect_kernel:
        _check(has_kernel, "the decode step did not run the Pallas kernel")

    diff, agree = reference_logprob_diff(cfg, params, reqs, seen["calls"])
    _say(f"serve: greedy agreement with the reference {agree:.3f} "
         f"(informational: random-init logits are near ties)")
    diff.check("serve vs kernel-free reference")
    check_greedy_tokens({r.rid: r for r in reqs}, seen["calls"], "serve")
    _say(f"serve: peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")


def plan_phase():
    import math

    from repro.core import tuning_cache
    from repro.core.oracle import MeasuredOracle, use_oracle
    from repro.core.tuner import TunerStats, tune_gemm
    from repro.kernels.ops import interpret_mode

    _check(not interpret_mode(), "kernels would run interpreted")
    # qwen3_1_7b projections at 512 tokens
    gemms = {"ffn_up": (512, 2048, 6144), "ffn_down": (512, 6144, 2048),
             "attn_q": (512, 2048, 2048)}
    stats = TunerStats()
    cache = tuning_cache.ProgramCache()
    t0 = time.perf_counter()
    with use_oracle(MeasuredOracle()) as orc:
        for name, (m, k, n) in gemms.items():
            prog = tune_gemm(m, k, n, stats=stats, cache=cache)
            _check(math.isfinite(prog.latency) and prog.latency > 0,
                   f"{name}: measured latency {prog.latency}")
            _say(f"plan: {name} {m}x{k}x{n} -> block {prog.block}, "
                 f"{prog.latency:.9f} s")
        pa = orc.paged_attention_cost(8, 2048, 16, 128, n_kv_heads=8,
                                      block_size=16)
    _check(math.isfinite(pa) and pa > 0, f"paged attention timed {pa}")
    _check(stats.measured_programs >= len(gemms),
           f"only {stats.measured_programs} kernels were timed")
    _say(f"plan: paged_attention B=8 kv_len=2048 -> {pa:.9f} s; "
         f"{stats.measured_programs} compiled kernels timed in "
         f"{time.perf_counter() - t0:.3f} s")


def four_chip_phase(cfg):
    import jax

    from repro.launch.mesh import make_mesh, make_test_mesh
    from repro.models.model import init_params
    from repro.serve.distributed import ShardedServeEngine
    from repro.serve.engine import ServeEngine
    from repro.serve.fleet import ReplicaSupervisor

    devices = jax.devices()
    _check(len(devices) >= 4, f"{len(devices)} devices, need 4")
    params = init_params(jax.random.PRNGKey(0), cfg)
    max_seq = PROMPT_LEN + MAX_NEW

    runs = {}
    for tp in (1, 4):
        t0 = time.perf_counter()
        if tp == 1:
            eng = ServeEngine(cfg, params, max_batch=MAX_BATCH,
                              max_seq=max_seq)
        else:
            eng = ShardedServeEngine(
                cfg, params, mesh=make_test_mesh(n_devices=4, model=4),
                max_batch=MAX_BATCH, max_seq=max_seq)
        _, seen = _record_decode(eng, REF_STEPS)
        reqs = _requests(cfg, N_REQUESTS)
        _drain(eng, reqs)
        runs[tp] = ({r.rid: r for r in reqs}, seen["calls"])
        check_greedy_tokens(*runs[tp], f"tp={tp}")
        _say(f"tp={tp}: {N_REQUESTS} requests served in "
             f"{time.perf_counter() - t0:.3f} s (compile included)")
        del eng
    # compare the two engines' logits wherever a row was fed the same
    # history (a near-tie sample may send a row down another path)
    diff = _Diff()
    (by1, calls1), (by4, calls4) = runs[1], runs[4]
    for j, ((r1, _, _, l1), (r4, _, _, l4)) in enumerate(zip(calls1,
                                                             calls4)):
        for i4, rid in enumerate(r4):
            if (rid is not None and rid in r1 and by1[rid].output[:j + 1]
                    == by4[rid].output[:j + 1]):
                diff.add(l4[i4], l1[r1.index(rid)])
    diff.check("tp=4 vs tp=1")

    def factory(i):
        mesh = make_mesh((1, 1), ("data", "model"), [devices[i]])
        return ShardedServeEngine(cfg, params, mesh=mesh,
                                  max_batch=MAX_BATCH, max_seq=max_seq,
                                  seed=i)

    t0 = time.perf_counter()
    sup = ReplicaSupervisor(factory, replicas=4, name=cfg.name)
    reqs = _requests(cfg, 4 * N_REQUESTS)
    for r in reqs:
        sup.submit(r)
    stats = sup.run()
    homes = {d for e in sup.engines
             for d in jax.tree.leaves(e.params)[0].devices()}
    _say(f"fleet: 4 replicas on devices {sorted(d.id for d in homes)}, "
         f"{stats['requests']} of {len(reqs)} requests, crashes "
         f"{stats['crashes']}, failed {stats['failed']}, dispatch "
         f"{stats['dispatch_histogram']}, "
         f"{time.perf_counter() - t0:.3f} s (compile included)")
    _check(len(homes) == 4, f"replica params live on {len(homes)} devices")
    _check(stats["crashes"] == 0, f"{stats['crashes']} replica crashes")
    _check(stats["failed"] == 0 and stats["requests"] == len(reqs),
           f"{stats['requests']} completed, {stats['failed']} failed")
    for r in reqs:
        _check(r.done and len(r.output) == MAX_NEW,
               f"request {r.rid} ended with {len(r.output)} tokens")


# -- entry point ---------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip phase")
    args = ap.parse_args(argv)

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{device.platform!r}); this smoke run needs a TPU chip and "
              f"has no CPU fallback", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 1
    _say(f"compile cache at {enable_compile_cache()}")
    _say(f"device {device.device_kind}, {len(jax.devices())} device(s) "
         f"visible, --chips {args.chips}")
    cfg = get_config(ARCH)
    try:
        if args.chips == 4:
            four_chip_phase(cfg)
        else:
            serve_phase(cfg)
            plan_phase()
    except Exception:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
