"""Distributed-serving bench (CI ``distributed-smoke``): tensor-parallel
bit-identity plus the replica fleet balancer.

Three arms:

  * ``dist_tp2_identity`` — a CPU subprocess (``JAX_PLATFORMS=cpu``)
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
    greedy-decodes the same reduced model once on a single device and
    once sharded tp=2 over a ``(1, 2)`` (data, model) mesh, for both KV
    layouts. The token streams must be **bit-identical**: GSPMD
    partitions the very jaxpr the single-device engine traced, so
    sharding is an execution detail, never a math change.
  * ``dist_fleet_vs_solo`` — a 2-replica :class:`ReplicaSet` draining the
    interleaved serve workload vs one replica alone, both warmed.
    Least-loaded outstanding-token dispatch must sustain >= the single
    replica (``SERVE_DIST_MIN_RATIO``, default 1.0): on one host the
    replicas share the CPU, so the fleet's win is batching reach (2x the
    slots), and the gate catches any balancer overhead regression.
  * ``dist_fleet_crash`` — the same fleet with one injected mid-decode
    crash on replica 0. Gates: **zero lost requests** (every request
    completes), outputs bit-identical to the fault-free drain, and at
    least one re-queue must land on the *surviving* replica
    (``requeued_to_survivor`` — recovery does not wait for the cold
    rebuild of the replica that died).

Run: ``PYTHONPATH=src:. python benchmarks/distributed_bench.py``
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from benchmarks import common
from repro.models.model import init_params
from repro.serve.engine import Request, ServeEngine
from repro.serve.fleet import ReplicaSet, RetryPolicy
from repro.util.faults import FaultInjector, crash_at

N_REQUESTS = 16
MAX_BATCH = 4
MAX_SEQ = 40


def _bench_cfg():
    return common.bench_config(n_layers=2, d_model=64, d_ff=512, n_heads=4,
                               n_kv_heads=2, head_dim=16, vocab_size=128)


def _workload(cfg):
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(N_REQUESTS):
        plen = 8 if i % 2 == 0 else 12
        n_new = 4 if i % 4 < 2 else 24
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=n_new))
    return reqs


# ---------------------------------------------------------------------------
# arm 1: tp=2 sharded decode is bit-identical to single-device decode
# ---------------------------------------------------------------------------

# The parent process already initialised jax with however many devices the
# host has, and XLA_FLAGS is read once at import — so the tp=2 arm runs in
# a fresh interpreter where the flag can still take effect. It is a CPU
# correctness check on four virtual host devices: the child runs with
# JAX_PLATFORMS=cpu, so it never asks for a chip the parent may hold.
_TP2_CODE = textwrap.dedent("""
    import jax, numpy as np
    from benchmarks import common
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import init_params
    from repro.serve.distributed import ShardedServeEngine
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.scheduler import SchedulerConfig

    assert len(jax.devices()) == 4, jax.devices()
    cfg = common.bench_config(n_layers=2, d_model=64, d_ff=512, n_heads=4,
                              n_kv_heads=2, head_dim=16, vocab_size=128)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def reqs():
        rng = np.random.default_rng(0)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            8 if i % 2 == 0 else 12
                                            ).astype(np.int32),
                        max_new_tokens=4 if i % 4 < 2 else 24)
                for i in range(16)]

    def drain(eng):
        for r in reqs():
            eng.submit(r)
        eng.run()
        return {r.rid: list(r.output) for r in eng.done}

    mesh = make_test_mesh(n_devices=2, model=2)   # (1, 2) (data, model)
    for layout in ("contiguous", "paged"):
        sched = SchedulerConfig(kv_layout=layout, page_size=8)
        want = drain(ServeEngine(cfg, params, max_batch=4, max_seq=40,
                                 scheduler=sched))
        got = drain(ShardedServeEngine(cfg, params, mesh=mesh, max_batch=4,
                                       max_seq=40, scheduler=sched))
        assert got == want, (
            f"tp=2 {layout} decode diverged for rids "
            f"{[r for r in want if got.get(r) != want[r]][:8]}")
        print(f"IDENTICAL {layout} tokens="
              f"{sum(len(v) for v in want.values())}")
""")


def run_tp2_identity():
    t = common.Timer()
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src:."
    proc = subprocess.run([sys.executable, "-c", _TP2_CODE],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"tp=2 identity subprocess failed:\n{proc.stdout}\n{proc.stderr}")
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("IDENTICAL")]
    if len(lines) != 2:
        raise RuntimeError(f"expected 2 IDENTICAL lines, got:\n{proc.stdout}")
    common.emit("dist_tp2_identity", t.us(),
                "identical=contiguous,paged;devices=4;mesh=1x2;"
                + lines[0].split()[-1])
    return lines


# ---------------------------------------------------------------------------
# arms 2+3: the fleet balancer
# ---------------------------------------------------------------------------

def _fleet(cfg, params, *, replicas, faults=None):
    def factory(i):
        return ServeEngine(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                           faults=faults if i == 0 else None,
                           fault_tag=f"bench#r{i}")
    return ReplicaSet(factory, replicas=replicas, name="bench",
                      retry=RetryPolicy(max_retries=2, backoff_s=60.0))


def _drain(sup, cfg):
    for r in _workload(cfg):
        sup.submit(r)
    sup.run()
    stats = sup.stats()
    outputs = {r.rid: list(r.output) for r in sup.completed}
    sup.reset_stats()
    return stats, outputs


def run_fleet():
    min_ratio = float(os.environ.get("SERVE_DIST_MIN_RATIO", "1.0"))
    cfg = _bench_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)

    # -- arm 2: 2-replica least-loaded dispatch vs one replica --------------
    t = common.Timer()
    solo = _fleet(cfg, params, replicas=1)
    duo = _fleet(cfg, params, replicas=2)
    _drain(solo, cfg)                      # warmup: compile every shape
    _drain(duo, cfg)
    solo_stats, solo_out = _drain(solo, cfg)
    duo_stats, duo_out = _drain(duo, cfg)
    for _ in range(2):                     # best-of-3 to dampen host noise
        s, _ = _drain(solo, cfg)
        if s["tokens_per_s"] > solo_stats["tokens_per_s"]:
            solo_stats = s
        s, _ = _drain(duo, cfg)
        if s["tokens_per_s"] > duo_stats["tokens_per_s"]:
            duo_stats = s
    assert duo_out == solo_out, "replica count changed greedy outputs"
    hist = duo_stats["dispatch_histogram"]
    ratio = duo_stats["tokens_per_s"] / max(solo_stats["tokens_per_s"], 1e-9)
    common.emit(
        "dist_fleet_vs_solo", t.us(),
        f"tokens_per_s={duo_stats['tokens_per_s']:.1f}"
        f";solo_tokens_per_s={solo_stats['tokens_per_s']:.1f}"
        f";ratio={ratio:.2f}"
        f";dispatch_histogram={hist}")
    if not all(hist):
        raise RuntimeError(
            f"least-loaded dispatch starved a replica: histogram {hist}")
    if ratio < min_ratio:
        raise RuntimeError(
            f"2-replica fleet fell below the single replica: "
            f"ratio {ratio:.2f} < {min_ratio}")

    # -- arm 3: one injected crash — zero lost, survivor absorbs ------------
    t = common.Timer()
    inj = FaultInjector(specs=[crash_at("decode:bench#r0", 3)])
    fleet = _fleet(cfg, params, replicas=2, faults=inj)
    chaos_stats, chaos_out = _drain(fleet, cfg)
    if chaos_out != solo_out:
        bad = [rid for rid in solo_out if chaos_out.get(rid) != solo_out[rid]]
        raise RuntimeError(
            f"re-queued outputs diverged from the fault-free drain "
            f"for rids {bad[:8]}")
    acct = chaos_stats["accounting"]
    common.emit(
        "dist_fleet_crash", t.us(),
        f"crashes={chaos_stats['crashes']}"
        f";requeued={chaos_stats['requeued']}"
        f";requeued_to_survivor={chaos_stats['requeued_to_survivor']}"
        f";requests={chaos_stats['requests']}"
        f";failed={chaos_stats['failed']}"
        f";dispatch_histogram={chaos_stats['dispatch_histogram']}")
    if chaos_stats["requests"] != N_REQUESTS or chaos_stats["failed"] \
            or acct["in_flight"]:
        raise RuntimeError(
            f"lost requests under the crash: completed "
            f"{chaos_stats['requests']}/{N_REQUESTS} "
            f"(failed={chaos_stats['failed']}, "
            f"in_flight={acct['in_flight']})")
    if not (chaos_stats["crashes"] >= 1
            and chaos_stats["requeued_to_survivor"] >= 1):
        raise RuntimeError(
            f"the crash did not exercise survivor re-queue: "
            f"crashes={chaos_stats['crashes']} "
            f"requeued_to_survivor={chaos_stats['requeued_to_survivor']}")
    return {"solo": solo_stats, "duo": duo_stats, "chaos": chaos_stats}


def run():
    run_tp2_identity()
    return run_fleet()


if __name__ == "__main__":
    run()
