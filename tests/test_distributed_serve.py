"""Tensor-parallel sharded serving (PR 10): partition-stamped artifacts,
the mesh-sharded engine, TP-honest oracle pricing, and the replica fleet
balancer.

Acceptance contract: tp=2 sharded greedy decode (contiguous AND paged
KV) is bit-identical to tp=1 on the granite reduced config under a
4-host-device mesh; tp=1 artifacts stay byte-identical to the pre-PR
schema (no ``partition`` key, schema v1); loading a tp=2 artifact on a
1-device host fails with an error naming both device counts; the
planner's ``tp=[1,2]`` sweep prices per-shard GEMMs plus an analytic
all-reduce term; and the fleet balancer dispatches by outstanding-token
count and re-queues a crashed replica's in-flight work onto survivors.

Mesh-requiring tests spawn subprocesses with forced host devices —
conftest must NOT set XLA_FLAGS globally.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.api import (ArtifactError, CPruneConfig, DeploymentArtifact,
                       PruningSession, TrainHooks, Workload, plan)
from repro.configs import all_configs, get_config, get_reduced_config
from repro.core import clear_tuning_caches
from repro.core.cost_model import (CALL_OVERHEAD_S, ICI_BW, collective_cost)
from repro.core.latency import fixed_latency
from repro.core.oracle import AnalyticOracle, MeasuredOracle
from repro.core.tasks import Workload as CoreWorkload
from repro.launch.mesh import (MeshError, make_production_mesh,
                               make_test_mesh, required_devices)
from repro.models.model import init_params
from repro.serve.distributed import validate_mesh
from repro.serve.engine import Request, ServeEngine
from repro.serve.fleet import (ReplicaSet, ReplicaSupervisor, RetryPolicy,
                               outstanding_tokens)
from repro.sharding import rules
from repro.util.faults import FaultInjector, crash_at

REPO = Path(__file__).resolve().parents[1]
ENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}


def _run(code: str, devices: int = 4, timeout: int = 600):
    env = {**ENV, "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(autouse=True)
def _cold_caches():
    clear_tuning_caches()
    yield
    clear_tuning_caches()


def _cfg():
    return get_reduced_config("qwen3_1_7b").with_overrides(
        n_layers=2, d_model=64, d_ff=512, n_heads=8, n_kv_heads=2,
        head_dim=8, vocab_size=128)


def _hooks(acc=0.9):
    return TrainHooks(short_term_train=lambda p, s: p,
                      eval_acc=lambda p, s: acc)


def _session(cfg, **kw):
    kw.setdefault("workload", Workload(tokens_global=8192))
    kw.setdefault("hooks", _hooks())
    kw.setdefault("pcfg", CPruneConfig(a_g=0.5, alpha=0.5, beta=0.9999,
                                       max_iterations=2, seq_len=64))
    return PruningSession(cfg, **kw)


def _req(rng, cfg, rid, n_new=4, **kw):
    return Request(rid=rid, prompt=rng.integers(
        0, cfg.vocab_size, size=8).astype(np.int32),
        max_new_tokens=n_new, **kw)


# ---------------------------------------------------------------------------
# Collective cost model + TP-honest fixed latency
# ---------------------------------------------------------------------------

def test_collective_cost_ring_formula_and_degenerate_cases():
    n = 1 << 20
    # ring all-reduce moves 2(tp-1)/tp * n bytes over the ICI
    want = 2 * (4 - 1) * n / 4 / ICI_BW + CALL_OVERHEAD_S
    assert collective_cost(n, 4) == pytest.approx(want)
    ag = collective_cost(n, 4, op="all_gather")
    rs = collective_cost(n, 4, op="reduce_scatter")
    assert ag == rs == pytest.approx((4 - 1) * n / 4 / ICI_BW
                                     + CALL_OVERHEAD_S)
    assert ag < collective_cost(n, 4)           # half the wire bytes
    # tp=1 and empty payloads cost exactly zero — never an overhead floor
    assert collective_cost(n, 1) == 0.0
    assert collective_cost(0, 8) == 0.0
    with pytest.raises(ValueError, match="unknown collective op"):
        collective_cost(n, 2, op="broadcast")


def test_every_oracle_backend_prices_collectives():
    n = 1 << 16
    want = AnalyticOracle().collective_cost(n, 2)
    assert want == collective_cost(n, 2)
    # measurement-backed oracles delegate the (unmeasurable-on-host)
    # collective term to the analytic model
    assert MeasuredOracle().collective_cost(n, 2) == want
    # fingerprints unchanged: the analytic backend stays ("analytic",)
    assert AnalyticOracle().fingerprint() == ("analytic",)


def test_fixed_latency_adds_collective_term_only_above_tp1():
    cfg = _cfg()
    wl1 = CoreWorkload(tokens_global=4096, tp=1)
    wl2 = CoreWorkload(tokens_global=4096, tp=2)
    t1, bd1 = fixed_latency(cfg, [], wl1, seq_len=64, use_tuning=False)
    t2, bd2 = fixed_latency(cfg, [], wl2, seq_len=64, use_tuning=False)
    assert "collective" not in bd1              # tp=1 prices stay untouched
    assert bd2["collective"] > 0.0
    # 2 all-reduces per layer + 1 logits all-gather, analytically priced
    m = wl2.tokens_local
    want = 2 * cfg.n_layers * collective_cost(
        m * cfg.d_model * wl2.dtype_bytes, 2)
    want += collective_cost(m * (cfg.vocab_size // 2) * wl2.dtype_bytes, 2,
                            op="all_gather")
    assert bd2["collective"] == pytest.approx(want)
    # per-shard GEMMs shrink, the collective term pushes back — both real
    assert t2 != t1


# ---------------------------------------------------------------------------
# Mesh construction errors (satellite: no silent truncation)
# ---------------------------------------------------------------------------

def test_make_test_mesh_errors_name_shape_and_device_shortfall():
    with pytest.raises(MeshError, match=r"model axis 3 does not divide"):
        make_test_mesh(n_devices=4, model=3)
    # this pytest process runs on exactly one CPU device
    with pytest.raises(MeshError, match=r"needs 4 devices \(2x2.*but only 1"):
        make_test_mesh(n_devices=4, model=2)
    err = None
    try:
        make_test_mesh(n_devices=4, model=2)
    except MeshError as e:
        err = str(e)
    assert "--xla_force_host_platform_device_count=4" in err


def test_make_production_mesh_refuses_undersized_host():
    with pytest.raises(MeshError, match=r"needs 256 devices.*16x16.*only 1"):
        make_production_mesh()
    with pytest.raises(MeshError, match=r"needs 512 devices"):
        make_production_mesh(multi_pod=True)
    assert required_devices(False) == 256 and required_devices(True) == 512


def test_validate_mesh_names_axes_and_tp_mismatch():
    with pytest.raises(MeshError, match=r"must carry a 'model' axis"):
        validate_mesh(rules.SpecMesh({"data": 4}))
    with pytest.raises(MeshError,
                       match=r"tp=4 model shards but the mesh's model axis "
                             r"is 2"):
        validate_mesh(rules.SpecMesh({"data": 1, "model": 2}), tp=4,
                      what="artifact 'x'")
    assert validate_mesh(rules.SpecMesh({"data": 2, "model": 2}), tp=2) == 2


# ---------------------------------------------------------------------------
# Sharding-rule coverage over every shipped config (satellite)
# ---------------------------------------------------------------------------

# leaves the rule table deliberately leaves replicated at tp=2: MQA KV
# projections (1 KV head), MoE routers (hidden dim over data only), odd
# vocab embeddings, RWKV token-mix bottlenecks
_KNOWN_REPLICATED = {"wk", "wv", "router", "embed", "lm_head", "tm_w1"}


def _model_sharded(spec) -> bool:
    for ax in tuple(spec):
        axes = ax if isinstance(ax, (tuple, list)) else (ax,)
        if "model" in axes:
            return True
    return False


@pytest.mark.parametrize("name", all_configs())
def test_rules_shard_every_shipped_config(name):
    """No silent fallthrough to replicated: at tp=2 the rule table must
    shard >= 95% of each shipped config's parameter bytes over the model
    axis, and any large replicated leaf must be a *known* irregular
    (documented above), not a name the table simply missed."""
    from repro.analysis.jaxpr_audit import audit_param_sharding, param_avals
    cfg = get_config(name)
    avals = param_avals(cfg)
    mesh = rules.SpecMesh({"data": 1, "model": 2})
    specs = rules.param_pspecs(avals, mesh)
    tot = sharded = 0

    def walk(a, s):
        nonlocal tot, sharded
        for k in a:
            if isinstance(a[k], dict):
                walk(a[k], s[k])
                continue
            nb = int(np.prod(a[k].shape)) * np.dtype(a[k].dtype).itemsize
            tot += nb
            if _model_sharded(s[k]):
                sharded += nb

    walk(avals, specs)
    assert sharded > 0, f"{name}: nothing model-sharded at tp=2"
    assert sharded / tot >= 0.95, \
        f"{name}: only {sharded / tot:.1%} of param bytes model-sharded"
    for d in audit_param_sharding(cfg, tp=2, min_mib=8.0):
        leaf = d.site.rsplit("/", 1)[-1]
        assert leaf in _KNOWN_REPLICATED, \
            f"{name}: large replicated leaf {d.site} not a known irregular"


# ---------------------------------------------------------------------------
# Artifact partition stamping + load-time validation
# ---------------------------------------------------------------------------

def test_tp1_export_stays_byte_identical_to_v1_schema(tmp_path):
    cfg = _cfg()
    art = _session(cfg).export(str(tmp_path / "a"), max_batch=2, max_seq=24)
    blob = json.loads((tmp_path / "a" / "artifact.json").read_text())
    assert blob["schema_version"] == 1
    assert "partition" not in blob              # tp=1 writes nothing new
    assert art.partition is None and art.tp == 1
    # and it round-trips + serves exactly as before
    eng = ServeEngine.from_artifact(str(tmp_path / "a"), max_batch=2,
                                    max_seq=24)
    assert type(eng) is ServeEngine


def test_tp2_export_stamps_partition_and_load_checks_devices(tmp_path):
    cfg = _cfg()
    session = _session(cfg)
    art = session.export(str(tmp_path / "a"), max_batch=2, max_seq=24, tp=2)
    assert art.tp == 2 and art.workload.tp == 2
    blob = json.loads((tmp_path / "a" / "artifact.json").read_text())
    part = blob["partition"]
    assert part["tp"] == 2
    assert part["mesh_axes"] == {"data": 1, "model": 2}
    # the layout derives from the rule table: q-projections shard heads
    assert any("model" in str(spec) for name, spec in part["params"].items()
               if name.endswith("wq"))
    # the tp=2 decode-step prediction prices per-shard GEMMs + collectives
    # and differs from the tp=1 price of the same artifact
    p2 = art.predict_step_s(2, 24)
    p1 = art.predict_step_s(2, 24, tp=1)
    assert p2 is not None and p1 is not None and p2 != p1
    # this pytest host has ONE device: loading must refuse, naming both
    with pytest.raises(ArtifactError, match=r"tp=2.*but only 1"):
        DeploymentArtifact.load(str(tmp_path / "a"))


def test_load_rejects_tampered_partition_stamp(tmp_path):
    cfg = _cfg()
    _session(cfg).export(str(tmp_path / "a"), max_batch=2, max_seq=24, tp=2)
    fn = tmp_path / "a" / "artifact.json"
    blob = json.loads(fn.read_text())
    blob["partition"]["tp"] = 1                 # disagree with workload.tp
    fn.write_text(json.dumps(blob))
    with pytest.raises(ArtifactError):
        DeploymentArtifact.load(str(tmp_path / "a"))


def test_export_tp_must_be_positive(tmp_path):
    with pytest.raises(ArtifactError, match="tp"):
        _session(_cfg()).export(str(tmp_path / "a"), tp=0)


# ---------------------------------------------------------------------------
# Planner: sharding competes with pruning on the frontier
# ---------------------------------------------------------------------------

def test_plan_tp_sweep_produces_tp_suffixed_arms(tmp_path):
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    pl = plan(cfg, accuracy_floor=0.0, targets=["tpu_v5e"],
              strategies=["uniform_l1"],
              workload=Workload(tokens_global=8192), hooks=_hooks(),
              params=params, pcfg=CPruneConfig(a_g=0.0, seq_len=64),
              strategy_kwargs={"uniform_l1": {"ratio": 0.5}},
              tp=[1, 2])
    tps = sorted(c.tp for c in pl.candidates)
    assert tps == [1, 2]
    names = {c.name for c in pl.candidates}
    assert any(n.endswith("@tp2") for n in names)
    assert all("@tp1" not in n for n in names)  # tp=1 names unchanged
    by_tp = {c.tp: c for c in pl.candidates}
    assert by_tp[2].latency_s != by_tp[1].latency_s
    # the catalog records each arm's degree (old manifests default tp=1)
    cat_dir = tmp_path / "cat"
    # export the full candidate list, not the frontier: at toy size the
    # collective term outweighs the per-shard GEMM savings, so the tp=2
    # arm is (correctly) dominated and would be skipped
    pl.export_catalog(str(cat_dir), list(pl.candidates),
                      max_batch=2, max_seq=24)
    man = json.loads((cat_dir / "catalog.json").read_text())
    assert sorted(e["tp"] for e in man["entries"]) == [1, 2]
    with pytest.raises(ValueError, match="tp degrees must be >= 1"):
        plan(cfg, accuracy_floor=0.0, targets=["tpu_v5e"],
             strategies=["uniform_l1"], hooks=_hooks(), params=params,
             tp=[0])


# ---------------------------------------------------------------------------
# Fleet balancer: outstanding-token dispatch, histogram, survivor re-queue
# ---------------------------------------------------------------------------

def test_replica_set_is_the_supervisor():
    assert ReplicaSet is ReplicaSupervisor


def test_balancer_dispatches_by_outstanding_tokens(setup=None):
    """One long request loads replica 0 with 12 outstanding tokens; the
    following short ones must all pile onto replica 1 (token-debt
    balancing), where request-count balancing would have split 2/2."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    sup = ReplicaSupervisor(
        lambda i: ServeEngine(cfg, params, max_batch=4, max_seq=24),
        name="tokens", replicas=2)
    rng = np.random.default_rng(0)
    sup.submit(_req(rng, cfg, 0, n_new=12))
    for i in range(1, 4):
        sup.submit(_req(rng, cfg, i, n_new=2))
    stats = sup.run()
    assert stats["dispatch_histogram"] == [1, 3]
    occ = stats["per_replica_occupancy"]
    assert [o["replica"] for o in occ] == [0, 1]
    assert [o["dispatched"] for o in occ] == [1, 3]
    assert all(o["outstanding_tokens"] == 0 for o in occ)   # drained
    assert stats["accounting"]["completed"] == 4
    for eng in sup.engines:
        assert outstanding_tokens(eng) == 0


def test_crashed_replica_requeues_onto_survivor():
    """Replica 0 crashes mid-decode with a long rebuild backoff: its
    in-flight requests must drain through the *surviving* replica 1 —
    counted in requeued_to_survivor — with zero lost requests."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    inj = FaultInjector(specs=[crash_at("decode", 0)])
    sup = ReplicaSupervisor(
        lambda i: ServeEngine(cfg, params, max_batch=4, max_seq=24,
                              faults=inj if i == 0 else None),
        name="survivor", replicas=2,
        retry=RetryPolicy(max_retries=2, backoff_s=60.0))
    rng = np.random.default_rng(1)
    reqs = [_req(rng, cfg, i, n_new=4) for i in range(4)]
    for r in reqs:
        sup.submit(r)
    stats = sup.run()
    assert stats["crashes"] == 1
    assert stats["requeued"] >= 1
    assert stats["requeued_to_survivor"] == stats["requeued"]
    assert stats["live_replicas"] == 1          # 0 still in backoff
    assert all(r.done for r in reqs)            # zero loss
    acc = stats["accounting"]
    assert acc["completed"] == 4 and acc["failed"] == 0
    hist = stats["dispatch_histogram"]
    assert sum(hist) == 4 + stats["requeued"]   # re-dispatches counted


# ---------------------------------------------------------------------------
# tp=2 bit-identity + artifact round trip (subprocesses, 4 host devices)
# ---------------------------------------------------------------------------

def test_tp2_sharded_decode_bit_identical_contiguous_and_paged():
    """The acceptance bar: greedy decode through ShardedServeEngine on a
    (2,2)/(1,2) mesh reproduces the tp=1 token stream exactly, for both
    KV layouts, on the granite (MoE) reduced config."""
    code = """
import jax, numpy as np
from repro.configs import get_reduced_config
from repro.launch.mesh import make_test_mesh
from repro.models.model import init_params
from repro.serve.distributed import ShardedServeEngine
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import SchedulerConfig

cfg = get_reduced_config("granite_moe_1b_a400m")
params = init_params(jax.random.PRNGKey(0), cfg)

def reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8 + i).astype(
                        np.int32),
                    max_new_tokens=6) for i in range(4)]

for layout in ("contiguous", "paged"):
    sched = SchedulerConfig(kv_layout=layout, page_size=8)
    ref = ServeEngine(cfg, params, max_batch=4, max_seq=32, scheduler=sched)
    rr = reqs()
    for r in rr: ref.submit(r)
    ref.run()
    assert all(r.done for r in rr)

    mesh = make_test_mesh(n_devices=4, model=2)
    eng = ShardedServeEngine(cfg, params, mesh=mesh, max_batch=4,
                             max_seq=32, scheduler=sched)
    assert eng.tp == 2
    ss = reqs()
    for r in ss: eng.submit(r)
    stats = eng.run()
    assert stats["tp"] == 2 and stats["mesh"] == {"data": 2, "model": 2}
    got = {r.rid: r.output for r in ss}
    want = {r.rid: r.output for r in rr}
    assert got == want, (layout, got, want)
    print("OK", layout)
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK contiguous" in r.stdout and "OK paged" in r.stdout


def test_tp2_paged_pallas_decode_runs_per_head_shard():
    """The Pallas paged-decode kernel cannot be GSPMD-partitioned, so a
    sharded engine runs it per KV-head shard (shard_map over ``model``).
    Forced onto the kernel (interpret mode here), tp=2 must reproduce
    the tp=1 greedy tokens."""
    code = """
import jax, numpy as np
from repro.configs import get_reduced_config
from repro.launch.mesh import make_test_mesh
from repro.models.model import init_params
from repro.serve.distributed import ShardedServeEngine
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import SchedulerConfig

cfg = get_reduced_config("qwen3_1_7b")
params = init_params(jax.random.PRNGKey(0), cfg)
sched = SchedulerConfig(page_size=8)

def drain(eng):
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8).astype(
                np.int32), max_new_tokens=5) for i in range(3)]
    for r in reqs: eng.submit(r)
    eng.run()
    return {r.rid: r.output for r in reqs}

want = drain(ServeEngine(cfg, params, max_batch=4, max_seq=16,
                         scheduler=sched))
eng = ShardedServeEngine(cfg, params, mesh=make_test_mesh(n_devices=2,
                                                          model=2),
                         max_batch=4, max_seq=16, scheduler=sched)
got = drain(eng)
assert got == want, (got, want)
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**ENV, "REPRO_PAGED_BACKEND": "pallas",
                            "XLA_FLAGS":
                            "--xla_force_host_platform_device_count=2"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_tp2_artifact_round_trip_serves_sharded():
    """export(tp=2) -> load (validated against 4 host devices) ->
    ServeEngine.from_artifact auto-dispatches to the sharded engine on
    the default (1,2) mesh and reproduces the tp=1 artifact's decode."""
    code = """
import tempfile
import jax, numpy as np
from repro.api import CPruneConfig, PruningSession, TrainHooks, Workload
from repro.api.artifact import DeploymentArtifact
from repro.configs import get_reduced_config
from repro.serve.distributed import ShardedServeEngine
from repro.serve.engine import Request, ServeEngine

cfg = get_reduced_config("qwen3_1_7b").with_overrides(
    n_layers=2, d_model=64, d_ff=512, n_heads=8, n_kv_heads=2,
    head_dim=8, vocab_size=128)
hooks = TrainHooks(short_term_train=lambda p, s: p,
                   eval_acc=lambda p, s: 0.9)
session = PruningSession(cfg, workload=Workload(tokens_global=8192),
                         hooks=hooks,
                         pcfg=CPruneConfig(a_g=0.5, seq_len=64))
root = tempfile.mkdtemp()
session.export(root + "/tp1", max_batch=2, max_seq=24)
session.export(root + "/tp2", max_batch=2, max_seq=24, tp=2)

def decode(eng):
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8).astype(
                np.int32), max_new_tokens=4) for i in range(2)]
    for r in reqs: eng.submit(r)
    eng.run()
    return {r.rid: r.output for r in reqs}

ref = decode(ServeEngine.from_artifact(root + "/tp1", max_batch=2,
                                       max_seq=24))
art = DeploymentArtifact.load(root + "/tp2")
assert art.tp == 2
eng = ServeEngine.from_artifact(art, max_batch=2, max_seq=24)
assert isinstance(eng, ShardedServeEngine)
assert eng.stats()["mesh"] == {"data": 1, "model": 2}
got = decode(eng)
assert got == ref, (got, ref)

# an explicit mesh whose model axis disagrees is refused by name
from repro.launch.mesh import MeshError, make_test_mesh
try:
    ServeEngine.from_artifact(art, mesh=make_test_mesh(n_devices=4, model=4))
except MeshError as e:
    assert "tp=2" in str(e) and "model axis is 4" in str(e), e
else:
    raise AssertionError("mesh mismatch accepted")
print("OK")
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
