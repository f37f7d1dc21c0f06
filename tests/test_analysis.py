"""repro.analysis: golden diagnostics, paged-KV sanitizer, export stamp.

Key contracts:
  * golden diagnostics — a misaligned matmul block is ``K001``, an
    edge-target flash-attention config is ``K003 vmem-overflow``, a
    hand-built dangling block table is a sanitizer error, and a clean
    granite config is zero errors on all three passes;
  * the checker is pure — no global oracle/tuning-cache/target state
    survives a check run (``clear_tuning_caches()`` not required after);
  * a pool-exhausted paged admission releases every block it acquired
    (the cohort is re-queued against an intact pool);
  * ``save()`` stamps ``checks: {passed, codes}`` into artifact.json;
    ``load(strict_checks=True)`` refuses unstamped artifacts, the
    default warns and loads them.
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.analysis import jaxpr_audit as ja
from repro.analysis import kernels as ak
from repro.analysis.diagnostics import (DIAGNOSTIC_CODES, AnalysisReport,
                                        Diagnostic)
from repro.analysis.kv_sanitizer import (check_allocator, check_cow,
                                         check_engine)
from repro.api import (ArtifactError, CPruneConfig, DeploymentArtifact,
                       PruningSession, TrainHooks, Workload)
from repro.api.targets import get_target
from repro.configs import get_config, get_reduced_config
from repro.core import clear_tuning_caches
from repro.core import oracle as oracle_mod
from repro.core import tuning_cache
from repro.core.cost_model import Block
from repro.models.model import init_params
from repro.models.paged_cache import RESERVED_BLOCKS, BlockAllocator
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import SchedulerConfig

GRANITE = "granite_moe_1b_a400m"


def _codes(diags):
    return {d.code for d in diags}


def _errors(diags):
    return [d for d in diags if d.severity == "error"]


# ---------------------------------------------------------------------------
# Diagnostic records
# ---------------------------------------------------------------------------

def test_diagnostic_rejects_unknown_code_and_severity():
    with pytest.raises(ValueError):
        Diagnostic("K999", "error", "x", "nope")
    with pytest.raises(ValueError):
        Diagnostic("K001", "fatal", "x", "nope")


def test_diagnostic_str_and_report_exit_semantics():
    d = Diagnostic("K003", "error", "layer.qkv", "needs 70MB of 64MB",
                   fix_hint="shrink the block")
    s = str(d)
    assert "K003" in s and "vmem-overflow" in s and "layer.qkv" in s
    rep = AnalysisReport().extend([d]).extend(
        [Diagnostic("J001", "warning", "y", "meh")])
    assert not rep.ok and len(rep.errors) == 1 and len(rep.warnings) == 1
    assert rep.codes == ["J001", "K003"]
    assert all(c in DIAGNOSTIC_CODES for c in rep.codes)


# ---------------------------------------------------------------------------
# Kernel static checker: golden diagnostics
# ---------------------------------------------------------------------------

def test_k001_misaligned_matmul_block():
    # bm=100 is neither the whole M dim nor sublane(8)-aligned
    call = ak.describe_matmul(1024, 1024, 1024, Block(100, 256, 256))
    diags = ak.check_call(call, get_target("tpu_v5e"))
    assert "K001" in _codes(_errors(diags))


def test_k001_paged_attention_per_head_blocks():
    # the per-(row, q head) launch the TPU lowering refuses: q (1, 1, D)
    # over (B, Hq, D) and K/V (1, bs, 1, D) over the pool both put a 1
    # against a head count in the second-minor dim
    B, hq, hkv, d, bs, nc = 8, 16, 8, 128, 16, 128
    pool = (RESERVED_BLOCKS + B * nc, bs, hkv, d)
    old = ak.KernelCall(
        kernel="paged_attention", site="per-head", grid=(B, hq, nc),
        blocks={"q": ((1, 1, d), (B, hq, d)), "kv": ((1, bs, 1, d), pool)},
        vmem_bytes=0, dtype_bytes=2, is_gemm=False)
    errs = _errors(ak.check_call(old, get_target("tpu_v5e")))
    assert _codes(errs) == {"K001"} and len(errs) == 2
    # the launch the kernel makes: whole-head blocks, at qwen3 (D=128)
    # and granite (D=64) widths
    for d in (128, 64):
        new = ak.describe_paged_attention(B, hq, d, nc, bs, n_kv_heads=hkv)
        assert ak.check_call(new, get_target("tpu_v5e")) == []


def test_k003_flash_attention_overflows_edge():
    call = ak.describe_flash_attention(1, 2048, 2048, 8, 128,
                                       bq=1024, bk=1024)
    edge = _errors(ak.check_call(call, get_target("edge")))
    assert _codes(edge) == {"K003"}
    # the same blocks fit a v5e comfortably
    assert not _errors(ak.check_call(call, get_target("tpu_v5e")))


def test_k002_degenerate_grid():
    call = ak.describe_matmul(0, 256, 256, Block(8, 128, 128))
    assert "K002" in _codes(_errors(ak.check_call(call,
                                                  get_target("tpu_v5e"))))


def test_aligned_tuned_blocks_are_clean():
    # a tuner-shaped block: sublane/lane aligned, VMEM-sized
    call = ak.describe_matmul(512, 1024, 2048, Block(64, 256, 256))
    assert ak.check_call(call, get_target("tpu_v5e")) == []


# ---------------------------------------------------------------------------
# Jaxpr auditor: golden diagnostics
# ---------------------------------------------------------------------------

def test_j002_flags_host_transfer_inside_step():
    def step(x, w):
        return jax.device_put(x) @ w
    jaxpr = jax.make_jaxpr(step)(
        jax.ShapeDtypeStruct((8, 16), np.float32),
        jax.ShapeDtypeStruct((16, 32), np.float32))
    diags = ja.audit_jaxpr(jaxpr, site="t", expect_bf16=False)
    assert "J002" in _codes(_errors(diags))


def test_j001_flags_f32_gemm_in_bf16_step():
    jaxpr = jax.make_jaxpr(lambda x, w: x @ w)(
        jax.ShapeDtypeStruct((8, 16), np.float32),
        jax.ShapeDtypeStruct((16, 32), np.float32))
    diags = ja.audit_jaxpr(jaxpr, site="t", expect_bf16=True)
    assert _codes(diags) == {"J001"}
    assert not _errors(diags)            # advisory, not an error
    # the same trace in an f32-configured model is silent
    assert ja.audit_jaxpr(jaxpr, site="t", expect_bf16=False) == []


def test_j004_serve_shape_hazards():
    diags = ja.audit_serve_shapes(
        SchedulerConfig(compact="exact"), max_batch=6, max_seq=100)
    assert _codes(diags) == {"J004"}
    assert len(diags) == 3               # exact compaction, batch, seq
    assert ja.audit_serve_shapes(SchedulerConfig(),
                                 max_batch=8, max_seq=512) == []


# ---------------------------------------------------------------------------
# Paged-KV sanitizer: hand-built defects
# ---------------------------------------------------------------------------

def test_v003_dangling_table_entry():
    alloc = BlockAllocator(8)
    b = alloc.alloc()
    table = np.array([[b]], np.int32)
    alloc.decref(b)                      # freed while the row points at it
    assert "V003" in _codes(check_allocator(alloc, [table]))


def test_v001_leak_unreachable_block():
    alloc = BlockAllocator(8)
    alloc.alloc()                        # acquired, never tabled
    diags = check_allocator(alloc, [])
    assert "V001" in _codes(diags)


def test_v002_refcount_vs_occurrences():
    alloc = BlockAllocator(8)
    b = alloc.alloc()                    # refcount 1...
    table = np.array([[b, b]], np.int32)  # ...but two live entries
    assert "V002" in _codes(check_allocator(alloc, [table]))


def test_v005_free_list_corruption():
    alloc = BlockAllocator(8)
    b = alloc.alloc()
    alloc.decref(b)
    alloc._free.append(b)                # simulate a double-free
    assert "V005" in _codes(check_allocator(alloc, []))


def test_v004_cow_violation_on_shared_frontier():
    alloc = BlockAllocator(8)
    b = alloc.alloc()
    alloc.incref(b)                      # shared by two rows
    table = np.array([[b], [b]], np.int32)
    diags = check_cow(alloc, table, [True, True], pos=5, plen=4,
                      block_size=16)
    assert _codes(diags) == {"V004"}
    # no decode write yet -> nothing to check
    assert check_cow(alloc, table, [True, True], pos=4, plen=4,
                     block_size=16) == []


def test_sanitizer_clean_allocator():
    alloc = BlockAllocator(8)
    bids = [alloc.alloc() for _ in range(3)]
    table = np.array([bids], np.int32)
    assert check_allocator(alloc, [table]) == []


# ---------------------------------------------------------------------------
# The clean golden config: zero errors on all three passes
# ---------------------------------------------------------------------------

def test_clean_granite_zero_errors_on_all_three_passes():
    cfg = get_config(GRANITE)
    tgt = get_target("tpu_v5e")
    assert not _errors(ak.check_model_kernels(cfg, tgt))
    assert not _errors(ja.audit_model(cfg, max_batch=2, max_seq=64))

    rcfg = get_reduced_config(GRANITE)
    params = init_params(jax.random.PRNGKey(0), rcfg)
    eng = ServeEngine(rcfg, params, max_batch=2, max_seq=32,
                      scheduler=SchedulerConfig(debug_kv=True, page_size=8))
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=rng.integers(
            1, rcfg.vocab_size, 9).astype(np.int32), max_new_tokens=4))
    stats = eng.serve_forever()
    assert stats["requests"] == 3
    assert stats["kv_debug_checks"] > 0
    assert stats["kv_debug_violations"] == 0
    assert ja.audit_engine_donation(eng) == []


def test_j003_flags_a_decode_step_that_donates_only_its_key():
    """The decode program donates the pools and the PRNG key; a step that
    donates the key alone still copies the whole pool per call, and the
    key's aliasing must not hide that."""
    rcfg = get_reduced_config(GRANITE)
    params = init_params(jax.random.PRNGKey(0), rcfg)
    eng = ServeEngine(rcfg, params, max_batch=2, max_seq=32,
                      scheduler=SchedulerConfig(page_size=8))
    assert ja.audit_engine_donation(eng) == []
    fused = eng._decode_paged
    eng._decode_paged = jax.jit(fused.__wrapped__, donate_argnums=6)
    diags = ja.audit_engine_donation(eng)
    assert [(d.code, d.site) for d in diags] == \
        [("J003", "engine.decode_step_paged")]


# ---------------------------------------------------------------------------
# Satellite: the checker must not mutate global state
# ---------------------------------------------------------------------------

def test_check_run_leaves_global_state_untouched():
    clear_tuning_caches()
    fp_before = tuning_cache.target_fingerprint()
    oracle_before = oracle_mod.active_oracle()
    assert len(tuning_cache.global_cache()._store) == 0

    # a target different from the ambient one: restoration must be exact
    diags = ak.check_model_kernels(get_config(GRANITE),
                                   get_target("tpu_v4"))
    assert not _errors(diags)

    # no clear_tuning_caches() in between — everything is already clean
    assert tuning_cache.target_fingerprint() == fp_before
    assert oracle_mod.active_oracle() is oracle_before
    assert len(tuning_cache.global_cache()._store) == 0


# ---------------------------------------------------------------------------
# Satellite: pool-exhausted admission must not leak blocks
# ---------------------------------------------------------------------------

def test_admission_exhaustion_releases_every_block():
    cfg = get_reduced_config("qwen3_1_7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    # 6 usable blocks; a width-2 cohort of 30-token prompts needs 8
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=64,
                      scheduler=SchedulerConfig(page_size=8),
                      kv_pool_blocks=RESERVED_BLOCKS + 6)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=rng.integers(
            1, 50, 30).astype(np.int32), max_new_tokens=4))
    with pytest.raises(RuntimeError):
        eng.step()
    # the failed cohort was re-queued and the pool is intact: no block
    # held, nothing leaked, the sanitizer agrees
    assert eng.kv_allocator.blocks_in_use == 0
    assert check_engine(eng) == []
    # chunked admission path, same exhaustion, same guarantee
    eng2 = ServeEngine(cfg, params, max_batch=4, max_seq=64,
                       scheduler=SchedulerConfig(page_size=8,
                                                 prefill_chunk=16),
                       kv_pool_blocks=RESERVED_BLOCKS + 3)
    eng2.submit(Request(rid=0, prompt=rng.integers(
        1, 50, 40).astype(np.int32), max_new_tokens=4))
    with pytest.raises(RuntimeError):
        eng2.step()
    assert eng2.kv_allocator.blocks_in_use == 0
    assert check_engine(eng2) == []


# ---------------------------------------------------------------------------
# Export stamp + strict load
# ---------------------------------------------------------------------------

def _stamped_artifact(tmp_path):
    cfg = get_reduced_config("qwen3_1_7b").with_overrides(
        n_layers=2, d_model=64, d_ff=512, n_heads=8, n_kv_heads=2,
        head_dim=8, vocab_size=128)
    session = PruningSession(
        cfg, workload=Workload(tokens_global=8192),
        hooks=TrainHooks(short_term_train=lambda p, s: p,
                         eval_acc=lambda p, s: 0.9),
        pcfg=CPruneConfig(a_g=0.5, alpha=0.5, beta=0.9999,
                          max_iterations=2, seq_len=64))
    session.prune(strategy="uniform_l1", ratio=0.5)
    path = str(tmp_path / "art")
    return session.export(path, max_batch=2, max_seq=24), path


def test_export_stamps_checks_and_strict_load_accepts(tmp_path):
    clear_tuning_caches()
    art, path = _stamped_artifact(tmp_path)
    with open(os.path.join(path, "artifact.json")) as f:
        blob = json.load(f)
    assert blob["checks"]["passed"] is True
    assert art.checks == blob["checks"]
    loaded = DeploymentArtifact.load(path, strict_checks=True)
    assert loaded.checks["passed"] is True


def test_unstamped_artifact_warns_by_default_and_strict_refuses(tmp_path):
    clear_tuning_caches()
    _, path = _stamped_artifact(tmp_path)
    fn = os.path.join(path, "artifact.json")
    with open(fn) as f:
        blob = json.load(f)
    del blob["checks"]                   # a pre-analysis export
    with open(fn, "w") as f:
        json.dump(blob, f)
    with pytest.warns(UserWarning, match="no static-analysis stamp"):
        DeploymentArtifact.load(path)
    with pytest.raises(ArtifactError, match="strict_checks"):
        DeploymentArtifact.load(path, strict_checks=True)
    # a stamp recording errors is refused outright, strict or not
    blob["checks"] = {"passed": False, "codes": ["K003"]}
    with open(fn, "w") as f:
        json.dump(blob, f)
    with pytest.raises(ArtifactError, match="K003"):
        DeploymentArtifact.load(path)
