"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Every Pallas kernel runs in interpret mode (CPU container; TPU is the
compile target) and must match ref.py within dtype-appropriate tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import Block
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (100, 200, 300),
                                   (8, 512, 128), (257, 129, 511)])
@pytest.mark.parametrize("block", [Block(32, 128, 128), Block(64, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul(m, k, n, block, dtype):
    a = jax.random.normal(KEY, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n),
                          jnp.float32).astype(dtype)
    out = matmul(a, b, block=block, interpret=True)
    expect = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype) * np.abs(np.asarray(expect)).max())


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,hq,hkv,causal,window", [
    (128, 128, 4, 2, True, 0),      # GQA causal
    (96, 96, 4, 1, True, 0),        # MQA, ragged seq
    (64, 64, 8, 8, False, 0),       # MHA bidirectional (encoder)
    (192, 192, 4, 2, True, 64),     # sliding window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(sq, sk, hq, hkv, causal, window, dtype):
    B, D = 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, sq, hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, sk, hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, sk, hkv, D), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=32, bk=32, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype) * 3)


def test_flash_attention_matches_blockwise_model_path():
    """Kernel vs the model's XLA blockwise path (two independent impls)."""
    from repro.models.attention import blockwise_attention
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    a = flash_attention(q, k, v, causal=True, bq=32, bk=32, interpret=True)
    b = blockwise_attention(q, k, v, causal=True, q_block=32, k_block=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def _paged_case(B, n_cols, bs, hq, hkv, D, dtype, *, seed=0, ragged=True):
    """Random pools + a shuffled block table + ragged per-row lengths.

    Block ids are a permutation of the pool (plus a couple of shared ids
    when the pool is large enough) so the kernel's table indirection is
    actually exercised — an identity table would hide gather bugs."""
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    n_blocks = B * n_cols + 2
    q = jax.random.normal(ks[0], (B, hq, D), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (n_blocks, bs, hkv, D),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (n_blocks, bs, hkv, D),
                           jnp.float32).astype(dtype)
    rng = np.random.default_rng(seed)
    table = rng.permutation(n_blocks)[:B * n_cols] \
        .reshape(B, n_cols).astype(np.int32)
    if ragged:
        lens = rng.integers(1, n_cols * bs + 1, size=B).astype(np.int32)
    else:
        lens = np.full(B, n_cols * bs, np.int32)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lens)


@pytest.mark.parametrize("n_cols,bs", [(1, 8), (3, 8), (2, 16), (5, 4)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1), (8, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matrix(n_cols, bs, hq, hkv, dtype):
    from repro.kernels.paged_attention import paged_attention
    B, D = 3, 32
    q, kp, vp, table, lens = _paged_case(B, n_cols, bs, hq, hkv, D, dtype,
                                         seed=n_cols * 100 + bs)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype) * 3)


@pytest.mark.parametrize("D", [16, 32, 64, 128])
def test_paged_attention_head_dims(D):
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, table, lens = _paged_case(2, 3, 8, 4, 2, D, jnp.float32,
                                         seed=D)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_len_one_and_full():
    """Boundary lengths: a single cached token and an exactly-full table."""
    from repro.kernels.paged_attention import paged_attention
    q, kp, vp, table, _ = _paged_case(2, 2, 8, 4, 2, 32, jnp.float32, seed=7)
    lens = jnp.asarray([1, 16], jnp.int32)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_matches_dense_decode():
    """Paged kernel vs blockwise_attention over the *same* KV laid out
    contiguously — the two layouts must agree on the decode step."""
    from repro.models.attention import blockwise_attention
    from repro.kernels.paged_attention import paged_attention
    B, n_cols, bs, Hq, Hkv, D = 2, 4, 8, 4, 2, 32
    q, kp, vp, table, lens = _paged_case(B, n_cols, bs, Hq, Hkv, D,
                                         jnp.float32, seed=11)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    for b in range(B):
        L = int(lens[b])
        kd = kp[table[b]].reshape(1, n_cols * bs, Hkv, D)[:, :L]
        vd = vp[table[b]].reshape(1, n_cols * bs, Hkv, D)[:, :L]
        dense = blockwise_attention(
            q[b][None, None], kd, vd, causal=True,
            q_positions=jnp.asarray([L - 1], jnp.int32))
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(dense[0, 0]),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,w,bs,bw", [(64, 64, 32, 64), (100, 96, 32, 32),
                                       (33, 17, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan(s, w, bs, bw, dtype):
    B = 2
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, s, w))).astype(dtype)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (B, s, w)).astype(dtype)
    y, s_last = rglru_scan(a, x, bs=bs, bw=bw, interpret=True)
    yr, sr = ref.rglru_scan_ref(a, x, jnp.zeros((B, w)))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype) * 3)
    np.testing.assert_allclose(np.asarray(s_last, np.float32),
                               np.asarray(sr, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype) * 3)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,h,d,bs", [(48, 2, 16, 16), (50, 3, 16, 16),
                                      (64, 1, 32, 32)])
def test_rwkv6_scan(s, h, d, bs):
    B = 2
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, s, h, d))
    k = jax.random.normal(ks[1], (B, s, h, d))
    v = jax.random.normal(ks[2], (B, s, h, d))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, s, h, d)))
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    o, sl = rwkv6_scan(r, k, v, w, u, bs=bs, interpret=True)
    orf, slr = ref.rwkv6_scan_ref(r, k, v, w, u, jnp.zeros((B, h, d, d)))
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sl), np.asarray(slr),
                               rtol=1e-4, atol=1e-4)


def test_rwkv6_scan_matches_model_block():
    """Kernel output must equal the model's wkv_scan given same inputs."""
    from repro.models.rwkv6 import wkv_scan
    B, S, H, D = 1, 40, 2, 16
    ks = jax.random.split(KEY, 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, D)))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    o1, s1 = rwkv6_scan(r, k, v, w, u, bs=8, interpret=True)
    o2, s2 = wkv_scan(r, k, v, w, u, jnp.zeros((B, H, D, D)))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# moe grouped GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,c,k,n", [(4, 64, 64, 64), (8, 100, 64, 96),
                                     (2, 33, 200, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(e, c, k, n, dtype):
    x = jax.random.normal(KEY, (e, c, k), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (e, k, n),
                          jnp.float32).astype(dtype)
    out = moe_gmm(x, w, block=Block(32, 64, 64), interpret=True)
    expect = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype) * np.abs(np.asarray(expect)).max())


# ---------------------------------------------------------------------------
# interpret mode: the CPU default, never on a TPU
# ---------------------------------------------------------------------------

def test_interpret_mode_is_refused_on_tpu(monkeypatch):
    from repro.core.oracle import MeasuredOracle, MeasurementConfig
    from repro.kernels.ops import interpret_mode
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert interpret_mode() is True                  # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    assert interpret_mode(False) is False
    with pytest.raises(RuntimeError, match="TPU"):
        interpret_mode(True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="TPU"):
        interpret_mode()
    with pytest.raises(RuntimeError, match="TPU"):
        MeasuredOracle(MeasurementConfig(interpret=True))._interpret()
