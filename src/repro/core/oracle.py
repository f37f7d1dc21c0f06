"""Pluggable latency oracles — the one answer site for "what does a
program cost".

CPrune's defining claim is that pruning decisions are informed by the
*compiler's measured execution* of candidate programs (the paper builds
and times every candidate with TVM on the target phone), not an analytic
proxy. This module makes that a swappable backend behind one protocol:

``analytic``
    The closed-form roofline model in :mod:`repro.core.cost_model`,
    evaluated over the whole candidate grid in one NumPy pass. The
    default — bit-identical to the pre-oracle scoring path.

``measured``
    Compiles and times the repo's own Pallas kernels
    (:mod:`repro.kernels.matmul` for plain GEMMs,
    :mod:`repro.kernels.moe_gmm` for batched/expert GEMMs) —
    ``pl.pallas_call`` in interpret mode on CPU, real compiled timings
    when a TPU backend is present. Measurements use warmup runs, k
    timed repeats with the extremes trimmed, and a median; large
    problems are measured on a clipped grid (a few grid steps per dim)
    and extrapolated by the exact grid-step ratio, the way per-block
    timings extrapolate in a tiled kernel. The analytic model pre-ranks
    the grid and only the shortlist is ever built and timed — the
    classic cost-model-guided measurement loop of AutoTVM/Ansor.

``replay``
    Deterministic record/playback of a ``measured`` run's log as a JSON
    artifact, so tests and CI exercise the measured code path — same
    shortlisting, same winner selection — without hardware variance.

Every consumer (tuner grid search, untuned programs, fixed-op latency,
attention/scan estimates) asks the *active* oracle; the tuning caches key
on :meth:`LatencyOracle.fingerprint`, so winners never cross backends.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import itertools
import json
import os
import time
from typing import Deque, Dict, Iterator, List, Optional, Protocol, Tuple, \
    Union, runtime_checkable

import numpy as np

from repro.core import cost_model
from repro.core.cost_model import Block

_LOG_VERSION = 1


@runtime_checkable
class LatencyOracle(Protocol):
    """What the tuner/latency stack needs to cost a program.

    ``score_grid`` is the tuner's inner loop (whole candidate grid at
    once); ``score_one`` costs a single fixed block config (untuned
    programs); the remaining methods cost the non-GEMM fixed ops so the
    latency model never reads :mod:`cost_model` directly.
    """

    name: str

    def fingerprint(self) -> Tuple: ...

    def score_grid(self, m: int, k: int, n: int,
                   bm: np.ndarray, bk: np.ndarray, bn: np.ndarray, *,
                   dtype_bytes: int, batch: int, epilogue_ops: int,
                   hw: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]],
                   stats=None) -> np.ndarray: ...

    def score_one(self, m: int, k: int, n: int, block: Block, *,
                  dtype_bytes: int, batch: int, epilogue_ops: int,
                  stats=None) -> float: ...

    def attention_cost(self, batch: int, sq: int, sk: int, n_heads: int,
                       head_dim: int, *, window: int = 0,
                       dtype_bytes: int = 2) -> float: ...

    def scan_cost(self, batch: int, seq: int, width: int,
                  state_bytes: int) -> float: ...

    def hbm_bytes_cost(self, n_bytes: int) -> float: ...

    def collective_cost(self, n_bytes: int, tp: int, *,
                        op: str = "all_reduce") -> float: ...


class AnalyticOracle:
    """The closed-form cost model of the *active* target constants —
    exactly the pre-oracle scoring path (enforced bit-identical by
    tests/test_oracle.py and the table1/fig8 golden checks)."""

    name = "analytic"

    def fingerprint(self) -> Tuple:
        return ("analytic",)

    def score_grid(self, m, k, n, bm, bk, bn, *, dtype_bytes, batch,
                   epilogue_ops, hw, stats=None) -> np.ndarray:
        return cost_model.matmul_cost_grid(
            m, k, n, bm, bk, bn, dtype_bytes=dtype_bytes, batch=batch,
            epilogue_ops=epilogue_ops, hw=hw)

    def score_one(self, m, k, n, block, *, dtype_bytes, batch,
                  epilogue_ops, stats=None) -> float:
        return cost_model.matmul_cost(m, k, n, block,
                                      dtype_bytes=dtype_bytes, batch=batch,
                                      epilogue_ops=epilogue_ops)

    def attention_cost(self, batch, sq, sk, n_heads, head_dim, *,
                       window=0, dtype_bytes=2) -> float:
        return cost_model.attention_cost(batch, sq, sk, n_heads, head_dim,
                                         window=window,
                                         dtype_bytes=dtype_bytes)

    def paged_attention_cost(self, batch, kv_len, n_heads, head_dim, *,
                             n_kv_heads=1, block_size=16,
                             dtype_bytes=2) -> float:
        """Decode attention through a block table (one query token per
        row, ``kv_len`` cached positions). Analytically identical to the
        dense decode estimate — paging changes *where* KV rows live, not
        how many bytes/FLOPs one step touches — so analytic fingerprints
        (and every tuning cache keyed on them) are unchanged.
        ``n_kv_heads``/``block_size`` only matter to measuring backends,
        which time the real kernel under those shapes."""
        del n_kv_heads, block_size
        return cost_model.attention_cost(batch, 1, kv_len, n_heads,
                                         head_dim, window=0,
                                         dtype_bytes=dtype_bytes)

    def scan_cost(self, batch, seq, width, state_bytes) -> float:
        return cost_model.scan_cost(batch, seq, width, state_bytes)

    def hbm_bytes_cost(self, n_bytes) -> float:
        return n_bytes / cost_model.HBM_BW

    def collective_cost(self, n_bytes, tp, *, op="all_reduce") -> float:
        """One TP collective (ring over ICI). Analytic in every backend —
        collectives are not Pallas programs a measuring oracle could time
        on a single host — so fingerprints (and every tuning cache keyed
        on them) are unchanged."""
        return cost_model.collective_cost(n_bytes, tp, op=op)


@dataclasses.dataclass(frozen=True)
class MeasurementConfig:
    """How the measured backend times a candidate program."""

    warmup: int = 1          # untimed runs before the clock starts
    repeats: int = 5         # timed runs per candidate
    trim: int = 1            # drop this many fastest+slowest before median
    measure_top_k: int = 4   # analytic-shortlisted candidates actually built
    max_grid_steps: int = 2  # grid steps measured per dim (then extrapolated)
    # None = interpret unless on a TPU; True on a TPU raises (the
    # measured backend never times the interpreter on the chip)
    interpret: Optional[bool] = None

    def fingerprint(self) -> Tuple:
        return (self.warmup, self.repeats, self.trim, self.measure_top_k,
                self.max_grid_steps, self.interpret)

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


class MeasurementLog:
    """A map from measurement key to seconds, with JSON persistence —
    the replay artifact (and the measured backend's in-run memo).

    Long-running serve processes record into a log continuously, so the
    store can be bounded: ``max_entries`` caps the key count with LRU
    eviction (``lookup`` refreshes recency, the oldest key is dropped on
    overflow — the same discipline as ``latency._FIXED_CACHE``), and
    ``evicted`` counts what was dropped. Independently, every ``record``
    appends to a per-key observation window (``window(key)``, newest
    last, at most ``window_size`` samples) so drift detectors can reason
    about *recent* behaviour instead of a single overwritten scalar.
    Bounds and windows are runtime-only: ``digest``/``save``/``load``
    operate on ``entries`` exactly as before, so replay-artifact digests
    are unaffected.
    """

    def __init__(self, config: Optional[MeasurementConfig] = None, *,
                 max_entries: Optional[int] = None, window_size: int = 32):
        self.config = config or MeasurementConfig()
        self.entries: "collections.OrderedDict[str, float]" = \
            collections.OrderedDict()
        self.max_entries = max_entries
        self.window_size = max(1, int(window_size))
        self.history: Dict[str, Deque[float]] = {}
        self.evicted = 0
        # where this log last touched disk (set by save/load) — lets a
        # session checkpoint round-trip its replay artifact by path
        self.path: Optional[str] = None

    def __len__(self) -> int:
        return len(self.entries)

    def copy(self) -> "MeasurementLog":
        """Snapshot of the current entries (same config/bounds, no path)."""
        new = MeasurementLog(self.config, max_entries=self.max_entries,
                             window_size=self.window_size)
        new.entries = collections.OrderedDict(self.entries)
        new.history = {k: collections.deque(v, maxlen=self.window_size)
                       for k, v in self.history.items()}
        return new

    @staticmethod
    def gemm_key(m: int, k: int, n: int, batch: int, dtype_bytes: int,
                 block: Block) -> str:
        return (f"gemm:{m}:{k}:{n}:{batch}:{dtype_bytes}:"
                f"{block.bm}:{block.bk}:{block.bn}")

    @staticmethod
    def paged_attention_key(batch: int, kv_len: int, n_heads: int,
                            head_dim: int, n_kv_heads: int, block_size: int,
                            dtype_bytes: int) -> str:
        return (f"paged_attn:{batch}:{kv_len}:{n_heads}:{head_dim}:"
                f"{n_kv_heads}:{block_size}:{dtype_bytes}")

    @staticmethod
    def step_key(tag: str, max_batch: int, max_seq: int) -> str:
        """Key for a serve-time *observed* decode step (whole model, one
        token, ``max_batch`` rows, ``max_seq``-deep cache). Recorded by
        ``ServeEngine.record_measurements``; read back by
        ``DeploymentArtifact.recalibrated_oracle`` to close the
        plan -> serve -> replan loop. Never consulted by the replay
        scorer itself (which looks up ``gemm:`` keys only)."""
        return f"serve_step:{tag}:{max_batch}:{max_seq}"

    def scaled(self, factor: float, *, prefix: str = "gemm:"
               ) -> "MeasurementLog":
        """A new log with every ``prefix``-keyed entry multiplied by
        ``factor`` (other entries copied verbatim) — the recalibration
        primitive: serve-time observation / plan-time prediction becomes
        the factor, and a :class:`ReplayOracle` over the result predicts
        what serving actually measured."""
        new = MeasurementLog(self.config)
        new.entries = collections.OrderedDict(
            (k, v * factor if k.startswith(prefix) else v)
            for k, v in self.entries.items())
        return new

    def record(self, key: str, seconds: float) -> None:
        secs = float(seconds)
        if key in self.entries:
            self.entries.move_to_end(key)
        self.entries[key] = secs
        self.history.setdefault(
            key, collections.deque(maxlen=self.window_size)).append(secs)
        if self.max_entries is not None:
            while len(self.entries) > self.max_entries:
                old, _ = self.entries.popitem(last=False)
                self.history.pop(old, None)
                self.evicted += 1

    def lookup(self, key: str) -> Optional[float]:
        secs = self.entries.get(key)
        if secs is not None:
            self.entries.move_to_end(key)   # refresh LRU recency
        return secs

    def window(self, key: str) -> List[float]:
        """Recent observations recorded under ``key`` (newest last, at
        most ``window_size`` of them)."""
        return list(self.history.get(key, ()))

    def digest(self) -> str:
        blob = json.dumps([self.config.to_dict(),
                           sorted(self.entries.items())], sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def save(self, path: str) -> int:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": _LOG_VERSION,
                       "config": self.config.to_dict(),
                       "entries": self.entries}, f, indent=1)
        os.replace(tmp, path)
        self.path = path
        return len(self.entries)

    @classmethod
    def load(cls, path: str) -> "MeasurementLog":
        with open(path) as f:
            blob = json.load(f)
        if blob.get("version") != _LOG_VERSION:
            raise ValueError(f"unsupported measurement log version "
                             f"{blob.get('version')!r} in {path}")
        log = cls(MeasurementConfig(**blob["config"]))
        log.entries = collections.OrderedDict(
            (k, float(v)) for k, v in blob["entries"].items())
        log.path = path
        return log


def _trimmed_median(times, trim: int) -> float:
    ts = sorted(times)
    if trim > 0 and len(ts) > 2 * trim:
        ts = ts[trim:-trim]
    mid = len(ts) // 2
    if len(ts) % 2:
        return ts[mid]
    return 0.5 * (ts[mid - 1] + ts[mid])


@dataclasses.dataclass(frozen=True)
class DriftReport:
    """Predicted-vs-observed latency for one measurement key.

    ``rel_error`` is ``(measured - predicted) / predicted`` — positive
    means the target is *slower* than the plan-time oracle believed
    (the direction that breaks latency budgets). ``window`` is how many
    recent observations backed ``measured_s`` (their median)."""

    key: str
    predicted_s: float
    measured_s: float
    rel_error: float
    window: int

    @property
    def magnitude(self) -> float:
        return abs(self.rel_error)


def score_drift(log: MeasurementLog, key: str, predicted_s: float, *,
                min_window: int = 2) -> Optional[DriftReport]:
    """Score how far serve-time observations under ``key`` have drifted
    from a plan-time prediction.

    Uses the median of the log's recent observation window (not the
    latest sample) so a single straggler step doesn't trip a replan.
    Returns ``None`` when there is not yet enough evidence: fewer than
    ``min_window`` observations, or a non-positive prediction."""
    window = log.window(key)
    if len(window) < max(1, min_window) or predicted_s <= 0.0:
        return None
    measured = _trimmed_median(window, 0)
    return DriftReport(key=key, predicted_s=float(predicted_s),
                       measured_s=measured,
                       rel_error=(measured - predicted_s) / predicted_s,
                       window=len(window))


class _MeasurementOracle:
    """Shared scoring logic for the measured and replay backends: analytic
    pre-ranking shortlists the grid, then each shortlisted candidate's
    kernel seconds come from ``_gemm_seconds`` (a real timer or the log).
    Non-GEMM fixed ops (attention, scans, HBM gathers) and the fused
    epilogue term stay analytic in both — deterministic, so a replay of a
    measured run reproduces the exact same scores.
    """

    def __init__(self, config: MeasurementConfig):
        self.config = config
        self._analytic = AnalyticOracle()

    # subclasses: obtain kernel seconds for one (possibly clipped) problem
    def _gemm_seconds(self, m, k, n, batch, dtype_bytes, block,
                      stats=None) -> float:
        raise NotImplementedError

    def _epilogue_s(self, m, n, batch, epilogue_ops, block) -> float:
        if not epilogue_ops:
            return 0.0
        gm, gn = -(-m // block.bm), -(-n // block.bn)
        bm_h = -(-block.bm // cost_model.SUBLANE) * cost_model.SUBLANE
        bn_h = -(-block.bn // cost_model.LANE) * cost_model.LANE
        return cost_model.epilogue_cost(batch, epilogue_ops, gm, bm_h,
                                        gn, bn_h)

    def score_grid(self, m, k, n, bm, bk, bn, *, dtype_bytes, batch,
                   epilogue_ops, hw, stats=None) -> np.ndarray:
        base = self._analytic.score_grid(
            m, k, n, bm, bk, bn, dtype_bytes=dtype_bytes, batch=batch,
            epilogue_ops=epilogue_ops, hw=hw)
        k_top = max(1, self.config.measure_top_k)
        shortlist = np.argsort(base, kind="stable")[:k_top]
        out = np.full(base.shape, np.inf)
        for i in shortlist:
            blk = Block(int(bm[i]), int(bk[i]), int(bn[i]))
            out[i] = self._gemm_seconds(m, k, n, batch, dtype_bytes, blk,
                                        stats=stats) \
                + self._epilogue_s(m, n, batch, epilogue_ops, blk)
        return out

    def score_one(self, m, k, n, block, *, dtype_bytes, batch,
                  epilogue_ops, stats=None) -> float:
        return self._gemm_seconds(m, k, n, batch, dtype_bytes, block,
                                  stats=stats) \
            + self._epilogue_s(m, n, batch, epilogue_ops, block)

    # non-GEMM fixed ops: analytic in every backend (the repo has no
    # measured path for gathers/scans yet; keeping them analytic keeps
    # measured vs replay deterministic-by-construction)
    def attention_cost(self, *a, **kw) -> float:
        return self._analytic.attention_cost(*a, **kw)

    def paged_attention_cost(self, *a, **kw) -> float:
        return self._analytic.paged_attention_cost(*a, **kw)

    def scan_cost(self, *a, **kw) -> float:
        return self._analytic.scan_cost(*a, **kw)

    def hbm_bytes_cost(self, n_bytes) -> float:
        return self._analytic.hbm_bytes_cost(n_bytes)

    def collective_cost(self, *a, **kw) -> float:
        return self._analytic.collective_cost(*a, **kw)


# distinguishes each *recording* MeasuredOracle in cache fingerprints:
# a recorder must observe every tuning problem itself (warm ProgramCache /
# fixed-latency entries from an earlier measured run would otherwise
# starve the log and ship an incomplete replay artifact)
_RECORDING_IDS = itertools.count(1)


class MeasuredOracle(_MeasurementOracle):
    """Times the repo's own Pallas kernels for every shortlisted candidate.

    Off the TPU the kernels run with ``interpret=True`` (the same code
    path a TPU compiles); on a TPU backend they are real compiled
    timings, and forcing interpret mode there raises. Pass
    ``record=MeasurementLog()`` to capture every measurement for later
    :class:`ReplayOracle` playback — the log also memoizes within the
    run, so a problem is never timed twice.
    """

    name = "measured"

    def __init__(self, config: Optional[MeasurementConfig] = None, *,
                 record: Optional[MeasurementLog] = None):
        super().__init__(config or MeasurementConfig())
        if record is not None and record.config != self.config:
            raise ValueError("record log's MeasurementConfig does not match "
                             "the oracle's")
        self.record = record
        self._recording_id = next(_RECORDING_IDS) if record is not None \
            else None

    def fingerprint(self) -> Tuple:
        fp = ("measured",) + self.config.fingerprint()
        if self._recording_id is not None:
            # each recorder is its own cache identity — see _RECORDING_IDS
            fp += ("recording", self._recording_id)
        return fp

    def _interpret(self) -> bool:
        from repro.kernels.ops import interpret_mode
        return interpret_mode(self.config.interpret)

    def _clipped(self, m, k, n, batch, block):
        """Measured problem dims: at most ``max_grid_steps`` grid steps per
        dim (and 2 experts), plus the exact step-count ratio to scale the
        measured time back up — per-block extrapolation, not a model."""
        cap = max(1, self.config.max_grid_steps)
        gm, gk, gn = -(-m // block.bm), -(-k // block.bk), -(-n // block.bn)
        gm_c, gk_c, gn_c = min(gm, cap), min(gk, cap), min(gn, cap)
        b_c = min(batch, 2)
        scale = (gm * gk * gn * batch) / (gm_c * gk_c * gn_c * b_c)
        return (gm_c * block.bm, gk_c * block.bk, gn_c * block.bn, b_c,
                scale)

    def _time_kernel(self, m, k, n, batch, dtype_bytes, block) -> float:
        import jax
        import jax.numpy as jnp

        from repro.kernels import matmul as _mm
        from repro.kernels import moe_gmm as _gmm

        dtype = jnp.bfloat16 if dtype_bytes <= 2 else jnp.float32
        interpret = self._interpret()
        key = jax.random.PRNGKey(0)
        if batch == 1:
            a = jax.random.normal(key, (m, k), jnp.float32).astype(dtype)
            b = jnp.ones((k, n), dtype)
            fn = jax.jit(lambda x, y: _mm.matmul(
                x, y, block=block, interpret=interpret))
        else:
            a = jax.random.normal(key, (batch, m, k),
                                  jnp.float32).astype(dtype)
            b = jnp.ones((batch, k, n), dtype)
            fn = jax.jit(lambda x, y: _gmm.moe_gmm(
                x, y, block=block, interpret=interpret))
        for _ in range(max(0, self.config.warmup)):
            jax.block_until_ready(fn(a, b))
        times = []
        for _ in range(max(1, self.config.repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(a, b))
            times.append(time.perf_counter() - t0)
        return _trimmed_median(times, self.config.trim)

    def _gemm_seconds(self, m, k, n, batch, dtype_bytes, block,
                      stats=None) -> float:
        key = MeasurementLog.gemm_key(m, k, n, batch, dtype_bytes, block)
        if self.record is not None:
            hit = self.record.lookup(key)
            if hit is not None:
                return hit
        m_c, k_c, n_c, b_c, scale = self._clipped(m, k, n, batch, block)
        t0 = time.perf_counter()
        secs = self._time_kernel(m_c, k_c, n_c, b_c, dtype_bytes, block) \
            * scale
        if stats is not None:
            stats.measured_programs += 1
            stats.measure_wall_s += time.perf_counter() - t0
        if self.record is not None:
            self.record.record(key, secs)
        return secs

    def _time_paged_attention(self, batch, n_chunks, n_heads, head_dim,
                              n_kv_heads, block_size, dtype_bytes) -> float:
        import jax
        import jax.numpy as jnp

        from repro.kernels.paged_attention import paged_attention

        dtype = jnp.bfloat16 if dtype_bytes <= 2 else jnp.float32
        interpret = self._interpret()
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (batch, n_heads, head_dim),
                              jnp.float32).astype(dtype)
        n_blocks = batch * n_chunks
        k_pool = jax.random.normal(key, (n_blocks, block_size, n_kv_heads,
                                         head_dim), jnp.float32).astype(dtype)
        v_pool = jnp.ones_like(k_pool)
        table = jnp.arange(n_blocks, dtype=jnp.int32).reshape(batch, n_chunks)
        lens = jnp.full((batch,), n_chunks * block_size, jnp.int32)
        fn = jax.jit(lambda *a: paged_attention(*a, interpret=interpret))
        for _ in range(max(0, self.config.warmup)):
            jax.block_until_ready(fn(q, k_pool, v_pool, table, lens))
        times = []
        for _ in range(max(1, self.config.repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k_pool, v_pool, table, lens))
            times.append(time.perf_counter() - t0)
        return _trimmed_median(times, self.config.trim)

    def paged_attention_cost(self, batch, kv_len, n_heads, head_dim, *,
                             n_kv_heads=1, block_size=16,
                             dtype_bytes=2) -> float:
        """Times the real paged-decode kernel (the grid is (batch, kv
        chunks) — clip chunks and batch, extrapolate by the exact
        step-count ratio, exactly like :meth:`_clipped` for GEMMs).
        Memoized under the full-dimension key in ``record``, so a replay
        of this run reproduces the same step predictions."""
        mkey = MeasurementLog.paged_attention_key(
            batch, kv_len, n_heads, head_dim, n_kv_heads, block_size,
            dtype_bytes)
        if self.record is not None:
            hit = self.record.lookup(mkey)
            if hit is not None:
                return hit
        cap = max(1, self.config.max_grid_steps)
        n_chunks = max(1, -(-int(kv_len) // block_size))
        nc_c, b_c = min(n_chunks, cap), min(batch, 2)
        scale = (n_chunks * batch) / (nc_c * b_c)
        secs = self._time_paged_attention(
            b_c, nc_c, n_heads, head_dim, n_kv_heads, block_size,
            dtype_bytes) * scale
        if self.record is not None:
            self.record.record(mkey, secs)
        return secs


class ReplayOracle(_MeasurementOracle):
    """Plays a recorded :class:`MeasurementLog` back deterministically:
    same analytic shortlist (the log pins the MeasurementConfig), same
    per-candidate seconds, hence the same winners and the same CPrune
    history as the run that recorded it — without hardware variance."""

    name = "replay"

    def __init__(self, log: Union[MeasurementLog, str]):
        if isinstance(log, str):
            log = MeasurementLog.load(log)
        super().__init__(log.config)
        self.log = log
        self._digest = log.digest()

    @classmethod
    def from_file(cls, path: str) -> "ReplayOracle":
        return cls(path)

    def fingerprint(self) -> Tuple:
        return ("replay", self._digest) + self.config.fingerprint()

    def _gemm_seconds(self, m, k, n, batch, dtype_bytes, block,
                      stats=None) -> float:
        key = MeasurementLog.gemm_key(m, k, n, batch, dtype_bytes, block)
        secs = self.log.lookup(key)
        if secs is None:
            raise KeyError(
                f"measurement {key!r} not in the replay log ({len(self.log)} "
                f"entries) — the log was recorded for a different model/"
                f"workload/target; re-record with MeasuredOracle(record=...) "
                f"or session.calibrate()")
        if stats is not None:
            stats.replay_hits += 1
        return secs

    def paged_attention_cost(self, batch, kv_len, n_heads, head_dim, *,
                             n_kv_heads=1, block_size=16,
                             dtype_bytes=2) -> float:
        """Replays a recorded paged-kernel timing when the log has one;
        falls back to the analytic estimate otherwise. Unlike ``gemm:``
        keys this is a soft lookup — logs recorded before the paged
        layout existed (or on contiguous-only workloads) stay valid."""
        secs = self.log.lookup(MeasurementLog.paged_attention_key(
            batch, kv_len, n_heads, head_dim, n_kv_heads, block_size,
            dtype_bytes))
        if secs is not None:
            return secs
        return self._analytic.paged_attention_cost(
            batch, kv_len, n_heads, head_dim, n_kv_heads=n_kv_heads,
            block_size=block_size, dtype_bytes=dtype_bytes)


# ---------------------------------------------------------------------------
# Active-oracle plumbing (mirrors the target_activation contract)
# ---------------------------------------------------------------------------

ANALYTIC = AnalyticOracle()

_ACTIVE: LatencyOracle = ANALYTIC


def active_oracle() -> LatencyOracle:
    return _ACTIVE


@contextlib.contextmanager
def use_oracle(oracle: Union[str, LatencyOracle, None]
               ) -> Iterator[LatencyOracle]:
    """Install ``oracle`` as the process-wide scoring backend for the
    body; restores the previous one on exit, exceptions included."""
    global _ACTIVE
    old, _ACTIVE = _ACTIVE, get_oracle(oracle)
    try:
        yield _ACTIVE
    finally:
        _ACTIVE = old


def get_oracle(spec: Union[str, LatencyOracle, None], *,
               log: Union[MeasurementLog, str, None] = None,
               config: Optional[MeasurementConfig] = None) -> LatencyOracle:
    """Resolve an oracle: ``None`` -> the active one, a name
    (``analytic``/``measured``/``replay``) -> a backend instance, or any
    :class:`LatencyOracle` implementation passed through. ``replay``
    requires ``log`` (a :class:`MeasurementLog` or a JSON path)."""
    if spec is None:
        return _ACTIVE
    if not isinstance(spec, str):
        if isinstance(spec, LatencyOracle):
            return spec
        raise TypeError(f"oracle must be a backend name or implement the "
                        f"LatencyOracle protocol, got {type(spec).__name__}")
    if spec == "analytic":
        return ANALYTIC
    if spec == "measured":
        return MeasuredOracle(config)
    if spec == "replay":
        if log is None:
            raise ValueError("oracle='replay' needs log=<MeasurementLog or "
                             "path> (record one with session.calibrate() or "
                             "MeasuredOracle(record=MeasurementLog()))")
        return ReplayOracle(log)
    raise KeyError(f"unknown oracle {spec!r}; "
                   f"backends: ['analytic', 'measured', 'replay']")
