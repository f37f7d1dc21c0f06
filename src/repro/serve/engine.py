"""Serving engine: prefill + decode behind a stepped scheduler core.

Design (vLLM-style, sized down to what a CPU example can drive):
  * a global budget of ``max_batch`` decode slots, shared by every live
    :class:`~repro.serve.scheduler.SlotGroup` (one admitted cohort of
    equal-length prompts mid-decode);
  * admission, prompt-length bucketing, and slot compaction live in
    :mod:`repro.serve.scheduler`; the engine is the execution half —
    :meth:`step` runs exactly one scheduling quantum (admit one cohort,
    or advance every live group one decode token) and never blocks on a
    queue, :meth:`serve_forever` loops it under an optional deadline;
  * finished requests release their slots mid-decode (groups compact to
    the surviving rows), so the next cohort prefils while earlier
    groups are still decoding — continuous batching at group
    granularity instead of the old blocking wave drain;
  * sampling: greedy or temperature, per request;
  * :meth:`run` is the legacy front door: a thin wrapper over
    ``serve_forever()`` with bit-identical greedy outputs.

Spans (:mod:`repro.util.spans`) time the engine's host work on the
profiler's clock and total it in ``stats()["spans"]``; their names are
fixed, each indented under the span that holds it:

  ``serve.step``         one :meth:`step`
    ``serve.select``     the scheduler's choice of a cohort
    ``serve.admit``      one admission (``cohort``, ``width``, ``rids``)
      ``serve.prefill`` ``serve.kv_blocks`` ``serve.kv_scatter``
      ``serve.sample`` ``serve.read_tokens`` ``serve.retire``
    ``serve.tick``       one decode tick over every live group
      ``serve.decode``   one group's decode call (``cohort``, ``width``,
                         ``pos``)
        ``serve.kv_table`` ``serve.dispatch`` ``serve.device_wait``
        ``serve.read_tokens`` ``serve.retire``
      ``serve.chunk``    one chunk of a chunked prefill (``cohort``,
                         ``pos``); the last one also holds
        ``serve.sample`` ``serve.read_tokens`` ``serve.retire``

A decode call is one jitted program that runs the model step and samples
inside it (:func:`sample_tokens`), so ``serve.sample`` times only the
first token of an admission or of a finished chunked prefill.
``serve.dispatch`` ends when the jitted call returns, ``serve.device_wait``
when its sampled tokens are ready; ``serve.read_tokens`` is one transfer
of those tokens to the host. A cohort's id joins its admission to its
decode calls.

Engines optionally record their measured decode-step seconds into a
:class:`~repro.core.oracle.MeasurementLog` (``measurements=``), which is
how a serve run feeds the latency oracle that planned it — see
``DeploymentArtifact.recalibrated_oracle``.

For the production mesh the same engine drives the sharded serve_step
(launch/serve.py); here everything stays single-device jit.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, LOCAL_ATTN, ModelConfig
from repro.core.oracle import MeasurementLog
from repro.models.model import Model
from repro.models.paged_cache import (RESERVED_BLOCKS, SCRATCH_BLOCK,
                                      BlockAllocator, init_paged_pools,
                                      paged_compatible,
                                      scatter_prefill_blocks)
from repro.serve.scheduler import (PagedSlotGroup, Scheduler,
                                   SchedulerConfig, SlotGroup)
from repro.util.faults import FaultInjector, StragglerMonitor
from repro.util.spans import Spans


def sample_tokens(logits: jax.Array, temps: jax.Array, key: jax.Array):
    """Next tokens from last-position logits ``(W, 1, V)``: greedy where
    ``temps`` ``(W,)`` is 0, a categorical draw at that temperature
    elsewhere. Splits ``key`` once and returns ``(tokens (W, 1) int32,
    new key)``. Pure, so it traces into the decode programs."""
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits[:, 0], axis=-1)
    noisy = jax.random.categorical(
        sub, logits[:, 0] / jnp.maximum(temps[:, None], 1e-6))
    tok = jnp.where(temps > 0, noisy, greedy)
    return tok[:, None].astype(jnp.int32), key


def sampled_decode_steps(model: Model):
    """The model's contiguous and paged decode steps, each followed by
    :func:`sample_tokens` in the same program: they take ``temps`` and
    the key after the step's own arguments and return ``(tokens, caches
    or pools, new key)``. The names keep the programs' names
    (``jit_decode_step``, ``jit_decode_step_paged``)."""
    def decode_step(params, cur, caches, temps, key):
        logits, caches = model.decode_step(params, cur, caches)
        tok, key = sample_tokens(logits, temps, key)
        return tok, caches, key

    def decode_step_paged(params, cur, pools, table, pos, temps, key):
        logits, pools = model.decode_step_paged(params, cur, pools, table,
                                                pos)
        tok, key = sample_tokens(logits, temps, key)
        return tok, pools, key
    return decode_step, decode_step_paged


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    # per-request SLO (consumed by repro.serve.router.Router; the plain
    # engine ignores both): route to the cheapest artifact whose recorded
    # accuracy >= accuracy_floor and predicted latency <= latency_budget_s
    latency_budget_s: Optional[float] = None
    accuracy_floor: Optional[float] = None
    # filled by the engine / router:
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    routed_to: Optional[str] = None
    slo_infeasible: bool = False
    # fleet supervision (repro.serve.fleet): re-queue/reject accounting.
    # A request ends in exactly one of three states: done, failed
    # (explicit, with a reason), or still in flight — never silently lost.
    retries: int = 0
    failed: bool = False
    fail_reason: Optional[str] = None

    @property
    def deadline_s(self) -> float:
        """Absolute wall-clock deadline (inf when unbudgeted or not yet
        submitted — the budget clock starts at first submit)."""
        if self.latency_budget_s is None or not self.t_submit:
            return float("inf")
        return self.t_submit + self.latency_budget_s

    def reset_for_retry(self) -> None:
        """Forget partial progress so a re-queued request re-prefils from
        its original prompt (greedy decode then reproduces the exact
        fault-free output). The submit time — and therefore the deadline
        — is deliberately preserved."""
        self.output = []
        self.done = False
        self.t_first_token = 0.0
        self.t_done = 0.0
        self.retries += 1


class ServeEngine:
    """The stepped serving engine (the ``Engine`` half of the redesign;
    :class:`~repro.serve.scheduler.Scheduler` is the policy half)."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 512, seed: int = 0,
                 predicted_step_s: Optional[float] = None,
                 scheduler: Union[SchedulerConfig, str, None] = None,
                 measurements: Optional[MeasurementLog] = None,
                 measurement_tag: Optional[str] = None,
                 faults: Optional[FaultInjector] = None,
                 fault_tag: Optional[str] = None,
                 straggler: Optional[StragglerMonitor] = None,
                 kv_pool_blocks: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.model = Model(cfg)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.key = jax.random.PRNGKey(seed)
        if scheduler is None:
            scheduler = SchedulerConfig()
        elif isinstance(scheduler, str):
            scheduler = SchedulerConfig(policy=scheduler)
        if scheduler.policy == "wave" and (scheduler.compact != "off"
                                          or scheduler.kv_layout != "contiguous"):
            # the legacy baseline verbatim: no compaction, contiguous KV
            scheduler = dataclasses.replace(scheduler, compact="off",
                                            kv_layout="contiguous",
                                            prefill_chunk=0)
        if scheduler.kv_layout == "paged" and not paged_compatible(cfg):
            # recurrent mixers / sliding windows have no block-table
            # analogue here — serve them from the contiguous layout
            scheduler = dataclasses.replace(scheduler,
                                            kv_layout="contiguous",
                                            prefill_chunk=0)
        self.kv_layout = scheduler.kv_layout
        self.scheduler = Scheduler(scheduler)
        self.groups: List[SlotGroup] = []
        self.done: List[Request] = []
        # the latency oracle's prediction for one decode step of this
        # model at max_batch (PruningSession.serve computes it); stats()
        # report it against the measured wall-clock per step so the
        # oracle's error on the *real* executing model is observable
        self.predicted_step_s = predicted_step_s
        # a serve run can record its observed decode step into a
        # MeasurementLog and hand it back to the oracle that planned it
        self.measurements = measurements
        self.measurement_tag = measurement_tag or cfg.name
        # fault injection (repro.util.faults): the engine fires the
        # "decode"/"prefill" points, tagged so a fleet-shared injector
        # can target one replica; straggler watches decode-tick wall time
        self.faults = faults
        self.fault_tag = fault_tag or self.measurement_tag
        self.straggler = straggler
        self.spans = Spans()
        self._next_cohort = 0
        # physically copied cache rows (engine-owned; every SlotGroup's
        # compact() increments it — the paged layout's zero-copy gate)
        self._copy_counter = {"rows": 0}
        # peak-KV accounting: bytes one token position costs across every
        # attention layer's K+V
        n_attn = sum(1 for k in cfg.layer_kinds() if k in (ATTN, LOCAL_ATTN))
        self._kv_row_bytes = (n_attn * 2 * cfg.n_kv_heads * cfg.head_dim
                              * jnp.dtype(cfg.dtype).itemsize)
        self._live_kv_slots = 0   # contiguous: currently allocated slots
        self._peak_kv_slots = 0
        self.kv_allocator: Optional[BlockAllocator] = None
        decode, decode_paged = sampled_decode_steps(self.model)
        if self.kv_layout == "paged":
            sc = self.scheduler.config
            if sc.prefill_chunk and (cfg.rope == "mrope"
                                     or cfg.frontend != "none"):
                raise ValueError(
                    "prefill_chunk requires a text-only rope model (mrope "
                    "positions and frontend inputs are not chunkable)")
            bs = sc.page_size
            n_blocks = kv_pool_blocks if kv_pool_blocks is not None else \
                RESERVED_BLOCKS + max_batch * (-(-max_seq // bs))
            self.kv_allocator = BlockAllocator(n_blocks)
            self._pools = init_paged_pools(self.model, n_blocks, bs)
            # donate the pools: the in-place block writes then update the
            # buffers directly instead of copying the whole pool per step
            self._decode_paged = jax.jit(decode_paged,
                                         donate_argnums=(2, 6))
            self._chunk_step = jax.jit(self.model.prefill_chunk_paged,
                                       donate_argnums=2)
            # prefill padded to the cohort's block multiple, not max_seq —
            # short prompts don't pay full-length attention at admission
            def prefill_padded(params, batch, padded_len):
                return self.model.prefill(params, batch, padded_len)
            self._prefill_padded = jax.jit(prefill_padded, static_argnums=2)
        # paged-KV sanitizer (repro.analysis.kv_sanitizer) at every
        # quantum boundary: SchedulerConfig(debug_kv=True), or
        # REPRO_DEBUG_KV=1 to flip it on without touching call sites
        self._debug_kv = self.kv_layout == "paged" and (
            self.scheduler.config.debug_kv
            or os.environ.get("REPRO_DEBUG_KV", "0") not in ("", "0"))
        self.reset_stats()

        def prefill(params, batch):
            return self.model.prefill(params, batch, max_seq)
        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=4)
        self._sample_tokens = jax.jit(sample_tokens)

    @classmethod
    def from_artifact(cls, artifact: Union[str, "os.PathLike", Any], *,
                      max_batch: Optional[int] = None,
                      max_seq: Optional[int] = None, seed: int = 0,
                      predict_step: bool = True,
                      scheduler: Union[SchedulerConfig, str, None] = None,
                      measurements: Optional[MeasurementLog] = None,
                      faults: Optional[FaultInjector] = None,
                      fault_tag: Optional[str] = None,
                      straggler: Optional[StragglerMonitor] = None,
                      mesh=None) -> "ServeEngine":
        """Serve a :class:`~repro.api.artifact.DeploymentArtifact` (an
        instance or a directory path) without constructing a
        ``PruningSession`` — the cheap, restartable half of the pipeline.

        ``max_batch``/``max_seq`` default to the artifact's recorded serve
        defaults, in which case the export-time decode-step prediction is
        reused; other shapes re-derive the prediction from the artifact's
        own target + oracle (None when its replay log cannot score them).

        ``mesh`` (a ``(data, model)`` device mesh) serves the artifact
        sharded through :class:`repro.serve.distributed.ShardedServeEngine`;
        a partition-stamped (tp > 1) artifact gets its default ``(1, tp)``
        mesh even without one. The mesh is validated against the
        artifact's partition with errors naming the mesh shape.
        """
        if isinstance(artifact, (str, os.PathLike)):
            from repro.api.artifact import DeploymentArtifact
            artifact = DeploymentArtifact.load(os.fspath(artifact))
        extra: Dict[str, Any] = {}
        if mesh is not None or int(getattr(artifact, "tp", 1)) > 1:
            from repro.serve.distributed import ShardedServeEngine
            if not issubclass(cls, ShardedServeEngine):
                return ShardedServeEngine.for_artifact(
                    artifact, mesh=mesh, max_batch=max_batch,
                    max_seq=max_seq, seed=seed, predict_step=predict_step,
                    scheduler=scheduler, measurements=measurements,
                    faults=faults, fault_tag=fault_tag,
                    straggler=straggler)
            extra["mesh"] = mesh
        defaults = artifact.metadata.get("serve_defaults") or {}
        if max_batch is None:
            max_batch = defaults.get("max_batch", 8)
        if max_seq is None:
            max_seq = defaults.get("max_seq", 512)
        predicted = None
        if predict_step:
            if (max_batch == defaults.get("max_batch")
                    and max_seq == defaults.get("max_seq")):
                predicted = artifact.metadata.get("predicted_step_s")
            if predicted is None:
                # other dims — or an artifact exported without a
                # prediction — re-derive from the artifact's own
                # target + oracle (None when its log cannot score it)
                predicted = artifact.predict_step_s(max_batch, max_seq)
        return cls(artifact.cfg, artifact.params, max_batch=max_batch,
                   max_seq=max_seq, seed=seed, predicted_step_s=predicted,
                   scheduler=scheduler, measurements=measurements,
                   measurement_tag=artifact.measurement_tag,
                   faults=faults, fault_tag=fault_tag, straggler=straggler,
                   **extra)

    # -- queueing -----------------------------------------------------------

    def submit(self, req: Request):
        # a re-queued request keeps its original submit time: the SLO
        # clock (deadline_s) must not restart just because a replica died
        if not req.t_submit:
            req.t_submit = time.time()
        self.scheduler.submit(req)

    @property
    def pending(self) -> List[Request]:
        """Requests admitted to the scheduler but not yet prefilled."""
        return self.scheduler.pending

    @property
    def has_work(self) -> bool:
        return bool(len(self.scheduler) or self.groups)

    def in_flight(self) -> List[Request]:
        """Every submitted-but-unfinished request: scheduler-pending plus
        the live decode rows. This is what a supervisor re-queues after a
        crash — by construction it is disjoint from ``done``, so nothing
        is ever counted twice or lost."""
        live = list(self.scheduler.pending)
        seen = {id(r) for r in live}
        for g in self.groups:
            for r in g.requests:
                if r is not None and not r.done and id(r) not in seen:
                    seen.add(id(r))
                    live.append(r)
        return live

    # -- the stepped core ---------------------------------------------------

    def step(self) -> Dict[str, Any]:
        """One non-blocking scheduling quantum.

        Admits one cohort (prefill + first sampled token) when the
        scheduler yields one for the free slots; otherwise advances every
        live group one decode token; otherwise reports ``idle``. Returns
        a small event record — callers interleave ``step()`` with their
        own work (the router round-robins it across engines)."""
        # wall time (``serve.step``) accrues per quantum, so an engine
        # driven by an external loop (the router round-robin) still
        # reports a meaningful tokens_per_s
        with self.spans.span("serve.step"):
            result = self._step_inner()
        if self._debug_kv:
            self._kv_debug_sweep()
        return result

    def _kv_debug_sweep(self) -> None:
        """Quantum-boundary sanitizer sweep (``debug_kv``): every paged-KV
        invariant over the allocator + live tables, raising
        ``KVSanitizerError`` on the first violation. Host-side only — no
        device sync — but O(pool), so it stays behind the debug flag."""
        from repro.analysis.kv_sanitizer import (KVSanitizerError,
                                                 check_engine)
        diags = check_engine(self)
        self._kv_debug_checks += 1
        if diags:
            self._kv_debug_violations += len(diags)
            raise KVSanitizerError(diags)

    def _step_inner(self) -> Dict[str, Any]:
        free = self.max_batch - sum(g.width for g in self.groups)
        with self.spans.span("serve.select"):
            batch = self.scheduler.select(free,
                                          live_groups=len(self.groups))
        if batch:
            try:
                self._admit(batch)
            except Exception:
                # an admission crash (e.g. injected prefill OOM) must
                # not lose the cohort: the scheduler already popped
                # it, so hand it back before propagating — the
                # supervisor then finds every request in in_flight()
                for r in batch:
                    self.scheduler.submit(r)
                raise
            return {"event": "prefill", "admitted": len(batch),
                    "prompt_len": len(batch[0].prompt),
                    "live_groups": len(self.groups)}
        if self.groups:
            new_tokens = self._decode_tick()
            return {"event": "decode",
                    "live_groups": len(self.groups),
                    "new_tokens": new_tokens}
        return {"event": "idle", "pending": len(self.scheduler)}

    def serve_forever(self, deadline_s: Optional[float] = None
                      ) -> Dict[str, Any]:
        """Step until drained, or until ``deadline_s`` wall seconds pass.

        Returns :meth:`stats`. The engine is resumable: a deadline exit
        leaves pending requests and live groups intact, and a later call
        (or :meth:`step`) picks up exactly where it stopped."""
        t0 = time.time()
        while True:
            if deadline_s is not None and time.time() - t0 >= deadline_s:
                break
            if self.step()["event"] == "idle":
                break
        if self.measurements is not None and self._step_times:
            self.record_measurements()
        return self.stats()

    def run(self) -> Dict[str, Any]:
        """Legacy blocking drain — a thin wrapper over
        :meth:`serve_forever` with identical greedy outputs."""
        return self.serve_forever()

    # -- internal: admission + decode ---------------------------------------

    def _admit(self, reqs: List[Request]) -> SlotGroup:
        cohort = self._next_cohort
        self._next_cohort += 1
        with self.spans.span("serve.admit", cohort=cohort, width=len(reqs),
                             rids=" ".join(str(r.rid) for r in reqs)):
            if self.faults is not None:
                self.faults.fire("prefill", self.fault_tag)
            if self.kv_layout == "paged":
                group = self._admit_paged(reqs)
            else:
                group = self._admit_contiguous(reqs)
        group.cohort = cohort
        return group

    def _admit_contiguous(self, reqs: List[Request]) -> SlotGroup:
        plen = len(reqs[0].prompt)
        toks = np.zeros((len(reqs), plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i] = r.prompt
        with self.spans.span("serve.prefill"):
            logits, caches = self._prefill(self.params,
                                           {"tokens": jnp.asarray(toks)})
        t_first = time.time()
        for r in reqs:
            r.t_first_token = t_first
        cur = self._sample(logits, reqs)
        with self.spans.span("serve.read_tokens"):
            toks = np.asarray(cur)
            for i, r in enumerate(reqs):
                r.output.append(int(toks[i, 0]))
        self._prefills += 1
        self._prefill_tokens += len(reqs) * plen
        self._live_kv_slots += len(reqs) * self.max_seq
        self._peak_kv_slots = max(self._peak_kv_slots, self._live_kv_slots)
        group = SlotGroup(reqs, caches, cur, plen)
        group.copy_counter = self._copy_counter
        self.groups.append(group)
        self._retire(group)
        return group

    def _admit_paged(self, reqs: List[Request]) -> SlotGroup:
        """Paged admission: prefill each *distinct* prompt once at the
        cohort's block-padded length, scatter whole KV blocks into the
        pools, and point every row's block table at them — full prefix
        blocks shared (refcounted) across identical prompt heads, the
        partial frontier block always private per row."""
        sc = self.scheduler.config
        bs = sc.page_size
        plen = len(reqs[0].prompt)
        if sc.prefill_chunk and plen > sc.prefill_chunk:
            return self._admit_chunked(reqs)
        W = len(reqs)
        alloc = self.kv_allocator
        prompts = [np.asarray(r.prompt, np.int32) for r in reqs]
        share = sc.share_prefix
        if share:
            # whole-prompt dedup within the cohort: prefill unique rows
            # only, fan the last-token logits back out per request
            uniq: Dict[bytes, int] = {}
            u_prompts: List[np.ndarray] = []
            row_to_u: List[int] = []
            for p in prompts:
                kb = p.tobytes()
                if kb not in uniq:
                    uniq[kb] = len(u_prompts)
                    u_prompts.append(p)
                row_to_u.append(uniq[kb])
        else:
            u_prompts, row_to_u = prompts, list(range(W))
        U = len(u_prompts)
        padded = -(-plen // bs) * bs
        ncb = padded // bs
        # tokens stay at plen (logits come from the true last position);
        # only the returned cache is block-padded — its slots past plen
        # hold garbage at absolute positions the causal mask hides until
        # decode overwrites them
        with self.spans.span("serve.prefill"):
            logits_u, caches = self._prefill_padded(
                self.params, {"tokens": jnp.asarray(np.stack(u_prompts))},
                padded)

        # block tables: one canonical table per unique prompt, built
        # column by column against the share registry; later rows with
        # the same prompt incref the full columns and get a private
        # frontier block (scattered from the same prefill row)
        with self.spans.span("serve.kv_blocks"):
            rows_s: List[int] = []   # scatter worklist into the U prefill rows
            cols_s: List[int] = []
            bids_s: List[int] = []
            # every reference acquired below, in order — pool exhaustion
            # mid-table must return them all before the cohort is re-queued,
            # or the pool shrinks for good (a V001 leak under debug_kv)
            acquired: List[int] = []
            u_tables = np.zeros((U, ncb), np.int32)
            try:
                for u, p in enumerate(u_prompts):
                    for j in range(ncb):
                        full = (j + 1) * bs <= plen
                        bid = None
                        if share and full:
                            # plen and U are part of the key: k/v bits can
                            # differ across padded lengths / batch widths, and
                            # a shared block must be byte-for-byte one
                            # computation
                            key = (plen, U, p[:(j + 1) * bs].tobytes())
                            bid = alloc.share(key)
                            if bid is not None:
                                acquired.append(bid)
                            else:
                                bid = alloc.alloc()
                                acquired.append(bid)
                                alloc.publish(key, bid)
                                rows_s.append(u); cols_s.append(j)
                                bids_s.append(bid)
                        else:
                            bid = alloc.alloc()
                            acquired.append(bid)
                            rows_s.append(u); cols_s.append(j); bids_s.append(bid)
                        u_tables[u, j] = bid
                table = np.zeros((W, ncb), np.int32)
                seen_u: Dict[int, int] = {}
                frontier = ncb - 1 if plen % bs else None
                for i in range(W):
                    u = row_to_u[i]
                    if u not in seen_u:
                        seen_u[u] = i
                        table[i] = u_tables[u]
                        continue
                    for j in range(ncb):
                        if j == frontier:
                            bid = alloc.alloc()  # private frontier per duplicate
                            acquired.append(bid)
                            rows_s.append(u); cols_s.append(j); bids_s.append(bid)
                        else:
                            bid = int(u_tables[u, j])
                            alloc.incref(bid, shared=True)
                            acquired.append(bid)
                        table[i, j] = bid
            except BaseException:
                for bid in reversed(acquired):
                    alloc.decref(bid)
                raise
        with self.spans.span("serve.kv_scatter"):
            self._pools = scatter_prefill_blocks(
                self._pools, caches, rows_s, cols_s, bids_s, block_size=bs)

        t_first = time.time()
        for r in reqs:
            r.t_first_token = t_first
        logits = logits_u if U == W else jnp.take(
            logits_u, jnp.asarray(row_to_u, jnp.int32), axis=0)
        cur = self._sample(logits, reqs)
        with self.spans.span("serve.read_tokens"):
            toks = np.asarray(cur)
            for i, r in enumerate(reqs):
                r.output.append(int(toks[i, 0]))
        self._prefills += 1
        self._prefill_tokens += U * plen
        group = PagedSlotGroup(reqs, table, cur, plen, allocator=alloc,
                               block_size=bs, pos=plen)
        group.copy_counter = self._copy_counter
        self.groups.append(group)
        self._retire(group)
        return group

    def _admit_chunked(self, reqs: List[Request]) -> SlotGroup:
        """Admit a long-prompt cohort for chunked prefill: allocate its
        real blocks (chunk-padding columns point at the scratch block)
        and let ``_decode_tick`` advance one chunk per tick, interleaved
        with other groups' decode steps. The first token is sampled when
        the last chunk lands. Chunked cohorts skip the share registry."""
        sc = self.scheduler.config
        bs, C = sc.page_size, sc.prefill_chunk
        W = len(reqs)
        plen = len(reqs[0].prompt)
        alloc = self.kv_allocator
        n_chunks = -(-plen // C)
        total_cols = n_chunks * C // bs
        ncb_real = -(-plen // bs)
        with self.spans.span("serve.kv_blocks"):
            table = np.full((W, total_cols), SCRATCH_BLOCK, np.int32)
            acquired: List[int] = []
            try:
                for i in range(W):
                    for j in range(ncb_real):
                        bid = alloc.alloc()
                        acquired.append(bid)
                        table[i, j] = bid
            except BaseException:
                # pool exhausted mid-table: return every block already taken
                # before the cohort is re-queued, or they leak for good
                for bid in reversed(acquired):
                    alloc.decref(bid)
                raise
        prompt_padded = np.zeros((W, n_chunks * C), np.int32)
        for i, r in enumerate(reqs):
            prompt_padded[i, :plen] = r.prompt
        group = PagedSlotGroup(reqs, table, None, plen, allocator=alloc,
                               block_size=bs, pos=plen)
        group.n_chunks = n_chunks
        group.prompt_padded = prompt_padded
        group.copy_counter = self._copy_counter
        self._prefills += 1
        self.groups.append(group)
        return group

    def _decode_tick(self) -> int:
        new_tokens = 0
        self._ticks += 1
        with self.spans.span("serve.tick"):
            for group in list(self.groups):
                if isinstance(group, PagedSlotGroup) and group.prefilling:
                    self._chunk_tick(group)
                else:
                    new_tokens += self._decode_group(group)
        return new_tokens

    def _decode_group(self, group: SlotGroup) -> int:
        """One decode call for ``group`` (the model step and its sampling
        in one program), then its token read and retirement; returns the
        tokens it added."""
        paged = isinstance(group, PagedSlotGroup)
        span = self.spans.span
        with span("serve.decode", cohort=group.cohort, width=group.width,
                  pos=group.pos if paged else -1) as decode:
            if self.faults is not None:
                # inside the timed region: a delay spec shows up as a
                # slow step (the straggler monitor must see it), a crash
                # spec kills the tick with the group state untouched
                self.faults.fire("decode", self.fault_tag)
            if paged:
                with span("serve.kv_table"):
                    if group.pos % group.block_size == 0:
                        # decode is about to cross into a new block-table
                        # column (prefill filled columns 0..ceil(plen/bs)-1)
                        group.ensure_frontier()
                    table = group.device_table()
                with span("serve.dispatch"):
                    tokens, self._pools, self.key = self._decode_paged(
                        self.params, group.cur, self._pools, table,
                        jnp.int32(group.pos), group.device_temps(),
                        self.key)
                group.pos += 1
            else:
                with span("serve.dispatch"):
                    tokens, group.caches, self.key = self._decode(
                        self.params, group.cur, group.caches,
                        group.device_temps(), self.key)
            with span("serve.device_wait") as wait:
                jax.block_until_ready(tokens)
            # the timed step: from the fault point to the device's result
            dt = wait.t1 - decode.t0
            if self.straggler is not None:
                self.straggler.observe(dt)
            self._step_times.append(dt)
            self._step_widths.append(group.width)
            self._decode_steps += 1
            self._slot_steps += group.width
            self._active_slot_steps += sum(
                1 for r in group.requests if r is not None)
            group.cur = tokens
            new_tokens = 0
            with span("serve.read_tokens"):
                toks = np.asarray(tokens)
                for i, r in enumerate(group.requests):
                    if r is not None and len(r.output) < r.max_new_tokens:
                        r.output.append(int(toks[i, 0]))
                        new_tokens += 1
            self._retire(group)
        return new_tokens

    def _chunk_tick(self, group: PagedSlotGroup) -> None:
        """Advance one prefill chunk of a chunked-admission group (no
        fault point: chunk work belongs to the admission's prefill)."""
        C = self.scheduler.config.prefill_chunk
        c = group.chunks_done
        start = c * C
        with self.spans.span("serve.chunk", cohort=group.cohort, pos=start):
            toks = jnp.asarray(group.prompt_padded[:, start:start + C])
            last = min(group.plen - 1 - start, C - 1)
            logits, self._pools = self._chunk_step(
                self.params, toks, self._pools, group.device_table(),
                jnp.int32(start), jnp.int32(last))
            jax.block_until_ready(logits)
            group.chunks_done += 1
            self._chunk_steps += 1
            self._prefill_tokens += group.width * C
            if not group.prefilling:
                t_first = time.time()
                for r in group.requests:
                    if r is not None:
                        r.t_first_token = t_first
                group.cur = self._sample(logits, group.requests)
                with self.spans.span("serve.read_tokens"):
                    toks = np.asarray(group.cur)
                    for i, r in enumerate(group.requests):
                        if r is not None:
                            r.output.append(int(toks[i, 0]))
                self._retire(group)

    def _retire(self, group: SlotGroup) -> None:
        """Move finished requests out of their rows, drop the group when
        empty, and compact the surviving rows (freed slots return to the
        global budget, so the next cohort can be admitted mid-decode)."""
        with self.spans.span("serve.retire"):
            now = time.time()
            for i, r in enumerate(group.requests):
                if r is not None and len(r.output) >= r.max_new_tokens:
                    r.done, r.t_done = True, now
                    self.done.append(r)
                    group.requests[i] = None
            if all(r is None for r in group.requests):
                self.groups.remove(group)
                if isinstance(group, PagedSlotGroup):
                    group.release()   # refcounts drop; orphaned blocks free
                else:
                    self._live_kv_slots -= group.width * self.max_seq
                return
            freed = group.compact(self.scheduler.config.compact)
            if freed and not isinstance(group, PagedSlotGroup):
                self._live_kv_slots -= freed * self.max_seq

    def _sample(self, logits: jax.Array,
                rows: List[Optional[Request]]) -> jax.Array:
        """The first token of an admission (or of a finished chunked
        prefill): one jitted :func:`sample_tokens` call that advances
        ``self.key`` in the same order as the decode calls do."""
        with self.spans.span("serve.sample"):
            temps = np.asarray([r.temperature if r is not None else 0.0
                                for r in rows], np.float32)
            tok, self.key = self._sample_tokens(logits, temps, self.key)
            return tok

    # -- stats + measurement feedback ---------------------------------------

    def reset_stats(self) -> None:
        """Zero every counter and forget retired requests (their Request
        objects keep their outputs). Benchmarks use this to exclude a
        warmup drain from a timed one."""
        self.done = []
        self._prefills = 0
        self._ticks = 0
        self._decode_steps = 0
        self._slot_steps = 0
        self._active_slot_steps = 0
        self._step_times: List[float] = []
        self._step_widths: List[int] = []
        self.spans.reset()
        self._prefill_tokens = 0
        self._chunk_steps = 0
        self._copy_counter["rows"] = 0
        self._kv_debug_checks = 0
        self._kv_debug_violations = 0
        self._peak_kv_slots = self._live_kv_slots
        if self.kv_allocator is not None:
            self.kv_allocator.reset_stats()
        if self.straggler is not None:
            # post-swap stats must not inherit pre-swap medians
            self.straggler.reset()

    def record_measurements(self, log: Optional[MeasurementLog] = None
                            ) -> Optional[str]:
        """Record the observed decode step (median over this engine's
        timed steps) into ``log`` (default: the attached ``measurements``
        log) under :meth:`MeasurementLog.step_key`; returns the key, or
        None when no step has run yet.

        The key claims a step at this engine's batch shape, but
        compaction runs many steps at narrower widths (which are cheaper)
        — so only the samples taken at the *widest* width observed (the
        full ``max_batch`` whenever it ever filled) enter the median."""
        log = self.measurements if log is None else log
        if log is None:
            raise ValueError("no MeasurementLog to record into; construct "
                             "the engine with measurements=MeasurementLog() "
                             "or pass one explicitly")
        if not self._step_times:
            return None
        widest = max(self._step_widths)
        samples = [t for t, w in zip(self._step_times, self._step_widths)
                   if w == widest]
        key = MeasurementLog.step_key(self.measurement_tag, self.max_batch,
                                      self.max_seq)
        log.record(key, float(np.median(np.asarray(samples))))
        return key

    @staticmethod
    def _pct(xs: List[float], q: float) -> float:
        """Percentile with an empty-sample guard: an idle engine reports
        zeros, never NaN."""
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

    def stats(self) -> Dict[str, Any]:
        total_tokens = sum(len(r.output) for r in self.done)
        ttfts = [r.t_first_token - r.t_submit for r in self.done]
        decodes = [r.t_done - r.t_first_token for r in self.done]
        spans = self.spans.totals()
        wall_s = spans.get("serve.step", {}).get("s", 0.0)
        stats = {
            "requests": len(self.done),
            "prefills": self._prefills,
            "total_new_tokens": total_tokens,
            "wall_s": wall_s,
            "tokens_per_s": total_tokens / max(wall_s, 1e-9),
            # tail latency: TTFT and per-request decode time across
            # requests, plus per-decode-step percentiles — the serve-time
            # check for the planner's per-step latency claims
            "p50_ttft_s": self._pct(ttfts, 50),
            "p95_ttft_s": self._pct(ttfts, 95),
            "p50_decode_s": self._pct(decodes, 50),
            "p95_decode_s": self._pct(decodes, 95),
            "p50_step_s": self._pct(self._step_times, 50),
            "p95_step_s": self._pct(self._step_times, 95),
            # scheduler-core accounting: decode_steps counts jitted decode
            # calls (one per live group per tick), slot_steps the batch
            # rows they carried, active_slot_steps the rows doing useful
            # work; occupancy is useful rows over the global slot budget
            "decode_steps": self._decode_steps,
            "decode_ticks": self._ticks,
            "slot_steps": self._slot_steps,
            "active_slot_steps": self._active_slot_steps,
            "mean_batch_occupancy": (
                self._active_slot_steps / (self._ticks * self.max_batch)
                if self._ticks else 0.0),
            # decode ticks slower than factor x rolling median (0 when no
            # StragglerMonitor is attached — fleets attach one per engine)
            "straggler_steps": (self.straggler.stragglers
                                if self.straggler is not None else 0),
            # predicted-vs-measured step latency: how wrong the latency
            # oracle is on the model that is actually executing
            "measured_step_s": sum(self._step_times) / self._decode_steps
            if self._decode_steps else 0.0,
            "predicted_step_s": self.predicted_step_s,
            # KV storage accounting. kv_row_copies counts physically
            # gathered cache rows (paged compaction rewrites tables, so
            # it stays 0 there); peak_kv_bytes is the peak *used* KV —
            # block-granular for paged, width x max_seq for contiguous
            "kv_layout": self.kv_layout,
            "kv_row_copies": self._copy_counter["rows"],
            "prefill_tokens": self._prefill_tokens,
            "chunk_steps": self._chunk_steps,
            "kv_blocks_peak": (self.kv_allocator.peak_blocks
                               if self.kv_allocator is not None else 0),
            "kv_blocks_in_use": (self.kv_allocator.blocks_in_use
                                 if self.kv_allocator is not None else 0),
            "kv_shared_blocks": (self.kv_allocator.shared_hits
                                 if self.kv_allocator is not None else 0),
            # paged-KV sanitizer accounting (debug_kv): quantum-boundary
            # sweeps run and invariant violations seen (violations also
            # raise, so a drained run should report checks > 0, 0 here)
            "kv_debug_checks": self._kv_debug_checks,
            "kv_debug_violations": self._kv_debug_violations,
            "peak_kv_bytes": (
                self.kv_allocator.peak_blocks
                * self.scheduler.config.page_size * self._kv_row_bytes
                if self.kv_layout == "paged"
                else self._peak_kv_slots * self._kv_row_bytes),
            # host spans by name (cumulative since reset_stats): count,
            # seconds, and seconds not covered by child spans
            "spans": spans,
        }
        if self.predicted_step_s is not None and self._decode_steps:
            meas = stats["measured_step_s"]
            stats["oracle_rel_error"] = \
                (self.predicted_step_s - meas) / max(meas, 1e-12)
        return stats


#: The redesign's name for the execution half; ``ServeEngine`` is kept as
#: the primary name because every artifact/session entry point returns it.
Engine = ServeEngine
